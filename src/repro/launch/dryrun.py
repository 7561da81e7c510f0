import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # a compile tool: never takes a chip

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` forces GSPMD to
resolve every sharding, insert every collective, and do full buffer
assignment for the production meshes — a sharding mismatch, an
unsupported collective, or an OOM shows up here as a compile error.

Per cell we record into ``results/dryrun/<cell>.json``:
  * ``memory_analysis()``  — per-device argument/temp/output bytes;
  * ``cost_analysis()``    — per-device HLO FLOPs + bytes accessed;
  * collective bytes parsed from the post-SPMD HLO text, by op kind;
  * the planner's napkin-math estimates (``launch/plan.py``) so the two
    can be compared in EXPERIMENTS.md §Dry-run.

NOTE the first two lines of this file: jax locks the device count at
first init, so the 512 placeholder host devices MUST be forced before any
other import.  Nothing else in the repo sets XLA_FLAGS.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs-file f.txt]
  python -m repro.launch.dryrun --sim            # E2C engine sweep cell
"""
# NOTE: no ``from __future__`` here — the XLA_FLAGS lines must be the very
# first statements in the file (they are), and __future__ imports are only
# legal at the top.

import argparse
import json
import re
import subprocess
import sys
import time
import traceback

HW = {  # TPU v5e, per chip
    "peak_flops": 197e12,        # bf16
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link (approx, 4 links/chip)
}

COLLECTIVE_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][,\s]*)+)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(shape_str: str) -> int:
    """'bf16[128,1024]' -> bytes."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str.strip().rstrip(","))
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, by kind.

    The compiled module is post-SPMD (per-device shapes).  For all-reduce
    result==operand; for all-gather the result is the full gathered
    tensor (the ring moves (n-1)/n of it); for reduce-scatter the operand
    dominates but the result-sum still lower-bounds traffic — we record
    result bytes uniformly and note the convention in EXPERIMENTS.md.
    """
    out: dict[str, dict] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        shapes, kind = m.groups()
        b = sum(_shape_bytes(s) for s in shapes.split(",") if "[" in s)
        d = out.setdefault(kind, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    return out


def roofline_terms(flops: float, bytes_acc: float, coll_bytes: float,
                   n_chips: int) -> dict:
    """Per-device seconds for each roofline term (cost_analysis numbers
    are already per-device post-SPMD)."""
    return {
        "t_compute_s": flops / HW["peak_flops"],
        "t_memory_s": bytes_acc / HW["hbm_bw"],
        "t_collective_s": coll_bytes / HW["ici_bw"],
    }


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); D = tokens processed."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch          # one token per sequence
    return 2.0 * n * tokens


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, fsdp: str = "auto",
             variant: str = "base", attn: str = "chunked") -> dict:
    import jax
    from repro.configs.base import SHAPES, cell_is_runnable, get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch.plan import plan_cell
    from repro.launch import train as LT
    from repro.launch import serve as LS
    from repro.models.transformer import ModelOptions

    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
                 "variant": variant, "status": "ok"}
    runnable, why = cell_is_runnable(cfg, shape)
    if not runnable:
        rec["status"] = "skipped"
        rec["why"] = why
        return _save(rec, out_dir)

    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.flat)
    plan = plan_cell(cfg, shape, mesh)
    if fsdp != "auto":
        plan.fsdp = fsdp == "on"
    rec["plan"] = plan.to_dict()
    rec["attn"] = attn
    try:
        if shape.kind == "train":
            arts = LT.build_train_artifacts(
                cfg, shape, mesh, plan=plan,
                mopts=ModelOptions(attn_impl=attn))
            params_sds = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, arts.mopts.dtype),
                arts.param_shapes)
            import repro.optim as O
            opt_sds = jax.eval_shape(O.adamw_init, params_sds)
            from repro.models import model as MM
            batch_sds = MM.input_specs(cfg, shape, arts.mopts)["batch"]
            lowered = arts.jitted.lower(params_sds, opt_sds, batch_sds)
        else:
            arts = LS.build_serve_artifacts(
                cfg, shape, mesh, fsdp=plan.fsdp,
                mopts=ModelOptions(remat=False, attn_impl=attn))
            from repro.models import model as MM
            params_sds = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, arts.mopts.dtype),
                jax.eval_shape(lambda k: MM.init_params(k, cfg)[0],
                               jax.random.PRNGKey(0)))
            if shape.kind == "prefill":
                lowered = arts.jitted.lower(params_sds,
                                            arts.input_specs["batch"])
            else:
                lowered = arts.jitted.lower(params_sds,
                                            arts.input_specs["cache"],
                                            arts.input_specs["tokens"])
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": round(ma.argument_size_in_bytes / 1e9, 4),
            "output_gb": round(ma.output_size_in_bytes / 1e9, 4),
            "temp_gb": round(ma.temp_size_in_bytes / 1e9, 4),
            "alias_gb": round(ma.alias_size_in_bytes / 1e9, 4),
            "total_gb": round((ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes) / 1e9, 4),
        }
        # trip-count-aware walk of the post-SPMD HLO (XLA's cost_analysis
        # counts while bodies once — useless for scanned stacks; see
        # launch/hlo_cost.py and tests/test_hlo_cost.py)
        from repro.launch import hlo_cost
        hlo_text = compiled.as_text()
        walked = hlo_cost.analyze(hlo_text)
        flops = walked.flops
        bytes_acc = walked.bytes
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops_per_device": flops,
                       "bytes_per_device": bytes_acc,
                       "xla_flops_uncorrected": float(ca.get("flops", 0.0)),
                       "unknown_loops": walked.unknown_loops}
        rec["collectives"] = walked.collectives
        coll_bytes = walked.collective_bytes
        rec["roofline"] = roofline_terms(flops, bytes_acc, coll_bytes,
                                         n_chips)
        mf = model_flops(cfg, shape)
        rec["model_flops_global"] = mf
        hlo_global = flops * n_chips
        rec["useful_flops_ratio"] = round(mf / hlo_global, 4) \
            if hlo_global else None
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["bottleneck"] = dom.replace("t_", "").replace("_s", "")
        rec["total_s"] = round(time.perf_counter() - t0, 2)
    except Exception as e:  # noqa: BLE001 — record, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _save(rec, out_dir)


def run_sim_cell(*, multi_pod: bool, out_dir: str,
                 n_replicas: int = 4096, n_tasks: int = 256,
                 n_machines: int = 64) -> dict:
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sim import build_sharded_sweep

    mesh_tag = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": "e2c-sim-sweep", "shape":
                 f"r{n_replicas}_t{n_tasks}_m{n_machines}",
                 "mesh": mesh_tag, "variant": "base", "status": "ok"}
    t0 = time.perf_counter()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        arts = build_sharded_sweep(mesh, n_replicas, n_tasks, n_machines,
                                   abstract=True)
        lowered = arts.jitted.lower(*arts.inputs)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": round(ma.argument_size_in_bytes / 1e9, 6),
            "temp_gb": round(ma.temp_size_in_bytes / 1e9, 6)}
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops_per_device": float(ca.get("flops", 0.0)),
                       "bytes_per_device":
                       float(ca.get("bytes accessed", 0.0))}
        rec["collectives"] = parse_collectives(compiled.as_text())
        rec["total_s"] = round(time.perf_counter() - t0, 2)
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _save(rec, out_dir)


def _save(rec: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    name = (f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
            + (f"__{rec['variant']}" if rec.get("variant", "base") != "base"
               else "") + ".json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = rec.get("why") or rec.get("error") or ""
    print(f"[dryrun] {rec['arch']} x {rec['shape']} x {rec['mesh']}: "
          f"{status} {extra}", flush=True)
    return rec


def cell_done(arch: str, shape: str, mesh_tag: str, out_dir: str,
              variant: str = "base") -> bool:
    name = (f"{arch}__{shape}__{mesh_tag}"
            + (f"__{variant}" if variant != "base" else "") + ".json")
    path = os.path.join(out_dir, name)
    if not os.path.exists(path):
        return False
    with open(path) as f:
        rec = json.load(f)
    return rec.get("status") in ("ok", "skipped")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run all pending cells via subprocesses")
    ap.add_argument("--both-meshes", action="store_true",
                    help="with --all: run single- AND multi-pod")
    ap.add_argument("--sim", action="store_true",
                    help="run the E2C simulator sweep cell")
    ap.add_argument("--fsdp", choices=("auto", "on", "off"), default="auto")
    ap.add_argument("--attn", choices=("chunked", "hier", "block"),
                    default="chunked")
    ap.add_argument("--variant", default="base",
                    help="tag for perf-iteration records")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    if args.all:
        from repro.configs.base import SHAPES, list_archs
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        jobs = []
        for mp in meshes:
            tag = "2x16x16" if mp else "16x16"
            for arch in list_archs():
                for shape in SHAPES:
                    if args.force or not cell_done(arch, shape, tag,
                                                   args.out):
                        jobs.append((arch, shape, mp))
        print(f"[dryrun] {len(jobs)} pending cells")
        fails = 0
        for arch, shape, mp in jobs:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", args.out,
                   "--fsdp", args.fsdp]
            if mp:
                cmd.append("--multi-pod")
            r = subprocess.run(cmd, check=False)
            fails += r.returncode != 0
        print(f"[dryrun] sweep done, {fails} subprocess failures")
        return

    if args.sim:
        run_sim_cell(multi_pod=args.multi_pod, out_dir=args.out)
        return

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all / --sim)")
    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   out_dir=args.out, fsdp=args.fsdp, variant=args.variant,
                   attn=args.attn)
    if rec["status"] == "error":
        print(rec.get("traceback", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
