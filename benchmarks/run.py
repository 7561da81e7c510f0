"""Benchmark aggregator: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one harness per paper table/claim (see DESIGN.md §9) plus the
roofline readers over whatever dry-run records exist, and writes JSON
artifacts to results/bench/:

* ``<module>.json``         — each harness's latest payload (overwritten),
* ``run-<timestamp>.json``  — ONE machine-readable record per aggregate
  run (all module payloads + check results + versions + wall time), so
  the perf trajectory of the repo is tracked run-over-run; CI uploads
  these as artifacts.

``--smoke`` runs a CI-sized subset (small replica counts, quick modules
only) so the whole aggregate finishes in a couple of minutes on a CPU
runner.  Results are recorded in EXPERIMENTS.md.

``--compare [prev.json]`` turns the ledger into a regression gate
(docs/observability.md): the fresh record is diffed against ``prev.json``
(default: the most recent ``run-*.json`` already in results/bench/).  A
check that flipped PASS -> FAIL, or a benchmark row whose
``per_replica_ms`` grew beyond ``COMPARE_RATIO`` (2x — CI-runner noise
is real; tighten locally), is a regression: the machine-readable verdict
is printed and stored in the record, and the process exits 3.  With no
baseline available the gate degrades to a non-blocking warning, so the
first run of a fresh checkout still passes.
"""
from __future__ import annotations

import glob
import inspect
import json
import os
import platform
import sys
import time

#: timing-regression threshold for --compare (cur > ratio * prev fails)
COMPARE_RATIO = 2.0


def _versions() -> dict:
    v = {"python": platform.python_version()}
    for mod in ("jax", "jaxlib", "numpy"):
        try:
            v[mod] = __import__(mod).__version__
        except Exception:  # noqa: BLE001
            v[mod] = None
    return v


def _latest_run(results_dir: str, before: str | None = None) -> str | None:
    """Path of the newest ``run-*.json`` ledger record (optionally
    excluding ``before``, the record being written)."""
    runs = sorted(glob.glob(os.path.join(results_dir, "run-*.json")))
    runs = [r for r in runs if r != before]
    return runs[-1] if runs else None


def compare_runs(prev: dict, cur: dict,
                 ratio: float = COMPARE_RATIO) -> dict:
    """Diff two ledger records -> machine-readable regression verdict.

    Two regression classes:

    * a check present in both records that flipped True -> False;
    * a benchmark row (matched by module + ``replicas`` label) whose
      ``per_replica_ms`` grew beyond ``ratio`` x the baseline.

    Checks/rows only present on one side are reported as ``added`` /
    ``removed`` but never fail the gate (new benches must be landable).
    """
    checks_prev = prev.get("checks") or {}
    checks_cur = cur.get("checks") or {}
    check_regressions = sorted(
        k for k, v in checks_cur.items()
        if not v and checks_prev.get(k) is True)
    timing_regressions = []
    for mod, payload in (cur.get("payloads") or {}).items():
        prev_rows = {str(r.get("replicas")): r
                     for r in (prev.get("payloads", {}).get(mod, {})
                               .get("rows") or [])}
        for row in payload.get("rows") or []:
            base = prev_rows.get(str(row.get("replicas")))
            if not base:
                continue
            b, c = base.get("per_replica_ms"), row.get("per_replica_ms")
            if b and c and c > ratio * b:
                timing_regressions.append(
                    {"module": mod, "row": str(row.get("replicas")),
                     "prev_ms": b, "cur_ms": c,
                     "ratio": round(c / b, 2)})
    return {
        "baseline": prev.get("timestamp"),
        "ratio_threshold": ratio,
        "check_regressions": check_regressions,
        "timing_regressions": timing_regressions,
        "checks_added": sorted(set(checks_cur) - set(checks_prev)),
        "checks_removed": sorted(set(checks_prev) - set(checks_cur)),
        "ok": not check_regressions and not timing_regressions,
    }


def _compile_cache_probe() -> dict:
    """Enable jax's persistent compilation cache and measure it.

    Turns on ``jax_compilation_cache_dir`` (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``results/jax_cache`` under the checkout, via
    ``experiment.enable_compilation_cache``), then times one tiny
    canonical sweep twice: the first call pays trace + compile ("cold" —
    on a re-run of this process the XLA compile is served from disk, so
    this number is the cache's measured benefit run-over-run), the
    second hits jax's in-process caches ("warm").  Both land as attrs on
    a ``compile_cache`` telemetry span and in the run ledger record.
    """
    import jax

    from repro.core import telemetry as TL
    from repro.launch import experiment as XP
    from repro.launch.sim import make_replicas

    cache_dir = XP.enable_compilation_cache()
    info: dict = {"dir": cache_dir}
    with TL.span("compile_cache", dir=info["dir"]) as sp:
        probe = make_replicas(2, 16, 4, seed=0) + (None, None, None)
        sweep = XP.compile_sweep()
        t0 = time.perf_counter()
        jax.block_until_ready(sweep(*probe)["completed"])
        info["cold_compile_s"] = round(time.perf_counter() - t0, 4)
        t0 = time.perf_counter()
        jax.block_until_ready(sweep(*probe)["completed"])
        info["warm_run_s"] = round(time.perf_counter() - t0, 4)
        sp.update(info)
    print(f"compile cache: {info}")
    return info


def main(argv=None):
    t0 = time.perf_counter()
    stamp = time.strftime("%Y%m%dT%H%M%S")
    argv = list(argv or [])
    smoke = "--smoke" in argv
    if smoke:
        argv.remove("--smoke")
    baseline_path = None
    compare = "--compare" in argv
    if compare:
        i = argv.index("--compare")
        argv.pop(i)
        if i < len(argv) and argv[i].endswith(".json"):
            baseline_path = argv.pop(i)
    from benchmarks import (bench_energy, bench_engine, bench_kernels,
                            bench_policies, eet_from_roofline, roofline)
    from benchmarks.common import RESULTS_DIR
    cache_info = _compile_cache_probe()
    mods = [("bench_policies", bench_policies),
            ("bench_energy", bench_energy),
            ("bench_engine", bench_engine),
            ("bench_kernels", bench_kernels),
            ("roofline", roofline),
            ("eet_from_roofline", eet_from_roofline)]
    if smoke:
        # CI subset: the engine claims + the kernel canary + cheap readers
        smoke_set = {"bench_engine", "bench_energy", "bench_kernels",
                     "roofline", "eet_from_roofline"}
        mods = [(n, m) for n, m in mods if n in smoke_set]
    if argv:
        mods = [(n, m) for n, m in mods if n in argv]
    failures = []
    all_checks: dict[str, bool] = {}
    payloads: dict[str, dict] = {}
    for name, mod in mods:
        print(f"\n{'='*70}\n# {name}\n{'='*70}")
        try:
            kwargs = {}
            if smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            payload = mod.run(**kwargs)
            payloads[name] = payload
            for k, v in (payload.get("checks") or {}).items():
                all_checks[f"{name}.{k}"] = v
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
    seconds = time.perf_counter() - t0
    # one timestamped machine-readable record per aggregate run
    record = {
        "timestamp": stamp,
        "smoke": smoke,
        "modules_run": [n for n, _ in mods],
        "seconds": round(seconds, 2),
        "versions": _versions(),
        "compile_cache": cache_info,
        "checks": all_checks,
        "failures": [{"module": n, "error": e} for n, e in failures],
        "payloads": payloads,
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    run_path = os.path.join(RESULTS_DIR, f"run-{stamp}.json")
    verdict = None
    if compare:
        path = baseline_path or _latest_run(RESULTS_DIR, before=run_path)
        if path is None:
            print("compare: no baseline run-*.json found — "
                  "recording this run as the first baseline (non-blocking)")
        else:
            try:
                with open(path) as f:
                    verdict = compare_runs(json.load(f), record)
                verdict["baseline_path"] = path
                record["compare"] = verdict
            except Exception as e:  # noqa: BLE001
                print(f"compare: unreadable baseline {path}: {e!r} "
                      "(non-blocking)")
    with open(run_path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    print(f"\n{'='*70}\n# summary ({seconds:.1f}s) -> {run_path}")
    for k, v in sorted(all_checks.items()):
        print(f"  {'PASS' if v else 'FAIL'}  {k}")
    if verdict is not None:
        print("compare verdict:", json.dumps(verdict, default=str))
    if failures:
        print("harness failures:", failures)
        sys.exit(1)
    bad = [k for k, v in all_checks.items() if not v]
    if bad:
        print("failed checks:", bad)
        sys.exit(2)
    if verdict is not None and not verdict["ok"]:
        print("regression vs baseline", verdict["baseline"])
        sys.exit(3)
    print("all benchmark checks passed")


if __name__ == "__main__":
    main(sys.argv[1:])
