"""Run the simulator's main path on a TPU and check what comes out.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded grid only

One process does everything; it starts no other.  Phases (one chip):

  device     names the platform, device kind and count; anything but a
             TPU exits non-zero (there is no CPU fallback);
  cache      turns on the persistent compilation cache
             (``experiment.enable_compilation_cache``);
  grid       a dense Monte-Carlo study through
             ``run_experiment(spec, chunk=C)``, the donated,
             device-reduced path: every registered heuristic policy x
             poisson/bursty arrivals x fail rate x DVFS state, with spot
             kills; every task of every replica must end terminal;
  pallas     a sub-grid with ``pallas=True`` against ``pallas=False``:
             final SimState and metrics bitwise equal, and the compiled
             executable holds Mosaic kernels (``tpu_custom_call``); then
             the same sub-grid through ``run_experiment(spec, chunk=C)``
             with ``pallas=True``: every task terminal and the aggregate
             bitwise equal to the ``pallas=False`` one;
  streaming  a 16384-task stream through a 512-slot window; every task
             must retire.  It is dispatched first, so the oracle's host
             work below overlaps its device time;
  oracle     one replica per registered policy (``mlp``/``linear`` with
             ``mct_mlp_params()``) against ``core/ref_engine.simulate_ref``
             under failures and powersave DVFS, with the tier-1 rules of
             tests/test_engine_vs_ref.py.

``--chips 4`` runs the same chunked grid sharded over a 4-device mesh
and on one device, in this process, and asserts the two aggregates are
bitwise equal.  Any failed phase raises, so the exit code is non-zero
and the result line is not printed.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the dense grid: a study users run (sizes are module constants so the
# phases can be driven at other sizes from a REPL).  The stream is 32
# windows long: on one v5e the engine retires ~170 tasks/s per stream,
# so a 65536-task stream alone would take ~6.5 minutes, more than the
# rest of the run leaves of a 20-minute budget.
GRID_REPLICAS = 1024
GRID_TASKS = 2048
GRID_MACHINES = 64
GRID_MACHINE_TYPES = 4
GRID_CHUNK = 512
PALLAS_REPLICAS = 256
ORACLE_TASKS = 1024
STREAM_TASKS = 16384
STREAM_WINDOW = 512
STREAM_REPLICAS = 2


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Sums jax's XLA compile-duration events (tracing is not counted)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


def bitwise_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def grid_spec(n_replicas: int, *, pallas: bool = False):
    from repro.core import neural as NN
    from repro.core import schedulers as P
    from repro.launch import experiment as X
    heuristics = tuple(p for p in P.POLICY_NAMES
                       if p not in NN.LEARNED_POLICIES)
    return X.ExperimentSpec(
        n_replicas=n_replicas,
        fleet=X.FleetAxis(GRID_MACHINES, GRID_MACHINE_TYPES),
        workload=X.WorkloadAxis(GRID_TASKS,
                                arrivals=("poisson", "bursty")),
        scenario=X.ScenarioAxis(fail_rates=(0.0, 0.05),
                                dvfs_states=("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(heuristics),
        pallas=pallas, seed=11)


def assert_agg_equal(x, y, what: str) -> None:
    """Two ``SweepAgg`` aggregates are bitwise equal, column by column."""
    for field in ("a", "b", "vmin", "vmax", "hist"):
        fx, fy = getattr(x, field), getattr(y, field)
        if fx.keys() != fy.keys():
            raise AssertionError(f"{what}: columns differ")
        for k in fx:
            if not bitwise_equal(fx[k], fy[k]):
                raise AssertionError(f"{what}: differ at {field}[{k}]")
    if not bitwise_equal(x.counts, y.counts):
        raise AssertionError(f"{what}: replica counts differ")


def terminal_total(agg) -> int:
    return sum(round(agg.total(k)) for k in
               ("completed", "missed", "cancelled", "preempted"))


def run_grid(spec, clock: CompileClock, *, mesh=None, chunk: int = 0,
             tag: str = "grid"):
    from repro.launch import experiment as X
    chunk = chunk or GRID_CHUNK
    c0 = clock.total
    t0 = time.perf_counter()
    res = X.run_experiment(spec, mesh=mesh, chunk=chunk)
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0
    agg = res.agg
    want = spec.n_replicas * spec.workload.n_tasks
    got = terminal_total(agg)
    log(tag, f"wall_s={wall} compile_s={compile_s} "
             f"replicas_per_s={spec.n_replicas / wall} "
             f"replicas_per_s_after_compile="
             f"{spec.n_replicas / max(wall - compile_s, 1e-9)} "
             f"chunk={chunk} normalize_s={res.chunked.normalize_s} "
             f"sync_s={res.chunked.sync_s}")
    log(tag, f"terminal tasks {got} of {want}; completion_rate mean "
             f"{agg.mean('completion_rate')}")
    if got != want or agg.count() != spec.n_replicas:
        raise AssertionError(f"{tag}: {got} of {want} tasks terminal, "
                             f"{agg.count()} of {spec.n_replicas} replicas")
    return agg


def phase_grid(clock: CompileClock):
    spec = grid_spec(GRID_REPLICAS)
    log("grid", f"R={spec.n_replicas} N={GRID_TASKS} M={GRID_MACHINES} "
                f"machine_types={GRID_MACHINE_TYPES} chunk={GRID_CHUNK} "
                f"policies={len(spec.policy.policies)} "
                f"arrivals={spec.workload.arrivals} "
                f"fail_rates={spec.scenario.fail_rates} "
                f"dvfs={spec.scenario.dvfs_states} "
                f"spot_frac={spec.scenario.spot_frac}")
    import warnings
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run_grid(spec, clock)
    donation = [str(w.message) for w in caught if "onat" in str(w.message)]
    if donation:
        raise AssertionError(f"grid: donation warnings {donation}")
    log("grid", f"donation warnings: none ({len(caught)} warnings in all)")


def final_states(spec, reps, policy_params=None):
    """Compile and run the vmapped engine returning every replica's final
    SimState plus its metrics row; also returns the compiled HLO text."""
    from repro.core import engine as E
    from repro.launch import experiment as X
    params = spec.sim_params

    def one(tasks, mtype, tables, pid, dyn):
        st = E.run_sim(tasks, mtype, tables, pid, params, dyn,
                       policy_params)
        return st, X.summarize_replica(st, tables, dyn)

    args = (reps.tasks, reps.mtype, reps.tables, reps.policy_ids,
            reps.dynamics)
    compiled = jax.jit(jax.vmap(one)).lower(*args).compile()
    out = jax.block_until_ready(compiled(*args))
    return out, compiled.as_text()


def phase_pallas(clock: CompileClock):
    from repro.launch import chunked as CH
    from repro.launch import experiment as X
    spec = grid_spec(PALLAS_REPLICAS)
    reps = X.normalize(spec)
    log("pallas", f"R={PALLAS_REPLICAS} N={GRID_TASKS} M={GRID_MACHINES}")
    outs = {}
    for pallas in (False, True):
        c0 = clock.total
        t0 = time.perf_counter()
        out, hlo = final_states(spec.with_(pallas=pallas), reps)
        n_kernels = hlo.count("tpu_custom_call")
        log("pallas", f"pallas={pallas} wall_s={time.perf_counter() - t0} "
                      f"compile_s={clock.total - c0} "
                      f"tpu_custom_call={n_kernels}")
        if pallas and not n_kernels:
            raise AssertionError("pallas=True compiled no Mosaic kernel")
        outs[pallas] = out
    leaves_off = jax.tree_util.tree_leaves_with_path(outs[False])
    leaves_on = jax.tree_util.tree_leaves(outs[True])
    if len(leaves_off) != len(leaves_on):
        raise AssertionError("pallas: result structures differ")
    for (path, a), b in zip(leaves_off, leaves_on):
        if not bitwise_equal(a, b):
            raise AssertionError(
                f"pallas on/off differ at {jax.tree_util.keystr(path)}")
    log("pallas", f"SimState + metrics bitwise equal over "
                  f"{len(leaves_on)} leaves")
    # the entry point users call: chunked, donated, reduced on device
    agg_off = CH.aggregate_metrics(outs[False][1], reps.policy_ids,
                                   spec.policy.policies)
    agg_on = run_grid(spec.with_(pallas=True), clock,
                      chunk=PALLAS_REPLICAS, tag="pallas")
    assert_agg_equal(agg_on, agg_off, "pallas run_experiment vs pallas=False")
    log("pallas", f"run_experiment(pallas=True) aggregate bitwise equal "
                  f"to pallas=False over {len(agg_on.columns)} columns")


def oracle_refs(spec, reps, pp) -> list:
    """``core/ref_engine`` results, one per replica (host only)."""
    from repro.core import ref_engine as RE
    from repro.core import schedulers as P
    params = spec.sim_params
    refs = []
    for r in range(spec.n_replicas):
        h = jax.tree.map(lambda x: np.asarray(x[r]), reps)
        dyn = h.dynamics
        refs.append(RE.simulate_ref(
            h.tasks.arrival, h.tasks.type_id, h.tasks.deadline,
            h.tables.eet, h.tables.power, h.mtype,
            policy=P.POLICY_NAMES[int(h.policy_ids)], lcap=params.lcap,
            qcap=params.qcap, cancel_infeasible=params.cancel_infeasible,
            noise=h.tables.noise, speed=dyn.speed,
            power_scale=dyn.power_scale, down_start=dyn.down_start,
            down_end=dyn.down_end, kill=dyn.kill, policy_params=pp))
    return refs


def phase_oracle(overlap=None):
    """jit == oracle per registered policy.  The oracle is host-only
    Python; ``overlap()`` (a device-bound phase already dispatched) runs
    its device work meanwhile and is finished before the engine side of
    this phase queues behind it."""
    from repro.core import neural as NN
    from repro.core import schedulers as P
    from repro.core import state as S
    from repro.launch import experiment as X
    names = tuple(P.POLICY_NAMES)
    spec = X.ExperimentSpec(
        n_replicas=len(names),
        fleet=X.FleetAxis(GRID_MACHINES // 4, GRID_MACHINE_TYPES),
        workload=X.WorkloadAxis(ORACLE_TASKS),
        scenario=X.ScenarioAxis(fail_rates=(0.05,),
                                dvfs_states=("powersave",), spot_frac=0.5),
        policy=X.PolicyAxis(names), seed=5)
    reps = X.normalize(spec)
    pp = NN.mct_mlp_params()
    log("oracle", f"N={ORACLE_TASKS} M={GRID_MACHINES // 4} fail=0.05 "
                  f"dvfs=powersave spot_frac=0.5 policies={len(names)} "
                  f"(mlp/linear weights: mct_mlp_params)")
    t0 = time.perf_counter()
    refs = oracle_refs(spec, reps, pp)
    log("oracle", f"oracle_wall_s={time.perf_counter() - t0}")
    if overlap is not None:
        overlap()
    (st, _), _ = final_states(spec, reps, pp)
    st = jax.tree.map(np.asarray, st)
    for r, ref in enumerate(refs):
        ctx = f"policy={names[r]}"
        np.testing.assert_array_equal(st.tasks.status[r], ref.status,
                                      err_msg=ctx)
        np.testing.assert_array_equal(st.tasks.machine[r], ref.machine,
                                      err_msg=ctx)
        np.testing.assert_allclose(st.tasks.t_start[r], ref.t_start,
                                   rtol=1e-5, atol=1e-4, err_msg=ctx)
        np.testing.assert_allclose(st.tasks.t_end[r], ref.t_end,
                                   rtol=1e-5, atol=1e-4, err_msg=ctx)
        np.testing.assert_allclose(st.machines.energy[r], ref.active_energy,
                                   rtol=1e-4, atol=1e-2, err_msg=ctx)
        np.testing.assert_array_equal(st.n_preempts[r], ref.n_preempts,
                                      err_msg=ctx)
        log("oracle", f"{names[r]}: parity ok (completed "
                      f"{int((ref.status == S.COMPLETED).sum())})")


def start_streaming(clock: CompileClock):
    """Dispatch the stream (compile is synchronous, the run is not) and
    return the function that waits for it and checks every task retired."""
    from repro.launch import experiment as X
    spec = X.ExperimentSpec(
        n_replicas=STREAM_REPLICAS,
        fleet=X.FleetAxis(GRID_MACHINES, GRID_MACHINE_TYPES),
        workload=X.WorkloadAxis(STREAM_TASKS, streaming=STREAM_WINDOW),
        policy=X.PolicyAxis(("mct", "ee_mct")), seed=3)
    log("streaming", f"R={STREAM_REPLICAS} N={STREAM_TASKS} "
                     f"W={STREAM_WINDOW} M={GRID_MACHINES}")
    c0 = clock.total
    t0 = time.perf_counter()
    res = X.run_experiment(spec)
    compile_s = clock.total - c0

    def finish():
        m = jax.tree.map(np.asarray, res.metrics)
        wall = time.perf_counter() - t0
        done = m["completed"] + m["missed"] + m["cancelled"] \
            + m["preempted"]
        log("streaming", f"wall_s={wall} compile_s={compile_s} "
                         f"tasks_per_s={STREAM_REPLICAS * STREAM_TASKS / wall}"
                         f" retired={done.tolist()}")
        if not (done == STREAM_TASKS).all():
            raise AssertionError(f"streaming: retired {done.tolist()} of "
                                 f"{STREAM_TASKS}")
    return finish


def phase_sharded(clock: CompileClock, n_dev: int):
    from repro.launch import experiment as X
    from repro.launch.mesh import make_local_mesh, put_chunk
    spec = grid_spec(GRID_REPLICAS)
    mesh = make_local_mesh(data=n_dev)
    log("sharded", f"mesh={dict(mesh.shape)} R={spec.n_replicas} "
                   f"N={GRID_TASKS} M={GRID_MACHINES} chunk={GRID_CHUNK}")
    first = X.normalize_chunk(spec, 0, GRID_CHUNK)
    placed = put_chunk({"tasks": first.tasks, "mtype": first.mtype,
                        "tables": first.tables,
                        "policy_ids": first.policy_ids,
                        "dynamics": first.dynamics}, mesh, GRID_CHUNK)
    for path, leaf in jax.tree_util.tree_leaves_with_path(placed):
        shards = sorted((s.device.id, s.data.shape[0])
                        for s in leaf.addressable_shards)
        log("sharded", f"input{jax.tree_util.keystr(path)} "
                       f"{leaf.shape}: (device, rows) {shards}")
        if len(shards) != n_dev or any(rows != GRID_CHUNK // n_dev
                                       for _, rows in shards):
            raise AssertionError(f"input{jax.tree_util.keystr(path)} is "
                                 f"not split R/{n_dev} per device")
    del placed
    a_mesh = run_grid(spec, clock, mesh=mesh, tag="sharded")
    # the aggregate is chunk-invariant: one chunk is the cheapest reference
    a_one = run_grid(spec, clock, chunk=spec.n_replicas, tag="one-device")
    assert_agg_equal(a_mesh, a_one, "sharded vs one device")
    log("sharded", f"aggregate bitwise equal to one device over "
                   f"{len(a_one.columns)} columns")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded grid vs one device")
    args = ap.parse_args(argv)

    dev = device_info()
    log("device", f"platform={dev['platform']} kind={dev['kind']} "
                  f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev['platform']})",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {dev['count']} found",
              file=sys.stderr)
        return 2

    from repro.launch import experiment as X
    log("cache", f"compilation cache dir {X.enable_compilation_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(clock, 4)
    else:
        phase_grid(clock)
        phase_pallas(clock)
        phase_oracle(overlap=start_streaming(clock))
    log("done", f"total_s={time.perf_counter() - t0} "
                f"compile_s={clock.total}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
