"""Simulator engine throughput: the "cheap controlled studies" claim.

The paper's motivation is that real-infrastructure studies are cost- and
time-prohibitive.  The quantitative claim of this reproduction is that
the vectorized engine makes *simulated* studies cheap at scale:

  T1. one jit'd replica beats the plain-Python reference engine;
  T2. vmapped replicas amortize: events/sec grows ~linearly with the
      replica count until the host saturates (on TPU this axis is then
      sharded over the pod — launch/experiment.py);
  ...
  T8. the ExperimentSpec executable cache works: building + running a
      SECOND same-shape spec skips retracing entirely and is >= 5x
      faster than the first (docs/experiments.md);
  T9. the streaming window engine's per-task drain cost stays flat
      (< 1.5x drift) when total traffic grows 100x at a fixed window —
      memory and per-event cost are O(W), never O(N)
      (docs/streaming.md);
  T10. the in-jit telemetry instruments (core/metrics.py: latency
      histograms + SLO windows + device-side tail quantiles) cost
      < 2x the idle baseline — cheaper than tracing because only the
      queue-depth sample scatters per event (docs/observability.md);
  T11. the chunked Monte-Carlo driver (launch/chunked.py) scales flat:
      per-replica cost at R=100k stays within 1.3x of R=1k (donated
      buffers + device-side SweepAgg reduction keep host and device
      memory O(chunk)) (docs/scaling.md).

All rows run through the declarative spec pipeline (one cached
executable per SimParams) — the same path users take.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import md_table, save_result
from repro.core import engine as E
from repro.core import ref_engine as RE
from repro.core import schedulers as P
from repro.launch import experiment as XP
from repro.launch.sim import make_replicas, run_grouped_sweep

N_TASKS, N_MACHINES = 128, 16

SCEN_AXIS = XP.ScenarioAxis((0.0, 0.05, 0.2), ("nominal", "powersave"),
                            spot_frac=0.5)


def _time_fn(fn, args, ready=lambda out: out["completed"]):
    out = fn(*args)                            # compile + warm
    jax.block_until_ready(ready(out))
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(ready(out))
    return time.perf_counter() - t0


def time_sweep(n_replicas: int) -> tuple[float, float]:
    inputs = make_replicas(n_replicas, N_TASKS, N_MACHINES, seed=0)
    sweep = XP.compile_sweep()
    dt = _time_fn(sweep, inputs + (None, None, None))
    return dt, dt / n_replicas


def time_scenario_sweep(n_replicas: int) -> tuple[float, float]:
    """Dynamic-scenario replicas (failure traces + DVFS + preemption)."""
    spec = XP.ExperimentSpec(
        n_replicas, XP.FleetAxis(N_MACHINES), XP.WorkloadAxis(N_TASKS),
        scenario=SCEN_AXIS,
        policy=XP.PolicyAxis(("mct", "minmin", "ee_mct")), seed=0)
    reps = jax.device_put(XP.normalize(spec))
    sweep = XP.compile_experiment(spec)
    dt = _time_fn(sweep, reps.legacy() + (None, None))
    return dt, dt / n_replicas


def time_traced_sweep(n_replicas: int) -> tuple[float, float]:
    """Replicas with in-jit trace capture on (EXPERIMENTS.md §Perf —
    the measured cost of the masked trace writes + snapshots)."""
    inputs = make_replicas(n_replicas, N_TASKS, N_MACHINES, seed=0)
    sweep = XP.compile_sweep(E.SimParams(trace=True))
    dt = _time_fn(sweep, inputs + (None, None, None),
                  ready=lambda out: out[1].n_rows)
    return dt, dt / n_replicas


def time_metrics_sweep(n_replicas: int) -> tuple[float, float]:
    """Replicas with the in-jit telemetry instruments on (T10 — the
    measured cost of the per-event queue-depth scatter + post-loop fold
    + device-side quantile columns; EXPERIMENTS.md §Perf)."""
    inputs = make_replicas(n_replicas, N_TASKS, N_MACHINES, seed=0)
    sweep = XP.compile_sweep(E.SimParams(metrics=True))
    dt = _time_fn(sweep, inputs + (None, None, None))
    return dt, dt / n_replicas


def time_experiment_cache(n_replicas: int) -> tuple[float, float, dict]:
    """T8: end-to-end (build + normalize + run) of two same-shape specs.

    The first spec pays compilation; the second (new seed, same shapes)
    must hit the executable cache AND jax's trace cache — no retracing.
    A dedicated SimParams (max_events pinned) keeps this row's cache
    entry disjoint from the other rows, so the first run really
    compiles.
    """
    params = E.SimParams(max_events=4 * N_TASKS + 17)

    def build_and_run(seed: int) -> float:
        spec = XP.ExperimentSpec(
            n_replicas, XP.FleetAxis(N_MACHINES),
            XP.WorkloadAxis(N_TASKS), scenario=SCEN_AXIS,
            policy=XP.PolicyAxis(("mct", "minmin", "ee_mct")),
            sim=params, seed=seed)
        t0 = time.perf_counter()
        res = XP.run_experiment(spec)
        jax.block_until_ready(res.metrics["completed"])
        return time.perf_counter() - t0

    stats0 = XP.cache_stats()
    t_first = build_and_run(0)
    t_second = build_and_run(1)
    stats = {k: XP.cache_stats()[k] - stats0[k] for k in ("hits", "misses")}
    return t_first, t_second, stats


def time_learned_dispatch(n_replicas: int) -> tuple[float, float]:
    """Learned-policy dispatch overhead, decision-for-decision.

    The MLP policy is run with the MCT-equivalent warm start
    (``neural.mct_mlp_params``), so both groups take *identical*
    scheduling decisions and event trajectories — the timing difference
    is purely the per-drain-step feature build + forward pass.  Both use
    the policy-grouped path so the heuristic baseline doesn't pay for
    the learned branch (batched lax.switch computes every branch).
    """
    from repro.core import neural as NN
    pp = NN.mct_mlp_params()
    base = make_replicas(n_replicas, N_TASKS, N_MACHINES,
                         policies=["mct"], seed=0)
    learned = base[:3] + (jnp.full_like(base[3], P.POLICY_IDS["mlp"]),)
    times = []
    for inputs, kw in ((base, {}), (learned, {"policy_params": pp})):
        run_grouped_sweep(inputs, **kw)              # compile + warm
        t0 = time.perf_counter()
        run_grouped_sweep(inputs, **kw)
        times.append((time.perf_counter() - t0) / n_replicas)
    return times[0], times[1]                        # (mct, mlp) s/replica


def time_workflow_sweep(n_replicas: int) -> tuple[float, float, float]:
    """DAG-engine rows (docs/workflows.md, EXPERIMENTS.md §Perf).

    Three per-replica timings at the same N, all single-policy (mct) so
    the drain logic is identical:

    * ``chain``   — a fully sequential chain workflow (the dependency-
      release phase is doing maximal work: one release per task);
    * ``inert``   — the *independent* workload run with an all(-1)
      parent table, i.e. the ``has_deps`` machinery compiled in but
      semantically idle — the pure machinery cost T7 bounds;
    * ``plain``   — the same independent workload with ``parents=None``
      (the pre-DAG engine, T7's baseline).
    """
    wf_spec = XP.ExperimentSpec(
        n_replicas, XP.FleetAxis(N_MACHINES),
        XP.WorkloadAxis(N_TASKS, shapes=("chain",)),
        policy=XP.PolicyAxis(("mct",)), seed=0)
    wf = jax.device_put(XP.normalize(wf_spec))
    sweep = XP.compile_sweep()
    base = make_replicas(n_replicas, N_TASKS, N_MACHINES,
                         policies=["mct"], seed=0)
    inert_parents = jnp.full((n_replicas, N_TASKS, 1), -1, jnp.int32)
    times = []
    for args in ((wf.tasks, wf.mtype, wf.tables, wf.policy_ids, None,
                  wf.parents, None),
                 base + (None, inert_parents, None),
                 base + (None, None, None)):
        times.append(_time_fn(sweep, args) / n_replicas)
    return times[0], times[1], times[2]        # (chain, inert, plain)


def time_streaming_drain(n_small: int, factor: int = 100,
                         window: int = 64) -> tuple[float, float]:
    """T9: streaming per-task drain cost at fixed W vs total traffic.

    Times ``streaming.simulate_stream`` (warm — compile excluded) on the
    same Poisson family at N and factor*N with the SAME window and
    chunk.  The window engine's state is O(W), so the per-task cost must
    not drift as N grows — the unlocking property for fleet-scale
    traffic (ROADMAP item 1, docs/streaming.md)."""
    from repro.core import streaming as STR
    from repro.core.eet import synth_eet
    from repro.core.workload import poisson_workload
    rng = np.random.default_rng(0)
    eet = synth_eet(4, 4, inconsistency=0.3, seed=0)
    power = np.stack([rng.uniform(20, 60, 4), rng.uniform(80, 300, 4)],
                     axis=1).astype(np.float32)
    mtype = rng.integers(0, 4, 8)
    per = []
    for n in (n_small, n_small * factor):
        wl = poisson_workload(n, rate=8.0, n_task_types=4,
                              mean_eet=eet.eet.mean(1), slack=4.0,
                              seed=1)

        def go():
            res = STR.simulate_stream(wl, eet, power, mtype,
                                      policy="mct", window=window,
                                      chunk=window, lcap=3)
            jax.block_until_ready(res.ws.agg.retired)
            assert int(res.ws.agg.retired) == n
            return res

        go()                                   # compile + warm
        t0 = time.perf_counter()
        go()
        per.append((time.perf_counter() - t0) / n)
    return per[0], per[1]


def time_chunked_sweep(n_small: int, n_big: int, chunk: int = 250):
    """T11: chunked driver per-replica cost at R=n_small vs R=n_big.

    One small experiment cell (16 tasks, 4 machines, single policy) so
    the replica axis is the only thing that grows.  Both runs go through
    ``run_experiment(spec, chunk=...)`` — the donated double-buffered
    driver folding the device-side SweepAgg — after a warm run that pays
    the chunk-shaped compilation.  Returns the two per-replica wall
    times plus the big run's :class:`chunked.ChunkedStats` (the host
    normalize / dispatch / sync split).
    """
    spec = XP.ExperimentSpec(
        n_small, XP.FleetAxis(4), XP.WorkloadAxis(16),
        policy=XP.PolicyAxis(("mct",)), seed=0)
    # compile + warm with the same chunk shape (cache key = SimParams +
    # chunk geometry, so both timed runs are pure cache hits)
    XP.run_experiment(spec.with_(n_replicas=2 * chunk), chunk=chunk)
    per, stats = [], None
    for n, seed in ((n_small, 0), (n_big, 1)):
        t0 = time.perf_counter()
        res = XP.run_experiment(spec.with_(n_replicas=n, seed=seed),
                                chunk=chunk)
        per.append((time.perf_counter() - t0) / n)
        stats = res.chunked
    return per[0], per[1], stats


def run(out_dir=None, smoke: bool = False) -> dict:
    # ref engine indexes tuple fields positionally; rebuild host-side
    inputs = make_replicas(2, N_TASKS, N_MACHINES, seed=0)
    t0 = time.perf_counter()
    for i in range(2):
        arr = jax.tree.map(lambda x: np.asarray(x[i]), inputs)
        tt, mt, tb, pid = arr
        RE.simulate_ref(tt.arrival, tt.type_id, tt.deadline, tb.eet,
                        tb.power, mt, policy=P.POLICY_NAMES[int(pid)],
                        noise=tb.noise)
    ref_per_replica = (time.perf_counter() - t0) / 2

    sizes = (1, 8, 32) if smoke else (1, 8, 64, 256)
    big = sizes[-1]
    rows = []
    per_replica_1 = None
    for n in sizes:
        total, per = time_sweep(n)
        if n == 1:
            per_replica_1 = per
        rows.append({"replicas": n, "total_s": round(total, 4),
                     "per_replica_ms": round(per * 1e3, 3),
                     "replicas_per_s": round(n / total, 1)})
    per_replica_big = rows[-1]["per_replica_ms"]

    # policy-grouped variant: batched lax.switch computes every policy
    # branch per replica; grouping makes the policy a compile-time
    # constant (see launch/sim.run_grouped_sweep)
    inputs = make_replicas(big, N_TASKS, N_MACHINES, seed=0)
    run_grouped_sweep(inputs)                   # compile + warm
    t0 = time.perf_counter()
    run_grouped_sweep(inputs)
    grouped_per = (time.perf_counter() - t0) / big
    rows.append({"replicas": f"{big} (policy-grouped)",
                 "total_s": round(grouped_per * big, 4),
                 "per_replica_ms": round(grouped_per * 1e3, 3),
                 "replicas_per_s": round(1 / grouped_per, 1)})

    # dynamic-scenario variant: availability traces + DVFS + preemption
    # add an event phase and masks; T4 bounds their overhead
    scen_n = 8 if smoke else 64
    scen_total, scen_per = time_scenario_sweep(scen_n)
    rows.append({"replicas": f"{scen_n} (scenario)",
                 "total_s": round(scen_total, 4),
                 "per_replica_ms": round(scen_per * 1e3, 3),
                 "replicas_per_s": round(scen_n / scen_total, 1)})
    static_same_n = next(r for r in rows
                         if r["replicas"] == scen_n)["per_replica_ms"]

    # traced variant: TraceBuffer recording inside the jitted loop; the
    # default-off path must stay at the static numbers above, and the
    # opt-in cost is bounded (T5, same static baseline as T4)
    trace_total, trace_per = time_traced_sweep(scen_n)
    rows.append({"replicas": f"{scen_n} (traced)",
                 "total_s": round(trace_total, 4),
                 "per_replica_ms": round(trace_per * 1e3, 3),
                 "replicas_per_s": round(scen_n / trace_total, 1)})

    # telemetry variant: latency histograms + SLO windows + device-side
    # quantiles inside the jitted loop; default-off compiles identical
    # HLO (tests/test_metrics.py), opt-in cost is bounded (T10)
    metrics_total, metrics_per = time_metrics_sweep(scen_n)
    rows.append({"replicas": f"{scen_n} (metrics)",
                 "total_s": round(metrics_total, 4),
                 "per_replica_ms": round(metrics_per * 1e3, 3),
                 "replicas_per_s": round(scen_n / metrics_total, 1)})

    # workflow (DAG) engine: chain vs independent at the same N, plus
    # the inert-parents run that isolates the has_deps machinery (T7)
    chain_per, inert_per, plain_per = time_workflow_sweep(scen_n)
    for label, per in (("chain DAG", chain_per),
                       ("independent + deps machinery", inert_per),
                       ("independent, mct", plain_per)):
        rows.append({"replicas": f"{scen_n} ({label})",
                     "total_s": round(per * scen_n, 4),
                     "per_replica_ms": round(per * 1e3, 3),
                     "replicas_per_s": round(1 / per, 1)})

    # learned-policy dispatch: MLP with the MCT warm start vs MCT itself
    # (identical decisions; difference = feature build + forward pass)
    mct_per, mlp_per = time_learned_dispatch(scen_n)
    rows.append({"replicas": f"{scen_n} (mct, grouped)",
                 "total_s": round(mct_per * scen_n, 4),
                 "per_replica_ms": round(mct_per * 1e3, 3),
                 "replicas_per_s": round(1 / mct_per, 1)})
    rows.append({"replicas": f"{scen_n} (learned mlp, grouped)",
                 "total_s": round(mlp_per * scen_n, 4),
                 "per_replica_ms": round(mlp_per * 1e3, 3),
                 "replicas_per_s": round(1 / mlp_per, 1)})

    # ExperimentSpec executable cache: build+run a spec twice (new seed,
    # same shapes) — the second must skip retracing entirely (T8).
    # Fixed small replica count: the check isolates compile-vs-cached
    # dispatch, so execution time must not drown the compile term.
    cache_n = 8
    cache_first, cache_second, cache_stats = time_experiment_cache(cache_n)
    for label, total in (("spec, first build+run", cache_first),
                         ("spec, same-shape re-run", cache_second)):
        rows.append({"replicas": f"{cache_n} ({label})",
                     "total_s": round(total, 4),
                     "per_replica_ms": round(total / cache_n * 1e3, 3),
                     "replicas_per_s": round(cache_n / total, 1)})

    # streaming window engine: same window, traffic x100 — the per-task
    # drain cost must stay flat because live state is O(W), not O(N) (T9)
    stream_n = 32 if smoke else 64
    stream_factor = 100
    stream_small, stream_big = time_streaming_drain(stream_n,
                                                    stream_factor)
    for label, n, per in (
            ("streaming W=64", stream_n, stream_small),
            ("streaming W=64", stream_n * stream_factor, stream_big)):
        rows.append({"replicas": f"{n} tasks ({label})",
                     "total_s": round(per * n, 4),
                     "per_replica_ms": round(per * 1e3, 3),
                     "replicas_per_s": round(1 / per, 1)})

    # chunked Monte-Carlo driver: the replica axis grows 10-100x at a
    # fixed chunk; per-replica cost must stay flat (T11)
    chunk_small, chunk_big = 1000, (10_000 if smoke else 100_000)
    chunked_small, chunked_big, chunked_stats = time_chunked_sweep(
        chunk_small, chunk_big)
    for n, per in ((chunk_small, chunked_small),
                   (chunk_big, chunked_big)):
        rows.append({"replicas": f"{n} (chunked, chunk=250)",
                     "total_s": round(per * n, 4),
                     "per_replica_ms": round(per * 1e3, 3),
                     "replicas_per_s": round(1 / per, 1)})

    checks = {
        "T1_jit_beats_python_ref": bool(per_replica_1 < ref_per_replica),
        "T2_vmap_amortizes": bool(per_replica_big
                                  < 2 * rows[0]["per_replica_ms"]),
        "T3_grouping_beats_batched_switch": bool(
            grouped_per * 1e3 < per_replica_big),
        "T4_scenario_overhead_bounded": bool(
            scen_per * 1e3 < 4 * static_same_n),
        "T5_trace_overhead_bounded": bool(
            trace_per * 1e3 < 3 * static_same_n),
        "T6_learned_dispatch_overhead_bounded": bool(mlp_per < 3 * mct_per),
        "T7_has_deps_overhead_bounded": bool(inert_per < 2 * plain_per),
        "T8_experiment_cache_hits": bool(
            cache_second * 5 <= cache_first
            and cache_stats == {"hits": 1, "misses": 1}),
        "T9_streaming_per_task_flat": bool(
            stream_big < 1.5 * stream_small),
        "T10_metrics_overhead_bounded": bool(
            metrics_per * 1e3 < 2 * static_same_n),
        "T11_chunked_per_replica_flat": bool(
            chunked_big < 1.3 * chunked_small),
    }
    payload = {"rows": rows,
               "chunked": {
                   "chunk": 250,
                   "n_small": chunk_small,
                   "n_big": chunk_big,
                   "per_replica_small_ms": round(chunked_small * 1e3, 3),
                   "per_replica_big_ms": round(chunked_big * 1e3, 3),
                   "drift": round(chunked_big / chunked_small, 3),
                   "normalize_s": round(chunked_stats.normalize_s, 3),
                   "sync_s": round(chunked_stats.sync_s, 3)},
               "ref_per_replica_ms": round(ref_per_replica * 1e3, 2),
               "experiment_cache": {
                   "first_s": round(cache_first, 4),
                   "second_s": round(cache_second, 4),
                   "speedup": round(cache_first / cache_second, 1),
                   **cache_stats},
               "streaming": {
                   "window": 64,
                   "n_small": stream_n,
                   "n_big": stream_n * stream_factor,
                   "per_task_small_ms": round(stream_small * 1e3, 4),
                   "per_task_big_ms": round(stream_big * 1e3, 4),
                   "drift": round(stream_big / stream_small, 3)},
               "checks": checks}
    save_result("bench_engine", payload, out_dir)
    print("\n## bench_engine — replica throughput "
          f"(python ref: {ref_per_replica*1e3:.1f} ms/replica)")
    print(md_table(rows))
    print("experiment cache:", payload["experiment_cache"])
    print("chunked:", payload["chunked"])
    print("checks:", checks)
    return payload


if __name__ == "__main__":
    run()
