"""Telemetry subsystem tests (docs/observability.md).

Three layers under test:

* **Instrument math** (``core/metrics.py``): closed-form bucket
  placement, quantile interpolation, conservation / monotone-CDF
  properties, host-vs-traced quantile agreement.
* **Engine integration**: ``SimParams(metrics=False)`` lowers to
  byte-identical HLO (the off-path costs literally nothing); with
  ``metrics=True`` the jit engine, the streaming window engine and the
  plain-Python oracle produce *bitwise identical* histogram counts for
  every registered policy, static and dynamic.
* **Pipeline telemetry** (``core/telemetry.py`` +
  ``launch/experiment.py``): span nesting / durations / error capture
  in the JSONL log, cache counters, and the experiment-level tail
  columns.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis optional (dev extra)
from conftest import make_instance

from repro.core import engine as E
from repro.core import metrics as ME
from repro.core import ref_engine as R
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import streaming as STR
from repro.core import telemetry as TL
from repro.core.workload import make_scenario

POLICIES = list(P.SCHEDULERS)

SMALL = ME.MetricsSpec(buckets=2, lo=1.0, hi=100.0)  # edges [1, 10, 100]


# ---------------------------------------------------------------------------
# Instrument math: closed-form buckets + quantiles
# ---------------------------------------------------------------------------
def test_bucket_edges_closed_form():
    np.testing.assert_allclose(ME.bucket_edges(SMALL), [1.0, 10.0, 100.0],
                               rtol=1e-6)


@pytest.mark.parametrize("x,expected", [
    (0.0, 0), (0.5, 0),            # underflow [0, lo)
    (1.0, 1), (9.9, 1),            # first bucket [1, 10)
    (10.0, 2), (99.0, 2),          # second bucket [10, 100)
    (100.0, 3), (1e6, 3),          # overflow [hi, inf)
])
def test_bucket_placement_closed_form(x, expected):
    assert int(ME.bucket_np(SMALL, x)) == expected


def test_fold_tasks_np_closed_form():
    """Two completions (resp 2 and 20), one miss, one cancel: exact
    counts per bin and per SLO window."""
    spec = ME.MetricsSpec(buckets=2, lo=1.0, hi=100.0, slo_target=5.0,
                          windows=4, window_s=16.0)
    status = np.array([S.COMPLETED, S.COMPLETED, S.MISSED_QUEUE,
                       S.CANCELLED])
    arrival = np.array([0.0, 10.0, 0.0, 0.0])
    t_start = np.array([1.0, 12.0, -1.0, -1.0])
    t_end = np.array([2.0, 30.0, 40.0, 0.0])
    c = ME.fold_tasks_np(spec, status, arrival, t_start, t_end)
    # responses 2.0 -> bucket 1, 20.0 -> bucket 2
    np.testing.assert_array_equal(c["response"], [0, 1, 1, 0])
    # waits: 1.0 -> bucket 1, 2.0 -> bucket 1 (cancel/miss never started)
    np.testing.assert_array_equal(c["wait"], [0, 2, 0, 0])
    # windows: t_end 2 -> w0, 30 -> w1; miss t_end 40 -> w2
    np.testing.assert_array_equal(c["win_done"], [1, 1, 0, 0])
    np.testing.assert_array_equal(c["win_miss"], [0, 0, 1, 0])
    # only the 20 s response exceeds the 5 s SLO target
    np.testing.assert_array_equal(c["win_over"], [0, 1, 0, 0])


def test_hist_quantile_interpolates_within_bucket():
    # 4 samples in [1, 10): p50 lands mid-bucket by linear interpolation
    counts = np.array([0, 4, 0, 0])
    assert ME.hist_quantile(counts, SMALL, 0) == pytest.approx(1.0)
    assert ME.hist_quantile(counts, SMALL, 50) == pytest.approx(5.5)
    assert ME.hist_quantile(counts, SMALL, 100) == pytest.approx(10.0)
    assert ME.hist_quantile(np.zeros(4), SMALL, 99) == 0.0


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.lognormal(1.0, 1.0, 257)
    for q in (50, 95, 99):
        assert ME.percentile(x, q) == pytest.approx(np.percentile(x, q))
    assert ME.percentile([], 99) == 0.0


def test_hist_quantile_matches_numpy_within_bucket_resolution():
    """Histogram-reconstructed percentiles vs exact np.percentile on the
    same samples: error bounded by one bucket width."""
    rng = np.random.default_rng(1)
    spec = ME.MetricsSpec(buckets=64, lo=1e-2, hi=1e3)
    x = rng.lognormal(0.5, 1.2, 4096).astype(np.float32)
    counts = np.bincount(ME.bucket_np(spec, x), minlength=spec.buckets + 2)
    lows, highs = ME.bucket_bounds(spec)
    for q in (50, 90, 95, 99):
        exact = np.percentile(x, q)
        approx = ME.hist_quantile(counts, spec, q)
        b = int(ME.bucket_np(spec, exact))
        assert lows[b] <= approx <= highs[b] * (1 + 1e-6), (q, exact, approx)


def test_quantiles_jnp_matches_host():
    rng = np.random.default_rng(2)
    counts = rng.integers(0, 50, ME.DEFAULT_SPEC.buckets + 2)
    dev = np.asarray(jax.jit(
        lambda c: ME.quantiles_jnp(c, ME.DEFAULT_SPEC))(counts))
    host = [ME.hist_quantile(counts, ME.DEFAULT_SPEC, q)
            for q in (50, 95, 99)]
    np.testing.assert_allclose(dev, host, rtol=1e-4, atol=1e-4)
    zero = np.asarray(ME.quantiles_jnp(np.zeros(counts.shape, np.int32),
                                       ME.DEFAULT_SPEC))
    np.testing.assert_array_equal(zero, 0.0)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=1e4,
                          allow_nan=False, width=32),
                min_size=0, max_size=200))
def test_histogram_properties(samples):
    """Counts are conserved (every sample lands in exactly one bin) and
    the reconstructed quantile function is monotone in q."""
    x = np.asarray(samples, np.float32)
    counts = np.bincount(ME.bucket_np(ME.DEFAULT_SPEC, x),
                         minlength=ME.DEFAULT_SPEC.buckets + 2)
    assert counts.sum() == x.size              # conservation
    assert (counts >= 0).all()                 # monotone CDF
    qs = [ME.hist_quantile(counts, ME.DEFAULT_SPEC, q)
          for q in (0, 25, 50, 75, 95, 99, 100)]
    assert all(a <= b + 1e-9 for a, b in zip(qs, qs[1:]))


def test_merge_adds_counts():
    a = ME.init(SMALL)
    b = dataclasses.replace(a, response=a.response.at[1].add(3))
    m = ME.merge(b, b)
    np.testing.assert_array_equal(np.asarray(m.response), [0, 6, 0, 0])
    with pytest.raises(ValueError):
        ME.merge(a, ME.init(ME.DEFAULT_SPEC))


# ---------------------------------------------------------------------------
# Engine integration: HLO identity + three-way count parity
# ---------------------------------------------------------------------------
def _lower_text(params: E.SimParams) -> str:
    """StableHLO text of the jitted engine for ``params`` on a fixed
    16-task instance."""
    eet, power, wl, mtype = make_instance(7, n_tasks=16, n_machines=4)
    tables = E.make_tables(eet, power, wl.n_tasks)
    tasks = wl.to_task_table()
    fn = jax.jit(lambda t, m, tb, p: E.run_sim(t, m, tb, p, params))
    return fn.lower(tasks, np.asarray(mtype, np.int32), tables,
                    np.int32(0)).as_text()


def test_metrics_off_hlo_identical():
    """The contract that makes metrics shippable as a default-off flag:
    ``metrics=False`` lowers to byte-identical HLO — the instruments
    compile out entirely, like ``trace=`` and ``pallas=``."""
    base = _lower_text(E.SimParams())
    off = _lower_text(E.SimParams(metrics=False))
    on = _lower_text(E.SimParams(metrics=True))
    assert off == base
    assert on != base
    nbin = ME.DEFAULT_SPEC.buckets + 2
    assert f"tensor<{nbin}xi32>" not in base   # no histogram buffers...
    assert f"tensor<{nbin}xi32>" in on         # ...until you ask


@pytest.mark.parametrize("policy", POLICIES)
def test_counts_jit_vs_ref_static(policy):
    """Bitwise histogram parity, jit engine vs plain-Python oracle, every
    registered policy (lognormal EET noise on)."""
    eet, power, wl, mtype = make_instance(11, n_tasks=48, n_machines=4)
    rng = np.random.default_rng(3)
    noise = rng.lognormal(0.0, 0.2, wl.n_tasks).astype(np.float32)
    st_jax = E.simulate(wl, eet, power, mtype, policy=policy, lcap=3,
                        noise=noise, metrics=True)
    ref = R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                         power, mtype, policy=policy, lcap=3, noise=noise,
                         metrics=True)
    jit_counts = ME.to_numpy(st_jax.metrics)
    for k in jit_counts:
        np.testing.assert_array_equal(
            jit_counts[k], ref.metrics[k],
            err_msg=f"{k} counts mismatch policy={policy}")


@pytest.mark.parametrize("policy", ["mct", "ee_mct", "fcfs"])
def test_counts_jit_vs_ref_dynamic(policy):
    """Same bitwise parity under a failure/DVFS/spot scenario — misses
    and preemptions must bucket identically too."""
    eet, power, wl, mtype = make_instance(23, n_tasks=32, n_machines=4,
                                          rate=4.0)
    scen = make_scenario(wl, len(mtype), fail_rate=0.25, mttr=2.5,
                         spot=True, dvfs="powersave", n_intervals=3,
                         seed=13)
    spec = ME.MetricsSpec(slo_target=3.0)
    st_jax = E.simulate(wl, eet, power, mtype, policy=policy,
                        dynamics=scen.dynamics(), metrics=True,
                        metrics_spec=spec)
    ref = R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                         power, mtype, policy=policy, speed=scen.speed,
                         power_scale=scen.power_scale,
                         down_start=scen.down_start,
                         down_end=scen.down_end, kill=scen.kill,
                         metrics=True, metrics_spec=spec)
    jit_counts = ME.to_numpy(st_jax.metrics)
    for k in jit_counts:
        np.testing.assert_array_equal(
            jit_counts[k], ref.metrics[k],
            err_msg=f"{k} counts mismatch policy={policy} dynamic")


@pytest.mark.parametrize("window", [64, 8])
def test_counts_dense_vs_streaming(window):
    """The fold-at-retirement strategy cannot change the counts: the
    streaming window engine produces bitwise the dense engine's per-task
    histograms (response/wait/slowdown/windows), in both the N <= W and
    the overflow N >> W regime.  ``queue_depth`` is an in-loop sample of
    *live* state, so it is only dense-identical when every arrived task
    fits the window (N <= W) — in overflow, tasks waiting outside the
    window are invisible to it by construction (docs/observability.md).
    """
    eet, power, wl, mtype = make_instance(17, n_tasks=48, n_machines=4,
                                          rate=6.0)
    dense = E.simulate(wl, eet, power, mtype, policy="mct", lcap=3,
                       metrics=True)
    res = STR.simulate_stream(wl, eet, power, mtype, policy="mct",
                              window=window, chunk=min(window, 16),
                              lcap=3, metrics=True)
    assert res.sim_metrics is not None
    dn, sn = ME.to_numpy(dense.metrics), ME.to_numpy(res.sim_metrics)
    for k in dn:
        if k == "queue_depth" and window < wl.n_tasks:
            continue
        np.testing.assert_array_equal(
            dn[k], sn[k], err_msg=f"{k} counts mismatch W={window}")


def test_metrics_off_leaves_state_field_none():
    eet, power, wl, mtype = make_instance(5)
    st_off = E.simulate(wl, eet, power, mtype, policy="mct")
    assert st_off.metrics is None
    res = STR.simulate_stream(wl, eet, power, mtype, policy="mct",
                              window=8, chunk=8)
    assert res.sim_metrics is None


def test_report_summary_columns():
    eet, power, wl, mtype = make_instance(9, n_tasks=32)
    from repro.core import report
    row = report.summarize(
        E.simulate(wl, eet, power, mtype, policy="mct", metrics=True),
        E.make_tables(eet, power, wl.n_tasks))
    for col in ("resp_p50", "resp_p99", "wait_p95", "slow_p50",
                "qdepth_p99", "slo_miss_rate"):
        assert col in row, col
    assert row["resp_p99"] >= row["resp_p50"] >= 0.0


# ---------------------------------------------------------------------------
# Pipeline telemetry: spans, events, experiment integration
# ---------------------------------------------------------------------------
def test_telemetry_span_nesting_and_errors(tmp_path):
    log = TL.TelemetryLog(str(tmp_path), run_id="t0")
    with log.span("outer", stage="x") as extra:
        extra["n"] = np.int64(3)           # numpy coerced to plain JSON
        with log.span("inner"):
            pass
        log.event("tick", value=1.5)
    with pytest.raises(RuntimeError):
        with log.span("boom"):
            raise RuntimeError("nope")
    log.close()
    recs = TL.read_jsonl(str(tmp_path / "telemetry-t0.jsonl"))
    by_name = {r["name"]: r for r in recs}
    assert [r["name"] for r in recs] == ["inner", "tick", "outer", "boom"]
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["outer"]["parent"] is None
    assert by_name["outer"]["n"] == 3 and by_name["outer"]["stage"] == "x"
    assert by_name["outer"]["dur_s"] >= by_name["inner"]["dur_s"] >= 0
    assert by_name["tick"]["kind"] == "event"
    assert "RuntimeError" in by_name["boom"]["error"]


def test_module_level_telemetry_disabled_is_noop():
    TL.disable()
    with TL.span("nothing") as extra:
        extra["x"] = 1                     # writable but goes nowhere
    TL.event("nothing")
    assert TL.current() is None


def test_experiment_emits_spans_and_tail_columns(tmp_path):
    from repro.launch import experiment as X
    log = TL.enable(str(tmp_path))
    try:
        spec = X.ExperimentSpec(
            n_replicas=4, fleet=X.FleetAxis(n_machines=4),
            workload=X.WorkloadAxis(n_tasks=16),
            policy=X.PolicyAxis(policies=("mct", "rr")),
            sim=E.SimParams(max_events=97), metrics=True, seed=0)
        res = X.run_experiment(spec)
    finally:
        TL.disable()
    for col in ("resp_p50", "resp_p95", "resp_p99", "qdepth_p99"):
        assert col in res.metrics
        assert np.asarray(res.metrics[col]).shape == (4,)
    resp = np.asarray(res.metrics["resp_p99"])
    assert (resp >= np.asarray(res.metrics["resp_p50"]) - 1e-5).all()
    recs = TL.read_jsonl(log.path)
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert {"experiment", "normalize", "compile", "execute"} <= set(spans)
    assert spans["normalize"]["parent"] == spans["experiment"]["span"]
    assert spans["normalize"]["n_replicas"] == 4
    assert spans["compile"]["misses"] >= 1
    events = [r for r in recs if r["kind"] == "event" and r["name"] == "cache"]
    assert events and "retraces" in events[-1]


def test_span_counts_trace_and_lower_seconds(tmp_path):
    """A jitted function first called inside a span: the span carries
    the trace, lower and backend seconds it caused; the log opens with
    the process's seconds before it, and ``disable`` writes the log's
    own totals, each as one ``compile_clock`` event."""
    jax.jit(lambda x: jnp.cos(x) - 2)(jnp.arange(5.0))
    log = TL.enable(str(tmp_path))
    try:
        with TL.span("first_call"):
            jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0))
        with TL.span("nothing_compiled"):
            pass
    finally:
        TL.disable()
    recs = TL.read_jsonl(log.path)
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    first = spans["first_call"]
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["trace_s"] + first["lower_s"] <= first["dur_s"]
    assert not {"trace_s", "lower_s"} & set(spans["nothing_compiled"])
    opened, clock = recs[0], recs[-1]
    assert opened["name"] == "compile_clock" and opened["window"] == "before"
    assert opened["trace_s"] > 0 and opened["lower_s"] > 0
    assert clock["kind"] == "event" and clock["name"] == "compile_clock"
    assert clock["window"] == "log"
    assert clock["trace_s"] >= first["trace_s"] > 0
    assert clock["lower_s"] >= first["lower_s"] > 0
    assert set(clock) >= {"trace_s", "lower_s", "backend_s"}


def test_nested_traces_count_once():
    """An event that encloses earlier ones (an outer trace around an
    inner jit's) adds only the time they leave uncovered."""
    clock = TL.CompileClock()
    clock.on_event("trace_s", 0.0)
    inner = clock.totals["trace_s"]
    clock.on_event("trace_s", 10.0)           # began long before: encloses
    assert clock.totals["trace_s"] == pytest.approx(10.0, abs=1e-3)
    assert inner <= 1e-3


def test_span_is_a_profiler_annotation(tmp_path):
    """With no log on, ``span()`` still lands in a ``jax.profiler``
    trace as ``e2c.<name>`` with its attributes as stats."""
    import glob

    from jax.profiler import ProfileData
    TL.disable()
    with jax.profiler.trace(str(tmp_path)):
        with TL.span("probe", n_replicas=3, chunk=1):
            jax.block_until_ready(jnp.ones(4) + 1)
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = [dict(ev.stats)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "e2c.probe"]
    assert found == [{"n_replicas": 3, "chunk": 1}]


PHASE_SCOPES = {
    "dense": ("next_event", "completions", "availability", "release",
              "arrivals", "deadline_drops", "drain", "start_tasks"),
    "stream": ("next_event", "completions", "availability", "arrivals",
               "deadline_drops", "drain", "start_tasks", "retire", "refill",
               "compact"),
}


@pytest.mark.parametrize("kind", sorted(PHASE_SCOPES))
def test_compiled_sweeps_carry_phase_scopes(kind):
    """The dense sweep (scenario + workflow, so every phase runs) and
    the stream sweep name each engine phase in their HLO ``op_name``."""
    import re

    from repro.launch import experiment as X
    scen = X.ScenarioAxis((0.1,), ("powersave",), spot_frac=0.5)
    if kind == "dense":
        wl = X.WorkloadAxis(8, 2, shapes=("fork_join",))
    else:
        wl = X.WorkloadAxis(8, 2, streaming=4)
    spec = X.ExperimentSpec(2, X.FleetAxis(3, 2), wl, scenario=scen,
                            policy=X.PolicyAxis(("mct",)), seed=0)
    reps = X.normalize(spec)
    fn = X.compile_experiment(spec)
    if kind == "dense":
        args = (reps.tasks, reps.mtype, reps.tables, reps.policy_ids,
                reps.dynamics, reps.parents, None)
    else:
        args = (X.to_streams(reps, spec.stream_chunk), reps.mtype,
                reps.tables.eet, reps.tables.power, reps.policy_ids,
                reps.dynamics, None)
    hlo = fn.lower(*args).compile().as_text()
    parts = {p for name in re.findall(r'op_name="([^"]*)"', hlo)
             for p in name.split("/")}
    missing = [ph for ph in PHASE_SCOPES[kind] if ph not in parts]
    assert not missing, missing


def test_cache_stats_count_retraces():
    from repro.launch import experiment as X
    X.clear_cache()
    assert X.cache_stats() == {"hits": 0, "misses": 0, "retraces": 0,
                               "size": 0}
    spec = X.ExperimentSpec(
        n_replicas=2, fleet=X.FleetAxis(n_machines=4),
        workload=X.WorkloadAxis(n_tasks=12),
        policy=X.PolicyAxis(policies=("mct",)),
        sim=E.SimParams(max_events=89), seed=0)
    X.run_experiment(spec)
    first = X.cache_stats()
    assert first["misses"] == 1 and first["retraces"] >= 1
    X.run_experiment(spec.with_(seed=1))       # same shapes: no retrace
    second = X.cache_stats()
    assert second["hits"] == first["hits"] + 1
    assert second["retraces"] == first["retraces"]


# ---------------------------------------------------------------------------
# Bench ledger regression gate (benchmarks/run.py --compare)
# ---------------------------------------------------------------------------
def _ledger(checks, rows_ms, stamp="a"):
    return {"timestamp": stamp, "checks": checks,
            "payloads": {"bench_engine": {
                "rows": [{"replicas": k, "per_replica_ms": v}
                         for k, v in rows_ms.items()]}}}


def test_compare_runs_flags_regressions():
    from benchmarks.run import compare_runs
    prev = _ledger({"t.ok": True, "t.was_bad": False}, {"8": 1.0})
    cur = _ledger({"t.ok": False, "t.was_bad": False, "t.new": False},
                  {"8": 3.0, "9": 5.0}, stamp="b")
    v = compare_runs(prev, cur, ratio=2.0)
    assert v["check_regressions"] == ["t.ok"]       # True -> False only
    assert v["checks_added"] == ["t.new"]           # new FAILs don't gate
    assert v["timing_regressions"] == [
        {"module": "bench_engine", "row": "8", "prev_ms": 1.0,
         "cur_ms": 3.0, "ratio": 3.0}]              # row "9" has no base
    assert not v["ok"]
    good = compare_runs(prev, _ledger({"t.ok": True}, {"8": 1.5}, "c"),
                        ratio=2.0)
    assert good["ok"] and not good["timing_regressions"]


def test_viz_metrics_dashboard():
    from repro.core import viz as V
    eet, power, wl, mtype = make_instance(13, n_tasks=32)
    stt = E.simulate(wl, eet, power, mtype, policy="mct", trace=True,
                     metrics=True)
    svg = V.metrics_dashboard(stt.metrics)
    assert svg.startswith("<svg") and "SLO windows" in svg
    html = V.html_report(stt, metrics=stt.metrics)
    assert "Telemetry dashboard" in html


def test_shared_executable_summary_matches_report_rows(shared_sweep):
    """The session-shared compiled sweep reproduces report.summarize's
    count columns replica by replica (metrics suite's user of the
    shared executable — tier-1 wall-time satellite)."""
    from repro.core import report as REP
    from repro.launch import experiment as X
    spec = X.ExperimentSpec(
        4, X.FleetAxis(4, 2), X.WorkloadAxis(20, 3),
        policy=X.PolicyAxis(("mct", "minmin")), seed=21)
    reps = X.normalize(spec)
    out = shared_sweep(reps.tasks, reps.mtype, reps.tables,
                       reps.policy_ids, None, None, None)
    for i in range(spec.n_replicas):
        tt = jax.tree.map(lambda x: x[i], reps.tasks)
        tb = jax.tree.map(lambda x: x[i], reps.tables)
        stt = E.run_sim(tt, reps.mtype[i], tb, reps.policy_ids[i])
        row = REP.summarize(stt, tb)
        assert int(out["completed"][i]) == row["completed"], f"rep {i}"
        assert int(out["missed"][i]) == row["missed"], f"rep {i}"
