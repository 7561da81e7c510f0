"""Chunked-vs-monolithic parity battery (launch/chunked.py, ISSUE 9).

The contract under test: ``run_experiment(spec, chunk=C)`` produces a
``SweepAgg`` that is **bitwise identical** for every chunk size —
including C = R (one chunk) and the monolithic path folded through
``aggregate_metrics`` — because the device-side reduction sums exact
integer mantissas instead of floats.  Plus: O(chunk) peak memory
(device live-buffer and host tracemalloc accounting), normalize/compute
overlap proven from telemetry spans, and per-chunk RNG determinism
against the normalize goldens.
"""
from __future__ import annotations

import math
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import engine as E
from repro.core import schedulers as P
from repro.core import telemetry as TL
from repro.launch import chunked as CH
from repro.launch import experiment as X

pytestmark = pytest.mark.chunked


# ---------------------------------------------------------------------------
# Spec zoo + exact-aggregate comparison helpers
# ---------------------------------------------------------------------------
def flat_spec(n=96, n_tasks=16, seed=7, **kw):
    return X.ExperimentSpec(
        n, X.FleetAxis(4, 2), X.WorkloadAxis(n_tasks, 3),
        policy=X.PolicyAxis(("mct", "ee_mct", "minmin")), seed=seed, **kw)


def scenario_spec(n=96, n_tasks=16, seed=3, **kw):
    return X.ExperimentSpec(
        n, X.FleetAxis(4, 2), X.WorkloadAxis(n_tasks, 3),
        scenario=X.ScenarioAxis((0.0, 0.1), ("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(("mct", "ee_mct")), seed=seed, **kw)


def streaming_spec(n=48, seed=5):
    return X.ExperimentSpec(
        n, X.FleetAxis(4, 2), X.WorkloadAxis(16, 3, streaming=16),
        policy=X.PolicyAxis(("mct", "rr")), seed=seed)


def workflow_spec(n=36, seed=11):
    return X.ExperimentSpec(
        n, X.FleetAxis(4, 2),
        X.WorkloadAxis(12, 3, shapes=("chain", "fork_join")),
        policy=X.PolicyAxis(("heft", "mct")), seed=seed)


SPECS = {
    "flat": flat_spec,
    "scenario": scenario_spec,
    "streaming": streaming_spec,
    "workflow": workflow_spec,
    "tail_metrics": lambda: flat_spec(n=48, metrics=True),
}


def assert_aggs_bitwise_equal(x: CH.SweepAgg, y: CH.SweepAgg):
    assert x.policies == y.policies and x.spec == y.spec
    assert x.columns == y.columns
    np.testing.assert_array_equal(x.counts, y.counts)
    for k in x.columns:
        for part in ("a", "b", "hist", "vmin", "vmax"):
            np.testing.assert_array_equal(
                getattr(x, part)[k], getattr(y, part)[k],
                err_msg=f"column {k} part {part}")


def monolithic_agg(spec, **kw) -> tuple[CH.SweepAgg, X.ExperimentResult]:
    res = X.run_experiment(spec, **kw)
    agg = CH.aggregate_metrics(res.metrics, res.replicas.policy_ids,
                               spec.policy.policies)
    return agg, res


# ---------------------------------------------------------------------------
# Bitwise parity: every summarize column, every grid mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", sorted(SPECS))
def test_chunked_matches_monolithic_bitwise(kind):
    spec = SPECS[kind]()
    mono, res = monolithic_agg(spec)
    ch = X.run_experiment(spec, chunk=8)
    assert set(ch.agg.columns) == set(res.metrics)   # every column
    assert_aggs_bitwise_equal(ch.agg, mono)


def test_chunked_matches_monolithic_every_policy(policy_id):
    """Single-policy grids: chunked == monolithic for each registered
    scheduler (learned ones run off the shared MCT warm start)."""
    from repro.core import neural as NN
    pp = (NN.mct_mlp_params() if policy_id in NN.LEARNED_POLICIES
          else None)
    spec = X.ExperimentSpec(12, X.FleetAxis(4, 2), X.WorkloadAxis(12, 3),
                            policy=X.PolicyAxis((policy_id,)), seed=2,
                            learned=pp is not None)
    mono, _ = monolithic_agg(spec, policy_params=pp)
    ch = X.run_experiment(spec, chunk=5, policy_params=pp)
    assert_aggs_bitwise_equal(ch.agg, mono)


def test_chunk_size_invariance():
    """R=96 through chunks of 8 / 16 / 96 → identical aggregates."""
    spec = scenario_spec()
    a8 = X.run_experiment(spec, chunk=8).agg
    a16 = X.run_experiment(spec, chunk=16).agg
    a96 = X.run_experiment(spec, chunk=96).agg
    assert_aggs_bitwise_equal(a8, a16)
    assert_aggs_bitwise_equal(a8, a96)


def test_remainder_chunk():
    """96 = 7·13 + 5: the short tail chunk folds identically."""
    spec = flat_spec()
    mono, _ = monolithic_agg(spec)
    ch = X.run_experiment(spec, chunk=13)
    assert ch.chunked.n_chunks == 8
    assert_aggs_bitwise_equal(ch.agg, mono)


def test_keep_replicas_roundtrip():
    """keep_replicas=True lands bitwise the monolithic per-replica
    metrics back on host, chunk boundaries invisible."""
    spec = scenario_spec()
    _, res = monolithic_agg(spec)
    ch = X.run_experiment(spec, chunk=16, keep_replicas=True)
    assert set(ch.metrics) == set(res.metrics)
    for k in res.metrics:
        np.testing.assert_array_equal(ch.metrics[k],
                                      np.asarray(res.metrics[k]),
                                      err_msg=f"column {k}")


def test_by_policy_off_the_aggregate():
    """ExperimentResult.by_policy works unchanged off the SweepAgg,
    with exact (correctly-rounded fsum) per-policy means."""
    spec = flat_spec()
    _, res = monolithic_agg(spec)
    ch = X.run_experiment(spec, chunk=16)
    rows_m = {r["policy"]: r for r in res.by_policy()}
    rows_c = {r["policy"]: r for r in ch.by_policy()}
    assert set(rows_c) == set(rows_m) == set(spec.policy.policies)
    pids = np.asarray(res.replicas.policy_ids)
    for pol, row in rows_c.items():
        assert row["replicas"] == rows_m[pol]["replicas"]
        sel = pids == P.POLICY_IDS[pol]
        for k in ("completion_rate", "missed", "energy", "makespan"):
            vals = np.asarray(res.metrics[k], np.float32)[sel]
            exact = math.fsum(vals.astype(np.float64)) / sel.sum()
            assert row[k] == exact, (pol, k)
            np.testing.assert_allclose(row[k], rows_m[pol][k],
                                       rtol=1e-5, atol=1e-6)


def test_aggregate_summary_quantiles_match_exact_percentile():
    """SweepAgg tails come from the shared hist_quantile implementation
    and bracket the exact sample percentiles within bucket resolution."""
    from repro.core import metrics as ME
    spec = flat_spec()
    _, res = monolithic_agg(spec)
    ch = X.run_experiment(spec, chunk=16)
    s = ch.agg.summary()
    vals = np.asarray(res.metrics["makespan"], np.float64)
    assert s["makespan"]["count"] == spec.n_replicas
    assert s["makespan"]["min"] == vals.min()
    assert s["makespan"]["max"] == vals.max()
    sp = ch.agg.spec
    ratio = (sp.hi / sp.lo) ** (1.0 / sp.buckets)   # geometric step
    for q in (50.0, 95.0, 99.0):
        got = ch.agg.quantile("makespan", q)
        exact = ME.percentile(vals, q)
        assert exact / ratio <= got <= exact * ratio, (q, got, exact)


# ---------------------------------------------------------------------------
# Fold algebra: order- and partition-invariance
# ---------------------------------------------------------------------------
def _fold_values(vals: np.ndarray) -> CH.SweepAgg:
    ids = np.full(len(vals), P.POLICY_IDS["mct"], np.int32)
    return CH.aggregate_metrics({"x": jnp.asarray(vals, jnp.float32)},
                                ids, ("mct",))


def test_fold_partition_and_order_invariance_deterministic():
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        rng.lognormal(0, 4, 200), -rng.lognormal(0, 4, 100),
        np.zeros(8), rng.normal(0, 1e-40, 16)]).astype(np.float32)
    whole = _fold_values(vals)
    for perm_seed in range(3):
        perm = np.random.default_rng(perm_seed).permutation(len(vals))
        assert_aggs_bitwise_equal(_fold_values(vals[perm]), whole)
    for cut in (1, 37, 200, len(vals) - 1):
        parts = _fold_values(vals[:cut]).merge(_fold_values(vals[cut:]))
        assert_aggs_bitwise_equal(parts, whole)
    # exact total matches correctly-rounded fsum of the true values
    assert whole.total("x") == math.fsum(vals.astype(np.float64))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(width=32, allow_nan=False,
                          allow_infinity=False),
                min_size=1, max_size=48),
       st.integers(min_value=0, max_value=47),
       st.randoms(use_true_random=False))
def test_fold_partition_and_order_invariance_property(xs, cut, rnd):
    """Hypothesis: SweepAgg folding is a commutative monoid action —
    any order, any partition of the samples, identical accumulator."""
    vals = np.asarray(xs, np.float32)
    cut = min(cut, len(vals) - 1)
    whole = _fold_values(vals)
    perm = list(range(len(vals)))
    rnd.shuffle(perm)
    assert_aggs_bitwise_equal(_fold_values(vals[perm]), whole)
    if cut > 0:
        parts = _fold_values(vals[:cut]).merge(_fold_values(vals[cut:]))
        assert_aggs_bitwise_equal(parts, whole)
    assert whole.total("x") == math.fsum(vals.astype(np.float64))


# ---------------------------------------------------------------------------
# Normalize determinism under chunking (PR-5 normalize goldens)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["flat", "scenario", "workflow",
                                  "streaming"])
def test_normalize_chunk_bitwise_equals_sliced_normalize(kind):
    spec = SPECS[kind]()
    full = X.normalize(spec)
    n = spec.n_replicas
    for lo, hi in ((0, 5), (5, n), (n - 1, n), (0, n), (7, 23)):
        got = X.normalize_chunk(spec, lo, hi)
        want = jax.tree.map(lambda x: x[lo:hi], full)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_normalize_is_prefix_stable():
    """The substream RNG makes draws independent of grid size: a bigger
    grid's prefix is bitwise the smaller grid (the property the old
    shared-sequential-RNG normalize did NOT have)."""
    small, big = flat_spec(n=8), flat_spec(n=32)
    a, b = X.normalize(small), X.normalize(big)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y)[:8])


def test_normalize_chunk_range_validation():
    spec = flat_spec(n=8)
    for lo, hi in ((-1, 4), (4, 4), (5, 3), (0, 9)):
        with pytest.raises(ValueError, match="chunk"):
            X.normalize_chunk(spec, lo, hi)


# ---------------------------------------------------------------------------
# Peak memory: O(chunk), not O(R)
# ---------------------------------------------------------------------------
def _live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def test_device_memory_stays_o_chunk():
    """jax.live_arrays() accounting: peak live device bytes during a
    chunked run stay within a few chunks' worth — far under the
    monolithic grid's footprint."""
    spec = flat_spec(n=256, n_tasks=64)
    chunk = 16
    chunk_reps = X.normalize_chunk(spec, 0, chunk)
    chunk_bytes = sum(np.asarray(leaf).nbytes
                      for leaf in jax.tree.leaves(chunk_reps))
    del chunk_reps
    X.run_experiment(spec.with_(n_replicas=32), chunk=chunk)  # warm jit
    base = _live_bytes()
    peak = 0

    def on_chunk(_c):
        nonlocal peak
        peak = max(peak, _live_bytes())

    X.run_experiment(spec, chunk=chunk, on_chunk=on_chunk)
    mono_bytes = chunk_bytes * (spec.n_replicas // chunk)
    delta = peak - base
    assert delta <= 6 * chunk_bytes, (delta, chunk_bytes)
    assert delta <= mono_bytes // 2, (delta, mono_bytes)


def test_host_memory_stays_o_chunk():
    """tracemalloc bound on the driver: host staging allocations track
    the chunk, not the grid (normalize of the full grid allocates an
    order of magnitude more)."""
    spec = flat_spec(n=256, n_tasks=64)
    X.run_experiment(spec.with_(n_replicas=32), chunk=16)     # warm jit
    tracemalloc.start()
    X.normalize(spec)
    _, mono_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    tracemalloc.start()
    X.run_experiment(spec, chunk=16)
    _, chunk_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert chunk_peak < mono_peak / 3, (chunk_peak, mono_peak)


# ---------------------------------------------------------------------------
# The async double-buffered driver: overlap + spans + validation
# ---------------------------------------------------------------------------
def test_chunk_spans_nest_and_order(tmp_path):
    """Telemetry timeline of the double-buffered driver: every chunk's
    normalize, dispatch and sync span sits under the ``experiment``
    span, and chunk c+1's normalize closes BEFORE chunk c's sync — the
    host order that lets the normalize run while chunk c is in flight
    (whether it actually hid is for a device trace to say)."""
    spec = flat_spec()
    log = TL.enable(str(tmp_path))
    try:
        res = X.run_experiment(spec, chunk=16)
    finally:
        TL.disable()
    recs = [r for r in TL.read_jsonl(log.path) if r["kind"] == "span"]
    order = {(r["name"], r.get("chunk")): i for i, r in enumerate(recs)}
    n_chunks = res.chunked.n_chunks
    parent = next(r for r in recs if r["name"] == "experiment")
    assert parent["chunked"] is True and parent["n_chunks"] == n_chunks
    for name in ("chunk_normalize", "chunk_dispatch", "chunk_sync"):
        mine = [r for r in recs if r["name"] == name]
        assert [r["chunk"] for r in sorted(mine, key=lambda r: r["chunk"])
                ] == list(range(n_chunks)), name
        assert all(r["parent"] == parent["span"] for r in mine), name
    normalized = [r for r in recs if r["name"] == "chunk_normalize"]
    assert [r["overlapped"] for r in normalized] == \
        [False] + [True] * (n_chunks - 1)
    assert sum(r["n_replicas"] for r in normalized) == spec.n_replicas
    for c in range(n_chunks - 1):
        assert order[("chunk_normalize", c + 1)] < \
            order[("chunk_sync", c)], f"chunk {c}"
        assert order[("chunk_dispatch", c)] < \
            order[("chunk_normalize", c + 1)], f"chunk {c}"
    assert 0 < res.chunked.normalize_s < res.chunked.wall_s


@pytest.mark.parametrize("kind", ["flat", "workflow", "streaming"])
@pytest.mark.parametrize("chunk", [None, 12])
def test_draw_and_stack_are_children_of_every_normalize(tmp_path, kind,
                                                        chunk):
    """Each normalize (monolithic) or chunk normalize span holds one
    ``draw`` (the per-replica host loop) and a ``stack`` (building the
    stacked inputs) — once per normalize, never once per replica."""
    spec = SPECS[kind]().with_(n_replicas=24)
    log = TL.enable(str(tmp_path))
    try:
        X.run_experiment(spec, chunk=chunk)
    finally:
        TL.disable()
    recs = [r for r in TL.read_jsonl(log.path) if r["kind"] == "span"]
    outer = [r for r in recs if r["name"] in ("normalize",
                                              "chunk_normalize")]
    assert len(outer) == (1 if chunk is None else 2)
    for o in outer:
        kids = [r["name"] for r in recs if r["parent"] == o["span"]]
        assert kids.count("draw") == 1 and "stack" in kids, kids
        assert set(kids) == {"draw", "stack"}, kids
    n_draws = sum(r["name"] == "draw" for r in recs)
    assert n_draws == len(outer)


def test_chunked_runs_through_shared_executable(shared_sweep):
    """The chunk step calls straight into the session-shared compiled
    sweep: after a chunked run the cache still maps default SimParams to
    the same callable, and chunked re-runs are pure cache hits."""
    spec = flat_spec(n=24)
    X.run_experiment(spec, chunk=8)
    assert X.compile_sweep(E.SimParams()) is shared_sweep
    before = X.cache_stats()
    X.run_experiment(spec, chunk=8)
    after = X.cache_stats()
    assert after["misses"] == before["misses"]
    assert after["retraces"] == before["retraces"]
    assert after["hits"] > before["hits"]


def test_chunked_validation_errors():
    spec = flat_spec(n=8)
    with pytest.raises(ValueError, match="chunk must be >= 1"):
        X.run_experiment(spec, chunk=0)
    with pytest.raises(ValueError, match="exact-sum"):
        X.run_experiment(spec, chunk=CH.MAX_CHUNK + 1)
    with pytest.raises(ValueError, match="trace"):
        X.run_experiment(spec.with_(trace=True), chunk=4)
    with pytest.raises(ValueError, match="only apply with chunk"):
        X.run_experiment(spec, keep_replicas=True)
    with pytest.raises(ValueError, match="outside the spec"):
        CH.aggregate_metrics(
            {"x": jnp.zeros(2)},
            np.full(2, P.POLICY_IDS["rr"], np.int32), ("mct",))


def test_chunked_accepts_pre_materialized_replicas():
    """replicas= short-circuits normalize; chunk slicing of a caller
    grid is bitwise the normalize_chunk path."""
    spec = flat_spec(n=48)
    reps = X.normalize(spec)
    mono, _ = monolithic_agg(spec, replicas=reps)
    ch = X.run_experiment(spec, chunk=16, replicas=reps)
    assert_aggs_bitwise_equal(ch.agg, mono)


def test_chunked_under_mesh():
    from repro.launch.mesh import make_local_mesh
    spec = flat_spec(n=24)
    mesh = make_local_mesh(data=1, model=1)
    mono, _ = monolithic_agg(spec)
    ch = X.run_experiment(spec, chunk=8, mesh=mesh)
    assert_aggs_bitwise_equal(ch.agg, mono)
