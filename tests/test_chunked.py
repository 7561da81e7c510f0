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
# Host-built inputs: no device work until one transfer per leaf
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["flat", "scenario", "workflow",
                                  "streaming"])
def test_normalize_starts_no_device_work(kind):
    """The draws, the stacking and the stream repack run on the host:
    under a guard that refuses every transfer (on the CPU plain
    ``disallow`` lets ``jnp.asarray`` through), each returns numpy
    leaves only."""
    spec = SPECS[kind]()
    with jax.transfer_guard("disallow_explicit"):
        if kind == "workflow":
            outs = [X._draw_workflow_cell(spec, 3)]
        else:
            outs = [X._draw_flat_replica(spec, 5)]
        reps = X.normalize_chunk(spec, 2, 11)
        outs += [X.normalize(spec), reps, X.sweep_args(spec, reps)]
    for out in outs:
        leaves = jax.tree.leaves(out)
        assert leaves and all(isinstance(x, (np.ndarray, int))
                              for x in leaves), {type(x) for x in leaves}


def _device_constructors(monkeypatch):
    """Swap the host constructors for the device constructors the normalize
    used to call (``jnp.asarray``/``jnp.zeros``/``jnp.full`` per leaf,
    ``jnp.stack`` over device arrays) — the reference path."""
    from repro.core import state as S
    from repro.core.workload import Scenario, Workload

    def task_table(w):
        n = w.n_tasks
        return S.TaskTable(
            arrival=jnp.asarray(w.arrival), type_id=jnp.asarray(w.type_id),
            deadline=jnp.asarray(w.deadline),
            status=jnp.zeros((n,), jnp.int32),
            machine=jnp.full((n,), -1, jnp.int32),
            seq=jnp.zeros((n,), jnp.int32),
            t_start=jnp.zeros((n,), jnp.float32),
            t_end=jnp.zeros((n,), jnp.float32))

    def dynamics(sc):
        return S.MachineDynamics(
            speed=jnp.asarray(sc.speed),
            power_scale=jnp.asarray(sc.power_scale),
            down_start=jnp.asarray(sc.down_start),
            down_end=jnp.asarray(sc.down_end), kill=jnp.asarray(sc.kill))

    def tables(eet, power, n_tasks, *, noise=None, rank=None):
        noise = np.ones(n_tasks, np.float32) if noise is None else noise
        rank = np.zeros(n_tasks, np.float32) if rank is None else rank
        return S.StaticTables(
            eet=jnp.asarray(eet.eet, jnp.float32),
            power=jnp.asarray(power, jnp.float32),
            noise=jnp.asarray(noise, jnp.float32),
            rank=jnp.asarray(rank, jnp.float32))

    monkeypatch.setattr(Workload, "host_task_table", task_table)
    monkeypatch.setattr(Scenario, "host_dynamics", dynamics)
    monkeypatch.setattr(E, "make_host_tables", tables)
    monkeypatch.setattr(X, "_stack", lambda trees: jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *trees))


def _executable_inputs(spec, chunk, mesh, monkeypatch) -> list:
    """What each executable call of ``run_experiment`` receives (one
    entry a call: the inputs, and the policy index of a chunk step);
    the executables are stubbed, so nothing is computed."""
    seen = []
    if chunk is None:
        monkeypatch.setattr(X, "compile_experiment", lambda spec: (
            lambda *args: seen.append(args[:-1]) or {}))
    else:
        def fake_step(params, aspec, streaming, keep):
            def step(cols, pol_idx, args, policy_params):
                seen.append((pol_idx, args))
                return cols, None, jnp.zeros(())
            return step
        monkeypatch.setattr(CH, "_compile_chunk_step", fake_step)
    X.run_experiment(spec, chunk=chunk, mesh=mesh)
    return seen


@pytest.mark.parametrize("kind", ["flat", "scenario", "workflow",
                                  "streaming"])
@pytest.mark.parametrize("chunk", [None, 8])
@pytest.mark.parametrize("sharded", [False, True])
def test_executable_inputs_match_device_constructors(kind, chunk, sharded,
                                                     monkeypatch):
    """Host-built, placed once: every executable call sees inputs
    bitwise, dtype-, shape- and sharding-equal to those the device
    constructors built, monolithic and chunked, with and without a
    mesh (one device here)."""
    from repro.launch.mesh import make_local_mesh
    spec = SPECS[kind]().with_(n_replicas=24)
    mesh = make_local_mesh(data=1, model=1) if sharded else None
    got = _executable_inputs(spec, chunk, mesh, monkeypatch)
    _device_constructors(monkeypatch)
    want = _executable_inputs(spec, chunk, mesh, monkeypatch)
    assert len(got) == len(want) == (1 if chunk is None else 3)
    for g, w in zip(got, want):
        assert jax.tree.structure(g) == jax.tree.structure(w)
        for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            assert isinstance(x, jax.Array) and isinstance(y, jax.Array)
            assert (x.dtype, x.shape) == (y.dtype, y.shape)
            assert x.sharding == y.sharding
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("kind", ["flat", "streaming"])
@pytest.mark.parametrize("chunked", [False, True])
def test_stack_span_counts_one_transfer_per_leaf(tmp_path, kind, chunked,
                                                 monkeypatch):
    """The ``stack`` span that places the inputs records one transfer
    per input leaf whatever the replica count, and bytes that scale
    with it (a call, or a chunk, of 8 and of 32 replicas)."""
    spans = {}
    for n in (8, 32):
        log = TL.enable(str(tmp_path / str(n)))
        try:
            inputs = _executable_inputs(
                SPECS[kind]().with_(n_replicas=n), n if chunked else None,
                None, monkeypatch)
        finally:
            TL.disable()
        spans[n] = [r for r in TL.read_jsonl(log.path)
                    if r["kind"] == "span" and "transfers" in r]
        assert len(spans[n]) == len(inputs)
        for sp, leaves in zip(spans[n], inputs):
            assert sp["name"] == "stack"
            assert sp["transfers"] == len(jax.tree.leaves(leaves))
            assert sp["h2d_bytes"] == sum(x.nbytes for x in
                                          jax.tree.leaves(leaves))
    small, big = spans[8][0], spans[32][0]
    assert small["transfers"] == big["transfers"]
    assert big["h2d_bytes"] == 4 * small["h2d_bytes"]


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
