"""Loop-trip accounting + K-way speculative drain parity battery.

Three contracts from the hot-loop overhaul (docs/engine_perf.md):

* **trip accounting** — the jitted engine's ``SimState.n_events``
  equals the reference engine's processed-event count for every
  registered policy: the incremental ``n_live``/``n_batch`` counters
  that now gate the event loop and the drain bound admit exactly the
  same trips the full-status scans did;
* **K-way == sequential** — ``SimParams(drain_k=K)`` produces the
  bitwise-identical final state (statuses, mapping seqs, float times,
  energies, event counts) as the single-step drain for every policy,
  both pallas modes; a hypothesis property extends the fixed seeds to
  random instances when the dev extra is installed;
* **loop-invariant hoists** — ``sorted_transitions`` + the
  searchsorted probe in ``_next_event_time`` select the same float the
  per-event ravel + concat + masked min used to (satellite pin), and
  the fused event-reduction kernels match their jnp oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis optional (dev extra)
from conftest import make_instance  # shared fleet builder (conftest.py)

from repro.core import engine as E
from repro.core import ref_engine as R
from repro.core import schedulers as P
from repro.core import state as S
from repro.kernels import ref as KREF
from repro.kernels import sched_argmin as K

POLICIES = list(P.POLICY_NAMES)
PALLAS_MODES = [False, pytest.param(True, marks=pytest.mark.pallas)]

_STATE_FIELDS = (
    ("tasks", ("status", "machine", "seq", "t_start", "t_end")),
    ("machines", ("running", "busy_until", "energy", "active_time")),
)


def _stacked_policy_instance(seed, n_tasks=24, n_machines=4, rate=3.0):
    """One fleet instance replicated across every registered policy —
    a single vmapped ``run_sim`` covers the whole policy matrix with
    one compilation per ``SimParams``."""
    eet, power, wl, mtype = make_instance(seed, n_tasks, n_machines,
                                          rate=rate)
    tt = wl.to_task_table()
    tb = E.make_tables(eet, power, wl.n_tasks)
    n_pol = len(POLICIES)
    tt, mt, tb = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_pol,) + x.shape),
        (tt, jnp.asarray(mtype), tb))
    return tt, mt, tb, jnp.arange(n_pol, dtype=jnp.int32)


def _run_all_policies(inputs, params):
    tt, mt, tb, pid = inputs
    fn = jax.jit(jax.vmap(
        lambda a, b, c, p: E.run_sim(a, b, c, p, params)),
        static_argnums=())
    return fn(tt, mt, tb, pid)


def _assert_bitwise(res_a, res_b, context):
    for group, fields in _STATE_FIELDS:
        ga, gb = getattr(res_a, group), getattr(res_b, group)
        for f in fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ga, f)), np.asarray(getattr(gb, f)),
                err_msg=f"{group}.{f} mismatch {context}")
    for f in ("time", "n_events", "seq_counter", "n_batch", "n_live",
              "mq_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_a, f)), np.asarray(getattr(res_b, f)),
            err_msg=f"{f} mismatch {context}")


# -------------------------------------------------------------------------
# trip accounting: engine n_events == reference event count
# -------------------------------------------------------------------------
@pytest.mark.parametrize("pallas", PALLAS_MODES)
def test_n_events_matches_ref(small_fleet, policy_id, pallas):
    eet, power, wl, mtype = small_fleet
    st_jax = E.simulate(wl, eet, power, mtype, policy=policy_id, lcap=3,
                        pallas=pallas)
    ref = R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                         power, mtype, policy=policy_id, lcap=3)
    assert int(st_jax.n_events) == ref.n_events, \
        f"loop-trip count diverged for policy={policy_id}"
    # the incremental live counter drained to zero exactly at the end
    assert int(st_jax.n_live) == 0


# -------------------------------------------------------------------------
# K-way drain == sequential drain, bitwise, all policies at once
# -------------------------------------------------------------------------
@pytest.mark.parametrize("pallas", PALLAS_MODES)
@pytest.mark.parametrize("k", [2, 8])
def test_kway_drain_bitwise_equals_sequential(k, pallas):
    inputs = _stacked_policy_instance(seed=3, n_tasks=24, n_machines=4)
    seq = _run_all_policies(inputs, E.SimParams(lcap=3, pallas=pallas))
    kway = _run_all_policies(
        inputs, E.SimParams(lcap=3, drain_k=k, pallas=pallas))
    _assert_bitwise(kway, seq, f"k={k} pallas={pallas} (all policies)")


def test_kway_drain_dense_batch():
    """The regime K-way was built for: every task arrives at t=0, the
    first drain schedules a deep queue — still bitwise sequential."""
    eet, power, wl, mtype = make_instance(11, 48, 6, rate=1e9)
    tt = wl.to_task_table()
    tt = type(tt)(**{**{f: getattr(tt, f)
                        for f in tt.__dataclass_fields__},
                     "arrival": jnp.zeros_like(tt.arrival)})
    tb = E.make_tables(eet, power, wl.n_tasks)
    for policy in ("fcfs", "mct", "edf_mct", "rr", "minmin"):
        pid = jnp.int32(P.POLICY_IDS[policy])
        seq = E.run_sim(tt, jnp.asarray(mtype), tb, pid,
                        E.SimParams(lcap=12))
        kway = E.run_sim(tt, jnp.asarray(mtype), tb, pid,
                         E.SimParams(lcap=12, drain_k=8))
        _assert_bitwise(kway, seq, f"dense policy={policy}")


def test_legacy_drain_bitwise_equals_hot():
    """The T12 baseline loop is a pure perf fork: same schedule."""
    inputs = _stacked_policy_instance(seed=5)
    hot = _run_all_policies(inputs, E.SimParams(lcap=3))
    legacy = _run_all_policies(inputs,
                               E.SimParams(lcap=3, legacy_drain=True))
    _assert_bitwise(legacy, hot, "legacy_drain (all policies)")


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 16), st.sampled_from([1.5, 4.0, 16.0]),
       st.sampled_from([2, 3, 8]))
def test_kway_drain_property(seed, rate, k):
    """Property: on random instances (fixed shapes, so the two
    executables compile once) the K-way drain is bitwise sequential for
    every policy simultaneously."""
    inputs = _stacked_policy_instance(seed=seed, rate=rate)
    seq = _run_all_policies(inputs, E.SimParams(lcap=3))
    kway = _run_all_policies(inputs, E.SimParams(lcap=3, drain_k=k))
    _assert_bitwise(kway, seq, f"seed={seed} rate={rate} k={k}")


# -------------------------------------------------------------------------
# satellite pin: hoisted availability transitions
# -------------------------------------------------------------------------
def test_sorted_transitions_pin():
    """``sorted_transitions`` + one searchsorted == the per-event
    ravel + concat + masked min it replaced, at every probe time
    including exact transition instants (strictly-after semantics)."""
    rng = np.random.default_rng(0)
    starts = jnp.asarray(rng.uniform(0, 50, (4, 3)).astype(np.float32))
    ends = starts + jnp.asarray(
        rng.uniform(0.5, 10, (4, 3)).astype(np.float32))
    dyn = S.MachineDynamics(
        down_start=starts, down_end=ends,
        kill=jnp.zeros(4, bool), speed=jnp.ones(4, jnp.float32),
        power_scale=jnp.ones(4, jnp.float32))
    trans_sorted = E.sorted_transitions(dyn)
    flat = np.concatenate([np.asarray(starts).ravel(),
                           np.asarray(ends).ravel()])
    probes = np.concatenate([flat, flat - 1e-3,
                             rng.uniform(-1, 70, 50).astype(np.float32)])
    for t in probes:
        idx = int(jnp.searchsorted(trans_sorted, jnp.float32(t),
                                   side="right"))
        hoisted = float(trans_sorted[min(idx, trans_sorted.shape[0] - 1)])
        legacy = float(jnp.min(jnp.where(jnp.asarray(flat) > t,
                                         jnp.asarray(flat), S.INF)))
        legacy = legacy if legacy < float(S.INF) else float("inf")
        assert hoisted == legacy, f"probe t={t}"


# -------------------------------------------------------------------------
# fused event-reduction kernels vs their jnp oracles (interpret mode)
# -------------------------------------------------------------------------
@pytest.mark.pallas
@pytest.mark.parametrize("n,m", [(16, 4), (100, 7), (256, 16), (301, 5)])
def test_fused_start_pick_matches_oracle(n, m):
    rng = np.random.default_rng(n * 31 + m)
    status = jnp.asarray(rng.integers(0, 8, n).astype(np.int32))
    machine = jnp.asarray(rng.integers(-1, m, n).astype(np.int32))
    seq = jnp.asarray(rng.integers(0, 1 << 20, n).astype(np.int32))
    pick, has = K.fused_start_pick(status, machine, seq, m,
                                   in_mq=S.IN_MQ, block_n=128,
                                   interpret=True)
    rpick, rhas = KREF.fused_start_pick_ref(status, machine, seq, m,
                                            in_mq=S.IN_MQ)
    np.testing.assert_array_equal(np.asarray(pick), np.asarray(rpick))
    np.testing.assert_array_equal(np.asarray(has), np.asarray(rhas))
    # oracle == the engine's materialized (N, M) formulation
    queued = (status == S.IN_MQ)[:, None] & (
        machine[:, None] == jnp.arange(m)[None, :])
    seqs = jnp.where(queued, seq[:, None], K.INT_MAX)
    np.testing.assert_array_equal(
        np.asarray(rpick), np.asarray(jnp.argmin(seqs, axis=0)))
    np.testing.assert_array_equal(
        np.asarray(rhas), np.asarray(queued.any(axis=0)))


@pytest.mark.pallas
@pytest.mark.parametrize("n", [16, 100, 256, 301])
def test_fused_event_bounds_matches_oracle(n):
    rng = np.random.default_rng(n)
    status = jnp.asarray(rng.integers(0, 8, n).astype(np.int32))
    arrival = jnp.asarray(rng.uniform(0, 100, n).astype(np.float32))
    deadline = jnp.asarray(rng.uniform(0, 200, n).astype(np.float32))
    t_arr, t_dl = K.fused_event_bounds(
        status, arrival, deadline, not_arrived=S.NOT_ARRIVED,
        live_lo=S.IN_BATCH, live_hi=S.RUNNING, block_n=128,
        interpret=True)
    r_arr, r_dl = KREF.fused_event_bounds_ref(
        status, arrival, deadline, not_arrived=S.NOT_ARRIVED,
        live_lo=S.IN_BATCH, live_hi=S.RUNNING)
    assert float(t_arr) == float(r_arr)     # bitwise, not allclose
    assert float(t_dl) == float(r_dl)
    # empty masks return the +inf sentinel
    t_arr, t_dl = K.fused_event_bounds(
        jnp.full((n,), 7, jnp.int32), arrival, deadline,
        not_arrived=S.NOT_ARRIVED, live_lo=S.IN_BATCH,
        live_hi=S.RUNNING, block_n=128, interpret=True)
    assert not np.isfinite(float(t_arr)) and not np.isfinite(float(t_dl))
