"""Event-loop invariants: trip accounting, hoists and fused kernels.

Contracts from the hot-loop overhaul (docs/engine_perf.md):

* **trip accounting** — the jitted engine's ``SimState.n_events``
  equals the reference engine's processed-event count for every
  registered policy: the incremental ``n_live``/``n_batch`` counters
  that gate the event loop and the drain bound admit exactly the
  same trips the full-status scans did;
* **loop-invariant hoists** — ``sorted_transitions`` + the
  searchsorted probe in ``_next_event_time`` select the same float the
  per-event ravel + concat + masked min used to, and the fused
  event-reduction kernels match their jnp oracles.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import ref_engine as R
from repro.core import state as S
from repro.kernels import ref as KREF
from repro.kernels import sched_argmin as K

PALLAS_MODES = [False, pytest.param(True, marks=pytest.mark.pallas)]


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
# hoisted availability transitions
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
