"""Property tests: the vectorized JAX DES engine == plain-Python oracle.

This is the central correctness claim of the reproduction: the jit'd,
vmappable engine implements E2C's task lifecycle *exactly* (statuses,
assignments, start/end times, energy), for every scheduling policy, on
randomized instances.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis optional (dev extra)
from conftest import make_instance  # shared fleet builder (conftest.py)

from repro.core import engine as E
from repro.core import ref_engine as R
from repro.core import schedulers as P
from repro.core import state as S

POLICIES = list(P.SCHEDULERS)

# pallas=True runs the same suite through the fused dispatch kernels
# (interpret mode on CPU) — the oracle parity doubles as the engine-level
# kernel contract (docs/kernels.md)
PALLAS_MODES = [False, pytest.param(True, marks=pytest.mark.pallas)]


def run_both(eet, power, wl, mtype, policy, lcap=3, qcap=1 << 30,
             cancel=True, pallas=False):
    st_jax = E.simulate(wl, eet, power, mtype, policy=policy, lcap=lcap,
                        qcap=qcap, cancel_infeasible=cancel, pallas=pallas)
    ref = R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                         power, mtype, policy=policy, lcap=lcap, qcap=qcap,
                         cancel_infeasible=cancel)
    return st_jax, ref


def assert_equivalent(st_jax, ref, context=""):
    np.testing.assert_array_equal(
        np.asarray(st_jax.tasks.status), ref.status,
        err_msg=f"status mismatch {context}")
    np.testing.assert_array_equal(
        np.asarray(st_jax.tasks.machine), ref.machine,
        err_msg=f"machine mismatch {context}")
    np.testing.assert_allclose(
        np.asarray(st_jax.tasks.t_start), ref.t_start, rtol=1e-5,
        atol=1e-4, err_msg=f"t_start mismatch {context}")
    np.testing.assert_allclose(
        np.asarray(st_jax.tasks.t_end), ref.t_end, rtol=1e-5, atol=1e-4,
        err_msg=f"t_end mismatch {context}")
    np.testing.assert_allclose(
        np.asarray(st_jax.machines.energy), ref.active_energy, rtol=1e-4,
        atol=1e-2, err_msg=f"energy mismatch {context}")


@pytest.mark.parametrize("pallas", PALLAS_MODES)
def test_engine_matches_ref_fixed(small_fleet, policy_id, pallas):
    eet, power, wl, mtype = small_fleet
    st_jax, ref = run_both(eet, power, wl, mtype, policy_id, pallas=pallas)
    assert_equivalent(st_jax, ref, f"policy={policy_id} pallas={pallas}")


def test_deep_queue_drain_matches_ref(policy_id):
    """Every task is in the batch queue at t=0, so the first drain makes
    many decisions in one event: the carried machine-available vector
    and the drain bound must still follow the oracle step for step."""
    eet, power, wl, mtype = make_instance(11, 48, 6, rate=1e9)
    wl = dataclasses.replace(wl, arrival=np.zeros_like(wl.arrival))
    st_jax, ref = run_both(eet, power, wl, mtype, policy_id, lcap=12)
    assert int(st_jax.n_events) == ref.n_events, \
        f"event count diverged policy={policy_id}"
    assert_equivalent(st_jax, ref, f"deep queue policy={policy_id}")


@pytest.mark.pallas
def test_pallas_flag_bitwise_identical(small_fleet, policy_id):
    """The tentpole contract: every policy's final state is *bitwise*
    identical with the fused kernels on vs off — not allclose, equal.
    The kernels reproduce jnp.argmin's first-flat-index tie-breaking, so
    every drain decision (and hence every downstream float) matches."""
    import jax
    eet, power, wl, mtype = small_fleet
    s_off = E.simulate(wl, eet, power, mtype, policy=policy_id)
    s_on = E.simulate(wl, eet, power, mtype, policy=policy_id, pallas=True)
    for a, b in zip(jax.tree_util.tree_leaves(s_off),
                    jax.tree_util.tree_leaves(s_on)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"pallas on/off divergence policy={policy_id}")


@pytest.mark.pallas
@pytest.mark.parametrize("policy", ["mct", "minmin", "maxmin", "heft"])
def test_pallas_flag_identical_trace_stream(small_fleet, policy):
    """Trace streams (event rows + fleet snapshots) are part of the
    bitwise contract: the kernels must not reorder or alter a single
    recorded transition."""
    import jax
    eet, power, wl, mtype = small_fleet
    t_off = E.simulate(wl, eet, power, mtype, policy=policy,
                       trace=True).trace
    t_on = E.simulate(wl, eet, power, mtype, policy=policy, trace=True,
                      pallas=True).trace
    for a, b in zip(jax.tree_util.tree_leaves(t_off),
                    jax.tree_util.tree_leaves(t_on)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"trace stream divergence policy={policy}")


@settings(max_examples=30, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_tasks=st.integers(4, 40),
    n_machines=st.integers(1, 6),
    n_task_types=st.integers(1, 4),
    n_machine_types=st.integers(1, 3),
    rate=st.floats(0.5, 8.0),
    slack=st.floats(1.0, 6.0),
    policy=st.sampled_from(POLICIES),
    lcap=st.integers(1, 4),
)
def test_engine_matches_ref_property(seed, n_tasks, n_machines,
                                     n_task_types, n_machine_types, rate,
                                     slack, policy, lcap):
    eet, power, wl, mtype = make_instance(
        seed, n_tasks, n_machines, n_task_types, n_machine_types, rate,
        slack)
    st_jax, ref = run_both(eet, power, wl, mtype, policy, lcap=lcap)
    assert_equivalent(
        st_jax, ref,
        f"seed={seed} policy={policy} lcap={lcap} n={n_tasks}")


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), qcap=st.integers(1, 8),
       policy=st.sampled_from(["fcfs", "mct", "minmin"]))
def test_batch_queue_overflow_cancels(seed, qcap, policy):
    """Bounded batch queue: overflow arrivals are cancelled in both."""
    eet, power, wl, mtype = make_instance(seed, 30, 2, 2, 2, rate=20.0,
                                          slack=3.0)
    st_jax, ref = run_both(eet, power, wl, mtype, policy, qcap=qcap)
    assert_equivalent(st_jax, ref, f"qcap={qcap}")


def test_every_task_reaches_terminal_state():
    eet, power, wl, mtype = make_instance(7, 64, 3, 4, 2, rate=6.0,
                                          slack=2.0)
    st_jax = E.simulate(wl, eet, power, mtype, policy="mct")
    status = np.asarray(st_jax.tasks.status)
    assert np.all(status >= S.COMPLETED), "live tasks left at end"


def test_noise_changes_actual_not_expected():
    """Scheduler uses EET; actual runtimes use noise (E2C's EET-vs-actual
    distinction)."""
    eet, power, wl, mtype = make_instance(3, 16, 2, 2, 2, rate=2.0,
                                          slack=5.0)
    noise = np.full(wl.n_tasks, 1.5, np.float32)
    st_noisy = E.simulate(wl, eet, power, mtype, policy="mct", noise=noise)
    ref = R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                         power, mtype, policy="mct", noise=noise)
    assert_equivalent(st_noisy, ref, "noise=1.5")


def test_vmapped_sweep_matches_single_runs(shared_sweep):
    """run_sweep over stacked replicas == per-replica simulate; the
    session-shared compiled metrics sweep (conftest ``shared_sweep``)
    agrees on the same replicas instead of compiling its own."""
    import jax
    import jax.numpy as jnp
    replicas = []
    for seed in range(4):
        eet, power, wl, mtype = make_instance(seed, 12, 2, 2, 2, rate=3.0,
                                              slack=4.0)
        tables = E.make_tables(eet, power, wl.n_tasks)
        replicas.append((wl.to_task_table(), jnp.asarray(mtype),
                         tables, jnp.int32(P.POLICY_IDS["mct"])))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *replicas)
    out = E.run_sweep(*stacked)
    metrics = shared_sweep(*stacked, None, None, None)
    for i, (tt, mt, tb, pid) in enumerate(replicas):
        single = E.run_sim(tt, mt, tb, pid)
        np.testing.assert_array_equal(
            np.asarray(out.tasks.status[i]),
            np.asarray(single.tasks.status), err_msg=f"replica {i}")
        n_done = int(np.sum(np.asarray(single.tasks.status)
                            == S.COMPLETED))
        assert int(metrics["completed"][i]) == n_done, f"replica {i}"
