"""Workflow (DAG) workloads: precedence correctness, engine-vs-ref
parity (final state + trace stream, static and dynamic scenarios),
HEFT behaviour, and the workflow sweep plumbing.

The central new claims (ISSUE 4 acceptance criteria):
  * no task ever starts before every parent completed;
  * a task whose parent failed (missed / cancelled / preempted) never
    runs — the doomed subtree is cancelled, cascades included;
  * the jitted engine and the plain-Python oracle agree row-for-row on
    the trace event stream for every registered policy, including a
    failure + DVFS scenario;
  * HEFT beats round-robin on a fork-join benchmark scenario.
"""
from __future__ import annotations

import numpy as np
import pytest
from _hyp import example, given, settings, st  # hypothesis optional

from repro.core import engine as E
from repro.core import ref_engine as R
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import trace as T
from repro.core.eet import synth_eet
from repro.core.workload import (WORKFLOW_GENERATORS, Workflow,
                                 chain_workflow, fork_join_workflow,
                                 layered_workflow, make_scenario,
                                 map_reduce_workflow, upward_ranks)

POLICIES = list(P.SCHEDULERS)


def make_dag_instance(seed: int, n_tasks: int = 18, n_machines: int = 3,
                      n_task_types: int = 3, n_machine_types: int = 2,
                      slack: float = 4.0, slack_jitter: float = 0.0):
    rng = np.random.default_rng(seed)
    eet = synth_eet(n_task_types, n_machine_types, inconsistency=0.4,
                    seed=seed)
    power = np.stack([rng.uniform(10, 50, n_machine_types),
                      rng.uniform(60, 200, n_machine_types)],
                     axis=1).astype(np.float32)
    wf = layered_workflow(n_tasks, n_task_types, n_layers=4, max_parents=3,
                          mean_eet=eet.eet.mean(1), slack=slack,
                          slack_jitter=slack_jitter, seed=seed + 1)
    # (engine.simulate pads the edge list to a multiple of 64, so every
    # hypothesis example reuses one compiled engine)
    mtype = rng.integers(0, n_machine_types, n_machines)
    return eet, power, wf, mtype


def run_both(eet, power, wf, mtype, policy, *, scen=None, trace=False):
    dyn = scen.dynamics() if scen is not None else None
    st_jax = E.simulate(wf, eet, power, mtype, policy=policy,
                        dynamics=dyn, trace=trace)
    rank = wf.ranks(eet.eet.mean(1))
    kw = {}
    if scen is not None:
        kw = dict(speed=scen.speed, power_scale=scen.power_scale,
                  down_start=scen.down_start, down_end=scen.down_end,
                  kill=scen.kill)
    wl = wf.workload
    ref = R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                         power, mtype, policy=policy, trace=trace,
                         edges=(wf.src, wf.dst, wf.delay), rank=rank,
                         precision="float32", **kw)
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


def assert_trace_equal(st_jax, ref, context=""):
    ev = T.events(st_jax.trace)
    jit_rows = list(zip(ev["time"], ev["kind"], ev["task"], ev["machine"]))
    assert len(jit_rows) == len(ref.trace), \
        f"row count mismatch {context}: {len(jit_rows)} vs {len(ref.trace)}"
    for i, (a, b) in enumerate(zip(jit_rows, ref.trace)):
        assert abs(float(a[0]) - b[0]) < 1e-3 and tuple(
            int(x) for x in a[1:]) == b[1:], \
            f"trace row {i} mismatch {context}: {a} vs {b}"


def assert_precedence(wf: Workflow, st_jax):
    """No task starts before all its parents complete; a task with a
    failed parent never starts at all."""
    status = np.asarray(st_jax.tasks.status)
    t_start = np.asarray(st_jax.tasks.t_start)
    t_end = np.asarray(st_jax.tasks.t_end)
    for i in range(wf.n_tasks):
        ins = np.nonzero(wf.dst == i)[0]
        ps = [int(wf.src[e]) for e in ins]
        if t_start[i] >= 0:         # the task ran at some point
            for e in ins:
                p = int(wf.src[e])
                assert status[p] == S.COMPLETED, \
                    f"task {i} ran but parent {p} has status {status[p]}"
                assert t_start[i] >= t_end[p] + wf.delay[e] - 1e-4, \
                    f"task {i} started {t_start[i]} before parent {p}'s " \
                    f"output was staged {t_end[p]} + {wf.delay[e]}"
        if any(status[p] >= S.COMPLETED and status[p] != S.COMPLETED
               for p in ps):
            assert status[i] == S.CANCELLED and t_start[i] < 0, \
                f"task {i} should be cancelled (failed parent), got " \
                f"{status[i]}"


# --------------------------------------------------------------------------
# Generators + ranks
# --------------------------------------------------------------------------
def test_generators_are_topological():
    me = np.ones(9, np.float32)
    for name, gen in WORKFLOW_GENERATORS.items():
        wf = gen(17, 9, me, 7)
        assert wf.n_tasks == 17, name
        assert np.all(wf.src < wf.dst), f"{name} not topological"
        assert np.all(np.diff(wf.dst) >= 0), f"{name} not dst-sorted"
        ids = np.arange(wf.n_tasks)[:, None]
        assert np.all(wf.parents < ids), f"{name} not topological"
        assert np.all(wf.parents >= -1), name
        assert wf.n_edges > 0 or name == "chain", name


def test_workflow_rejects_non_topological():
    from repro.core.workload import Workload
    wl = Workload(np.zeros(3, np.float32), np.zeros(3, np.int32),
                  np.full(3, 10.0, np.float32))
    with pytest.raises(ValueError):
        Workflow.from_parents(wl, np.array([[1], [-1], [-1]], np.int32))
    with pytest.raises(ValueError):
        Workflow(wl, [1], [0])


def test_upward_ranks_closed_form():
    # chain 0 -> 1 -> 2 with w = [1, 2, 3]: rank = [6, 5, 3]
    np.testing.assert_allclose(upward_ranks([0, 1], [1, 2], [1.0, 2.0, 3.0]),
                               [6.0, 5.0, 3.0])
    # fork-join: 0 -> {1, 2} -> 3, unit weights: rank = [3, 2, 2, 1]
    src, dst = [0, 0, 1, 2], [1, 2, 3, 3]
    np.testing.assert_allclose(upward_ranks(src, dst, np.ones(4)),
                               [3.0, 2.0, 2.0, 1.0])
    # staging delays add on the edges (Topcuoglu's c̄): 0 -(0.5)-> 1,
    # 0 -(2)-> 2, {1, 2} -(0)-> 3
    np.testing.assert_allclose(
        upward_ranks(src, dst, np.ones(4), [0.5, 2.0, 0.0, 0.0]),
        [5.0, 2.0, 2.0, 1.0])


def test_chain_executes_sequentially():
    eet, power, _, _ = make_dag_instance(0)
    wf = chain_workflow(8, 3, mean_eet=eet.eet.mean(1), slack=6.0)
    st_jax = E.simulate(wf, eet, power, [0, 1, 0], policy="mct")
    status = np.asarray(st_jax.tasks.status)
    t_start = np.asarray(st_jax.tasks.t_start)
    t_end = np.asarray(st_jax.tasks.t_end)
    assert np.all(status == S.COMPLETED)
    assert np.all(t_start[1:] >= t_end[:-1] - 1e-4)


# --------------------------------------------------------------------------
# Engine vs reference: final state + trace stream, every policy
# --------------------------------------------------------------------------
@pytest.mark.parametrize("policy", POLICIES)
def test_dag_engine_matches_ref_static(policy):
    eet, power, wf, mtype = make_dag_instance(2)
    st_jax, ref = run_both(eet, power, wf, mtype, policy, trace=True)
    assert_equivalent(st_jax, ref, f"policy={policy}")
    assert_trace_equal(st_jax, ref, f"policy={policy}")
    assert_precedence(wf, st_jax)


@pytest.mark.parametrize("policy", POLICIES)
def test_dag_engine_matches_ref_dynamic(policy):
    """Failure + DVFS scenario: the acceptance-criterion parity case."""
    eet, power, wf, mtype = make_dag_instance(3, slack=3.0)
    scen = make_scenario(wf.workload, len(mtype), fail_rate=0.06,
                         mttr=3.0, spot=False, dvfs="powersave", seed=3)
    st_jax, ref = run_both(eet, power, wf, mtype, policy, scen=scen,
                           trace=True)
    assert_equivalent(st_jax, ref, f"policy={policy} dynamic")
    assert_trace_equal(st_jax, ref, f"policy={policy} dynamic")
    assert_precedence(wf, st_jax)


@settings(max_examples=20, deadline=None)
@example(seed=8945, policy="minmin", slack=2.0)   # a float32 tie: tasks
# 7 and 11 end one ulp apart in float32, at one time in float64
@given(seed=st.integers(0, 10_000),
       policy=st.sampled_from(["mct", "heft", "ee_mct", "minmin", "rr"]),
       slack=st.floats(1.5, 6.0))
def test_dag_property_no_early_starts(seed, policy, slack):
    """Seeded random layered DAGs: precedence holds and the oracle
    agrees on the final lifecycle, under deadline pressure (cascade
    cancels included)."""
    eet, power, wf, mtype = make_dag_instance(seed, slack=slack,
                                              slack_jitter=0.3)
    st_jax, ref = run_both(eet, power, wf, mtype, policy)
    assert_equivalent(st_jax, ref, f"seed={seed} policy={policy}")
    assert_precedence(wf, st_jax)


def test_failed_parent_cascades_to_descendants():
    """Kill the chain head via an impossible deadline: every descendant
    must be cancelled without ever starting."""
    eet, power, _, _ = make_dag_instance(3)
    wf = chain_workflow(6, 3, mean_eet=eet.eet.mean(1), slack=6.0)
    deadline = wf.workload.deadline.copy()
    deadline[0] = 1e-4           # head can never finish in time
    wl = wf.workload
    wl.deadline = deadline
    wf = Workflow(wl, wf.src, wf.dst)
    st_jax, ref = run_both(eet, power, wf, [0, 1], "mct", trace=True)
    status = np.asarray(st_jax.tasks.status)
    assert status[0] in (S.CANCELLED, S.MISSED_QUEUE, S.MISSED_RUNNING)
    np.testing.assert_array_equal(status[1:], S.CANCELLED)
    assert np.all(np.asarray(st_jax.tasks.t_start)[1:] < 0)
    assert_equivalent(st_jax, ref, "cascade")
    assert_trace_equal(st_jax, ref, "cascade")


def test_empty_parent_table_matches_independent():
    """A parents table with no edges must reproduce the independent-task
    results exactly (the DAG machinery is semantically inert)."""
    import jax.numpy as jnp
    from repro.core.workload import poisson_workload
    rng = np.random.default_rng(5)
    eet = synth_eet(3, 2, seed=5)
    power = np.stack([rng.uniform(10, 50, 2),
                      rng.uniform(60, 200, 2)], axis=1).astype(np.float32)
    wl = poisson_workload(20, rate=3.0, n_task_types=3,
                          mean_eet=eet.eet.mean(1), slack=4.0, seed=6)
    mtype = jnp.asarray([0, 1, 0], jnp.int32)
    tables = E.make_tables(eet, power, wl.n_tasks)
    base = E.run_sim(wl.to_task_table(), mtype, tables,
                     P.POLICY_IDS["mct"])
    empty = jnp.full((wl.n_tasks, 2), -1, jnp.int32)
    dag = E.run_sim(wl.to_task_table(), mtype, tables,
                    P.POLICY_IDS["mct"], parents=empty)
    np.testing.assert_array_equal(np.asarray(base.tasks.status),
                                  np.asarray(dag.tasks.status))
    np.testing.assert_allclose(np.asarray(base.tasks.t_end),
                               np.asarray(dag.tasks.t_end), atol=1e-5)


# --------------------------------------------------------------------------
# HEFT
# --------------------------------------------------------------------------
def fork_join_bench(policy: str):
    eet = synth_eet(3, 2, inconsistency=0.6, seed=41)
    power = np.array([[10., 80.], [20., 160.]], np.float32)
    wf = fork_join_workflow(8, 2, 3, mean_eet=eet.eet.mean(1), slack=50.0,
                            seed=41)
    st_jax = E.simulate(wf, eet, power, [0, 0, 1, 1], policy=policy)
    status = np.asarray(st_jax.tasks.status)
    makespan = float(np.asarray(st_jax.tasks.t_end).max())
    return status, makespan


def test_heft_beats_round_robin_on_fork_join():
    s_heft, mk_heft = fork_join_bench("heft")
    s_rr, mk_rr = fork_join_bench("rr")
    assert np.all(s_heft == S.COMPLETED)
    assert (s_heft == S.COMPLETED).sum() >= (s_rr == S.COMPLETED).sum()
    assert mk_heft < mk_rr, (mk_heft, mk_rr)


def test_heft_degenerates_to_mct_on_independent_tasks():
    """Zero ranks: heft = head-of-queue + min completion = mct."""
    from repro.core.workload import poisson_workload
    eet = synth_eet(3, 2, seed=9)
    power = np.array([[10., 80.], [20., 120.]], np.float32)
    wl = poisson_workload(20, rate=3.0, n_task_types=3,
                          mean_eet=eet.eet.mean(1), slack=4.0, seed=9)
    a = E.simulate(wl, eet, power, [0, 1], policy="heft")
    b = E.simulate(wl, eet, power, [0, 1], policy="mct")
    np.testing.assert_array_equal(np.asarray(a.tasks.status),
                                  np.asarray(b.tasks.status))
    np.testing.assert_allclose(np.asarray(a.tasks.t_end),
                               np.asarray(b.tasks.t_end), atol=1e-5)


# --------------------------------------------------------------------------
# Sweep plumbing + viz
# --------------------------------------------------------------------------
def test_workflow_sweep_matches_single_runs():
    import jax
    from repro.launch.sim import (build_scenario_sweep,
                                  make_workflow_replicas)
    inputs = make_workflow_replicas(6, 14, 3, seed=2)
    sweep = jax.jit(build_scenario_sweep(14, 3, workflow=True))
    out = sweep(*inputs)
    for i in (0, 3, 5):
        rep = jax.tree.map(lambda x: np.asarray(x)[i], tuple(inputs))
        single = E.run_sim(rep[0], rep[1], rep[2], rep[3],
                           E.SimParams(), rep[4], parents=rep[5])
        assert int(out["completed"][i]) == int(
            (np.asarray(single.tasks.status) == S.COMPLETED).sum())


def test_gantt_draws_dependency_arrows():
    from repro.core import viz
    eet, power, _, _ = make_dag_instance(1)
    wf = fork_join_workflow(4, 1, 3, mean_eet=eet.eet.mean(1), slack=50.0,
                            seed=1)
    st_jax = E.simulate(wf, eet, power, [0, 1, 0], policy="heft",
                        trace=True)
    svg = viz.gantt(st_jax, workflow=wf)
    assert svg.count("marker-end") >= wf.n_edges - 1
    assert "critical path" in svg
    # raw parent arrays work too, and the overlay can be disabled
    svg2 = viz.gantt(st_jax, workflow=wf.parents, critical_path=False)
    assert "critical path" not in svg2
