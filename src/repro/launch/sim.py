"""Legacy sweep-builder surface — thin deprecated shims over the
declarative :mod:`repro.launch.experiment` layer.

The paper's motivating workflow — "examine all permutations of
configurations, workload intensities and scheduling policies" — is now
ONE declarative object: build an ``ExperimentSpec`` and call
``run_experiment`` (docs/experiments.md).  The seven builders that grew
here across PRs 1-4 (``build_sim_sweep``, ``build_scenario_sweep``,
``build_traced_sweep``, ``jitted_scenario_sweep``,
``make_scenario_replicas``, ``make_workflow_replicas`` and
``learn.make_grid``) survive as shims that delegate to the spec
pipeline: replica construction is bitwise-identical and sweep results
are the same arrays (golden-tested in tests/test_experiment.py), but
each shim emits one ``DeprecationWarning`` per process.

Still first-class here (not deprecated):

* :func:`make_replicas` — the base independent-replica constructor
  (delegates to the spec materializer);
* :func:`run_grouped_sweep` — the policy-grouped execution strategy;
* :func:`trace_replica` — re-run one replica of a stacked sweep with
  tracing on;
* :func:`build_sharded_sweep` — mesh-sharded artifacts for the dry-run.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as E
from repro.core import schedulers as P
from repro.core import state as S
from repro.launch.experiment import (ExperimentSpec, FleetAxis, PolicyAxis,
                                     ScenarioAxis, WorkloadAxis,
                                     compile_sweep, normalize,
                                     summarize_replica)

__all__ = [
    "summarize_replica", "build_sim_sweep", "build_scenario_sweep",
    "build_traced_sweep", "jitted_scenario_sweep", "trace_replica",
    "run_grouped_sweep", "make_replicas", "make_scenario_replicas",
    "make_workflow_replicas", "build_sharded_sweep", "SimSweepArtifacts",
]

_WARNED: set[str] = set()


def _deprecated(name: str, hint: str) -> None:
    """One ``DeprecationWarning`` per builder per process (tests reset
    via ``_WARNED.clear()``)."""
    if name in _WARNED:
        return
    _WARNED.add(name)
    warnings.warn(
        f"launch.sim.{name} is deprecated: build an ExperimentSpec and "
        f"use repro.launch.experiment.{hint} instead (docs/experiments.md)",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Sweep builders (deprecated shims over the cached canonical executable)
# ---------------------------------------------------------------------------
def build_sim_sweep(n_tasks: int, n_machines: int,
                    params: E.SimParams = E.SimParams(),
                    learned: bool = False, workflow: bool = False):
    """DEPRECATED shim -> ``experiment.compile_sweep(params)``.

    -> f(task_table[R], mtype[R,M], tables[R], policy[R][, parents[R]]
         [, policy_params]) -> metrics[R]   (legacy argument orders).
    """
    _deprecated("build_sim_sweep", "run_experiment / compile_sweep")
    fn = compile_sweep(params)
    if learned:
        return lambda tt, mt, tb, pid, pp: fn(tt, mt, tb, pid, None, None,
                                              pp)
    if workflow:
        return lambda tt, mt, tb, pid, par: fn(tt, mt, tb, pid, None, par,
                                               None)
    return lambda tt, mt, tb, pid: fn(tt, mt, tb, pid, None, None, None)


def build_scenario_sweep(n_tasks: int, n_machines: int,
                         params: E.SimParams = E.SimParams(),
                         learned: bool = False, workflow: bool = False):
    """DEPRECATED shim -> ``experiment.compile_sweep(params)`` with a
    stacked ``MachineDynamics`` input (legacy argument orders)."""
    _deprecated("build_scenario_sweep", "run_experiment / compile_sweep")
    fn = compile_sweep(params)
    if learned and workflow:
        return lambda tt, mt, tb, pid, dyn, par, pp: fn(tt, mt, tb, pid,
                                                        dyn, par, pp)
    if learned:
        return lambda tt, mt, tb, pid, dyn, pp: fn(tt, mt, tb, pid, dyn,
                                                   None, pp)
    if workflow:
        return lambda tt, mt, tb, pid, dyn, par: fn(tt, mt, tb, pid, dyn,
                                                    par, None)
    return lambda tt, mt, tb, pid, dyn: fn(tt, mt, tb, pid, dyn, None, None)


def build_traced_sweep(n_tasks: int, n_machines: int,
                       params: E.SimParams = E.SimParams()):
    """DEPRECATED shim -> ``experiment`` with ``trace=True``: each
    replica also returns its ``TraceBuffer``.

    -> f(task_table[R], mtype[R,M], tables[R], policy[R][, dynamics[R]])
       -> (metrics[R], trace[R])
    """
    _deprecated("build_traced_sweep",
                "run_experiment with ExperimentSpec(trace=True)")
    fn = compile_sweep(params._replace(trace=True))

    def sweep(tt, mt, tb, pid, dynamics=None):
        return fn(tt, mt, tb, pid, dynamics, None, None)

    return sweep


_SWEEP_CACHE: dict = {}


def jitted_scenario_sweep(n_tasks: int, n_machines: int,
                          params: E.SimParams = E.SimParams(),
                          learned: bool = False):
    """DEPRECATED shim -> the experiment executable cache.

    The retrace-avoidance this helper existed for is now the default:
    ``experiment.compile_sweep`` caches ONE jitted callable per
    ``SimParams`` and jax specializes per input structure inside it.
    Kept so older call sites continue to get a stable callable identity
    per (shape, params, learned) key.
    """
    _deprecated("jitted_scenario_sweep", "compile_sweep")
    key = (n_tasks, n_machines, params, learned)
    if key not in _SWEEP_CACHE:
        fn = compile_sweep(params)
        if learned:
            _SWEEP_CACHE[key] = (
                lambda tt, mt, tb, pid, dyn, pp: fn(tt, mt, tb, pid, dyn,
                                                    None, pp))
        else:
            _SWEEP_CACHE[key] = (
                lambda tt, mt, tb, pid, dyn: fn(tt, mt, tb, pid, dyn,
                                                None, None))
    return _SWEEP_CACHE[key]


def trace_replica(inputs: tuple, i: int,
                  params: E.SimParams = E.SimParams(),
                  trace: bool = True) -> S.SimState:
    """Re-run replica ``i`` of a stacked sweep input with tracing on.

    The cheap path for "dump one replica's timeline from a big sweep":
    run the (traceless, fast) sweep, pick the replica you care about
    from its metrics, then re-simulate just that one with ``trace=True``
    and hand the returned state to ``core/viz.py``.  ``inputs`` is a
    legacy 4/5/6-tuple or an ``experiment.Replicas`` (its ``legacy()``
    view is taken automatically).
    """
    from repro.launch.experiment import Replicas
    if isinstance(inputs, Replicas):
        inputs = inputs.legacy()
    rep = jax.tree.map(lambda x: jnp.asarray(x)[i], tuple(inputs))
    dyn = rep[4] if len(rep) > 4 else None
    par = rep[5] if len(rep) > 5 else None
    params = params._replace(trace=trace)
    return E.run_sim(rep[0], rep[1], rep[2], rep[3], params, dyn,
                     parents=par)


# ---------------------------------------------------------------------------
# Policy-grouped execution (still first-class: a strategy, not a builder)
# ---------------------------------------------------------------------------
_GROUPED_CACHE: dict = {}


def _grouped_fn(pid: int, params: E.SimParams, learned: bool = False):
    key = (pid, params, learned)
    if key not in _GROUPED_CACHE:
        if learned:
            def one_pp(tasks, mtype, tables, policy_params):
                st = E.run_sim(tasks, mtype, tables, jnp.int32(pid), params,
                               policy_params=policy_params)
                return summarize_replica(st, tables)
            _GROUPED_CACHE[key] = jax.jit(
                jax.vmap(one_pp, in_axes=(0, 0, 0, None)))
        else:
            def one(tasks, mtype, tables):
                st = E.run_sim(tasks, mtype, tables, jnp.int32(pid), params)
                return summarize_replica(st, tables)
            _GROUPED_CACHE[key] = jax.jit(jax.vmap(one))
    return _GROUPED_CACHE[key]


def run_grouped_sweep(inputs, params: E.SimParams = E.SimParams(),
                      policy_params=None):
    """Policy-grouped sweep: one vmap per distinct policy id.

    A *vmapped* ``lax.switch`` over per-replica policy ids computes EVERY
    policy branch for every replica (batched switch lowers to select);
    grouping replicas by policy makes the id a trace-time constant, so
    each group compiles exactly one policy's drain logic — §Perf sim-cell
    iteration.  Returns metrics in the original replica order.

    ``policy_params`` (optional ``neural.PolicyParams``, shared by all
    replicas) supplies learned-policy weights — how learned-vs-heuristic
    dispatch overhead is measured (benchmarks/bench_engine.py).
    """
    from repro.launch.experiment import Replicas
    if isinstance(inputs, Replicas):
        if inputs.dynamics is not None or inputs.parents is not None:
            raise ValueError(
                "run_grouped_sweep only supports flat replicas; this "
                "Replicas carries dynamics/parents — use "
                "experiment.run_experiment for scenario/workflow grids")
        inputs = inputs.legacy()
    tt, mt, tb, pids = inputs
    pids_np = np.asarray(pids)
    out_parts = {}
    for pid in np.unique(pids_np):
        sel = np.nonzero(pids_np == pid)[0]
        take = lambda x: jax.tree.map(lambda a: a[sel], x)
        fn = _grouped_fn(int(pid), params, policy_params is not None)
        args = (take(tt), take(mt), take(tb))
        if policy_params is not None:
            args = args + (policy_params,)
        out_parts[int(pid)] = (sel, fn(*args))
    # stitch back to original order
    R = pids_np.shape[0]
    keys = out_parts[int(pids_np[0])][1].keys()
    merged = {}
    for k in keys:
        buf = np.zeros((R,), np.asarray(
            next(iter(out_parts.values()))[1][k]).dtype)
        for sel, metrics in out_parts.values():
            buf[sel] = np.asarray(metrics[k])
        merged[k] = buf
    return merged


# ---------------------------------------------------------------------------
# Replica constructors (shims over experiment.normalize)
# ---------------------------------------------------------------------------
def make_replicas(n_replicas: int, n_tasks: int, n_machines: int,
                  n_task_types: int = 4, n_machine_types: int = 4, *,
                  policies: list[str] | None = None, rate: float = 4.0,
                  seed: int = 0) -> tuple:
    """Host-side replica construction: workloads x policies x EET draws.

    Delegates to ``experiment.normalize`` (the spec materializer); kept
    first-class as the base independent-replica constructor.
    """
    policies = policies or ["fcfs", "met", "mct", "minmin", "ee_mct"]
    spec = ExperimentSpec(
        n_replicas, FleetAxis(n_machines, n_machine_types),
        WorkloadAxis(n_tasks, n_task_types, rate),
        policy=PolicyAxis(tuple(policies)), seed=seed)
    return jax.device_put(normalize(spec)).legacy()


def make_scenario_replicas(n_replicas: int, n_tasks: int, n_machines: int,
                           n_task_types: int = 4, n_machine_types: int = 4,
                           *, policies: list[str] | None = None,
                           fail_rates: list[float] | None = None,
                           dvfs_states: list[str] | None = None,
                           arrivals: tuple[str, ...] | None = None,
                           spot_frac: float = 0.5, mttr: float = 4.0,
                           n_intervals: int = 4, rate: float = 4.0,
                           seed: int = 0) -> tuple:
    """DEPRECATED shim -> ``experiment.normalize`` with a
    ``ScenarioAxis`` (failure rate x DVFS x policy [x arrival] grid).

    Returns ``(task_tables, mtypes, tables, policy_ids, dynamics)`` with
    a leading replica axis on every leaf — bitwise-identical to the
    pre-spec builder.
    """
    _deprecated("make_scenario_replicas",
                "normalize with ExperimentSpec(scenario=ScenarioAxis(...))")
    policies = policies or ["mct", "minmin", "ee_mct"]
    fail_rates = fail_rates if fail_rates is not None else [0.0, 0.05, 0.2]
    dvfs_states = dvfs_states or ["nominal", "powersave"]
    spec = ExperimentSpec(
        n_replicas, FleetAxis(n_machines, n_machine_types),
        WorkloadAxis(n_tasks, n_task_types, rate,
                     arrivals=None if arrivals is None else tuple(arrivals)),
        scenario=ScenarioAxis(tuple(fail_rates), tuple(dvfs_states),
                              spot_frac, mttr, n_intervals),
        policy=PolicyAxis(tuple(policies)), seed=seed)
    return jax.device_put(normalize(spec)).legacy()


def make_workflow_replicas(n_replicas: int, n_tasks: int, n_machines: int,
                           n_task_types: int = 4, n_machine_types: int = 4,
                           *, policies: list[str] | None = None,
                           shapes: tuple[str, ...] = ("chain", "fork_join",
                                                      "layered"),
                           fail_rates: list[float] | None = None,
                           dvfs_states: list[str] | None = None,
                           spot_frac: float = 0.0, mttr: float = 4.0,
                           n_intervals: int = 4, seed: int = 0) -> tuple:
    """DEPRECATED shim -> ``experiment.normalize`` in workflow mode
    (policy axis *paired* per DAG instance; parent tables padded to the
    grid's widest in-degree; HEFT ranks precomputed).

    Returns ``(task_tables, mtypes, tables, policy_ids, dynamics,
    parents)`` — bitwise-identical to the pre-spec builder.
    """
    _deprecated("make_workflow_replicas",
                "normalize with ExperimentSpec(WorkloadAxis(shapes=...))")
    policies = policies or ["heft", "mct", "rr"]
    fail_rates = fail_rates if fail_rates is not None else [0.0]
    dvfs_states = dvfs_states or ["nominal"]
    spec = ExperimentSpec(
        n_replicas, FleetAxis(n_machines, n_machine_types),
        WorkloadAxis(n_tasks, n_task_types, shapes=tuple(shapes)),
        scenario=ScenarioAxis(tuple(fail_rates), tuple(dvfs_states),
                              spot_frac, mttr, n_intervals),
        policy=PolicyAxis(tuple(policies)), seed=seed)
    return jax.device_put(normalize(spec)).legacy()


# ---------------------------------------------------------------------------
# Mesh-sharded artifacts (dry-run / AOT lowering)
# ---------------------------------------------------------------------------
@dataclass
class SimSweepArtifacts:
    jitted: Any
    inputs: Any               # ShapeDtypeStructs (dry-run) or arrays
    n_replicas: int


def build_sharded_sweep(mesh, n_replicas: int, n_tasks: int,
                        n_machines: int, *, n_task_types: int = 4,
                        n_machine_types: int = 4,
                        params: E.SimParams = E.SimParams(),
                        scenarios: bool = False, n_intervals: int = 4,
                        abstract: bool = False) -> SimSweepArtifacts:
    """Shard the replica axis over every mesh axis (pod x data x model).

    AOT-lowering companion of ``experiment.run_experiment(mesh=...)``:
    returns an explicitly ``in_shardings``-pinned jitted sweep plus
    matching (possibly abstract) inputs, so the dry-run can lower and
    cost-model the pod program without devices.  With ``scenarios=True``
    the sweep carries a stacked ``MachineDynamics`` input."""
    from repro.launch.mesh import mesh_device_count, replica_sharding
    fn = compile_sweep(params)

    if scenarios:
        def sweep(tt, mt, tb, pid, dyn):
            return fn(tt, mt, tb, pid, dyn, None, None)
    else:
        def sweep(tt, mt, tb, pid):
            return fn(tt, mt, tb, pid, None, None, None)

    ns = replica_sharding(mesh)
    n_dev = mesh_device_count(mesh)
    if n_replicas % n_dev:
        raise ValueError(f"n_replicas {n_replicas} must divide over "
                         f"{n_dev} devices")
    jitted = jax.jit(sweep, in_shardings=ns, out_shardings=None)
    if abstract:
        tt = S.TaskTable(
            arrival=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.float32),
            type_id=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.int32),
            deadline=jax.ShapeDtypeStruct((n_replicas, n_tasks),
                                          jnp.float32),
            status=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.int32),
            machine=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.int32),
            seq=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.int32),
            t_start=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.float32),
            t_end=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.float32),
        )
        tables = S.StaticTables(
            eet=jax.ShapeDtypeStruct(
                (n_replicas, n_task_types, n_machine_types), jnp.float32),
            power=jax.ShapeDtypeStruct(
                (n_replicas, n_machine_types, 2), jnp.float32),
            noise=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.float32),
            rank=jax.ShapeDtypeStruct((n_replicas, n_tasks), jnp.float32),
        )
        inputs = (tt,
                  jax.ShapeDtypeStruct((n_replicas, n_machines), jnp.int32),
                  tables,
                  jax.ShapeDtypeStruct((n_replicas,), jnp.int32))
        if scenarios:
            dyn = S.MachineDynamics(
                speed=jax.ShapeDtypeStruct((n_replicas, n_machines),
                                           jnp.float32),
                power_scale=jax.ShapeDtypeStruct((n_replicas, n_machines),
                                                 jnp.float32),
                down_start=jax.ShapeDtypeStruct(
                    (n_replicas, n_machines, n_intervals), jnp.float32),
                down_end=jax.ShapeDtypeStruct(
                    (n_replicas, n_machines, n_intervals), jnp.float32),
                kill=jax.ShapeDtypeStruct((n_replicas, n_machines),
                                          jnp.bool_),
            )
            inputs = inputs + (dyn,)
    else:
        spec = ExperimentSpec(
            n_replicas, FleetAxis(n_machines, n_machine_types),
            WorkloadAxis(n_tasks, n_task_types),
            scenario=(ScenarioAxis((0.0, 0.05, 0.2),
                                   ("nominal", "powersave"),
                                   spot_frac=0.5, n_intervals=n_intervals)
                      if scenarios else None),
            policy=PolicyAxis(("mct", "minmin", "ee_mct") if scenarios
                              else ("fcfs", "met", "mct", "minmin",
                                    "ee_mct")))
        inputs = jax.device_put(normalize(spec)).legacy()
    return SimSweepArtifacts(jitted=jitted, inputs=inputs,
                             n_replicas=n_replicas)
