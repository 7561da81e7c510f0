"""ExperimentSpec: ONE declarative layer for every E2C sweep.

The paper's value proposition is "examine system-level solutions under
various system configurations"; the real workload of such a simulator is
*grids of configurations*, not single runs.  After the scenario, trace,
learned-policy and workflow subsystems landed, the launch layer had
grown seven overlapping entry points (``build_sim_sweep``,
``build_scenario_sweep``, ``build_traced_sweep``,
``jitted_scenario_sweep``, ``make_scenario_replicas``,
``make_workflow_replicas``, ``learn.make_grid``) wired together with
boolean flags.  This module collapses them into one pipeline
(docs/experiments.md):

  spec       :class:`ExperimentSpec` — ``FleetAxis x WorkloadAxis x
              ScenarioAxis x PolicyAxis`` plus the ``trace`` /
              ``learned`` flags; the whole experiment as data.
  normalize  :func:`normalize` — materialize the grid host-side into a
              stacked :class:`Replicas` pytree (the padding / pairing /
              dynamics-trace logic previously duplicated across the
              ``make_*_replicas`` builders).
              Its leaves are numpy: it starts no device work, and the
              caller moves the inputs in one transfer per leaf.
  compile    :func:`compile_sweep` — ONE canonical jitted executable per
              ``SimParams``, cached process-wide, so same-shape re-runs
              never retrace (bench check T8).  Optional inputs
              (dynamics / parents / policy params) enter as ``None``
              pytrees, so jax specializes per input *structure* inside
              one cached callable instead of per hand-built closure.
  execute    :func:`run_experiment` — normalize + compile + run; give it
              a ``jax.sharding.Mesh`` and the replica axis shards over
              every mesh axis (``launch/mesh.py``) transparently.

The legacy builders in ``launch/sim.py`` survive as thin deprecated
shims delegating here; their replica construction is bitwise-identical
(golden-tested in tests/test_experiment.py).
"""
from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as EN
from repro.core import engine as E
from repro.core import metrics as ME
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import telemetry as TL
from repro.core.eet import synth_eet
from repro.core.workload import (WORKFLOW_GENERATORS, make_scenario,
                                 resolve_arrivals, resolve_shapes)

__all__ = [
    "FleetAxis", "WorkloadAxis", "ScenarioAxis", "PolicyAxis",
    "ExperimentSpec", "Replicas", "ExperimentResult", "normalize",
    "normalize_chunk",
    "compile_sweep", "compile_stream_sweep", "compile_experiment",
    "run_experiment", "to_streams", "sweep_args", "h2d",
    "summarize_replica", "cache_stats", "clear_cache",
]


# ---------------------------------------------------------------------------
# Per-replica summary (shared by every sweep shape)
# ---------------------------------------------------------------------------
def summarize_replica(st: S.SimState, tables: S.StaticTables,
                      dynamics: S.MachineDynamics | None = None) -> dict:
    """Scalar metrics for one replica (traced; used under vmap).

    With ``dynamics`` the summary also reports preemption counts, mean
    machine availability, and the active/idle energy split with downtime
    (powered-off machines) subtracted from the idle integral.
    """
    status = st.tasks.status
    completed = jnp.sum(status == S.COMPLETED)
    missed = jnp.sum((status == S.MISSED_QUEUE)
                     | (status == S.MISSED_RUNNING))
    cancelled = jnp.sum(status == S.CANCELLED)
    preempted = jnp.sum(status == S.PREEMPTED)
    makespan = EN.makespan(st)
    active_e = jnp.sum(st.machines.energy)
    idle_e = jnp.sum(EN.idle_energy(st, tables, dynamics))
    avail = jnp.float32(1.0) if dynamics is None else jnp.mean(
        EN.availability(dynamics, makespan))
    n = status.shape[0]
    return {
        "completed": completed, "missed": missed, "cancelled": cancelled,
        "preempted": preempted,
        "requeues": jnp.sum(st.n_preempts) - preempted,
        "availability": avail,
        "completion_rate": completed / n,
        "makespan": makespan,
        "energy": active_e + idle_e,
        "active_energy": active_e,
        "idle_energy": idle_e,
        "mean_response": jnp.sum(jnp.where(status == S.COMPLETED,
                                           st.tasks.t_end - st.tasks.arrival,
                                           0.0)) / jnp.maximum(completed, 1),
    }


def _tail_columns(mt: ME.SimMetrics) -> dict:
    """Device-side tail columns (traced; used under vmap) appended to the
    replica summary when ``SimParams.metrics`` is on.  Keys match
    :func:`repro.core.metrics.summary` so experiment tables and report
    rows stay join-compatible."""
    out = {}
    for key, col in (("response", "resp"), ("wait", "wait"),
                     ("slowdown", "slow"), ("queue_depth", "qdepth")):
        p50, p95, p99 = ME.quantiles_jnp(getattr(mt, key), mt.spec)
        out[f"{col}_p50"] = p50
        out[f"{col}_p95"] = p95
        out[f"{col}_p99"] = p99
    return out


# ---------------------------------------------------------------------------
# The spec: axes + flags
# ---------------------------------------------------------------------------
def _astuple(x) -> tuple | None:
    return None if x is None else tuple(x)


@dataclass(frozen=True)
class FleetAxis:
    """The machine side of a replica: fleet size and type diversity.

    Each replica draws its machine-type assignment and per-type power
    table independently (Monte-Carlo over fleet composition)."""
    n_machines: int
    n_machine_types: int = 4


@dataclass(frozen=True)
class WorkloadAxis:
    """The task side: either arrival processes or workflow (DAG) shapes.

    ``arrivals`` names ``workload.ARRIVAL_GENERATORS`` entries and makes
    the arrival process a grid axis (None = Poisson everywhere, which
    preserves the exact draws of the legacy builders).  ``shapes`` names
    ``workload.WORKFLOW_GENERATORS`` entries and switches the experiment
    to workflow mode (parent tables padded to the grid's widest
    in-degree, HEFT ranks precomputed, policy axis *paired* per DAG
    instance).  The two are mutually exclusive.

    ``streaming=W`` runs every replica through the bounded-memory
    streaming engine (``core/streaming.py``) with a W-slot live-task
    window instead of the dense engine — same draws, same metrics keys,
    per-replica memory O(W) instead of O(n_tasks).  ``stream_chunk``
    sets the arrival-chunk granularity (results are invariant to it;
    default ``min(n_tasks, W)``).  Streaming composes with ``arrivals``
    and scenario axes but not with ``shapes`` (experiment-level DAG
    cells pad parent tables across the grid, which has no bounded-window
    equivalent yet — use ``streaming.simulate_stream`` directly for a
    single DAG; docs/streaming.md).
    """
    n_tasks: int
    n_task_types: int = 4
    rate: float = 4.0
    arrivals: tuple[str, ...] | None = None
    shapes: tuple[str, ...] | None = None
    streaming: int | None = None
    stream_chunk: int | None = None

    def __post_init__(self):
        object.__setattr__(self, "arrivals", _astuple(self.arrivals))
        object.__setattr__(self, "shapes", _astuple(self.shapes))
        if self.arrivals is not None and self.shapes is not None:
            raise ValueError("WorkloadAxis takes arrivals OR shapes, not "
                             "both (DAG generators emit their own arrival "
                             "times)")
        if self.arrivals is not None:
            resolve_arrivals(self.arrivals)
        if self.shapes is not None:
            resolve_shapes(self.shapes)
        if self.streaming is not None:
            if self.shapes is not None:
                raise ValueError(
                    "streaming does not compose with shapes (workflow "
                    "cells pad parent tables across the grid); run DAGs "
                    "through streaming.simulate_stream directly")
            if self.streaming < 1:
                raise ValueError(f"streaming window must be >= 1, got "
                                 f"{self.streaming}")
        if self.stream_chunk is not None:
            if self.streaming is None:
                raise ValueError("stream_chunk requires streaming=W")
            if self.stream_chunk < 1:
                raise ValueError(f"stream_chunk must be >= 1, got "
                                 f"{self.stream_chunk}")


@dataclass(frozen=True)
class ScenarioAxis:
    """Machine dynamics grid: failure rates x DVFS states (+ spot draw).

    Eviction semantics is NOT a grid axis: each replica draws
    kill-vs-requeue as an independent Bernoulli(``spot_frac``) — pin it
    to 0.0 or 1.0 to compare the two cleanly (docs/scenarios.md)."""
    fail_rates: tuple[float, ...] = (0.0,)
    dvfs_states: tuple[str, ...] = ("nominal",)
    spot_frac: float = 0.0
    mttr: float = 4.0
    n_intervals: int = 4

    def __post_init__(self):
        object.__setattr__(self, "fail_rates", tuple(self.fail_rates))
        object.__setattr__(self, "dvfs_states", tuple(self.dvfs_states))


@dataclass(frozen=True)
class PolicyAxis:
    """Scheduling policies swept over replicas (names from
    ``schedulers.POLICY_IDS``, including learned policies)."""
    policies: tuple[str, ...] = ("mct",)

    def __post_init__(self):
        object.__setattr__(self, "policies", tuple(self.policies))
        unknown = [p for p in self.policies if p not in P.POLICY_IDS]
        if unknown:
            raise ValueError(
                f"unknown policies {unknown}; known: "
                f"{sorted(P.POLICY_IDS)}")


@dataclass(frozen=True)
class ExperimentSpec:
    """One declarative experiment: axes x flags, ready to normalize,
    compile and execute (docs/experiments.md).

    Grid semantics (mixed-radix over the replica index ``r``):

    * flat mode (no scenario, no shapes): policy = ``r % n_p``, arrival
      process (if given) = ``(r // n_p) % n_a``;
    * scenario mode: fail = ``r % n_f``, dvfs = ``(r // n_f) % n_d``,
      policy = ``(r // (n_f n_d)) % n_p``, arrival =
      ``(r // (n_f n_d n_p)) % n_a`` — identical to the legacy
      ``make_scenario_replicas`` layout;
    * workflow mode (``workload.shapes``): replicas come in *paired*
      cells — the ``n_p`` consecutive replicas of a cell share one DAG /
      EET draw / fleet / failure trace so per-policy aggregates compare
      apples to apples; shape = ``cell % n_s``, fail =
      ``(cell // n_s) % n_f``, dvfs = ``(cell // (n_s n_f)) % n_d``.

    ``trace=True`` compiles the in-jit TraceBuffer in (results carry a
    per-replica trace); ``pallas=True`` routes dispatch through the fused
    Pallas kernels (bitwise-identical results, docs/kernels.md);
    ``learned=True`` declares that the run takes a shared
    ``neural.PolicyParams`` pytree (pass it to :func:`run_experiment`).
    """
    n_replicas: int
    fleet: FleetAxis
    workload: WorkloadAxis
    scenario: ScenarioAxis | None = None
    policy: PolicyAxis = field(default_factory=PolicyAxis)
    sim: E.SimParams = field(default_factory=E.SimParams)
    trace: bool = False
    pallas: bool = False
    metrics: bool = False
    learned: bool = False
    seed: int = 0

    def __post_init__(self):
        if self.n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got "
                             f"{self.n_replicas}")

    # -- derived flags ----------------------------------------------------
    @property
    def workflow(self) -> bool:
        return self.workload.shapes is not None

    @property
    def streaming(self) -> bool:
        return self.workload.streaming is not None

    @property
    def stream_params(self):
        """Effective :class:`streaming.StreamParams` (streaming specs)."""
        from repro.core import streaming as ST
        sp = self.sim_params
        return ST.StreamParams(
            window=self.workload.streaming, lcap=sp.lcap, qcap=sp.qcap,
            cancel_infeasible=sp.cancel_infeasible,
            max_events=sp.max_events, trace=sp.trace,
            trace_capacity=sp.trace_capacity, pallas=sp.pallas,
            metrics=sp.metrics, metrics_spec=sp.metrics_spec)

    @property
    def stream_chunk(self) -> int:
        wk = self.workload
        return wk.stream_chunk or max(min(wk.n_tasks, wk.streaming), 1)

    @property
    def scenarios(self) -> bool:
        """Dynamics are materialized for any scenario axis AND for every
        workflow experiment (workflow cells always carry a — possibly
        inert — failure trace, like the legacy builder)."""
        return self.scenario is not None or self.workflow

    @property
    def sim_params(self) -> E.SimParams:
        """Effective static engine params (``trace``/``pallas`` folded in).

        Both flags are part of the ``SimParams`` executable-cache key, so
        pallas-on and pallas-off sweeps each cache their own compiled
        executable (docs/kernels.md)."""
        sp = self.sim
        if self.trace:
            sp = sp._replace(trace=True)
        if self.pallas:
            sp = sp._replace(pallas=True)
        if self.metrics:
            sp = sp._replace(metrics=True)
        return sp

    def with_(self, **kw) -> "ExperimentSpec":
        """Functional update — ``spec.with_(seed=1, trace=True)``."""
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# normalize: spec -> stacked replicas
# ---------------------------------------------------------------------------
class Replicas(NamedTuple):
    """Stacked per-replica inputs (leading axis R on every leaf): numpy
    leaves as :func:`normalize` builds them, device leaves once placed
    (``jax.device_put``).

    ``dynamics`` / ``parents`` are None when the spec compiles them out;
    ``legacy()`` returns the positional tuple shape the pre-spec
    builders produced (4-, 5- or 6-tuple)."""
    tasks: S.TaskTable
    mtype: jnp.ndarray
    tables: S.StaticTables
    policy_ids: jnp.ndarray
    dynamics: S.MachineDynamics | None = None
    parents: jnp.ndarray | None = None

    def legacy(self) -> tuple:
        out = (self.tasks, self.mtype, self.tables, self.policy_ids)
        if self.dynamics is not None:
            out = out + (self.dynamics,)
        if self.parents is not None:
            out = out + (self.parents,)
        return out

    @property
    def n_replicas(self) -> int:
        return int(self.policy_ids.shape[0])


def _stack(trees):
    return jax.tree.map(lambda *xs: np.stack(xs), *trees)


def _draw_power(rng, n_machine_types: int) -> np.ndarray:
    """[idle_W, active_W] per machine type — one Monte-Carlo draw."""
    return np.stack([rng.uniform(20, 60, n_machine_types),
                     rng.uniform(80, 300, n_machine_types)],
                    axis=1).astype(np.float32)


def _draw_workload(spec: ExperimentSpec, eet, r: int):
    """Arrival-process draw for replica ``r`` (flat/scenario modes).

    ``arrivals=None`` reproduces the legacy builders' direct Poisson
    call bit-for-bit (it equals the registered "poisson" generator)."""
    from repro.core.workload import ARRIVAL_GENERATORS, poisson_workload
    wk, sc, n_p = spec.workload, spec.scenario, len(spec.policy.policies)
    seed = spec.seed + 7919 * r
    if wk.arrivals is None:
        return poisson_workload(wk.n_tasks, rate=wk.rate,
                                n_task_types=wk.n_task_types,
                                mean_eet=eet.eet.mean(1), slack=4.0,
                                seed=seed)
    if sc is not None:
        idx = (r // (len(sc.fail_rates) * len(sc.dvfs_states) * n_p)) \
            % len(wk.arrivals)
    else:
        idx = (r // n_p) % len(wk.arrivals)
    gen = ARRIVAL_GENERATORS[wk.arrivals[idx]]
    return gen(wk.n_tasks, wk.rate, wk.n_task_types, eet.eet.mean(1), seed)


def _draw_flat_replica(spec: ExperimentSpec, r: int):
    """One flat/scenario-mode replica, fully determined by ``(spec, r)``.

    The Monte-Carlo draws (power, [spot], noise, mtype — in that order)
    come from the per-replica substream ``default_rng([seed, r])`` (the
    ``poisson_workload_chunks`` spawn pattern), so any contiguous range
    of replicas can be materialized without consuming the draws of the
    replicas before it — the property :func:`normalize_chunk` needs."""
    wk, fl, sc = spec.workload, spec.fleet, spec.scenario
    policies = spec.policy.policies
    n_p = len(policies)
    rng = np.random.default_rng([spec.seed, r])
    eet = synth_eet(wk.n_task_types, fl.n_machine_types,
                    inconsistency=0.3, seed=spec.seed + r)
    power = _draw_power(rng, fl.n_machine_types)
    wl = _draw_workload(spec, eet, r)
    dyn = None
    if sc is not None:
        n_f, n_d = len(sc.fail_rates), len(sc.dvfs_states)
        scen = make_scenario(
            wl, fl.n_machines,
            fail_rate=sc.fail_rates[r % n_f],
            mttr=sc.mttr,
            spot=(rng.random() < sc.spot_frac),
            dvfs=sc.dvfs_states[(r // n_f) % n_d],
            n_intervals=sc.n_intervals, seed=spec.seed + 31 * r)
        dyn = scen.host_dynamics()
        pol = policies[(r // (n_f * n_d)) % n_p]
    else:
        pol = policies[r % n_p]
    noise = rng.lognormal(0.0, 0.1, wk.n_tasks).astype(np.float32)
    tt = wl.host_task_table()
    tab = E.make_host_tables(eet, power, wk.n_tasks, noise=noise)
    mt = rng.integers(0, fl.n_machine_types, fl.n_machines)
    return tt, mt, tab, P.POLICY_IDS[pol], dyn


def _materialize_flat(spec: ExperimentSpec, lo: int = 0,
                      hi: int | None = None) -> Replicas:
    """Flat + scenario modes: one replica per grid cell, each drawn from
    its own RNG substream (:func:`_draw_flat_replica`), so replicas
    ``[lo, hi)`` materialize identically whether drawn alone or as part
    of the full grid — chunked normalization is bitwise-stable."""
    hi = spec.n_replicas if hi is None else hi
    tts, mts, tabs, pids, dyns = [], [], [], [], []
    with TL.span("draw"):
        for r in range(lo, hi):
            tt, mt, tab, pid, dyn = _draw_flat_replica(spec, r)
            tts.append(tt)
            mts.append(mt)
            tabs.append(tab)
            pids.append(pid)
            if dyn is not None:
                dyns.append(dyn)
    with TL.span("stack"):
        reps = Replicas(
            _stack(tts), np.stack(mts).astype(np.int32),
            _stack(tabs), np.asarray(pids, np.int32),
            _stack(dyns) if dyns else None, None)
    return reps


def _draw_workflow_cell(spec: ExperimentSpec, cell: int):
    """One workflow cell (shared by its ``n_p`` paired replicas), fully
    determined by ``(spec, cell)`` via the per-cell substream
    ``default_rng(seed + 104729 * cell)`` — already random-access."""
    wk, fl = spec.workload, spec.fleet
    sc = spec.scenario or ScenarioAxis()
    shapes = wk.shapes
    n_s, n_f = len(shapes), len(sc.fail_rates)
    crng = np.random.default_rng(spec.seed + 104729 * cell)
    eet = synth_eet(wk.n_task_types, fl.n_machine_types,
                    inconsistency=0.3, seed=spec.seed + cell)
    power = _draw_power(crng, fl.n_machine_types)
    gen = WORKFLOW_GENERATORS[shapes[cell % n_s]]
    wf = gen(wk.n_tasks, wk.n_task_types, eet.eet.mean(1),
             spec.seed + 7919 * cell)
    scen = make_scenario(
        wf.workload, fl.n_machines,
        fail_rate=sc.fail_rates[(cell // n_s) % n_f],
        mttr=sc.mttr, spot=(crng.random() < sc.spot_frac),
        dvfs=sc.dvfs_states[(cell // (n_s * n_f))
                            % len(sc.dvfs_states)],
        n_intervals=sc.n_intervals, seed=spec.seed + 31 * cell)
    noise = crng.lognormal(0.0, 0.1, wk.n_tasks).astype(np.float32)
    tt = wf.workload.host_task_table()
    mt = crng.integers(0, fl.n_machine_types, fl.n_machines)
    tab = E.make_host_tables(eet, power, wk.n_tasks, noise=noise,
                             rank=wf.ranks(eet.eet.mean(1)))
    return tt, mt, tab, scen.host_dynamics(), wf.parents


_KMAX_CACHE: dict[ExperimentSpec, int] = {}


def _workflow_kmax(spec: ExperimentSpec) -> int:
    """Grid-wide widest DAG in-degree — the parent-table pad width.

    Chunked normalization needs it up front (a chunk only sees its own
    cells, but every chunk must pad to the same width as the monolithic
    grid).  DAG generation is deterministic per cell, so a cheap
    generate-and-discard pre-pass over the cells recovers exactly the
    width :func:`_materialize_workflow` computes from the full grid."""
    km = _KMAX_CACHE.get(spec)
    if km is None:
        wk, fl = spec.workload, spec.fleet
        shapes = wk.shapes
        n_s = len(shapes)
        n_p = len(spec.policy.policies)
        km = 0
        for cell in range(-(-spec.n_replicas // n_p)):
            eet = synth_eet(wk.n_task_types, fl.n_machine_types,
                            inconsistency=0.3, seed=spec.seed + cell)
            gen = WORKFLOW_GENERATORS[shapes[cell % n_s]]
            wf = gen(wk.n_tasks, wk.n_task_types, eet.eet.mean(1),
                     spec.seed + 7919 * cell)
            km = max(km, wf.parents.shape[1])
        _KMAX_CACHE[spec] = km
    return km


def _materialize_workflow(spec: ExperimentSpec, lo: int = 0,
                          hi: int | None = None,
                          k_max: int | None = None) -> Replicas:
    """Workflow mode: per-cell RNG, *paired* policy axis — the ``n_p``
    consecutive replicas of a cell share one DAG / EET / fleet / failure
    trace.  Parent tables pad to the grid's widest in-degree (``k_max``,
    computed from the materialized range when not given — chunked
    callers pass the grid-wide :func:`_workflow_kmax`)."""
    hi = spec.n_replicas if hi is None else hi
    policies = spec.policy.policies
    n_p = len(policies)
    tts, mts, tabs, pids, dyns, pars = [], [], [], [], [], []
    with TL.span("draw"):
        for cell in range(lo // n_p, -(-hi // n_p)):
            tt, mt, tab, dyn, parents = _draw_workflow_cell(spec, cell)
            for p in range(n_p):
                r = cell * n_p + p
                if lo <= r < hi:
                    tts.append(tt)
                    mts.append(mt)
                    tabs.append(tab)
                    pids.append(P.POLICY_IDS[policies[p]])
                    dyns.append(dyn)
                    pars.append(parents)
    with TL.span("stack"):
        k_max = max(p.shape[1] for p in pars) if k_max is None else k_max
        parents = np.full((hi - lo, spec.workload.n_tasks, k_max), -1,
                          np.int32)
        for i, p in enumerate(pars):
            parents[i, :, :p.shape[1]] = p
        reps = Replicas(
            _stack(tts), np.stack(mts).astype(np.int32),
            _stack(tabs), np.asarray(pids, np.int32), _stack(dyns),
            parents)
    return reps


def normalize(spec: ExperimentSpec) -> Replicas:
    """Materialize the spec's grid into one stacked :class:`Replicas`
    pytree — the normalization pass of the pipeline (padding parent
    tables, pairing policy grids, materializing dynamics traces).

    Every leaf is a numpy array built on the host: normalize starts no
    device work, so it can run beside a computation on the device, and
    the caller places the result once (``jax.device_put``, one transfer
    per leaf, straight to the shards under a sharding)."""
    if spec.workflow:
        return _materialize_workflow(spec)
    return _materialize_flat(spec)


def normalize_chunk(spec: ExperimentSpec, lo: int, hi: int) -> Replicas:
    """Materialize replicas ``[lo, hi)`` of the grid — bitwise-identical
    to slicing :func:`normalize`'s output, without drawing the other
    replicas (per-replica/per-cell RNG substreams make the grid
    random-access; launch/chunked.py normalizes one chunk at a time).
    Host leaves, as :func:`normalize`.
    """
    if not (0 <= lo < hi <= spec.n_replicas):
        raise ValueError(f"chunk [{lo}, {hi}) outside grid "
                         f"[0, {spec.n_replicas})")
    if spec.workflow:
        return _materialize_workflow(spec, lo, hi,
                                     k_max=_workflow_kmax(spec))
    return _materialize_flat(spec, lo, hi)


# ---------------------------------------------------------------------------
# compile: one cached executable per SimParams
# ---------------------------------------------------------------------------
_EXEC_CACHE: dict[E.SimParams, Any] = {}
_CACHE_STATS = {"hits": 0, "misses": 0, "retraces": 0}


#: the checkout's own cache directory, fixed by this file's location (the
#: path is part of jax's cache key, so it must not follow the cwd)
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, "results", "jax_cache"))


def persistent_cache_dir() -> str | None:
    """The configured ``jax_compilation_cache_dir`` (None = disabled)."""
    return jax.config.jax_compilation_cache_dir


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Compiled executables (every ``compile_sweep`` specialization, the
    streaming twin, the chunked driver) are serialized to disk and
    reloaded by later *processes*: a bench re-run pays jax's trace time
    but skips the XLA compile (docs/experiments.md §Compilation cache).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it:
    that directory is returned and no other is set.  Otherwise the
    cache goes to :data:`DEFAULT_CACHE_DIR` (``results/jax_cache`` under
    the checkout) with the size/time thresholds zeroed, so every sweep is
    cached.  Either way the cache key takes in the HLO metadata: an
    executable of another build of the program, whose ``op_name``\ s
    (the engine's phase scopes) differ, is never loaded in its place.
    A failure to create or configure it raises.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache every entry: the sweeps worth caching are small but many
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


def _count_retrace(vf):
    """Wrap a vmapped sweep so every *trace* of the jitted callable bumps
    ``_CACHE_STATS["retraces"]`` — the body only runs at trace time, so
    the counter distinguishes jax's trace-cache hits (free re-runs) from
    shape/structure-triggered retraces (bench check T8's failure mode,
    now observable via :func:`cache_stats` and the telemetry log)."""
    def traced(*args):
        _CACHE_STATS["retraces"] += 1
        return vf(*args)
    return traced


def compile_sweep(params: E.SimParams = E.SimParams()):
    """-> the canonical jitted sweep for ``params``, cached process-wide.

    Signature (leading replica axis on the first six args;
    ``policy_params`` is shared across replicas)::

        f(tasks, mtype, tables, policy_ids, dynamics, parents,
          policy_params) -> metrics            # params.trace=False
                         -> (metrics, traces)  # params.trace=True

    Optional inputs are passed as ``None`` — an empty pytree under
    ``vmap``/``jit``, so jax compiles the corresponding engine feature
    out and caches one specialization per input *structure and shape*
    inside this single callable.  That is the whole executable cache:
    every spec with the same ``SimParams`` shares this function, and a
    same-shape re-run is a dictionary hit plus jax's own trace-cache hit
    (bench check T8 pins >= 5x).
    """
    fn = _EXEC_CACHE.get(params)
    if fn is not None:
        _CACHE_STATS["hits"] += 1
        return fn
    _CACHE_STATS["misses"] += 1
    TL.event("compile_sweep_miss", params=str(params),
             persistent_cache_dir=persistent_cache_dir())

    def one(tasks, mtype, tables, pid, dyn, par, pp):
        st = E.run_sim(tasks, mtype, tables, pid, params, dyn, pp, par)
        m = summarize_replica(st, tables, dyn)
        if params.metrics:
            m.update(_tail_columns(st.metrics))
        return (m, st.trace) if params.trace else m

    fn = jax.jit(_count_retrace(
        jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, None))))
    _EXEC_CACHE[params] = fn
    return fn


def compile_stream_sweep(params):
    """Streaming twin of :func:`compile_sweep`: one cached vmapped
    executable per :class:`streaming.StreamParams`, sharing
    ``_EXEC_CACHE`` (both key types are NamedTuples, so dense and
    streaming specs coexist in one cache and T8's re-run economics apply
    unchanged).

    Signature (leading replica axis on all but ``policy_params``)::

        f(stream, mtype, eet, power, policy_ids, dynamics,
          policy_params) -> metrics            # params.trace=False
                         -> (metrics, traces)  # params.trace=True

    ``stream`` is a :class:`streaming.TaskStream` with ``(R, nc, C)``
    leaves (:func:`to_streams`); metrics carry the same keys as
    :func:`summarize_replica`, computed from the running aggregates.
    """
    from repro.core import streaming as ST
    fn = _EXEC_CACHE.get(params)
    if fn is not None:
        _CACHE_STATS["hits"] += 1
        return fn
    _CACHE_STATS["misses"] += 1

    def one(stream, mtype, eet, power, pid, dyn, pp):
        ws = ST.run_stream(stream, mtype, eet, power, pid, params,
                           dyn, pp)
        n = jnp.sum(stream.gid >= 0)
        m = ST.summarize_stream_replica(ws, n, dyn)
        if params.metrics:
            m.update(_tail_columns(ws.agg.metrics))
        return (m, ws.sim.trace) if params.trace else m

    fn = jax.jit(_count_retrace(
        jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, None))))
    _EXEC_CACHE[params] = fn
    return fn


def to_streams(reps: Replicas, chunk: int):
    """Repack stacked ``(R, N)`` replica columns as ``(R, nc, C)``
    :class:`streaming.TaskStream` columns of numpy arrays (the batch
    analogue of ``streaming.make_stream``; per-task noise rides in the
    stream, the tail chunk pads with inert ``gid = -1`` rows)."""
    from repro.core import streaming as ST
    if reps.parents is not None:
        raise ValueError("streaming replicas cannot carry parent tables")
    n = int(reps.tasks.arrival.shape[1])
    r = int(reps.tasks.arrival.shape[0])
    chunk = int(chunk)
    n_chunks = max(-(-n // chunk), 1)
    total = n_chunks * chunk

    def pad(x, fill):
        x = np.asarray(x)
        out = np.full((r, total), fill, x.dtype)
        out[:, :n] = x
        return out.reshape(r, n_chunks, chunk)

    gid = np.full((total,), -1, np.int32)
    gid[:n] = np.arange(n, dtype=np.int32)
    gid = np.tile(gid.reshape(1, n_chunks, chunk), (r, 1, 1))
    return ST.TaskStream(
        arrival=pad(reps.tasks.arrival, np.inf),
        type_id=pad(reps.tasks.type_id, 0),
        deadline=pad(reps.tasks.deadline, np.inf),
        noise=pad(reps.tables.noise, 1.0),
        rank=pad(reps.tables.rank, 0.0),
        gid=gid,
    )


def sweep_args(spec: ExperimentSpec, reps: Replicas) -> tuple:
    """The spec's executable inputs from ``reps``, all but
    ``policy_params``: the dense sweep takes the replicas' columns as
    they are, the streaming sweep the task columns as
    :func:`to_streams` repacks them."""
    if spec.streaming:
        return (to_streams(reps, spec.stream_chunk), reps.mtype,
                reps.tables.eet, reps.tables.power, reps.policy_ids,
                reps.dynamics)
    return (reps.tasks, reps.mtype, reps.tables, reps.policy_ids,
            reps.dynamics, reps.parents)


def h2d(tree) -> dict:
    """What one ``jax.device_put`` of ``tree`` moves from the host: one
    transfer per leaf not yet on a device (``transfers``), and their
    bytes (``h2d_bytes``) — the ``stack`` span's attributes."""
    host = [x for x in jax.tree.leaves(tree) if not isinstance(x, jax.Array)]
    return {"transfers": len(host),
            "h2d_bytes": sum(np.asarray(x).nbytes for x in host)}


def compile_experiment(spec: ExperimentSpec):
    """Spec-level view of :func:`compile_sweep` (folds the trace flag);
    streaming specs route to :func:`compile_stream_sweep`."""
    if spec.streaming:
        return compile_stream_sweep(spec.stream_params)
    return compile_sweep(spec.sim_params)


def cache_stats() -> dict:
    """Executable-cache counters: {hits, misses, retraces, size}.

    ``retraces`` counts actual jax traces of cached callables (shape /
    structure specializations); a dictionary hit that also hits jax's
    trace cache leaves it unchanged."""
    return dict(_CACHE_STATS, size=len(_EXEC_CACHE))


def clear_cache() -> None:
    _EXEC_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0, retraces=0)


# ---------------------------------------------------------------------------
# execute: normalize + compile + (optionally sharded) run
# ---------------------------------------------------------------------------
@dataclass
class ExperimentResult:
    """Output bundle of :func:`run_experiment`.

    Chunked runs (``chunk=``) carry the device-reduced
    ``launch/chunked.py::SweepAgg`` in ``agg`` (plus driver timing in
    ``chunked``); ``replicas``/``metrics`` are then ``None`` unless
    ``keep_replicas=True`` stacked host copies of the per-replica
    metrics back together.  Otherwise ``replicas`` holds the inputs as
    :func:`normalize` built them (host leaves) or as the caller passed
    them."""
    spec: ExperimentSpec
    replicas: Replicas | None
    metrics: dict | None
    traces: Any = None
    agg: Any = None
    chunked: Any = None

    def by_policy(self, keys: tuple[str, ...] = ("completion_rate",
                                                 "missed", "energy",
                                                 "makespan")) -> list[dict]:
        """Per-policy mean rows (host-side), in spec policy order.

        Chunked results read the rows off the on-device aggregate
        (exact means); monolithic results average the per-replica
        columns as before."""
        if self.agg is not None:
            return self.agg.by_policy(keys)
        pids = np.asarray(self.replicas.policy_ids)
        rows = []
        for pol in self.spec.policy.policies:
            sel = pids == P.POLICY_IDS[pol]
            row = {"policy": pol, "replicas": int(sel.sum())}
            for k in keys:
                row[k] = float(np.mean(np.asarray(self.metrics[k])[sel]))
            rows.append(row)
        return rows


def run_experiment(spec: ExperimentSpec, *, mesh=None, policy_params=None,
                   replicas: Replicas | None = None,
                   chunk: int | None = None,
                   keep_replicas: bool = False,
                   on_chunk=None) -> ExperimentResult:
    """The one-call pipeline: normalize -> compile (cached) -> execute.

    ``mesh`` (a ``jax.sharding.Mesh``) shards the replica axis over
    every mesh axis jointly (``launch/mesh.py::replica_sharding``);
    ``n_replicas`` must divide the device count.  ``policy_params``
    supplies shared learned-policy weights (``learned=True`` specs).
    ``replicas`` short-circuits normalization when the caller already
    materialized inputs (e.g. to re-run a grid under a different policy
    column).

    ``chunk=C`` switches to the pod-scale path (``launch/chunked.py``,
    docs/scaling.md): the grid runs C replicas at a time with donated
    device buffers and an on-device ``SweepAgg`` reduction, normalize
    overlapped with device compute — peak memory O(C) instead of O(R),
    aggregates bitwise-equal to the monolithic path.  ``keep_replicas``
    additionally stacks host copies of the per-replica metrics;
    ``on_chunk(c)`` fires as each chunk retires.

    When telemetry is enabled (``repro.core.telemetry``), each stage
    emits a span — normalize/compile/execute wall times, replica counts,
    executable-cache counters, device and mesh info — under one parent
    ``experiment`` span (docs/observability.md).  The spans are
    ``e2c.<stage>`` annotations too, so wrapping the call in
    ``jax.profiler.trace`` shows them beside the device ops.
    """
    if chunk is not None:
        from repro.launch.chunked import run_chunked_experiment
        return run_chunked_experiment(
            spec, chunk, mesh=mesh, policy_params=policy_params,
            replicas=replicas, keep_replicas=keep_replicas,
            on_chunk=on_chunk)
    if keep_replicas or on_chunk is not None:
        raise ValueError("keep_replicas/on_chunk only apply with chunk=")
    reused = replicas is not None
    n_rep = replicas.n_replicas if reused else spec.n_replicas
    sharding = None
    if mesh is not None:
        from repro.launch.mesh import mesh_device_count, replica_sharding
        n_dev = mesh_device_count(mesh)
        if n_rep % n_dev:
            raise ValueError(f"n_replicas {n_rep} must divide "
                             f"over {n_dev} devices")
        sharding = replica_sharding(mesh)
    with TL.span("experiment", streaming=bool(spec.streaming),
                 policies=spec.policy.policies,
                 backend=jax.default_backend(),
                 devices=jax.device_count()) as xsp:
        with TL.span("normalize", reused=reused, n_replicas=n_rep):
            reps = replicas if reused else normalize(spec)
            with TL.span("stack") as ssp:
                args = sweep_args(spec, reps)
                ssp.update(h2d(args))
                args = jax.device_put(args, sharding)
        xsp["n_replicas"] = n_rep
        if mesh is not None:
            xsp["mesh"] = dict(getattr(mesh, "shape", {}) or {})
        with TL.span("compile") as csp:
            fn = compile_experiment(spec)
            csp.update(cache_stats())
            csp["persistent_cache_dir"] = persistent_cache_dir()
        with TL.span("execute") as esp:
            out = fn(*args, policy_params)
            # only force the sync when someone is timing the stage
            # (keeps the default path's async dispatch untouched)
            if TL.current() is not None:
                out = jax.block_until_ready(out)
            esp["retraces"] = _CACHE_STATS["retraces"]
        TL.event("cache", **cache_stats())
    # the executable's output shape follows the EFFECTIVE params (the
    # trace flag may also arrive via sim=SimParams(trace=True))
    metrics, traces = out if spec.sim_params.trace else (out, None)
    return ExperimentResult(spec=spec, replicas=reps, metrics=metrics,
                            traces=traces)
