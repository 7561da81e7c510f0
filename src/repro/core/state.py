"""Array-encoded simulation state for the E2C discrete-event engine.

The original E2C simulator keeps Python object queues (batch queue, per-machine
queues) mutated by a Qt event loop.  To make the simulator jit-able, vmappable
and shardable we re-encode the exact same lifecycle as fixed-shape arrays:

* the *batch queue* is the set of tasks with ``status == IN_BATCH`` (FIFO order
  is task-id order; workloads are sorted by arrival time),
* a *machine queue* is the set of tasks with ``status == IN_MQ`` and
  ``machine == m`` (service order is the mapping sequence number ``seq``),
* the *cancelled* / *missed* pools of the GUI are the terminal statuses.

Every E2C state transition becomes a masked vector update.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Task lifecycle (matches the E2C GUI components; see DESIGN.md table).
# ---------------------------------------------------------------------------
NOT_ARRIVED = 0      # generated but not yet in the system
IN_BATCH = 1         # waiting in the batch queue
IN_MQ = 2            # mapped: waiting in a machine's local queue
RUNNING = 3          # executing on a machine
COMPLETED = 4        # finished before its deadline
CANCELLED = 5        # scheduler cancelled (E2C "canceled tasks" pool)
MISSED_QUEUE = 6     # deadline expired while waiting (batch or machine queue)
MISSED_RUNNING = 7   # deadline expired while executing -> dropped from machine
PREEMPTED = 8        # killed by a machine failure / spot reclaim (kill mode)

NUM_STATUSES = 9
TERMINAL = (COMPLETED, CANCELLED, MISSED_QUEUE, MISSED_RUNNING, PREEMPTED)

INF = jnp.float32(jnp.inf)


def register_pytree(cls):
    """Register a dataclass as a JAX pytree (all fields are leaves)."""
    fields = [f.name for f in dataclasses.fields(cls)]

    def flatten(obj):
        return tuple(getattr(obj, n) for n in fields), None

    def unflatten(_, leaves):
        return cls(*leaves)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@register_pytree
@dataclasses.dataclass
class TaskTable:
    """One row per task (fixed N; pad with NOT_ARRIVED + arrival=inf)."""

    arrival: jnp.ndarray    # f32 (N,)
    type_id: jnp.ndarray    # i32 (N,)  row of the EET matrix
    deadline: jnp.ndarray   # f32 (N,)  absolute time
    status: jnp.ndarray     # i32 (N,)
    machine: jnp.ndarray    # i32 (N,)  assigned machine id, -1 if unmapped
    seq: jnp.ndarray        # i32 (N,)  mapping sequence number (queue order)
    t_start: jnp.ndarray    # f32 (N,)  execution start time (-1 if never ran)
    t_end: jnp.ndarray      # f32 (N,)  terminal time (-1 while live)


@register_pytree
@dataclasses.dataclass
class MachineState:
    """One row per machine.

    ``speed``/``power_scale`` are the machine's DVFS operating point,
    copied from :class:`MachineDynamics` at init: execution time is
    ``EET / speed`` and both idle and active power are multiplied by
    ``power_scale``.  They live here (not only in the dynamics tables) so
    every engine phase can read them without threading the dynamics.
    """

    mtype: jnp.ndarray        # i32 (M,)  row of the power table / EET column
    running: jnp.ndarray      # i32 (M,)  task id currently executing, -1 idle
    busy_until: jnp.ndarray   # f32 (M,)  completion time of `running`
    active_time: jnp.ndarray  # f32 (M,)  accumulated execution seconds
    energy: jnp.ndarray       # f32 (M,)  accumulated *active* energy (J)
    speed: jnp.ndarray        # f32 (M,)  DVFS speed multiplier (EET /= speed)
    power_scale: jnp.ndarray  # f32 (M,)  DVFS power multiplier


@register_pytree
@dataclasses.dataclass
class MachineDynamics:
    """Dynamic-scenario description of the fleet (fixed shape, vmappable).

    Availability is a trace of up to K down-intervals per machine
    (``down_start[m, k] <= t < down_end[m, k]`` means machine ``m`` is
    unavailable at ``t``); pad unused intervals with ``inf``.  A down
    transition preempts the running task and flushes the machine queue:
    with ``kill[m]`` the evicted tasks go to the terminal ``PREEMPTED``
    pool (spot reclaim), otherwise they are requeued to the batch queue
    and restart from scratch (fail/repair).  Partial energy for the work
    already done is charged either way.

    ``speed``/``power_scale`` are per-machine DVFS multipliers applied to
    the EET rows and to idle/active power respectively.
    """

    speed: jnp.ndarray        # f32 (M,)  execution-speed multiplier
    power_scale: jnp.ndarray  # f32 (M,)  idle/active power multiplier
    down_start: jnp.ndarray   # f32 (M, K) interval starts (inf = unused)
    down_end: jnp.ndarray     # f32 (M, K) interval ends   (inf = open/unused)
    kill: jnp.ndarray         # bool (M,) True: evictions kill, else requeue


def static_dynamics(n_machines: int, n_intervals: int = 1) -> MachineDynamics:
    """A no-op scenario: full speed, nominal power, never down."""
    return MachineDynamics(
        speed=jnp.ones((n_machines,), jnp.float32),
        power_scale=jnp.ones((n_machines,), jnp.float32),
        down_start=jnp.full((n_machines, n_intervals), jnp.inf, jnp.float32),
        down_end=jnp.full((n_machines, n_intervals), jnp.inf, jnp.float32),
        kill=jnp.zeros((n_machines,), bool),
    )


def machine_up(dyn: MachineDynamics, t: jnp.ndarray) -> jnp.ndarray:
    """(M,) bool: machine available (not inside any down interval) at t."""
    down = (dyn.down_start <= t) & (t < dyn.down_end)
    return ~jnp.any(down, axis=-1)


@register_pytree
@dataclasses.dataclass
class SimState:
    """Full simulator state threaded through ``lax.while_loop``."""

    time: jnp.ndarray        # f32 ()  current simulation time
    tasks: TaskTable
    machines: MachineState
    seq_counter: jnp.ndarray  # i32 () next mapping sequence number
    rr_ptr: jnp.ndarray       # i32 () round-robin machine pointer
    n_events: jnp.ndarray     # i32 () processed event count (guard/telemetry)
    n_preempts: jnp.ndarray   # i32 (N,) forced evictions per task (running
    #                           or queued on a machine that went down)
    mq_count: jnp.ndarray     # i32 (M,) tasks waiting per machine queue —
    #                           incrementally maintained (exact int math),
    #                           replaces an O(N*M) recount per drain step
    trace: Any = None         # trace.TraceBuffer when SimParams.trace is
    #                           on, else None (tracing compiles out; the
    #                           engine gates recording on a Python check)
    deps_left: Any = None     # i32 (N,) remaining unfinished parents per
    #                           task (workflow mode; None = independent
    #                           tasks, which compiles the pre-DAG HLO —
    #                           gated on a Python-level None check like
    #                           `trace`).  Maintained by the engine's
    #                           dependency-release phase; a task may only
    #                           arrive once its counter reaches zero.
    metrics: Any = None       # metrics.SimMetrics when SimParams.metrics
    #                           is on, else None (instruments compile
    #                           out; same Python-level gate as `trace`)
    n_batch: Any = None       # i32 () batch-queue population (status ==
    #                           IN_BATCH) — incrementally maintained at
    #                           every mutation point (exact int math, like
    #                           mq_count); replaces the O(N) status scans
    #                           in _arrivals and _drain's trip bound
    n_live: Any = None        # i32 () non-terminal population (status <
    #                           COMPLETED) — the event loop's `cond` reads
    #                           this scalar instead of reducing the full
    #                           status column every trip
    ready_at: Any = None      # f32 (N,) staging-release time per task
    #                           (edge-list workflow mode, else None): the
    #                           latest ``t_end[src] + delay`` over the
    #                           task's in-edges, -inf without parents.
    #                           Set by the release phase; a released task
    #                           arrives once it is <= the event time


@register_pytree
@dataclasses.dataclass
class StaticTables:
    """Read-only problem description (still traced so it can be vmapped)."""

    eet: jnp.ndarray        # f32 (T_types, M_types) expected execution times
    power: jnp.ndarray      # f32 (M_types, 2) [idle_W, active_W]
    noise: jnp.ndarray      # f32 (N,) multiplicative actual/expected exec time
    rank: jnp.ndarray       # f32 (N,) HEFT upward rank per task (zeros for
    #                         independent workloads; precomputed host-side
    #                         by workload.upward_ranks and consumed by the
    #                         `heft` policy through SchedView.rank)


@register_pytree
@dataclasses.dataclass
class Edges:
    """Workflow precedence as an edge list, sorted by child (CSR order).

    Edge ``e`` runs from parent ``src[e]`` to child ``dst[e]`` and holds
    the staging delay ``delay[e]`` (the parent's output file over the
    shared storage's bandwidth).  The in-edges of task ``i`` are
    ``[end[i-1], end[i])`` (``end[-1]`` taken as 0).  Edges past
    ``end[N-1]`` pad the list to a grid's widest edge count and lie in no
    task's range (``src = -1``, ``dst = N``, ``delay = 0``).
    """

    src: jnp.ndarray        # i32 (E,) parent of each edge, -1 = padding
    dst: jnp.ndarray        # i32 (E,) child, nondecreasing; N = padding
    delay: jnp.ndarray      # f32 (E,) staging delay, simulated time units
    pos: jnp.ndarray        # i32 (E,) the edge's index in its task's range
    end: jnp.ndarray        # i32 (N,) one past each task's last in-edge


def pad_edges(e: Edges, width: int) -> Edges:
    """``e`` (numpy leaves) padded to ``width`` edges that lie in no
    task's range — the shape a grid of DAGs shares."""
    pad = width - e.src.shape[0]
    if pad < 0:
        raise ValueError(f"width {width} < {e.src.shape[0]} edges")
    n = e.end.shape[0]
    return Edges(src=np.concatenate([e.src, np.full(pad, -1, np.int32)]),
                 dst=np.concatenate([e.dst, np.full(pad, n, np.int32)]),
                 delay=np.concatenate([e.delay, np.zeros(pad, np.float32)]),
                 pos=np.concatenate([e.pos, np.zeros(pad, np.int32)]),
                 end=e.end)


def _seg_scan(x: jnp.ndarray, pos: jnp.ndarray, op, fill) -> jnp.ndarray:
    """Inclusive scan of ``op`` along the last axis within each CSR
    range (``pos[e]``: edge ``e``'s index in its task's range): log2(E)
    steps, each a shift by a power of two merged where it stays in the
    range (Hillis–Steele); contiguous slices only, no scatter."""
    width = x.shape[-1]
    s = 1
    while s < width:
        shifted = jnp.concatenate(
            [jnp.full(x.shape[:-1] + (s,), fill, x.dtype), x[..., :-s]], -1)
        x = jnp.where(pos >= s, op(x, shifted), x)
        s *= 2
    return x


def _seg_last(x: jnp.ndarray, end: jnp.ndarray, fill) -> jnp.ndarray:
    """Each task's value at its last in-edge (``fill`` without one)."""
    start = jnp.concatenate([jnp.zeros((1,), end.dtype), end[:-1]])
    last = jnp.clip(end - 1, 0, x.shape[-1] - 1)
    return jnp.where(end > start, x[..., last], fill)


def dep_state(status: jnp.ndarray, parents
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-task dependency summary from the current status column.

    ``parents`` is an :class:`Edges` list (log2(E) steps of O(E)) or,
    for the streaming window, a fixed-width (N, K) parent table padded
    with -1.
    Returns ``(left, failed)``: ``left[i]`` counts parents of ``i`` not
    yet in a terminal state (the remaining-parents counter), and
    ``failed[i]`` is True when some parent terminated without completing
    (cancelled / missed / preempted) — such a task can never run and is
    cancelled by the engine's release phase.
    """
    n = status.shape[0]
    if isinstance(parents, Edges):
        valid = parents.src >= 0
        ps = status[jnp.clip(parents.src, 0, n - 1)]   # (E,)
        term = valid & (ps >= COMPLETED)
        counts = jnp.stack([(valid & ~term).astype(jnp.int32),
                            (term & (ps != COMPLETED)).astype(jnp.int32)])
        counts = _seg_scan(counts, parents.pos, jnp.add, 0)
        left, n_failed = _seg_last(counts, parents.end, 0)
        return left, n_failed > 0
    valid = parents >= 0
    ps = status[jnp.clip(parents, 0, n - 1)]          # (N, K)
    term = valid & (ps >= COMPLETED)
    left = jnp.sum(valid & ~term, axis=1).astype(jnp.int32)
    failed = jnp.any(term & (ps != COMPLETED), axis=1)
    return left, failed


def ready_times(t_end: jnp.ndarray, edges: Edges) -> jnp.ndarray:
    """(N,) the latest ``t_end[src] + delay`` over each task's in-edges
    (-inf without any): when its last input file has been staged.  Only
    meaningful where every parent completed.  A max does not difference,
    so it is a segmented max scan over the dst-sorted edges, read at
    each task's last edge."""
    n = t_end.shape[0]
    v = jnp.where(edges.src >= 0,
                  t_end[jnp.clip(edges.src, 0, n - 1)] + edges.delay,
                  -INF)
    return _seg_last(_seg_scan(v, edges.pos, jnp.maximum, -INF),
                     edges.end, -INF)


def in_degree(parents) -> jnp.ndarray:
    """(N,) i32 number of parents of each task."""
    if isinstance(parents, Edges):
        start = jnp.concatenate([jnp.zeros((1,), parents.end.dtype),
                                 parents.end[:-1]])
        return (parents.end - start).astype(jnp.int32)
    return jnp.sum(parents >= 0, axis=1).astype(jnp.int32)


def init_state(tasks: TaskTable, mtype: jnp.ndarray,
               dynamics: MachineDynamics | None = None,
               parents: jnp.ndarray | None = None) -> SimState:
    n = tasks.arrival.shape[0]
    m = mtype.shape[0]
    if dynamics is None:
        speed = jnp.ones((m,), jnp.float32)
        power_scale = jnp.ones((m,), jnp.float32)
    else:
        speed = dynamics.speed.astype(jnp.float32)
        power_scale = dynamics.power_scale.astype(jnp.float32)
    machines = MachineState(
        mtype=mtype.astype(jnp.int32),
        running=jnp.full((m,), -1, jnp.int32),
        busy_until=jnp.zeros((m,), jnp.float32),
        active_time=jnp.zeros((m,), jnp.float32),
        energy=jnp.zeros((m,), jnp.float32),
        speed=speed,
        power_scale=power_scale,
    )
    tasks = TaskTable(
        arrival=tasks.arrival.astype(jnp.float32),
        type_id=tasks.type_id.astype(jnp.int32),
        deadline=tasks.deadline.astype(jnp.float32),
        status=jnp.full((n,), NOT_ARRIVED, jnp.int32),
        machine=jnp.full((n,), -1, jnp.int32),
        seq=jnp.full((n,), jnp.iinfo(jnp.int32).max, jnp.int32),
        t_start=jnp.full((n,), -1.0, jnp.float32),
        t_end=jnp.full((n,), -1.0, jnp.float32),
    )
    deps_left = ready_at = None
    if parents is not None:
        deps_left = in_degree(parents)
    if isinstance(parents, Edges):
        ready_at = jnp.full((n,), -INF, jnp.float32)
    return SimState(
        time=jnp.float32(0.0),
        tasks=tasks,
        machines=machines,
        seq_counter=jnp.int32(0),
        rr_ptr=jnp.int32(0),
        n_events=jnp.int32(0),
        n_preempts=jnp.zeros((n,), jnp.int32),
        mq_count=jnp.zeros((m,), jnp.int32),
        deps_left=deps_left,
        n_batch=jnp.int32(0),
        n_live=jnp.int32(n),
        ready_at=ready_at,
    )


def is_terminal(status: jnp.ndarray) -> jnp.ndarray:
    return status >= COMPLETED


def exec_time(tables: StaticTables, tasks: TaskTable, task_id: jnp.ndarray,
              mtype: jnp.ndarray,
              speed: jnp.ndarray | float = 1.0) -> jnp.ndarray:
    """Actual execution time of `task_id` on a machine of type `mtype`
    running at DVFS `speed` (EET scaled by 1/speed)."""
    ttype = tasks.type_id[task_id]
    return tables.eet[ttype, mtype] * tables.noise[task_id] / speed


def queue_count(tasks: TaskTable, m: int | jnp.ndarray) -> jnp.ndarray:
    return jnp.sum((tasks.status == IN_MQ) & (tasks.machine == m))


def queue_counts(tasks: TaskTable, n_machines: int) -> jnp.ndarray:
    """(M,) number of tasks waiting in each machine queue."""
    onehot = (tasks.status == IN_MQ)[:, None] & (
        tasks.machine[:, None] == jnp.arange(n_machines)[None, :])
    return jnp.sum(onehot, axis=0).astype(jnp.int32)


def queued_work(tasks: TaskTable, tables: StaticTables,
                machines: MachineState) -> jnp.ndarray:
    """(M,) total *expected* work waiting in each machine's queue.

    Deliberately uses EET (not noise-adjusted actual times): the scheduler
    only knows expectations, as in E2C.  The DVFS speed IS known to the
    system, so expectations are scaled by it.
    """
    n_machines = machines.mtype.shape[0]
    per_task = tables.eet[tasks.type_id[:, None], machines.mtype[None, :]] \
        / machines.speed[None, :]
    mask = (tasks.status == IN_MQ)[:, None] & (
        tasks.machine[:, None] == jnp.arange(n_machines)[None, :])
    return jnp.sum(jnp.where(mask, per_task, 0.0), axis=0)


def machine_available(state: SimState, tables: StaticTables) -> jnp.ndarray:
    """(M,) earliest time each machine could start a *new* task."""
    mach = state.machines
    base = jnp.maximum(state.time, jnp.where(mach.running >= 0,
                                             mach.busy_until, state.time))
    return base + queued_work(state.tasks, tables, mach)
