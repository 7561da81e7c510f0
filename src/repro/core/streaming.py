"""Streaming arrivals: the bounded-memory live-task window engine.

The dense engine (``core/engine.py``) sizes every per-task array by the
total task count N — one ``(N, M)`` EET matrix per drain step is the
memory wall ROADMAP item 1 calls out.  This module restructures the
event loop around a fixed-capacity **live-task window**: W in-flight
task slots (W static, N unbounded), refilled from arrival chunks via
``lax.scan``, with all of ``report.summarize``'s metrics aggregated
*streamingly* when a slot retires.  Per-event cost depends on W and M
only, never on N.

Design invariants (what the parity/property battery in
``tests/test_streaming.py`` locks down):

* **Slots are kept compacted in global-task-id order.**  After every
  refill the window is stably sorted by the global id (``slot_task``) of
  the task each slot holds.  The dense phase functions therefore apply
  *verbatim* to the (W,)-shaped state, and every order-sensitive
  semantic — FCFS head-of-queue, argmin index tie-breaks, cumsum
  admission ranks, trace emission order — matches the dense engine
  exactly.  For N <= W the two engines are equivalent final-state
  bit-for-bit; results are independent of the chunk size and of W
  (for any W that covers the maximum concurrent liveness).
* **Loading is eager and strictly in stream order.**  Free slots are
  refilled before each event, never-used slots first (so retired rows
  keep their data for final-state extraction when N <= W); the loaded
  set is always a prefix of the stream.  An event runs only when the
  window is full while stream tasks are still pending, or in the final
  drain after the stream is exhausted.
* **Time never runs backwards.**  A task loaded after its arrival time
  has passed (window overflow = pure admission delay) is admitted at
  the current simulation time: ``t = max(next_event, now)``.  The clamp
  is a no-op whenever N <= W, because the dense engine admits every
  ripe arrival within the event that ripens it.
* **A slot retires only when nothing can still read it.**  Retirement
  (terminal status, plus — in workflow mode — all children loaded and
  no loaded child still dependency-blocked) is the aggregation point:
  the slot's metrics fold into the running :class:`StreamAgg` and the
  slot becomes reusable.  Parents are resolved through a
  slot-indirection table (``pslot``), valid for DAGs whose dependency
  frontier fits the window (docs/streaming.md discusses the caveat).

Tracing works unchanged: phases record slot ids, which are rewritten to
global ids immediately after each event (before any refill can recycle
the mapping), so the emitted stream equals the dense engine's for
N <= W and the streaming reference mirror's otherwise.
"""
from __future__ import annotations

import dataclasses
from dataclasses import replace
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as EN
from repro.core import engine as E
from repro.core import metrics as ME
from repro.core import neural as NN
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import trace as T
from repro.core.eet import EETTable
from repro.core.workload import Workload

INT_MAX = jnp.iinfo(jnp.int32).max


class StreamParams(NamedTuple):
    """Static (compile-time) parameters of the streaming engine.

    ``window`` is W, the live-task slot count — the only N-independent
    memory knob.  The rest mirror :class:`engine.SimParams`.
    """
    window: int
    lcap: int = 4
    qcap: int = 1 << 30
    cancel_infeasible: bool = True
    max_events: int | None = None
    trace: bool = False
    trace_capacity: int | None = None
    pallas: bool = False          # fused dispatch + event kernels
    #                               (docs/kernels.md)
    metrics: bool = False         # in-jit histograms + SLO windows folded
    #                               into StreamAgg (docs/observability.md)
    metrics_spec: ME.MetricsSpec | None = None
    drain_k: int = 1              # speculative drain width (the window
    #                               runs the dense drain loop verbatim —
    #                               docs/engine_perf.md)

    def sim_params(self) -> E.SimParams:
        """The dense-engine view (phases read lcap/qcap/cancel from it;
        metrics accumulation is the *window* engine's job — per-slot
        folds at retirement — so it is not forwarded here)."""
        return E.SimParams(lcap=self.lcap, qcap=self.qcap,
                           cancel_infeasible=self.cancel_infeasible,
                           pallas=self.pallas, drain_k=self.drain_k)


class TaskStream(NamedTuple):
    """The workload as arrival-ordered chunks: every leaf is
    ``(n_chunks, chunk)`` (+ trailing K for parents), padded with
    ``gid = -1`` rows.  ``gid`` is the global task id; ids must be
    nondecreasing along the flattened stream (Workload sorts by arrival,
    Workflow ids are a topological order with nondecreasing arrivals)."""
    arrival: jnp.ndarray        # f32 (nc, C)
    type_id: jnp.ndarray        # i32 (nc, C)
    deadline: jnp.ndarray       # f32 (nc, C)
    noise: jnp.ndarray          # f32 (nc, C)
    rank: jnp.ndarray           # f32 (nc, C)  HEFT upward rank
    gid: jnp.ndarray            # i32 (nc, C)  global id, -1 = padding
    parents: Any = None         # i32 (nc, C, K) global parent ids, -1 pad
    n_children: Any = None      # i32 (nc, C)  out-degree per task


class StreamAgg(NamedTuple):
    """Running aggregates folded in at slot retirement — everything
    ``report.summarize`` needs, in O(1) memory."""
    retired: jnp.ndarray        # i32  slots retired (== N when done)
    completed: jnp.ndarray      # i32
    cancelled: jnp.ndarray      # i32
    missed_queue: jnp.ndarray   # i32
    missed_running: jnp.ndarray  # i32
    preempted: jnp.ndarray      # i32
    evictions: jnp.ndarray      # i32  total forced evictions (n_preempts)
    n_started: jnp.ndarray      # i32  tasks that ever started executing
    sum_response: jnp.ndarray   # f32  sum of t_end - arrival (completed)
    sum_wait: jnp.ndarray       # f32  sum of t_start - arrival (started)
    makespan: jnp.ndarray       # f32  max terminal time seen (>= 0)
    metrics: Any = None         # metrics.SimMetrics with
    #                             StreamParams(metrics=True): histograms +
    #                             SLO windows folded per retiring slot —
    #                             O(buckets) memory however large N grows
    #                             (None compiles out, like SimState.trace)


def _init_agg() -> StreamAgg:
    z = jnp.int32(0)
    f = jnp.float32(0.0)
    return StreamAgg(retired=z, completed=z, cancelled=z, missed_queue=z,
                     missed_running=z, preempted=z, evictions=z,
                     n_started=z, sum_response=f, sum_wait=f, makespan=f)


@S.register_pytree
@dataclasses.dataclass
class WindowState:
    """The scan/while carry: a W-slot ``SimState`` plus window metadata.

    ``sim.tasks`` (and ``n_preempts`` / ``deps_left`` / ``wtab.noise`` /
    ``wtab.rank``) are (W,)-shaped; the dense phase functions run on
    them unmodified.  ``slot_task[j]`` is the global id of the task slot
    ``j`` holds (-1 = never used); ``retired[j]`` marks a slot whose
    metrics are already aggregated and which may be recycled.
    """
    sim: S.SimState             # W-shaped simulator state
    wtab: S.StaticTables        # eet/power global; noise/rank per-slot (W,)
    slot_task: jnp.ndarray      # i32 (W,) global id per slot, -1 never used
    retired: jnp.ndarray        # bool (W,) aggregated & recyclable
    cursor: jnp.ndarray         # i32 () consumed rows of the active chunk
    agg: StreamAgg
    children_unloaded: Any = None   # i32 (W,) children not yet loaded
    pslot: Any = None               # i32 (W, K) parents as slot indices


# ---------------------------------------------------------------------------
# Window phases: retire -> refill -> compact (then the dense event phases)
# ---------------------------------------------------------------------------
@jax.named_scope("retire")
def _retire(ws: WindowState) -> WindowState:
    """Fold terminal slots into the running aggregates and free them.

    Workflow mode gates on the dependency frontier: a parent slot stays
    resident until every child has been loaded (``children_unloaded``)
    and every loaded child has left NOT_ARRIVED — children read the
    parent's terminal status through ``pslot`` until they arrive or are
    cascade-cancelled.
    """
    st = ws.sim
    w = ws.slot_task.shape[0]
    ok = S.is_terminal(st.tasks.status) & ~ws.retired
    if ws.pslot is not None:
        child_live = (st.tasks.status == S.NOT_ARRIVED) & ~ws.retired
        pv = jnp.where(child_live[:, None] & (ws.pslot >= 0), ws.pslot, w)
        refs = jnp.zeros((w,), jnp.int32).at[pv.ravel()].add(1, mode="drop")
        ok = ok & (ws.children_unloaded == 0) & (refs == 0)
    status = st.tasks.status
    started = st.tasks.t_start >= 0
    done = status == S.COMPLETED
    a = ws.agg

    def cnt(pred):
        return jnp.sum(ok & pred).astype(jnp.int32)

    agg = StreamAgg(
        retired=a.retired + jnp.sum(ok).astype(jnp.int32),
        completed=a.completed + cnt(done),
        cancelled=a.cancelled + cnt(status == S.CANCELLED),
        missed_queue=a.missed_queue + cnt(status == S.MISSED_QUEUE),
        missed_running=a.missed_running + cnt(status == S.MISSED_RUNNING),
        preempted=a.preempted + cnt(status == S.PREEMPTED),
        evictions=a.evictions + jnp.sum(jnp.where(ok, st.n_preempts, 0)),
        n_started=a.n_started + cnt(started),
        sum_response=a.sum_response + jnp.sum(jnp.where(
            ok & done, st.tasks.t_end - st.tasks.arrival, 0.0)),
        sum_wait=a.sum_wait + jnp.sum(jnp.where(
            ok & started, st.tasks.t_start - st.tasks.arrival, 0.0)),
        makespan=jnp.maximum(a.makespan,
                             jnp.max(jnp.where(ok, st.tasks.t_end, 0.0))),
        metrics=a.metrics if a.metrics is None
        else ME.fold_tasks(a.metrics, st.tasks, mask=ok),
    )
    return dataclasses.replace(ws, retired=ws.retired | ok, agg=agg)


@jax.named_scope("refill")
def _refill(ws: WindowState, chunk: TaskStream,
            n_valid: jnp.ndarray) -> WindowState:
    """Load as many pending stream rows as there are free slots.

    Free slots are ranked never-used first, then retired-data (so a
    retired row is only overwritten once the fresh slots run out —
    preserving the full final task table whenever N <= W).  Rows are
    consumed strictly in stream order; the window is re-compacted to
    global-id order afterwards.
    """
    st = ws.sim
    w = ws.slot_task.shape[0]
    c = chunk.arrival.shape[0]
    free = ws.retired
    never = free & (ws.slot_task < 0)
    reuse = free & (ws.slot_task >= 0)
    n_free = jnp.sum(free).astype(jnp.int32)
    n_never = jnp.sum(never).astype(jnp.int32)
    load = jnp.minimum(n_free, jnp.maximum(n_valid - ws.cursor, 0))
    fr = jnp.where(never, jnp.cumsum(never.astype(jnp.int32)) - 1,
                   n_never + jnp.cumsum(reuse.astype(jnp.int32)) - 1)
    fr = jnp.where(free, fr, jnp.int32(w + c))
    do = free & (fr < load)
    take = jnp.clip(ws.cursor + fr, 0, c - 1)

    def ld(col, old):
        return jnp.where(do, col[take], old)

    tasks = replace(
        st.tasks,
        arrival=ld(chunk.arrival, st.tasks.arrival),
        type_id=ld(chunk.type_id, st.tasks.type_id),
        deadline=ld(chunk.deadline, st.tasks.deadline),
        status=jnp.where(do, S.NOT_ARRIVED, st.tasks.status),
        machine=jnp.where(do, -1, st.tasks.machine),
        seq=jnp.where(do, INT_MAX, st.tasks.seq),
        t_start=jnp.where(do, -1.0, st.tasks.t_start),
        t_end=jnp.where(do, -1.0, st.tasks.t_end),
    )
    wtab = replace(ws.wtab, noise=ld(chunk.noise, ws.wtab.noise),
                   rank=ld(chunk.rank, ws.wtab.rank))
    slot_task = jnp.where(do, chunk.gid[take], ws.slot_task)
    retired = ws.retired & ~do
    sim = replace(st, tasks=tasks,
                  n_preempts=jnp.where(do, 0, st.n_preempts),
                  # revived slots rejoin the live population (exact int)
                  n_live=st.n_live + jnp.sum(do, dtype=jnp.int32))

    cu, pslot = ws.children_unloaded, ws.pslot
    if pslot is not None:
        cu = jnp.where(do, chunk.n_children[take], cu)
        pg = jnp.where(do[:, None], chunk.parents[take], -1)   # (W, K) gids
        # gid -> slot through the post-load table: a parent loads before
        # its last child (topological ids, stream order) and cannot have
        # retired while children_unloaded > 0, so the match is total
        match = (slot_task[None, None, :] == pg[:, :, None]) \
            & (pg >= 0)[:, :, None] & (~retired)[None, None, :]
        found = match.any(axis=2)
        new_ps = jnp.where(found, jnp.argmax(match, axis=2),
                           -1).astype(jnp.int32)
        pslot = jnp.where(do[:, None], new_ps, pslot)
        dec = jnp.where(do[:, None] & found, new_ps, w)
        cu = cu.at[dec.ravel()].add(-1, mode="drop")
        sim = replace(sim, deps_left=jnp.where(
            do, jnp.sum(pg >= 0, axis=1).astype(jnp.int32), st.deps_left))
    return _compact(dataclasses.replace(
        ws, sim=sim, wtab=wtab, slot_task=slot_task, retired=retired,
        cursor=ws.cursor + load, children_unloaded=cu, pslot=pslot))


@jax.named_scope("compact")
def _compact(ws: WindowState) -> WindowState:
    """Stably sort slots by global task id (never-used slots last).

    This is what preserves every order-dependent semantic of the dense
    engine: after compaction, slot order == global-id order, so FCFS
    heads, argmin tie-breaks and cumsum admission ranks agree with the
    dense engine (N <= W) and the streaming reference mirror (overflow).
    ``machines.running`` and ``pslot`` hold slot indices, so their
    *values* are remapped through the inverse permutation; the trace is
    untouched (its rows are already globalized per event).
    """
    w = ws.slot_task.shape[0]
    key = jnp.where(ws.slot_task >= 0, ws.slot_task, INT_MAX)
    perm = jnp.argsort(key, stable=True)
    inv = jnp.zeros((w,), jnp.int32).at[perm].set(
        jnp.arange(w, dtype=jnp.int32))

    def g(x):
        return x[perm]

    st = ws.sim
    running = st.machines.running
    running = jnp.where(running >= 0, inv[jnp.clip(running, 0, w - 1)],
                        running)
    sim = replace(
        st,
        tasks=jax.tree.map(g, st.tasks),
        machines=replace(st.machines, running=running),
        n_preempts=g(st.n_preempts),
        deps_left=None if st.deps_left is None else g(st.deps_left),
    )
    wtab = replace(ws.wtab, noise=g(ws.wtab.noise), rank=g(ws.wtab.rank))
    pslot = ws.pslot
    if pslot is not None:
        pslot = pslot[perm]
        pslot = jnp.where(pslot >= 0, inv[jnp.clip(pslot, 0, w - 1)], pslot)
    return dataclasses.replace(
        ws, sim=sim, wtab=wtab, slot_task=g(ws.slot_task),
        retired=g(ws.retired),
        children_unloaded=None if ws.children_unloaded is None
        else g(ws.children_unloaded),
        pslot=pslot)


def _globalize_rows(tb: T.TraceBuffer, n0: jnp.ndarray,
                    slot_task: jnp.ndarray) -> T.TraceBuffer:
    """Rewrite slot ids to global ids in every trace row appended since
    ``n0`` (must run before the next refill recycles the mapping)."""
    w = slot_task.shape[0]
    idx = jnp.arange(tb.ev_task.shape[-1])
    tsk = tb.ev_task
    glob = jnp.where((tsk >= 0) & (tsk < w),
                     slot_task[jnp.clip(tsk, 0, w - 1)], tsk)
    return dataclasses.replace(tb, ev_task=jnp.where(idx >= n0, glob, tsk))


def _one_event(ws: WindowState, policy_id: jnp.ndarray,
               sparams: E.SimParams,
               dynamics: S.MachineDynamics | None,
               policy_params,
               transitions: jnp.ndarray | None = None) -> WindowState:
    """Process one event timestamp with the dense engine's six phases.

    Identical to ``engine.run_sim``'s loop body on (W,)-shaped state,
    except: the event time is clamped to be monotone (late-loaded
    arrivals admit *now* — a no-op whenever N <= W), the (W, M)
    expected-time/energy invariants are recomputed per event (slot
    contents change across refills), and trace rows/snapshots are
    globalized before the mapping can be recycled.
    """
    st = ws.sim
    w = ws.slot_task.shape[0]
    t = jnp.maximum(E._next_event_time(st, dynamics, ws.pslot, transitions,
                                       pallas=sparams.pallas), st.time)
    st = replace(st, time=t)
    n0 = None if st.trace is None else st.trace.n_rows
    st = E._completions(st, ws.wtab)
    up = None
    if dynamics is not None:
        st = E._availability(st, ws.wtab, dynamics)
        up = S.machine_up(dynamics, st.time)
    if ws.pslot is not None:
        st = E._release(st, ws.pslot)
    st = E._arrivals(st, sparams.qcap)
    st = E._deadline_drops(st, ws.wtab)
    mtype = st.machines.mtype
    eet_nm = ws.wtab.eet[st.tasks.type_id[:, None], mtype[None, :]] \
        / st.machines.speed[None, :]
    energy_nm = eet_nm * (ws.wtab.power[mtype, 1]
                          * st.machines.power_scale)[None, :]
    st = E._drain(st, ws.wtab, policy_id, sparams, (eet_nm, energy_nm),
                  up, policy_params)
    st = E._start_tasks(st, ws.wtab, up, pallas=sparams.pallas)
    if st.trace is not None:
        tb = _globalize_rows(st.trace, n0, ws.slot_task)
        run_g = jnp.where(st.machines.running >= 0,
                          ws.slot_task[jnp.clip(st.machines.running, 0,
                                                w - 1)],
                          st.machines.running)
        tb = T.snapshot(tb, replace(
            st, machines=replace(st.machines, running=run_g)))
        st = replace(st, trace=tb)
    agg = ws.agg
    if agg.metrics is not None:
        # the queue-depth sample is count-exact vs the dense engine for
        # N <= W: unloaded tasks are NOT_ARRIVED there, unused slots are
        # terminal here — neither is IN_BATCH/IN_MQ
        agg = agg._replace(metrics=ME.observe_event(agg.metrics, st.tasks))
    return dataclasses.replace(ws, agg=agg,
                               sim=replace(st, n_events=st.n_events + 1))


# ---------------------------------------------------------------------------
# Top-level streaming engine
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("params",))
def run_stream(stream: TaskStream, mtype: jnp.ndarray, eet: jnp.ndarray,
               power: jnp.ndarray, policy_id: jnp.ndarray,
               params: StreamParams,
               dynamics: S.MachineDynamics | None = None,
               policy_params=None) -> WindowState:
    """Run one streaming replica to completion; returns the final
    :class:`WindowState` (aggregates in ``.agg``, fleet in
    ``.sim.machines``, last-resident tasks in the window columns).

    ``stream`` carries the workload as ``(n_chunks, chunk)`` columns
    (:func:`make_stream`); ``eet``/``power`` are the *global* (T, Mt) /
    (Mt, 2) tables — per-task noise/rank ride in the stream.  All array
    arguments may carry leading batch dims via ``vmap``.  Event loop
    structure: ``scan`` over chunks, each chunk an inner while of
    retire -> refill -> (event if the chunk still has pending rows),
    then a final drain to quiescence and a last retirement pass.
    """
    if policy_params is None:
        policy_params = NN.default_params()
    w = params.window
    n_chunks, c = stream.arrival.shape
    n_total = n_chunks * c
    m = mtype.shape[-1]
    has_deps = stream.parents is not None
    max_events = params.max_events or (4 * n_total + 16)
    if dynamics is not None and params.max_events is None:
        max_events += 2 * dynamics.down_start.shape[-1] * m
    if has_deps and params.max_events is None:
        max_events += n_total

    tasks0 = S.TaskTable(
        arrival=jnp.full((w,), jnp.inf, jnp.float32),
        type_id=jnp.zeros((w,), jnp.int32),
        deadline=jnp.full((w,), jnp.inf, jnp.float32),
        status=jnp.full((w,), S.COMPLETED, jnp.int32),
        machine=jnp.full((w,), -1, jnp.int32),
        seq=jnp.full((w,), INT_MAX, jnp.int32),
        t_start=jnp.full((w,), -1.0, jnp.float32),
        t_end=jnp.full((w,), -1.0, jnp.float32),
    )
    sim = S.init_state(tasks0, mtype, dynamics, parents=None)
    # every slot starts retired-terminal (inert to all phases); the live
    # counter starts at zero accordingly (_refill revives slots)
    sim = replace(sim, tasks=tasks0, n_live=jnp.int32(0))
    if has_deps:
        sim = replace(sim, deps_left=jnp.zeros((w,), jnp.int32))
    if params.trace:
        k = dynamics.down_start.shape[-1] if dynamics is not None else 0
        cap = params.trace_capacity or T.row_capacity_bound(
            n_total, params.lcap, m, k)
        sim = replace(sim, trace=T.make_buffer(cap, max_events, m,
                                               pad=max(w, m)))
    wtab = S.StaticTables(
        eet=jnp.asarray(eet, jnp.float32),
        power=jnp.asarray(power, jnp.float32),
        noise=jnp.ones((w,), jnp.float32),
        rank=jnp.zeros((w,), jnp.float32),
    )
    kk = stream.parents.shape[-1] if has_deps else 0
    ws = WindowState(
        sim=sim, wtab=wtab,
        slot_task=jnp.full((w,), -1, jnp.int32),
        retired=jnp.ones((w,), bool),
        cursor=jnp.int32(0),
        agg=_init_agg(),
        children_unloaded=jnp.zeros((w,), jnp.int32) if has_deps else None,
        pslot=jnp.full((w, kk), -1, jnp.int32) if has_deps else None,
    )
    if params.metrics:
        ws = dataclasses.replace(ws, agg=ws.agg._replace(
            metrics=ME.init(params.metrics_spec)))
    policy_id = jnp.asarray(policy_id, jnp.int32)
    sparams = params.sim_params()
    transitions = E.sorted_transitions(dynamics) \
        if dynamics is not None else None

    def event(ws):
        return _one_event(ws, policy_id, sparams, dynamics, policy_params,
                          transitions)

    def chunk_step(ws, chunk):
        n_valid = jnp.sum(chunk.gid >= 0).astype(jnp.int32)
        ws = dataclasses.replace(ws, cursor=jnp.int32(0))

        def cond(ws):
            # time goes +inf exactly when every loaded task is terminal
            # yet unretirable while rows are still pending — a DAG whose
            # dependency frontier exceeds W (see docs/streaming.md).
            # Stop instead of burning events; agg.retired < N flags it.
            return (ws.cursor < n_valid) & (ws.sim.n_events < max_events) \
                & jnp.isfinite(ws.sim.time)

        def body(ws):
            ws = _refill(_retire(ws), chunk, n_valid)
            # run an event only while rows are still pending (the window
            # is full) — keeps the event sequence chunk-size invariant
            return jax.lax.cond(ws.cursor < n_valid, event,
                                lambda x: x, ws)

        return jax.lax.while_loop(cond, body, ws), None

    ws, _ = jax.lax.scan(chunk_step, ws, stream)

    def drain_cond(ws):
        # incremental non-terminal counter (bitwise the status reduction)
        return (ws.sim.n_live > 0) & (ws.sim.n_events < max_events)

    ws = jax.lax.while_loop(drain_cond, event, ws)
    return _retire(ws)


def summarize_stream_replica(ws: WindowState, n_tasks: int,
                             dynamics: S.MachineDynamics | None = None
                             ) -> dict:
    """Scalar metrics for one streaming replica (traced; used under
    vmap) — same keys as ``experiment.summarize_replica``, computed from
    the running aggregates instead of an (N,) final state."""
    a = ws.agg
    mach = ws.sim.machines
    span = jnp.maximum(a.makespan, 0.0)
    active_e = jnp.sum(mach.energy)
    idle_t = jnp.maximum(span - mach.active_time, 0.0)
    if dynamics is not None:
        idle_t = jnp.maximum(idle_t - EN.downtime(dynamics, span), 0.0)
    idle_e = jnp.sum(ws.wtab.power[mach.mtype, 0] * mach.power_scale
                     * idle_t)
    avail = jnp.float32(1.0) if dynamics is None else jnp.mean(
        EN.availability(dynamics, span))
    return {
        "completed": a.completed,
        "missed": a.missed_queue + a.missed_running,
        "cancelled": a.cancelled,
        "preempted": a.preempted,
        "requeues": a.evictions - a.preempted,
        "availability": avail,
        "completion_rate": a.completed / n_tasks,
        "makespan": span,
        "energy": active_e + idle_e,
        "active_energy": active_e,
        "idle_energy": idle_e,
        "mean_response": a.sum_response / jnp.maximum(a.completed, 1),
    }


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------
def make_stream(workload: Workload, chunk: int, *,
                noise: np.ndarray | None = None,
                rank: np.ndarray | None = None,
                parents: np.ndarray | None = None) -> TaskStream:
    """Pack a workload into ``(n_chunks, chunk)`` stream columns.

    The tail chunk is padded with ``gid = -1`` rows (arrival/deadline
    inf) that the refill never loads.  ``parents`` (global-id (N, K)
    table) switches on workflow mode; per-task out-degrees are
    precomputed so the engine can gate slot retirement on the
    dependency frontier.
    """
    n = workload.n_tasks
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    n_chunks = max(-(-n // chunk), 1)
    total = n_chunks * chunk

    def pad(x, fill, dtype):
        out = np.full((total,), fill, dtype)
        out[:n] = x
        return jnp.asarray(out.reshape(n_chunks, chunk))

    gid = np.full((total,), -1, np.int32)
    gid[:n] = np.arange(n, dtype=np.int32)
    parents_s = n_children_s = None
    if parents is not None:
        parents = np.asarray(parents, np.int32)
        k = parents.shape[1]
        pp = np.full((total, k), -1, np.int32)
        pp[:n] = parents
        parents_s = jnp.asarray(pp.reshape(n_chunks, chunk, k))
        n_children = np.zeros((total,), np.int32)
        np.add.at(n_children, parents[parents >= 0], 1)
        n_children_s = jnp.asarray(n_children.reshape(n_chunks, chunk))
    return TaskStream(
        arrival=pad(workload.arrival, np.inf, np.float32),
        type_id=pad(workload.type_id, 0, np.int32),
        deadline=pad(workload.deadline, np.inf, np.float32),
        noise=pad(np.ones(n, np.float32) if noise is None else noise,
                  1.0, np.float32),
        rank=pad(np.zeros(n, np.float32) if rank is None else rank,
                 0.0, np.float32),
        gid=jnp.asarray(gid.reshape(n_chunks, chunk)),
        parents=parents_s,
        n_children=n_children_s,
    )


@dataclasses.dataclass
class StreamResult:
    """Host-friendly bundle around a finished :class:`WindowState`."""
    ws: WindowState
    n_tasks: int
    params: StreamParams
    dynamics: S.MachineDynamics | None
    eet: np.ndarray
    power: np.ndarray
    mtype: np.ndarray

    @property
    def window(self) -> int:
        return self.params.window

    @property
    def agg(self) -> StreamAgg:
        return self.ws.agg

    @property
    def machines(self) -> S.MachineState:
        return self.ws.sim.machines

    @property
    def trace(self):
        return self.ws.sim.trace

    @property
    def sim_metrics(self):
        """``metrics.SimMetrics`` when run with ``metrics=True``, else
        None — histograms/SLO windows folded over every retired task."""
        return self.ws.agg.metrics

    @property
    def n_events(self) -> int:
        return int(self.ws.sim.n_events)

    @property
    def stalled(self) -> bool:
        """True when the run stopped with unretired work — a DAG whose
        dependency frontier exceeded the window (docs/streaming.md).
        A healthy run always ends with ``agg.retired == n_tasks``."""
        return int(np.asarray(self.ws.agg.retired)) < self.n_tasks

    @property
    def resident_gids(self) -> np.ndarray:
        """Global ids whose rows are still materialized in the window."""
        slot = np.asarray(self.ws.slot_task)
        return np.sort(slot[slot >= 0])

    def resident_state(self) -> S.SimState:
        """Dense-shaped view of the window's resident rows, in global-id
        order.  When N <= window this is the complete final task table
        (retired rows keep their data: refills prefer never-used slots),
        so it compares 1:1 against ``engine.simulate``'s output."""
        st = self.ws.sim
        slot = np.asarray(self.ws.slot_task)
        idx = np.nonzero(slot >= 0)[0]
        idx = idx[np.argsort(slot[idx], kind="stable")]

        def g(x):
            return jnp.asarray(np.asarray(x)[idx])

        return dataclasses.replace(
            st, tasks=jax.tree.map(g, st.tasks),
            n_preempts=g(st.n_preempts), trace=None, deps_left=None)

    def summarize(self) -> dict:
        from repro.core import report
        return report.summarize_stream(self)


def min_window(parents: np.ndarray) -> int:
    """Static floor on W for a DAG: a task loads only while all its
    parents are still resident, so W must be at least the maximum
    in-degree + 1.  This is necessary, not sufficient — how many other
    slots are pinned at that moment is execution-dependent, so size W
    generously and check :attr:`StreamResult.stalled` after the run."""
    p = np.asarray(parents)
    if p.size == 0:
        return 1
    return int((p >= 0).sum(axis=1).max()) + 1


def simulate_stream(workload, eet: EETTable | np.ndarray,
                    power: np.ndarray,
                    machine_types: np.ndarray | list[int],
                    policy: str = "mct", *, window: int,
                    chunk: int | None = None, lcap: int = 4,
                    qcap: int | None = None,
                    cancel_infeasible: bool = True,
                    noise: np.ndarray | None = None,
                    dynamics: S.MachineDynamics | None = None,
                    trace: bool = False,
                    trace_capacity: int | None = None,
                    policy_params=None,
                    max_events: int | None = None,
                    pallas: bool = False,
                    metrics: bool = False,
                    metrics_spec: ME.MetricsSpec | None = None
                    ) -> StreamResult:
    """Host-friendly streaming run: the ``engine.simulate`` mirror.

    ``window`` is the live-slot count W (the memory bound); ``chunk``
    the stream granularity (defaults to ``min(n_tasks, window)`` —
    results are invariant to it).  ``workload`` may be a ``Workload`` or
    a ``Workflow`` (DAG mode; the dependency frontier must fit the
    window, and edges carry no staging delay yet — docs/streaming.md).
    Remaining kwargs match ``engine.simulate``.
    """
    from repro.core.workload import Workflow
    eet_arr = eet.eet if isinstance(eet, EETTable) else np.asarray(eet)
    parents = rank = None
    if isinstance(workload, Workflow):
        if np.any(workload.delay > 0):
            raise ValueError("the streaming window has no staging delays: "
                             "run a workflow with staged edges through "
                             "engine.simulate")
        parents = np.asarray(workload.parents, np.int32)
        rank = workload.ranks(np.asarray(eet_arr).mean(axis=1))
        workload = workload.workload
    n = workload.n_tasks
    window = int(window)
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if chunk is None:
        chunk = max(min(n, window), 1)
    stream = make_stream(workload, chunk, noise=noise, rank=rank,
                         parents=parents)
    params = StreamParams(window=window, lcap=lcap,
                          qcap=qcap or (1 << 30),
                          cancel_infeasible=cancel_infeasible,
                          max_events=max_events, trace=trace,
                          trace_capacity=trace_capacity, pallas=pallas,
                          metrics=metrics, metrics_spec=metrics_spec)
    mtype = jnp.asarray(np.asarray(machine_types, np.int32))
    ws = run_stream(stream, mtype, jnp.asarray(eet_arr, jnp.float32),
                    jnp.asarray(power, jnp.float32),
                    P.POLICY_IDS[policy], params, dynamics, policy_params)
    return StreamResult(ws=ws, n_tasks=n, params=params, dynamics=dynamics,
                        eet=np.asarray(eet_arr), power=np.asarray(power),
                        mtype=np.asarray(machine_types, np.int32))
