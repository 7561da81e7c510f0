"""The E2C discrete-event engine, vectorized in JAX.

One simulation replica is a ``lax.while_loop`` whose body processes exactly
one event timestamp: retire completions, admit arrivals, drop deadline
misses, run the scheduler drain loop, start queued work on idle machines.
All queue mutations are masked vector updates over the fixed-shape state in
``core/state.py`` — no host round-trips, so replicas compose under ``vmap``
(Monte-Carlo sweeps over workloads / policies / EET draws) and shard under
``pjit`` across a pod (see launch/sim.py).

Event ordering within a timestamp `t` (matches the E2C loop):
  1. completions  (``busy_until <= t``; finishing exactly at the deadline
     counts as completed),
  2. availability (dynamic scenarios only: machines inside a down interval
     preempt their running task and flush their queue — kill to the
     PREEMPTED pool or requeue to the batch queue; partial energy is
     charged either way),
  2b. dependency release (workflow mode only: refresh each task's
     remaining-parents counter from the status column; tasks whose
     parents all terminated but not all *completed* can never run and
     are cancelled — cascades resolve to a fixpoint within the phase;
     then each task's staging-release time, the latest parent end plus
     its edge's staging delay),
  3. arrivals     (``arrival <= t`` AND all parents completed AND every
     input staged -> batch queue, overflow -> cancelled),
  4. deadline drops (queued -> MISSED_QUEUE, running -> MISSED_RUNNING and
     the machine is freed; partial energy is charged),
  5. scheduler drain (policy picks (task, machine) pairs until no room / no
     tasks; down machines are masked out of ``SchedView.room``;
     cancellation wrapper may send tasks to the cancelled pool),
  6. start tasks on idle *available* machines (lowest mapping-sequence
     first — FIFO within a machine queue, E2C's sequential execution).

Workflows: ``run_sim(..., parents=state.Edges)`` makes task precedence
first-class — a task's effective arrival is ``max(arrival, max over its
in-edges of parent end + staging delay)``, and precedence costs
O(E log E) work in log2(E) steps an event, where a padded table costs
O(N·K_max).  (A padded (N, K) parent table, as the streaming window keeps,
is also accepted and means zero delays.)  The static ``has_deps`` choice
is a Python-level ``parents is None`` check (like tracing), so
independent-task mode compiles the identical HLO it compiled before DAGs
existed.  See docs/workflows.md.

DVFS: each machine's ``speed`` divides its EET row (both the scheduler's
expectations and actual runtimes) and ``power_scale`` multiplies its
idle/active power — see ``state.MachineDynamics``.

Tracing: with ``SimParams(trace=True)`` every phase appends its
transitions to a fixed-capacity ``trace.TraceBuffer`` on the state and
the loop writes one fleet snapshot per event (docs/visualization.md).
The default (off) leaves ``SimState.trace`` as ``None`` and compiles
the exact pre-trace HLO — recording is gated on Python-level ``None``
checks, never ``lax.cond``.

Telemetry: ``SimParams(metrics=True)`` attaches fixed-bucket
``metrics.SimMetrics`` instruments (latency/slowdown/queue-depth
histograms + windowed SLO counters, docs/observability.md) — a
queue-depth sample per event inside the loop, one vectorized per-task
fold after it.  Off is the same Python-level gate as ``trace``: the
HLO is byte-identical to the uninstrumented engine.
"""
from __future__ import annotations

import dataclasses
from dataclasses import replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as ME
from repro.core import neural as NN
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import trace as T
from repro.core.eet import EETTable
from repro.core.workload import Workload
from repro.kernels import sched_argmin as K

INT_MAX = jnp.iinfo(jnp.int32).max


class SimParams(NamedTuple):
    """Static (compile-time) simulation parameters."""
    lcap: int = 4                 # machine-queue size (paper Fig. 3 option)
    qcap: int = 1 << 30           # batch-queue capacity
    cancel_infeasible: bool = True
    max_events: int | None = None
    trace: bool = False           # record TraceBuffer (docs/visualization.md)
    trace_capacity: int | None = None   # rows; default row_capacity_bound
    pallas: bool = False          # fused dispatch + event-reduction kernels
    #                               (docs/kernels.md); bitwise-identical
    #                               results, off compiles the identical
    #                               pre-kernel HLO
    metrics: bool = False         # in-jit histograms + SLO windows
    #                               (docs/observability.md); off compiles
    #                               the identical uninstrumented HLO
    metrics_spec: ME.MetricsSpec | None = None   # bucket/window geometry;
    #                               None = metrics.DEFAULT_SPEC
    drain_k: int = 1              # speculative drain width: candidate
    #                               decisions per drain trip, validated to
    #                               a sequentially-consistent prefix and
    #                               applied in one masked scatter — bitwise
    #                               the single-step schedule
    #                               (docs/engine_perf.md); 1 = sequential.
    #                               Pays off when dispatch is cheap
    #                               (grouped single-policy runs); under the
    #                               batched lax.switch every branch runs
    #                               K-fold, so the sweep default stays 1
    legacy_drain: bool = False    # PR-9-equivalent drain loop (recompute
    #                               machine_available + O(N) queue scan
    #                               every iteration) — the measured T12
    #                               baseline, never a production setting


# --------------------------------------------------------------------------
# Event phases
# --------------------------------------------------------------------------
# Each phase runs under a ``jax.named_scope`` of its name, so the HLO
# ``op_name`` of every op (and so a device trace) names the phase it
# belongs to; a scope is compile-time metadata only.
@jax.named_scope("completions")
def _completions(st: S.SimState, tb: S.StaticTables) -> S.SimState:
    mach, tasks = st.machines, st.tasks
    n = tasks.arrival.shape[0]
    done_m = (mach.running >= 0) & (mach.busy_until <= st.time)
    tid = jnp.where(done_m, mach.running, n)          # n = dropped by scatter
    dur = mach.busy_until - tasks.t_start[jnp.clip(mach.running, 0, n - 1)]
    dur = jnp.where(done_m, dur, 0.0)
    p_active = tb.power[mach.mtype, 1] * mach.power_scale

    if st.trace is not None:
        n_m = mach.mtype.shape[0]
        st = replace(st, trace=T.record(
            st.trace, st.time, T.EV_COMPLETE, mach.running,
            jnp.arange(n_m), done_m))
    tasks = replace(
        tasks,
        status=tasks.status.at[tid].set(S.COMPLETED, mode="drop"),
        t_end=tasks.t_end.at[tid].set(
            jnp.where(done_m, mach.busy_until, 0.0), mode="drop"),
    )
    mach = replace(
        mach,
        energy=mach.energy + p_active * dur,
        active_time=mach.active_time + dur,
        running=jnp.where(done_m, -1, mach.running),
    )
    return replace(st, tasks=tasks, machines=mach,
                   n_live=st.n_live - jnp.sum(done_m, dtype=jnp.int32))


@jax.named_scope("availability")
def _availability(st: S.SimState, tb: S.StaticTables,
                  dyn: S.MachineDynamics) -> S.SimState:
    """Dynamic-scenario phase: evict work from machines that are down.

    Runs between completions and arrivals.  A machine inside a down
    interval at the current event time preempts its running task (partial
    energy charged for the slice already executed) and flushes its local
    queue.  ``dyn.kill[m]`` selects spot-reclaim semantics (evictions are
    terminal ``PREEMPTED``) vs fail/repair semantics (evictions rejoin the
    batch queue and restart from scratch).  Because the scheduler masks
    down machines out of ``room`` and ``_start_tasks`` skips them, work
    only ever needs evicting at the down transition itself.
    """
    tasks, mach = st.tasks, st.machines
    n = tasks.arrival.shape[0]
    n_m = mach.mtype.shape[0]
    down = ~S.machine_up(dyn, st.time)                     # (M,)

    # -- running tasks on down machines: charge the partial slice ---------
    running0 = mach.running
    hit = down & (running0 >= 0)
    rid = jnp.clip(running0, 0, n - 1)
    dur = jnp.where(hit, st.time - tasks.t_start[rid], 0.0)
    p_active = tb.power[mach.mtype, 1] * mach.power_scale
    mach = replace(
        mach,
        energy=mach.energy + p_active * dur,
        active_time=mach.active_time + dur,
        running=jnp.where(hit, -1, running0),
    )
    tid_kill = jnp.where(hit & dyn.kill, running0, n)
    tid_req = jnp.where(hit & ~dyn.kill, running0, n)
    if st.trace is not None:
        kinds = jnp.where(dyn.kill, T.EV_PREEMPT, T.EV_REQUEUE)
        st = replace(st, trace=T.record(
            st.trace, st.time, kinds, running0, jnp.arange(n_m), hit))
    status = tasks.status.at[tid_kill].set(S.PREEMPTED, mode="drop") \
                         .at[tid_req].set(S.IN_BATCH, mode="drop")
    t_end = tasks.t_end.at[tid_kill].set(st.time, mode="drop")
    t_start = tasks.t_start.at[tid_req].set(-1.0, mode="drop")
    machine = tasks.machine.at[tid_req].set(-1, mode="drop")
    seq = tasks.seq.at[tid_req].set(INT_MAX, mode="drop")
    n_pre = st.n_preempts.at[jnp.where(hit, running0, n)].add(1, mode="drop")

    # -- queued tasks on down machines: flush the machine queue -----------
    m_of = jnp.clip(machine, 0, n_m - 1)
    in_down_q = (status == S.IN_MQ) & (machine >= 0) & down[m_of]
    kq = in_down_q & dyn.kill[m_of]
    rq = in_down_q & ~dyn.kill[m_of]
    if st.trace is not None:
        kinds = jnp.where(dyn.kill[m_of], T.EV_PREEMPT, T.EV_REQUEUE)
        st = replace(st, trace=T.record(
            st.trace, st.time, kinds, jnp.arange(n), machine, in_down_q))
    status = jnp.where(kq, S.PREEMPTED, status)
    t_end = jnp.where(kq, st.time, t_end)
    status = jnp.where(rq, S.IN_BATCH, status)
    machine = jnp.where(rq, -1, machine)
    seq = jnp.where(rq, INT_MAX, seq)
    n_pre = n_pre + in_down_q.astype(jnp.int32)
    mq_count = jnp.where(down, 0, st.mq_count)

    # incremental population counters: kills leave the live pool, requeues
    # (running or machine-queued) rejoin the batch queue
    kills = jnp.sum(hit & dyn.kill, dtype=jnp.int32) + \
        jnp.sum(kq, dtype=jnp.int32)
    requeues = jnp.sum(hit & ~dyn.kill, dtype=jnp.int32) + \
        jnp.sum(rq, dtype=jnp.int32)
    tasks = replace(tasks, status=status, t_end=t_end, t_start=t_start,
                    machine=machine, seq=seq)
    return replace(st, tasks=tasks, machines=mach, n_preempts=n_pre,
                   mq_count=mq_count, n_live=st.n_live - kills,
                   n_batch=st.n_batch + requeues)


@jax.named_scope("release")
def _release(st: S.SimState, parents) -> S.SimState:
    """Workflow-mode phase: refresh dependency state, cancel dead branches.

    Runs between availability and arrivals.  The remaining-parents
    counter (``SimState.deps_left``) is recomputed from the status
    column — exact integer math, no drift — and tasks whose parents have
    all terminated with at least one *failure* (cancelled / missed /
    preempted) are cancelled: they can never satisfy their precedence
    constraint.  Cancelling such a task may doom its own children, so
    the phase iterates to a fixpoint (each trip resolves one cascade
    level; the loop is bounded by the not-yet-arrived population).
    With an edge list (``state.Edges``) each trip is O(E log E) work in
    log2(E) steps, and after the fixpoint ``SimState.ready_at`` takes
    each task's staging-release time (``state.ready_times``).

    Tracing note: like the drain loop, cascade cancels are recorded once
    per event via a status diff (task-id order), keeping the buffers out
    of the while-loop carry; the reference engine emits the same order.
    """
    n = st.tasks.arrival.shape[0]
    status_before = st.tasks.status
    trace = st.trace
    st = replace(st, trace=None)

    def body(c):
        s, _ = c
        left, failed = S.dep_state(s.tasks.status, parents)
        kill = (s.tasks.status == S.NOT_ARRIVED) & (left == 0) & failed
        tasks = replace(
            s.tasks,
            status=jnp.where(kill, S.CANCELLED, s.tasks.status),
            t_end=jnp.where(kill, s.time, s.tasks.t_end))
        return replace(s, tasks=tasks, deps_left=left,
                       n_live=s.n_live - jnp.sum(kill, dtype=jnp.int32)
                       ), kill.any()

    st, _ = jax.lax.while_loop(lambda c: c[1], body,
                               (st, jnp.bool_(True)))
    if trace is not None:
        killed = (status_before == S.NOT_ARRIVED) & (
            st.tasks.status == S.CANCELLED)
        trace = T.record(trace, st.time, T.EV_CANCEL, jnp.arange(n), -1,
                         killed)
    # deps_left is current: the loop only exits on a pass that changed
    # nothing, so the last stored counters reflect the final statuses
    # (the arrivals phase reads deps_left == 0 as "all parents completed")
    if st.ready_at is not None:
        st = replace(st, ready_at=S.ready_times(st.tasks.t_end, parents))
    return replace(st, trace=trace)


@jax.named_scope("arrivals")
def _arrivals(st: S.SimState, qcap: int) -> S.SimState:
    tasks = st.tasks
    new = (tasks.status == S.NOT_ARRIVED) & (tasks.arrival <= st.time)
    if st.deps_left is not None:
        new = new & (st.deps_left == 0)
    if st.ready_at is not None:
        new = new & (st.ready_at <= st.time)
    # batch-queue population from the incremental counter — the former
    # O(N) status scan was paid on every event (docs/engine_perf.md)
    in_batch = st.n_batch
    pos = jnp.cumsum(new.astype(jnp.int32))           # 1-based admission rank
    admitted = new & (in_batch + pos <= qcap)
    overflow = new & ~admitted
    if st.trace is not None:
        n = tasks.arrival.shape[0]
        st = replace(st, trace=T.record(
            st.trace, st.time, T.EV_CANCEL, jnp.arange(n), -1, overflow))
    status = jnp.where(admitted, S.IN_BATCH, tasks.status)
    status = jnp.where(overflow, S.CANCELLED, status)
    t_end = jnp.where(overflow, tasks.arrival, tasks.t_end)
    return replace(st, tasks=replace(tasks, status=status, t_end=t_end),
                   n_batch=st.n_batch + jnp.sum(admitted, dtype=jnp.int32),
                   n_live=st.n_live - jnp.sum(overflow, dtype=jnp.int32))


@jax.named_scope("deadline_drops")
def _deadline_drops(st: S.SimState, tb: S.StaticTables) -> S.SimState:
    tasks, mach = st.tasks, st.machines
    n = tasks.arrival.shape[0]
    n_m = mach.mtype.shape[0]
    # queued tasks (batch queue or machine queue) past deadline
    waiting = (tasks.status == S.IN_BATCH) | (tasks.status == S.IN_MQ)
    miss_q = waiting & (tasks.deadline <= st.time)
    # machine-queue departures decrement the incremental counts
    from_mq = miss_q & (tasks.status == S.IN_MQ)
    mq_count = st.mq_count - jnp.zeros((n_m,), jnp.int32).at[
        jnp.where(from_mq, tasks.machine, n_m)].add(1, mode="drop")
    from_batch = miss_q & (tasks.status == S.IN_BATCH)
    st = replace(st, mq_count=mq_count,
                 n_batch=st.n_batch - jnp.sum(from_batch, dtype=jnp.int32))
    if st.trace is not None:
        st = replace(st, trace=T.record(
            st.trace, st.time, T.EV_MISS_QUEUE, jnp.arange(n),
            tasks.machine, miss_q))
    status = jnp.where(miss_q, S.MISSED_QUEUE, tasks.status)
    t_end = jnp.where(miss_q, tasks.deadline, tasks.t_end)

    # running tasks past deadline: drop from the machine, charge partial energy
    run_id = jnp.clip(mach.running, 0, n - 1)
    run_dl = tasks.deadline[run_id]
    miss_r = (mach.running >= 0) & (run_dl <= st.time)
    if st.trace is not None:
        st = replace(st, trace=T.record(
            st.trace, st.time, T.EV_MISS_RUNNING, mach.running,
            jnp.arange(n_m), miss_r))
    tid = jnp.where(miss_r, mach.running, n)
    dur = jnp.where(miss_r, run_dl - tasks.t_start[run_id], 0.0)
    status = status.at[tid].set(S.MISSED_RUNNING, mode="drop")
    t_end = t_end.at[tid].set(jnp.where(miss_r, run_dl, 0.0), mode="drop")
    p_active = tb.power[mach.mtype, 1] * mach.power_scale
    mach = replace(
        mach,
        energy=mach.energy + p_active * dur,
        active_time=mach.active_time + dur,
        running=jnp.where(miss_r, -1, mach.running),
    )
    dropped = jnp.sum(miss_q, dtype=jnp.int32) + \
        jnp.sum(miss_r, dtype=jnp.int32)
    return replace(st, tasks=replace(tasks, status=status, t_end=t_end),
                   machines=mach, n_live=st.n_live - dropped)


def _apply_decision(st: S.SimState, dec: P.Decision) -> S.SimState:
    tasks = st.tasks
    n = tasks.arrival.shape[0]
    do_map = (dec.task >= 0) & ~dec.cancel
    do_cancel = (dec.task >= 0) & dec.cancel
    tid_map = jnp.where(do_map, dec.task, n)
    tid_cxl = jnp.where(do_cancel, dec.task, n)
    tasks = replace(
        tasks,
        status=tasks.status.at[tid_map].set(S.IN_MQ, mode="drop")
                           .at[tid_cxl].set(S.CANCELLED, mode="drop"),
        machine=tasks.machine.at[tid_map].set(dec.machine, mode="drop"),
        seq=tasks.seq.at[tid_map].set(st.seq_counter, mode="drop"),
        t_end=tasks.t_end.at[tid_cxl].set(st.time, mode="drop"),
    )
    n_m = st.machines.mtype.shape[0]
    rr_ptr = jnp.where(do_map, (dec.machine + 1) % n_m, st.rr_ptr)
    mq_count = st.mq_count.at[jnp.where(do_map, dec.machine, n_m)].add(
        1, mode="drop")
    return replace(st, tasks=tasks, seq_counter=st.seq_counter +
                   do_map.astype(jnp.int32), rr_ptr=rr_ptr,
                   mq_count=mq_count,
                   n_batch=st.n_batch - (dec.task >= 0).astype(jnp.int32),
                   n_live=st.n_live - do_cancel.astype(jnp.int32))


def _apply_decisions_k(st: S.SimState, dec: P.Decision, use: jnp.ndarray
                       ) -> tuple[S.SimState, jnp.ndarray]:
    """Apply a validated K-prefix of drain decisions in one masked scatter.

    ``use`` masks the sequentially-consistent prefix (``P.dispatch_k``,
    which also returns the carried machine-available vector after the
    prefix); per-candidate semantics are exactly ``_apply_decision``'s,
    with the mapping-sequence numbers assigned in candidate order
    (exclusive cumsum) and ``rr_ptr`` advanced past the last applied
    map.  Returns the state and the applied count for the drain-loop
    bound.
    """
    tasks = st.tasks
    n = tasks.arrival.shape[0]
    n_m = st.machines.mtype.shape[0]
    k = dec.task.shape[0]
    do_map = use & ~dec.cancel
    do_cxl = use & dec.cancel
    tid_map = jnp.where(do_map, dec.task, n)
    tid_cxl = jnp.where(do_cxl, dec.task, n)
    seq_rank = jnp.cumsum(do_map.astype(jnp.int32)) - \
        do_map.astype(jnp.int32)
    tasks = replace(
        tasks,
        status=tasks.status.at[tid_map].set(S.IN_MQ, mode="drop")
                           .at[tid_cxl].set(S.CANCELLED, mode="drop"),
        machine=tasks.machine.at[tid_map].set(dec.machine, mode="drop"),
        seq=tasks.seq.at[tid_map].set(st.seq_counter + seq_rank,
                                      mode="drop"),
        t_end=tasks.t_end.at[tid_cxl].set(st.time, mode="drop"),
    )
    mid = jnp.where(do_map, dec.machine, n_m)
    mq_count = st.mq_count.at[mid].add(1, mode="drop")
    # rr_ptr: one past the last applied mapped machine (unchanged when the
    # prefix mapped nothing) — sequential per-map advancement telescopes
    last = jnp.max(jnp.where(do_map, jnp.arange(k), -1))
    m_last = dec.machine[jnp.clip(last, 0, k - 1)]
    rr_ptr = jnp.where(last >= 0, (m_last + 1) % n_m, st.rr_ptr)
    n_applied = jnp.sum(use, dtype=jnp.int32)
    st = replace(st, tasks=tasks,
                 seq_counter=st.seq_counter + jnp.sum(do_map,
                                                      dtype=jnp.int32),
                 rr_ptr=rr_ptr, mq_count=mq_count,
                 n_batch=st.n_batch - n_applied,
                 n_live=st.n_live - jnp.sum(do_cxl, dtype=jnp.int32))
    return st, n_applied


@jax.named_scope("drain")
def _drain(st: S.SimState, tb: S.StaticTables, policy_id: jnp.ndarray,
           params: SimParams, const: tuple | None = None,
           up: jnp.ndarray | None = None,
           pparams: NN.PolicyParams | None = None) -> S.SimState:
    """Invoke the scheduler until it returns a no-op.

    The machine-available vector is computed once per event and carried
    through the loop — each mapped decision adds its expected time to
    exactly one machine, which both matches the reference engine's
    sequential (seq-order) accumulation and drops the former O(N·M)
    ``queued_work`` reduction from every drain step.

    With ``params.drain_k > 1`` each trip speculates up to K sequential
    decisions in one batched dispatch and applies the maximal
    sequentially-consistent prefix (``P.dispatch_k`` — bitwise the
    single-step schedule), cutting trips from O(queue) to O(queue/K);
    the loop remains bounded by the batch-queue population, now read
    from the incremental ``n_batch`` counter.

    Tracing note: cancel rows are recorded *after* the loop by diffing
    the status column (one masked write per event, in task-id order)
    instead of inside ``_apply_decision`` — per-iteration scatters in
    this inner loop were the bulk of the tracing overhead.  The
    reference engine emits its drain cancels in the same task-id order.
    """
    n = st.tasks.arrival.shape[0]
    bound = st.n_batch
    status_before = st.tasks.status
    trace = st.trace
    st = replace(st, trace=None)      # keep the buffers out of the carry

    if const is None:
        mach = st.machines
        eet_nm = tb.eet[st.tasks.type_id[:, None], mach.mtype[None, :]] \
            / mach.speed[None, :]
        energy_nm = eet_nm * (tb.power[mach.mtype, 1]
                              * mach.power_scale)[None, :]
        const = (eet_nm, energy_nm)
    eet_nm = const[0]

    if params.legacy_drain:
        # PR-9-equivalent loop (the T12 bench baseline, never a
        # production setting): every iteration re-runs the O(N·M)
        # ``machine_available`` reduction inside ``build_view`` and the
        # bound is the O(N) status scan — docs/engine_perf.md
        bound_l = jnp.sum(st.tasks.status == S.IN_BATCH, dtype=jnp.int32)

        def cond_l(c):
            _, cont, iters = c
            return cont & (iters < bound_l)

        def body_l(c):
            s, _, iters = c
            dec = P.dispatch(policy_id, s, tb, params.lcap,
                             params.cancel_infeasible, const, up, pparams,
                             pallas=params.pallas)
            return _apply_decision(s, dec), dec.task >= 0, iters + 1

        st, _, _ = jax.lax.while_loop(
            cond_l, body_l, (st, jnp.bool_(True), jnp.int32(0)))
        return _drain_trace(st, trace, status_before)

    # one availability reduction per event, reusing the hoisted eet_nm
    # (the same floats machine_available gathers, summed in the same
    # task-id order)
    mach = st.machines
    base = jnp.maximum(st.time, jnp.where(mach.running >= 0,
                                          mach.busy_until, st.time))
    in_mq = (st.tasks.status == S.IN_MQ)[:, None] & (
        st.tasks.machine[:, None] == jnp.arange(mach.mtype.shape[0])[None])
    avail0 = base + jnp.sum(jnp.where(in_mq, eet_nm, 0.0), axis=0)
    k = max(1, int(params.drain_k))

    def cond(c):
        _, _, cont, iters = c
        return cont & (iters < bound)

    def single_step(s, avail, iters):
        dec = P.dispatch(policy_id, s, tb, params.lcap,
                         params.cancel_infeasible, const, up, pparams,
                         pallas=params.pallas, avail=avail)
        s = _apply_decision(s, dec)
        do_map = (dec.task >= 0) & ~dec.cancel
        m_oh = (jnp.arange(avail.shape[0]) == dec.machine) & do_map
        avail = jnp.where(
            m_oh, avail + eet_nm[jnp.clip(dec.task, 0, n - 1)], avail)
        return s, avail, dec.task >= 0, iters + 1

    if k == 1:
        def body(c):
            s, avail, _, iters = c
            return single_step(s, avail, iters)
    else:
        # K-wide trip: one batched dispatch constructs/validates up to K
        # sequential decisions and applies the maximal prefix in one
        # masked scatter.  (No shallow-queue fallback branch: under vmap
        # a ``lax.cond`` batches into a select that executes BOTH
        # branches every trip, so a hybrid costs the sum of the paths —
        # measured in docs/engine_perf.md.)
        def body(c):
            s, avail, _, iters = c
            dec, use, av = P.dispatch_k(policy_id, s, tb, params.lcap,
                                        params.cancel_infeasible, k,
                                        const, up, pparams,
                                        pallas=params.pallas, avail=avail)
            s, n_applied = _apply_decisions_k(s, dec, use)
            return s, av, dec.task[0] >= 0, iters + n_applied

    st, _, _, _ = jax.lax.while_loop(cond, body, (st, avail0,
                                                  jnp.bool_(True),
                                                  jnp.int32(0)))
    return _drain_trace(st, trace, status_before)


def _drain_trace(st: S.SimState, trace, status_before) -> S.SimState:
    """Re-attach the trace, recording the drain's cancels post-loop."""
    if trace is not None:
        n = st.tasks.arrival.shape[0]
        cancelled = (status_before != S.CANCELLED) & (
            st.tasks.status == S.CANCELLED)
        trace = T.record(trace, st.time, T.EV_CANCEL, jnp.arange(n), -1,
                         cancelled)
    return replace(st, trace=trace)


@jax.named_scope("start_tasks")
def _start_tasks(st: S.SimState, tb: S.StaticTables,
                 up: jnp.ndarray | None = None, *,
                 pallas: bool = False) -> S.SimState:
    tasks, mach = st.tasks, st.machines
    n = tasks.arrival.shape[0]
    n_m = mach.mtype.shape[0]
    idle = mach.running < 0
    if up is not None:
        idle = idle & up
    if pallas:
        # segmented per-machine lowest-seq pick; the (N, M) queued mask
        # never exists in HBM (docs/kernels.md) — integer seqs, so the
        # kernel's jnp-argmin tie-break contract makes it bitwise exact
        pick, has = K.fused_start_pick(tasks.status, tasks.machine,
                                       tasks.seq, n_m, in_mq=S.IN_MQ,
                                       interpret=K.default_interpret())
    else:
        # (N, M) queued mask; lowest mapping-seq task per idle machine
        queued = (tasks.status == S.IN_MQ)[:, None] & (
            tasks.machine[:, None] == jnp.arange(n_m)[None, :])
        seqs = jnp.where(queued, tasks.seq[:, None], INT_MAX)
        pick = jnp.argmin(seqs, axis=0).astype(jnp.int32)    # (M,)
        has = queued.any(axis=0)
    start = idle & has
    if st.trace is not None:
        st = replace(st, trace=T.record(
            st.trace, st.time, T.EV_START, pick, jnp.arange(n_m), start))
    tid = jnp.where(start, pick, n)
    dur = S.exec_time(tb, tasks, jnp.clip(pick, 0, n - 1), mach.mtype,
                      mach.speed)
    tasks = replace(
        tasks,
        status=tasks.status.at[tid].set(S.RUNNING, mode="drop"),
        t_start=tasks.t_start.at[tid].set(st.time, mode="drop"),
    )
    mach = replace(
        mach,
        running=jnp.where(start, pick, mach.running),
        busy_until=jnp.where(start, st.time + dur, mach.busy_until),
    )
    mq_count = st.mq_count - start.astype(jnp.int32)
    return replace(st, tasks=tasks, machines=mach, mq_count=mq_count)


def sorted_transitions(dyn: S.MachineDynamics) -> jnp.ndarray:
    """Loop-invariant availability-transition vector, +inf-terminated.

    ``_next_event_time`` needs the earliest transition strictly after the
    current time; on a sorted vector that is one ``searchsorted`` instead
    of the ravel + concat + masked min the loop used to rebuild every
    event.  The floats are untouched (sorting only reorders), so the
    result is bitwise identical to the original reduction.
    """
    trans = jnp.sort(jnp.concatenate([dyn.down_start.ravel(),
                                      dyn.down_end.ravel()]))
    return jnp.concatenate([trans, jnp.full((1,), jnp.inf, jnp.float32)])


@jax.named_scope("next_event")
def _next_event_time(st: S.SimState,
                     dyn: S.MachineDynamics | None = None,
                     parents: jnp.ndarray | None = None,
                     transitions: jnp.ndarray | None = None, *,
                     pallas: bool = False) -> jnp.ndarray:
    tasks, mach = st.tasks, st.machines
    not_arrived = tasks.status == S.NOT_ARRIVED
    if parents is None:
        if pallas:
            # fused single-pass arrival/deadline minima (docs/kernels.md);
            # min is order-independent, so the kernel is bitwise exact
            t_arr, t_dl = K.fused_event_bounds(
                tasks.status, tasks.arrival, tasks.deadline,
                not_arrived=S.NOT_ARRIVED, live_lo=S.IN_BATCH,
                live_hi=S.RUNNING, interpret=K.default_interpret())
            t_cmp = jnp.min(jnp.where(mach.running >= 0, mach.busy_until,
                                      S.INF))
            t = jnp.minimum(jnp.minimum(t_arr, t_cmp), t_dl)
            return _fold_transitions(t, st, dyn, transitions)
        t_arr = jnp.min(jnp.where(not_arrived, tasks.arrival, S.INF))
    else:
        t_arr = _next_release(st, parents)
    t_cmp = jnp.min(jnp.where(mach.running >= 0, mach.busy_until, S.INF))
    live = (tasks.status == S.IN_BATCH) | (tasks.status == S.IN_MQ) | (
        tasks.status == S.RUNNING)
    t_dl = jnp.min(jnp.where(live, tasks.deadline, S.INF))
    t = jnp.minimum(jnp.minimum(t_arr, t_cmp), t_dl)
    return _fold_transitions(t, st, dyn, transitions)


@jax.named_scope("release")
def _next_release(st: S.SimState, parents) -> jnp.ndarray:
    """The earliest pending arrival of a task whose parents all
    completed: its own arrival or, with staging delays, the time its
    last input is staged (``SimState.ready_at``, current since the
    release phase: no parent completes between it and the next event)."""
    tasks = st.tasks
    not_arrived = tasks.status == S.NOT_ARRIVED
    # a dependency-blocked task has no pending arrival event: its
    # release rides on a parent's terminal transition, which is
    # already an event candidate (completion / deadline / cancel).
    left, failed = S.dep_state(tasks.status, parents)
    t_eff = tasks.arrival if st.ready_at is None else jnp.maximum(
        tasks.arrival, st.ready_at)
    t_arr = jnp.min(jnp.where(not_arrived & (left == 0) & ~failed,
                              t_eff, S.INF))
    # a parent that *failed* during phases 3-6 (overflow cancel,
    # deadline drop, drain cancel) leaves a cascade pending after
    # the release phase already ran — process it at the current
    # timestamp so the doomed subtree terminates promptly.
    pending = not_arrived & (left == 0) & failed
    return jnp.minimum(t_arr, jnp.where(pending.any(), st.time, S.INF))


def _fold_transitions(t, st, dyn, transitions):
    if dyn is None:
        return t
    # availability transitions are events too; strictly future ones
    # only (a transition at the current time was already processed)
    if transitions is not None:
        # sorted +inf-terminated vector hoisted out of the loop
        # (``sorted_transitions``): the earliest element strictly after
        # the current time is one searchsorted probe — the same float
        # the masked min below would select
        idx = jnp.searchsorted(transitions, st.time, side="right")
        t_tr = transitions[jnp.minimum(idx, transitions.shape[0] - 1)]
    else:
        trans = jnp.concatenate([dyn.down_start.ravel(),
                                 dyn.down_end.ravel()])
        t_tr = jnp.min(jnp.where(trans > st.time, trans, S.INF))
    return jnp.minimum(t, t_tr)


# --------------------------------------------------------------------------
# Top-level engine
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("params",))
def run_sim(tasks: S.TaskTable, mtype: jnp.ndarray, tables: S.StaticTables,
            policy_id: jnp.ndarray, params: SimParams = SimParams(),
            dynamics: S.MachineDynamics | None = None,
            policy_params: NN.PolicyParams | None = None,
            parents: jnp.ndarray | None = None) -> S.SimState:
    """Run one simulation replica to completion; returns the final state.

    All array arguments may carry leading batch dims via ``vmap`` (see
    ``run_sweep``).  ``params`` is static.  ``dynamics`` (optional) adds
    machine availability traces + DVFS states; omitting it compiles the
    static-fleet engine with zero scenario overhead.  ``policy_params``
    (optional) carries learned-policy weights (``neural.PolicyParams``) —
    when omitted the zero default is used, so heuristic runs need not
    build one; vmapping this axis evaluates a *population* of policies
    (core/train_policy.py).  ``parents`` (optional: a ``state.Edges``
    edge list with staging delays, or an (N, K) int32 parent table
    padded with -1, which means zero delays) adds workflow precedence
    constraints — a task arrives only once every parent completed and
    its inputs are staged (docs/workflows.md); omitting it compiles the
    independent-task engine with zero DAG overhead.
    """
    if policy_params is None:
        policy_params = NN.default_params()
    st = S.init_state(tasks, mtype, dynamics, parents)
    n = tasks.arrival.shape[0]
    n_m = mtype.shape[-1]
    max_events = params.max_events or (4 * n + 16)
    if dynamics is not None and params.max_events is None:
        # every down interval contributes at most 2 extra events
        max_events += 2 * dynamics.down_start.shape[-1] * n_m
    if parents is not None and params.max_events is None:
        # every failure-release cascade echoes at most one extra event
        # per cancelled task (same-timestamp re-entry)
        max_events += n
    if isinstance(parents, S.Edges) and params.max_events is None:
        # a staged release is an event of its own, one per task
        max_events += n
    if params.trace:
        k = dynamics.down_start.shape[-1] if dynamics is not None else 0
        cap = params.trace_capacity or T.row_capacity_bound(
            n, params.lcap, n_m, k)
        st = replace(st, trace=T.make_buffer(cap, max_events, n_m,
                                             pad=max(n, n_m)))
    if params.metrics:
        st = replace(st, metrics=ME.init(params.metrics_spec))
    policy_id = jnp.asarray(policy_id, jnp.int32)

    # simulation invariants hoisted out of the event/drain loops: the
    # (N, M) expected-time and energy matrices never change mid-run
    # (DVFS operating points are fixed per run, so they fold in here)
    eet_nm = tables.eet[tasks.type_id[:, None], mtype[None, :]] \
        / st.machines.speed[None, :]
    energy_nm = eet_nm * (tables.power[mtype, 1]
                          * st.machines.power_scale)[None, :]
    const = (eet_nm, energy_nm)
    # loop-invariant sorted availability transitions (one searchsorted
    # per event instead of a ravel + concat + masked min)
    transitions = sorted_transitions(dynamics) if dynamics is not None \
        else None

    def cond(st):
        # incremental non-terminal population counter — the former
        # full-status reduction ran on every loop-trip evaluation
        return (st.n_live > 0) & (st.n_events < max_events)

    def body(st):
        t = _next_event_time(st, dynamics, parents, transitions,
                             pallas=params.pallas)
        st = replace(st, time=t)
        st = _completions(st, tables)
        up = None
        if dynamics is not None:
            st = _availability(st, tables, dynamics)
            up = S.machine_up(dynamics, st.time)
        if parents is not None:
            st = _release(st, parents)
        st = _arrivals(st, params.qcap)
        st = _deadline_drops(st, tables)
        st = _drain(st, tables, policy_id, params, const, up, policy_params)
        st = _start_tasks(st, tables, up, pallas=params.pallas)
        if params.trace:
            st = replace(st, trace=T.snapshot(st.trace, st))
        if params.metrics:
            st = replace(st, metrics=ME.observe_event(st.metrics, st.tasks))
        return replace(st, n_events=st.n_events + 1)

    st = jax.lax.while_loop(cond, body, st)
    if params.metrics:
        # per-task telemetry folds once the table is final — provably the
        # same counts as folding each task at its terminal event (every
        # task is terminal exactly once), without per-event scatters in
        # the loop (PR 2's trace-overhead lesson)
        st = replace(st, metrics=ME.fold_tasks(st.metrics, st.tasks))
    return st


def make_host_tables(eet: EETTable | np.ndarray, power: np.ndarray,
                     n_tasks: int, *, noise: np.ndarray | None = None,
                     rank: np.ndarray | None = None) -> S.StaticTables:
    """The static tables with float32 numpy leaves, built on the host
    with no device work.  ``rank`` (optional (N,) f32): HEFT upward
    ranks for workflow workloads (``workload.upward_ranks``); zeros
    otherwise, where the ``heft`` policy degenerates to head-of-queue
    MCT."""
    eet_arr = eet.eet if isinstance(eet, EETTable) else np.asarray(eet)
    if noise is None:
        noise = np.ones((n_tasks,), np.float32)
    if rank is None:
        rank = np.zeros((n_tasks,), np.float32)
    return S.StaticTables(eet=np.asarray(eet_arr, np.float32),
                          power=np.asarray(power, np.float32),
                          noise=np.asarray(noise, np.float32),
                          rank=np.asarray(rank, np.float32))


def make_tables(eet: EETTable | np.ndarray, power: np.ndarray,
                n_tasks: int, *, noise: np.ndarray | None = None,
                rank: np.ndarray | None = None) -> S.StaticTables:
    """:func:`make_host_tables` on the device."""
    return jax.device_put(make_host_tables(eet, power, n_tasks,
                                           noise=noise, rank=rank))


def simulate(workload, eet: EETTable, power: np.ndarray,
             machine_types: np.ndarray | list[int], policy: str = "mct",
             *, lcap: int = 4, qcap: int | None = None,
             cancel_infeasible: bool = True,
             noise: np.ndarray | None = None,
             dynamics: S.MachineDynamics | None = None,
             trace: bool = False,
             trace_capacity: int | None = None,
             policy_params: NN.PolicyParams | None = None,
             pallas: bool = False,
             metrics: bool = False,
             metrics_spec: ME.MetricsSpec | None = None) -> S.SimState:
    """Host-friendly wrapper: one replica, named policy.

    ``workload`` is a ``workload.Workload`` (independent tasks) or a
    ``workload.Workflow`` (DAG) — the latter threads its edge list and
    staging delays into the engine's dependency-release phase and
    precomputes the HEFT upward ranks from the EET row means
    (docs/workflows.md).
    ``dynamics`` makes the fleet dynamic (failures / spot preemption /
    DVFS) — build one with ``workload.Scenario.dynamics()`` or
    ``state.static_dynamics``.  ``trace=True`` attaches a
    ``trace.TraceBuffer`` to the returned state (``.trace``) — the event
    stream + fleet snapshots behind ``core/viz.py`` (see
    docs/visualization.md).  ``policy_params`` supplies learned-policy
    weights for the ``mlp``/``linear`` policies (docs/learned_scheduling.md).
    ``pallas=True`` routes the scheduler drain through the fused Pallas
    dispatch kernels — bitwise-identical results (docs/kernels.md).
    ``metrics=True`` attaches ``metrics.SimMetrics`` instruments to the
    returned state (``.metrics``): latency/slowdown/queue-depth
    histograms + windowed SLO counters (docs/observability.md), with
    ``metrics_spec`` overriding the default bucket/window geometry.
    """
    from repro.core.workload import Workflow
    parents = rank = None
    if isinstance(workload, Workflow):
        eet_arr = eet.eet if isinstance(eet, EETTable) else np.asarray(eet)
        # pad the edge list to a multiple of 64 so DAGs of similar size
        # share one compiled engine (padding edges lie in no task's range)
        parents = jax.device_put(workload.host_edges(
            -(-max(workload.n_edges, 1) // 64) * 64))
        rank = workload.ranks(np.asarray(eet_arr).mean(axis=1))
        workload = workload.workload
    params = SimParams(lcap=lcap, qcap=qcap or (1 << 30),
                       cancel_infeasible=cancel_infeasible, trace=trace,
                       trace_capacity=trace_capacity, pallas=pallas,
                       metrics=metrics, metrics_spec=metrics_spec)
    tables = make_tables(eet, power, workload.n_tasks, noise=noise,
                         rank=rank)
    mtype = jnp.asarray(np.asarray(machine_types, np.int32))
    return run_sim(workload.to_task_table(), mtype, tables,
                   P.POLICY_IDS[policy], params, dynamics, policy_params,
                   parents)


def run_sweep(tasks: S.TaskTable, mtype: jnp.ndarray,
              tables: S.StaticTables, policy_ids: jnp.ndarray,
              params: SimParams = SimParams(),
              dynamics: S.MachineDynamics | None = None,
              policy_params: NN.PolicyParams | None = None,
              parents: jnp.ndarray | None = None) -> S.SimState:
    """vmap over leading replica axes of any/all array arguments.

    Arguments that should be shared across replicas must be broadcast by the
    caller (see ``launch/sim.py`` which also shards the replica axis over the
    ("pod", "data") mesh axes for pod-scale Monte-Carlo).  ``dynamics``,
    when given, carries a leading replica axis like everything else — a
    Monte-Carlo grid over failure rates / DVFS states is just another
    stacked input.  So does ``policy_params``: stacking perturbed weight
    pytrees along the replica axis evaluates a whole ES population in one
    call (core/train_policy.py).  And so does ``parents`` (an
    ``Edges`` with (R, E) leaves, or (R, N, K)): a grid over workflow
    DAG shapes is one more stacked axis.  Optional
    inputs left as ``None`` compile their feature out of every replica,
    exactly as in ``run_sim`` (None is an empty pytree under vmap).
    """
    def one(tasks, mtype, tables, pid, dyn, pp, par):
        return run_sim(tasks, mtype, tables, pid, params, dyn, pp, par)
    return jax.vmap(one)(tasks, mtype, tables, policy_ids, dynamics,
                         policy_params, parents)
