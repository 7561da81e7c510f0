"""Pluggable scheduling policies (the paper's feature (ii)).

Every policy is a pure function
``(state, tables, view, rr_ptr, params) -> Decision`` where
``Decision = (task_id, machine_id)`` (int32; ``task_id == -1`` means "nothing
to schedule") and ``params`` is the shared learned-policy weight pytree
(``neural.PolicyParams`` — heuristics ignore it; parameterized policies
read their weights from it).  The engine dispatches on an integer policy
id with ``lax.switch`` so a whole *sweep over policies* can be expressed
with `vmap`, and because ``params`` is an ordinary traced operand a
*population of policies* (ES training, core/train_policy.py) is just one
more vmapped axis.

Adding a new method = writing one function and registering it — exactly the
paper's plug-in workflow, minus the GUI dialog.

Immediate policies (head-of-queue task, choose machine):
  FCFS   earliest-available machine
  RR     round-robin over machines with queue room
  MET    minimum expected execution time (load-blind)
  MCT    minimum expected completion time
  EE_MET minimum energy (EET * P_active)
  EE_MCT minimum energy among deadline-feasible machines, else min completion
         (FELARE [12] style energy-aware scheduling)

Batch policies (choose both task and machine from the whole batch queue):
  MINMIN  classic Min-Min (pair with minimum completion time)
  MAXMIN  classic Max-Min (task whose best completion is worst)
  EDF_MCT earliest-deadline-first task, min-completion machine
  HEFT    highest-upward-rank task (workflow DAGs; ranks precomputed by
          workload.upward_ranks), min-expected-finish machine

Cancellation (the E2C "canceled tasks" pool) is a wrapper: when
``cancel_infeasible`` is on and even the *best* machine cannot meet the
selected task's deadline, the task is cancelled instead of mapped.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import state as S
from repro.kernels import sched_argmin as K


class Decision(NamedTuple):
    task: jnp.ndarray      # i32 () task id, -1 = no-op
    machine: jnp.ndarray   # i32 () machine id, -1 = no-op
    cancel: jnp.ndarray    # bool () cancel instead of map


class SchedView(NamedTuple):
    """Precomputed tensors shared by all policies (built once per call).

    The full (N, M) completion matrix is NOT precomputed — only the two
    batch policies need it (``completion_full``); immediate policies use
    one O(M) row (``completion_row``), which cuts the per-drain-step
    work for the common case (EXPERIMENTS.md §Perf sim-cell iteration).

    ``room`` already folds in the machine-availability mask of dynamic
    scenarios (down machines never have room), so policies that respect
    ``room`` — all of them — are automatically failure-aware.
    """
    in_batch: jnp.ndarray    # bool (N,)
    room: jnp.ndarray        # bool (M,)  machine queue has space AND is up
    avail: jnp.ndarray       # f32 (M,)   earliest start time for new work
    eet_nm: jnp.ndarray      # f32 (N, M) expected exec time of task n on m
    energy_nm: jnp.ndarray   # f32 (N, M) eet * active power
    head: jnp.ndarray        # i32 ()     FIFO head of batch queue (-1 empty)
    any_room: jnp.ndarray    # bool ()
    rank: jnp.ndarray        # f32 (N,)   HEFT upward rank (StaticTables.rank;
    #                          zeros on independent workloads, where `heft`
    #                          degenerates to head-of-queue MCT)

    def completion_row(self, t) -> jnp.ndarray:
        """(M,) expected completion of task t on each machine."""
        return self.avail + self.eet_nm[t]

    def completion_full(self) -> jnp.ndarray:
        return self.avail[None, :] + self.eet_nm


BIG = jnp.float32(1e30)


def build_view(state: S.SimState, tables: S.StaticTables,
               lcap: int, const: tuple | None = None,
               up: jnp.ndarray | None = None,
               avail: jnp.ndarray | None = None) -> SchedView:
    """``const``: optional precomputed (eet_nm, energy_nm) — both are
    simulation invariants (DVFS multipliers folded in); the engine hoists
    them out of the drain loop (EXPERIMENTS.md §Perf, sim-cell iteration).
    ``up``: optional (M,) availability mask from the scenario dynamics —
    down machines are removed from ``room``.
    ``avail``: optional precomputed (M,) machine-available vector — the
    engine's drain loop computes it once per event and carries it through
    the loop with one exact add per mapped decision, instead of paying
    the O(N·M) ``queued_work`` reduction on every drain step."""
    tasks, mach = state.tasks, state.machines
    n = tasks.arrival.shape[0]
    in_batch = tasks.status == S.IN_BATCH
    # incremental integer queue counts maintained by the engine (exact)
    qc = state.mq_count
    room = qc < lcap
    if up is not None:
        room = room & up
    if avail is None:
        avail = S.machine_available(state, tables)
    if const is None:
        eet_nm = tables.eet[tasks.type_id[:, None], mach.mtype[None, :]] \
            / mach.speed[None, :]
        energy_nm = eet_nm * (tables.power[mach.mtype, 1]
                              * mach.power_scale)[None, :]
    else:
        eet_nm, energy_nm = const
    head = jnp.where(in_batch.any(),
                     jnp.argmax(in_batch), -1).astype(jnp.int32)
    return SchedView(in_batch, room, avail, eet_nm, energy_nm,
                     head, room.any(), tables.rank)


def _kernel_argmin(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """(M,) masked argmin through the Pallas kernel (docs/kernels.md).

    The kernel's contract is exact-``jnp.argmin`` tie-breaking with a -1
    sentinel on an empty mask, so substituting it for the jnp expression
    is bitwise invisible wherever the empty case is gated off anyway.
    """
    m, _ = K.masked_argmin(scores[None, :], mask[None, :],
                           interpret=K.default_interpret())
    return m.astype(jnp.int32)


def _pick_machine(view: SchedView, scores: jnp.ndarray, *,
                  kernel: bool = False) -> jnp.ndarray:
    """argmin of (M,) scores over machines with room; -1 if none."""
    if kernel:
        m = _kernel_argmin(scores, view.room)
    else:
        masked = jnp.where(view.room, scores, BIG)
        m = jnp.argmin(masked).astype(jnp.int32)
    return jnp.where(view.any_room, m, -1)


def _head_decision(view: SchedView, scores_m: jnp.ndarray, *,
                   kernel: bool = False) -> Decision:
    ok = (view.head >= 0) & view.any_room
    m = _pick_machine(view, scores_m, kernel=kernel)
    return Decision(jnp.where(ok, view.head, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32),
                    jnp.bool_(False))


# --------------------------------------------------------------------------
# Immediate policies
# --------------------------------------------------------------------------
def fcfs(state, tables, view: SchedView, rr_ptr, params, *,
         kernel: bool = False) -> Decision:
    return _head_decision(view, view.avail, kernel=kernel)


def round_robin(state, tables, view: SchedView, rr_ptr, params) -> Decision:
    n_m = view.room.shape[0]
    # first machine with room at or after rr_ptr (cyclic)
    order = (jnp.arange(n_m) + rr_ptr) % n_m
    has_room = view.room[order]
    pick = jnp.argmax(has_room)             # first True in cyclic order
    m = order[pick].astype(jnp.int32)
    ok = (view.head >= 0) & view.any_room
    return Decision(jnp.where(ok, view.head, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32), jnp.bool_(False))


def met(state, tables, view: SchedView, rr_ptr, params, *,
        kernel: bool = False) -> Decision:
    scores = jnp.where(view.head >= 0, view.eet_nm[view.head], BIG)
    return _head_decision(view, scores, kernel=kernel)


def mct(state, tables, view: SchedView, rr_ptr, params, *,
        kernel: bool = False) -> Decision:
    scores = jnp.where(view.head >= 0,
                       view.completion_row(view.head), BIG)
    return _head_decision(view, scores, kernel=kernel)


def ee_met(state, tables, view: SchedView, rr_ptr, params, *,
           kernel: bool = False) -> Decision:
    scores = jnp.where(view.head >= 0, view.energy_nm[view.head], BIG)
    return _head_decision(view, scores, kernel=kernel)


def ee_mct(state, tables, view: SchedView, rr_ptr, params, *,
           kernel: bool = False) -> Decision:
    """Min energy among deadline-feasible machines, else min completion."""
    h = jnp.maximum(view.head, 0)
    dl = state.tasks.deadline[h]
    crow = view.completion_row(h)
    feasible = (crow <= dl) & view.room
    energy = jnp.where(feasible, view.energy_nm[h], BIG)
    fallback = jnp.where(view.room, crow, BIG)
    scores = jnp.where(feasible.any(), energy, fallback)
    ok = (view.head >= 0) & view.any_room
    if kernel:
        # scores already fold the feasibility/room masking -> all-True mask
        m = _kernel_argmin(scores, jnp.ones_like(view.room))
    else:
        m = jnp.argmin(scores).astype(jnp.int32)
    return Decision(jnp.where(ok, view.head, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32), jnp.bool_(False))


# --------------------------------------------------------------------------
# Batch policies
# --------------------------------------------------------------------------
def _pair_mask(view: SchedView) -> jnp.ndarray:
    return view.in_batch[:, None] & view.room[None, :]


def minmin(state, tables, view: SchedView, rr_ptr, params) -> Decision:
    mask = _pair_mask(view)
    c = jnp.where(mask, view.completion_full(), BIG)
    flat = jnp.argmin(c)
    n_m = view.room.shape[0]
    t, m = flat // n_m, flat % n_m
    ok = mask.any()
    return Decision(jnp.where(ok, t, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32), jnp.bool_(False))


def maxmin(state, tables, view: SchedView, rr_ptr, params) -> Decision:
    mask = _pair_mask(view)
    c = jnp.where(mask, view.completion_full(), BIG)
    best_c = jnp.min(c, axis=1)              # (N,) best completion per task
    best_m = jnp.argmin(c, axis=1)           # (N,)
    task_score = jnp.where(view.in_batch & view.any_room, best_c, -BIG)
    t = jnp.argmax(task_score).astype(jnp.int32)
    ok = mask.any()
    return Decision(jnp.where(ok, t, -1).astype(jnp.int32),
                    jnp.where(ok, best_m[t], -1).astype(jnp.int32),
                    jnp.bool_(False))


def heft(state, tables, view: SchedView, rr_ptr, params, *,
         kernel: bool = False) -> Decision:
    """HEFT-style list scheduling (Topcuoglu et al.): pick the queued task
    with the highest *upward rank* (critical-path length from the task to
    a DAG exit, precomputed host-side by ``workload.upward_ranks`` and
    threaded in through ``StaticTables.rank``), then map it to the
    machine with the earliest expected finish time.  On independent
    workloads every rank is zero, so the policy degenerates to
    head-of-queue + min completion (MCT)."""
    score = jnp.where(view.in_batch, view.rank, -BIG)
    t = jnp.argmax(score).astype(jnp.int32)
    ok = view.in_batch.any() & view.any_room
    m = _pick_machine(view, view.completion_row(t), kernel=kernel)
    return Decision(jnp.where(ok, t, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32), jnp.bool_(False))


def edf_mct(state, tables, view: SchedView, rr_ptr, params, *,
            kernel: bool = False) -> Decision:
    dl = jnp.where(view.in_batch, state.tasks.deadline, BIG)
    t = jnp.argmin(dl).astype(jnp.int32)
    ok = view.in_batch.any() & view.any_room
    scores = view.completion_row(t)
    m = _pick_machine(view, scores, kernel=kernel)
    return Decision(jnp.where(ok, t, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32), jnp.bool_(False))


# --------------------------------------------------------------------------
# Fused Pallas variants (docs/kernels.md)
# --------------------------------------------------------------------------
def _scaled_eet_table(state, tables) -> jnp.ndarray:
    """(T, M) DVFS/speed-scaled EET table for the fused kernels.

    ``(eet[:, mtype] / speed)[type_id]`` is elementwise the same float
    division as the engine's hoisted ``eet_nm`` gather, so the fused path
    sees bitwise-identical completion times without the (N, M) matrix.
    """
    return tables.eet[:, state.machines.mtype] / state.machines.speed[None, :]


def minmin_pallas(state, tables, view: SchedView, rr_ptr, params) -> Decision:
    """`minmin` with the mask + gather + completion + argmin fused into
    one Pallas kernel — nothing O(N·M) is materialized."""
    flat, _ = K.fused_minmin(view.avail, view.in_batch, view.room,
                             state.tasks.type_id,
                             _scaled_eet_table(state, tables),
                             interpret=K.default_interpret())
    n_m = view.room.shape[0]
    f = jnp.maximum(flat, 0)
    ok = view.in_batch.any() & view.any_room
    return Decision(jnp.where(ok, f // n_m, -1).astype(jnp.int32),
                    jnp.where(ok, f % n_m, -1).astype(jnp.int32),
                    jnp.bool_(False))


def maxmin_pallas(state, tables, view: SchedView, rr_ptr, params) -> Decision:
    """`maxmin` with the per-task row minima and the running (task,
    machine) argmax pair carried in SMEM scratch across grid steps."""
    t, m, _ = K.fused_maxmin(view.avail, view.in_batch, view.room,
                             state.tasks.type_id,
                             _scaled_eet_table(state, tables),
                             interpret=K.default_interpret())
    ok = view.in_batch.any() & view.any_room
    return Decision(jnp.where(ok, t, -1).astype(jnp.int32),
                    jnp.where(ok, m, -1).astype(jnp.int32), jnp.bool_(False))


PolicyFn = Callable[..., Decision]

SCHEDULERS: dict[str, PolicyFn] = {
    "fcfs": fcfs,
    "rr": round_robin,
    "met": met,
    "mct": mct,
    "ee_met": ee_met,
    "ee_mct": ee_mct,
    "minmin": minmin,
    "maxmin": maxmin,
    "edf_mct": edf_mct,
    "heft": heft,
}
POLICY_NAMES = list(SCHEDULERS)
POLICY_IDS = {n: i for i, n in enumerate(POLICY_NAMES)}
BATCH_POLICIES = {"minmin", "maxmin", "edf_mct", "heft"}

# Kernel-backed variants substituted into the lax.switch branch list when
# ``SimParams(pallas=True)``.  Policies without an entry (``rr`` has no
# argmin; learned / user-registered policies own their scoring) fall back
# to their jnp implementation — the flag is a per-policy no-op there.
PALLAS_SCHEDULERS: dict[str, PolicyFn] = {
    "fcfs": functools.partial(fcfs, kernel=True),
    "met": functools.partial(met, kernel=True),
    "mct": functools.partial(mct, kernel=True),
    "ee_met": functools.partial(ee_met, kernel=True),
    "ee_mct": functools.partial(ee_mct, kernel=True),
    "minmin": minmin_pallas,
    "maxmin": maxmin_pallas,
    "edf_mct": functools.partial(edf_mct, kernel=True),
    "heft": functools.partial(heft, kernel=True),
}


def register_policy(name: str, fn: PolicyFn) -> int:
    """Plug in a user-defined scheduling method (paper feature (ii))."""
    if name in SCHEDULERS:
        raise ValueError(f"policy {name!r} already registered")
    SCHEDULERS[name] = fn
    POLICY_NAMES.append(name)
    POLICY_IDS[name] = len(POLICY_NAMES) - 1
    return POLICY_IDS[name]


def _switch_policy(policy_id, state, tables, view, params, *,
                   pallas: bool = False) -> Decision:
    """One ``lax.switch`` over the registered policy table + the
    cancellation wrapper, evaluated against the given view."""
    table = {**SCHEDULERS, **PALLAS_SCHEDULERS} if pallas else SCHEDULERS
    branches = [
        (lambda fn: (lambda args: fn(*args)))(table[n])
        for n in POLICY_NAMES
    ]
    return jax.lax.switch(policy_id, branches,
                          (state, tables, view, state.rr_ptr, params))


def _cancel_wrap(dec: Decision, view: SchedView, state: S.SimState,
                 cancel_infeasible) -> Decision:
    # Cancellation wrapper: if even the best machine cannot meet the selected
    # task's deadline, cancel it (E2C's "canceled tasks" pool).
    t = jnp.maximum(dec.task, 0)
    best_completion = jnp.min(
        jnp.where(view.room, view.completion_row(t), BIG))
    infeasible = best_completion > state.tasks.deadline[t]
    cancel = (dec.task >= 0) & jnp.asarray(cancel_infeasible) & infeasible
    return Decision(dec.task, dec.machine, cancel)


def dispatch(policy_id: jnp.ndarray, state: S.SimState,
             tables: S.StaticTables, lcap: int,
             cancel_infeasible: bool | jnp.ndarray,
             const: tuple | None = None,
             up: jnp.ndarray | None = None,
             params=None, *, pallas: bool = False,
             avail: jnp.ndarray | None = None) -> Decision:
    """Run the selected policy + the cancellation wrapper.

    ``params`` is the learned-policy weight pytree shared by every
    branch (``neural.PolicyParams``); the engine always materializes one
    (default zeros) so the switch operands have a fixed structure.

    ``pallas`` (static, like the engine's ``trace=``) swaps the fused
    kernel variants (``PALLAS_SCHEDULERS``) into the switch branch list;
    off compiles the identical pre-kernel HLO.  The kernels' exact
    jnp-argmin tie-breaking keeps results bitwise identical either way
    (docs/kernels.md).

    ``avail`` optionally short-circuits the O(N·M) machine-availability
    reduction with the engine's carried vector (docs/engine_perf.md).
    """
    if params is None:
        from repro.core import neural as NN
        params = NN.default_params()
    view = build_view(state, tables, lcap, const, up, avail)
    dec = _switch_policy(policy_id, state, tables, view, params,
                         pallas=pallas)
    return _cancel_wrap(dec, view, state, cancel_infeasible)
