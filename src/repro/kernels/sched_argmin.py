"""Pallas kernels for the E2C scheduler's inner reductions.

MCT / Min-Min / Max-Min all reduce a masked (tasks x machines) completion-
time matrix to an argmin pair — the one compute hot-spot of the paper's
artifact when sweeping thousands of replicas with large task batches.

The family (docs/kernels.md):

  masked_argmin  (N, M) values + mask -> (flat_idx, min).  The generic
                 reduction every immediate policy pays once per drain step
                 (M-row argmin) and the building block of the oracles.
  fused_minmin   mask + DVFS-scaled EET gather + completion compute +
                 flat argmin in one kernel: the (N, M) completion matrix
                 is never materialized in HBM.  Backs the `minmin` policy.
  fused_maxmin   same fusion, but per-task row minima feed a running
                 argmax: the Max-Min (task, machine) pair in one pass.
  fused_start_pick / fused_event_bounds
                 the event loop's per-machine FIFO-head pick and its
                 next-arrival / next-deadline minima.

Layout (what Mosaic accepts on a TPU): every operand is 2-D and every
block is either the whole dimension or a multiple of the (8, 128) tile.
Per-task vectors ride the lane axis as (1, N) rows, per-machine vectors
the sublane axis as (M, 1) columns, so a fused tile is (M, block_n) and
the task axis is padded to whole blocks.  Masks are int32, not bool.
The running winner is carried across the sequential grid in (1, 1)
VMEM scratch and written out once, at the last block.

Contract (shared with kernels/ref.py and schedulers._pick_machine):
  * tie-breaking matches ``jnp.argmin`` / ``jnp.argmax`` exactly — first
    flat index, row-major — so engine results are bitwise identical when
    the kernels are switched in (``SimParams(pallas=True)``).  Within a
    block an argmin is ``min`` then the least index attaining it; across
    blocks only a strict improvement replaces the carry;
  * an all-False mask returns the (-1, BIG) sentinel (the schedulers'
    "no feasible pair" answer) instead of a bogus index 0;
  * masked cells compare as BIG (1e30): a *valid* cell >= BIG loses to
    the first masked cell exactly as it does under ``jnp.argmin`` of
    ``where(mask, v, BIG)``; pad cells compare as +inf, after every real
    cell.  NaNs are out of contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30  # python float: jnp constants would be captured tracers in pallas
INT_MAX = 2**31 - 1   # python int, same reason as BIG
INF = float("inf")
LANES = 128
SUBLANES = 8


def default_interpret() -> bool:
    """Pallas kernels interpret everywhere but on a real TPU backend."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _blocks(n: int, block_n: int, align: int) -> tuple[int, int]:
    """(block, n_blocks) tiling ``n``: one whole-dimension block when it
    fits, else ``align``-multiple blocks over a padded axis."""
    if n <= block_n:
        return n, 1
    bn = _round_up(block_n, align)
    return bn, -(-n // bn)


def _pad_to(x: jnp.ndarray, n: int, axis: int, value=0) -> jnp.ndarray:
    pad = n - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _row(x: jnp.ndarray, n: int, value=0) -> jnp.ndarray:
    """(N,) per-task vector -> (1, n) int32/f32 lane row, padded."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    return _pad_to(x, n, 0, value)[None, :]


def _col(x: jnp.ndarray) -> jnp.ndarray:
    """(M,) per-machine vector -> (M, 1) sublane column."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    return x[:, None]


def _min11(x):
    """Full reduction to a (1, 1) array (two keepdims passes)."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _max11(x):
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _first_min(vals, idx):
    """(1, 1) min of ``vals`` and the least ``idx`` attaining it — argmin
    with jnp's first-index tie-break, built from two ``min`` passes."""
    vmin = _min11(vals)
    return vmin, _min11(jnp.where(vals == vmin, idx, INT_MAX))


def _carry(i, n_blocks, better, new, scr, finalize):
    """Fold one block's candidate into the (1, 1) VMEM carry.

    ``new``/``scr`` are parallel tuples (key, payload..., any-flag):
    block 0 initializes, later blocks replace key and payloads only where
    ``better(new_key, old_key)`` holds, and OR the any-valid flag.
    ``finalize(*carry)`` runs at the last block."""
    @pl.when(i == 0)
    def _init():
        for ref, v in zip(scr, new):
            ref[...] = v

    @pl.when(i > 0)
    def _merge():
        imp = better(new[0], scr[0][...])
        for ref, v in zip(scr[:-1], new[:-1]):
            ref[...] = jnp.where(imp, v, ref[...])
        scr[-1][...] = jnp.maximum(scr[-1][...], new[-1])

    @pl.when(i == n_blocks - 1)
    def _fin():
        finalize(*(ref[...] for ref in scr))


# --------------------------------------------------------------------------
# masked argmin
# --------------------------------------------------------------------------
def _argmin_kernel(val_ref, mask_ref, idx_out, min_out,
                   min_scr, idx_scr, any_scr, *,
                   bn: int, m: int, n_blocks: int, n_total: int):
    i = pl.program_id(0)
    rows = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, m), 1)
    real = rows < n_total
    valid = (mask_ref[...] != 0) & real
    vals = jnp.where(real, jnp.where(valid, val_ref[...], BIG), INF)
    vmin, imin = _first_min(vals, rows * m + cols)
    found = _max11(valid.astype(jnp.int32))

    def fin(vmin, imin, found):
        idx_out[...] = jnp.where(found > 0, imin, -1)
        min_out[...] = jnp.where(found > 0, vmin, BIG)

    _carry(i, n_blocks, jnp.less, (vmin, imin, found),
           (min_scr, idx_scr, any_scr), fin)


def masked_argmin(values: jnp.ndarray, mask: jnp.ndarray, *,
                  block_n: int = 256, interpret: bool = False):
    """(N, M) masked argmin -> (flat_idx i32, min f32).

    Empty mask -> the (-1, BIG) sentinel; otherwise identical (index and
    value) to ``jnp.argmin(jnp.where(mask, values, BIG))``.  Rows tile
    the sublane axis; M stays whole on the lane axis.
    """
    N, M = values.shape
    bn, n_blocks = _blocks(N, block_n, SUBLANES)
    values = _pad_to(values.astype(jnp.float32), bn * n_blocks, 0)
    mask = _pad_to(mask.astype(jnp.int32), bn * n_blocks, 0)
    kernel = functools.partial(_argmin_kernel, bn=bn, m=M,
                               n_blocks=n_blocks, n_total=N)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    idx, vmin = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((bn, M), lambda i: (i, 0)),
                  pl.BlockSpec((bn, M), lambda i: (i, 0))],
        out_specs=[scalar, scalar],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.int32),
                        pltpu.VMEM((1, 1), jnp.int32)],
        interpret=interpret,
    )(values, mask)
    return idx[0, 0], vmin[0, 0]


# --------------------------------------------------------------------------
# fused dispatch kernels: mask + EET gather + completion + reduction
# --------------------------------------------------------------------------
def _completion_block(avail_ref, room_ref, eet_ref, inb_ref, tid_ref,
                      i, bn, m, t, n_total):
    """One (m, bn) machine-major tile of the masked completion matrix.

    ``eet_ref`` is the (M, T) *type*-level DVFS-scaled EET table
    (machine speed already divided in); the per-task gather is a select
    over the T type columns, so the (N, M) matrix never exists in HBM.
    """
    tid = tid_ref[...]                                        # (1, bn)
    eet = eet_ref[...]                                        # (m, t)
    cm = jnp.broadcast_to(eet[:, 0:1], (m, bn))
    for k in range(1, t):
        cm = jnp.where(tid == k, eet[:, k:k + 1], cm)
    comp = avail_ref[...] + cm                                # (m, bn)
    task = i * bn + jax.lax.broadcasted_iota(jnp.int32, (m, bn), 1)
    mach = jax.lax.broadcasted_iota(jnp.int32, (m, bn), 0)
    real = task < n_total
    valid = (inb_ref[...] != 0) & (room_ref[...] != 0) & real
    return comp, valid, real, task, mach


def _minmin_kernel(avail_ref, room_ref, eet_ref, inb_ref, tid_ref,
                   idx_out, min_out, min_scr, idx_scr, any_scr, *,
                   bn: int, m: int, t: int, n_blocks: int, n_total: int):
    i = pl.program_id(0)
    comp, valid, real, task, mach = _completion_block(
        avail_ref, room_ref, eet_ref, inb_ref, tid_ref, i, bn, m, t, n_total)
    vals = jnp.where(real, jnp.where(valid, comp, BIG), INF)
    vmin, imin = _first_min(vals, task * m + mach)
    found = _max11(valid.astype(jnp.int32))

    def fin(vmin, imin, found):
        idx_out[...] = jnp.where(found > 0, imin, -1)
        min_out[...] = jnp.where(found > 0, vmin, BIG)

    _carry(i, n_blocks, jnp.less, (vmin, imin, found),
           (min_scr, idx_scr, any_scr), fin)


def _maxmin_kernel(avail_ref, room_ref, eet_ref, inb_ref, tid_ref,
                   task_out, mach_out, score_out,
                   max_scr, task_scr, mach_scr, any_scr, *,
                   bn: int, m: int, t: int, n_blocks: int, n_total: int):
    i = pl.program_id(0)
    comp, valid, real, task, mach = _completion_block(
        avail_ref, room_ref, eet_ref, inb_ref, tid_ref, i, bn, m, t, n_total)
    c = jnp.where(valid, comp, BIG)                           # (m, bn)
    rowmin = jnp.min(c, axis=0, keepdims=True)                # (1, bn)
    rowarg = jnp.min(jnp.where(c == rowmin, mach, INT_MAX),   # first index
                     axis=0, keepdims=True)
    trow = task[0:1, :]
    score = jnp.where(inb_ref[...] != 0, rowmin, -BIG)
    score = jnp.where(trow < n_total, score, -INF)            # (1, bn)
    smax = _max11(score)
    j = _min11(jnp.where(score == smax, trow, INT_MAX))      # first max
    gmach = _min11(jnp.where(trow == j, rowarg, INT_MAX))
    found = _max11(valid.astype(jnp.int32))

    def fin(smax, j, gmach, found):
        task_out[...] = jnp.where(found > 0, j, -1)
        mach_out[...] = jnp.where(found > 0, gmach, -1)
        score_out[...] = jnp.where(found > 0, smax, -BIG)

    _carry(i, n_blocks, jnp.greater, (smax, j, gmach, found),
           (max_scr, task_scr, mach_scr, any_scr), fin)


def _fused_call(kernel, avail, in_batch, room, type_id, eet_m, block_n,
                outs, scratch, interpret):
    M = avail.shape[0]
    T = eet_m.shape[0]
    n = in_batch.shape[0]
    bn, n_blocks = _blocks(n, block_n, LANES)
    npad = bn * n_blocks
    kernel = functools.partial(kernel, bn=bn, m=M, t=T,
                               n_blocks=n_blocks, n_total=n)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[full((M, 1)), full((M, 1)), full((M, T)),
                  pl.BlockSpec((1, bn), lambda i: (0, i)),
                  pl.BlockSpec((1, bn), lambda i: (0, i))],
        out_specs=[full((1, 1)) for _ in outs],
        out_shape=[jax.ShapeDtypeStruct((1, 1), d) for d in outs],
        scratch_shapes=[pltpu.VMEM((1, 1), d) for d in scratch],
        interpret=interpret,
    )(_col(avail.astype(jnp.float32)), _col(room),
      eet_m.astype(jnp.float32).T, _row(in_batch, npad),
      _row(type_id.astype(jnp.int32), npad))
    return tuple(o[0, 0] for o in out)


def fused_minmin(avail: jnp.ndarray, in_batch: jnp.ndarray,
                 room: jnp.ndarray, type_id: jnp.ndarray,
                 eet_m: jnp.ndarray, *, block_n: int = 256,
                 interpret: bool = False):
    """Min-Min inner loop in one kernel -> (flat_idx i32, min f32).

    ``eet_m`` is the (T, M) speed-scaled EET table
    (``tables.eet[:, mtype] / speed``); the (N, M) gather + completion +
    mask + argmin all happen per VMEM tile, so nothing O(N·M) is
    materialized.  No valid (in_batch, room) pair -> (-1, BIG).
    """
    return _fused_call(_minmin_kernel, avail, in_batch, room, type_id,
                       eet_m, block_n, (jnp.int32, jnp.float32),
                       (jnp.float32, jnp.int32, jnp.int32), interpret)


def fused_maxmin(avail: jnp.ndarray, in_batch: jnp.ndarray,
                 room: jnp.ndarray, type_id: jnp.ndarray,
                 eet_m: jnp.ndarray, *, block_n: int = 256,
                 interpret: bool = False):
    """Max-Min inner loop in one kernel -> (task i32, machine i32, score).

    Per-task minima of the masked completion matrix feed a running argmax
    carried across grid steps; the winning task's first-index best
    machine rides along.  No valid (in_batch, room) pair -> (-1, -1, -BIG).
    """
    return _fused_call(_maxmin_kernel, avail, in_batch, room, type_id,
                       eet_m, block_n, (jnp.int32, jnp.int32, jnp.float32),
                       (jnp.float32, jnp.int32, jnp.int32, jnp.int32),
                       interpret)


# --------------------------------------------------------------------------
# fused event-loop kernels: start-pick and next-event reductions
# --------------------------------------------------------------------------
def _start_pick_kernel(status_ref, machine_ref, seq_ref, pick_out, has_out,
                       best_scr, task_scr, any_scr, *,
                       bn: int, m: int, n_blocks: int, in_mq: int):
    """Segmented per-machine lowest-seq pick for ``engine._start_tasks``.

    Each grid step builds one (m, bn) membership tile in-register — the
    (N, M) queued mask never exists in HBM — and folds its per-machine
    minima into the (m, 1) running (best seq, task id, any) carried
    across blocks.  Tie-breaking matches ``jnp.argmin(seqs, axis=0)``
    exactly: within a block the least task id attaining the minimum, and
    across blocks only a strict improvement replaces the incumbent, so
    the lowest task id among equal seqs (including the all-INT_MAX empty
    column) wins.
    """
    i = pl.program_id(0)
    mcol = jax.lax.broadcasted_iota(jnp.int32, (m, bn), 0)
    task = i * bn + jax.lax.broadcasted_iota(jnp.int32, (m, bn), 1)
    valid = (status_ref[...] == in_mq) & (machine_ref[...] == mcol)
    seqs = jnp.where(valid, seq_ref[...], INT_MAX)           # (m, bn)
    bmin = jnp.min(seqs, axis=1, keepdims=True)               # (m, 1)
    btask = jnp.min(jnp.where(seqs == bmin, task, INT_MAX),
                    axis=1, keepdims=True)
    bany = jnp.max(valid.astype(jnp.int32), axis=1, keepdims=True)

    def fin(best, task, found):
        pick_out[...] = task
        has_out[...] = found

    _carry(i, n_blocks, jnp.less, (bmin, btask, bany),
           (best_scr, task_scr, any_scr), fin)


def fused_start_pick(status: jnp.ndarray, machine: jnp.ndarray,
                     seq: jnp.ndarray, n_machines: int, *,
                     in_mq: int = 2, block_n: int = 256,
                     interpret: bool = False):
    """Per-machine FIFO head -> (pick (M,) i32, has (M,) bool).

    Identical (index and flag) to the engine's materialized path:
    ``argmin(where(queued, seq[:, None], INT_MAX), axis=0)`` plus
    ``queued.any(axis=0)`` where ``queued = (status == IN_MQ) &
    (machine == arange(M))`` — integer seqs, so equality is exact.
    """
    n = status.shape[0]
    m = n_machines
    bn, n_blocks = _blocks(n, block_n, LANES)
    npad = bn * n_blocks
    kernel = functools.partial(_start_pick_kernel, bn=bn, m=m,
                               n_blocks=n_blocks, in_mq=in_mq)
    lane = pl.BlockSpec((1, bn), lambda i: (0, i))
    col = pl.BlockSpec((m, 1), lambda i: (0, 0))
    pick, has = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[lane, lane, lane],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((m, 1), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((m, 1), jnp.int32) for _ in range(3)],
        interpret=interpret,
    )(_row(status, npad, -1), _row(machine, npad, -1),
      _row(seq, npad, INT_MAX))
    return pick[:, 0], has[:, 0] > 0


def _event_bounds_kernel(status_ref, arrival_ref, deadline_ref,
                         arr_out, dl_out, arr_scr, dl_scr, *,
                         n_blocks: int, not_arrived: int,
                         live_lo: int, live_hi: int):
    """Fused next-event reduction: one pass over the task table computes
    the pending-arrival minimum (status == NOT_ARRIVED) and the live-
    deadline minimum (IN_BATCH/IN_MQ/RUNNING, a contiguous status range)
    together.  ``min`` is exact and order-independent, so the result is
    bitwise identical to the two separate ``jnp.min(where(...))``
    reductions it replaces."""
    i = pl.program_id(0)
    st = status_ref[...]
    a = _min11(jnp.where(st == not_arrived, arrival_ref[...], INF))
    d = _min11(jnp.where((st >= live_lo) & (st <= live_hi),
                         deadline_ref[...], INF))

    @pl.when(i == 0)
    def _init():
        arr_scr[...] = a
        dl_scr[...] = d

    @pl.when(i > 0)
    def _merge():
        arr_scr[...] = jnp.minimum(arr_scr[...], a)
        dl_scr[...] = jnp.minimum(dl_scr[...], d)

    @pl.when(i == n_blocks - 1)
    def _finalize():
        arr_out[...] = arr_scr[...]
        dl_out[...] = dl_scr[...]


def fused_event_bounds(status: jnp.ndarray, arrival: jnp.ndarray,
                       deadline: jnp.ndarray, *, not_arrived: int = 0,
                       live_lo: int = 1, live_hi: int = 3,
                       block_n: int = 256, interpret: bool = False):
    """Next-event candidates -> (t_arr f32 (), t_dl f32 ()).

    Bitwise equal to ``jnp.min(where(status == NOT_ARRIVED, arrival,
    inf))`` and ``jnp.min(where(live, deadline, inf))`` with ``live``
    the IN_BATCH..RUNNING status range; empty masks return +inf.
    """
    n = status.shape[0]
    bn, n_blocks = _blocks(n, block_n, LANES)
    npad = bn * n_blocks
    kernel = functools.partial(_event_bounds_kernel, n_blocks=n_blocks,
                               not_arrived=not_arrived, live_lo=live_lo,
                               live_hi=live_hi)
    lane = pl.BlockSpec((1, bn), lambda i: (0, i))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    t_arr, t_dl = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[lane, lane, lane],
        out_specs=[scalar, scalar],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32) for _ in range(2)],
        interpret=interpret,
    )(_row(status, npad, -1), _row(arrival, npad), _row(deadline, npad))
    return t_arr[0, 0], t_dl[0, 0]
