"""Jit'd public wrappers for the Pallas kernels.

On a CPU backend the kernels execute in ``interpret=True`` mode —
the kernel body runs in Python against the same BlockSpec tiling, which is
what the correctness tests validate.  On a real TPU backend the same calls
compile to Mosaic.  ``interpret`` can be forced either way for tests.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.grouped_matmul import grouped_matmul as _gmm
from repro.kernels.sched_argmin import default_interpret as _default_interpret
from repro.kernels.sched_argmin import fused_maxmin as _maxmin
from repro.kernels.sched_argmin import fused_minmin as _minmin
from repro.kernels.sched_argmin import masked_argmin as _argmin


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """(BH, Sq, hd) x (BH, Sk, hd)^2 -> (BH, Sq, hd)."""
    it = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, interpret=it)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def masked_argmin(values, mask, *, block_n: int = 256,
                  interpret: bool | None = None):
    """(N, M) masked argmin -> (flat_idx i32, min f32)."""
    it = _default_interpret() if interpret is None else interpret
    return _argmin(values, mask, block_n=block_n, interpret=it)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_minmin(avail, in_batch, room, type_id, eet_m, *,
                 block_n: int = 256, interpret: bool | None = None):
    """Fused Min-Min pair: (M,) avail + (N,) batch/type + (T, M) EET
    -> (flat_idx i32, min f32); no valid pair -> (-1, BIG)."""
    it = _default_interpret() if interpret is None else interpret
    return _minmin(avail, in_batch, room, type_id, eet_m,
                   block_n=block_n, interpret=it)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_maxmin(avail, in_batch, room, type_id, eet_m, *,
                 block_n: int = 256, interpret: bool | None = None):
    """Fused Max-Min pair -> (task i32, machine i32, score f32); no
    valid pair -> (-1, -1, -BIG)."""
    it = _default_interpret() if interpret is None else interpret
    return _maxmin(avail, in_batch, room, type_id, eet_m,
                   block_n=block_n, interpret=it)


@partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def grouped_matmul(lhs, rhs, group_sizes, *, block_c: int = 128,
                   block_f: int = 128, interpret: bool | None = None):
    """(G, C, D) x (G, D, F) + (G,) sizes -> (G, C, F)."""
    it = _default_interpret() if interpret is None else interpret
    return _gmm(lhs, rhs, group_sizes, block_c=block_c, block_f=block_f,
                interpret=it)


__all__ = ["flash_attention", "masked_argmin", "fused_minmin",
           "fused_maxmin", "grouped_matmul", "ref"]
