"""Kernel-vs-jnp-oracle differential battery for the fused dispatch family.

Every Pallas kernel behind ``SimParams(pallas=True)`` (docs/kernels.md) is
pinned here against its materialized-jnp oracle (``kernels/ref.py``) in
interpret mode, so the battery is CI-safe on CPU.  The contract under
test:

  * tie-breaking == ``jnp.argmin``/``jnp.argmax`` exactly (first flat
    index, row-major) — the property that makes the engine bitwise
    identical under the flag;
  * an all-False mask returns the (-1, BIG) / (-1, -1, -BIG) sentinel;
  * masked cells compare as BIG, so ±inf / >= BIG valid values behave
    exactly as they do under ``jnp.argmin(where(mask, v, BIG))``;
  * ragged task dims (N not a multiple of block_n) never leak pad rows.

Hypothesis properties extend the fixed cases when the dev extra is
installed; without it they collect as skips (tests/_hyp.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis optional (dev extra)

from repro.kernels import ops, ref

pytestmark = pytest.mark.pallas

BIG = float(jnp.float32(1e30))


def _argmin_case(vals, mask, bn=8):
    ki, kv = ops.masked_argmin(jnp.asarray(vals), jnp.asarray(mask),
                               block_n=bn, interpret=True)
    ri, rv = ref.masked_argmin_ref(jnp.asarray(vals), jnp.asarray(mask))
    assert int(ki) == int(ri)
    assert float(kv) == float(rv)     # bitwise, not allclose
    return int(ki), float(kv)


def _fused_instance(seed, n, m, t):
    rng = np.random.default_rng(seed)
    avail = jnp.asarray(rng.uniform(0, 20, m).astype(np.float32))
    in_batch = jnp.asarray(rng.random(n) < 0.5)
    room = jnp.asarray(rng.random(m) < 0.7)
    type_id = jnp.asarray(rng.integers(0, t, n).astype(np.int32))
    eet_m = jnp.asarray(rng.uniform(0.1, 9.0, (t, m)).astype(np.float32))
    return avail, in_batch, room, type_id, eet_m


def _assert_minmin(args, bn=128):
    ki, kv = ops.fused_minmin(*args, block_n=bn, interpret=True)
    ri, rv = ref.fused_minmin_ref(*args)
    assert int(ki) == int(ri)
    assert float(kv) == float(rv)


def _assert_maxmin(args, bn=128):
    kt, km, ks = ops.fused_maxmin(*args, block_n=bn, interpret=True)
    rt, rm, rs = ref.fused_maxmin_ref(*args)
    assert (int(kt), int(km)) == (int(rt), int(rm))
    assert float(ks) == float(rs)


# ---------------------------------------------------------------------------
# masked_argmin: fixed adversarial cases
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,bn", [
    (5, 3, 4),          # ragged tail, tiny
    (24, 4, 8),         # engine-shaped
    (300, 7, 256),      # ragged tail across the default block size
    (1, 1, 8),          # degenerate single cell
    (17, 5, 8),         # ragged, odd machine count
    (64, 8, 64),        # single block, exact fit
])
def test_masked_argmin_random_shapes(n, m, bn):
    rng = np.random.default_rng(n * 31 + m)
    vals = rng.standard_normal((n, m)).astype(np.float32)
    mask = rng.random((n, m)) < 0.6
    _argmin_case(vals, mask, bn)


def test_all_false_mask_sentinel():
    idx, vmin = _argmin_case(np.ones((20, 3), np.float32),
                             np.zeros((20, 3), bool), bn=8)
    assert (idx, vmin) == (-1, BIG)


def test_all_false_mask_sentinel_ragged():
    idx, vmin = _argmin_case(-np.ones((21, 3), np.float32),
                             np.zeros((21, 3), bool), bn=8)
    assert (idx, vmin) == (-1, BIG)


def test_single_valid_cell():
    """Exactly one unmasked cell — it must win regardless of its value."""
    vals = np.zeros((40, 6), np.float32)
    vals[23, 4] = 7.5                     # worse than every masked zero
    mask = np.zeros((40, 6), bool)
    mask[23, 4] = True
    idx, vmin = _argmin_case(vals, mask, bn=16)
    assert (idx, vmin) == (23 * 6 + 4, 7.5)


def test_single_valid_cell_in_pad_tail_block():
    """The lone valid cell sits in the ragged final block."""
    vals = np.full((33, 4), 2.0, np.float32)
    mask = np.zeros((33, 4), bool)
    mask[32, 1] = True
    idx, vmin = _argmin_case(vals, mask, bn=16)
    assert (idx, vmin) == (32 * 4 + 1, 2.0)


def test_duplicate_minima_first_flat_index():
    """Ties resolve to the first flat index — within a block and across
    blocks (a later block must not steal an equal minimum)."""
    vals = np.full((50, 4), 3.0, np.float32)
    vals[[7, 29, 41], [2, 0, 3]] = 1.0    # three equal global minima
    mask = np.ones((50, 4), bool)
    idx, _ = _argmin_case(vals, mask, bn=16)
    assert idx == 7 * 4 + 2


def test_duplicate_minima_everywhere():
    idx, vmin = _argmin_case(np.zeros((37, 5), np.float32),
                             np.ones((37, 5), bool), bn=16)
    assert (idx, vmin) == (0, 0.0)


def test_neg_inf_valid_cell_wins():
    vals = np.ones((22, 3), np.float32)
    vals[13, 1] = -np.inf
    _argmin_case(vals, np.ones((22, 3), bool), bn=8)


def test_pos_inf_valid_cells_lose_to_masked_big():
    """All valid cells are +inf: under the jnp oracle the first *masked*
    cell (compared as BIG < inf) wins — the kernel must agree exactly."""
    vals = np.full((18, 3), np.inf, np.float32)
    mask = np.ones((18, 3), bool)
    mask[9, 2] = False
    idx, vmin = _argmin_case(vals, mask, bn=8)
    assert (idx, vmin) == (9 * 3 + 2, BIG)


def test_values_above_big_match_oracle():
    """Valid cells >= BIG are indistinguishable from masked cells under
    the where(mask, v, BIG) contract; both paths must agree."""
    vals = np.full((12, 4), 2e30, np.float32)
    mask = np.ones((12, 4), bool)
    mask[5, 1] = False
    _argmin_case(vals, mask, bn=8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes_match_oracle(dtype):
    """bf16 inputs are upcast to f32 at load in both kernel and oracle,
    so results (index AND value) stay bitwise equal."""
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.standard_normal((30, 5)), dtype)
    mask = jnp.asarray(rng.random((30, 5)) < 0.5)
    _argmin_case(vals, mask, bn=8)


def test_vmapped_kernel_matches_per_replica():
    """The run_sweep path: vmap over the pallas_call batches cleanly."""
    rng = np.random.default_rng(11)
    vs = jnp.asarray(rng.standard_normal((6, 19, 4)).astype(np.float32))
    mks = jnp.asarray(rng.random((6, 19, 4)) < 0.5)
    bi, bv = jax.vmap(
        lambda v, mk: ops.masked_argmin(v, mk, block_n=8, interpret=True)
    )(vs, mks)
    for i in range(6):
        ri, rv = ref.masked_argmin_ref(vs[i], mks[i])
        assert int(bi[i]) == int(ri)
        assert float(bv[i]) == float(rv)


# ---------------------------------------------------------------------------
# fused min-min / max-min
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,n,m,t,bn", [
    (0, 24, 4, 3, 128),     # engine-shaped, one whole-axis block
    (1, 300, 6, 5, 128),    # ragged tail
    (2, 7, 2, 2, 128),      # tiny
    (3, 512, 8, 4, 128),    # multi-block exact fit
    (4, 129, 5, 3, 64),     # ragged across blocks
    (5, 1, 1, 1, 128),      # degenerate
])
def test_fused_pair_kernels_match_oracle(seed, n, m, t, bn):
    args = _fused_instance(seed, n, m, t)
    _assert_minmin(args, bn)
    _assert_maxmin(args, bn)


def test_fused_empty_batch_sentinel():
    avail, _, room, tid, eet_m = _fused_instance(6, 300, 4, 2)
    args = (avail, jnp.zeros(300, bool), room, tid, eet_m)
    ki, kv = ops.fused_minmin(*args, block_n=128, interpret=True)
    assert (int(ki), float(kv)) == (-1, BIG)
    kt, km, ks = ops.fused_maxmin(*args, block_n=128, interpret=True)
    assert (int(kt), int(km)) == (-1, -1)
    _assert_minmin(args)
    _assert_maxmin(args)


def test_fused_no_room_sentinel():
    avail, inb, _, tid, eet_m = _fused_instance(7, 300, 4, 2)
    args = (avail, inb, jnp.zeros(4, bool), tid, eet_m)
    ki, _ = ops.fused_minmin(*args, block_n=128, interpret=True)
    kt, km, _ = ops.fused_maxmin(*args, block_n=128, interpret=True)
    assert int(ki) == int(kt) == int(km) == -1
    _assert_minmin(args)
    _assert_maxmin(args)


def test_fused_single_valid_pair():
    avail, _, _, tid, eet_m = _fused_instance(8, 300, 5, 3)
    inb = jnp.zeros(300, bool).at[217].set(True)
    room = jnp.zeros(5, bool).at[3].set(True)
    args = (avail, inb, room, tid, eet_m)
    ki, _ = ops.fused_minmin(*args, block_n=128, interpret=True)
    assert int(ki) == 217 * 5 + 3
    kt, km, _ = ops.fused_maxmin(*args, block_n=128, interpret=True)
    assert (int(kt), int(km)) == (217, 3)
    _assert_minmin(args)
    _assert_maxmin(args)


def test_fused_duplicate_completions_tie_break():
    """Identical EET rows + equal availability => every pair ties; both
    kernels must pick jnp's first index (task-major for min-min; for
    max-min the first queued task and its first machine)."""
    n, m = 300, 4
    avail = jnp.zeros(m)
    inb = jnp.ones(n, bool).at[0].set(False)     # first queued task is #1
    room = jnp.ones(m, bool)
    tid = jnp.zeros(n, jnp.int32)
    eet_m = jnp.ones((2, m))
    args = (avail, inb, room, tid, eet_m)
    ki, _ = ops.fused_minmin(*args, block_n=128, interpret=True)
    assert int(ki) == 1 * m + 0
    kt, km, _ = ops.fused_maxmin(*args, block_n=128, interpret=True)
    assert (int(kt), int(km)) == (1, 0)
    _assert_minmin(args)
    _assert_maxmin(args)


def test_fused_large_values_match_oracle():
    avail, inb, room, tid, _ = _fused_instance(9, 300, 3, 2)
    eet_m = jnp.asarray([[1e28, 2e30, 5.0], [np.inf, 0.25, 1e29]],
                        jnp.float32)
    args = (avail, inb, room, tid, eet_m)
    _assert_minmin(args)
    _assert_maxmin(args)


def test_fused_vmapped_matches_per_replica():
    B, n, m, t = 4, 300, 5, 3
    rng = np.random.default_rng(12)
    stack = [_fused_instance(100 + i, n, m, t) for i in range(B)]
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *stack)
    fi, fv = jax.vmap(
        lambda *a: ops.fused_minmin(*a, block_n=128, interpret=True)
    )(*batched)
    for i in range(B):
        ri, rv = ref.fused_minmin_ref(*stack[i])
        assert int(fi[i]) == int(ri)
        assert float(fv[i]) == float(rv)


# ---------------------------------------------------------------------------
# hypothesis properties (optional dev extra)
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 80),
       m=st.integers(1, 12), bn=st.sampled_from([4, 8, 16, 256]),
       p=st.floats(0.0, 1.0))
def test_property_masked_argmin(seed, n, m, bn, p):
    """Any shape (incl. N % block_n != 0), any mask density (incl. the
    all-False sentinel case), duplicate-heavy values: kernel == oracle
    bitwise."""
    rng = np.random.default_rng(seed)
    # quantized values force frequent duplicate minima
    vals = (rng.integers(0, 6, (n, m)) * 0.5).astype(np.float32)
    mask = rng.random((n, m)) < p
    _argmin_case(vals, mask, bn)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 400),
       m=st.integers(1, 10), t=st.integers(1, 5),
       bn=st.sampled_from([128, 256]))
def test_property_fused_pair_kernels(seed, n, m, t, bn):
    args = _fused_instance(seed, n, m, t)
    _assert_minmin(args, bn)
    _assert_maxmin(args, bn)
