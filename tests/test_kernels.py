"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (same BlockSpec tiling, kernel body
executed in Python) — this validates indexing, masking, online-softmax
accumulation and the padded-row skip logic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,sk,hd,bq,bk", [
    (128, 128, 64, 64, 64),
    (130, 130, 64, 64, 64),     # ragged: padding correctness
    (64, 256, 128, 64, 128),    # cross-attention shape (sq != sk)
    (37, 53, 16, 16, 32),       # odd everything
    (256, 256, 256, 128, 128),  # gemma3 head_dim
])
def test_flash_attention_causal(dtype, sq, sk, hd, bq, bk):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k1, (3, sq, hd), dtype)
    k = _rand(k2, (3, sk, hd), dtype)
    v = _rand(k3, (3, sk, hd), dtype)
    causal = sq == sk
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_sliding_window(window):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, (2, 192, 32), jnp.float32)
    k = _rand(k2, (2, 192, 32), jnp.float32)
    v = _rand(k3, (2, 192, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_softcap():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(k1, (2, 64, 32), jnp.float32) * 3
    k = _rand(k2, (2, 64, 32), jnp.float32) * 3
    v = _rand(k3, (2, 64, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, softcap=20.0,
                              block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Scheduler masked argmin
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,bn", [(64, 8, 32), (100, 7, 32), (7, 3, 8),
                                    (1024, 64, 256), (256, 1, 64)])
def test_masked_argmin_matches_ref(n, m, bn):
    key = jax.random.PRNGKey(n * m)
    vals = jax.random.normal(key, (n, m), jnp.float32)
    mask = jax.random.bernoulli(jax.random.PRNGKey(n + m), 0.6, (n, m))
    idx, vmin = ops.masked_argmin(vals, mask, block_n=bn, interpret=True)
    ridx, rmin = ref.masked_argmin_ref(vals, mask)
    assert int(idx) == int(ridx)
    np.testing.assert_allclose(float(vmin), float(rmin), rtol=1e-6)


def test_masked_argmin_empty_mask():
    """All-masked input returns the documented (-1, BIG) sentinel —
    matching ``schedulers._pick_machine``'s "no feasible machine" answer
    — not a bogus index 0 (regression: the index scratch used to stay at
    its init value on an all-masked input)."""
    vals = jnp.ones((32, 4))
    mask = jnp.zeros((32, 4), bool)
    idx, vmin = ops.masked_argmin(vals, mask, block_n=16, interpret=True)
    assert int(idx) == -1            # sentinel, not a valid-looking cell
    assert float(vmin) >= 1e29       # BIG sentinel: "nothing schedulable"


def test_masked_argmin_empty_mask_with_padded_tail():
    """Empty mask AND a ragged tail (N % block_n != 0): neither the
    masked-out rows nor the pad rows may leak into the reduction."""
    vals = -jnp.ones((33, 4))        # negative: any leak would win
    mask = jnp.zeros((33, 4), bool)
    idx, vmin = ops.masked_argmin(vals, mask, block_n=16, interpret=True)
    assert int(idx) == -1
    assert float(vmin) >= 1e29


def test_masked_argmin_ties_lowest_flat_index():
    vals = jnp.zeros((64, 4))
    mask = jnp.ones((64, 4), bool)
    idx, _ = ops.masked_argmin(vals, mask, block_n=16, interpret=True)
    assert int(idx) == 0


@pytest.mark.parametrize("n,bn", [(33, 16), (100, 32), (257, 256)])
def test_masked_argmin_padded_tail_vs_jnp_oracle(n, bn):
    """Ragged task dims (N % block_n != 0): the kernel pads the last
    block with zero rows, which MUST stay masked out — all-positive
    values make any pad leak win the argmin and fail loudly.  Oracle is
    plain ``jnp.argmin`` over the BIG-masked matrix (the exact reduction
    the MCT/Min-Min schedulers perform)."""
    key = jax.random.PRNGKey(7 * n + bn)
    vals = jax.random.uniform(key, (n, 5), jnp.float32, 1.0, 2.0)
    mask = jax.random.bernoulli(jax.random.PRNGKey(n - bn), 0.5, (n, 5))
    idx, vmin = ops.masked_argmin(vals, mask, block_n=bn, interpret=True)
    masked = jnp.where(mask, vals, jnp.float32(1e30))
    want_idx = int(jnp.argmin(masked))
    assert int(idx) == want_idx
    np.testing.assert_allclose(float(vmin),
                               float(masked.reshape(-1)[want_idx]),
                               rtol=1e-6)


def test_masked_argmin_min_in_tail_block():
    """The global minimum sits in the ragged final block's valid rows —
    the carried (min, argmin) scratch must be updated by the last
    grid step, not just initialized by the first."""
    vals = jnp.full((70, 3), 5.0).at[69, 2].set(0.5)
    mask = jnp.ones((70, 3), bool)
    idx, vmin = ops.masked_argmin(vals, mask, block_n=32, interpret=True)
    assert int(idx) == 69 * 3 + 2
    assert float(vmin) == 0.5


def test_masked_argmin_sched_shapes_vs_jnp_oracle():
    """The (tasks x machines) shapes the batch policies would feed the
    kernel once it is plugged in (lcap*M head slots x M machines)."""
    for n, m in ((4 * 16, 16), (4 * 64, 64), (8 * 24, 24)):
        key = jax.random.PRNGKey(n + m)
        vals = jax.random.uniform(key, (n, m), jnp.float32, 0.1, 9.0)
        mask = jax.random.bernoulli(jax.random.PRNGKey(m), 0.7, (n, m))
        idx, _ = ops.masked_argmin(vals, mask, interpret=True)
        assert int(idx) == int(jnp.argmin(jnp.where(mask, vals, 1e30)))


# ---------------------------------------------------------------------------
# Grouped matmul (MoE expert GEMM)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("g,c,d,f,bc,bf", [
    (4, 40, 96, 72, 16, 32),
    (8, 128, 64, 128, 64, 64),
    (2, 16, 256, 512, 16, 128),
    (3, 33, 48, 40, 16, 16),    # ragged
])
def test_grouped_matmul_matches_ref(dtype, g, c, d, f, bc, bf):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(g * c), 3)
    lhs = _rand(k1, (g, c, d), dtype)
    rhs = _rand(k2, (g, d, f), dtype)
    gs = jax.random.randint(k3, (g,), 0, c + 1)
    out = ops.grouped_matmul(lhs, rhs, gs, block_c=bc, block_f=bf,
                             interpret=True)
    want = ref.grouped_matmul_ref(lhs, rhs, gs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype] * d, rtol=TOL[dtype])


def test_grouped_matmul_all_empty_groups():
    lhs = jnp.ones((4, 32, 16))
    rhs = jnp.ones((4, 16, 24))
    gs = jnp.zeros((4,), jnp.int32)
    out = ops.grouped_matmul(lhs, rhs, gs, block_c=16, block_f=24,
                             interpret=True)
    assert float(jnp.abs(out).max()) == 0.0
