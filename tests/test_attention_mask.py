"""Attention masks as one rule (`parallel/attention_mask.py`): the rule against a
brute-force numpy square, for the block-diffusion mask and for causal with and without a
window; the runs of KV tiles the forward walks and the live tile pairs the backward walks
against the same square; and the flash forward and XLA backward under the block mask
(pallas interpreted, as `tests/test_ring_attention.py` runs it) against dense float32
attention: `out`, `lse`, `dq`, `dk`, `dv`, `dbias`. On the CPU, at sizes of tens."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.parallel import ring_attention as ra
from kubeflow_tpu.parallel.attention_mask import (
    BlockDiffusion,
    Causal,
    causal_window,
    kv_runs,
    live_tile_pairs,
    mask_of,
)


def blockdiff_square(half: int, block: int) -> np.ndarray:
    """(2 half, 2 half) bools by the four cases of ISSUE 32, clean positions first."""
    i, j = np.arange(2 * half)[:, None], np.arange(2 * half)[None, :]
    bi, bj, ni, nj = (i % half) // block, (j % half) // block, i >= half, j >= half
    return np.where(ni & nj, bi == bj, np.where(ni, bj < bi, ~nj & (bj <= bi)))


def causal_square(lq: int, lk: int, window: int) -> np.ndarray:
    i, j = np.arange(lq)[:, None], np.arange(lk)[None, :]
    return (j <= i) & ((i - j < window) if window else True)


# (mask, lq, lk, the square it should be)
RULES = {
    "blockdiff-16-2": (BlockDiffusion(16, 2), 32, 32, blockdiff_square(16, 2)),
    "blockdiff-32-4": (BlockDiffusion(32, 4), 64, 64, blockdiff_square(32, 4)),
    "blockdiff-64-16": (BlockDiffusion(64, 16), 128, 128, blockdiff_square(64, 16)),
    "blockdiff-24-3": (BlockDiffusion(24, 3), 48, 48, blockdiff_square(24, 3)),  # no shift
    "causal": (Causal(), 64, 64, causal_square(64, 64, 0)),
    "causal-window-12": (Causal(12), 64, 64, causal_square(64, 64, 12)),
    "causal-window-1": (Causal(1), 32, 32, causal_square(32, 32, 1)),
    "causal-lq-under-lk": (Causal(), 32, 64, causal_square(32, 64, 0)),
}


@pytest.mark.parametrize("case", sorted(RULES))
def test_hidden_is_the_brute_force_square(case):
    mask, lq, lk, square = RULES[case]
    hidden = mask.hidden(np.arange(lq)[:, None], np.arange(lk)[None, :])
    np.testing.assert_array_equal(~hidden, square)
    # the same rule on jax arrays, as the kernels call it
    np.testing.assert_array_equal(
        np.asarray(mask.hidden(jnp.arange(lq)[:, None], jnp.arange(lk)[None, :])), hidden)


@pytest.mark.parametrize("half,block", [(16, 2), (32, 4), (64, 16), (24, 3)])
def test_blockdiff_leaves_l_squared_plus_l_b_pairs_visible(half, block):
    at = np.arange(2 * half)
    seen = ~BlockDiffusion(half, block).hidden(at[:, None], at[None, :])
    assert seen.sum() == blockdiff_square(half, block).sum() == half * half + half * block
    assert not seen[:half, half:].any()  # clean queries, noisy keys: the empty quarter


@pytest.mark.parametrize("block_q,block_k", [(8, 8), (4, 8), (8, 4), (16, 16), (2, 2)])
@pytest.mark.parametrize("case", sorted(RULES))
def test_live_pairs_and_runs_are_the_squares_tiles(case, block_q, block_k):
    """The live tile pairs are exactly the tiles with a visible element; the forward's
    runs of a query tile cover exactly its live tiles; a tile claimed whole hides nothing."""
    mask, lq, lk, square = RULES[case]
    if mask.period(lq) % block_q or mask.period(lk) % block_k:
        pytest.skip("tiles that straddle the mask's period are refused")
    n_q, n_kv = lq // block_q, lk // block_k
    tiles = square.reshape(n_q, block_q, n_kv, block_k)
    want = tiles.any(axis=(1, 3))
    np.testing.assert_array_equal(live_tile_pairs(mask, lq, lk, block_q, block_k), want)
    pairs = ra.flash_backward_live_pairs(lq, lk, block_q, block_k, mask)
    assert pairs == [(i, j) for j in range(n_kv) for i in range(n_q) if want[i, j]]
    for iq in range(n_q):
        walked, whole = [], []
        for lo, lo_full, hi_full, hi in kv_runs(mask, np.int64(iq * block_q), block_q, block_k, n_kv, np):
            assert lo <= lo_full <= hi_full <= hi
            walked += range(int(lo), int(hi))
            whole += range(int(lo_full), int(hi_full))
        assert sorted(walked) == [j for j in range(n_kv) if want[iq, j]] and len(set(walked)) == len(walked)
        assert all(tiles[iq, :, j, :].all() for j in whole)


def test_the_cells_masks_count_their_live_pairs():
    # sdar30b-train-4k at the backward's 512 x 512 blocks: 36 clean-clean + 36 noisy-clean + 8 noisy-noisy
    assert len(ra.flash_backward_live_pairs(8192, 8192, 512, 512, BlockDiffusion(4096, 4))) == 80
    assert ra.flash_backward_xla_blocks(8192, 8192, 128, 128, BlockDiffusion(4096, 4)) == (512, 512)
    # a clean KV block is live to two runs of query blocks, a noisy one to one
    live = live_tile_pairs(BlockDiffusion(4096, 4), 8192, 8192, 512, 512)
    assert ra._unbroken_runs(list(np.flatnonzero(live[:, 3]))) == [(3, 5), (11, 5)]
    assert ra._unbroken_runs(list(np.flatnonzero(live[:, 11]))) == [(11, 1)]
    assert not live[:8, 8:].any()  # clean queries, noisy keys: the empty quarter


def test_mask_of_spells_the_old_arguments():
    assert mask_of(False) is None and mask_of(True) == Causal() and mask_of(True, 7) == Causal(7)
    assert [causal_window(m) for m in (None, Causal(), Causal(7))] == [(False, 0), (True, 0), (True, 7)]
    with pytest.raises(ValueError, match="requires causal"):
        mask_of(False, 4)
    with pytest.raises(NotImplementedError, match="causal and window masks only"):
        causal_window(BlockDiffusion(8, 2))
    with pytest.raises(ValueError, match="do not tile"):
        BlockDiffusion(10, 4)
    with pytest.raises(ValueError, match="two copies"):
        BlockDiffusion(8, 2).period(24)
    q = jnp.zeros((1, 16, 1, 8))
    with pytest.raises(ValueError, match="not both"):
        ra.flash_attention(q, q, q, jnp.zeros((1, 1, 1, 16)), causal=True, mask=Causal())


# ------------------------------------------------- the kernels under the block mask

def _qkvbg(n, pad=0, b=2, h=4, d=16):
    ks = jax.random.split(jax.random.PRNGKey(32), 5)
    q, k, v, g = (jax.random.normal(ks[i], (b, n, h, d), jnp.float32) for i in (0, 1, 2, 4))
    bias = jax.random.normal(ks[3], (b, 1, 1, n), jnp.float32) * 0.3
    if pad:
        bias = bias.at[..., n - pad:].set(-1e9)
    return q, k, v, bias, g


def _dense_f32(q, k, v, bias, square):
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision="highest") / q.shape[-1] ** 0.5 + bias
    s = jnp.where(jnp.asarray(square)[None, None], s, -1e9)
    out = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, -1), v, precision="highest")
    return out, jax.nn.logsumexp(s, -1).reshape(-1, q.shape[1], 1)


# (half, block, the caller's granule, padded keys)
KERNEL_CASES = [(16, 2, 8, 0), (32, 4, 8, 0), (64, 16, 8, 0), (64, 4, 16, 5), (32, 8, 32, 0)]


@pytest.mark.parametrize("resident", [True, False], ids=["resident", "kvgrid"])
@pytest.mark.parametrize("half,block,granule,pad", KERNEL_CASES)
def test_forward_out_and_lse_match_float32_attention(half, block, granule, pad, resident):
    mask = BlockDiffusion(half, block)
    q, k, v, bias, _ = _qkvbg(2 * half, pad)
    out, lse = ra._flash_forward_tiled(
        q, k, v, bias, ra.FlashTiling(resident, min(granule, half), min(granule, half)), mask)
    want_out, want_lse = _dense_f32(q, k, v, bias, blockdiff_square(half, block))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("half,block,granule,pad", KERNEL_CASES)
def test_flash_attention_and_its_xla_backward_match_float32_attention(half, block, granule, pad):
    """`flash_attention(mask=...)` end to end: the tile the rule chooses, the backward's
    blocks and its two runs of query blocks a clean KV block."""
    mask, square = BlockDiffusion(half, block), blockdiff_square(half, block)
    q, k, v, bias, g = _qkvbg(2 * half, pad)
    attend = lambda *a: ra.flash_attention(*a, block=granule, mask=mask)  # noqa: E731
    np.testing.assert_allclose(np.asarray(jax.jit(attend)(q, k, v, bias)),
                               np.asarray(_dense_f32(q, k, v, bias, square)[0]), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: (attend(*a) * g).sum(), argnums=(0, 1, 2, 3))(q, k, v, bias)
    want = jax.grad(lambda *a: (_dense_f32(*a, square)[0] * g).sum(), argnums=(0, 1, 2, 3))(q, k, v, bias)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5, err_msg=name)


def test_the_kernels_are_named_for_the_mask_and_no_square_is_built():
    mask = BlockDiffusion(32, 4)
    q, k, v, bias, g = _qkvbg(64, b=1, h=2)
    fn = jax.grad(lambda *a: (ra.flash_attention(*a, block=8, mask=mask) * g).sum(), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, k, v, bias).as_text(debug_info=True)
    assert "flash_fwd_resident_q32_k32_blockdiff" in text
    live = len(ra.flash_backward_live_pairs(64, 64, 8, 16, mask))
    assert f"flash_bwd_xla_q8_k16_live{live}of32_blockdiff" in text
    jaxpr = jax.make_jaxpr(fn)(q, k, v, bias)
    shapes = {v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars}
    assert not any(s[-2:] == (64, 64) for s in shapes if len(s) >= 2)  # no (2L, 2L) bias or scores
    # the causal callers keep their names
    causal = jax.jit(lambda *a: ra.flash_attention(*a, block=8, causal=True)).lower(q, k, v, bias)
    text = causal.as_text(debug_info=True)
    assert "flash_fwd_resident_q64_k64_g2" in text and "blockdiff" not in text


def test_what_the_block_mask_cannot_do_says_so():
    mask = BlockDiffusion(24, 3)
    q, k, v, bias, g = _qkvbg(48, b=1, h=1)
    with pytest.raises(NotImplementedError, match="blockwise fallback"):
        ra.flash_attention(q, k, v, bias, block=16, mask=mask)  # 16 does not tile 24
    with pytest.raises(ValueError, match="straddle"):
        ra._flash_forward_tiled(q, k, v, bias, ra.FlashTiling(True, 16, 8), mask)
    out, lse = ra._flash_forward_tiled(q, k, v, bias, ra.FlashTiling(True, 8, 8), mask)
    with pytest.raises(NotImplementedError, match="causal and window masks only"):
        ra._flash_backward(q, k, v, bias, out, lse, g, 8, 8, mask, impl="loop2")
