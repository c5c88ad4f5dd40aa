"""Context-parallel attention: ring attention, Ulysses, and a pallas flash kernel.

The reference platform has NO sequence parallelism anywhere (SURVEY.md §5.7)
— it schedules containers and never sees sequence length. For capability
parity as a long-context training platform, this module supplies it
TPU-first:

  ring_attention     KV blocks rotate around the ICI ring via ppermute while
                     each device accumulates online-softmax partial results —
                     sequence memory per chip is L/ring_size, compute overlaps
                     communication (Liu et al., Ring Attention; PAPERS.md).
  ulysses_attention  all-to-all head scatter: re-shard (seq/ctx, heads) ->
                     (seq, heads/ctx), run dense/blockwise attention locally,
                     scatter back (DeepSpeed-Ulysses; PAPERS.md).
  flash_attention    single-device blockwise-softmax pallas kernel (MXU
                     matmuls, f32 softmax), custom-VJP'd. Forward: one grid
                     step per (head group, query tile); the group's whole K
                     and V stay in VMEM and the KV loop runs inside the
                     kernel, stopped at the causal diagonal and started at
                     the window's edge, a mask built only on the tiles those
                     cross. Where a head's K and V pass the VMEM budget the
                     KV axis goes on the grid instead. The tile is a function
                     of the call's shapes (flash_forward_tiling), and the
                     pallas_call's name carries branch and tile
                     (flash_fwd_resident_q256_k512) into every trace.
                     Backward: recomputes probability tiles from the saved
                     logsumexp — FlashAttention-2 style, no O(L²) residuals
                     (FLASH_BWD_IMPL chooses among its implementations).

All functions share the signature of models.bert.dense_attention:
  (q, k, v, bias, dropout_rng, dropout_rate, block) -> out
with q/k/v: (B, L, H, D), bias: (B, 1, 1, L) additive, out: (B, L, H, D).
Attention-probability dropout is unsupported in the context-parallel paths
(standard for ring implementations); pass dropout_rate=0.

Layout contract under context parallelism (models/bert.py ACT_SPEC):
  q/k/v sharded P((data, fsdp), context, model, None) — seq over `context`,
  heads over `model`; bias P((data, fsdp), None, None, context).
"""

from __future__ import annotations

import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.parallel.attention_mask import (
    causal_window,
    kv_runs,
    live_tile_pairs,
    mask_of,
    name_suffix,
)
from kubeflow_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_MODEL,
    in_manual_region,
)
from kubeflow_tpu.parallel.sharding import BATCH_AXES

NEG_INF = -1e9

# Gradient path for blockwise_attention (and therefore the ring/ulysses
# local attention). Read and validated ONCE at import — like
# KFT_FLASH_BWD_IMPL below — because a trace-time read would silently
# ignore env changes after a jitted train step has compiled.
BLOCKWISE_VJP = os.environ.get("KFT_BLOCKWISE_VJP", "custom")
if BLOCKWISE_VJP not in ("custom", "autodiff"):
    raise ValueError(
        f"KFT_BLOCKWISE_VJP={BLOCKWISE_VJP!r} is not 'custom' or 'autodiff'")

# batch rides ALL data-like axes — sharding.BATCH_AXES, the one canonical
# definition (expert parallelism subdivides data parallelism; an earlier
# hand-inlined tuple omitted expert and silently forced a batch gather at
# the ring boundary)
QKV_SPEC = P(BATCH_AXES, AXIS_CONTEXT, AXIS_MODEL, None)
BIAS_SPEC = P(BATCH_AXES, None, None, AXIS_CONTEXT)
# the flash kernel's per-device view: sequence whole on every device
FLASH_SPEC = P(BATCH_AXES, None, AXIS_MODEL, None)
FLASH_BIAS_SPEC = P(BATCH_AXES, None, None, None)


def _context_size() -> int:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        try:  # eager path; raises inside jit, where abstract mesh is set
            mesh = jax.sharding.get_mesh()
        except ValueError:
            return 1
    if mesh.empty or AXIS_CONTEXT not in mesh.shape:
        return 1
    return mesh.shape[AXIS_CONTEXT]


# --------------------------------------------------------------------- jnp core


def _online_block(carry, kv, q, scale, q_pos=None, k_pos=None,
                  window: int = 0):
    """One online-softmax accumulation step against a KV block.

    carry: (o_acc f32 (B,Lq,H,D), m (B,H,Lq,1) running max, l (B,H,Lq,1) sum)
    kv:    (k_blk, v_blk, bias_blk (B,1,1,Lk))
    q_pos/k_pos: global token positions (Lq,)/(Lk,) for causal masking —
    positions, not block indices, so the mask stays correct when blocks live
    on different ring shards. window > 0 additionally hides keys older than
    window-1 positions (Mistral sliding window; requires causal positions).
    """
    o_acc, m, l = carry
    k_blk, v_blk, bias_blk = kv
    s = _block_scores(q, k_blk, bias_blk, scale, q_pos, k_pos, window)
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * corr + p.sum(-1, keepdims=True)
    pv = jnp.einsum("bhlm,bmhd->blhd", p.astype(q.dtype), v_blk).astype(jnp.float32)
    o_new = o_acc * corr.transpose(0, 2, 1, 3) + pv
    return (o_new, m_new, l_new)


def _finalize(o_acc, m, l, dtype):
    return (o_acc / l.transpose(0, 2, 1, 3)).astype(dtype)


def _init_carry(q, dv: int | None = None):
    b, lq, h, d = q.shape
    return (
        jnp.zeros((b, lq, h, dv or d), jnp.float32),  # the output has v's head size
        jnp.full((b, h, lq, 1), NEG_INF, jnp.float32),
        jnp.zeros((b, h, lq, 1), jnp.float32),
    )


def _kv_blocks(k, v, bias, block):
    """Split KV (+ bias + key positions) into scan-ready block stacks."""
    b, lk, h, d = k.shape
    block = min(block, lk)
    n_blocks = lk // block
    if n_blocks * block != lk:  # ragged tail: fall back to one block
        n_blocks, block = 1, lk
    kb = k.reshape(b, n_blocks, block, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, block, h, -1).transpose(1, 0, 2, 3, 4)
    bias_b = bias.reshape(b, 1, 1, n_blocks, block).transpose(3, 0, 1, 2, 4)
    k_pos = jnp.arange(lk).reshape(n_blocks, block)
    return kb, vb, bias_b, k_pos, block


def _block_scores(q, k_blk, bias_blk, scale, q_pos, kp, window):
    """The ONE score computation the forward and the custom backward share
    — bit-identical recompute keeps exp(s - lse) consistent with the lse
    the forward saved."""
    s = jnp.einsum("blhd,bmhd->bhlm", q, k_blk).astype(jnp.float32) * scale
    s = s + bias_blk.astype(jnp.float32)
    if q_pos is not None:
        masked = kp[None, :] > q_pos[:, None]
        if window:
            masked = masked | (q_pos[:, None] - kp[None, :] >= window)
        s = s + jnp.where(masked, NEG_INF, 0.0)[None, None, :, :]
    return s


def _blockwise_fwd_impl(q, k, v, bias, block, causal, window, scale=None):
    """Online-softmax scan over KV blocks -> (out, lse (B,H,Lq,1) f32)."""
    kb, vb, bias_b, k_pos, _ = _kv_blocks(k, v, bias, block)
    scale = 1.0 / (q.shape[-1] ** 0.5) if scale is None else scale
    q_pos = jnp.arange(q.shape[1]) if causal else None

    def step(carry, kv):
        k_blk, v_blk, bias_blk, kp = kv
        return _online_block(
            carry, (k_blk, v_blk, bias_blk), q, scale,
            q_pos, kp if causal else None, window=window,
        ), None

    (o_acc, m, l), _ = jax.lax.scan(
        step, _init_carry(q, v.shape[-1]), (kb, vb, bias_b, k_pos)
    )
    return _finalize(o_acc, m, l, q.dtype), m + jnp.log(l)


def _block_grads(q, k_blk, v_blk, bias_blk, g, gf, dd, lse, scale,
                 q_pos, k_pos, window):
    """FA2 per-block gradients — the ONE gradient-math implementation the
    blockwise AND ring custom backwards share (a drift between them would
    be invisible to tests that only compare each against dense).

    Matmuls mirror the forward's precision: operands in the input dtype,
    f32 accumulation (MXU-native). Returns (dq_blk, dk_blk, dv_blk,
    dbias_rows (B, Lk_blk))."""
    s = _block_scores(q, k_blk, bias_blk, scale, q_pos, k_pos, window)
    p = jnp.exp(s - lse)
    dp = jnp.einsum("blhd,bmhd->bhlm", gf, v_blk.astype(jnp.float32))
    ds = p * (dp - dd)
    dsq = ds.astype(q.dtype)
    dq_blk = jnp.einsum("bhlm,bmhd->blhd", dsq, k_blk,
                        preferred_element_type=jnp.float32) * scale
    dk_blk = jnp.einsum("bhlm,blhd->bmhd", dsq, q,
                        preferred_element_type=jnp.float32) * scale
    dv_blk = jnp.einsum("bhlm,blhd->bmhd", p.astype(q.dtype), g,
                        preferred_element_type=jnp.float32)
    dbias_rows = ds.sum(axis=(1, 2))  # bias (B,1,1,Lk) broadcasts h, Lq
    return dq_blk, dk_blk, dv_blk, dbias_rows


def _blockwise_bwd_impl(q, k, v, bias, out, lse, g, block, causal, window,
                        scale=None):
    """FlashAttention-2-style backward: recompute p = exp(s − lse) block
    by block from the saved logsumexp; residual memory is O(L), not the
    O(L²/block · n_blocks) probability tiles reverse-AD of the forward
    scan would save. Also the gradient path ring/ulysses local attention
    actually trains through — kept out of reverse-AD entirely because the
    r5 hardware forensics (probe_flash_r5b, docs/perf.md §Round 5) implicate
    the scan-autodiff max/exp chain for dq/dk/dbias NaNs on Mosaic."""
    kb, vb, bias_b, k_pos, _ = _kv_blocks(k, v, bias, block)
    b, lk, h, d = k.shape
    scale = 1.0 / (q.shape[-1] ** 0.5) if scale is None else scale
    q_pos = jnp.arange(q.shape[1]) if causal else None
    gf = g.astype(jnp.float32)
    # D_i = Σ_d dO∘O — the dv-free half of ds = p·(dp − D)
    dd = jnp.einsum("blhd,blhd->bhl", gf, out.astype(jnp.float32))[..., None]

    def step(dq_acc, kv):
        k_blk, v_blk, bias_blk, kp = kv
        dq_blk, dk_blk, dv_blk, dbias_blk = _block_grads(
            q, k_blk, v_blk, bias_blk, g, gf, dd, lse, scale,
            q_pos, kp if causal else None, window)
        return dq_acc + dq_blk, (dk_blk, dv_blk, dbias_blk)

    dq, (dks, dvs, dbs) = jax.lax.scan(
        step, jnp.zeros(q.shape, jnp.float32), (kb, vb, bias_b, k_pos)
    )
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, lk, h, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, lk, h, -1)
    dbias = dbs.transpose(1, 0, 2).reshape(b, lk)[:, None, None, :]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias.astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _blockwise_cvjp(block, causal, window, scale, q, k, v, bias):
    out, _ = _blockwise_fwd_impl(q, k, v, bias, block, causal, window, scale)
    return out


def _blockwise_cvjp_fwd(block, causal, window, scale, q, k, v, bias):
    out, lse = _blockwise_fwd_impl(q, k, v, bias, block, causal, window, scale)
    return out, (q, k, v, bias, out, lse)


def _blockwise_cvjp_bwd(block, causal, window, scale, res, g):
    q, k, v, bias, out, lse = res
    return _blockwise_bwd_impl(q, k, v, bias, out, lse, g, block, causal,
                               window, scale)


_blockwise_cvjp.defvjp(_blockwise_cvjp_fwd, _blockwise_cvjp_bwd)


def blockwise_attention(q, k, v, bias, block: int = 256, causal: bool = False,
                        window: int = 0, vjp: str | None = None, scale=None):
    """Memory-efficient attention: lax.scan over KV blocks, online softmax.

    The numerics reference for both the pallas kernel and the ring path. v
    may have another head size than q and k (the output has v's); `scale`
    multiplies the scores (None: 1/sqrt(q's head size)). causal=True masks
    k_pos > q_pos (global positions); window > 0 (causal only): (i - window, i].

    vjp selects the gradient path (default: KFT_BLOCKWISE_VJP, validated
    at import time):
      "custom"   (default) FlashAttention-2-style custom VJP — the
                 backward recomputes probabilities from the saved
                 logsumexp, so residuals are O(L) and reverse-AD never
                 traverses the online max/exp chain (which the r5
                 hardware forensics implicate for NaN gradients on
                 Mosaic — docs/perf.md §Round 5).
      "autodiff" reverse-AD through the forward scan (pre-r5 behavior;
                 kept as the forensics subject and escape hatch).
    """
    if window and not causal:
        raise ValueError("attention window requires causal=True")
    if vjp is None:
        vjp = BLOCKWISE_VJP
    if vjp == "autodiff":
        out, _ = _blockwise_fwd_impl(q, k, v, bias, block, causal, window, scale)
        return out
    if vjp != "custom":
        raise ValueError(f"unknown blockwise vjp {vjp!r}")
    return _blockwise_cvjp(block, causal, window, scale, q, k, v, bias)


# ------------------------------------------------------------------------ ring


def _rope_qk(q, k, pos, theta):
    """Rotate q and k by the given (global) positions — the ONE rope
    application the context-parallel paths share."""
    from kubeflow_tpu.parallel.rope import apply_rope

    return apply_rope(q, pos, theta), apply_rope(k, pos, theta)


def _ring_hops(ring: int, l_loc: int, window: int) -> int:
    """Ring steps that can contribute under a causal sliding window.

    Query shard i needs KV from source shards [i - h, i] where the oldest
    key any of its queries can see is global position i·l_loc − window + 1
    (query p = 0). Source shard at hop s is (i − s) mod ring, so the
    largest useful hop is ceil(window / l_loc) — uniform across shards
    (SPMD-safe: window, l_loc, ring are all static)."""
    if not window:
        return ring
    return min(ring, -(-window // l_loc) + 1)


def ring_attention(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                   block: int = 256, axis_name: str = AXIS_CONTEXT,
                   causal: bool = False, rope_theta: float | None = None,
                   window: int = 0, vjp: str | None = None):
    """Ring attention over the `context` mesh axis.

    Inside: per-device online-softmax accumulation against the local KV
    block, then ppermute rotates (k, v, bias) one hop around the ring;
    after ring_size steps every query block has seen every KV block. The
    softmax statistics (m, l) make the result exactly equal to dense
    attention — verified in tests to 1e-5.

    causal=True masks with GLOBAL positions: query shard i holds positions
    [i·L_loc, (i+1)·L_loc); the KV block at ring step s originated on shard
    (i - s) mod ring, so its positions are reconstructed per step — the
    hard part of causal ring attention (SURVEY.md §7 hard-part 2).

    window > 0 (requires causal) is the Mistral sliding window — and on
    the ring it is a COMMUNICATION win, not just masking: hops past
    ceil(window/L_loc) carry only keys every local query has already
    out-scrolled, so the ring runs min(ring, ceil(window/L_loc)+1) steps
    instead of ring_size. At 32k context over an 8-shard ring with a 4k
    window that is 2 hops instead of 8 — both the ppermute traffic and
    the score matmuls drop ~4x.

    vjp: "custom" (default via KFT_BLOCKWISE_VJP) runs the ring-rotating
    FA2-style backward (_ring_core_bwd): O(L_loc) residuals and no
    reverse-AD through the online max/exp chain (the r5 Mosaic-NaN
    suspect); "autodiff" reverse-ADs the forward ring (pre-r5 behavior).
    """
    if dropout_rate:
        raise NotImplementedError("attention dropout unsupported in ring path")
    if window and not causal:
        raise ValueError("attention window requires causal=True")
    ctx = _context_size()
    if ctx == 1 or in_manual_region():
        # ctx == 1: nothing to ring over. in_manual_region (inside a
        # gpipe stage): a NESTED shard_map's reverse AD corrupts
        # cotangents in current JAX (forward exact, grads exploding
        # geometrically with layers-per-stage — caught by the r5
        # real-dim composed step: finite loss, NaN grad-norm; pinned by
        # tests/test_composed_realdim.py). Identical math on the
        # auto-partitioned global-shaped values — the XLA partitioner
        # inserts the context collectives itself.
        if rope_theta is not None:
            q, k = _rope_qk(q, k, jnp.arange(q.shape[1]), rope_theta)
        return blockwise_attention(q, k, v, bias, block, causal=causal,
                                   window=window, vjp=vjp)

    scale = 1.0 / (q.shape[-1] ** 0.5)
    if vjp is None:
        vjp = BLOCKWISE_VJP
    if vjp not in ("custom", "autodiff"):
        raise ValueError(f"unknown ring vjp {vjp!r}")

    def per_device(q, k, v, bias):
        # _ring_positions is the ONE definition of the global-position
        # vector — rope here and the causal masks in _ring_fwd_impl /
        # _ring_core_bwd all call it, so they cannot desync
        pos = _ring_positions(axis_name, q.shape[1])
        if rope_theta is not None:
            # rotate by GLOBAL position before the ring starts: each
            # shard rotates its LOCAL q and k once, and rotated K blocks
            # then travel the ring carrying their rotation (the same
            # invariant the KV cache keeps by storing rotated keys)
            q, k = _rope_qk(q, k, pos, rope_theta)
        if vjp == "autodiff":
            out, _ = _ring_fwd_impl(axis_name, causal, window, scale,
                                    q, k, v, bias)
            return out
        return _ring_core(axis_name, causal, window, scale, q, k, v, bias)

    return jax.shard_map(
        per_device,
        in_specs=(QKV_SPEC, QKV_SPEC, QKV_SPEC, BIAS_SPEC),
        out_specs=QKV_SPEC,
        check_vma=False,
    )(q, k, v, bias)


def _ring_positions(axis_name, l_loc):
    """Global token positions of this shard's local sequence block — the
    ONE definition rope and the fwd/bwd causal masks share."""
    return jax.lax.axis_index(axis_name) * l_loc + jnp.arange(l_loc)


def _ring_fwd_impl(axis_name, causal, window, scale, q, k, v, bias):
    """The ring forward: per-hop online-softmax accumulation against the
    visiting KV block, ppermute rotating (k, v, bias) one hop per step.
    Returns (out, lse (B,H,Lq,1) f32) — lse is the residual the custom
    backward recomputes probabilities from."""
    ring = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % ring) for i in range(ring)]
    l_loc = q.shape[1]
    q_pos = _ring_positions(axis_name, l_loc) if causal else None
    hops = _ring_hops(ring, l_loc, window) if causal else ring

    def step(i, carry_kv):
        carry, kv = carry_kv
        if causal:
            src = (idx - i) % ring  # shard this KV block originated on
            k_pos = src * l_loc + jnp.arange(l_loc)
            carry = _online_block(carry, kv, q, scale, q_pos, k_pos,
                                  window=window)
        else:
            carry = _online_block(carry, kv, q, scale)
        # rotate KV (+ its bias slice) one hop; unconditional so the
        # collective never sits inside data-dependent control flow (the
        # final rotation restores placement on a full ring; a window-
        # shortened ring just stops — the kv copy is consumed). XLA
        # overlaps the ppermute with the next iteration's matmuls.
        kv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
        return (carry, kv)

    (o_acc, m, l), _ = jax.lax.fori_loop(
        0, hops, step, (_init_carry(q), (k, v, bias))
    )
    return _finalize(o_acc, m, l, q.dtype), m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ring_core(axis_name, causal, window, scale, q, k, v, bias):
    out, _ = _ring_fwd_impl(axis_name, causal, window, scale, q, k, v, bias)
    return out


def _ring_core_fwd(axis_name, causal, window, scale, q, k, v, bias):
    out, lse = _ring_fwd_impl(axis_name, causal, window, scale, q, k, v,
                              bias)
    return out, (q, k, v, bias, out, lse)


def _ring_core_bwd(axis_name, causal, window, scale, res, g):
    """Ring-rotating FlashAttention-2-style backward.

    The KV blocks travel the SAME ring as the forward, and a zero-init
    (dk, dv, dbias) accumulator travels WITH each block: when device i
    attends the block originating on shard (i − s), it adds that hop's
    dk/dv/dbias contribution to the visiting accumulator before both
    rotate on. After `hops` rotations block j sits on shard (j + hops);
    a single closing ppermute by −hops returns every accumulator to its
    home shard with contributions from ALL query shards on board (a full
    ring needs no closing hop — ring rotations compose to identity).
    dq accumulates locally. Like the blockwise custom VJP, probabilities
    are recomputed as exp(s − lse) from the saved global logsumexp, so
    reverse-AD never traverses the online max/exp chain and residual
    memory stays O(L_loc) per device."""
    q, k, v, bias, out, lse = res
    ring = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % ring) for i in range(ring)]
    l_loc = q.shape[1]
    q_pos = _ring_positions(axis_name, l_loc) if causal else None
    hops = _ring_hops(ring, l_loc, window) if causal else ring
    gf = g.astype(jnp.float32)
    dd = jnp.einsum("blhd,blhd->bhl", gf, out.astype(jnp.float32))[..., None]

    def step(i, carry):
        dq, k_c, v_c, bias_c, dk_c, dv_c, dbias_c = carry
        if causal:
            src = (idx - i) % ring
            k_pos = src * l_loc + jnp.arange(l_loc)
        else:
            k_pos = None
        dq_blk, dk_blk, dv_blk, dbias_rows = _block_grads(
            q, k_c, v_c, bias_c, g, gf, dd, lse, scale, q_pos, k_pos,
            window)
        dq = dq + dq_blk
        dk_c = dk_c + dk_blk
        dv_c = dv_c + dv_blk
        dbias_c = dbias_c + dbias_rows[:, None, None, :]
        rot = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return (dq, rot(k_c), rot(v_c), rot(bias_c),
                rot(dk_c), rot(dv_c), rot(dbias_c))

    zeros_f32 = lambda x: jnp.zeros(x.shape, jnp.float32)
    dq, _, _, _, dk, dv, dbias = jax.lax.fori_loop(
        0, hops, step,
        (zeros_f32(q), k, v, bias, zeros_f32(k), zeros_f32(v),
         zeros_f32(bias)),
    )
    if hops % ring:  # closing rotation: send accumulators home in one hop
        home = [(i, (i - hops) % ring) for i in range(ring)]
        go = lambda x: jax.lax.ppermute(x, axis_name, home)
        dk, dv, dbias = go(dk), go(dv), go(dbias)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias.astype(bias.dtype))


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


# --------------------------------------------------------------------- ulysses


def ulysses_attention(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                      block: int = 256, axis_name: str = AXIS_CONTEXT,
                      causal: bool = False, rope_theta: float | None = None,
                      window: int = 0):
    """Ulysses context parallelism: all-to-all seq<->head re-shard.

    Each device exchanges its sequence shard for a head shard (one all-to-all
    over ICI), runs full-sequence blockwise attention on its heads, and
    scatters back. Cheaper than ring when heads >= ring size and sequence
    fits after the exchange. window > 0 (requires causal) applies the
    Mistral sliding window in the local full-sequence attention.
    """
    if dropout_rate:
        raise NotImplementedError("attention dropout unsupported in ulysses path")
    if window and not causal:
        raise ValueError("attention window requires causal=True")
    ctx = _context_size()
    if ctx == 1 or in_manual_region():
        # same nested-manual AD hazard as ring_attention (see note there)
        if rope_theta is not None:
            q, k = _rope_qk(q, k, jnp.arange(q.shape[1]), rope_theta)
        return blockwise_attention(q, k, v, bias, block, causal=causal,
                                   window=window)
    mesh = jax.sharding.get_abstract_mesh()
    model = mesh.shape.get(AXIS_MODEL, 1)
    heads = q.shape[2]
    if (heads // model) % ctx:
        raise ValueError(
            f"ulysses needs heads/model_parallel ({heads}/{model}) divisible "
            f"by context axis size {ctx}; use ring attention instead"
        )

    def per_device(q, k, v, bias):
        # (b, l/ctx, h_loc, d) -> (b, L, h_loc/ctx, d)
        a2a = functools.partial(
            jax.lax.all_to_all, axis_name=axis_name, split_axis=2,
            concat_axis=1, tiled=True,
        )
        qg, kg, vg = a2a(q), a2a(k), a2a(v)
        bias_g = jax.lax.all_gather(
            bias, axis_name, axis=3, tiled=True
        )
        # after the exchange every device holds the FULL sequence for its
        # heads, so causal masking is the ordinary global-position mask —
        # and rope rotation is the ordinary global arange
        if rope_theta is not None:
            qg, kg = _rope_qk(qg, kg, jnp.arange(qg.shape[1]), rope_theta)
        o = blockwise_attention(qg, kg, vg, bias_g, block, causal=causal,
                                window=window)
        return jax.lax.all_to_all(
            o, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    return jax.shard_map(
        per_device,
        in_specs=(QKV_SPEC, QKV_SPEC, QKV_SPEC, BIAS_SPEC),
        out_specs=QKV_SPEC,
        check_vma=False,
    )(q, k, v, bias)


# ------------------------------------------------------------------ pallas fwd

#: VMEM the forward's tile rule plans within, by its own count
#: (`_flash_fwd_vmem_bytes`). Mosaic scopes a kernel to 16 MiB unless told
#: otherwise. Compiling for the v5e, what it refused was resident K/V blocks
#: past that (16k positions: counted 19 MiB); a score tile counted at 18 MiB
#: it took, so the count is on the safe side where the tile is the large part.
FLASH_FWD_VMEM_BUDGET = 12 * 2**20
#: (block_q, block_k) the rule aims for, timed on the v5e (PERF.md, PR 26).
#: K/V resident, by `causal`: `block_k` under 512 loses to the per-slice cost
#: of the `(block_q, 1)` row statistics, which fill one lane of a register
#: where the scores fill 128; under the diagonal a wide `block_q` computes
#: masked scores, with no diagonal it saves loads of K and V into the MXU.
_FLASH_FWD_RESIDENT_TARGET = {True: (256, 512), False: (512, 512)}
#: KV on the grid: every grid step costs its half microsecond and a K/V
#: copy, so the tiles are as wide as the budget lets them be
_FLASH_FWD_KVGRID_TARGET = (1024, 1024)
#: heads a grid step may take where a head is one tile (short sequences: a
#: step a head is mostly the step's own cost), most first
_FLASH_FWD_HEAD_GROUPS = (4, 2, 1)


class FlashTiling(NamedTuple):
    """How one forward call tiles. `resident`: a head's whole K and V sit in
    VMEM and the KV loop runs inside the kernel, `heads` heads a grid step;
    else the KV axis stays on the grid (contexts whose K and V do not fit)."""

    resident: bool
    block_q: int
    block_k: int
    heads: int = 1

    @property
    def name(self) -> str:
        """The pallas_call's name: what a trace shows of how the kernel ran."""
        branch = "resident" if self.resident else "kvgrid"
        group = f"_g{self.heads}" if self.heads > 1 else ""
        return f"flash_fwd_{branch}_q{self.block_q}_k{self.block_k}{group}"


def _flash_fwd_vmem_bytes(tiling: FlashTiling, lk: int, d: int, dtype,
                          dv: int | None = None) -> int:
    """VMEM one grid step of the forward holds: the pipeline's two buffers of
    every block, lanes padded to 128 (q's and k's `d`, v's and the output's `dv`,
    None: `d`) and sublanes to a tile, plus the float32 score tile three times over
    (scores, probabilities, the cast or mask) and the maximum, sum and accumulator."""
    item = jnp.dtype(dtype).itemsize
    lanes, lanes_v = (-(-n // 128) * 128 for n in (d, dv or d))
    bq, bk, g = tiling.block_q, tiling.block_k, tiling.heads
    kv_rows = lk if tiling.resident else bk
    blocks = (
        g * bq * (lanes + lanes_v) * item            # q in, out
        + g * kv_rows * (lanes + lanes_v) * item     # k, v
        + g * bq * 128 * 4                   # lse: one lane of 128 used
        + 8 * kv_rows * 4                    # bias row: one sublane of 8 used
    )
    work = 3 * bq * max(bk, 128) * 4 + bq * (lanes_v + 2 * 128) * 4
    return 2 * blocks + work


def _largest_tile(n: int, target: int, granule: int) -> int:
    """The largest multiple of `granule` that divides `n` and is at most
    `target`; `granule`, which divides `n`, where there is none."""
    return max((t for t in range(granule, min(n, target) + 1, granule)
                if n % t == 0), default=granule)


def flash_forward_tiling(lq: int, lk: int, d: int, dtype, mask=None, *,
                         block_q: int = 128, block_k: int = 128,
                         heads: int = 1, dv: int | None = None,
                         vmem_budget: int = FLASH_FWD_VMEM_BUDGET
                         ) -> FlashTiling:
    """The forward's tile, from what the call can see. `block_q`/`block_k`
    are the caller's granules (they tile `lq`/`lk`; every tile is a multiple
    of them, so a tiny test shape stays legal); `heads` is the head count, which
    a head group has to divide to share a bias row; `d` is q's and k's head
    size, `dv` v's (None: `d`). `mask` is a mask of `attention_mask.py` or None,
    whose `period` the tiles divide (a block-diffusion tile lies in one half).
    A window moves no tile (timed at 4,096 positions, window 1,024): the loop's
    bounds skip what it hides."""
    # what a tile has to divide: the length, or the mask's period of it
    pq, pk = (lq, lk) if mask is None else (mask.period(lq), mask.period(lk))
    gq, gk = min(block_q, pq), min(block_k, pk)

    def fits(tiling):
        return _flash_fwd_vmem_bytes(tiling, lk, d, dtype, dv) <= vmem_budget

    target_q, target_k = _FLASH_FWD_RESIDENT_TARGET[mask is not None]
    bq, bk = _largest_tile(pq, target_q, gq), _largest_tile(pk, target_k, gk)
    one_tile = (bq, bk) == (lq, lk)
    for g in _FLASH_FWD_HEAD_GROUPS if one_tile else (1,):
        if heads % g == 0 and fits(FlashTiling(True, bq, bk, g)):
            return FlashTiling(True, bq, bk, g)
    # one head's K and V do not fit beside the tiles: the KV axis goes back
    # on the grid, and the wider tile shrinks until a step fits
    target_q, target_k = _FLASH_FWD_KVGRID_TARGET
    bq, bk = _largest_tile(pq, target_q, gq), _largest_tile(pk, target_k, gk)
    while not fits(FlashTiling(False, bq, bk)) and (bq > gq or bk > gk):
        if bq > gq and (bq >= bk or bk == gk):
            bq = _largest_tile(pq, bq - 1, gq)
        else:
            bk = _largest_tile(pk, bk - 1, gk)
    return FlashTiling(False, bq, bk)


def _flash_tile(q, k, v, bias_row, carry, *, scale: float, mask):
    """One online-softmax step of the forward: a (block_q, d) query tile
    against a (block_k, d) KV slice. `mask` is None for a tile no row of
    which the mask hides anything of, else (row0, col0, hidden): the tile's
    origin and the band's rule `hidden(rows, cols)`."""
    m, l, acc = carry
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)
    s = s + bias_row.astype(jnp.float32)[None, :]
    if mask is not None:
        row0, col0, hidden = mask
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = s + jnp.where(hidden(rows, cols), NEG_INF, 0.0)
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = l * corr + p.sum(-1, keepdims=True)
    acc = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def _flash_tile_init(block_q: int, d: int):
    return (jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, d), jnp.float32))


def _flash_resident_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                           *, scale: float, mask, block_k: int):
    """Forward for one (head group, query tile): the group's whole K and V
    are in VMEM (their block does not move with the query tile, so they are
    fetched once a group), the KV loop runs here over `block_k` slices with
    the running maximum, sum and accumulator as its carry, over the runs of
    KV tiles the mask's bands leave the query tile (`kv_runs`: under `Causal`
    one run that stops at the diagonal); only the tiles a band's edge
    crosses build a mask."""
    heads, block_q, d = q_ref.shape
    n_kv = k_ref.shape[1] // block_k
    row0 = pl.program_id(1) * block_q
    for g in range(heads):
        q = q_ref[g]

        def tile(j, carry, hidden, g=g, q=q):
            col0 = pl.multiple_of(j * block_k, block_k)
            return _flash_tile(
                q, k_ref[g, pl.ds(col0, block_k), :],
                v_ref[g, pl.ds(col0, block_k), :],
                bias_ref[0, 0, 0, pl.ds(col0, block_k)], carry, scale=scale,
                mask=(row0, col0, hidden) if hidden else None)

        def tiles(lo, hi, hidden, carry, tile=tile):
            return jax.lax.fori_loop(
                lo, hi, functools.partial(tile, hidden=hidden), carry)

        carry = _flash_tile_init(block_q, v_ref.shape[2])
        if mask is not None:
            for band, (lo, lo_full, hi_full, hi) in zip(
                    mask.bands(jnp), kv_runs(mask, row0, block_q, block_k, n_kv, jnp)):
                if band.start is not None:  # else lo == lo_full: no loop to set up
                    carry = tiles(lo, lo_full, band.hidden, carry)
                carry = tiles(lo_full, hi_full, None, carry)
                carry = tiles(hi_full, hi, band.hidden, carry)
        else:
            carry = tiles(0, n_kv, None, carry)
        m, l, acc = carry
        o_ref[g] = (acc / l).astype(o_ref.dtype)
        # logsumexp residual for the fused backward kernels
        lse_ref[g] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_kvgrid_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                         m_scr, l_scr, acc_scr,
                         *, scale: float, mask):
    """Forward for one (head, query tile, KV tile): the KV axis is the
    grid's last, sequential, with the running maximum, sum and accumulator
    in VMEM scratch across it. A step outside every run of the mask's
    (`kv_runs`) does nothing, and fetches nothing: the K/V index maps
    (`_flash_forward_tiled`) hold it on a tile that is already there."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    ik, n_kv = pl.program_id(2), pl.num_programs(2)
    row0 = pl.program_id(1) * block_q

    @pl.when(ik == 0)
    def _():
        m_scr[:], l_scr[:], acc_scr[:] = _flash_tile_init(
            block_q, v_ref.shape[2])

    def step(hidden):
        m_scr[:], l_scr[:], acc_scr[:] = _flash_tile(
            q_ref[0], k_ref[0], v_ref[0], bias_ref[0, 0, 0, :],
            (m_scr[:], l_scr[:], acc_scr[:]), scale=scale,
            mask=(row0, ik * block_k, hidden) if hidden else None)

    if mask is not None:
        # the bands' runs share no tile: at most one of these fires a step
        for band, (lo, lo_full, hi_full, hi) in zip(
                mask.bands(jnp), kv_runs(mask, row0, block_q, block_k, n_kv, jnp)):
            full = jnp.logical_and(ik >= lo_full, ik < hi_full)
            live = jnp.logical_and(ik >= lo, ik < hi)
            pl.when(full)(functools.partial(step, None))
            pl.when(jnp.logical_and(live, jnp.logical_not(full)))(
                functools.partial(step, band.hidden))
    else:
        step(None)

    @pl.when(ik == n_kv - 1)
    def _():
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _flash_forward_tiled(q, k, v, bias, tiling: FlashTiling, mask=None, scale=None):
    """The forward kernel at a given tiling -> (out (B,Lq,H,Dv), lse
    (B*H,Lq,1) f32): q and k share a head size, v and the output another. The
    tiling has to divide the lengths (the mask's period of them), its head group the
    head count. `mask`: of `attention_mask.py`, or None; `scale`: None is 1/sqrt(D)."""
    b, lq, h, d = q.shape
    lk, dv = k.shape[1], v.shape[3]
    resident, block_q, block_k, group = tiling
    if mask is not None and (mask.period(lq) % block_q or mask.period(lk) % block_k):
        raise ValueError(f"tiles of {block_q} x {block_k} straddle the mask's periods")
    scale = 1.0 / (d**0.5) if scale is None else scale
    # fold heads into batch: (B*H, L, D)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, dv)
    n_q, n_kv = lq // block_q, lk // block_k
    common = dict(
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, lq, 1), jnp.float32),
        ],
        # branch, tile and the mask's tag, in every trace of the call
        name=tiling.name + name_suffix(mask) + head_sizes_suffix(d, dv),
        interpret=jax.default_backend() == "cpu",
    )
    if resident:
        kv_spec = lambda n: pl.BlockSpec(  # noqa: E731
            (group, lk, n), lambda g, iq: (g, 0, 0))
        of, lse = pl.pallas_call(
            functools.partial(_flash_resident_kernel, scale=scale,
                              mask=mask, block_k=block_k),
            grid=(b * h // group, n_q),
            in_specs=[
                pl.BlockSpec((group, block_q, d), lambda g, iq: (g, iq, 0)),
                kv_spec(d), kv_spec(dv),
                pl.BlockSpec((1, 1, 1, lk),
                             lambda g, iq: (g * group // h, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((group, block_q, dv), lambda g, iq: (g, iq, 0)),
                pl.BlockSpec((group, block_q, 1), lambda g, iq: (g, iq, 0)),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            **common,
        )(qf, kf, vf, bias)
    else:
        def kv_tile(iq, ik):
            # a step with nothing to do names the nearest tile that has, so
            # the pipeline sees no new block and starts no copy
            if mask is None:
                return ik
            runs = kv_runs(mask, iq * block_q, block_q, block_k, n_kv, jnp)
            lo, _, _, hi = runs[-1]
            tile = jnp.clip(ik, lo, jnp.maximum(hi - 1, lo))
            for lo, _, _, hi in runs[-2::-1]:  # an earlier run, up to its end
                tile = jnp.where(ik < hi, jnp.clip(ik, lo, hi - 1), tile)
            return tile

        kv_spec = lambda n: pl.BlockSpec(  # noqa: E731
            (1, block_k, n), lambda bh, iq, ik: (bh, kv_tile(iq, ik), 0))
        of, lse = pl.pallas_call(
            functools.partial(_flash_kvgrid_kernel, scale=scale, mask=mask),
            grid=(b * h, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
                kv_spec(d), kv_spec(dv),
                pl.BlockSpec(
                    (1, 1, 1, block_k),
                    lambda bh, iq, ik: (bh // h, 0, 0, kv_tile(iq, ik))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv), lambda bh, iq, ik: (bh, iq, 0)),
                pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ],
            # the KV axis is a sequential accumulation (scratch carries
            # m/l/acc across ik); heads and query tiles are independent
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            **common,
        )(qf, kf, vf, bias)
    return of.reshape(b, h, lq, dv).transpose(0, 2, 1, 3), lse


def _flash_forward(q, k, v, bias, block_q: int, block_k: int,
                   mask=None, want_lse: bool = False, scale=None):
    """`block_q`/`block_k` are the backward's tile and the fallback's; of
    the forward they decide only whether the lengths tile at all (the
    backward consumes `lse` at that tile) and the granule of its own tile,
    which `flash_forward_tiling` chooses. `mask`: a mask of
    `attention_mask.py`, or None."""
    lq, lk, h, d = q.shape[1], k.shape[1], q.shape[2], q.shape[3]
    pq, pk = (lq, lk) if mask is None else (mask.period(lq), mask.period(lk))
    if pq % min(block_q, pq) or pk % min(block_k, pk):
        causal, window = causal_window(mask)
        out = blockwise_attention(q, k, v, bias, causal=causal,
                                  window=window, scale=scale)
        return (out, None) if want_lse else out
    tiling = flash_forward_tiling(lq, lk, d, q.dtype, mask, dv=v.shape[3],
                                  block_q=block_q, block_k=block_k, heads=h)
    out, lse = _flash_forward_tiled(q, k, v, bias, tiling, mask, scale)
    return (out, lse) if want_lse else out


# ------------------------------------------------------------------ pallas bwd


def _block_live(iq, ik, block_q, block_k, causal, window):
    """Whether a (q_block, kv_block) pair can contribute: at-or-below the
    causal diagonal AND, under a sliding window, not entirely older than
    every query's window."""
    live = ik * block_k <= iq * block_q + (block_q - 1)
    if window:
        live = jnp.logical_and(
            live,
            ik * block_k + (block_k - 1) >= iq * block_q - (window - 1),
        )
    return live


def _flash_bwd_scores(q, k, bias_row, lse, scale, causal, iq, ik,
                      block_q, block_k, window: int = 0):
    """Recompute the probability tile p = exp(s - lse) for one (q, kv) block
    pair — shared by the dq and dk/dv kernels."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s = s + bias_row.astype(jnp.float32)[None, :]
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        masked = cols > rows
        if window:
            masked = masked | (rows - cols >= window)
        s = s + jnp.where(masked, NEG_INF, 0.0)
    return jnp.exp(s - lse)


def _flash_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dd_ref,
                     dq_ref, acc_scr, *, scale, n_kv, causal,
                     block_q, block_k, window: int = 0):
    """dq tile: sequential grid over KV blocks, accumulator in VMEM.
    ds = p * (dO·vᵀ − D);  dq = scale · Σ_k ds·k."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        p = _flash_bwd_scores(
            q_ref[0], k_ref[0], bias_ref[0, 0, 0, :], lse_ref[0],
            scale, causal, iq, ik, block_q, block_k, window,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0])
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_block_live(iq, ik, block_q, block_k, causal, window))(
            _compute)
    else:
        _compute()

    @pl.when(ik == n_kv - 1)
    def _():
        dq_ref[0] = (acc_scr[:] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dd_ref,
                      dk_ref, dv_ref, dbias_ref, dk_scr, dv_scr, db_scr,
                      *, scale, n_q, causal, block_q, block_k,
                      window: int = 0):
    """dk/dv/dbias tiles: sequential grid over Q blocks per KV block.
    dv = Σ_q pᵀ·dO;  dk = scale · Σ_q dsᵀ·q;  dbias = Σ_q Σ_rows ds."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    def _compute():
        p = _flash_bwd_scores(
            q_ref[0], k_ref[0], bias_ref[0, 0, 0, :], lse_ref[0],
            scale, causal, iq, ik, block_q, block_k, window,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0])
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db_scr[:] += ds.sum(axis=0, keepdims=True)

    if causal:
        pl.when(_block_live(iq, ik, block_q, block_k, causal, window))(
            _compute)
    else:
        _compute()

    @pl.when(iq == n_q - 1)
    def _():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
        dbias_ref[0] = db_scr[:].astype(dbias_ref.dtype)


# Backward implementation selector.
#   "xla"     — XLA einsums over KV blocks consuming the pallas forward's
#               saved (o, lse) residuals: standard FlashAttention-2
#               backward math, no forward replay, no pallas in the
#               gradient path. THE DEFAULT: probe_flash_fix (r3, on
#               hardware) showed BOTH pallas backwards NaN under Mosaic
#               (dq/dk/dbias NaN, dv clean, interpret passes), so until a
#               hardware PASS is recorded the training path keeps the
#               validated pallas forward and a known-good backward.
#   "scratch" — pallas, cross-grid-step VMEM accumulators.
#   "loop"    — pallas, fori_loop per output block, no cross-step scratch
#               (r3 fix candidate; hardware verdict: still NaN — the bug
#               is in the shared ds dataflow).
#   "loop2"   — r4 fix candidate from the r3 NaN forensics. The hardware
#               evidence isolates the dd operand: dv (which never reads
#               dd) is clean in the SAME dkv kernel invocation whose
#               dk/dbias NaN, the forward out/lse are finite (out_err
#               6e-5; dv correct ⇒ p ⇒ lse reads fine), and every ds
#               term is mathematically finite. dd is the one operand
#               produced by an XLA reduction and read through a
#               lane-dim-1 BlockSpec (1, block_q, 1) — the layout public
#               TPU flash kernels avoid for row statistics. loop2 drops
#               the dd operand entirely: the kernels take the forward
#               output tile o (a normal (block_q, d) operand, like dO)
#               and recompute D = Σ_d dO∘O in-kernel in f32.
#   "ddpre"   — r5 fix candidate B (VERDICT r4 weak #2: one window, one
#               candidate). Keeps the loop kernels' dd operand but
#               produces it with a TRIVIAL pallas pre-kernel instead of
#               an XLA reduction — so the (BH, Lq, 1) row-stat array is
#               pallas-laid-out exactly like the forward's lse, which the
#               same kernels read cleanly. If the producer-layout theory
#               is right, ddpre passes; if ddpre NaNs while loop2 passes,
#               the bug is the lane-dim-1 CONSUMER BlockSpec itself.
#               Either way one window yields a decisive answer AND at
#               least one working pallas backward (or a minimal
#               reproducer for a backend bug).
# All variants are numerically identical in interpret/CPU mode
# (test_ring_attention pins it).
# KFT_FLASH_BWD_IMPL overrides the default (ROADMAP S1(b) gives each
# candidate its verdict against a float32 reference on the chip; D3 then
# keeps one). The pallas variants tile by flash_attention's `block`, the XLA
# one by flash_backward_xla_blocks and the forward by flash_forward_tiling,
# each from the shapes; `lse`, a per-row statistic of shape (B*H, Lq, 1), is
# all that passes between forward and backward.
import os as _os  # noqa: E402

_FLASH_BWD_IMPLS = ("xla", "loop2", "ddpre", "loop", "scratch")
FLASH_BWD_IMPL = _os.environ.get("KFT_FLASH_BWD_IMPL", "xla")
if FLASH_BWD_IMPL not in _FLASH_BWD_IMPLS:
    raise ValueError(
        f"KFT_FLASH_BWD_IMPL={FLASH_BWD_IMPL!r} is not one of "
        f"{_FLASH_BWD_IMPLS} — refusing to fall through to an arbitrary "
        "backward (the scratch kernels NaN on Mosaic)")


def _flash_backward_xla(qf, kf, vf, bias, gf, lse, dd, *, b, h, lq, lk, d,
                        scale, block_q, block_k, mask, out_dtypes,
                        bias_dtype):
    """Flash backward as XLA einsums over the live (query block, KV block)
    pairs, from saved residuals.

    Cheaper than jax.vjp(blockwise_attention) — which must REPLAY the
    whole online-softmax forward to rebuild residuals — by one full
    forward pass: p tiles come from exp(s − lse) with the lse the pallas
    forward already saved. The time follows the score elements it touches
    (float32 (BH, block_q, block_k) tiles of s, p, dp, ds), so it walks
    `flash_backward_live_pairs` only: a `scan` over the KV blocks, and
    inside it a loop over each run of query blocks that KV block's keys are
    visible to (one run under `Causal`, two under `BlockDiffusion`: clean
    and noisy queries). A pair the mask hides whole (probability and
    gradient exactly 0) is never computed; the mask still applies inside a
    live pair. `mask`: a mask of `attention_mask.py`, or None. Takes the
    same prefolded residuals as the pallas variants (one shared prep in
    _flash_backward).
    """
    dq_dtype, dk_dtype, dv_dtype = out_dtypes
    n_q, n_kv = lq // block_q, lk // block_k
    pairs = flash_backward_live_pairs(lq, lk, block_q, block_k, mask)
    # a KV block's live query blocks are runs [first, first + count), as many
    # runs a KV block as the fullest has (a run may be empty)
    live = [_unbroken_runs([iq for iq, ik in pairs if ik == j]) for j in range(n_kv)]
    n_runs = max(map(len, live))
    live = [runs + [(0, 0)] * (n_runs - len(runs)) for runs in live]
    first, count = (tuple(jnp.asarray([runs[r][part] for runs in live], jnp.int32)
                          for r in range(n_runs)) for part in (0, 1))
    # bias row per folded batch*head: (B,1,1,Lk) -> (BH, Lk)
    bias_bh = jnp.repeat(
        bias.reshape(b, lk).astype(jnp.float32), h, axis=0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    cut = lambda t, j: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        t, j * block_k, block_k, 1)
    # the query side is indexed by block, through a (BH, n_q, block_q, ..)
    # view: a dynamic index there is aligned by construction, where a dynamic
    # offset along Lq is not, and the update of dq then rewrites far more
    # than its block. One query block, every KV block live to it (every call
    # that is not causal), needs neither the view nor the loop, and is the
    # program it always was.
    one = n_q == 1 and len(pairs) == n_kv
    by_block = (lambda t: t) if one else (  # noqa: E731
        lambda t: t.reshape(b * h, n_q, block_q, *t.shape[2:]))
    block = (lambda t, i: t) if one else (  # noqa: E731
        lambda t, i: jax.lax.dynamic_index_in_dim(t, i, 1, keepdims=False))
    put = (lambda acc, t, i: t) if one else (  # noqa: E731
        lambda acc, t, i: jax.lax.dynamic_update_index_in_dim(acc, t, i, 1))
    qb, gb, lseb, ddb = by_block(qf), by_block(gf), by_block(lse), by_block(dd)

    def kv_step(dq_acc, xs):
        j, firsts_j, counts_j = xs
        kj, vj, bj = cut(kf, j), cut(vf, j), cut(bias_bh, j)

        def q_step(i, acc):
            dq_acc, dkj, dvj, dbj = acc
            qi, gi = block(qb, i), block(gb, i)
            s = jnp.einsum("bqd,bkd->bqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            s = s + bj[:, None, :]
            if mask is not None:
                r, c = i * block_q + rows, j * block_k + cols
                s = s + jnp.where(mask.hidden(r, c), NEG_INF, 0.0)
            p = jnp.exp(s - block(lseb, i))                  # (BH, bq, bk)
            dp = jnp.einsum("bqd,bkd->bqk", gi, vj,
                            preferred_element_type=jnp.float32)
            ds32 = p * (dp - block(ddb, i))
            ds = ds32.astype(qf.dtype)  # bf16 onto the MXU, like the kernels
            p16 = p.astype(qf.dtype)
            dq_acc = put(dq_acc, block(dq_acc, i) + jnp.einsum(
                "bqk,bkd->bqd", ds, kj,
                preferred_element_type=jnp.float32), i)
            dkj = dkj + jnp.einsum("bqk,bqd->bkd", ds, qi,
                                   preferred_element_type=jnp.float32)
            dvj = dvj + jnp.einsum("bqk,bqd->bkd", p16, gi,
                                   preferred_element_type=jnp.float32)
            # bias is (B, 1, 1, Lk): reduce rows AND heads, in f32 (the
            # pallas paths sum the f32 ds — a bf16 pre-cast would round
            # every element before a Lq*h-long reduction)
            dbj = dbj + ds32.sum(1).reshape(b, h, block_k).sum(1)
            return dq_acc, dkj, dvj, dbj

        zeros = jnp.zeros((b * h, block_k, d), jnp.float32)
        acc = (dq_acc, zeros, _zeros_of_width(zeros, vf),
               jnp.zeros((b, block_k), jnp.float32))
        if one:
            acc = q_step(0, acc)
        else:
            for first_j, count_j in zip(firsts_j, counts_j):
                acc = jax.lax.fori_loop(first_j, first_j + count_j, q_step, acc)
        dq_acc, dkj, dvj, dbj = acc
        return dq_acc, (dkj * scale, dvj, dbj)

    # the scope names what ran in any trace, as the forward's kernel name does
    with jax.named_scope(f"flash_bwd_xla_q{block_q}_k{block_k}"
                         f"_live{len(pairs)}of{n_q * n_kv}{name_suffix(mask)}"
                         + head_sizes_suffix(d, vf.shape[2])):
        dq_acc, (dks, dvs, dbs) = jax.lax.scan(
            kv_step, by_block(jnp.zeros((b * h, lq, d), jnp.float32)),
            (jnp.arange(n_kv), first, count))
        dqf = (dq_acc.reshape(b * h, lq, d) * scale).astype(dq_dtype)
        # scan stacks (n_kv, BH, bk, d): move the block axis back into Lk
        dkf = jnp.moveaxis(dks, 0, 1).reshape(b * h, lk, d).astype(dk_dtype)
        dvf = jnp.moveaxis(dvs, 0, 1).reshape(b * h, lk, -1).astype(dv_dtype)
        dbias = jnp.moveaxis(dbs, 0, 1).reshape(b, lk)[:, None, None, :]
    return dqf, dkf, dvf, dbias.astype(bias_dtype)


def _flash_dq_loop_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                          dd_ref, dq_ref, *, scale, n_kv, causal,
                          block_q, block_k, window: int = 0):
    """dq for one q block: fori_loop over kv blocks, accumulator carried as
    a loop value (registers/VMEM), output written exactly once."""
    iq = pl.program_id(1)
    qb = q_ref[0]
    dob = do_ref[0]
    lseb = lse_ref[0]
    ddb = dd_ref[0]

    def body(ik, acc):
        kb = k_ref[0, pl.dslice(ik * block_k, block_k), :]
        vb = v_ref[0, pl.dslice(ik * block_k, block_k), :]
        bias_row = bias_ref[0, 0, 0, pl.dslice(ik * block_k, block_k)]
        p = _flash_bwd_scores(qb, kb, bias_row, lseb, scale, causal, iq, ik,
                              block_q, block_k, window)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - ddb)
        return acc + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # kv blocks strictly above the diagonal contribute nothing
        upper = jnp.minimum(
            (iq * block_q + block_q - 1) // block_k + 1, n_kv
        )
        lower = (jnp.maximum(iq * block_q - (window - 1), 0) // block_k
                 if window else 0)
    else:
        upper, lower = n_kv, 0
    acc = jax.lax.fori_loop(
        lower, upper, body, jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)
    )
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_dkv_loop_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                           dd_ref, dk_ref, dv_ref, dbias_ref,
                           *, scale, n_q, causal, block_q, block_k,
                           window: int = 0):
    """dk/dv/dbias for one kv block: fori_loop over q blocks, three
    accumulators carried as loop values, outputs written exactly once."""
    ik = pl.program_id(1)
    kb = k_ref[0]
    vb = v_ref[0]
    bias_row = bias_ref[0, 0, 0, :]
    d = q_ref.shape[2]

    def body(iq, carry):
        dk_acc, dv_acc, db_acc = carry
        qb = q_ref[0, pl.dslice(iq * block_q, block_q), :]
        dob = do_ref[0, pl.dslice(iq * block_q, block_q), :]
        lseb = lse_ref[0, pl.dslice(iq * block_q, block_q), :]
        ddb = dd_ref[0, pl.dslice(iq * block_q, block_q), :]
        p = _flash_bwd_scores(qb, kb, bias_row, lseb, scale, causal, iq, ik,
                              block_q, block_k, window)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - ddb)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db_acc = db_acc + ds.sum(axis=0, keepdims=True)
        return dk_acc, dv_acc, db_acc

    if causal:
        # q blocks strictly above the diagonal see nothing of this kv block
        lower = (ik * block_k) // block_q
        upper = (jnp.minimum(
            (ik * block_k + block_k - 1 + window - 1) // block_q + 1, n_q)
            if window else n_q)
    else:
        lower, upper = 0, n_q
    init = (
        jnp.zeros((block_k, d), jnp.float32),
        jnp.zeros((block_k, d), jnp.float32),
        jnp.zeros((1, block_k), jnp.float32),
    )
    dk_acc, dv_acc, db_acc = jax.lax.fori_loop(lower, upper, body, init)
    dk_ref[0] = (dk_acc * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)
    dbias_ref[0] = db_acc.astype(dbias_ref.dtype)


def _flash_dq_loop2_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                           lse_ref, dq_ref, *, scale, n_kv, causal,
                           block_q, block_k, window: int = 0):
    """dq for one q block, D recomputed in-kernel from (dO, O) tiles —
    no lane-dim-1 dd operand (see FLASH_BWD_IMPL "loop2" note)."""
    iq = pl.program_id(1)
    qb = q_ref[0]
    dob = do_ref[0]
    lseb = lse_ref[0]
    ddb = (dob.astype(jnp.float32) * o_ref[0].astype(jnp.float32)).sum(
        axis=-1, keepdims=True)

    def body(ik, acc):
        kb = k_ref[0, pl.dslice(ik * block_k, block_k), :]
        vb = v_ref[0, pl.dslice(ik * block_k, block_k), :]
        bias_row = bias_ref[0, 0, 0, pl.dslice(ik * block_k, block_k)]
        p = _flash_bwd_scores(qb, kb, bias_row, lseb, scale, causal, iq, ik,
                              block_q, block_k, window)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - ddb)
        return acc + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        upper = jnp.minimum(
            (iq * block_q + block_q - 1) // block_k + 1, n_kv
        )
        # sliding window: kv blocks wholly older than every query's
        # window contribute nothing
        lower = (jnp.maximum(iq * block_q - (window - 1), 0) // block_k
                 if window else 0)
    else:
        upper, lower = n_kv, 0
    acc = jax.lax.fori_loop(
        lower, upper, body, jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)
    )
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_dkv_loop2_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                            lse_ref, dk_ref, dv_ref, dbias_ref,
                            *, scale, n_q, causal, block_q, block_k,
                            window: int = 0):
    """dk/dv/dbias for one kv block, D recomputed in-kernel per q tile
    from (dO, O) — no lane-dim-1 dd operand."""
    ik = pl.program_id(1)
    kb = k_ref[0]
    vb = v_ref[0]
    bias_row = bias_ref[0, 0, 0, :]
    d = q_ref.shape[2]

    def body(iq, carry):
        dk_acc, dv_acc, db_acc = carry
        qb = q_ref[0, pl.dslice(iq * block_q, block_q), :]
        dob = do_ref[0, pl.dslice(iq * block_q, block_q), :]
        ob = o_ref[0, pl.dslice(iq * block_q, block_q), :]
        lseb = lse_ref[0, pl.dslice(iq * block_q, block_q), :]
        ddb = (dob.astype(jnp.float32) * ob.astype(jnp.float32)).sum(
            axis=-1, keepdims=True)
        p = _flash_bwd_scores(qb, kb, bias_row, lseb, scale, causal, iq, ik,
                              block_q, block_k, window)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - ddb)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db_acc = db_acc + ds.sum(axis=0, keepdims=True)
        return dk_acc, dv_acc, db_acc

    if causal:
        lower = (ik * block_k) // block_q
        # sliding window: q blocks wholly past this kv block's window
        # (r >= c + window for every r, c) contribute nothing
        upper = (jnp.minimum(
            (ik * block_k + block_k - 1 + window - 1) // block_q + 1, n_q)
            if window else n_q)
    else:
        lower, upper = 0, n_q
    init = (
        jnp.zeros((block_k, d), jnp.float32),
        jnp.zeros((block_k, d), jnp.float32),
        jnp.zeros((1, block_k), jnp.float32),
    )
    dk_acc, dv_acc, db_acc = jax.lax.fori_loop(lower, upper, body, init)
    dk_ref[0] = (dk_acc * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)
    dbias_ref[0] = db_acc.astype(dbias_ref.dtype)


def _flash_backward_loop2(qf, kf, vf, bias, gf, of, lse, *, b, h, lq, lk, d,
                          scale, block_q, block_k, n_q, n_kv, causal,
                          interpret, out_dtypes, window: int = 0):
    """loop2 backward: grid over output blocks, D in-kernel from (dO, O)."""
    dq_dtype, dk_dtype, dv_dtype = out_dtypes
    dqf = pl.pallas_call(
        functools.partial(_flash_dq_loop2_kernel, scale=scale, n_kv=n_kv,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, lk, d), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, lk, d), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, 1, 1, lk), lambda bh, iq, h=h: (bh // h, 0, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), dq_dtype),
        interpret=interpret,
    )(qf, kf, vf, bias, gf, of, lse)

    dkf, dvf, dbias_bh = pl.pallas_call(
        functools.partial(_flash_dkv_loop2_kernel, scale=scale, n_q=n_q,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(b * h, n_kv),
        in_specs=[
            pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec(
                (1, 1, 1, block_k), lambda bh, ik, h=h: (bh // h, 0, 0, ik)
            ),
            pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, lq, 1), lambda bh, ik: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, ik: (bh, 0, ik)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lk, d), dk_dtype),
            jax.ShapeDtypeStruct((b * h, lk, d), dv_dtype),
            jax.ShapeDtypeStruct((b * h, 1, lk), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, bias, gf, of, lse)
    return dqf, dkf, dvf, dbias_bh


def _flash_backward_loop(qf, kf, vf, bias, gf, lse, dd, *, b, h, lq, lk, d,
                         scale, block_q, block_k, n_q, n_kv, causal,
                         interpret, out_dtypes, window: int = 0):
    """Loop-variant backward: grid over output blocks only; the full
    opposite-axis sequence is resident per kernel invocation (fine for the
    per-shard lengths context parallelism leaves on a chip)."""
    dq_dtype, dk_dtype, dv_dtype = out_dtypes
    dqf = pl.pallas_call(
        functools.partial(_flash_dq_loop_kernel, scale=scale, n_kv=n_kv,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, lk, d), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, lk, d), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, 1, 1, lk), lambda bh, iq, h=h: (bh // h, 0, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), dq_dtype),
        interpret=interpret,
    )(qf, kf, vf, bias, gf, lse, dd)

    dkf, dvf, dbias_bh = pl.pallas_call(
        functools.partial(_flash_dkv_loop_kernel, scale=scale, n_q=n_q,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(b * h, n_kv),
        in_specs=[
            pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec(
                (1, 1, 1, block_k), lambda bh, ik, h=h: (bh // h, 0, 0, ik)
            ),
            pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, lq, 1), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, lq, 1), lambda bh, ik: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, ik: (bh, 0, ik)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lk, d), dk_dtype),
            jax.ShapeDtypeStruct((b * h, lk, d), dv_dtype),
            jax.ShapeDtypeStruct((b * h, 1, lk), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, bias, gf, lse, dd)
    return dqf, dkf, dvf, dbias_bh


def _dd_prekernel(gf, of, *, b, h, lq, d, block_q, n_q, interpret):
    """D = Σ_d dO∘O produced by a trivial pallas kernel, so the
    (BH, Lq, 1) row-stat operand the loop kernels read through their
    lane-dim-1 BlockSpec is PALLAS-laid-out — exactly like the forward's
    lse, which those kernels demonstrably read cleanly on hardware
    (r3 probe: dv correct ⇒ p ⇒ lse fine). Fix candidate B for the
    Mosaic dd NaN (see FLASH_BWD_IMPL "ddpre" note)."""
    def kernel(do_ref, o_ref, dd_ref):
        dd_ref[0] = (do_ref[0].astype(jnp.float32)
                     * o_ref[0].astype(jnp.float32)).sum(-1, keepdims=True)

    return pl.pallas_call(
        kernel,
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, iq: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, 1), jnp.float32),
        interpret=interpret,
    )(gf, of)


def _flash_backward(q, k, v, bias, o, lse, g, block_q, block_k, mask,
                    impl: str | None = None, scale=None):
    if (impl or FLASH_BWD_IMPL) != "xla":
        causal, window = _pallas_backward_masks(mask, q, v, scale)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = 1.0 / (d**0.5) if scale is None else scale
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    fold = lambda t, L: t.transpose(0, 2, 1, 3).reshape(b * h, L, -1)  # noqa: E731
    qf, kf, vf = fold(q, lq), fold(k, lk), fold(v, lk)
    of, gf = fold(o, lq), fold(g, lq)
    n_q, n_kv = lq // block_q, lk // block_k
    interpret = jax.default_backend() == "cpu"

    def _dd():
        # D_i = Σ_d dO_i · O_i (FlashAttention-2 softmax-jacobian term) —
        # only the xla/loop/scratch backwards consume this XLA-produced
        # reduction; loop2 recomputes D in-kernel (its raison d'être)
        return (gf.astype(jnp.float32) * of.astype(jnp.float32)).sum(
            -1, keepdims=True)

    if (impl or FLASH_BWD_IMPL) == "xla":
        xla_q, xla_k = flash_backward_xla_blocks(lq, lk, block_q, block_k,
                                                 mask)
        dqf, dkf, dvf, dbias = _flash_backward_xla(
            qf, kf, vf, bias, gf, lse, _dd(), b=b, h=h, lq=lq, lk=lk, d=d,
            scale=scale, block_q=xla_q, block_k=xla_k, mask=mask,
            out_dtypes=(q.dtype, k.dtype, v.dtype), bias_dtype=bias.dtype,
        )
        unfold = lambda t, L: t.reshape(b, h, L, -1).transpose(0, 2, 1, 3)  # noqa: E731
        return unfold(dqf, lq), unfold(dkf, lk), unfold(dvf, lk), dbias

    if (impl or FLASH_BWD_IMPL) == "loop2":
        dqf, dkf, dvf, dbias_bh = _flash_backward_loop2(
            qf, kf, vf, bias, gf, of, lse, b=b, h=h, lq=lq, lk=lk, d=d,
            scale=scale, block_q=block_q, block_k=block_k, n_q=n_q,
            n_kv=n_kv, causal=causal, interpret=interpret,
            out_dtypes=(q.dtype, k.dtype, v.dtype), window=window,
        )
        unfold = lambda t, L: t.reshape(b, h, L, d).transpose(0, 2, 1, 3)  # noqa: E731
        dbias = dbias_bh.reshape(b, h, 1, lk).sum(axis=1, keepdims=False)
        dbias = dbias[:, None, :, :].astype(bias.dtype)  # (B, 1, 1, Lk)
        return unfold(dqf, lq), unfold(dkf, lk), unfold(dvf, lk), dbias

    if (impl or FLASH_BWD_IMPL) in ("loop", "ddpre"):
        # same loop kernels either way; ddpre differs ONLY in who produces
        # the dd operand (pallas pre-kernel vs XLA reduction) — the exact
        # single-variable experiment the r3 forensics call for
        dd = (_dd_prekernel(gf, of, b=b, h=h, lq=lq, d=d, block_q=block_q,
                            n_q=n_q, interpret=interpret)
              if (impl or FLASH_BWD_IMPL) == "ddpre" else _dd())
        dqf, dkf, dvf, dbias_bh = _flash_backward_loop(
            qf, kf, vf, bias, gf, lse, dd, b=b, h=h, lq=lq, lk=lk, d=d,
            scale=scale, block_q=block_q, block_k=block_k, n_q=n_q,
            n_kv=n_kv, causal=causal, interpret=interpret,
            out_dtypes=(q.dtype, k.dtype, v.dtype), window=window,
        )
        unfold = lambda t, L: t.reshape(b, h, L, d).transpose(0, 2, 1, 3)  # noqa: E731
        dbias = dbias_bh.reshape(b, h, 1, lk).sum(axis=1, keepdims=False)
        dbias = dbias[:, None, :, :].astype(bias.dtype)  # (B, 1, 1, Lk)
        return unfold(dqf, lq), unfold(dkf, lk), unfold(dvf, lk), dbias

    if (impl or FLASH_BWD_IMPL) != "scratch":
        raise ValueError(
            f"unknown flash backward impl {(impl or FLASH_BWD_IMPL)!r} "
            f"(one of {_FLASH_BWD_IMPLS})")
    dd = _dd()
    qspec = pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0))
    bspec = pl.BlockSpec(
        (1, 1, 1, block_k), lambda bh, iq, ik, h=h: (bh // h, 0, 0, ik)
    )
    rowspec = pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0))

    dqf = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, n_kv=n_kv,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(b * h, n_q, n_kv),
        in_specs=[qspec, kspec, kspec, bspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, bias, gf, lse, dd)

    # dkv grid: (bh, KV block, Q block) — q varies fastest
    qspec2 = pl.BlockSpec((1, block_q, d), lambda bh, ik, iq: (bh, iq, 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0))
    bspec2 = pl.BlockSpec(
        (1, 1, 1, block_k), lambda bh, ik, iq, h=h: (bh // h, 0, 0, ik)
    )
    rowspec2 = pl.BlockSpec((1, block_q, 1), lambda bh, ik, iq: (bh, iq, 0))
    dkf, dvf, dbias_bh = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, n_q=n_q,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(b * h, n_kv, n_q),
        in_specs=[qspec2, kspec2, kspec2, bspec2, qspec2, rowspec2, rowspec2],
        out_specs=[
            kspec2, kspec2,
            pl.BlockSpec((1, 1, block_k), lambda bh, ik, iq: (bh, 0, ik)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, lk, d), v.dtype),
            jax.ShapeDtypeStruct((b * h, 1, lk), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, bias, gf, lse, dd)

    unfold = lambda t, L: t.reshape(b, h, L, d).transpose(0, 2, 1, 3)  # noqa: E731
    dbias = dbias_bh.reshape(b, h, 1, lk).sum(axis=1, keepdims=False)
    dbias = dbias[:, None, :, :].astype(bias.dtype)  # (B, 1, 1, Lk)
    return unfold(dqf, lq), unfold(dkf, lk), unfold(dvf, lk), dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, block_q, block_k, mask, scale=None):
    return _flash_forward(q, k, v, bias, block_q, block_k, mask, scale=scale)


def _flash_fwd(q, k, v, bias, block_q, block_k, mask, scale=None):
    # one source of truth for the fused-vs-fallback decision: the forward
    # itself — lse is None exactly when it took the blockwise fallback
    out, lse = _flash_forward(
        q, k, v, bias, block_q, block_k, mask, want_lse=True, scale=scale,
    )
    return _flash_residuals(q, k, v, bias, out, lse)


def _flash_bwd(block_q, block_k, mask, scale, residuals, g):
    q, k, v, bias, o, lse = residuals
    if lse is not None:
        # fused pallas backward: recompute probability tiles from the saved
        # logsumexp — no O(L²) residuals, no full forward replay
        return _flash_backward(q, k, v, bias, o, lse, g, block_q, block_k,
                               mask, scale=scale)
    # ragged shapes fell back to blockwise in the forward: mirror it here
    causal, window = causal_window(mask)
    _, vjp = jax.vjp(
        lambda q, k, v, bias: blockwise_attention(
            q, k, v, bias, block_k, causal=causal, window=window, scale=scale
        ),
        q, k, v, bias,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                    block: int = 128, causal: bool = False,
                    window: int = 0, mask=None, scale: float | None = None):
    """Pallas flash attention (single device / per-shard): a pallas forward
    and a backward from its saved (out, lse), by default XLA's (FLASH_BWD_IMPL);
    attention dropout unsupported. q and k (B, L, H, D) share a head size, v
    (B, Lk, H, Dv) may have another, which is the output's (latent attention:
    192 | 128; nothing is padded in HBM); `scale` multiplies the scores, None
    is 1/sqrt(D). `mask` is a rule over (row, column) of `attention_mask.py`:
    `Causal(window)` (query i sees keys in (i - window, i]), `BlockDiffusion(half,
    block)`, or None for every key; `causal` and `window` spell the first for the
    callers that always did; `bias` (B, 1, 1, Lk) is added over the keys whatever
    the mask. Tiles the mask hides whole are skipped in forward and backward
    (`kv_runs`, `flash_backward_live_pairs`): the attention costs what is visible,
    and no (Lq, Lk) bias is ever built. `block` is the granule of the backward's
    blocks (flash_backward_xla_blocks widens them from the shapes) and the
    blockwise fallback's (lengths it does not tile take the fallback, which
    knows the causal and window masks only); the forward kernel chooses its
    own tile from the shapes and the two head sizes (flash_forward_tiling)."""
    if dropout_rate:
        raise NotImplementedError("attention dropout unsupported in flash path")
    if mask is None:
        mask = mask_of(causal, window)
    elif causal or window:
        raise ValueError("give the mask, or causal and window, not both")

    def per_device(q, k, v, bias):
        return _flash(q, k, v, bias, block, block, mask, scale)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or in_manual_region():
        return per_device(q, k, v, bias)
    # Mosaic kernels cannot be partitioned automatically (the chip refuses
    # the step outright): under a mesh each device runs the kernel on its
    # own batch rows and heads, the whole sequence local — the layout
    # attention has when nothing shards the sequence
    return jax.shard_map(
        per_device,
        in_specs=(FLASH_SPEC, FLASH_SPEC, FLASH_SPEC, FLASH_BIAS_SPEC),
        out_specs=FLASH_SPEC,
        check_vma=False,
    )(q, k, v, bias)


#: the XLA backward's blocks, timed on the v5e at nine shapes (PERF.md, PR 29;
#: ms a call). Under a causal mask narrow blocks hug the diagonal and wide
#: ones pay fewer steps: no block wider than 512 (at (1,8192,32,128) with
#: window 2,048: 512x512 8.49, 256x1024 9.25, 256x512 9.47, 512x256 11.30,
#: 1024x512 12.34, the parent's whole square 67.6), eight query blocks to a
#: length (at (8,1024,16,64): 128x256 1.50, 256x256 1.71, 64x256 1.89, the
#: parent 2.98) and four KV blocks, whose step also pays the K/V slices and
#: the write of dk and dv (128x128 1.60, 128x512 1.56). Not causal, every pair
#: is live and nothing is gained: one query block, which needs no loop, and
#: the KV block the parent walked with (a sixteenth of the keys up to 512, the
#: caller's block up to 2k keys): the parent's program, and its time
#: (BERT-like (32,512,12,64): 3.00 both; (1,8192,32,128): 67.6 both).
_FLASH_BWD_XLA_WIDEST = 512
_FLASH_BWD_XLA_Q_BLOCKS, _FLASH_BWD_XLA_KV_BLOCKS = 8, 4
_FLASH_BWD_XLA_FULL_KV_BLOCKS = 16


def flash_backward_xla_blocks(lq: int, lk: int, block_q: int, block_k: int,
                              mask) -> tuple[int, int]:
    """The XLA backward's (query block, KV block), from what the call can
    see: multiples of the caller's blocks (they tile the lengths) that
    divide the lengths (a mask's period of them), never under the caller's.
    It sits at the end of the file because the Mosaic payload of the forward kernel embeds its callers'
    line numbers (ROADMAP D17): a line added above `flash_attention` is
    another program for every model that calls it."""
    widest = _FLASH_BWD_XLA_WIDEST
    if mask is None:
        kv = min(lk // _FLASH_BWD_XLA_FULL_KV_BLOCKS, widest)
        return lq, _largest_tile(lk, max(block_k, kv), block_k)
    want_q = min(lq // _FLASH_BWD_XLA_Q_BLOCKS, widest)
    want_k = min(lk // _FLASH_BWD_XLA_KV_BLOCKS, widest)
    pq, pk = mask.period(lq), mask.period(lk)
    block_q, block_k = min(block_q, pq), min(block_k, pk)
    return (_largest_tile(pq, max(block_q, want_q), block_q),
            _largest_tile(pk, max(block_k, want_k), block_k))


def flash_backward_live_pairs(lq: int, lk: int, block_q: int, block_k: int,
                              mask) -> list[tuple[int, int]]:
    """The (query block, KV block) pairs with at least one visible element
    under the mask as `_flash_backward_xla` applies it (a mask of
    `attention_mask.py`, or None; under `Causal` query row `i` sees key
    column `c` iff `c <= i` and, with a window, `i - c < window`, both
    counted from 0). KV block major, the query blocks of one KV block in a
    row; no mask: every pair. A KV block that no query sees (`lq < lk`)
    has no pair, and its `dk`, `dv`, `dbias` stay zero."""
    n_q, n_kv = lq // block_q, lk // block_k
    if mask is None:
        return [(iq, ik) for ik in range(n_kv) for iq in range(n_q)]
    live = live_tile_pairs(mask, lq, lk, block_q, block_k)
    return [(iq, ik) for ik in range(n_kv) for iq in range(n_q) if live[iq, ik]]


def _unbroken_runs(blocks: list[int]) -> list[tuple[int, int]]:
    """Ascending block indices as runs (first, count) of consecutive ones."""
    runs: list[tuple[int, int]] = []
    for i in blocks:
        if runs and i == sum(runs[-1]):
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((i, 1))
    return runs


from jax.ad_checkpoint import checkpoint_name  # noqa: E402

#: The two arrays the fused forward hands its backward, by the names a remat
#: policy can keep them under (`jax.ad_checkpoint.checkpoint_name`). The
#: blockwise fallback names nothing, and without `remat` a name lowers to
#: nothing.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")

#: The remat policy of the decoder blocks that can run the flash kernel
#: (`GPTLM`, `AfmoeLM`, `SdarMoeLM`): recompute the block in the backward pass
#: but for the kernel's output and row statistic, so that pass does not launch
#: the forward kernel again. A block whose attention is dense emits no name and
#: is recomputed whole. What a job gives up between the passes, a layer a row of
#: batch at 8,192 positions, 32 heads of 128: `out` 64 MiB in bf16 and `lse`
#: 128 MiB (1 MiB of float32 values that the chip pads 128-fold, kept in the
#: kernel's own layout: PERF.md, PR 33).
FLASH_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    *FLASH_RESIDUAL_NAMES)


def _flash_residuals(q, k, v, bias, out, lse):
    """`_flash_fwd`'s return: the output and the residuals of `_flash_bwd`,
    `out` and `lse` under FLASH_RESIDUAL_NAMES on the fused path. The output is
    the named array too: what consumes it downstream then needs no forward
    kernel either. Down here for the reason `flash_backward_xla_blocks` is."""
    if lse is None:
        return out, (q, k, v, bias, None, None)
    out, lse = map(checkpoint_name, (out, lse), FLASH_RESIDUAL_NAMES)
    return out, (q, k, v, bias, out, lse)


def head_sizes_suffix(d: int, dv: int) -> str:
    """What a kernel's name says of its head sizes: nothing where q, k and v
    share one (every name stays what it was), `_d192v128` where v's differs."""
    return "" if dv == d else f"_d{d}v{dv}"


def _zeros_of_width(zeros, like):
    """`zeros` itself where `like` has its last size (one head size: the XLA
    backward's program as it was), else zeros at `like`'s last size: dv's
    accumulator under keys wider than the values."""
    if like.shape[-1] == zeros.shape[-1]:
        return zeros
    return jnp.zeros((*zeros.shape[:-1], like.shape[-1]), zeros.dtype)


def _pallas_backward_masks(mask, q, v, scale):
    """(causal, window) for the four pallas backwards, which know one head size,
    the scale 1/sqrt(D) and the causal and window masks only (ROADMAP D3)."""
    if v.shape[-1] != q.shape[-1] or scale is not None:
        raise NotImplementedError(
            "the pallas flash backwards take one head size for q, k and v and no "
            f"given scale (got {q.shape[-1]} | {v.shape[-1]}, scale {scale}): the "
            "XLA backward (KFT_FLASH_BWD_IMPL=xla, the default) takes both")
    return causal_window(mask)
