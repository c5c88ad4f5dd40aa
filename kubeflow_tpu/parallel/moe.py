"""Expert parallelism — mixture-of-experts dispatch over the `expert` axis.

Reference parity: the reference has no in-platform MoE (DeepSpeed-MoE user
images supply it — SURVEY.md §2.2 "Expert parallel (EP/MoE)"); here it is a
first-class construct, TPU-first:

  - EP is a subdivision of data parallelism (the Megatron/DeepSpeed-EP
    layout): the batch is sharded over (data, fsdp, expert) and expert
    weights over `expert`, so the token exchange is a true all-to-all that
    rides ICI inside the expert group.
  - The dispatch is a *partial-manual* shard_map over the data-like axes
    (data, fsdp, expert): `lax.all_to_all` is explicit (the one collective
    that matters) and routing is shard-local, while model/context shardings
    inside the body stay automatic — XLA still inserts the TP psums for the
    expert matmuls. (With `global_dispatch=True` only `expert` is manual
    and fsdp stays auto inside the body.)
  - Top-k softmax router (f32), capacity-factor slotting via cumsum
    priority, dropped tokens pass through with zero combine weight (the
    residual connection carries them), Switch-style load-balance aux loss.

Capacity is LOCAL per (data, fsdp, expert) shard: C = ceil(k * t_local * cf
/ E) where t_local is the shard's own token count. The dispatch shard_map is
manual over the data-like axes too, so the slot-assignment cumsum never
spans data shards — no collective scan inside the router (the Switch/
DeepSpeed-EP local-dispatch recipe; the earlier GShard-style global cumsum
ran a cross-shard scan per MoE layer). `global_dispatch=True` restores the
old behavior (global capacity pool, cross-shard cumsum) for comparison.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_MODEL,
    in_manual_region,
)

# Param-path regex -> PartitionSpec for MoE params (merged into model rules).
MOE_PARTITION_RULES: list[tuple[str, P]] = [
    (r"moe/(w_up|w_gate)$", P(AXIS_EXPERT, AXIS_FSDP, AXIS_MODEL)),
    (r"moe/(b_up|b_gate)$", P(AXIS_EXPERT, AXIS_MODEL)),
    (r"moe/w_down$", P(AXIS_EXPERT, AXIS_MODEL, AXIS_FSDP)),
    (r"moe/b_down$", P(AXIS_EXPERT, AXIS_FSDP)),
]


def _route(logits: jax.Array, top_k: int, capacity: int):
    """Shared routing math for both the sharded and dense paths.

    logits: (T, E) f32. Returns (combine (T, E, C), dispatch (T, E, C) bool,
    aux_loss scalar). Tokens beyond an expert's capacity are dropped (zero
    combine weight); the caller's residual connection carries them through.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)              # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, e, dtype=logits.dtype)   # (T, K, E)

    # Switch-transformer load balance: E * Σ_e fraction_of_tokens_e · mean_prob_e
    frac = onehot[:, 0].mean(axis=0)                      # top-1 assignment share
    aux = e * jnp.sum(frac * probs.mean(axis=0))

    # slot position: cumsum priority in (token-major, then k) order
    flat = onehot.reshape(t * top_k, e)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat)     # (T*K, E)
    pos = (pos_in_expert * flat).sum(-1).reshape(t, top_k).astype(jnp.int32)
    keep = (pos < capacity).astype(logits.dtype)
    slot = jax.nn.one_hot(pos, capacity, dtype=logits.dtype)  # (T, K, C)

    combine = jnp.einsum("tke,tkc->tec", onehot * (gates * keep)[..., None], slot)
    dispatch = jnp.einsum("tke,tkc->tec", onehot * keep[..., None], slot)
    return combine, dispatch, aux


class MoeMlp(nn.Module):
    """Drop-in MoE replacement for a transformer MLP block.

    __call__(x) with x: (B, L, H) returns (B, L, H); the load-balance aux
    loss is sown into the 'losses' collection (the Trainer adds every
    'losses' leaf to the objective).
    """

    hidden_size: int
    mlp_dim: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 2.0
    dtype: Any = jnp.float32
    # True restores the round-2-initial GShard-style dispatch: one capacity
    # pool over the whole (data x fsdp x expert) batch, slot cumsum as a
    # cross-shard collective scan. Default is local dispatch (see module
    # docstring).
    global_dispatch: bool = False
    # Expert FFN shape: "gelu" (GShard/BERT default, biased) or "swiglu"
    # (Mixtral: silu(gate)·up per expert); use_bias=False drops every
    # expert bias. Defaults keep the historical parameter tree byte-
    # identical (checkpoint-compatible).
    activation: str = "gelu"
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array, dropless: bool = False) -> jax.Array:
        h, f, e = self.hidden_size, self.mlp_dim, self.num_experts
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(
                f"activation {self.activation!r} is not gelu|swiglu")
        router = self.param(
            "router", nn.initializers.normal(stddev=0.02), (h, e), jnp.float32
        )
        init = nn.initializers.lecun_normal()
        zeros = nn.initializers.zeros
        swiglu = self.activation == "swiglu"
        # weights live in ONE dict pytree so every dispatch path (dropless
        # / local shard_map / global) threads the same set, whatever the
        # activation/bias combination. Creation ORDER preserves the
        # historical sequence (w_up, b_up, w_down, b_down) with new swiglu
        # params strictly after — flax folds a per-scope call counter into
        # each param's init RNG, so reordering would silently change
        # fresh-init values for the default config.
        ws = {"w_up": self.param("w_up", init, (e, h, f))}
        if self.use_bias:
            ws["b_up"] = self.param("b_up", zeros, (e, f))
        ws["w_down"] = self.param("w_down", init, (e, f, h))
        if self.use_bias:
            ws["b_down"] = self.param("b_down", zeros, (e, h))
        if swiglu:
            ws["w_gate"] = self.param("w_gate", init, (e, h, f))
            if self.use_bias:
                ws["b_gate"] = self.param("b_gate", zeros, (e, f))

        def ffn(xin, ws):
            """Per-expert FFN: xin (E, C, H) against stacked weights."""
            up = jnp.einsum("ech,ehf->ecf", xin,
                            ws["w_up"].astype(xin.dtype))
            if "b_up" in ws:
                up = up + ws["b_up"].astype(xin.dtype)[:, None, :]
            if swiglu:
                gate = jnp.einsum("ech,ehf->ecf", xin,
                                  ws["w_gate"].astype(xin.dtype))
                if "b_gate" in ws:
                    gate = gate + ws["b_gate"].astype(xin.dtype)[:, None, :]
                act = nn.silu(gate) * up
            else:
                act = nn.gelu(up)
            y = jnp.einsum("ecf,efh->ech", act, ws["w_down"].astype(xin.dtype))
            if "b_down" in ws:
                y = y + ws["b_down"].astype(xin.dtype)[:, None, :]
            return y

        if dropless:
            # DROPLESS routing — the decode path (VERDICT r4 #6). Every
            # token gets its full top-k combine, no capacity, no cumsum:
            # each token's output depends only on ITS hidden state, so
            # rows are independent and continuous batching / speculative
            # verify compose with MoE exactly (capacity dispatch couples
            # rows: the drop pattern depends on batch composition).
            # Cost: every expert runs on every token — at decode widths
            # (1..gamma+1 tokens/row) the weights stream from HBM anyway
            # (bandwidth-bound), so the extra FLOPs ride the same bytes.
            # No aux loss: decode never trains.
            b, l, _ = x.shape
            xt = x.reshape(b * l, h)
            logits = xt.astype(jnp.float32) @ router        # (T, E)
            probs = jax.nn.softmax(logits, axis=-1)
            gates, idx = jax.lax.top_k(probs, self.top_k)
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
            weight = (jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * gates[..., None]).sum(1)            # (T, E)
            down = ffn(jnp.broadcast_to(xt[None], (e, b * l, h)), ws)
            y = jnp.einsum("te,eth->th", weight.astype(xt.dtype), down)
            return y.reshape(b, l, h)

        mesh = jax.sharding.get_abstract_mesh()
        ep = 1 if mesh.empty else mesh.shape.get(AXIS_EXPERT, 1)
        if e % ep:
            raise ValueError(f"num_experts {e} not divisible by expert axis {ep}")
        # data-like extents: with local dispatch these axes join the manual
        # region so the router's cumsum stays shard-local. A context-sharded
        # sequence dim joins too — routing is per-token, so context shards
        # are just more local tokens (otherwise the partitioner must gather
        # L at the dispatch boundary, a full-remat reshard under a pipeline
        # ring with sequence parallelism).
        dp = 1 if mesh.empty else mesh.shape.get(AXIS_DATA, 1)
        fs = 1 if mesh.empty else mesh.shape.get(AXIS_FSDP, 1)
        cp = 1 if mesh.empty else mesh.shape.get(AXIS_CONTEXT, 1)

        def moe_body(xb, rw, ws, manual_axes):
            """xb (B_local, L, H); ws: dict of stacked expert weights,
            leading dim E/ep inside the manual region. With local dispatch
            the data axes are manual too, so `t` — and the capacity — are
            per-shard and the cumsum in _route never crosses shards."""
            b, l, _ = xb.shape
            t = b * l
            cap = int(np.ceil(self.top_k * t * self.capacity_factor / e))
            xt = xb.reshape(t, h)
            logits = xt.astype(jnp.float32) @ rw
            combine, dispatch, aux = _route(logits, self.top_k, cap)
            combine = combine.astype(xt.dtype)
            dispatch = dispatch.astype(xt.dtype)
            expert_in = jnp.einsum("tec,th->ech", dispatch, xt)  # (E, C, H)
            # the explicit all-to-all needs AXIS_EXPERT bound as manual;
            # the auto-partitioned path (manual_axes=(), e.g. inside a
            # gpipe stage) lets XLA place the exchange itself
            if ep > 1 and manual_axes:
                # exchange token slots: (E, C, H) -> (E/ep, ep*C, H); each
                # group now holds every shard's slots for ITS experts
                expert_in = jax.lax.all_to_all(
                    expert_in, AXIS_EXPERT, split_axis=0, concat_axis=1, tiled=True
                )
            out = ffn(expert_in, ws)
            if ep > 1 and manual_axes:
                out = jax.lax.all_to_all(
                    out, AXIS_EXPERT, split_axis=1, concat_axis=0, tiled=True
                )
            y = jnp.einsum("tec,ech->th", combine, out)
            reduce_axes = tuple(a for a in manual_axes if mesh.shape.get(a, 1) > 1)
            if reduce_axes:
                aux = jax.lax.pmean(aux, reduce_axes)
            return y.reshape(b, l, h), aux

        local = not self.global_dispatch
        manual: tuple = ()
        # inside a gpipe stage body (in_manual_region): a NESTED
        # shard_map's reverse AD corrupts cotangents in current JAX (see
        # mesh.manual_region and the ring_attention note) — keep
        # manual=() so the dispatch runs auto-partitioned below (global
        # capacity pool; XLA inserts the expert collectives)
        if not mesh.empty and not in_manual_region():
            if local and (ep > 1 or dp > 1 or fs > 1 or cp > 1):
                manual = (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)
                if x.shape[0] % (dp * fs * ep) or x.shape[1] % cp:
                    # local dispatch needs the batch dim split across ALL
                    # data-like axes; a batch that only divides the expert
                    # extent keeps the old expert-only manual region (global
                    # capacity pool) instead of failing deep inside shard_map
                    import warnings

                    warnings.warn(
                        f"MoeMlp: batch {x.shape[0]} not divisible by the "
                        f"data-like mesh extent {dp * fs * ep} (or seq "
                        f"{x.shape[1]} by context {cp}); falling back "
                        f"to GLOBAL dispatch (cross-shard routing cumsum, "
                        f"global capacity pool) — pad the batch for local "
                        f"dispatch",
                        stacklevel=2,
                    )
                    manual = (AXIS_EXPERT,) if ep > 1 else ()
                elif cp > 1:
                    # context-sharded tokens are just more local tokens
                    manual = manual + (AXIS_CONTEXT,)
            elif ep > 1:
                manual = (AXIS_EXPERT,)
        if not manual:
            y, aux = moe_body(x, router, ws, ())
        else:
            batch_axes = tuple(a for a in manual if a != AXIS_CONTEXT)
            batch_spec = P(
                batch_axes,
                AXIS_CONTEXT if AXIS_CONTEXT in manual else None,
                None,
            )
            ws_specs = {k: (P(AXIS_EXPERT, None, None) if v.ndim == 3
                            else P(AXIS_EXPERT, None))
                        for k, v in ws.items()}
            y, aux = jax.shard_map(
                partial(moe_body, manual_axes=manual),
                mesh=mesh,
                axis_names=set(manual),
                in_specs=(
                    batch_spec,                   # batch dim carries the manual axes
                    P(None, None),                # router replicated
                    ws_specs,
                ),
                out_specs=(batch_spec, P()),
                check_vma=False,
            )(x, router, ws)
        self.sow("losses", "moe_aux", aux,
                 reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        return y


# ---------------------------------------------------------------------------
# The held-experts layer: this chip's share of an expert-parallel layer.
#
# The layer is told which experts it holds (`experts_held`, a range of the
# router's outputs), routes every token over ALL of them, and computes its
# own experts' part of the result for the tokens routed to them. What the
# absent experts would add is left out: on one chip there is no exchange,
# and no code stands in for it. No token is ever dropped: the rows a share
# can be asked for are bounded by tokens x top_k, which is the static
# shape of every buffer of sorted rows, and the work follows the rows in
# use: R = sum of the held experts' loads, a prefix of the sorted order.
# The grouped products' grid is the row tiles in use; what else costs per
# sorted row (the dispatch gather, the SwiGLU, the combine's gradient) walks
# the chunks of ROW_CHUNK rows below R. Rows at and past R are written by no
# one and read unmasked by no one. What costs per (token, choice) pair (the
# two sums over a token's choices) covers all tokens x top_k pairs.

#: the flax collection of the router's state that is no parameter: the
#: selection bias and the counts it is moved by (`bias`, `counts`), and
#: `rows_here`, the rows in use R of the step. The Trainer carries it in
#: TrainState.extra and reads the step's counters from it
#: (`router_counters`: the rows walked are computed from `rows_here`).
ROUTER_STATE = "router_state"
#: the grouped product's (rows, contraction, columns) tile. Of the tiles timed
#: at published widths on the v5e the row tile moved nothing (128, 256, 512:
#: within 2 %); 512 x 2048 x 1024 and 1024 x 1024 x 1024 were refused for VMEM
GMM_TILING = (512, 1024, 1024)


def _gmm_kwargs(m: int, k: int, n: int) -> dict:
    return {"tiling": tuple(min(t, d) for t, d in zip(GMM_TILING, (m, k, n))),
            "interpret": jax.default_backend() == "cpu"}


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False, out_dtype=None):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, out_dtype or lhs.dtype,
               transpose_rhs=transpose_rhs, **_gmm_kwargs(*lhs.shape, n))


def _tgmm(lhs, grad, group_sizes, out_dtype):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    return tgmm(lhs.swapaxes(0, 1), grad, group_sizes, out_dtype,
                **_gmm_kwargs(*lhs.shape, grad.shape[1]))


@jax.custom_vjp
def grouped_matmul(rows, weights, group_sizes):
    """rows (M, K) sorted by group, weights (G, K, N), group_sizes (G,)
    int32 -> (M, N): rows of group g times weights[g]. The pallas grouped
    product (megablox): its grid is the row tiles IN USE, so the work is in
    proportion to sum(group_sizes), not to M. Rows past sum(group_sizes)
    are NOT written, here or in the gradient of `rows`: they hold whatever
    the buffer held (NaN, for all anyone knows), and they may hold anything
    in `rows` and in the cotangent too: the kernels select by group, so
    nothing of them reaches a written row or the gradient of `weights`.
    The caller masks them where pairs go back to tokens (`_dispatch`'s
    gradient and `_combine` select by `held`)."""
    return _gmm(rows, weights, group_sizes)


def _grouped_matmul_fwd(rows, weights, group_sizes):
    return _gmm(rows, weights, group_sizes), (rows, weights, group_sizes)


def _grouped_matmul_bwd(res, g):
    rows, weights, group_sizes = res
    d_rows = _gmm(g, weights, group_sizes, transpose_rhs=True, out_dtype=rows.dtype)
    d_weights = _tgmm(rows, g, group_sizes, weights.dtype)
    return d_rows, d_weights, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# What costs per sorted row follows the rows in use. The (token, choice) pairs
# are sorted with the held experts' first, so the rows in use are the prefix
# [0, R), R = group_sizes.sum(), of a buffer whose static shape is the bound
# tokens x top_k (rounded up to whole chunks). Every pass over such a buffer
# is a loop over its chunks of ROW_CHUNK rows with the traced trip count
# ceil(R / ROW_CHUNK): the chunk index is a dimension of its own, so the write
# of a chunk is in place (an offset along the rows that the compiler cannot
# know aligned is not: PERF.md, PR 29). Rows at and past R hold whatever the
# buffer held, NaN included, and no one may read them unmasked: the grouped
# products select by group, and the two sums over a token's choices below
# select by `held`.

#: rows a trip of the per-row loops covers. One layer at published widths on
#: the v5e, forward and backward at 12,288 rows in use: 17.18 ms at 1,024,
#: 17.09 at 2,048, 17.05 at 4,096 (my chip run, PR 31, call 1): the trips cost
#: little, and a larger chunk walks more rows past the rows in use (half a
#: chunk a pass, on average).
ROW_CHUNK = 2048
#: a gather of 65,536 rows of 2,048 (bf16) out of a source of 96 MiB and less
#: took 0.44 ms on the v5e whatever the indices, out of 128 MiB and more 2.2 to
#: 2.3 ms, the identity included; masked and summed over a token's eight
#: choices 0.97-1.07 against 2.77, and 3.4 out of a prefix of 128 to 192 MiB
#: (my chip runs, PR 31, calls 2 and 3)
SMALL_SOURCE_BYTES = 96 << 20


def _unwritten(shape, dtype, after):
    """A buffer no one has written (the loops below write the chunks in use),
    allocated once `after` (any array) is computed and no earlier: a pallas
    call that does nothing. `jax.lax.empty` has no operand, and the TPU's
    scheduler then moves every layer's allocations to the start of the step,
    where all are live at once (a step of `trinitym-train-8k` compiled to
    11.1 GB of temporaries against 2.9: PERF.md, PR 31)."""
    from jax.experimental import pallas as pl

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda after_ref, out_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[anywhere], out_specs=anywhere, name="unwritten",
        interpret=jax.default_backend() == "cpu")(after)


def _live_chunks(n_rows):
    return (n_rows + ROW_CHUNK - 1) // ROW_CHUNK


def _per_live_chunk(fn, n_rows, *operands, over: int = 0):
    """fn row by row over the rows in use: `operands` are (M, ...) buffers of
    sorted rows, fn takes a chunk of each, (ROW_CHUNK, ...), and returns a
    tuple of chunks; the results are (M, ...) buffers written for the chunks
    below `n_rows` (traced) and unwritten past that. The first `over` results
    are written over the first `over` operands, chunk by chunk in place (same
    shape and type; the operand is dead then)."""
    by_chunk = tuple(a.reshape(-1, ROW_CHUNK, *a.shape[1:]) for a in operands)
    shapes = jax.eval_shape(fn, *(a[0] for a in by_chunk))
    fresh = tuple(_unwritten((by_chunk[0].shape[0], *s.shape), s.dtype, operands[0])
                  for s in shapes[over:])
    # where each result goes in the loop's carry (operands, then fresh buffers)
    slots = (*range(over), *range(len(operands), len(operands) + len(fresh)))

    def body(i, bufs):
        bufs = list(bufs)
        for slot, chunk in zip(slots, fn(*(a[i] for a in bufs[:len(operands)]))):
            bufs[slot] = bufs[slot].at[i].set(chunk)
        return tuple(bufs)

    bufs = jax.lax.fori_loop(0, _live_chunks(n_rows), body, by_chunk + fresh)
    return tuple(bufs[slot].reshape(-1, *bufs[slot].shape[2:]) for slot in slots)


def _sum_over_choices(rows, inverse, held, scale, n_rows):
    """rows (M, H) in sorted order -> (T, H) float32: token t's sum over its
    choices k of scale[t, k] * rows[inverse[t, k]], the held pairs' only
    (`inverse`, `held`, `scale` are (T, K); a pair whose expert is absent
    reads a row no one wrote, and is masked). It costs per (token, choice)
    pair, all T x K of them, but what a pair costs follows the STATIC size of
    the gather's source (SMALL_SOURCE_BYTES), and the rows in use are the
    prefix below `n_rows`: where they fit a source of that size, a branch on
    `n_rows` gathers from the prefix alone. The gather is choice-major, (K, T,
    H): the sum adds K dense (T, H) slabs whatever K is, where (T, K, H) puts
    the choices on the tiled second-minor dimension and, at a K that does not
    fill the tile (6), costs a relayout of every gathered row (PERF.md, PR 35)."""
    inverse, held = inverse.T, held.T
    scale = None if scale is None else jnp.where(held, scale.T, 0.0)

    def over(src):
        picked = jnp.where(held[..., None], src[jnp.minimum(inverse, src.shape[0] - 1)],
                           jnp.zeros((), src.dtype)).astype(jnp.float32)
        return (picked if scale is None else picked * scale[..., None]).sum(0)

    cap = SMALL_SOURCE_BYTES // (rows.shape[1] * rows.dtype.itemsize)
    if not 0 < cap < rows.shape[0]:
        return over(rows)
    return jax.lax.cond(n_rows <= cap, lambda: over(rows[:cap]), lambda: over(rows))


@jax.custom_vjp
def _dispatch(xt, order, inverse, held, n_rows):
    """xt (T, H) -> (M, H), M = T x K rounded up to whole chunks: row i <
    n_rows is the token of the i-th (token, choice) pair in sorted order,
    `order[i] // K`. Only the chunks below `n_rows` (traced, the rows in use)
    are gathered; the rest is unwritten. `order` (M,) is the sorted order of
    the pairs, padded; `inverse` (T, K) is its inverse permutation; `held`
    (T, K) says whether a pair's expert is held here. The gradient brings the
    pairs' cotangents back to token order, the absent experts' masked (their
    rows are unwritten), and sums over a token's choices."""
    return _dispatch_fwd(xt, order, inverse, held, n_rows)[0]


def _dispatch_fwd(xt, order, inverse, held, n_rows):
    rows, = _per_live_chunk(lambda tok: (xt[tok],), n_rows, order // held.shape[1])
    return rows, (inverse, held, n_rows)


def _dispatch_bwd(res, g):
    inverse, held, n_rows = res
    return _sum_over_choices(g, inverse, held, None, n_rows).astype(g.dtype), None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _swiglu(gate_up, n_rows):
    """gate_up (M, 2m), the gate's and the up projection's products side by
    side -> silu(gate) * up, (M, m), over the chunks below `n_rows`; computed
    in float32 and rounded once. The gradient covers the same chunks."""
    return _swiglu_fwd(gate_up, n_rows)[0]


def _swiglu_fwd(gate_up, n_rows):
    def act(gu):
        gate, up = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
        return ((gate * jax.nn.sigmoid(gate) * up).astype(gu.dtype),)

    hidden, = _per_live_chunk(act, n_rows, gate_up)
    return hidden, (gate_up, n_rows)


def _swiglu_bwd(res, g):
    gate_up, n_rows = res

    def d_act(gu, g):
        gate, up = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
        g, s = g.astype(jnp.float32), jax.nn.sigmoid(gate)
        d_gate = g * up * s * (1.0 + gate * (1.0 - s))
        return (jnp.concatenate([d_gate, g * gate * s], axis=-1).astype(gu.dtype),)

    d_gate_up, = _per_live_chunk(d_act, n_rows, gate_up, g, over=1)
    return d_gate_up, None


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@jax.custom_vjp
def _combine(y, weights, order, inverse, held, n_rows):
    """y (M, H) in sorted order, weights (T, K) float32 -> (T, H) float32:
    token t's sum over its choices k of weights[t, k] * y[inverse[t, k]], the
    held experts' only (`order`, `inverse`, `held` as `_dispatch` takes them).
    The gradient is written in row space, over the chunks below `n_rows`:
    d_y[r] = w[r] * d_out[token(r)], a gather from the T rows of d_out,
    written over y, and d_w[r] = <y[r], d_out[token(r)]> in float32; no
    (T, K, H) cotangent exists. The weights reach row order and d_w goes back
    to (T, K) as values of a sort keyed on the permutation (`inverse`,
    `order`): a gather of T x K single scalars costs as much as the gather of
    as many whole rows (8-10 ns an element: PERF.md, PR 35)."""
    return _combine_fwd(y, weights, order, inverse, held, n_rows)[0]


def _combine_fwd(y, weights, order, inverse, held, n_rows):
    return (_sum_over_choices(y, inverse, held, weights, n_rows),
            (y, weights, order, inverse, held, n_rows))


def _combine_bwd(res, g):
    y, weights, order, inverse, held, n_rows = res
    pairs = held.size
    _, by_row = jax.lax.sort((inverse.reshape(-1), weights.reshape(-1)), num_keys=1)

    def d_rows(y, pair, w):
        d = g[pair // held.shape[1]]
        return ((w[:, None] * d).astype(y.dtype), (y.astype(jnp.float32) * d).sum(-1))

    d_y, d_w = _per_live_chunk(d_rows, n_rows, y, order,
                               jnp.pad(by_row, (0, order.size - pairs)), over=1)
    # rows at and past n_rows hold anything, NaN included: the sort carries
    # them as values it never compares, and `held` masks them
    _, d_w = jax.lax.sort((order[:pairs], d_w[:pairs]), num_keys=1)
    return d_y, jnp.where(held, d_w.reshape(held.shape), 0.0), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _route_top_k(score, x, kernel, bias, top_k: int, scale: float,
                 renormalise: bool = True):
    """x (T, H), kernel (H, E), bias (E,) -> (idx (T, K) int32, weights
    (T, K) f32, scores (T, E) f32). Scores are `score` of the router's
    product in float32; the bias takes part in the choice of the K experts
    and not in their weights, which are the chosen scores scaled: normalised
    over the K first (`renormalise`), or as the scores stand (a softmax's K
    largest then sum to less than 1: `norm_topk_prob` false)."""
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = score(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    # the chosen scores by a select over the expert axis, exact (one term of
    # the sum is not zero) and not `take_along_axis`: its gather of T x K
    # single scalars, forward, and the scatter-add back cost what gathering
    # as many whole rows does. No one-hot product: the MXU would round to bf16.
    # Behind a barrier, or XLA folds the normalisation's sum over the K into
    # this one and adds the chosen scores in the experts' order, not the choices'
    picked = jax.lax.optimization_barrier(jnp.where(
        idx[..., None] == jnp.arange(scores.shape[-1]), scores[:, None, :], 0.0).sum(-1))
    if not renormalise:
        return idx, scale * picked, scores
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return idx, weights, scores


def route_sigmoid(x, kernel, bias, top_k: int, scale: float, renormalise: bool = True):
    """The sigmoid router: each expert's score is a sigmoid of its own logit
    (`_route_top_k`)."""
    return _route_top_k(jax.nn.sigmoid, x, kernel, bias, top_k, scale, renormalise)


def route_softmax(x, kernel, bias, top_k: int, scale: float, renormalise: bool = True):
    """The softmax router: scores are the softmax over ALL the experts, then
    the K largest, normalised over the K (`norm_topk_prob`) or left as they
    are (`_route_top_k`)."""
    return _route_top_k(jax.nn.softmax, x, kernel, bias, top_k, scale, renormalise)


#: `HeldExpertsMlp.score_func` -> the router: (x, kernel, bias, top_k, scale,
#: renormalise) -> (idx, weights, scores)
ROUTERS = {"sigmoid": route_sigmoid, "softmax": route_softmax}


def sequence_balance_loss(scores, idx, num_experts: int):
    """The sequence-wise balance loss of a softmax router before its
    coefficient (DeepSeek-V2, arXiv:2405.04434, `seq_aux`): scores (B, L, E)
    float32, idx (B, L, K) the chosen experts -> the mean over rows of
    `sum_e f_e P_e`, where `f_e = count_e E / (K L)` is how often the row's
    K L choices fell on expert e against the balanced share (no gradient) and
    `P_e` the row's mean score of e. 1 at a uniform router, E / K where every
    token takes the same K with certainty."""
    b, length, k = idx.shape
    count = (idx.reshape(b, length * k, 1) == jnp.arange(num_experts)).sum(1)
    f = count.astype(jnp.float32) * (num_experts / (k * length))
    return (f * scores.mean(1)).sum(-1).mean()


def share_centred_normal(share: int):
    """A router's initialiser (`HeldExpertsMlp.router_init`): normal draws at
    the layer's own 0.02, and in each run of `share` columns (the experts one
    chip holds) the columns sum to zero. Whatever direction the positions'
    representations have in common then favours no chip's experts over
    another's, to first order: a share's load is the balanced one and steady
    from seed to seed, as under a router trained to balance."""
    draw = nn.initializers.normal(stddev=0.02)

    def init(key, shape, dtype=jnp.float32):
        w = draw(key, shape, dtype).reshape(shape[0], -1, share)
        return (w - w.mean(-1, keepdims=True)).reshape(shape)

    return init


def router_counters(router_state) -> dict[str, jax.Array]:
    """The step's routing counters out of the ROUTER_STATE collection (every
    expert layer's `counts`, `rows_here` and `bias`), for the step's metrics:
    `moe_rows_here` (rows the held experts computed, summed over layers),
    `moe_rows_walked` (rows the layers' per-row loops covered: each layer's
    rows in use rounded up to whole chunks of ROW_CHUNK),
    `moe_load_max_over_mean` (the fullest of all the router's experts over
    the mean, worst layer), `moe_bias_abs_max`, and, where a layer sows a
    balance loss, `moe_balance_loss` (its value, summed over the layers)."""
    from flax.traverse_util import flatten_dict

    flat = flatten_dict(router_state)
    of = lambda name: [v for path, v in flat.items() if path[-1] == name]  # noqa: E731
    counters = {
        "moe_rows_here": sum(r.astype(jnp.float32) for r in of("rows_here")),
        "moe_rows_walked": sum((_live_chunks(r) * ROW_CHUNK).astype(jnp.float32)
                               for r in of("rows_here")),
        "moe_load_max_over_mean": jnp.stack(
            [c.max() / jnp.maximum(c.mean(), 1e-9) for c in of("counts")]).max(),
        "moe_bias_abs_max": jnp.stack([jnp.abs(b).max() for b in of("bias")]).max(),
    }
    if of("balance_loss"):  # the layers that sow a balance loss keep its value
        counters["moe_balance_loss"] = sum(of("balance_loss"))
    return counters


class HeldExpertsMlp(nn.Module):
    """Routed SwiGLU experts, beside `num_shared_experts` shared ones where
    the model has any; this share holds the routed experts `experts_held` =
    [lo, hi) of the router's `num_experts`. x (B, L, H) -> (B, L, H):

        shared(x) + sum over e in S(x), lo <= e < hi, of w_e(x) expert_e(x)

    S and w come from the router `score_func` names (`ROUTERS`: sigmoids, or
    a softmax over all `num_experts`), the K chosen scores normalised over the K
    where `renormalise` (else the weights are the scores themselves). Where
    `balance_loss` is not 0 the layer sows that coefficient times
    `sequence_balance_loss` into the `losses` collection, which the Trainer adds
    to the objective, and keeps its value in ROUTER_STATE (`balance_loss`) for the
    step's counters; at 0 nothing is sown and no such variable exists.
    Tokens x top_k (token, choice) pairs are sorted by expert, the pairs of
    absent experts last; the held experts' rows are a prefix of the sorted
    order and the grouped products run over that prefix only. With
    `train=True` and a mutable ROUTER_STATE the step's load is counted
    (`router_counters`) and, where `bias_update_rate` is not 0, the selection
    bias moves by it against the load (no gradient, no optimizer state).
    Without shared experts there is no `moe.shared` scope and no `shared_*`
    parameter; at rate 0 the bias stays the zeros it was made as.
    `router_init` draws the router's (H, num_experts) weights."""

    hidden_size: int
    expert_dim: int
    num_experts: int
    top_k: int
    experts_held: tuple[int, int] | None = None   # None: all of them
    num_shared_experts: int = 1
    route_scale: float = 1.0
    bias_update_rate: float = 0.001
    dtype: Any = jnp.float32
    score_func: str = "sigmoid"                   # a key of ROUTERS
    router_init: Callable = nn.initializers.normal(stddev=0.02)
    renormalise: bool = True                      # the K chosen weights sum to route_scale
    balance_loss: float = 0.0                     # coefficient of the sown loss

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        h, m, e, k = self.hidden_size, self.expert_dim, self.num_experts, self.top_k
        lo, hi = self.experts_held or (0, e)
        if not 0 <= lo < hi <= e:
            raise ValueError(f"experts_held {self.experts_held} is no range of {e} experts")
        held = hi - lo
        router = self.param("router", self.router_init, (h, e), jnp.float32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("w_gate", init, (held, h, m))
        w_up = self.param("w_up", init, (held, h, m))
        w_down = self.param("w_down", init, (held, m, h))
        zeros_e = lambda: jnp.zeros((e,), jnp.float32)  # noqa: E731
        bias = self.variable(ROUTER_STATE, "bias", zeros_e)
        counts = self.variable(ROUTER_STATE, "counts", zeros_e)
        rows_here = self.variable(ROUTER_STATE, "rows_here",
                                  lambda: jnp.zeros((), jnp.int32))
        balance = self.variable(ROUTER_STATE, "balance_loss", lambda: jnp.zeros(
            (), jnp.float32)) if self.balance_loss else None

        b, l, _ = x.shape
        xt = x.reshape(b * l, h)
        with jax.named_scope("moe.route"):
            idx, weights, scores = ROUTERS[self.score_func](
                xt, router, bias.value, k, self.route_scale, self.renormalise)
            flat = idx.reshape(-1)                               # (T*K,)
            load = (flat[:, None] == jnp.arange(e)).sum(0, dtype=jnp.int32)
            if self.balance_loss:
                aux = self.balance_loss * sequence_balance_loss(
                    scores.reshape(b, l, e), idx.reshape(b, l, k), e)
                self.sow("losses", "moe_balance", aux,
                         reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        with jax.named_scope("moe.dispatch"):
            # sort the (token, choice) pairs by local expert, the absent
            # experts' last: the held experts' rows are a prefix of the order
            held_pair = (flat >= lo) & (flat < hi)
            local = jnp.where(held_pair, flat - lo, held)
            pairs = jnp.arange(b * l * k, dtype=jnp.int32)
            _, order = jax.lax.sort((local, pairs), num_keys=1, is_stable=True)
            _, inverse = jax.lax.sort((order, pairs), num_keys=1)
            group_sizes = load[lo:hi]
            n_rows = group_sizes.sum()
            # whole chunks: the pairs past tokens x top_k are never in use
            order = jnp.pad(order, (0, -order.size % ROW_CHUNK))
            inverse, held_pair = inverse.reshape(b * l, k), held_pair.reshape(b * l, k)
            rows = _dispatch(xt.astype(self.dtype), order, inverse, held_pair, n_rows)
        with jax.named_scope("moe.experts"):
            cast = lambda w: w.astype(self.dtype)  # noqa: E731
            # gate and up side by side: one product of `rows`, one gradient
            gate_up = grouped_matmul(
                rows, jnp.concatenate([cast(w_gate), cast(w_up)], axis=-1), group_sizes)
            y = grouped_matmul(_swiglu(gate_up, n_rows), cast(w_down), group_sizes)
        with jax.named_scope("moe.combine"):
            out = _combine(y, weights, order, inverse, held_pair, n_rows)
        shared = None
        if self.num_shared_experts:
            with jax.named_scope("moe.shared"):
                width = self.num_shared_experts * m
                dense = lambda n, name: nn.Dense(  # noqa: E731
                    n, use_bias=False, dtype=self.dtype, name=name)
                shared = dense(h, "shared_down")(
                    nn.silu(dense(width, "shared_gate")(xt)) * dense(width, "shared_up")(xt))

        if train and self.is_mutable_collection(ROUTER_STATE) and not self.is_initializing():
            load = load.astype(jnp.float32)
            counts.value = load
            rows_here.value = n_rows
            if balance is not None:
                balance.value = jax.lax.stop_gradient(aux)
            if self.bias_update_rate:
                bias.value = bias.value + self.bias_update_rate * jnp.sign(load.mean() - load)
        if shared is not None:
            out = out + shared.astype(jnp.float32)
        return out.astype(self.dtype).reshape(b, l, h)


HELD_EXPERTS_PARTITION_RULES: list[tuple[str, P]] = [
    *MOE_PARTITION_RULES,
    (r"moe/shared_(gate|up)/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"moe/shared_down/kernel$", P(AXIS_MODEL, AXIS_FSDP)),
]
