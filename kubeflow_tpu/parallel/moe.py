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
from typing import Any

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
