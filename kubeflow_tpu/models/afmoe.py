"""AFMoE — a decoder whose layers differ (`model_type: afmoe`, Arcee's
Trinity family), on the training path.

What one model mixes, layer by layer:

  - the kind of attention (`layer_types`): `sliding_attention` layers see a
    window of keys and rotate queries and keys (rotary positions);
    `full_attention` layers see every earlier key and rotate nothing;
  - the kind of MLP: the first `num_dense_layers` blocks carry a dense
    SwiGLU, the rest a `HeldExpertsMlp` (sigmoid router over all
    `num_experts`, this share's `experts_held`, a shared expert);
  - a head size that is its own (`head_dim`, not hidden / heads), grouped
    keys and values, RMSNorm on each head's queries and keys, a sigmoid
    gate on the attention output, and a norm after each sublayer as well
    as before it. The embedding is scaled by sqrt(hidden) (`mup_enabled`).

Module names follow the step's trace readers: blocks are `layer_N`, the
attention core lives under `attention` beside its projections `query`,
`key`, `value`, `attn_out`; the gate's projection is a dense product and is
the block's `attn_gate`; the expert layer is `layer_N/moe`.

Training and evaluation only: there is no decode cache here yet.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.models.gpt import (
    ACT_SPEC,
    PARTITION_RULES as GPT_PARTITION_RULES,
    VocabEmbed,
    causal_dense_attention,
    constrain,
)
from kubeflow_tpu.parallel.mesh import AXIS_FSDP, AXIS_MODEL
from kubeflow_tpu.parallel.moe import (HELD_EXPERTS_PARTITION_RULES,
                                       ROUTER_STATE, HeldExpertsMlp,
                                       router_counters)
from kubeflow_tpu.parallel.ring_attention import (FLASH_REMAT_POLICY,
                                                  flash_attention)
from kubeflow_tpu.parallel.rope import apply_rope

SLIDING, FULL = "sliding_attention", "full_attention"

PARTITION_RULES: list[tuple[str, P]] = [
    *GPT_PARTITION_RULES,
    *HELD_EXPERTS_PARTITION_RULES,
    (r"attn_gate/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
]


@dataclass(frozen=True)
class AfmoeConfig:
    """Keys as the published `config.json` names them, where the program's
    other models have no name of their own for the same thing."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    mlp_dim: int = 6144                   # the leading dense layers' width
    num_dense_layers: int = 2
    # one kind a layer; () is the published pattern, a full layer every
    # `global_attn_every_n_layers`-th
    layer_types: tuple[str, ...] = ()
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    num_experts: int = 128                # the router's width
    # the routed experts this share holds, [lo, hi); None is all of them
    experts_held: tuple[int, int] | None = None
    top_k: int = 8
    expert_dim: int = 1024
    num_shared_experts: int = 1
    route_scale: float = 2.826
    bias_update_rate: float = 0.001       # `load_balance_coeff`
    scale_embedding: bool = True          # `mup_enabled`
    dtype: Any = jnp.float32
    attention: str = "dense"              # dense | flash
    # recompute each block in the backward pass, but for the flash kernel's
    # output and row statistic (ring_attention.FLASH_REMAT_POLICY keeps them)
    remat: bool = False

    def __post_init__(self):
        kinds = self.layer_types or tuple(
            FULL if (i + 1) % self.global_attn_every_n_layers == 0 else SLIDING
            for i in range(self.num_layers))
        object.__setattr__(self, "layer_types", tuple(kinds))
        if len(kinds) != self.num_layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers as "
                f"{SLIDING}|{FULL} (got {kinds})")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"num_kv_heads {self.num_kv_heads} must divide num_heads "
                f"{self.num_heads}, and head_dim {self.head_dim} be even")
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention {self.attention!r} is not dense|flash")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of {self.num_layers}")

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        """Test-sized: both layer kinds, a dense and three expert layers."""
        d = dict(vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
                 num_kv_heads=2, head_dim=32, mlp_dim=128, num_dense_layers=1,
                 sliding_window=16, num_experts=8, top_k=2, expert_dim=32)
        d.update(kw)
        return AfmoeConfig(**d)


def _norm(c: AfmoeConfig, name: str):
    return nn.RMSNorm(epsilon=c.norm_eps, dtype=c.dtype, name=name)


class AfmoeAttention(nn.Module):
    """The attention of one layer kind; `gate` is the block's `attn_gate`
    projection of the same input, (B, L, H*d)."""

    cfg: AfmoeConfig
    kind: str

    @nn.compact
    def __call__(self, x, gate):
        c = self.cfg
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, c.head_dim), use_bias=False, dtype=c.dtype, name=name)
        q = heads(c.num_heads, "query")(x)
        k = heads(c.num_kv_heads, "key")(x)
        v = heads(c.num_kv_heads, "value")(x)
        # one gain vector each, over head_dim, shared by the heads
        q, k = _norm(c, "q_norm")(q), _norm(c, "k_norm")(k)
        window = 0
        if self.kind == SLIDING:
            pos = jnp.arange(x.shape[1])
            q, k = apply_rope(q, pos, c.rope_theta), apply_rope(k, pos, c.rope_theta)
            window = c.sliding_window
        # query head j reads key/value head j // group (as GPTLM trains GQA:
        # the kernels stay single-shape)
        group = c.num_heads // c.num_kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if c.attention == "flash":
            bias = jnp.zeros((x.shape[0], 1, 1, x.shape[1]), c.dtype)
            y = flash_attention(q, k, v, bias, causal=True, window=window)
        else:
            y = causal_dense_attention(q, k, v, None, window=window)
        y = y * jax.nn.sigmoid(gate.reshape(y.shape).astype(jnp.float32)).astype(y.dtype)
        return nn.DenseGeneral(c.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=c.dtype, name="attn_out")(y)


class AfmoeBlock(nn.Module):
    """x + n(attention(n(x))), then x + n(mlp(n(x))): a norm before and
    after each sublayer."""

    cfg: AfmoeConfig
    kind: str
    dense_mlp: bool

    @nn.compact
    def __call__(self, x, train: bool):
        c = self.cfg
        a = _norm(c, "ln_attn")(x)
        gate = nn.Dense(c.num_heads * c.head_dim, use_bias=False, dtype=c.dtype,
                        name="attn_gate")(a)
        y = AfmoeAttention(c, self.kind, name="attention")(a, gate)
        x = constrain(x + _norm(c, "ln_attn_post")(y), ACT_SPEC)
        b = _norm(c, "ln_mlp")(x)
        if self.dense_mlp:
            dense = lambda n, name: nn.Dense(  # noqa: E731
                n, use_bias=False, dtype=c.dtype, name=name)
            f = dense(c.hidden_size, "mlp_down")(
                nn.silu(dense(c.mlp_dim, "mlp_gate")(b)) * dense(c.mlp_dim, "mlp_up")(b))
        else:
            f = HeldExpertsMlp(
                hidden_size=c.hidden_size, expert_dim=c.expert_dim,
                num_experts=c.num_experts, top_k=c.top_k,
                experts_held=c.experts_held,
                num_shared_experts=c.num_shared_experts,
                route_scale=c.route_scale,
                bias_update_rate=c.bias_update_rate, dtype=c.dtype, name="moe",
            )(b, train)
        return constrain(x + _norm(c, "ln_mlp_post")(f), ACT_SPEC)


class AfmoeLM(nn.Module):
    """Causal language model: __call__(input_ids (B, L)) -> (B, L, vocab)
    float32 logits. No padding mask: position i sees every j <= i (and, in
    a sliding layer, i - j < sliding_window); padded labels (id 0) are
    masked by `causal_lm_loss` as for GPTLM."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        c = self.cfg
        x = VocabEmbed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       name="token_embed")(input_ids)
        if c.scale_embedding:
            x = x * jnp.asarray(c.hidden_size ** 0.5, x.dtype)
        x = constrain(x, ACT_SPEC)
        block_cls = nn.remat(AfmoeBlock, static_argnums=(2,),
                             policy=FLASH_REMAT_POLICY) if c.remat else AfmoeBlock
        for i, kind in enumerate(c.layer_types):
            x = block_cls(c, kind, i < c.num_dense_layers, name=f"layer_{i}")(x, train)
        x = _norm(c, "ln_final")(x)
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)

    @staticmethod
    def step_counters(extra, y=None) -> dict:
        """What the Trainer adds to a step's metrics (train/trainer.py
        `_step_metrics`): the routers' counters, out of the collection the
        expert layers keep in `TrainState.extra`; nothing for a model whose
        layers are all dense. The labels `y` count nothing here."""
        return router_counters(extra[ROUTER_STATE]) if ROUTER_STATE in extra else {}


AfmoeLM.PARTITION_RULES = PARTITION_RULES
AfmoeLM.PREFERRED_COMPUTE_DTYPE = jnp.bfloat16
