"""Granite 4.0-H — a decoder of Mamba-2 layers with a few attention layers
among them (`model_type: granitemoehybrid`, its dense form: no experts), on
the training path.

What sets it apart from the program's other decoders:

  - the kind of mixer follows `layer_types`: a `mamba` layer carries the
    Mamba-2 mixer of `parallel/ssm.py` (chunked scan, causal depthwise
    convolution, gated norm), an `attention` layer grouped-query attention
    with no positions at all (`position_embedding_type: nope`) and a softmax
    scale of its own (`attention_multiplier`);
  - four multipliers of the μP kind: the embedding times
    `embedding_multiplier`, each sublayer's output times
    `residual_multiplier` before its residual add, the scores times
    `attention_multiplier`, the logits divided by `logits_scaling`;
  - the head is the embedding, transposed (tied).

Every block is `x + m (mixer(n(x)))`, then `x + m (mlp(n(x)))`, with RMSNorm
before each sublayer and a SwiGLU MLP (`mlp_gate`, `mlp_up`, `mlp_down`: the
family's one `input_linear` split gate first, `output_linear`).

Module names follow the step's trace readers: blocks are `layer_N`; the
mixer of a Mamba-2 layer is `layer_N/mamba`; the attention core lives under
`layer_N/attention` beside its projections `query`, `key`, `value` and
`attn_out`, as in the other decoders.

Initialisation. Weights drawn from a seed stand in for a checkpoint (tests,
the benchmark). The mixer is drawn as Mamba-2 is but for its steps, a
decade slower so that a state outlives its chunk (`Mamba2Mixer`); the
embedding's entries at 1 / `embedding_multiplier`, so that the residual
stream enters the first layer at unit scale, as the multiplier intends; the
rest as flax draws it.

Training and evaluation only: no decode cache (the state and the
convolution's window beside keys and values are ROADMAP M4).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.models.gpt import (
    ACT_SPEC,
    PARTITION_RULES as GPT_PARTITION_RULES,
    VocabEmbed,
    causal_dense_attention,
    constrain,
)
from kubeflow_tpu.parallel.ring_attention import (FLASH_REMAT_POLICY,
                                                  flash_attention)
from kubeflow_tpu.parallel.ssm import (SSM_PARTITION_RULES, SSM_STATE,
                                       Mamba2Mixer, ssm_counters)

MAMBA, ATTENTION = "mamba", "attention"

PARTITION_RULES: list[tuple[str, P]] = [*GPT_PARTITION_RULES, *SSM_PARTITION_RULES]


@dataclass(frozen=True)
class GraniteHybridConfig:
    """Keys as the published `config.json` names them, where the program's
    other models have no name of their own for the same thing."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    # one kind a layer; () is the published period of ten, five Mamba-2
    # layers, an attention layer, four more
    layer_types: tuple[str, ...] = ()
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 8192                   # `shared_intermediate_size`
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128                # `mamba_d_state`
    mamba_groups: int = 1
    mamba_conv: int = 4                   # `mamba_d_conv`
    mamba_chunk: int = 256                # `mamba_chunk_size`
    norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    dtype: Any = jnp.float32
    attention: str = "dense"              # dense | flash
    # recompute each block in the backward pass, but for the flash kernel's
    # output and row statistic (ring_attention.FLASH_REMAT_POLICY keeps them)
    remat: bool = False

    def __post_init__(self):
        kinds = self.layer_types or tuple(
            ATTENTION if i % 10 == 5 else MAMBA for i in range(self.num_layers))
        object.__setattr__(self, "layer_types", tuple(kinds))
        if len(kinds) != self.num_layers or set(kinds) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types must name {self.num_layers} layers as "
                             f"{MAMBA}|{ATTENTION} (got {kinds})")
        if self.hidden_size % self.num_heads or self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} heads over {self.num_kv_heads} key/value "
                             f"heads do not tile hidden {self.hidden_size}")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError(f"{self.mamba_groups} groups do not divide {self.mamba_heads} heads")
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention {self.attention!r} is not dense|flash")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """Test-sized: both layer kinds, chunks of 8 so a row of 32 walks
        four of them."""
        d = dict(vocab_size=512, hidden_size=64, num_layers=3,
                 layer_types=(MAMBA, ATTENTION, MAMBA), num_heads=4, num_kv_heads=2,
                 mlp_dim=128, mamba_heads=4, mamba_head_dim=32, mamba_state=16,
                 mamba_chunk=8)
        d.update(kw)
        return GraniteHybridConfig(**d)


def _norm(c: GraniteHybridConfig, name: str):
    return nn.RMSNorm(epsilon=c.norm_eps, dtype=c.dtype, name=name)


class GraniteAttention(nn.Module):
    """Grouped-query attention without positions: `num_heads` query heads,
    each `num_heads / num_kv_heads` of them reading one key/value head, at
    the scale `attention_multiplier`."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, c.head_dim), use_bias=False, dtype=c.dtype, name=name)
        q, k, v = heads(c.num_heads, "query")(x), heads(c.num_kv_heads, "key")(x), \
            heads(c.num_kv_heads, "value")(x)
        group = c.num_heads // c.num_kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if c.attention == "flash":
            bias = jnp.zeros((x.shape[0], 1, 1, x.shape[1]), c.dtype)
            y = flash_attention(q, k, v, bias, causal=True, scale=c.attention_multiplier)
        else:  # the square, for sizes a test runs; it divides the scores by sqrt(d)
            y = causal_dense_attention(q * jnp.asarray(c.attention_multiplier * c.head_dim ** 0.5,
                                                       q.dtype), k, v, None)
        return nn.DenseGeneral(c.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=c.dtype, name="attn_out")(y)


class GraniteHybridBlock(nn.Module):
    """x + m mixer(n(x)), then x + m mlp(n(x))."""

    cfg: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, train: bool):
        c = self.cfg
        a = _norm(c, "ln_mixer")(x)
        if self.kind == MAMBA:
            y = Mamba2Mixer(
                hidden_size=c.hidden_size, num_heads=c.mamba_heads,
                head_dim=c.mamba_head_dim, state_size=c.mamba_state,
                n_groups=c.mamba_groups, conv_kernel=c.mamba_conv,
                chunk_size=c.mamba_chunk, norm_eps=c.norm_eps, dtype=c.dtype,
                name="mamba")(a, train)
        else:
            y = GraniteAttention(c, name="attention")(a)
        # the multiplier in float32: bf16 holds 0.22 as 0.2197, every sublayer 0.12 % short
        scaled = lambda v: (v.astype(jnp.float32) * c.residual_multiplier).astype(x.dtype)  # noqa: E731
        x = constrain(x + scaled(y), ACT_SPEC)
        b = _norm(c, "ln_mlp")(x)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=c.dtype, name=name)  # noqa: E731
        f = dense(c.hidden_size, "mlp_down")(
            nn.silu(dense(c.mlp_dim, "mlp_gate")(b)) * dense(c.mlp_dim, "mlp_up")(b))
        return constrain(x + scaled(f), ACT_SPEC)


class GraniteHybridLM(nn.Module):
    """Causal language model: __call__(input_ids (B, L)) -> (B, L, vocab)
    float32 logits. No padding mask: position i sees every j <= i, and a
    Mamba-2 layer's state runs through the whole row; padded labels (id 0)
    are masked by `causal_lm_loss` as for GPTLM."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        c = self.cfg
        embed = VocabEmbed(c.vocab_size, c.hidden_size, dtype=c.dtype, name="token_embed",
                           embedding_init=nn.initializers.normal(
                               stddev=1.0 / c.embedding_multiplier))
        x = embed(input_ids) * jnp.asarray(c.embedding_multiplier, c.dtype)
        x = constrain(x, ACT_SPEC)
        block_cls = nn.remat(GraniteHybridBlock, static_argnums=(2,),
                             policy=FLASH_REMAT_POLICY) if c.remat else GraniteHybridBlock
        for i, kind in enumerate(c.layer_types):
            x = block_cls(c, kind, name=f"layer_{i}")(x, train)
        x = _norm(c, "ln_final")(x)
        logits = embed.attend(x) / jnp.asarray(c.logits_scaling, x.dtype)
        return logits.astype(jnp.float32)

    @staticmethod
    def step_counters(extra, y=None) -> dict:
        """What the Trainer adds to a step's metrics: `ssm_chunk_decay`, out
        of the collection the mixers keep in `TrainState.extra`; nothing for
        a model without a Mamba-2 layer."""
        return ssm_counters(extra[SSM_STATE]) if SSM_STATE in extra else {}


GraniteHybridLM.PARTITION_RULES = PARTITION_RULES
GraniteHybridLM.PREFERRED_COMPUTE_DTYPE = jnp.bfloat16
