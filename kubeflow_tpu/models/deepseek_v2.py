"""DeepSeek-V2 — a decoder with latent attention (MLA) and a softmax-routed
expert layer beside shared experts (`model_type: deepseek_v2`; DeepSeek-V2,
arXiv:2405.04434), on the training path.

What sets the block apart from the program's other decoders:

  - keys and values come from a *latent*: `kv_latent` projects the block's
    input down to `kv_lora_rank` numbers a position (and, beside them, ONE
    rotary key of `qk_rope_head_dim`, shared by all heads), norms the latent,
    and projects it up to every head's `qk_nope_head_dim` key without
    position and `v_head_dim` value. In training the latent is expanded: the
    attention core sees ordinary per-head keys and values;
  - a query and a key are `qk_nope_head_dim + qk_rope_head_dim` wide (a part
    without position, then a rotary part), a value and the output
    `v_head_dim`: `flash_attention` takes the two sizes as they are, nothing
    is padded;
  - only the rotary part is rotated, by YaRN's blended frequencies
    (`parallel/rope.py yarn_frequencies`), and the softmax scale carries
    YaRN's `mscale` squared: `(nope + rope)^-0.5 * yarn_mscale^2`;
  - no query compression here (`q_lora_rank: null`, the Lite model's);
  - the first `num_dense_layers` blocks carry a dense SwiGLU, the rest a
    `HeldExpertsMlp`: softmax over all `num_experts`, the `top_k` largest
    taken as they are (`norm_topk_prob` false: the weights are the softmax's
    own values and sum to less than 1), `num_shared_experts` shared experts
    as one SwiGLU of that many widths, and the sequence-wise balance loss
    (`seq_aux`, coefficient `balance_loss`) sown into `losses`, which the
    Trainer adds to the cross entropy.

Module names follow the step's trace readers: blocks are `layer_N`; the
attention core lives under `attention` beside its projections `query` and
`attn_out`; the latent path is the block's `kv_latent` (`down`, `norm`, `up`,
under the device scopes `mla.kv_down`, `mla.kv_norm`, `mla.kv_up`), beside
`attention` and not under it, so its products count with the block's dense
work and not with the core, and a cache of latents has a module to hang on;
the rotation, the shared key's broadcast and the two concatenations are the
core's (scope `mla.rope`); the expert layer is `layer_N/moe`.

Initialisation. Weights drawn from a seed stand in for a checkpoint (tests,
the benchmark), and a plainly drawn softmax router collapses (every position
of a layer to the same experts: `models/sdar_moe.py`, Initialisation). The
cure here is two of that model's three: embedding entries at unit scale (a
position carries its token through the layers) and each share's router
columns summing to zero (`parallel/moe.py share_centred_normal`). Attention
is NOT sharpened: the one gain its path has is the latent norm's, which
scales every head's values with its keys, and at the published widths a gain
of 3 spread the deepest layer's load further (fullest expert 3.1-3.8 times
the mean against 1.7-2.3 at gain 1; PERF.md, Findings, PR 34).

Training and evaluation only: a paged cache of latents and the up-projection
absorbed into the query are serving's, and not here (ROADMAP, M4).
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
    constrain,
)
from kubeflow_tpu.parallel.mesh import AXIS_FSDP, AXIS_MODEL
from kubeflow_tpu.parallel.moe import (HELD_EXPERTS_PARTITION_RULES,
                                       ROUTER_STATE, HeldExpertsMlp,
                                       router_counters, share_centred_normal)
from kubeflow_tpu.parallel.ring_attention import (FLASH_REMAT_POLICY, NEG_INF,
                                                  flash_attention)
from kubeflow_tpu.parallel.rope import (apply_rope, yarn_frequencies,
                                        yarn_mscale)

PARTITION_RULES: list[tuple[str, P]] = [
    *GPT_PARTITION_RULES,
    *HELD_EXPERTS_PARTITION_RULES,
    # the latent is small and every head reads all of it: down and its norm
    # stay whole, up splits its heads like a key/value projection
    (r"kv_latent/down/kernel$", P(AXIS_FSDP, None)),
    (r"kv_latent/up/kernel$", P(AXIS_FSDP, AXIS_MODEL, None)),
]


@dataclass(frozen=True)
class DeepseekV2Config:
    """Keys as the published `config.json` names them, where the program's
    other models have no name of their own for the same thing."""

    vocab_size: int = 102400
    hidden_size: int = 2048
    num_layers: int = 27
    num_heads: int = 16
    qk_nope_head_dim: int = 128           # a query's and a key's part without position
    qk_rope_head_dim: int = 64            # their rotary part; the key's is shared by the heads
    v_head_dim: int = 128
    kv_lora_rank: int = 512               # the latent's width
    mlp_dim: int = 10944                  # the leading dense layers' width
    num_dense_layers: int = 1             # `first_k_dense_replace`
    num_experts: int = 64                 # the router's width
    # the routed experts this share holds, [lo, hi); None is all of them
    experts_held: tuple[int, int] | None = None
    top_k: int = 6
    expert_dim: int = 1408
    num_shared_experts: int = 2
    route_scale: float = 1.0              # `routed_scaling_factor`
    renormalise: bool = False             # `norm_topk_prob`
    balance_loss: float = 0.001           # `aux_loss_alpha`, sequence-wise (`seq_aux`)
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # `rope_scaling` (YaRN); factor 1 is plain rotary frequencies
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    dtype: Any = jnp.float32
    attention: str = "dense"              # dense | flash
    # recompute each block in the backward pass, but for the flash kernel's
    # output and row statistic (ring_attention.FLASH_REMAT_POLICY keeps them)
    remat: bool = False

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} must be even")
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention {self.attention!r} is not dense|flash")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of {self.num_layers}")
        if self.num_experts % self.share:
            raise ValueError(f"shares of {self.share} experts do not tile {self.num_experts}")
        if self.rope_mscale != self.rope_mscale_all_dim:
            # cos and sin would carry the ratio of the two temperatures
            raise NotImplementedError(
                f"rope_mscale {self.rope_mscale} differs from rope_mscale_all_dim "
                f"{self.rope_mscale_all_dim}: the rotation carries no gain")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def share(self) -> int:
        """Experts a chip holds: the width of `experts_held`."""
        lo, hi = self.experts_held or (0, self.num_experts)
        return hi - lo

    @property
    def softmax_scale(self) -> float:
        """`qk_head_dim^-0.5` times YaRN's `mscale_all_dim` temperature squared."""
        return self.qk_head_dim ** -0.5 * yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2

    def rope_frequencies(self):
        return yarn_frequencies(self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
                                self.rope_original_max_position, self.rope_beta_fast,
                                self.rope_beta_slow)

    @staticmethod
    def tiny(**kw) -> "DeepseekV2Config":
        """Test-sized: a dense and two expert layers, unequal head sizes, and
        an original context short enough that YaRN blends at test lengths."""
        d = dict(vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                 mlp_dim=128, num_dense_layers=1, num_experts=8, top_k=2, expert_dim=32,
                 num_shared_experts=2, rope_original_max_position=16)
        d.update(kw)
        return DeepseekV2Config(**d)


def _norm(c: DeepseekV2Config, name: str):
    return nn.RMSNorm(epsilon=c.norm_eps, dtype=c.dtype, name=name)


def _dense(c: DeepseekV2Config, features, name: str, **kw):
    return nn.DenseGeneral(features, use_bias=False, dtype=c.dtype, name=name, **kw)


class KvLatent(nn.Module):
    """The compressed key/value path: a (B, L, hidden) -> (k_nope (B, L, H,
    nope), v (B, L, H, v), k_pe (B, L, rope)): down to the latent and the
    shared rotary key, the latent's norm, up to every head's key and value."""

    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, a):
        c = self.cfg
        with jax.named_scope("mla.kv_down"):
            ckv = _dense(c, c.kv_lora_rank + c.qk_rope_head_dim, "down")(a)
            latent, k_pe = jnp.split(ckv, [c.kv_lora_rank], axis=-1)
        with jax.named_scope("mla.kv_norm"):
            latent = _norm(c, "norm")(latent)
        with jax.named_scope("mla.kv_up"):
            kv = _dense(c, (c.num_heads, c.qk_nope_head_dim + c.v_head_dim), "up")(latent)
            k_nope, v = jnp.split(kv, [c.qk_nope_head_dim], axis=-1)
        return k_nope, v, k_pe


class DeepseekV2Attention(nn.Module):
    """The query projection, the attention core over keys of `qk_head_dim`
    and values of `v_head_dim`, and the output projection."""

    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, a, k_nope, v, k_pe):
        c = self.cfg
        q = _dense(c, (c.num_heads, c.qk_head_dim), "query")(a)
        with jax.named_scope("mla.rope"):
            pos, freqs = jnp.arange(a.shape[1]), c.rope_frequencies()
            q_nope, q_pe = jnp.split(q, [c.qk_nope_head_dim], axis=-1)
            q_pe = apply_rope(q_pe, pos, freqs=freqs)
            k_pe = apply_rope(k_pe[:, :, None, :], pos, freqs=freqs)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], k_pe.shape[-1]))], axis=-1)
        if c.attention == "flash":
            bias = jnp.zeros((a.shape[0], 1, 1, a.shape[1]), c.dtype)
            y = flash_attention(q, k, v, bias, causal=True, scale=c.softmax_scale)
        else:  # the square, for sizes a test runs
            at = jnp.arange(a.shape[1])
            s = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) * c.softmax_scale
            s = jnp.where(at[None, :] > at[:, None], NEG_INF, s)
            y = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1).astype(q.dtype), v)
        return _dense(c, c.hidden_size, "attn_out", axis=(-2, -1))(y)


class DeepseekV2Block(nn.Module):
    """x + attention(n(x)), then x + mlp(n(x)): a norm before each sublayer."""

    cfg: DeepseekV2Config
    dense_mlp: bool

    @nn.compact
    def __call__(self, x, train: bool):
        c = self.cfg
        a = _norm(c, "ln_attn")(x)
        y = DeepseekV2Attention(c, name="attention")(a, *KvLatent(c, name="kv_latent")(a))
        x = constrain(x + y, ACT_SPEC)
        b = _norm(c, "ln_mlp")(x)
        if self.dense_mlp:
            f = _dense(c, c.hidden_size, "mlp_down")(
                nn.silu(_dense(c, c.mlp_dim, "mlp_gate")(b)) * _dense(c, c.mlp_dim, "mlp_up")(b))
        else:
            f = HeldExpertsMlp(
                hidden_size=c.hidden_size, expert_dim=c.expert_dim,
                num_experts=c.num_experts, top_k=c.top_k, experts_held=c.experts_held,
                score_func="softmax", num_shared_experts=c.num_shared_experts,
                route_scale=c.route_scale, bias_update_rate=0.0, dtype=c.dtype,
                router_init=share_centred_normal(c.share), renormalise=c.renormalise,
                balance_loss=c.balance_loss, name="moe",
            )(b, train)
        return constrain(x + f, ACT_SPEC)


class DeepseekV2LM(nn.Module):
    """Causal language model: __call__(input_ids (B, L)) -> (B, L, vocab)
    float32 logits. No padding mask: position i sees every j <= i; padded
    labels (id 0) are masked by `causal_lm_loss` as for GPTLM."""

    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        c = self.cfg
        x = VocabEmbed(c.vocab_size, c.hidden_size, dtype=c.dtype, name="token_embed",
                       embedding_init=nn.initializers.normal(stddev=1.0))(input_ids)
        x = constrain(x, ACT_SPEC)
        block_cls = nn.remat(DeepseekV2Block, static_argnums=(2,),
                             policy=FLASH_REMAT_POLICY) if c.remat else DeepseekV2Block
        for i in range(c.num_layers):
            x = block_cls(c, i < c.num_dense_layers, name=f"layer_{i}")(x, train)
        x = _norm(c, "ln_final")(x)
        return _dense(c, c.vocab_size, "lm_head")(x).astype(jnp.float32)

    @staticmethod
    def step_counters(extra, y=None) -> dict:
        """What the Trainer adds to a step's metrics: the routers' counters and
        `moe_balance_loss`, the sown balance loss summed over the expert
        layers; nothing for a model whose layers are all dense."""
        return router_counters(extra[ROUTER_STATE]) if ROUTER_STATE in extra else {}


DeepseekV2LM.PARTITION_RULES = PARTITION_RULES
DeepseekV2LM.PREFERRED_COMPUTE_DTYPE = jnp.bfloat16
