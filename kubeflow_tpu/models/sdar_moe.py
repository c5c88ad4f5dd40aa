"""SDAR-MoE — a softmax-routed sparse decoder trained by diffusion over
blocks (`model_type: sdar_moe`; SDAR, arXiv:2510.06303, on the objective of
Block Diffusion, arXiv:2503.09573), on the training path.

The block is the public Qwen3-MoE one: RMSNorm before each sublayer and none
after, grouped keys and values with a head size of its own, RMSNorm on each
head's queries and keys, rotary positions in every layer, no bias anywhere,
no gate on attention, and in every layer a `HeldExpertsMlp` whose router is
a softmax over all `num_experts`, the `top_k` chosen renormalised, with no
selection bias and no shared expert.

What makes it another model is the objective. A row of `L` data tokens is cut
into blocks of `block_length`; `corrupt` draws a masking rate a block,
uniform on `[mask_rate_min, 1]`, and masks each token of the block with that
probability. The model runs on `2 L` positions, the clean row first and its
noisy copy after it (a token and its noisy copy share a rotary position),
under `attention_mask.BlockDiffusion`: a noisy query sees its own noisy
block and every earlier clean block, a clean query its own and every earlier
clean block. Logits are taken at the `L` noisy positions only, and the loss
of a row is `(1 / L) sum_i masked_i / t_blk(i) * CE(logits_i, x0_i)`: no
shift, a masked position predicts its own token. The Trainer applies
`corrupt` with the step's rng before the forward pass (`train/trainer.py
_corrupted`), and hands `sdar_loss` what it returned.

Module names follow the step's trace readers: blocks are `layer_N`, the
attention core lives under `attention` beside its projections `query`, `key`,
`value`, `attn_out`; the expert layer is `layer_N/moe`.

Initialisation. Who trains this model continues from a checkpoint, whose
positions carry their tokens, whose attention is peaked and whose router is
balanced; weights drawn from a seed stand in for one (tests, the benchmark),
and plainly drawn they do none of it: an embedding row is a hundredth of what
a sublayer adds to it, a query averages every key it sees, every position of
a layer ends as that same average, and the router sends them all to the same
`top_k` experts. Three initialisers differ from the plain ones for that:
`token_rows`, `QK_GAIN_INIT` and `parallel/moe.py share_centred_normal`.

Training and evaluation only: a serving step that yields a block of tokens
is not here (ROADMAP, "Mechanisms the program lacks").
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
from kubeflow_tpu.parallel.attention_mask import BlockDiffusion
from kubeflow_tpu.parallel.moe import (HELD_EXPERTS_PARTITION_RULES,
                                       ROUTER_STATE, HeldExpertsMlp,
                                       router_counters, share_centred_normal)
from kubeflow_tpu.parallel.ring_attention import (FLASH_REMAT_POLICY, NEG_INF,
                                                  flash_attention)
from kubeflow_tpu.parallel.rope import apply_rope

PARTITION_RULES: list[tuple[str, P]] = [
    *GPT_PARTITION_RULES,
    *HELD_EXPERTS_PARTITION_RULES,
]


@dataclass(frozen=True)
class SdarMoeConfig:
    """Keys as the published `config.json` names them, where the program's
    other models have no name of their own for the same thing."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    num_experts: int = 128                # the router's width
    # the routed experts this share holds, [lo, hi); None is all of them
    experts_held: tuple[int, int] | None = None
    top_k: int = 8
    expert_dim: int = 768
    block_length: int = 4                 # tokens a diffusion block
    mask_rate_min: float = 0.05           # a block's rate is uniform on [this, 1]
    dtype: Any = jnp.float32
    attention: str = "dense"              # dense | flash
    # recompute each block in the backward pass, but for the flash kernel's
    # output and row statistic (ring_attention.FLASH_REMAT_POLICY keeps them)
    remat: bool = False

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"num_kv_heads {self.num_kv_heads} must divide num_heads "
                f"{self.num_heads}, and head_dim {self.head_dim} be even")
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention {self.attention!r} is not dense|flash")
        if not 0.0 < self.mask_rate_min <= 1.0:
            raise ValueError(f"mask_rate_min {self.mask_rate_min} is not in (0, 1]")
        if self.num_experts % self.share:
            raise ValueError(f"shares of {self.share} experts do not tile {self.num_experts}")

    @property
    def share(self) -> int:
        """Experts a chip holds: the width of `experts_held`."""
        lo, hi = self.experts_held or (0, self.num_experts)
        return hi - lo

    @staticmethod
    def tiny(**kw) -> "SdarMoeConfig":
        d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 num_kv_heads=2, head_dim=32, num_experts=8, top_k=2, expert_dim=32)
        d.update(kw)
        return SdarMoeConfig(**d)


#: what the gains of `q_norm` and `k_norm` start at: a query's scores then spread
#: by its square, 4, so it reads some tens of the keys it sees, not their mean,
#: and a masked position is told from another by its context
QK_GAIN_INIT = 2.0
#: what the mask id's row of the embedding is drawn at, beside 1 for a token's
MASK_ROW_SCALE = 1e-3


def token_rows(key, shape, dtype=jnp.float32):
    """The embedding's initialiser: entries at unit scale, so that a position
    carries its token through the layers (a sublayer adds entries of a tenth),
    and the mask id's row, the last, at `MASK_ROW_SCALE`: a masked position has
    no token of its own, it is what attention brings it of its context, and
    one row a quarter of all positions shared would route them as one."""
    rows = jax.random.normal(key, shape, dtype)
    return rows.at[-1].multiply(MASK_ROW_SCALE)


def _norm(c: SdarMoeConfig, name: str, gain: float = 1.0):
    return nn.RMSNorm(epsilon=c.norm_eps, dtype=c.dtype, name=name,
                      scale_init=nn.initializers.constant(gain))


class SdarAttention(nn.Module):
    """Attention over the 2 L positions of a clean row and its noisy copy."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        half = x.shape[1] // 2
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, c.head_dim), use_bias=False, dtype=c.dtype, name=name)
        q = heads(c.num_heads, "query")(x)
        k = heads(c.num_kv_heads, "key")(x)
        v = heads(c.num_kv_heads, "value")(x)
        # one gain vector each, over head_dim, shared by the heads
        q, k = _norm(c, "q_norm", QK_GAIN_INIT)(q), _norm(c, "k_norm", QK_GAIN_INIT)(k)
        pos = jnp.arange(x.shape[1]) % half  # a token and its noisy copy share one
        q, k = apply_rope(q, pos, c.rope_theta), apply_rope(k, pos, c.rope_theta)
        # query head j reads key/value head j // group (as AfmoeLM trains GQA:
        # the kernels stay single-shape)
        group = c.num_heads // c.num_kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        mask = BlockDiffusion(half, c.block_length)
        if c.attention == "flash":
            bias = jnp.zeros((x.shape[0], 1, 1, x.shape[1]), c.dtype)
            y = flash_attention(q, k, v, bias, mask=mask)
        else:  # the square, for sizes a test runs
            at = jnp.arange(x.shape[1])
            s = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) / c.head_dim ** 0.5
            s = jnp.where(mask.hidden(at[:, None], at[None, :]), NEG_INF, s)
            y = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1).astype(q.dtype), v)
        return nn.DenseGeneral(c.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=c.dtype, name="attn_out")(y)


class SdarMoeBlock(nn.Module):
    """x + attention(n(x)), then x + moe(n(x))."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x, train: bool):
        c = self.cfg
        x = constrain(x + SdarAttention(c, name="attention")(_norm(c, "ln_attn")(x)), ACT_SPEC)
        f = HeldExpertsMlp(
            hidden_size=c.hidden_size, expert_dim=c.expert_dim,
            num_experts=c.num_experts, top_k=c.top_k, experts_held=c.experts_held,
            score_func="softmax", num_shared_experts=0, route_scale=1.0,
            bias_update_rate=0.0, dtype=c.dtype,
            router_init=share_centred_normal(c.share), name="moe",
        )(_norm(c, "ln_mlp")(x), train)
        return constrain(x + f, ACT_SPEC)


class SdarMoeLM(nn.Module):
    """__call__(ids (B, 2 L): a clean row, then its noisy copy, as `corrupt`
    lays them out) -> (B, L, vocab) float32 logits at the noisy positions."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        c = self.cfg
        if input_ids.shape[1] % (2 * c.block_length):
            raise ValueError(
                f"{input_ids.shape[1]} positions are not a clean and a noisy copy "
                f"of whole blocks of {c.block_length}")
        x = VocabEmbed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       embedding_init=token_rows, name="token_embed")(input_ids)
        x = constrain(x, ACT_SPEC)
        block_cls = nn.remat(SdarMoeBlock, static_argnums=(2,),
                             policy=FLASH_REMAT_POLICY) if c.remat else SdarMoeBlock
        for i in range(c.num_layers):
            x = block_cls(c, name=f"layer_{i}")(x, train)
        x = _norm(c, "ln_final")(x[:, x.shape[1] // 2:])
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)

    @nn.nowrap
    def corrupt(self, rng, x, y):
        """The step's noise (the Trainer's `_corrupted`): x (B, L) data ids,
        y its labels (x itself) -> (ids (B, 2 L): x, then x with the drawn
        positions replaced by the mask id, the vocabulary's last row;
        {labels, masked, weights}, each
        (B, L)). A block's rate t is uniform on [mask_rate_min, 1], its
        tokens are masked independently with probability t, and a masked
        position weighs 1 / t (arXiv:2503.09573's linear schedule)."""
        c = self.cfg
        b, length = x.shape
        if length % c.block_length:
            raise ValueError(f"blocks of {c.block_length} do not tile {length} tokens")
        rate_rng, mask_rng = jax.random.split(rng)
        rate = jax.random.uniform(rate_rng, (b, length // c.block_length),
                                  minval=c.mask_rate_min, maxval=1.0)
        rate = jnp.repeat(rate, c.block_length, axis=1)
        masked = jax.random.uniform(mask_rng, (b, length)) < rate
        noisy = jnp.where(masked, jnp.asarray(c.vocab_size - 1, x.dtype), x)
        return (jnp.concatenate([x, noisy], axis=1),
                {"labels": y, "masked": masked, "weights": masked / rate})

    @staticmethod
    def step_counters(extra, y) -> dict:
        """What the Trainer adds to a step's metrics: the routers' counters,
        the share of the step's positions that `corrupt` masked and the
        largest weight it gave one."""
        return {**router_counters(extra[ROUTER_STATE]),
                "diffusion_masked_share": y["masked"].mean(dtype=jnp.float32),
                "diffusion_weight_max": y["weights"].max()}


def _per_token(logits, y):
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(logits, y["labels"])


def sdar_loss(logits: jax.Array, y: dict) -> jax.Array:
    """The block-diffusion objective: a row's loss is the mean over its L
    positions of weight x cross entropy (the weight is 0 where nothing was
    masked); the batch's is the mean over rows. No shift."""
    return (_per_token(logits, y) * y["weights"]).mean()


def sdar_eval_metrics(logits: jax.Array, y: dict):
    """Per-example (objective, accuracy over the masked positions): the eval
    twin of `sdar_loss` (Trainer `eval_metrics_fn` contract)."""
    masked = y["masked"].astype(jnp.float32)
    hit = (jnp.argmax(logits, -1) == y["labels"]) * masked
    return ((_per_token(logits, y) * y["weights"]).mean(-1),
            hit.sum(-1) / jnp.maximum(masked.sum(-1), 1.0))


SdarMoeLM.PARTITION_RULES = PARTITION_RULES
SdarMoeLM.PREFERRED_COMPUTE_DTYPE = jnp.bfloat16
