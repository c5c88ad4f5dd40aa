"""BERT encoder family — north-star config #3 (BASELINE.md: BERT-base steps/sec).

Reference parity: the reference fine-tunes BERT via Horovod user images under
MPIJob (SURVEY.md §3.2); here the encoder is in-tree and every parallelism
axis is first-class:

  - TP (Megatron-style) is *declarative*: PARTITION_RULES map param paths to
    PartitionSpecs over the mesh's (fsdp, model) axes; XLA's SPMD partitioner
    inserts the all-gathers/reduce-scatters — no hand-written collectives.
  - Activation shardings are pinned at the residual stream via
    with_sharding_constraint (P(("data","fsdp"), None, None)) so the
    partitioner never materializes a replicated (B, L, H) tensor.
  - Attention is pluggable (`attention=`): "dense" (this file),
    "ring" / "ulysses" (kubeflow_tpu.parallel.ring_attention) for context
    parallelism over the `context` mesh axis.
  - bf16 compute / f32 params; static seq_len; padding via pad_token_id==0
    derived inside the model, so the data pipeline ships one int32 array.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_MODEL,
)
from kubeflow_tpu.parallel.sharding import BATCH_AXES
from kubeflow_tpu.parallel.moe import MOE_PARTITION_RULES, MoeMlp

# Param-path regex -> PartitionSpec. fsdp shards the "long" dim that the
# model axis leaves free; tiny params (LayerNorm, biases) replicate via the
# default heuristic in parallel/sharding.py.
PARTITION_RULES: list[tuple[str, P]] = [
    (r"(query|key|value)/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"attn_out/kernel$", P(AXIS_MODEL, AXIS_FSDP)),
    (r"mlp_up/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"mlp_down/kernel$", P(AXIS_MODEL, AXIS_FSDP)),
    (r"token_embed/embedding$", P(AXIS_MODEL, AXIS_FSDP)),
    (r"(position_embed|type_embed)/embedding$", P(None, AXIS_FSDP)),
    (r"pooler/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"mlm_dense/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    *MOE_PARTITION_RULES,
]

# residual-stream activation layout: batch over data-like axes (expert
# parallelism subdivides data parallelism — parallel/moe.py), hidden
# replicated
ACT_SPEC = P((AXIS_DATA, AXIS_FSDP, AXIS_EXPERT), AXIS_CONTEXT, None)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """Sharding pin that is a no-op when no ambient mesh is set."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


class VocabEmbed(nn.Embed):
    """nn.Embed that lowers the lookup to a one-hot matmul when the ambient
    mesh shards the vocab dim over `model` (TP).

    A plain gather over a vocab-sharded table cannot be partitioned by XLA's
    SPMD pass — it falls back to rematerializing the full table on every
    device (the round-1 "Involuntary full rematerialization" cliff). The
    one-hot contraction is the Megatron/maxtext recipe: the table stays put,
    XLA inserts one psum over `model`, and the matmul rides the MXU.
    """

    def __call__(self, inputs: jax.Array) -> jax.Array:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return super().__call__(inputs)
        (table,) = self.promote_dtype(self.embedding, dtype=self.dtype,
                                      inexact=False)
        if mesh.shape.get(AXIS_MODEL, 1) > 1:
            onehot = jax.nn.one_hot(inputs, self.num_embeddings, dtype=table.dtype)
            return jnp.dot(onehot, table)
        # No vocab-dim sharding: all-gather any feature shards up-front (the
        # FSDP gather-at-use contract) so the take sees a replicated operand
        # and the partitioner never warns about resharding gather output.
        table = constrain(table, P(None, None))
        return jnp.take(table, inputs, axis=0)


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    dropout_rate: float = 0.1
    pad_token_id: int = 0
    dtype: Any = jnp.float32
    attention: str = "dense"  # dense | ring | ulysses
    attention_block: int = 128  # ring attention KV block size
    # rematerialize each encoder block on backward (jax.checkpoint) — the
    # long-context HBM lever (activation memory O(seq·hidden), one extra
    # forward)
    remat: bool = False
    # MoE: 0 = dense MLP; >0 replaces every MLP with a MoeMlp of this many
    # experts, dispatched over the `expert` mesh axis (parallel/moe.py)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0

    def __post_init__(self):
        # fail fast on malformed architectures: NAS sweeps feed these fields
        # from search spaces, and a non-dividing head count would silently
        # train a truncated model (head_dim floor-divides)
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} not divisible by "
                f"num_heads {self.num_heads}"
            )

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        d = dict(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                 mlp_dim=128, max_len=128)
        d.update(kw)
        return BertConfig(**d)


# (B, H, L_q, L_k) attention scores: batch over the canonical data-like axes
# (sharding.BATCH_AXES — one definition, so specs cannot drift when a
# data-like axis is added), heads over `model`, query positions over
# `context` (matching ACT_SPEC's L sharding; the key dim is reduced by the
# softmax and stays gathered, the best dense attention can do under SP).
# Pinned explicitly because inside remat/scan regions (pipeline stages) the
# partitioner otherwise picks a different sharding for the forward residual
# than the backward wants, triggering an involuntary full-remat reshard of
# the scores gradient at the shard_map boundary.
SCORES_SPEC = P(BATCH_AXES, AXIS_MODEL, AXIS_CONTEXT, None)


def dense_attention(q, k, v, bias, dropout_rng=None, dropout_rate=0.0, block=None):
    """Reference softmax attention: (B, L, H, D) tensors, additive bias."""
    depth = q.shape[-1]
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(depth).astype(q.dtype)
    if bias is not None:
        scores = scores + bias
    scores = constrain(scores, SCORES_SPEC)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rng is not None and dropout_rate > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


def _resolve_attention(kind: str) -> Callable:
    if kind == "dense":
        return dense_attention
    if kind in ("ring", "ulysses", "flash"):
        from kubeflow_tpu.parallel import ring_attention as ra

        return {
            "ring": ra.ring_attention,
            "ulysses": ra.ulysses_attention,
            "flash": ra.flash_attention,
        }[kind]
    raise ValueError(f"unknown attention kind {kind!r}")


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask, train: bool):
        c = self.cfg
        head_dim = c.hidden_size // c.num_heads
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (c.num_heads, head_dim), dtype=c.dtype, name=name
        )
        q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        # additive bias from padding mask: (B, 1, 1, L)
        bias = jnp.where(mask[:, None, None, :], 0.0, -1e9).astype(c.dtype)
        rng = self.make_rng("dropout") if train and c.dropout_rate > 0 else None
        attn_fn = _resolve_attention(c.attention)
        y = attn_fn(q, k, v, bias, dropout_rng=rng,
                    dropout_rate=c.dropout_rate if train else 0.0,
                    block=c.attention_block)
        y = nn.DenseGeneral(
            c.hidden_size, axis=(-2, -1), dtype=c.dtype, name="attn_out"
        )(y)
        return y


class BertLayer(nn.Module):
    """Post-LN transformer block (original BERT residual structure)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask, train: bool):
        c = self.cfg
        y = SelfAttention(c, name="attention")(x, mask, train)
        y = nn.Dropout(c.dropout_rate, deterministic=not train)(y)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_attn")(x + y)
        x = constrain(x, ACT_SPEC)
        if c.moe_experts:
            y = MoeMlp(
                hidden_size=c.hidden_size, mlp_dim=c.mlp_dim,
                num_experts=c.moe_experts, top_k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor, dtype=c.dtype,
                name="moe",
            )(x)
        else:
            y = nn.Dense(c.mlp_dim, dtype=c.dtype, name="mlp_up")(x)
            y = nn.gelu(y)
            y = nn.Dense(c.hidden_size, dtype=c.dtype, name="mlp_down")(y)
        y = nn.Dropout(c.dropout_rate, deterministic=not train)(y)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_mlp")(x + y)
        return constrain(x, ACT_SPEC)


class BertEmbeddings(nn.Module):
    """Token + position + type embeddings with the post-embedding LN.

    token_embed can be a shared nn.Embed (weight tying with an MLM head).
    Split out of BertEncoder so the pipeline-parallel model (bert_pp.py) can
    run it outside the stage ring (boundary stages replicate, the stack
    pipelines — the maxtext recipe).
    """

    cfg: BertConfig
    token_embed: Any = None

    @nn.compact
    def __call__(self, input_ids, train: bool = False, token_type_ids=None):
        c = self.cfg
        embed_mod = self.token_embed or VocabEmbed(
            c.vocab_size, c.hidden_size, dtype=c.dtype, name="token_embed"
        )
        embed = embed_mod(input_ids)
        pos = jnp.arange(input_ids.shape[1])[None, :]
        embed = embed + VocabEmbed(c.max_len, c.hidden_size, dtype=c.dtype,
                                   name="position_embed")(pos)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        embed = embed + VocabEmbed(2, c.hidden_size, dtype=c.dtype,
                                   name="type_embed")(token_type_ids)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_embed")(embed)
        x = nn.Dropout(c.dropout_rate, deterministic=not train)(x)
        return constrain(x, ACT_SPEC)


class BertEncoder(nn.Module):
    """Embeddings + transformer stack; returns (B, L, H) hidden states."""

    cfg: BertConfig
    token_embed: Any = None

    @nn.compact
    def __call__(self, input_ids, train: bool = False, token_type_ids=None):
        c = self.cfg
        mask = input_ids != c.pad_token_id
        x = BertEmbeddings(c, token_embed=self.token_embed, name="embeddings")(
            input_ids, train, token_type_ids
        )
        layer_cls = (
            nn.remat(BertLayer, static_argnums=(3,)) if c.remat else BertLayer
        )
        for i in range(c.num_layers):
            x = layer_cls(c, name=f"layer_{i}")(x, mask, train)
        return x


class BertForSequenceClassification(nn.Module):
    """[CLS]-pooled classifier — the north-star fine-tune head."""

    cfg: BertConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        x = BertEncoder(self.cfg, name="encoder")(input_ids, train)
        cls = x[:, 0]
        pooled = jnp.tanh(nn.Dense(self.cfg.hidden_size, dtype=self.cfg.dtype,
                                   name="pooler")(cls))
        pooled = nn.Dropout(self.cfg.dropout_rate, deterministic=not train)(pooled)
        logits = nn.Dense(self.num_classes, dtype=self.cfg.dtype,
                          name="classifier")(pooled)
        return logits.astype(jnp.float32)


class BertForMaskedLM(nn.Module):
    """MLM head with tied input embeddings (pretraining parity)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        c = self.cfg
        token_embed = VocabEmbed(
            c.vocab_size, c.hidden_size, dtype=c.dtype, name="token_embed"
        )
        x = BertEncoder(c, token_embed=token_embed, name="encoder")(input_ids, train)
        x = nn.gelu(nn.Dense(c.hidden_size, dtype=c.dtype, name="mlm_dense")(x))
        x = nn.LayerNorm(dtype=c.dtype, name="mlm_ln")(x)
        logits = token_embed.attend(x)  # tied output projection
        logits = logits + self.param(
            "mlm_bias", nn.initializers.zeros, (c.vocab_size,)
        ).astype(c.dtype)
        return logits.astype(jnp.float32)


# the Trainer picks TP rules up from the model class (trainer.py); the
# encoder family is MXU-heavy, so AUTO compute dtype resolves to bf16 on
# accelerator backends (trainer.resolve_compute_dtype)
for _cls in (BertEncoder, BertForSequenceClassification, BertForMaskedLM):
    _cls.PARTITION_RULES = PARTITION_RULES
    _cls.PREFERRED_COMPUTE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------- MLM training

from kubeflow_tpu.train.data import IGNORE_LABEL  # noqa: E402 — shared sentinel


def masked_lm_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """BERT pretraining objective: cross entropy at masked positions only.

    labels: (B, L) int — original token ids at masked positions,
    IGNORE_LABEL elsewhere (train/data.py mask_tokens_for_mlm builds them).
    """
    import optax

    w = (labels != IGNORE_LABEL).astype(jnp.float32)
    safe = jnp.where(labels == IGNORE_LABEL, 0, labels)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    return (per_tok * w).sum() / jnp.maximum(w.sum(), 1.0)


def masked_lm_eval_metrics(logits: jax.Array, labels: jax.Array):
    """Per-example (masked loss, masked accuracy) — Trainer eval contract."""
    import optax

    w = (labels != IGNORE_LABEL).astype(jnp.float32)
    safe = jnp.where(labels == IGNORE_LABEL, 0, labels)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    denom = jnp.maximum(w.sum(-1), 1.0)
    per_ex = (per_tok * w).sum(-1) / denom
    acc = ((jnp.argmax(logits, -1) == safe) * w).sum(-1) / denom
    return per_ex, acc
