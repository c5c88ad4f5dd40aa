"""GPT — decoder-only causal LM, the long-context flagship.

The reference platform ships no models (kubeflow/examples supplies encoder
images — SURVEY.md L6); this family exists because long-context training is
first-class here (SURVEY.md §5.7): causal ring attention shards the sequence
over the `context` axis with GLOBAL-position masking (parallel/ring_attention
.py), so a sequence 8x one device's memory trains with the same module.

Architecture: pre-LN transformer decoder (GPT-2 shape), learned OR rotary
positions (GPTConfig.position_embedding — rope has no position table),
optional grouped-query attention (num_kv_heads), weight-tied LM head,
bf16 compute / f32 params. TP/FSDP via the same declarative
PARTITION_RULES mechanism as BERT.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.models.bert import (
    ACT_SPEC,
    VocabEmbed,
    _resolve_attention,
    constrain,
)
from kubeflow_tpu.parallel.mesh import AXIS_FSDP, AXIS_MODEL
from kubeflow_tpu.parallel.moe import MOE_PARTITION_RULES, MoeMlp
from kubeflow_tpu.parallel.ring_attention import FLASH_REMAT_POLICY

PARTITION_RULES: list[tuple[str, P]] = [
    (r"(query|key|value)/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"attn_out/kernel$", P(AXIS_MODEL, AXIS_FSDP)),
    (r"(mlp_up|mlp_gate)/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"mlp_down/kernel$", P(AXIS_MODEL, AXIS_FSDP)),
    (r"token_embed/embedding$", P(AXIS_MODEL, AXIS_FSDP)),
    (r"lm_head/kernel$", P(AXIS_FSDP, AXIS_MODEL)),
    (r"position_embed/embedding$", P(None, AXIS_FSDP)),
    *MOE_PARTITION_RULES,
]


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    # grouped-query attention (Llama/Mistral shape): K/V projected to this
    # many heads, each shared by num_heads/num_kv_heads query heads. 0 =
    # num_heads (MHA); 1 = multi-query. The KV cache shrinks by the same
    # ratio — the direct lever on decode, which is HBM-bandwidth-bound.
    num_kv_heads: int = 0
    # "learned" (GPT-2 absolute embeddings) | "rope" (rotary, the
    # Llama/Mistral scheme: positions enter as Q/K rotations per layer,
    # no position table — decode rotates by the cache index, so the
    # pattern extrapolates with sequence position)
    position_embedding: str = "learned"
    rope_theta: float = 10000.0
    # rolling decode cache (Mistral serving): with a sliding window, the
    # KV cache can be a ring buffer of this many slots instead of a full
    # (max_len)-deep buffer — decode attention bandwidth and cache memory
    # scale with the capacity, not the context budget. Prompts must fit
    # capacity - window + 1 positions (trace-time check); 0 = full cache.
    kv_cache_capacity: int = 0
    # sliding-window attention (Mistral): each query attends to at most
    # the previous `attention_window` positions (itself included). 0 =
    # full causal. Composes with GQA + rope on EVERY path since r4:
    # dense, decode, flash (whole out-of-window KV blocks skipped,
    # O(L·W)), ring (hop count shrinks to ceil(window/L_loc)+1), ulysses
    attention_window: int = 0
    mlp_dim: int = 3072
    max_len: int = 1024
    dropout_rate: float = 0.1
    dtype: Any = jnp.float32
    attention: str = "dense"  # dense | ring | ulysses | flash
    attention_block: int = 128
    # Llama/Mistral-shape knobs (GPTConfig.llama() sets all four):
    #   norm       "layernorm" (GPT-2) | "rmsnorm" (scale-only, no mean
    #              subtraction — cheaper on TPU: one reduction, no bias add)
    #   activation "gelu" (single up-projection) | "swiglu"
    #              (silu(gate)·up — two up-projections; mlp_dim is the
    #              intermediate width in both cases)
    #   use_bias   False drops bias from every projection and LayerNorm
    #   tie_embeddings  False reads logits from a separate lm_head matmul
    #              instead of token_embed.attend (Llama unties; GPT-2 ties)
    norm: str = "layernorm"
    activation: str = "gelu"
    use_bias: bool = True
    tie_embeddings: bool = True
    # norm epsilon (flax default 1e-6); HF checkpoints vary (Llama-2 uses
    # 1e-5) and the importer threads the checkpoint's value for parity
    norm_eps: float = 1e-6
    # recompute each block in the backward pass (jax.checkpoint), but for the
    # flash kernel's output and row statistic, which FLASH_REMAT_POLICY keeps
    # (ring_attention.py has what they cost): the long-context HBM lever
    remat: bool = False
    # MoE decoder (Mixtral shape): 0 = dense MLP; >0 replaces every block's
    # MLP with a MoeMlp of this many experts over the `expert` mesh axis
    # (parallel/moe.py — same dispatch as the BERT encoder)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0

    def __post_init__(self):
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} not divisible by "
                f"num_heads {self.num_heads}"
            )
        if self.num_kv_heads and (
                self.num_kv_heads < 0
                or self.num_heads % self.num_kv_heads):
            raise ValueError(
                f"num_kv_heads {self.num_kv_heads} must be a positive "
                f"divisor of num_heads {self.num_heads} (or 0 for MHA)"
            )
        if self.position_embedding not in ("learned", "rope"):
            raise ValueError(
                f"position_embedding {self.position_embedding!r} "
                "(learned|rope)")
        if self.position_embedding == "rope":
            if (self.hidden_size // self.num_heads) % 2:
                raise ValueError(
                    "rope needs an even head_dim "
                    f"(got {self.hidden_size // self.num_heads})")
        if self.attention_window:
            if self.attention_window < 1:
                raise ValueError(
                    f"attention_window {self.attention_window} must be "
                    ">= 1 (or 0 for full causal)")
            if self.attention not in ("dense", "flash", "ring", "ulysses"):
                raise ValueError(
                    "attention_window composes with dense/flash/ring/"
                    f"ulysses + decode (got attention={self.attention!r})")
        if self.kv_cache_capacity:
            if not self.attention_window:
                raise ValueError(
                    "kv_cache_capacity (rolling decode cache) requires "
                    "attention_window — without a window, arbitrarily old "
                    "keys stay visible and may never be evicted")
            if self.kv_cache_capacity < self.attention_window:
                raise ValueError(
                    f"kv_cache_capacity {self.kv_cache_capacity} < "
                    f"attention_window {self.attention_window}: a slot "
                    "would be evicted while still inside every query's "
                    "window")
            if self.kv_cache_capacity >= self.max_len:
                raise ValueError(
                    f"kv_cache_capacity {self.kv_cache_capacity} >= "
                    f"max_len {self.max_len}: rolling would only cost "
                    "masking math — leave it 0 for the plain full cache")
        if self.moe_experts and self.moe_top_k > self.moe_experts:
            raise ValueError(
                f"moe_top_k {self.moe_top_k} > moe_experts "
                f"{self.moe_experts}"
            )
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm {self.norm!r} is not layernorm|rmsnorm")
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(
                f"activation {self.activation!r} is not gelu|swiglu")

    @staticmethod
    def small(**kw) -> "GPTConfig":
        return GPTConfig(**kw)  # GPT-2 small shape

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 mlp_dim=128, max_len=256)
        d.update(kw)
        return GPTConfig(**d)

    @staticmethod
    def llama(**kw) -> "GPTConfig":
        """Llama/Mistral-shaped decoder: RMSNorm, SwiGLU, rope, GQA-ready,
        bias-free, untied head. Defaults to a test-sized shape; pass real
        dims for production (Mistral-7B ≈ hidden 4096, layers 32, heads
        32, num_kv_heads 8, mlp_dim 14336, attention_window 4096)."""
        d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 num_kv_heads=2, mlp_dim=176, max_len=256,
                 norm="rmsnorm", activation="swiglu", use_bias=False,
                 tie_embeddings=False, position_embedding="rope",
                 dropout_rate=0.0)
        d.update(kw)
        return GPTConfig(**d)


# shared with the context-parallel attention paths (parallel/rope.py);
# re-exported here as the family's public name
from kubeflow_tpu.parallel.rope import apply_rope  # noqa: E402


def causal_dense_attention(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                           block=None, window: int = 0):
    """Reference causal softmax attention (numerics baseline for tests).
    window > 0 adds Mistral-style sliding-window masking: query i sees
    keys in (i - window, i]."""
    depth = q.shape[-1]
    s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(depth).astype(q.dtype)
    if bias is not None:
        s = s + bias
    lq, lk = q.shape[1], k.shape[1]
    mask = jnp.tril(jnp.ones((lq, lk), bool))
    if window:
        rows = jnp.arange(lq)[:, None]
        cols = jnp.arange(lk)[None, :]
        mask = mask & (rows - cols < window)
    s = jnp.where(mask[None, None], s.astype(jnp.float32), -1e9)
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if dropout_rng is not None and dropout_rate > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


def _decoder_norm(c: "GPTConfig", name: str):
    """The block norm: LayerNorm (GPT-2) or scale-only RMSNorm (Llama)."""
    if c.norm == "rmsnorm":
        return nn.RMSNorm(dtype=c.dtype, name=name, epsilon=c.norm_eps)
    return nn.LayerNorm(dtype=c.dtype, name=name, use_bias=c.use_bias,
                        epsilon=c.norm_eps)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, bias, train: bool, decode: bool = False):
        c = self.cfg
        head_dim = c.hidden_size // c.num_heads
        kv_heads = c.num_kv_heads or c.num_heads
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, head_dim), dtype=c.dtype, name=name, use_bias=c.use_bias
        )
        q = heads(c.num_heads, "query")(x)
        k = heads(kv_heads, "key")(x)
        v = heads(kv_heads, "value")(x)
        if decode:
            y = self._cached_attention(q, k, v)
        else:
            rope_inside = (c.position_embedding == "rope"
                           and c.attention in ("ring", "ulysses"))
            if c.position_embedding == "rope" and not rope_inside:
                # dense/flash see the full local sequence: rotate here.
                # ring/ulysses shard the sequence — THEY rotate, by global
                # position, inside their shard regions
                pos = jnp.arange(q.shape[1])
                q = apply_rope(q, pos, c.rope_theta)
                k = apply_rope(k, pos, c.rope_theta)
            if kv_heads != c.num_heads:
                # training path: broadcast KV groups up to full heads (the
                # parameter + cache savings stand; the attention kernels
                # stay single-shape). Decode keeps the grouped einsum and
                # the small cache — that's where the bandwidth win lives.
                group = c.num_heads // kv_heads
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            rng = (self.make_rng("dropout")
                   if train and c.dropout_rate > 0 else None)
            if c.attention == "dense":
                y = causal_dense_attention(
                    q, k, v, bias, dropout_rng=rng,
                    dropout_rate=c.dropout_rate if train else 0.0,
                    window=c.attention_window,
                )
            else:
                attn_fn = _resolve_attention(c.attention)
                kw = ({"rope_theta": c.rope_theta} if rope_inside else {})
                if c.attention_window:
                    kw["window"] = c.attention_window
                y = attn_fn(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                            block=c.attention_block, causal=True, **kw)
        return nn.DenseGeneral(
            c.hidden_size, axis=(-2, -1), dtype=c.dtype, name="attn_out",
            use_bias=c.use_bias,
        )(y)

    def _cached_attention(self, q, k, v):
        """KV-cache attention — ONE static-shape code path for both prefill
        (L = prompt length) and decode (L = 1), the TPU-idiomatic
        autoregressive loop: the cache is a fixed (B, max_len, KVH, D)
        buffer, new K/V write at the running index via
        dynamic_update_slice, and every step attends over the full buffer
        under a position mask — no shape ever depends on how many tokens
        have been generated, so XLA compiles exactly two executables
        (prefill + decode step). Under GQA (KVH < H) the query heads fold
        into (KVH, group) and the einsums contract against the small cache
        directly — the repeated-KV tensor is never materialized."""
        c = self.cfg
        b, l, h, d = q.shape
        kvh = k.shape[2]
        # Rolling cache (kv_cache_capacity with a sliding window): the
        # buffer is a ring of C slots instead of max_len — decode
        # attention bandwidth and cache memory scale with C. Capacity
        # math: a block write of L positions evicts positions <= last - C,
        # and the earliest query in the block still needs back to
        # cur - window + 1, so C >= window + L - 1 keeps every visible
        # key (checked below at trace time).
        C = c.kv_cache_capacity or c.max_len
        rolling = C < c.max_len
        ck = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((b, C, kvh, d), c.dtype))
        cv = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((b, C, kvh, d), c.dtype))
        # PER-ROW index (B,): in-flight rows may sit at different depths
        # (continuous batching, serving/continuous.py); uniform decode
        # (generate/speculative) is the all-rows-equal special case
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32))
        cur = idx.value                                  # (B,)
        q_pos = cur[:, None] + jnp.arange(l)[None, :]    # (B, L)
        if c.position_embedding == "rope":
            # rotate by ABSOLUTE position before the cache write: cached
            # keys carry their rotation, so one decode step only rotates
            # the new (q, k) pair
            q = apply_rope(q, q_pos, c.rope_theta)
            k = apply_rope(k, q_pos, c.rope_theta)
        if rolling and l > C - c.attention_window + 1:
            raise ValueError(
                f"prompt/block of {l} positions exceeds the rolling "
                f"cache's budget (capacity {C} - window "
                f"{c.attention_window} + 1 = {C - c.attention_window + 1})"
                " — raise kv_cache_capacity")
        if l == 1:
            # decode step: batched scatter — each row writes at ITS slot
            rows = jnp.arange(b)
            ck.value = ck.value.at[rows, cur % C].set(k[:, 0])
            cv.value = cv.value.at[rows, cur % C].set(v[:, 0])
        elif rolling:
            # prefill onto the ring: slots may wrap; l <= C (from the
            # budget check), so the l slots are distinct
            slots = (cur[0] + jnp.arange(l)) % C
            ck.value = ck.value.at[:, slots].set(k)
            cv.value = cv.value.at[:, slots].set(v)
        else:
            # block write (L > 1) at PER-ROW depths: a vmapped per-row
            # dynamic_update_slice — all-rows-equal prefill (generate,
            # engine admission) is the special case, and rows at DIFFERENT
            # depths (the continuous engine's speculative verify pass,
            # serving/continuous.py) write each at their own index
            def row_write(buf, kv, start):
                return jax.lax.dynamic_update_slice(buf, kv, (start, 0, 0))

            ck.value = jax.vmap(row_write)(ck.value, k, cur)
            cv.value = jax.vmap(row_write)(cv.value, v, cur)
        idx.value = cur + l
        qg = q.reshape(b, l, kvh, h // kvh, d)
        s = jnp.einsum("blkgd,bmkd->bkglm", qg, ck.value).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(d))
        if rolling:
            # slot j holds the NEWEST position p ≡ j (mod C) this row has
            # written: p_j = last - ((last - j) mod C); unwritten slots
            # reconstruct to p_j < 0. Visible = written AND causal AND
            # inside the window. (Incompatible with speculative rewind:
            # after a rewind, slot identity is ambiguous — speculative
            # rejects rolling configs.)
            j = jnp.arange(C)
            last = (cur + l - 1)[:, None]                # (B, 1)
            p_j = last - ((last - j[None, :]) % C)       # (B, C)
            visible = (
                (p_j[:, None, :] >= 0)
                & (p_j[:, None, :] <= q_pos[:, :, None])
                & (q_pos[:, :, None] - p_j[:, None, :] < c.attention_window)
            )
        else:
            k_pos = jnp.arange(C)                        # (max_len,)
            # causal + not-yet-written mask in one comparison: a key
            # position is visible iff it <= this query's position
            # (unwritten slots are all > that row's cur + l - 1 by
            # construction). A sliding window additionally hides keys
            # older than window-1 positions.
            visible = k_pos[None, None, :] <= q_pos[:, :, None]
            if c.attention_window:
                visible = visible & (
                    q_pos[:, :, None] - k_pos[None, None, :]
                    < c.attention_window)
        s = jnp.where(visible[:, None, None], s, -1e9)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        y = jnp.einsum("bkglm,bmkd->blkgd", p, cv.value)
        return y.reshape(b, l, h, d)


class GPTBlock(nn.Module):
    """Pre-LN decoder block (GPT-2 residual structure)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, bias, train: bool, decode: bool = False):
        c = self.cfg
        y = CausalSelfAttention(c, name="attention")(
            _decoder_norm(c, "ln_attn")(x), bias, train,
            decode=decode,
        )
        y = nn.Dropout(c.dropout_rate, deterministic=not train)(y)
        x = constrain(x + y, ACT_SPEC)
        h = _decoder_norm(c, "ln_mlp")(x)
        if c.moe_experts:
            # short decode blocks route DROPLESS (no capacity, row-
            # independent) so KV-cache decode — solo, continuous-batched,
            # or speculative verify — never couples rows through the drop
            # pattern; long blocks (prompt prefill) keep routed dispatch
            # (dense-all-experts at L=1k would multiply prefill MLP FLOPs
            # by E/k). MOE_DROPLESS_MAX_LEN is module-level (defined
            # below; resolved at call time).
            h = MoeMlp(
                hidden_size=c.hidden_size, mlp_dim=c.mlp_dim,
                num_experts=c.moe_experts, top_k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor, dtype=c.dtype,
                activation=c.activation, use_bias=c.use_bias,
                name="moe",
            )(h, dropless=decode and x.shape[1] <= MOE_DROPLESS_MAX_LEN)
        elif c.activation == "swiglu":
            # Llama MLP: silu(gate)·up, both width mlp_dim, then down
            gate = nn.Dense(c.mlp_dim, dtype=c.dtype, use_bias=c.use_bias,
                            name="mlp_gate")(h)
            up = nn.Dense(c.mlp_dim, dtype=c.dtype, use_bias=c.use_bias,
                          name="mlp_up")(h)
            h = nn.Dense(c.hidden_size, dtype=c.dtype, use_bias=c.use_bias,
                         name="mlp_down")(nn.silu(gate) * up)
        else:
            h = nn.gelu(nn.Dense(c.mlp_dim, dtype=c.dtype,
                                 use_bias=c.use_bias, name="mlp_up")(h))
            h = nn.Dense(c.hidden_size, dtype=c.dtype, use_bias=c.use_bias,
                         name="mlp_down")(h)
        h = nn.Dropout(c.dropout_rate, deterministic=not train)(h)
        return constrain(x + h, ACT_SPEC)


class GPTLM(nn.Module):
    """Causal language model: logits over the next token at every position.

    __call__(input_ids (B, L)) -> (B, L, vocab) f32 logits; pad positions
    carry a large negative additive bias so they are never attended to.
    """

    cfg: GPTConfig
    pad_token_id: int = 0

    @nn.compact
    def __call__(self, input_ids, train: bool = False, decode: bool = False):
        c = self.cfg
        token_embed = VocabEmbed(
            c.vocab_size, c.hidden_size, dtype=c.dtype, name="token_embed"
        )
        x = token_embed(input_ids)
        if decode:
            # autoregressive mode: positions continue from the PER-ROW
            # running offset (rows at different depths under continuous
            # batching); attention masking is positional via the KV cache
            # (generation prompts are unpadded — see generate())
            b = input_ids.shape[0]
            pidx = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((b,), jnp.int32))
            pos = pidx.value[:, None] + jnp.arange(input_ids.shape[1])[None, :]
            pidx.value = pidx.value + input_ids.shape[1]
            bias = None
        else:
            pos = jnp.arange(input_ids.shape[1])[None, :]
            mask = input_ids != self.pad_token_id
            bias = jnp.where(mask[:, None, None, :], 0.0, -1e9).astype(c.dtype)
        if c.position_embedding == "learned":
            x = x + VocabEmbed(c.max_len, c.hidden_size, dtype=c.dtype,
                               name="position_embed")(pos)
        # rope: positions enter per-layer as Q/K rotations — no table
        x = nn.Dropout(c.dropout_rate, deterministic=not train)(x)
        x = constrain(x, ACT_SPEC)
        # remat never wraps the decode path: generation is forward-only and
        # its cache writes must not re-execute
        block_cls = (
            nn.remat(GPTBlock, static_argnums=(3, 4), policy=FLASH_REMAT_POLICY)
            if (c.remat and not decode) else GPTBlock
        )
        for i in range(c.num_layers):
            x = block_cls(c, name=f"layer_{i}")(x, bias, train, decode)
        x = _decoder_norm(c, "ln_final")(x)
        if c.tie_embeddings:
            logits = token_embed.attend(x)  # weight-tied head (GPT-2)
        else:
            logits = nn.Dense(c.vocab_size, dtype=c.dtype, use_bias=False,
                              name="lm_head")(x)  # untied (Llama)
        return logits.astype(jnp.float32)


GPTLM.PARTITION_RULES = PARTITION_RULES
# bf16-by-default (trainer.resolve_compute_dtype): transformer LM matmuls
# are MXU-bound — on accelerator backends the Trainer flips the module's
# compute dtype to this unless the user pins compute_dtype explicitly
GPTLM.PREFERRED_COMPUTE_DTYPE = jnp.bfloat16


# Decode blocks at or under this many tokens route MoE DROPLESS (dense
# all-experts — row-independent, exact for continuous batching and
# speculative verify); longer blocks (prompt prefill) keep the routed
# capacity dispatch whose FLOPs scale with top_k, not num_experts. The
# engine prefills batch-1, so routed prefill is trivially row-independent
# there, and solo generate() takes the identical branch per shape — the
# engine-equals-solo exactness contract holds on both sides of the
# threshold.
MOE_DROPLESS_MAX_LEN = 16


def set_cache_indices(cache: dict, values=None, active=None) -> dict:
    """Rewrite every layer's per-row cache_index (and the LM's pos_index).

    The ONE owner of the cache-index contract (speculative rewind, the
    continuous engine's row parking and spec-round rewind all route here —
    three hand-rolled copies diverged before). values: scalar or (B,)
    replacement; None keeps the existing value. active: (B,) bool mask —
    rows where it is False park at 0 (so free rows' garbage decode can
    never creep an index past max_len)."""
    def fix(path, leaf):
        name = getattr(path[-1], "key", path[-1]) if path else ""
        if name in ("cache_index", "pos_index"):
            vals = (leaf if values is None else jnp.broadcast_to(
                jnp.asarray(values), leaf.shape).astype(leaf.dtype))
            if active is not None:
                vals = jnp.where(active, vals, 0)
            return vals
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def gather_kv_rows(cache: dict, starts, window: int) -> dict:
    """Gather every cached_key/cached_value leaf's per-row slice
    ``[starts[b] : starts[b] + window]`` -> {'/'-joined leaf path:
    (B, window, kv_heads, head_dim)}.

    The read-side twin of the per-row block write: rows sit at different
    depths (continuous batching), so the gather is a vmapped per-row
    dynamic_slice at each row's own start — ONE dispatch per tick
    regardless of row count. The serving engine uses it to extract the
    decode step's freshly-written K/V for the paged pool's per-row block
    chains (serving/fleet/pagedkv.py); `window` is static (T decode
    steps, or gamma+1 for a speculative round), so jitting the caller
    yields one executable per window length."""
    starts = jnp.asarray(starts, jnp.int32)
    out: dict = {}

    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            for k in sorted(tree):
                walk(tree[k], f"{prefix}/{k}")
            return
        name = prefix.rsplit("/", 1)[-1]
        if name in ("cached_key", "cached_value"):
            def row(buf, s, _w=window):
                return jax.lax.dynamic_slice(
                    buf, (s,) + (0,) * (buf.ndim - 1),
                    (_w,) + buf.shape[1:])

            out[prefix] = jax.vmap(row)(tree, starts)

    walk(cache)
    return out


def eos_id_array(eos_token_id):
    """Normalize an eos spec — int, or a sequence of stop ids (Llama-3
    instruct checkpoints stop on any of several) — to a 1-D int32 array,
    or None. The FIRST id is the canonical clamp token every decode path
    emits after a row finishes."""
    if eos_token_id is None:
        return None
    ids = jnp.atleast_1d(jnp.asarray(eos_token_id, jnp.int32))
    if ids.size == 0:
        return None
    return ids


def generate(
    model: GPTLM,
    variables: dict,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    rng: jax.Array | None = None,
    eos_token_id=None,
) -> jax.Array:
    """Autoregressive generation with the KV cache — fully jittable.

    prompt_ids: (B, prompt_len) int32, UNPADDED (all prompts same length;
    generation-time position masking is by cache index, not pad id).
    Returns (B, max_new_tokens) int32. temperature == 0 -> greedy;
    otherwise categorical over logits/temperature, restricted to the top_k
    logits when top_k > 0. Static shapes throughout: ONE prefill executable
    + ONE decode-step executable inside a lax.scan, the TPU decode shape.
    The LM's max_len bounds prompt_len + max_new_tokens.

    eos_token_id: per-row early stop under static shapes — an int or a
    sequence of stop ids (any of which finishes the row; Llama-3-style).
    Once a row emits a stop id, every later position in that row is the
    FIRST stop id (callers trim at the first occurrence). The decode
    loop still runs max_new_tokens steps (TPU-idiomatic: no
    data-dependent trip count), but finished rows feed the clamp token
    forward so their cache stays consistent with the clamped output.
    """
    b, prompt_len = prompt_ids.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt_len + max_new_tokens > model.cfg.max_len:
        raise ValueError(
            f"prompt {prompt_len} + {max_new_tokens} new tokens exceeds "
            f"max_len {model.cfg.max_len}"
        )
    if temperature == 0.0:
        rng = jax.random.PRNGKey(0)  # unused; keeps the scan carry uniform
    elif rng is None:
        raise ValueError("sampling (temperature > 0) needs an rng")

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / jnp.float32(temperature)
        if top_k > 0:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    # prefill: one pass over the whole prompt builds the cache
    logits, cache = model.apply(
        variables, prompt_ids, decode=True, mutable=["cache"]
    )
    rng, key = jax.random.split(rng)
    tok = sample(logits[:, -1], key)
    stops = eos_id_array(eos_token_id)
    done0 = (jnp.full((b,), False) if stops is None
             else jnp.isin(tok, stops))

    def step(carry, _):
        cache, tok, rng, done = carry
        logits, cache = model.apply(
            {**variables, **cache}, tok[:, None], decode=True,
            mutable=["cache"],
        )
        rng, key = jax.random.split(rng)
        nxt = sample(logits[:, 0], key)
        if stops is not None:
            nxt = jnp.where(done, stops[0], nxt)
            done = done | jnp.isin(nxt, stops)
        return (cache, nxt, rng, done), tok

    (_, last, _, _), toks = jax.lax.scan(
        step, (cache, tok, rng, done0), None, length=max_new_tokens - 1
    )
    out = jnp.concatenate([toks, last[None]], axis=0)
    return out.T  # (B, max_new_tokens)


def beam_search(
    model: GPTLM,
    variables: dict,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    num_beams: int = 4,
) -> tuple[jax.Array, jax.Array]:
    """Beam-search decoding with the KV cache — fully jittable, static
    shapes (beams ride the batch dim; each step reorders the cache rows by
    beam parent with a batched take).

    Returns (ids (B, max_new_tokens), scores (B,)) for the best beam per
    input, scores being exact sequence log-probs. All beams decode exactly
    max_new_tokens tokens (no EOS), so no length penalty is offered — with
    equal lengths it could never change the winner. Unpadded prompts, as
    in generate()."""
    b, prompt_len = prompt_ids.shape
    k = num_beams
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt_len + max_new_tokens > model.cfg.max_len:
        raise ValueError(
            f"prompt {prompt_len} + {max_new_tokens} new tokens exceeds "
            f"max_len {model.cfg.max_len}"
        )

    # prefill ONCE per input, then expand the cache to (B*K) rows — the
    # K beams of an input are identical until the first top-k, so running
    # K prompt copies through the model would waste (K-1)/K of the prefill
    logits, cache = model.apply(
        variables, prompt_ids, decode=True, mutable=["cache"]
    )
    cache = jax.tree.map(
        lambda a: jnp.repeat(a, k, axis=0) if a.ndim and a.shape[0] == b
        else a,
        cache,
    )
    log_p = jnp.repeat(
        jax.nn.log_softmax(logits[:, -1].astype(jnp.float32)), k, axis=0
    )                                                          # (B*K, V)
    # all beams of an input start identical, so all but beam 0 get -inf
    # initial score (else top-k picks K copies of the same continuation)
    vocab = log_p.shape[-1]
    init_mask = jnp.where(jnp.arange(k) == 0, 0.0, -jnp.inf)   # (K,)
    scores = jnp.tile(init_mask, (b,))                         # (B*K,)

    def step(carry, _):
        cache, scores, tok_prev = carry
        logits, cache = model.apply(
            {**variables, **cache}, tok_prev[:, None], decode=True,
            mutable=["cache"],
        )
        log_p = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32))
        total = scores[:, None] + log_p                        # (B*K, V)
        joint = total.reshape(b, k * vocab)
        top_scores, top_idx = jax.lax.top_k(joint, k)          # (B, K)
        parent = top_idx // vocab                              # beam index
        tok = (top_idx % vocab).astype(jnp.int32)              # (B, K)
        # flat row index of each new beam's parent
        rows = (jnp.arange(b)[:, None] * k + parent).reshape(b * k)
        cache = jax.tree.map(
            lambda a: jnp.take(a, rows, axis=0) if a.ndim and
            a.shape[0] == b * k else a,
            cache,
        )
        return (cache, top_scores.reshape(b * k),
                tok.reshape(b * k)), (tok.reshape(b * k), rows)

    # first real step consumes the prefill logits: fold it into the scan by
    # seeding tok_prev from the prefill distribution
    total0 = scores[:, None] + log_p
    joint0 = total0.reshape(b, k * vocab)
    s0, i0 = jax.lax.top_k(joint0, k)
    parent0 = (jnp.arange(b)[:, None] * k + i0 // vocab).reshape(b * k)
    tok0 = (i0 % vocab).astype(jnp.int32).reshape(b * k)
    cache = jax.tree.map(
        lambda a: jnp.take(a, parent0, axis=0) if a.ndim and
        a.shape[0] == b * k else a,
        cache,
    )
    (cache, scores, last), (toks, parents) = jax.lax.scan(
        step, (cache, s0.reshape(b * k), tok0), None,
        length=max_new_tokens - 1,
    )
    # backtrack: walk parent pointers from the best final beam
    all_toks = jnp.concatenate([tok0[None], toks], axis=0)     # (T, B*K)
    all_parents = jnp.concatenate(
        [jnp.arange(b * k)[None], parents], axis=0
    )                                                          # (T, B*K)
    best = jnp.argmax(scores.reshape(b, k), axis=-1)           # (B,)
    row = jnp.arange(b) * k + best

    def back(row, t_arr):
        seq = jnp.zeros((all_toks.shape[0],), jnp.int32)

        def body(i, carry):
            row, seq = carry
            t = all_toks.shape[0] - 1 - i
            seq = seq.at[t].set(t_arr[t, row])
            row = all_parents[t, row]
            return (row, seq)

        _, seq = jax.lax.fori_loop(0, all_toks.shape[0], body, (row, seq))
        return seq

    out = jax.vmap(lambda r: back(r, all_toks))(row)           # (B, T)
    return out, jnp.take(scores, row)


def causal_lm_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Next-token cross entropy; labels == input_ids (the shift happens
    here), pad labels (0) are masked out of the mean."""
    import optax

    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        shift_logits, shift_labels
    )
    w = (shift_labels != 0).astype(jnp.float32)
    return (per_tok * w).sum() / jnp.maximum(w.sum(), 1.0)


def causal_lm_eval_metrics(logits: jax.Array, labels: jax.Array):
    """Per-example (next-token loss, next-token accuracy) — the eval twin of
    causal_lm_loss, shifted the same way so eval measures what training
    optimizes (Trainer eval_metrics_fn contract)."""
    import optax

    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        shift_logits, shift_labels
    )
    w = (shift_labels != 0).astype(jnp.float32)
    denom = jnp.maximum(w.sum(-1), 1.0)
    per_ex = (per_tok * w).sum(-1) / denom
    acc = (
        ((jnp.argmax(shift_logits, -1) == shift_labels) * w).sum(-1) / denom
    )
    return per_ex, acc
