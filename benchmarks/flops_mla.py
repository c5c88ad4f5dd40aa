"""Operations and bytes of what latent attention (MLA, `model_type: deepseek_v2`) adds,
from shapes alone: a causal attention core whose queries and keys are wider than its values
and output, with the mask taken off, and the family's matrix weights a token. Beside
`flops_moe.py`, whose expert counts it uses; `tests/benchmarks` holds the hand counts."""

from __future__ import annotations

from benchmarks import flops_moe


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs a causal row of `seq_len` positions computes: `L (L + 1) / 2`."""
    return flops_moe.visible_pairs(seq_len)


def attention_fwd_flop(batch: int, heads: int, qk_dim: int, v_dim: int, pairs: int) -> int:
    """One layer's forward core over `pairs` visible pairs a row: the score product, 2 x
    `qk_dim`, and the context product, 2 x `v_dim`, a pair and head."""
    return (2 * qk_dim + 2 * v_dim) * batch * heads * pairs


def attention_bwd_flop(batch: int, heads: int, qk_dim: int, v_dim: int, pairs: int) -> int:
    """One layer's backward core: the scores again from the saved row statistic, dQ and dK
    (three products of 2 x `qk_dim`), dV and dP (two of 2 x `v_dim`) a pair and head. No
    recomputed forward: the reader adds one where the trace shows it."""
    return 2 * (3 * qk_dim + 2 * v_dim) * batch * heads * pairs


def attention_fwd_bytes(batch: int, heads: int, qk_dim: int, v_dim: int, seq_len: int,
                        itemsize: int = 2) -> int:
    """The least one layer's forward core moves: it reads q and k at `qk_dim`, v at `v_dim`,
    and writes the output at `v_dim` and a float32 row statistic, once."""
    positions = batch * seq_len * heads
    return positions * ((2 * qk_dim + 2 * v_dim) * itemsize + 4)


def attention_bwd_bytes(batch: int, heads: int, qk_dim: int, v_dim: int, seq_len: int,
                        itemsize: int = 2) -> int:
    """The backward core reads q, k (at `qk_dim`), v, the output, its cotangent (at `v_dim`)
    and the statistic, and writes dq, dk (at `qk_dim`) and dv (at `v_dim`), once."""
    positions = batch * seq_len * heads
    return positions * ((4 * qk_dim + 4 * v_dim) * itemsize + 4)


def attention_params(cfg: dict) -> int:
    """Matrix weights of one layer's attention: the query projection, down to the latent
    and the shared rotary key, up to every head's key and value, and the output."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv, rank = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                             "v_head_dim", "kv_lora_rank"))
    return (h * heads * (nope + rope) + h * (rank + rope) + rank * heads * (nope + dv)
            + heads * dv * h)


def matmul_params_per_token(cfg: dict) -> int:
    """Matrix weights one token multiplies in a forward pass of the configuration as the
    file cuts it: every layer's attention, the leading dense layers' SwiGLU, in every
    expert layer the router, the shared experts and the routed experts THIS share computes
    for it at the balanced load (`num_experts_per_tok` x experts held / the router's
    width), and the head. The embedding's lookup multiplies nothing."""
    h = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    expert = flops_moe.swiglu_params(h, cfg["moe_intermediate_size"])
    routed = cfg["num_experts_per_tok"] * cfg["num_experts"] * expert // cfg["router_width"]
    moe = h * cfg["router_width"] + cfg["num_shared_experts"] * expert + routed
    return (layers * attention_params(cfg) + dense * flops_moe.swiglu_params(h, cfg["intermediate_size"])
            + (layers - dense) * moe + h * cfg["vocab_size"])
