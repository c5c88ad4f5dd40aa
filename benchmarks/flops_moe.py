"""Operations and bytes of what the AFMoE family adds, from shapes alone: causal and
windowed attention with the masks taken off, the grouped expert products, the family's
matrix weights a token. Beside `flops.py` and `flops_attention.py`, which no later PR
changes; `tests/benchmarks` holds the hand counts."""

from __future__ import annotations


def visible_pairs(seq_len: int, window: int = 0) -> int:
    """(query, key) pairs a causal row of `seq_len` positions computes: position i sees
    j <= i and, under a window, i - j < window. 0 is no window."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_fwd_flop(batch: int, heads: int, head_dim: int, pairs: int) -> int:
    """One layer's forward core over `pairs` visible pairs a row: the score product and
    the context product, 2 x head_dim each a pair and head. Masked pairs count for
    nothing, unlike `flops_attention.flash_fwd_flop`: at 8,192 positions the whole
    square is 4.6 times what a window of 2,048 needs."""
    return 4 * batch * heads * head_dim * pairs


def attention_bwd_flop(batch: int, heads: int, head_dim: int, pairs: int) -> int:
    """One layer's backward core: the scores again from the saved row statistics, and the
    four products of dV, dP, dQ and dK: five products of 2 x head_dim a pair and head."""
    return 10 * batch * heads * head_dim * pairs


def swiglu_params(hidden: int, width: int) -> int:
    """Gate, up and down of one SwiGLU."""
    return 3 * hidden * width


def matmul_params_per_token(cfg: dict) -> int:
    """Matrix weights one token multiplies in a forward pass of the configuration as the
    file cuts it: the five attention projections of every layer (query, key, value, gate,
    output), the leading dense layers' SwiGLU, and in every expert layer the router, the
    shared expert and the routed experts THIS share computes for it at the balanced load
    (`num_experts_per_tok` x experts held / the router's width), and the head. The
    embedding's lookup multiplies nothing. The load is assumed balanced: `moe_rows_here`
    in the step's metrics says how far a run was from it."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    attention = h * d * (3 * heads + 2 * groups)
    expert = swiglu_params(h, cfg["moe_intermediate_size"])
    # the routed experts a token's choices reach here, in weights: k x held / width experts
    routed = cfg["num_experts_per_tok"] * cfg["num_experts"] * expert // cfg["router_width"]
    moe = h * cfg["router_width"] + cfg["num_shared_experts"] * expert + routed
    return (layers * attention + dense * swiglu_params(h, cfg["intermediate_size"])
            + (layers - dense) * moe + h * cfg["vocab_size"])


def grouped_rows(tokens: int, cfg: dict) -> int:
    """Rows one expert layer's grouped products compute here at the balanced load."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] // cfg["router_width"]


def expert_products_flop(rows: float, hidden: int, width: int, passes: int) -> float:
    """The three grouped products of a layer (gate, up, down) over `rows` rows: 2 x hidden
    x width each a row. `passes` is in forward passes: 1 the forward, 2 the backward (a
    product by the transposed weights and one for the weights' gradient, each product)."""
    return passes * 3 * 2 * rows * hidden * width


def expert_products_bytes(rows: float, experts: int, hidden: int, width: int, passes: int,
                          itemsize: int = 2) -> int:
    """The least the three grouped products move, in `itemsize`-byte elements: each reads
    its rows and its experts' weights and writes its result, once a pass. Gate and up
    share their input rows but are two products, so it is read twice."""
    gate_up = 2 * (rows * hidden + experts * hidden * width + rows * width)
    down = rows * width + experts * width * hidden + rows * hidden
    return passes * (gate_up + down) * itemsize
