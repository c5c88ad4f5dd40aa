"""Operations and bytes of what a Mamba-2 mixer adds (the chunked state-space-duality scan
of `model_type: granitemoehybrid`), from the configuration's shapes alone: what any
implementation of the scan has to compute and move, not what one implementation happens to
keep. Beside them the family's matrix weights a token. `tests/benchmarks` holds the hand
counts."""

from __future__ import annotations

import math


def _chunks(seq_len: int, chunk: int) -> tuple[int, int]:
    q = min(chunk, seq_len)
    return q, math.ceil(seq_len / q)


def scan_fwd_flop(batch: int, seq_len: int, heads: int, head_dim: int, state: int,
                  groups: int, chunk: int) -> int:
    """One layer's forward scan in chunks of Q: `C B^T` over the visible pairs of each chunk
    (`2 G c Q (Q + 1) / 2 N`), the products within a chunk over the same pairs (`2 H c Q (Q +
    1) / 2 P`), each chunk's state and every position's read of the carried state (`4 H L N
    P`), with `c` the chunks of a row."""
    q, c = _chunks(seq_len, chunk)
    pairs = c * q * (q + 1) // 2
    return batch * (2 * groups * pairs * state + 2 * heads * pairs * head_dim
                    + 4 * heads * seq_len * state * head_dim)


def scan_bwd_flop(*shape) -> int:
    """The backward scan: twice the forward's products. No recomputed forward: the reader
    adds one where the trace shows it."""
    return 2 * scan_fwd_flop(*shape)


def _tensors_bytes(batch, seq_len, heads, head_dim, state, groups, itemsize) -> int:
    """x and y at the compute dtype, the steps in float32, B and C at the compute dtype."""
    return batch * seq_len * (2 * heads * head_dim * itemsize + heads * 4
                              + 2 * groups * state * itemsize)


def _states_bytes(batch, seq_len, heads, head_dim, state, chunk) -> int:
    """Each chunk's float32 state, `(H, P, N)`."""
    return batch * _chunks(seq_len, chunk)[1] * heads * head_dim * state * 4


def scan_fwd_bytes(batch: int, seq_len: int, heads: int, head_dim: int, state: int,
                   groups: int, chunk: int, itemsize: int = 2) -> int:
    """The least one layer's forward scan moves: x, the steps, B and C read, y written, and
    the chunks' float32 states, once."""
    return (_tensors_bytes(batch, seq_len, heads, head_dim, state, groups, itemsize)
            + _states_bytes(batch, seq_len, heads, head_dim, state, chunk))


def scan_bwd_bytes(batch: int, seq_len: int, heads: int, head_dim: int, state: int,
                   groups: int, chunk: int, itemsize: int = 2) -> int:
    """The backward scan reads x, the steps, B, C and y's cotangent, writes the cotangents of
    x, the steps, B and C, and reads the chunks' states, once."""
    return (2 * _tensors_bytes(batch, seq_len, heads, head_dim, state, groups, itemsize)
            + _states_bytes(batch, seq_len, heads, head_dim, state, chunk))


def scan_shape(cfg: dict, mix: dict) -> tuple[int, int, int, int, int, int, int]:
    """The arguments of the functions above for a configuration and a mix."""
    return (int(mix["batch"]), int(mix["seq_len"]), cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_chunk_size"])


def mixer_params(cfg: dict) -> int:
    """Matrix weights of one Mamba-2 mixer: `in_proj` to z, xBC and the steps, `out_proj`.
    The convolution's taps are a product of no matrix."""
    h, heads, hp = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = heads * hp
    return h * (2 * inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] + heads) + inner * h


def attention_params(cfg: dict) -> int:
    h, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    return 2 * h * heads * d + 2 * h * kv * d


def matmul_params_per_token(cfg: dict) -> int:
    """Matrix weights one token multiplies in a forward pass: each layer's mixer or attention
    by `layer_types`, every layer's SwiGLU (`shared_intermediate_size`), and the tied head.
    The embedding's lookup multiplies nothing."""
    kinds = cfg["layer_types"]
    mlp = 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]
    return (kinds.count("mamba") * mixer_params(cfg) + kinds.count("attention") * attention_params(cfg)
            + len(kinds) * mlp + cfg["hidden_size"] * cfg["vocab_size"])
