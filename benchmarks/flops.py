"""Operations that the algorithm needs, from shapes alone. Kept here, where no
later PR can change them; `tests/benchmarks` holds the hand counts."""

from __future__ import annotations


def block_matmul_params(layers: int, hidden: int, intermediate: int) -> int:
    """Weights of the matrix products in `layers` transformer blocks: query, key, value
    and output projections (4 h^2) and the two MLP matrices (2 h i). Biases, LayerNorms
    and embedding tables multiply nothing."""
    return layers * (4 * hidden * hidden + 2 * hidden * intermediate)


def train_flop_per_token(matmul_params: int, layers: int, hidden: int, seq_len: int) -> int:
    """Forward and backward, nothing recomputed: 6 a matrix weight (2 forward, 4 backward)
    and 12 x layers x hidden x sequence for the two attention products (PaLM, appendix B).
    The attention term is counted in full for a causal model too, as that convention
    does: what a causal kernel must do is half of it, so an MFU from this is an upper
    reading by at most that half term's share."""
    return 6 * matmul_params + 12 * layers * hidden * seq_len
