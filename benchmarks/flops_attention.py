"""Operations of the attention kernels, from shapes alone: beside `flops.py`, which no
later PR changes; `tests/benchmarks` holds the hand counts."""

from __future__ import annotations


def flash_fwd_flop(batch: int, heads: int, seq_len: int, head_dim: int) -> int:
    """One forward call of the flash kernel: the score product and the context product,
    2 x seq x seq x head_dim each for every head of every row. Counted in full for the
    causal kernel too, the convention `flops.train_flop_per_token` states: a kernel
    that skips the masked half does half of this, so a share of the peak computed from
    it is an upper reading by at most that factor of two."""
    return 4 * batch * heads * seq_len * seq_len * head_dim
