"""Operations and bytes of what training by diffusion over blocks adds, from shapes alone:
attention under the block-diffusion mask (a clean row of `L` tokens and its noisy copy
side by side, blocks of `B`), with the mask taken off. Beside `flops_moe.py`, whose
per-pair counts it uses; `tests/benchmarks` holds the hand counts."""

from __future__ import annotations

from benchmarks import flops_moe


def visible_pairs(seq_len: int, block: int) -> int:
    """(query, key) pairs of the `2 L` x `2 L` square the mask leaves visible, a row of `L`
    = `seq_len` data tokens: clean on clean, block-causal: `L (L + B) / 2`; noisy on
    earlier clean blocks: `L (L - B) / 2`; noisy on its own noisy block: `L B`; clean on
    noisy: none. `L^2 + L B` of `4 L^2`."""
    if seq_len % block:
        raise ValueError(f"blocks of {block} do not tile {seq_len} tokens")
    return seq_len * (seq_len + block)


def attention_flop(batch: int, heads: int, head_dim: int, seq_len: int, block: int,
                   backward: bool, remat: bool = False) -> int:
    """One layer's attention core over the visible pairs: forward 4 x pairs x heads x
    head size (`flops_moe.attention_fwd_flop`), backward 10 (`attention_bwd_flop`), to
    which `remat` adds the forward it recomputes (timed with the backward)."""
    shape = (batch, heads, head_dim, visible_pairs(seq_len, block))
    if not backward:
        return flops_moe.attention_fwd_flop(*shape)
    return flops_moe.attention_bwd_flop(*shape) + (flops_moe.attention_fwd_flop(*shape) if remat else 0)


def attention_bytes(batch: int, heads: int, head_dim: int, seq_len: int, backward: bool,
                    remat: bool = False, itemsize: int = 2) -> int:
    """The least one layer's attention core moves over its `2 L` positions (keys and
    values already repeated to `heads`, as the program hands them over): the forward reads
    q, k, v and writes the output and a float32 row statistic; the backward reads q, k, v,
    the output, its cotangent and the statistic and writes dq, dk, dv; `remat` adds the
    forward."""
    positions = 2 * seq_len
    tensor = batch * positions * heads * head_dim * itemsize
    stat = batch * positions * heads * 4
    forward = 4 * tensor + stat
    if not backward:
        return forward
    return 8 * tensor + stat + (forward if remat else 0)
