"""Attention masks as one rule over (row, column).

A mask says which keys (columns) a query (row) sees. The flash kernels
(`parallel/ring_attention.py`) ask three things of it, and all three come
from the mask's *bands*:

  (a) inside a tile the mask crosses, which elements are hidden;
  (b) for the forward, which runs of KV tiles a query tile walks, and which
      of those tiles no row of it hides anything of (no mask is built there);
  (c) for the backward, which (query block, KV block) pairs have a visible
      element at all: the live pairs.

A band is a run of columns of which row `i` sees `[start(i), stop(i))`, with
`start` and `stop` non-decreasing in `i` over the rows of one tile and the
intervals of consecutive rows touching or overlapping. Then the rows
`[row0, last]` of a tile see, between them, exactly the columns
`[start(row0), stop(last))` of the band, and every one of them sees
`[start(last), stop(row0))`: (b) and (c) are arithmetic on the two ends, with
no square ever built. A row's visible set is the union of its bands'
intervals; `hidden` is the same rule element by element, written the cheapest
way for the kernels (the tests hold the two against a brute-force square).

`Causal(window)` is the one band `[max(i - window + 1, 0), i + 1)`.
`BlockDiffusion(half, block)` is the mask of training by diffusion over
blocks (arXiv:2503.09573): `2 * half` positions, the clean copy of a row of
`half` tokens first and its noisy copy after it, cut into blocks of `block`.
With `blk(i) = (i mod half) // block`: a clean query sees the clean keys of
its own and of every earlier block; a noisy query sees the clean keys of every
earlier block and the noisy keys of its own block; nobody sees another
block's noise. Two bands, the clean columns and the noisy ones; a tile must not
straddle the halves (`period`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax.numpy as jnp
import numpy as np


class Band(NamedTuple):
    start: Callable | None  # row -> first visible column; None: column 0
    stop: Callable          # row -> one past the last visible column
    hidden: Callable        # (rows, cols) -> bool, for columns of this band


@dataclass(frozen=True)
class Causal:
    """Row i sees column c iff c <= i and, with a window, i - c < window."""

    window: int = 0
    tag = ""  # the kernels' names say nothing more for it than they did

    def period(self, length: int) -> int:
        return length

    def bands(self, xp) -> tuple[Band, ...]:
        w = self.window
        start = (lambda i: xp.maximum(i - (w - 1), 0)) if w else None
        return (Band(start, lambda i: i + 1, self.hidden),)

    def hidden(self, rows, cols):
        masked = cols > rows
        if self.window:
            masked = masked | (rows - cols >= self.window)
        return masked


@dataclass(frozen=True)
class BlockDiffusion:
    """Clean copy in positions [0, half), noisy copy in [half, 2 * half),
    blocks of `block` (see the module's text)."""

    half: int
    block: int
    tag = "blockdiff"

    def __post_init__(self):
        if self.block < 1 or self.half % self.block:
            raise ValueError(f"blocks of {self.block} do not tile {self.half} positions")

    def period(self, length: int) -> int:
        if length != 2 * self.half:
            raise ValueError(f"{length} positions are not two copies of {self.half}")
        return self.half

    def _blk(self, x):
        b = self.block  # a shift where it can be one: Mosaic has no vector division
        return x >> (b.bit_length() - 1) if b & (b - 1) == 0 else x // b

    def bands(self, xp) -> tuple[Band, ...]:
        half, b, blk = self.half, self.block, self._blk
        per_half = half // b

        def last_clean_block(rows):  # of the clean keys a row sees; -1: none
            return xp.where(rows >= half, blk(rows) - (per_half + 1), blk(rows))

        clean = Band(None, lambda i: (last_clean_block(i) + 1) * b,
                     lambda rows, cols: blk(cols) > last_clean_block(rows))
        noisy = Band(lambda i: xp.where(i >= half, blk(i) * b, half),
                     lambda i: xp.where(i >= half, (blk(i) + 1) * b, half),
                     lambda rows, cols: blk(cols) != blk(rows))
        return clean, noisy

    def hidden(self, rows, cols):
        xp = np if isinstance(rows, np.ndarray) else jnp
        clean, noisy = self.bands(xp)
        return xp.where(cols >= self.half, noisy.hidden(rows, cols), clean.hidden(rows, cols))


def mask_of(causal: bool, window: int = 0):
    """The pair (`causal`, `window`) that `flash_attention`'s callers always
    gave, as a mask: `Causal(window)`, or None (every key) when not causal."""
    if window and not causal:
        raise ValueError("attention window requires causal=True")
    return Causal(window) if causal else None


def causal_window(mask) -> tuple[bool, int]:
    """The other way, for the code that knows those two masks only (the
    blockwise fallback, the pallas flash backwards)."""
    if isinstance(mask, BlockDiffusion):
        raise NotImplementedError(
            "the blockwise fallback and the pallas flash backwards know the "
            "causal and window masks only")
    return mask is not None, mask.window if mask else 0


def name_suffix(mask) -> str:
    """What a kernel's name says of its mask beyond what it always said: the
    tag of a mask that is not causal, after an underscore."""
    return f"_{mask.tag}" if mask is not None and mask.tag else ""


def kv_runs(mask, row0, block_q: int, block_k: int, n_kv, xp):
    """For the query tile of `block_q` rows from `row0`: a band at a time, the
    KV tiles `[lo, hi)` it can see and within them `[lo_full, hi_full)` that
    no row hides anything of. `lo` and `lo_full` are the integer 0 where the
    band starts at column 0."""
    last = row0 + block_q - 1
    runs = []
    for band in mask.bands(xp):
        hi = xp.minimum((band.stop(last) + block_k - 1) // block_k, n_kv)
        if band.start is None:
            lo = lo_full = 0
        else:
            lo = xp.minimum(band.start(row0) // block_k, hi)
            lo_full = xp.clip((band.start(last) + block_k - 1) // block_k, lo, hi)
        hi_full = xp.clip(band.stop(row0) // block_k, lo_full, hi)
        runs.append((lo, lo_full, hi_full, hi))
    return runs


def live_tile_pairs(mask, lq: int, lk: int, block_q: int, block_k: int) -> np.ndarray:
    """(lq // block_q, lk // block_k) bools: the tile has a visible element."""
    row0 = np.arange(lq // block_q) * block_q
    col0 = np.arange(lk // block_k)[None, :] * block_k
    live = np.zeros((row0.size, col0.size), bool)
    for band in mask.bands(np):
        lo = (band.start(row0) if band.start else np.zeros_like(row0))[:, None]
        hi = band.stop(row0 + block_q - 1)[:, None]
        live |= (lo < hi) & (lo < col0 + block_k) & (col0 < hi)
    return live
