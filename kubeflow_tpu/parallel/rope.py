"""Rotary position embedding — shared by the GPT family and the
context-parallel attention paths (which must rotate by GLOBAL position
inside their shard regions; see ring/ulysses in ring_attention.py) — and
YaRN's rescaling of its frequencies (arXiv:2309.00071), which the latent
attention of `models/deepseek_v2.py` rotates its 64 rotary dimensions by."""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def apply_rope(x, pos, theta: float = 10000.0, freqs=None):
    """Rotary position embedding (half-split convention): rotate each
    head-dim pair (i, i + D/2) of the array it is given by pos * freqs[i]
    (a caller that rotates part of a head slices that part). `freqs` (D/2,)
    are the pairs' frequencies, None: theta^(-2i/D). x: (B, L, H, D); pos:
    (L,) shared across the batch, or (B, L) per-row (continuous-batching
    decode, where in-flight rows sit at different depths)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * freqs  # (..., L, D/2)
    if ang.ndim == 2:                                 # shared (L, D/2)
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]                 # (B|1, L, 1, D/2)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max_position: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> np.ndarray:
    """The dim/2 rotary frequencies under YaRN, float32, for `apply_rope`'s
    `freqs`: pair i's own `theta^(-2i/dim)` where it turns more than
    `beta_fast` times within the original context (kept), that over `factor`
    where it turns less than `beta_slow` times (interpolated), and a linear
    blend over the pairs between. `corr(r) = dim ln(original / (2 pi r)) /
    (2 ln theta)` is the pair that turns r times; the blend runs from
    floor(corr(beta_fast)) to ceil(corr(beta_slow)), clipped to the pairs
    there are."""
    pairs = np.arange(dim // 2, dtype=np.float64)
    own = theta ** (-2.0 * pairs / dim)

    def corr(turns: float) -> float:
        return dim * math.log(original_max_position / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (own * (1.0 - ramp) + own / factor * ramp).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature `0.1 mscale ln(factor) + 1` (1 where the
    context is not extended): the softmax scale carries `yarn_mscale(factor,
    mscale_all_dim) ** 2`. Cos and sin would carry `yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)`, 1 in every configuration there is:
    `apply_rope` has no such factor, and `DeepseekV2Config` refuses two that differ."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0
