"""The one generator of inputs. A traffic mix is a data file under `traffic/`; what it may
say is what these functions read. Everything is drawn from the seed with numpy on the
host: the same seed gives the same bytes, another seed gives others."""

from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def train_pool(mix: dict, vocab_size: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(x, y) for `pool_batches` steps of `batch` rows of `seq_len` ids in [1, vocab).

    `causal_lm`: a noisy affine chain (next = (31 tok + 17) mod (V - 1) + 1, replaced by a
    uniform draw with probability `chain_noise`), every position a real token; y is x.
    `classification`: each of `num_classes` classes has its own skewed unigram law
    (logits N(0, 1.5)); a row holds between seq_len / 2 and seq_len tokens of its class
    and zeros (padding) after them; y is the class."""
    n, length = int(mix["pool_batches"]) * int(mix["batch"]), int(mix["seq_len"])
    rng = _rng(seed, 1)
    if mix["task"] == "causal_lm":
        x = np.empty((n, length), np.int64)
        x[:, 0] = rng.integers(1, vocab_size, size=n)
        flip = rng.random((n, length)) < float(mix["chain_noise"])
        fresh = rng.integers(1, vocab_size, size=(n, length))
        for t in range(1, length):
            x[:, t] = np.where(flip[:, t], fresh[:, t], (x[:, t - 1] * 31 + 17) % (vocab_size - 1) + 1)
        x = x.astype(np.int32)
        return x, x.copy()
    if mix["task"] == "classification":
        classes = int(mix["num_classes"])
        logits = rng.normal(0.0, 1.5, size=(classes, vocab_size - 1))
        cdf = np.cumsum(np.exp(logits) / np.exp(logits).sum(-1, keepdims=True), axis=-1)
        y = rng.integers(0, classes, size=n).astype(np.int32)
        tokens = np.empty((n, length), np.int32)
        for c in range(classes):
            rows = np.flatnonzero(y == c)
            draws = np.searchsorted(cdf[c], rng.random((rows.size, length)))
            tokens[rows] = np.minimum(draws, vocab_size - 2) + 1
        lengths = rng.integers(length // 2, length + 1, size=n)
        x = np.where(np.arange(length)[None, :] < lengths[:, None], tokens, 0).astype(np.int32)
        return x, y
    raise ValueError(f"traffic task {mix['task']!r} is not causal_lm or classification")
