"""The plain float32 forward, loss and first training step of the SDAR-MoE decoder
(`model_type: sdar_moe`; SDAR, arXiv:2510.06303, trained by the objective of Block
Diffusion, arXiv:2503.09573) that decide `correct` for its cells: straight `jax.numpy`, no
kernels, no sorting, no grouped product, every matrix product at `highest` precision.
Nothing is imported from `kubeflow_tpu`: `families/sdar_moe.py` hands over the program's
parameters as the flat dict used here, and the step's noise as data, like the weights.

A row of `L` data tokens `x0` is cut into blocks of `B`. The noise of a step is, a
position, whether it is masked and its weight (`masked / t`, `t` its block's rate); the
noisy row is `xt = MASK where masked else x0`. The model runs on `2 L` positions, **the
clean row first, its noisy copy after it**, as the program lays them out. With
`n(x; g) = x / sqrt(mean(x^2) + eps) * g` and `blk(i) = (i mod L) // B`:

- `x = Emb[ids]` (no scale);
- attention: `a = n(x; g1)`; `q, k, v` its three projections, no bias; `q = n(q; gq)`,
  `k = n(k; gk)` over the head size, one gain vector each; `q` and `k` rotated
  (rotate-half pairs `(i, i + d/2)`, theta 1e6) by the position `i mod L`, so a token and
  its noisy copy share one; query head `j` reads key/value head `j // (H/G)`; query `i`
  sees key `j` iff
      i noisy, j noisy:  blk(j) == blk(i)
      i noisy, j clean:  blk(j) <  blk(i)
      i clean, j clean:  blk(j) <= blk(i)
      i clean, j noisy:  never;
  `x = x + softmax(q k^T / sqrt(d) + M) v Wo`;
- experts: `b = n(x; g2)`; `p = softmax(b Wr)` over all the router's experts, `S` the `k`
  largest, `w_e = p_e / sum over S of p`; `x = x + sum over e in S and held here of w_e *
  Wdown_e(silu(Wgate_e b) * Wup_e b)`: the share of the layer that holds the experts
  `experts_held`, the absent experts' part left out; no shared expert, no bias;
- `logits = n(x; gf) Whead` at the `L` noisy positions; the loss of a row is `(1/L) sum_i
  weight_i * CE(logits_i, x0_i)`, no shift.

So that 8,192 positions fit beside a training state, attention runs a block of queries at
a time and the loss a block of rows at a time, and each block, expert and layer is under
`jax.checkpoint`; the arithmetic is the same. `first_update` is the first step of
training: the gradient of the mean loss and one step of plain Adam from zero moments."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotate(x, positions, theta: float):
    """x (P, heads, d): the pair (i, i + d/2) turned by positions[p] * theta^(-2i/d)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def visible(rows, cols, half: int, block: int):
    """Whether query `rows` sees key `cols` (broadcast): the four cases of the module's
    text, positions [0, half) clean and [half, 2 half) noisy."""
    q_noisy, k_noisy = rows >= half, cols >= half
    q_blk, k_blk = (rows % half) // block, (cols % half) // block
    return jnp.where(q_noisy,
                     jnp.where(k_noisy, k_blk == q_blk, k_blk < q_blk),
                     ~k_noisy & (k_blk <= q_blk))


def attention(a, p, spec: dict, query_block: int):
    """One row and its noisy copy: a (2L, h) -> (2L, h)."""
    n, half = a.shape[0], a.shape[0] // 2
    heads, groups, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    q = _mm(a, p["wq"].reshape(a.shape[1], heads * d)).reshape(n, heads, d)
    k = _mm(a, p["wk"].reshape(a.shape[1], groups * d)).reshape(n, groups, d)
    v = _mm(a, p["wv"].reshape(a.shape[1], groups * d)).reshape(n, groups, d)
    q, k = rms_norm(q, p["gq"], spec["eps"]), rms_norm(k, p["gk"], spec["eps"])
    positions = jnp.arange(n) % half
    q, k = rotate(q, positions, spec["theta"]), rotate(k, positions, spec["theta"])
    k, v = jnp.repeat(k, heads // groups, axis=1), jnp.repeat(v, heads // groups, axis=1)
    block = min(query_block, n)
    if n % block:
        raise ValueError(f"{n} positions are no multiple of the query block {block}")
    cols = jnp.arange(n)[None, :]

    def some_queries(start):
        seen = visible(start + jnp.arange(block)[:, None], cols, half, spec["block_length"])
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    o = jax.lax.map(jax.checkpoint(some_queries), jnp.arange(0, n, block))
    return _mm(o.reshape(n, heads * d), p["wo"].reshape(heads * d, a.shape[1]))


def route(b, router, top_k: int, chosen=None):
    """(T, E) weights: w_e for the chosen experts of each token and 0 elsewhere. `chosen`
    (T, K) takes the place of the router's own choice of experts (a test's way to compare
    the arithmetic apart from near-ties in the choice); the weights follow the sets given."""
    probs = jax.nn.softmax(_mm(b, router), axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(probs, top_k)
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    w = picked / picked.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(b.shape[0])[:, None], chosen].set(w)


def expert_layer(b, p, spec: dict, chosen=None):
    """b (T, h) -> (T, h): this share's part of the routed sum, over the experts
    `spec["experts_held"]` = [lo, hi) whose weights `p` holds."""
    lo, hi = spec["experts_held"]
    weights = route(b, p["router"], spec["top_k"], chosen)

    @jax.checkpoint
    def add_expert(acc, e):
        y = swiglu(b, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        w = jax.lax.dynamic_index_in_dim(weights, lo + e, axis=1, keepdims=True)
        return acc + w * y, None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(b), jnp.arange(hi - lo))
    return out


def decoder_layer(x, p, spec: dict, query_block: int):
    """One layer of one row and its noisy copy: x (2L, h) -> (2L, h)."""
    x = x + attention(rms_norm(x, p["g1"], spec["eps"]), p, spec, query_block)
    return x + expert_layer(rms_norm(x, p["g2"], spec["eps"]), p, spec)


def hidden_states(params: dict, ids, spec: dict, query_block: int = 512):
    """ids (B, 2L), a clean row then its noisy copy -> (B, L, h): the final norm's output
    at the noisy positions, what the head multiplies."""
    def one_row(row):
        x = params["emb"][row]
        for p in params["layers"]:
            x = jax.checkpoint(lambda x, p: decoder_layer(x, p, spec, query_block))(x, p)
        return rms_norm(x[row.shape[0] // 2:], params["gf"], spec["eps"])

    return jnp.stack([one_row(row) for row in ids])


def logits(params: dict, ids, spec: dict, query_block: int = 512):
    return _mm(hidden_states(params, ids, spec, query_block), params["head"])


def noisy_ids(x0, masked, mask_id: int):
    """(B, 2L): the clean row, then the row with its masked positions replaced."""
    return jnp.concatenate([x0, jnp.where(masked, mask_id, x0)], axis=1)


def diffusion_loss_sums(params: dict, x0, masked, weights, spec: dict, query_block: int = 512,
                        row_block: int = 1024):
    """(sum over rows and positions of weight x cross-entropy of the noisy position's
    logits against its own token, the number of positions B x L): their quotient is the
    batch's loss. `masked` and `weights` (B, L) are the step's noise; the logits are made
    `row_block` positions at a time."""
    hidden = hidden_states(params, noisy_ids(x0, masked, spec["mask_id"]), spec, query_block)
    b, n, h = hidden.shape
    block = min(row_block, n)
    if n % block:
        raise ValueError(f"{n} positions are no multiple of the row block {block}")

    @jax.checkpoint
    def some_rows(args):
        x, y, w = args
        logp = jax.nn.log_softmax(_mm(x, params["head"]), axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        return (nll * w).sum()

    by_block = lambda t: t.reshape(b, -1, block, *t.shape[2:]).swapaxes(0, 1)  # noqa: E731
    total = jax.lax.map(some_rows, (by_block(hidden), by_block(x0),
                                    by_block(weights.astype(jnp.float32))))
    return total.sum(), jnp.float32(b * n)


def first_update(params: dict, x0, masked, weights, spec: dict, learning_rate: float,
                 b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 query_block: int = 128, row_block: int = 512):
    """(summed loss, its divisor, the parameters after the first step): one step of Adam
    from zero moments on the gradient of the batch's loss. The blocks are narrower than
    the loss alone takes: the gradient holds a block's scores several times over."""
    def mean_loss(p):
        total, weight = diffusion_loss_sums(p, x0, masked, weights, spec, query_block, row_block)
        return total / weight, (total, weight)

    def adam(p, g):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        return p - learning_rate * (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)

    grads, (total, weight) = jax.grad(mean_loss, has_aux=True)(params)
    return total, weight, jax.tree.map(adam, params, grads)
