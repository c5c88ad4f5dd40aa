"""The plain float32 forward of the DeepSeek-V2 decoder (`model_type: deepseek_v2`,
arXiv:2405.04434; the Lite model's form, without query compression) that decides `correct`
for its cells: straight `jax.numpy`, no kernels, no sorting, no grouped product, every
matrix product at `highest` precision. Nothing is imported from `kubeflow_tpu`:
`families/deepseek_v2.py` hands over the program's parameters as the flat dict used here.
What it computes, with `n(x; g) = x / sqrt(mean(x^2) + eps) * g`, for one row of `L` tokens:

- `x = Emb[ids]` (no scale);
- attention: `a = n(x; g1)`; `q = a Wq`, a head `[q_nope (nope) | q_pe (rope)]`; `ckv = a
  Wdkv`, split `[c (rank) | k_pe (rope)]`: ONE `k_pe` a position, shared by the heads; `c =
  n(c; gc)`; `kv = c Wukv`, a head `[k_nope (nope) | v (dv)]`; `q_pe` and `k_pe` are rotated
  (pairs `(i, i + rope/2)`) by YaRN's frequencies, computed below from the configuration's
  six numbers; `q = [q_nope | q_pe]`, `k = [k_nope | k_pe repeated over the heads]`; `y =
  softmax(q k^T s + causal) v Wo` with `s = (nope + rope)^-0.5 m^2`, `m = 0.1 mscale_all_dim
  ln(factor) + 1`;
- block: `x = x + y`; `b = n(x; g2)`; `x = x + f(b)`;
- `f` dense: `(silu(b Wgate) * (b Wup)) Wdown`; `f` of an expert layer: `p = softmax(b Wr)`
  over all the router's experts, `S` the `k` largest, `w_e = scale * p_e` (NOT renormalised
  over `S`), `f = shared(b) + sum over e in S and held here of w_e * expert_e(b)`: the share
  of the layer that holds the experts `experts_held`, the absent experts' part left out;
- the layer's balance term: `sum_e f_e P_e` with `f_e = count_e E / (k L)` (`count_e` of the
  row's `k L` choices fell on expert `e`; no gradient) and `P_e = mean_t p_{t,e}`;
- `logits = n(x; gf) Whead`; the loss is the mean next-token cross entropy over labels
  that are not 0, plus `alpha` x the mean over rows of the balance terms summed over the
  expert layers.

So that 8,192 positions fit beside a training state, attention runs a block of queries at
a time and the loss a block of rows at a time, and each block, expert and layer is under
`jax.checkpoint`; the arithmetic is the same. `first_update` is the first step of training
for the runner kind that compares the parameters' change (`kinds/train_job_update.py`):
the gradient of the loss above and one step of plain Adam from zero moments."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(spec: dict):
    """The `rope / 2` rotary frequencies: `f_i = theta^(-2i/rope)`; `corr(r) = rope
    ln(original / (2 pi r)) / (2 ln theta)`; `low = floor(corr(beta_fast))`, `high =
    ceil(corr(beta_slow))`; `ramp_i = clip((i - low) / (high - low), 0, 1)`; `f_i (1 -
    ramp_i) + f_i / factor * ramp_i`."""
    rope, theta, ys = spec["rope"], spec["theta"], spec["yarn"]

    def corr(turns):
        return rope * math.log(ys["original_max_position_embeddings"] / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), rope - 1)
    i = jnp.arange(rope // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / rope)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / ys["factor"] * ramp


def softmax_scale(spec: dict) -> float:
    ys = spec["yarn"]
    return (spec["nope"] + spec["rope"]) ** -0.5 * yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2


def rotate(x, spec: dict):
    """x (L, heads, rope): the pair (i, i + rope/2) turned by position * inv_freq_i, cos and
    sin times `m(mscale) / m(mscale_all_dim)`."""
    ys = spec["yarn"]
    gain = yarn_mscale(ys["factor"], ys["mscale"]) / yarn_mscale(ys["factor"], ys["mscale_all_dim"])
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * yarn_inv_freq(spec)
    cos, sin = gain * jnp.cos(angle)[:, None, :], gain * jnp.sin(angle)[:, None, :]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def attention(a, p, spec: dict, query_block: int):
    """One row: a (L, h) -> (L, h)."""
    length, hidden = a.shape
    heads, nope, rope, dv, rank = (spec[k] for k in ("num_heads", "nope", "rope", "dv", "rank"))
    q = _mm(a, p["wq"].reshape(hidden, heads * (nope + rope))).reshape(length, heads, nope + rope)
    ckv = _mm(a, p["wdkv"])
    c, k_pe = rms_norm(ckv[:, :rank], p["gc"], spec["eps"]), ckv[:, rank:]
    kv = _mm(c, p["wukv"].reshape(rank, heads * (nope + dv))).reshape(length, heads, nope + dv)
    k_pe = rotate(k_pe[:, None, :], spec)                       # (L, 1, rope): one a position
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], spec)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.tile(k_pe, (1, heads, 1))], axis=-1)
    v = kv[..., nope:]
    block = min(query_block, length)
    if length % block:
        raise ValueError(f"{length} positions are no multiple of the query block {block}")
    cols = jnp.arange(length)[None, :]

    def some_queries(start):
        rows = start + jnp.arange(block)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * softmax_scale(spec)
        probs = jax.nn.softmax(jnp.where((cols <= rows)[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    o = jax.lax.map(jax.checkpoint(some_queries), jnp.arange(0, length, block))
    return _mm(o.reshape(length, heads * dv), p["wo"].reshape(heads * dv, hidden))


def route(b, router, top_k: int, scale: float, chosen=None):
    """(p (T, E) the softmax over all experts, w (T, E): `scale * p_e` for the chosen experts
    of each token and 0 elsewhere, chosen (T, K)). `chosen` given takes the place of the
    router's own choice (a test's way to compare the arithmetic apart from near-ties)."""
    p = jax.nn.softmax(_mm(b, router), axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(p, top_k)
    w = jnp.zeros_like(p).at[jnp.arange(b.shape[0])[:, None], chosen].set(
        scale * jnp.take_along_axis(p, chosen, axis=-1))
    return p, w, chosen


def balance_term(p, chosen):
    """One row's `sum_e f_e P_e`: p (L, E), chosen (L, K)."""
    length, experts = p.shape
    count = (chosen.reshape(-1, 1) == jnp.arange(experts)).sum(0)
    f = jax.lax.stop_gradient(count.astype(jnp.float32)) * experts / chosen.size
    return (f * p.mean(0)).sum()


def expert_layer(b, p, spec: dict, chosen=None):
    """b (L, h), one row -> ((L, h), the row's balance term): the shared experts (one SwiGLU
    of their widths together) and this share's part of the routed sum, over the experts
    `spec["experts_held"]` = [lo, hi) whose weights `p` holds."""
    lo, hi = spec["experts_held"]
    probs, weights, chosen = route(b, p["router"], spec["top_k"], spec["route_scale"], chosen)
    out = swiglu(b, p["shared_gate"], p["shared_up"], p["shared_down"])

    @jax.checkpoint
    def add_expert(acc, e):
        y = swiglu(b, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        w = jax.lax.dynamic_index_in_dim(weights, lo + e, axis=1, keepdims=True)
        return acc + w * y, None

    out, _ = jax.lax.scan(add_expert, out, jnp.arange(hi - lo))
    return out, balance_term(probs, chosen)


def decoder_layer(x, p, spec: dict, query_block: int):
    """One layer of one row: x (L, h) -> (x, its balance term, 0 for a dense layer)."""
    x = x + attention(rms_norm(x, p["g1"], spec["eps"]), p, spec, query_block)
    b = rms_norm(x, p["g2"], spec["eps"])
    if "router" in p:
        f, aux = expert_layer(b, p, spec)
    else:
        f, aux = swiglu(b, p["w_gate"], p["w_up"], p["w_down"]), jnp.float32(0.0)
    return x + f, aux


def hidden_states(params: dict, ids, spec: dict, query_block: int = 512):
    """ids (B, L) -> ((B, L, h): the final norm's output, what the head multiplies; the mean
    over rows of the balance terms summed over the layers)."""

    def one_row(row):
        x, aux = params["emb"][row], jnp.float32(0.0)
        for p in params["layers"]:
            x, a = jax.checkpoint(lambda x, p: decoder_layer(x, p, spec, query_block))(x, p)
            aux = aux + a
        return rms_norm(x, params["gf"], spec["eps"]), aux

    rows = [one_row(row) for row in ids]
    return jnp.stack([h for h, _ in rows]), sum(a for _, a in rows) / len(rows)


def logits(params: dict, ids, spec: dict, query_block: int = 512):
    return _mm(hidden_states(params, ids, spec, query_block)[0], params["head"])


def causal_lm_loss_sums(params: dict, ids, labels, spec: dict, query_block: int = 512,
                        row_block: int = 1024):
    """(total, weight): `weight` the number of labels that are not 0, `total / weight` the
    loss the step reports: the mean cross entropy of position t's logits against `labels[t
    + 1]` over those labels plus `spec["balance_loss"]` x the balance term; the logits
    made `row_block` positions at a time."""
    hidden, aux = hidden_states(params, ids, spec, query_block)
    hidden = hidden[:, :-1]
    labels = labels[:, 1:]
    b, n, h = hidden.shape
    block = min(row_block, n)
    pad = -n % block
    hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, block, h)
    labels = jnp.pad(labels, ((0, 0), (0, pad))).reshape(b, -1, block)

    @jax.checkpoint
    def some_rows(args):
        x, y = args
        logp = jax.nn.log_softmax(_mm(x, params["head"]), axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        w = (y != 0).astype(jnp.float32)
        return (nll * w).sum(), w.sum()

    total, weight = jax.lax.map(some_rows, (hidden.swapaxes(0, 1), labels.swapaxes(0, 1)))
    weight = weight.sum()
    return total.sum() + spec["balance_loss"] * aux * weight, weight


def first_update(params: dict, ids, labels, spec: dict, learning_rate: float,
                 b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 query_block: int = 128, row_block: int = 512):
    """(total, weight, the parameters after the first step): one step of Adam from zero
    moments on the gradient of `total / weight`. The blocks are narrower than the loss alone
    takes: the gradient holds a block's scores several times over."""
    def mean_loss(p):
        total, weight = causal_lm_loss_sums(p, ids, labels, spec, query_block, row_block)
        return total / weight, (total, weight)

    def adam(p, g):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        return p - learning_rate * (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)

    grads, (total, weight) = jax.grad(mean_loss, has_aux=True)(params)
    return total, weight, jax.tree.map(adam, params, grads)
