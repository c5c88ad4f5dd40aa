"""The plain float32 forward of the AFMoE decoder (`model_type: afmoe`, Arcee's Trinity
family) that decides `correct` for its cells: straight `jax.numpy`, no kernels, no sorting,
no grouped product, every matrix product at `highest` precision. Nothing is imported from
`kubeflow_tpu.models`: `families/afmoe.py` hands over the program's parameters as the flat
dict used here. What it computes, with `n(x; g) = x / sqrt(mean(x^2) + eps) * g`:

- `x = Emb[ids] * sqrt(h)`;
- attention of a layer of kind `layer_types[l]`: `a = n(x; g1)`; `q, k, v, z` its four
  projections; `q = n(q; gq)`, `k = n(k; gk)` over the head size, one gain vector each;
  a `sliding_attention` layer rotates `q` and `k` (rotate-half pairs `(i, i + d/2)`) and
  sees `j <= i` with `i - j < window`; a `full_attention` layer rotates nothing and sees
  `j <= i`; query head `j` reads key/value head `j // (H/G)`; `y = (softmax(q k^T /
  sqrt(d)) v * sigmoid(z)) Wo`;
- block: `x = x + n(y; g2)`; `b = n(x; g3)`; `x = x + n(f(b); g4)`;
- `f` dense: `(silu(b Wgate) * (b Wup)) Wdown`; `f` of an expert layer: `r = sigmoid(b
  Wr)`, `S` the `k` largest of `r + bias`, `w_e = scale * r_e / (sum over S of r + 1e-20)`,
  `f = shared(b) + sum over e in S and held here of w_e * expert_e(b)`: the share of the
  layer that holds the experts `experts_held`, the absent experts' part left out;
- `logits = n(x; gf) Whead`; the loss is the mean next-token cross-entropy over labels
  that are not 0.

So that 8,192 positions fit beside a training state, attention runs a block of queries at
a time and the loss a block of rows at a time, and each block, expert and layer is under
`jax.checkpoint`, so that the gradient keeps a layer's input and no more; the arithmetic
is the same.

`first_update` is the first step of training as the configuration states it, for the
runner kind that compares the parameters' change (`kinds/train_job_update.py`): the
gradient of the loss above, one step of plain Adam from zero moments, and the router's
`bias += rate * sign(mean(c) - c)` over the tokens `c_e` routed to each expert."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotate(x, theta: float):
    """x (L, heads, d): the pair (i, i + d/2) turned by position * theta^(-2i/d)."""
    length, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def attention(a, p, spec: dict, kind: str, query_block: int):
    """One row: a (L, h) -> (L, h)."""
    length = a.shape[0]
    heads, groups, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    q = _mm(a, p["wq"].reshape(a.shape[1], heads * d)).reshape(length, heads, d)
    k = _mm(a, p["wk"].reshape(a.shape[1], groups * d)).reshape(length, groups, d)
    v = _mm(a, p["wv"].reshape(a.shape[1], groups * d)).reshape(length, groups, d)
    z = _mm(a, p["wz"])
    q, k = rms_norm(q, p["gq"], spec["eps"]), rms_norm(k, p["gk"], spec["eps"])
    if kind == SLIDING:
        q, k = rotate(q, spec["theta"]), rotate(k, spec["theta"])
    k, v = jnp.repeat(k, heads // groups, axis=1), jnp.repeat(v, heads // groups, axis=1)
    block = min(query_block, length)
    if length % block:
        raise ValueError(f"{length} positions are no multiple of the query block {block}")
    cols = jnp.arange(length)[None, :]

    def some_queries(start):
        rows = start + jnp.arange(block)[:, None]
        visible = cols <= rows
        if kind == SLIDING:
            visible = visible & (rows - cols < spec["window"])
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    o = jax.lax.map(jax.checkpoint(some_queries), jnp.arange(0, length, block))
    o = o.reshape(length, heads * d)
    return _mm(o * jax.nn.sigmoid(z), p["wo"].reshape(heads * d, a.shape[1]))


def route(b, router, bias, top_k: int, scale: float, chosen=None):
    """(T, E) weights: w_e for the chosen experts of each token and 0 elsewhere. `chosen`
    (T, K) takes the place of the router's own choice of experts (a test's way to compare
    the arithmetic apart from near-ties in the choice); the weights follow the sets given."""
    r = jax.nn.sigmoid(_mm(b, router))
    if chosen is None:
        _, chosen = jax.lax.top_k(r + bias, top_k)
    picked = jnp.take_along_axis(r, chosen, axis=-1)
    w = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(r).at[jnp.arange(b.shape[0])[:, None], chosen].set(w)


def expert_layer(b, p, spec: dict, chosen=None, with_counts: bool = False):
    """b (T, h) -> (T, h): the shared expert and this share's part of the routed sum, over
    the experts `spec["experts_held"]` = [lo, hi) whose weights `p` holds. `with_counts`:
    also the tokens routed to each of the router's experts, (E,)."""
    lo, hi = spec["experts_held"]
    bias = p.get("bias", jnp.zeros((p["router"].shape[1],), jnp.float32))
    weights = route(b, p["router"], bias, spec["top_k"], spec["route_scale"], chosen)
    out = swiglu(b, p["shared_gate"], p["shared_up"], p["shared_down"])

    @jax.checkpoint
    def add_expert(acc, e):
        y = swiglu(b, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        w = jax.lax.dynamic_index_in_dim(weights, lo + e, axis=1, keepdims=True)
        return acc + w * y, None

    out, _ = jax.lax.scan(add_expert, out, jnp.arange(hi - lo))
    # a chosen expert's weight is scale * sigmoid / sum: never 0
    return (out, (weights > 0).sum(0)) if with_counts else out


def decoder_layer(x, p, spec: dict, kind: str, query_block: int):
    """One layer of one row: x (L, h) -> (x, the tokens routed to each expert or None)."""
    eps = spec["eps"]
    y = attention(rms_norm(x, p["g1"], eps), p, spec, kind, query_block)
    x = x + rms_norm(y, p["g2"], eps)
    b = rms_norm(x, p["g3"], eps)
    if "router" in p:
        f, counts = expert_layer(b, p, spec, with_counts=True)
    else:
        f, counts = swiglu(b, p["w_gate"], p["w_up"], p["w_down"]), None
    return x + rms_norm(f, p["g4"], eps), counts


def hidden_states(params: dict, ids, spec: dict, query_block: int = 512,
                  with_counts: bool = False):
    """ids (B, L) -> (B, L, h): the final norm's output, what the head multiplies.
    `with_counts`: also, a layer, the tokens of all rows routed to each expert (None for a
    dense layer)."""

    def one_row(row):
        x = params["emb"][row] * jnp.sqrt(jnp.float32(params["emb"].shape[1]))
        counts = []
        for kind, p in zip(spec["layer_types"], params["layers"]):
            x, c = jax.checkpoint(lambda x, p, kind=kind: decoder_layer(x, p, spec, kind, query_block))(x, p)
            counts.append(c)
        return rms_norm(x, params["gf"], spec["eps"]), counts

    rows = [one_row(row) for row in ids]
    hidden = jnp.stack([h for h, _ in rows])
    if not with_counts:
        return hidden
    return hidden, [None if c[0] is None else sum(c) for c in zip(*(c for _, c in rows))]


def logits(params: dict, ids, spec: dict, query_block: int = 512):
    return _mm(hidden_states(params, ids, spec, query_block), params["head"])


def causal_lm_loss_sums(params: dict, ids, labels, spec: dict, query_block: int = 512,
                        row_block: int = 1024, with_counts: bool = False):
    """(summed cross-entropy of position t's logits against `labels[t + 1]`, over the
    labels that are not 0, and their number), the logits made `row_block` positions at a
    time; `with_counts` adds `hidden_states`' routing counts."""
    hidden, counts = hidden_states(params, ids, spec, query_block, with_counts=True)
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
    return (total.sum(), weight.sum(), counts) if with_counts else (total.sum(), weight.sum())


def first_update(params: dict, ids, labels, spec: dict, learning_rate: float,
                 bias_rate: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 query_block: int = 128, row_block: int = 512):
    """(summed loss, summed weight, the state after the first step): `params` as
    `causal_lm_loss_sums` takes them, every expert layer with its `bias`. The weights take
    one step of Adam from zero moments on the gradient of the mean loss; a `bias` has no
    gradient and moves by `bias_rate * sign(mean(c) - c)`. The blocks are narrower than
    the loss alone takes: the gradient holds a block's scores several times over (at
    8,192 positions the v5e's compiler counts 3.6 GB of temporaries so, 4.4 GB at 512 and
    1,024)."""
    def mean_loss(p):
        total, weight, counts = causal_lm_loss_sums(p, ids, labels, spec, query_block, row_block,
                                                    with_counts=True)
        return total / weight, (total, weight, counts)

    def adam(p, g):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        return p - learning_rate * (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)

    grads, (total, weight, counts) = jax.grad(mean_loss, has_aux=True)(params)
    after = jax.tree.map(adam, params, grads)
    for layer, before, c in zip(after["layers"], params["layers"], counts):
        if c is not None:
            c = c.astype(jnp.float32)
            layer["bias"] = before["bias"] + bias_rate * jnp.sign(c.mean() - c)
    return total, weight, after
