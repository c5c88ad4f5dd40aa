"""The plain float32 forward of the Granite 4.0-H decoder (`model_type: granitemoehybrid`,
dense: no experts) that decides `correct` for its cells: straight `jax.numpy`, no kernels,
every matrix product at `highest` precision. Nothing is imported from `kubeflow_tpu`:
`families/granite_hybrid.py` hands over the program's parameters as the flat dict used here.
What it computes, with `n(x; g) = x / sqrt(mean(x^2) + eps) * g`, for one row of `L` tokens:

- `x = 12 Emb[ids]` (`embedding_multiplier`);
- a block: `x = x + 0.22 mixer(n(x; g1))`, then `x = x + 0.22 (silu(b Wg) * (b Wu)) Wd` with
  `b = n(x; g2)` (`residual_multiplier`; the family's `input_linear` is `[Wg | Wu]`, gate
  first);
- a Mamba-2 mixer: `[z | xBC | d] = a Win`; `xBC = silu(conv(xBC) + cb)`, a depthwise
  causal convolution of width K (`lax.conv_general_dilated`, one group a channel, K - 1
  zeros before the row); `[x | B | C] = xBC`; `D_t = softplus(d_t + dt_bias)`, `A =
  -exp(A_log)`; `S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T`, `S_0 = 0`; `y_t = S_t C_t + D
  x_t`; `out = n(y * silu(z); gm) Wo` over each group's part of `y` (one group of all of
  it here);
- an attention layer: `q` of `heads`, `k` and `v` of `kv_heads` heads, each key/value head
  read by `heads / kv_heads` query heads; no positions; `softmax(q k^T s + causal) v Wo`
  with `s = attention_multiplier`;
- `logits = n(x; gf) Emb^T / 8` (tied, `logits_scaling`); the loss is the mean next-token
  cross entropy over labels that are not 0.

The recurrence is computed chunk by chunk (`ssd`): within a chunk from the segment sums of
`D_t A` (a masked cumulative sum, not a difference of running sums), across chunks by the
state carried from one to the next; `spec["carry_state"]` false drops that state, the
fault a chunked scan can make on its own. `ssd_by_position` is the recurrence as written,
for the tests. So that 8,192 positions fit beside a training state, each chunk, each
layer, attention's query blocks, the MLP's and the loss's row blocks are under
`jax.checkpoint`; the arithmetic is the same. `first_update` is the first step of training
for the runner kind that compares the parameters' change (`kinds/train_job_update.py`):
the gradient of the loss above (`first_gradient`) and one step of plain Adam from zero
moments (`adam_first_step`).

Departures from the family's modelling code: none in the arithmetic as written above; the
gated norm's group is the family's `n_groups` (`mamba_n_groups`, 1); the convolution's bias
is added before the activation as there."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def causal_conv(x, w, b):
    """x (L, C), w (C, K), b (C,): each channel convolved with its own K taps, position t
    from t - K + 1 .. t, zeros before the row."""
    k = w.shape[-1]
    y = jax.lax.conv_general_dilated(
        x[None], w.T[:, None, :], window_strides=(1,), padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=x.shape[-1],
        precision=HIGHEST)
    return y[0] + b


def segment_sums(a):
    """a (..., Q) -> (..., Q, Q): `sum of a[s+1 .. t]` at (t, s) for s <= t, -inf above."""
    q = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], (*a.shape, q))                   # [t, s] = a[t]
    below = jnp.tril(jnp.ones((q, q), bool), -1)
    sums = jnp.cumsum(jnp.where(below, rep, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((q, q), bool)), sums, -jnp.inf)


def ssd(x, dt, a, b, c, chunk: int, carry_state: bool = True):
    """One row: x (L, H, P), dt (L, H) the steps after softplus, a (H,) negative, b and c
    (L, G, N) -> y (L, H, P) without the `D x` term, chunk by chunk."""
    length, heads, hp = x.shape
    groups, n = b.shape[1:]
    q = min(chunk, length)
    if length % q:
        raise ValueError(f"{length} positions are no multiple of the chunk {q}")
    rep = heads // groups
    b, c = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)           # (L, H, N)
    split = lambda v: v.reshape(length // q, q, *v.shape[1:])               # noqa: E731

    @jax.checkpoint
    def one_chunk(state, inputs):
        xq, dtq, bq, cq = inputs
        da = (dtq * a).T                                                     # (H, Q)
        decay = jnp.exp(segment_sums(da))                                    # (H, Q, Q)
        scores = _einsum("thn,shn->hts", cq, bq) * decay * dtq.T[:, None, :]
        y = _einsum("hts,shp->thp", scores, xq)
        cum = jnp.cumsum(da, axis=-1)                                        # (H, Q)
        y = y + _einsum("thn,hpn->thp", cq, state) * jnp.exp(cum).T[..., None]
        to_end = jnp.exp(cum[:, -1:] - cum) * dtq.T                          # (H, Q)
        own = _einsum("shp,shn->hpn", xq * to_end.T[..., None], bq)
        kept = jnp.exp(cum[:, -1])[:, None, None] * state if carry_state else 0.0
        return kept + own, y

    state0 = jnp.zeros((heads, hp, n), jnp.float32)
    _, y = jax.lax.scan(one_chunk, state0, (split(x), split(dt), split(b), split(c)))
    return y.reshape(length, heads, hp)


def ssd_by_position(x, dt, a, b, c):
    """The recurrence position by position: `S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T`,
    `y_t = S_t C_t`."""
    heads, hp = x.shape[1:]
    rep = heads // b.shape[1]
    b, c = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)

    def step(state, inputs):
        xt, dtt, bt, ct = inputs
        state = jnp.exp(dtt * a)[:, None, None] * state + (dtt[:, None] * xt)[..., None] * bt[:, None, :]
        return state, _einsum("hpn,hn->hp", state, ct)

    state0 = jnp.zeros((heads, hp, b.shape[-1]), jnp.float32)
    return jax.lax.scan(step, state0, (x, dt, b, c))[1]


def mamba(u, p, spec: dict):
    """One row: u (L, h) -> (L, h)."""
    length = u.shape[0]
    heads, hp, n, groups = spec["mamba_heads"], spec["mamba_head_dim"], spec["state"], spec["groups"]
    inner = heads * hp
    zxbcdt = _mm(u, p["w_in"])
    z, xbc, d = zxbcdt[:, :inner], zxbcdt[:, inner:-heads], zxbcdt[:, -heads:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[:, :inner].reshape(length, heads, hp)
    b = xbc[:, inner:inner + groups * n].reshape(length, groups, n)
    c = xbc[:, inner + groups * n:].reshape(length, groups, n)
    dt = jax.nn.softplus(d + p["dt_bias"])
    y = ssd(x, dt, -jnp.exp(p["a_log"]), b, c, spec["chunk"], spec.get("carry_state", True))
    y = (y + x * p["d"][:, None]).reshape(length, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(length, groups, -1), 1.0, spec["eps"]).reshape(length, inner) * p["g_m"]
    return _mm(y, p["w_o"])


def attention(a, p, spec: dict, query_block: int):
    """One row: a (L, h) -> (L, h)."""
    length, hidden = a.shape
    heads, kv, d = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = _mm(a, p["wq"].reshape(hidden, heads * d)).reshape(length, heads, d)
    k = jnp.repeat(_mm(a, p["wk"].reshape(hidden, kv * d)).reshape(length, kv, d), heads // kv, axis=1)
    v = jnp.repeat(_mm(a, p["wv"].reshape(hidden, kv * d)).reshape(length, kv, d), heads // kv, axis=1)
    block = min(query_block, length)
    if length % block:
        raise ValueError(f"{length} positions are no multiple of the query block {block}")
    cols = jnp.arange(length)[None, :]

    def some_queries(start):
        rows = start + jnp.arange(block)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = _einsum("qhd,khd->hqk", qb, k) * spec["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where((cols <= rows)[None], scores, -jnp.inf), axis=-1)
        return _einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(jax.checkpoint(some_queries), jnp.arange(0, length, block))
    return _mm(o.reshape(length, heads * d), p["wo"].reshape(heads * d, hidden))


def mlp(b, p, row_block: int):
    """One row's SwiGLU, `row_block` positions at a time."""
    length, hidden = b.shape
    block = min(row_block, length)
    if length % block:
        raise ValueError(f"{length} positions are no multiple of the row block {block}")
    out = jax.lax.map(jax.checkpoint(lambda r: swiglu(r, p["w_gate"], p["w_up"], p["w_down"])),
                      b.reshape(length // block, block, hidden))
    return out.reshape(length, hidden)


def decoder_layer(x, p, spec: dict, query_block: int, row_block: int):
    """One layer of one row: x (L, h) -> x. The kind is the parameters' own: a mixer has
    `w_in`, an attention layer `wq`."""
    a = rms_norm(x, p["g1"], spec["eps"])
    y = mamba(a, p, spec) if "w_in" in p else attention(a, p, spec, query_block)
    x = x + spec["residual_multiplier"] * y
    return x + spec["residual_multiplier"] * mlp(rms_norm(x, p["g2"], spec["eps"]), p, row_block)


def hidden_states(params: dict, ids, spec: dict, query_block: int = 512, row_block: int = 2048):
    """ids (B, L) -> (B, L, h): the final norm's output, what the tied head multiplies."""

    def one_row(row):
        x = spec["embedding_multiplier"] * params["emb"][row]
        for p in params["layers"]:
            x = jax.checkpoint(lambda x, p: decoder_layer(x, p, spec, query_block, row_block))(x, p)
        return rms_norm(x, params["gf"], spec["eps"])

    return jnp.stack([one_row(row) for row in ids])


def logits(params: dict, ids, spec: dict, query_block: int = 512):
    return _mm(hidden_states(params, ids, spec, query_block), params["emb"].T) / spec["logits_scaling"]


def causal_lm_loss_sums(params: dict, ids, labels, spec: dict, query_block: int = 512,
                        row_block: int = 1024):
    """(total, weight): `weight` the number of labels that are not 0, `total / weight` the
    mean cross entropy of position t's logits against `labels[t + 1]` over those labels;
    the logits made `row_block` positions at a time."""
    hidden = hidden_states(params, ids, spec, query_block, row_block)[:, :-1]
    labels = labels[:, 1:]
    b, n, h = hidden.shape
    block = min(row_block, n)
    pad = -n % block
    hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, block, h)
    labels = jnp.pad(labels, ((0, 0), (0, pad))).reshape(b, -1, block)

    @jax.checkpoint
    def some_rows(args):
        x, y = args
        logp = jax.nn.log_softmax(_mm(x, params["emb"].T) / spec["logits_scaling"], axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        w = (y != 0).astype(jnp.float32)
        return (nll * w).sum(), w.sum()

    total, weight = jax.lax.map(some_rows, (hidden.swapaxes(0, 1), labels.swapaxes(0, 1)))
    return total.sum(), weight.sum()


def first_gradient(params: dict, ids, labels, spec: dict, query_block: int = 256,
                   row_block: int = 512):
    """(total, weight, the gradient of `total / weight`). The blocks are narrower than the
    loss alone takes: the gradient holds a block's scores several times over."""
    def mean_loss(p):
        total, weight = causal_lm_loss_sums(p, ids, labels, spec, query_block, row_block)
        return total / weight, (total, weight)

    grads, (total, weight) = jax.grad(mean_loss, has_aux=True)(params)
    return total, weight, grads


def adam_first_step(p, g, learning_rate: float, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8):
    """One step of plain Adam from zero moments, on jax or numpy arrays alike."""
    m, v = (1 - b1) * g, (1 - b2) * g * g
    return p - learning_rate * (m / (1 - b1)) / ((v / (1 - b2)) ** 0.5 + eps)


def first_update(params: dict, ids, labels, spec: dict, learning_rate: float, **blocks):
    """(total, weight, the parameters after the first step): `adam_first_step` on the
    gradient of `first_gradient`."""
    total, weight, grads = first_gradient(params, ids, labels, spec, **blocks)
    return total, weight, jax.tree.map(lambda p, g: adam_first_step(p, g, learning_rate),
                                       params, grads)
