"""Plain float32 forwards that decide `correct`: GPT-2 (Radford et al. 2019, the
`openai-community/gpt2*` layout) and the BERT encoder with its pooled classifier (Devlin et
al. 2018, `bert-base-uncased`). Straight `jax.numpy`, no kernels, no cache, no batching
tricks, matrix products at `highest` precision (on a TPU a float32 product is otherwise
rounded to bf16). Nothing is imported from `kubeflow_tpu.models`: the family files under
`benchmarks/families/` turn the program's parameter tree into the flat dicts used here.

Departures from the published models, each because the program under test departs so and
the comparison is of arithmetic, not of a checkpoint:
- token id 0 is padding in both programs: its keys are masked and, in the LM loss, its
  labels carry no weight (the benchmark's traffic draws ids from 1 up, so GPT-2 sees none);
- `gelu` takes `approximate`: GPT-2 publishes the tanh form; BERT publishes the erf form,
  the program computes the tanh form, and the BERT family file says which it asks for.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def attention(x, p, heads: int, visible):
    """Multi-head softmax attention. `visible` is (B, 1, Lq, Lk) booleans. Weights are
    (H, H) matrices applied on the right, biases (H,)."""
    b, l, h = x.shape
    d = h // heads
    split = lambda t: t.reshape(b, l, heads, d).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = (split(_mm(x, p[w]) + p[c]) for w, c in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(visible, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)
    return _mm(out.transpose(0, 2, 1, 3).reshape(b, l, h), p["wo"]) + p["bo"]


def mlp(x, p, approximate: bool):
    return _mm(jax.nn.gelu(_mm(x, p["w_up"]) + p["b_up"], approximate=approximate),
               p["w_down"]) + p["b_down"]


def gpt2_logits(params: dict, ids, heads: int, eps: float):
    """ids (B, L) int -> (B, L, V) float32 logits. Pre-LN blocks, learned positions, tanh
    GELU, final LayerNorm, head tied to the token embedding."""
    b, l = ids.shape
    x = params["wte"][ids] + params["wpe"][jnp.arange(l)][None]
    causal = jnp.tril(jnp.ones((l, l), bool))[None, None]
    visible = causal & (ids != 0)[:, None, None, :]
    for p in params["blocks"]:
        x = x + attention(layer_norm(x, p["ln1_g"], p["ln1_b"], eps), p, heads, visible)
        x = x + mlp(layer_norm(x, p["ln2_g"], p["ln2_b"], eps), p, approximate=True)
    x = layer_norm(x, params["lnf_g"], params["lnf_b"], eps)
    return _mm(x, params["wte"].T)


def causal_lm_loss(logits, ids):
    """Mean next-token cross-entropy over the positions whose label is not padding."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    labels = ids[:, 1:]
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    w = (labels != 0).astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)


def bert_classifier_logits(params: dict, ids, heads: int, eps: float, approximate: bool):
    """ids (B, L) int -> (B, classes) float32. Post-LN blocks, token + position + type-0
    embeddings under a LayerNorm, tanh pooler over position 0, linear classifier."""
    _, l = ids.shape
    x = params["wte"][ids] + params["wpe"][jnp.arange(l)][None] + params["wtt"][0]
    x = layer_norm(x, params["lne_g"], params["lne_b"], eps)
    visible = (ids != 0)[:, None, None, :]
    for p in params["blocks"]:
        x = layer_norm(x + attention(x, p, heads, visible), p["ln1_g"], p["ln1_b"], eps)
        x = layer_norm(x + mlp(x, p, approximate), p["ln2_g"], p["ln2_b"], eps)
    pooled = jnp.tanh(_mm(x[:, 0], params["w_pool"]) + params["b_pool"])
    return _mm(pooled, params["w_cls"]) + params["b_cls"]


def classification_loss(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
