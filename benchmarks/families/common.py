"""What the family files share: the program's flax transformer block (the same names in
`models/gpt.py` and `models/bert.py`) as the flat dict `reference.py` takes."""

from __future__ import annotations

import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a, jnp.float32)


def blocks(tree, hidden: int) -> list[dict]:
    """`layer_0`, `layer_1`, ... of `tree`: head-split projection kernels folded back to
    (hidden, hidden), LayerNorms as gain and bias."""
    out = []
    while f"layer_{len(out)}" in tree:
        b = tree[f"layer_{len(out)}"]
        a = b["attention"]
        block = {"wo": f32(a["attn_out"]["kernel"]).reshape(hidden, hidden), "bo": f32(a["attn_out"]["bias"])}
        for ours, theirs in (("q", "query"), ("k", "key"), ("v", "value")):
            block[f"w{ours}"] = f32(a[theirs]["kernel"]).reshape(hidden, hidden)
            block[f"b{ours}"] = f32(a[theirs]["bias"]).reshape(hidden)
        for ours, theirs in (("ln1", "ln_attn"), ("ln2", "ln_mlp")):
            block[f"{ours}_g"], block[f"{ours}_b"] = f32(b[theirs]["scale"]), f32(b[theirs]["bias"])
        for ours, theirs in (("up", "mlp_up"), ("down", "mlp_down")):
            block[f"w_{ours}"], block[f"b_{ours}"] = f32(b[theirs]["kernel"]), f32(b[theirs]["bias"])
        out.append(block)
    return out
