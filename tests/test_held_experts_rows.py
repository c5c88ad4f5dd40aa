"""`HeldExpertsMlp`'s per-row work follows the rows in use (parallel/moe.py): at tiny
widths on the CPU, the layer against the whole-bound formulation kept here as a plain
`jax.numpy` function (every (token, choice) pair gathered, multiplied and combined,
gradients by autodiff: the arithmetic of the layer before its loops) and against the
float32 reference (benchmarks/reference_afmoe.py), at fills from no row to every row;
rows no one wrote poisoned with NaN; and the counter of the rows walked."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_afmoe
from kubeflow_tpu.parallel import moe
from kubeflow_tpu.parallel.moe import ROUTER_STATE, HeldExpertsMlp, route_sigmoid, router_counters

H, M, E, K, SCALE = 32, 16, 16, 4, 2.0
TOKENS = 24                      # one row of 24: tokens x K = 96 pairs
CHUNK = 16                       # what the tests make of moe.ROW_CHUNK: 96 rows are six chunks
SHARE = (4, 8)                   # a share that holds four of the sixteen experts
BALANCED = TOKENS * K * (SHARE[1] - SHARE[0]) // E


def _layer(held, k=K):
    return HeldExpertsMlp(hidden_size=H, expert_dim=M, num_experts=E, top_k=k,
                          experts_held=held, route_scale=SCALE)


def _routed(rows_wanted: int, held, seed: int = 0, k=K):
    """x (1, TOKENS, H), the layer's parameters and a selection bias of zero, with a router
    made so that exactly `rows_wanted` of the (token, choice) pairs fall on experts of
    `held`: the tokens are fewer than the hidden size, so a router that gives any wanted
    (TOKENS, E) matrix of logits exists (least squares), and the logits wanted put some of
    the held experts far above the rest and the others far below, token by token."""
    lo, hi = held
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, TOKENS, H))
    variables = _layer(held, k).init(jax.random.PRNGKey(seed + 1), x)
    params = dict(variables["params"])
    per_token = min(k, hi - lo)
    assert 0 <= rows_wanted <= TOKENS * per_token
    want = np.full((TOKENS,), rows_wanted // TOKENS)
    want[:rows_wanted % TOKENS] += 1          # how many of its K choices token t has here
    logits = np.array(jax.random.uniform(jax.random.PRNGKey(seed + 2), (TOKENS, E), minval=-1.0, maxval=1.0))
    if hi - lo < E:
        for t in range(TOKENS):
            logits[t, lo:hi] -= 6.0
            logits[t, lo:lo + want[t]] += 12.0
    router, *_ = np.linalg.lstsq(np.asarray(x[0], np.float64), logits.astype(np.float64), rcond=None)
    params["router"] = jnp.asarray(router, jnp.float32)
    return x, params, variables[ROUTER_STATE]


def whole_bound_layer(params, x, bias, held):
    """The layer with everything per row done over all tokens x K rows, in plain
    `jax.numpy`: full gathers in both directions, every row through its expert's three
    products (an absent expert's rows through any, masked after), the weighted sum over a
    token's choices; its gradients are autodiff's."""
    lo, hi = held
    xt = x.reshape(-1, H)
    idx, weights, _ = route_sigmoid(xt, params["router"], bias, K, SCALE)
    flat = idx.reshape(-1)
    here = (flat >= lo) & (flat < hi)
    local = jnp.where(here, flat - lo, hi - lo)
    order = jnp.argsort(local, stable=True)
    inverse = jnp.argsort(order)
    rows = xt[order // K]
    expert = jnp.minimum(local[order], hi - lo - 1)
    gate = jnp.einsum("rh,rhm->rm", rows, params["w_gate"][expert])
    up = jnp.einsum("rh,rhm->rm", rows, params["w_up"][expert])
    y = jnp.einsum("rm,rmh->rh", jax.nn.silu(gate) * up, params["w_down"][expert])
    here = here.reshape(-1, K)
    y = jnp.where(here[..., None], y[inverse].reshape(-1, K, H), 0.0)
    out = (y * jnp.where(here, weights, 0.0)[..., None]).sum(1)
    shared = (jax.nn.silu(xt @ params["shared_gate"]["kernel"]) * (xt @ params["shared_up"]["kernel"])
              ) @ params["shared_down"]["kernel"]
    return (out + shared).reshape(x.shape)


def float32_reference(params, x, bias, held):
    p = {n: params[n] for n in ("router", "w_gate", "w_up", "w_down")}
    p.update({n: params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")}, bias=bias)
    spec = {"top_k": K, "route_scale": SCALE, "experts_held": held}
    return reference_afmoe.expert_layer(x.reshape(-1, H), p, spec).reshape(x.shape)


def _value_and_grads(fn, params, x):
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    return jax.jit(jax.value_and_grad(lambda p, x: (fn(p, x) * cot).sum(), argnums=(0, 1)))(params, x)


def _program(held, state, k=K):
    def fn(p, x):
        return _layer(held, k).apply({"params": p, ROUTER_STATE: state}, x)
    return fn


def _assert_close(got, want, what):
    for (path, g), (_, w) in zip(jax.tree.leaves_with_path(got), jax.tree.leaves_with_path(want)):
        scale = float(jnp.abs(w).max()) + 1e-6
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale, (what, jax.tree_util.keystr(path))


FILLS = {"none": 0, "one": 1, "chunk-1": CHUNK - 1, "chunk": CHUNK, "chunk+1": CHUNK + 1,
         "balanced": BALANCED, "every-pair": TOKENS * K}


@pytest.mark.parametrize("small_source_rows", [None, 40])
@pytest.mark.parametrize("held,fill,chunk", [
    *[(SHARE, fill, CHUNK) for fill in FILLS],
    # all experts held: every pair is a row in use, whatever the chunk: one that divides
    # the pairs, one that does not (the buffers round up to 100 rows), the module's own
    # (one chunk, mostly past the pairs), and one row a chunk
    (None, "every-pair", CHUNK), (None, "every-pair", 20), (None, "every-pair", None),
    (None, "every-pair", 1), (SHARE, "balanced", 20), (SHARE, "chunk+1", None),
])
def test_output_and_gradients_at_any_fill_are_the_whole_bounds(monkeypatch, held, fill, chunk,
                                                             small_source_rows):
    """`small_source_rows`: the token-order sums take their rows from a prefix of that
    many where the rows in use fit it (40 rows: the fills up to 24 do, 96 does not);
    None leaves the module's size, which holds every buffer of these widths whole."""
    if chunk:
        monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    if small_source_rows:
        monkeypatch.setattr(moe, "SMALL_SOURCE_BYTES", small_source_rows * H * 4)
    range_held = held or (0, E)
    x, params, state = _routed(FILLS[fill], range_held)
    out, new = _layer(held).apply({"params": params, ROUTER_STATE: state}, x, train=True,
                                  mutable=[ROUTER_STATE])
    assert int(new[ROUTER_STATE]["rows_here"]) == FILLS[fill]
    got = _value_and_grads(_program(held, state), params, x)
    whole = _value_and_grads(lambda p, x: whole_bound_layer(p, x, state["bias"], range_held), params, x)
    plain = _value_and_grads(lambda p, x: float32_reference(p, x, state["bias"], range_held), params, x)
    _assert_close(got, whole, "whole bound")
    _assert_close(got, plain, "float32 reference")
    np.testing.assert_allclose(out, whole_bound_layer(params, x, state["bias"], range_held),
                               atol=2e-5, rtol=2e-5)
    d_params = got[1][0]
    reached = ("router", "w_gate", "w_up", "w_down") if FILLS[fill] else ()
    assert all(float(jnp.abs(d_params[n]).max()) > 0 for n in reached)


@pytest.mark.parametrize("k,rows", [
    *[(K, FILLS[fill]) for fill in ("one", "chunk+1", "balanced")],
    # six choices a token do not fill a tile's sublanes, eight do; 144 and 192 pairs, the
    # share's four experts at most 96 of them: fills below and above the prefix's 40 rows
    *[(k, rows) for k in (6, 8) for rows in (1, CHUNK + 1, 36, 48, 96)],
])
def test_rows_no_one_wrote_may_hold_nan(monkeypatch, k, rows):
    """Every buffer of the layer starts as NaN, and the rows the dispatch gathered past
    the rows in use (the last live chunk's tail) are NaN too: output and every gradient
    stay finite, and are what they were. The NaNs ride through the combine's gradient as
    values of its sorts (the weights' gradient in row order, brought back to pair order),
    which never compare them."""
    monkeypatch.setattr(moe, "ROW_CHUNK", CHUNK)
    monkeypatch.setattr(moe, "SMALL_SOURCE_BYTES", 40 * H * 4)
    x, params, state = _routed(rows, SHARE, k=k)
    want = _value_and_grads(_program(SHARE, state, k), params, x)

    dispatch = moe._dispatch

    def poisoned(xt, order, inverse, held, n_rows):
        rows = dispatch(xt, order, inverse, held, n_rows)
        return jnp.where(jnp.arange(rows.shape[0])[:, None] < n_rows, rows, jnp.nan)

    monkeypatch.setattr(moe, "_unwritten", lambda shape, dtype, after: jnp.full(shape, jnp.nan, dtype))
    monkeypatch.setattr(moe, "_dispatch", poisoned)
    got = _value_and_grads(_program(SHARE, state, k), params, x)
    assert all(bool(jnp.isfinite(a).all()) for a in jax.tree.leaves(got))
    _assert_close(got, want, "poisoned")


@pytest.mark.parametrize("chunk", [CHUNK, 20, None])
def test_the_rows_walked_are_the_rows_in_use_rounded_up_to_chunks(monkeypatch, chunk):
    if chunk:
        monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    chunk = moe.ROW_CHUNK
    rows = [0, 1, chunk - 1, chunk, chunk + 1, 5 * chunk]
    state = {f"layer_{i}": {"moe": {"rows_here": jnp.asarray(r, jnp.int32), "counts": jnp.ones((E,)),
                                    "bias": jnp.zeros((E,))}} for i, r in enumerate(rows)}
    counters = router_counters(state)
    assert float(counters["moe_rows_here"]) == sum(rows)
    assert float(counters["moe_rows_walked"]) == sum(-(-r // chunk) * chunk for r in rows)
    # and from a layer's own state after a step
    x, params, start = _routed(CHUNK + 1, SHARE)
    _, new = _layer(SHARE).apply({"params": params, ROUTER_STATE: start}, x, train=True,
                                 mutable=[ROUTER_STATE])
    walked = float(router_counters({"layer_1": {"moe": new[ROUTER_STATE]}})["moe_rows_walked"])
    assert walked == -(-(CHUNK + 1) // chunk) * chunk
