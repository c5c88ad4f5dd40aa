"""`HeldExpertsMlp` indexes nothing pair by pair through single scalars (parallel/moe.py):
the chosen scores are a select over the expert axis, the weights reach row order and their
gradient goes back to pair order as values of a sort, and the sums over a token's choices
gather choice-major. At tiny widths on the CPU, over the three forms the models call the
layer in: the jaxpr of its gradient holds no gather or scatter of as many single elements
as there are tokens, and loss and gradients are those of the formulation it replaced,
written out here (`take_along_axis`, `d_w[inverse]`, `flat[pair]`, the sums token-major):
bit for bit with the sums left token-major, within float32's order of summation as it is."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from kubeflow_tpu.parallel import moe
from kubeflow_tpu.parallel.moe import ROUTER_STATE, HeldExpertsMlp

H, M, TOKENS = 32, 16, 64
CHUNK = 64                       # what the tests make of moe.ROW_CHUNK: tokens x K rows are K chunks

#: the three callers' forms: models/afmoe.py, models/sdar_moe.py, models/deepseek_v2.py
FORMS = {
    "sigmoid-bias-shared-8of128": dict(
        num_experts=128, top_k=8, experts_held=(16, 32), route_scale=2.826),
    "softmax-renormalised-no-shared-8of128": dict(
        num_experts=128, top_k=8, experts_held=(16, 32), score_func="softmax",
        num_shared_experts=0, bias_update_rate=0.0),
    "softmax-raw-balance-shared-6of64": dict(
        num_experts=64, top_k=6, experts_held=(8, 16), score_func="softmax", renormalise=False,
        num_shared_experts=2, bias_update_rate=0.0, balance_loss=0.001),
}


def _setup(form: str, dtype):
    layer = HeldExpertsMlp(hidden_size=H, expert_dim=M, dtype=dtype, **FORMS[form])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, TOKENS, H)).astype(dtype)
    variables = layer.init(jax.random.PRNGKey(1), x)
    state = dict(variables[ROUTER_STATE])
    if layer.bias_update_rate:   # a selection bias that has moved: it chooses, and weighs nothing
        state["bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(2), state["bias"].shape)
    return layer, variables["params"], state, x


def _loss_of(layer, state):
    cot = jax.random.normal(jax.random.PRNGKey(9), (1, TOKENS, H))

    def loss(params, x):
        out, sown = layer.apply({"params": params, ROUTER_STATE: state}, x, mutable=["losses"])
        return (out.astype(jnp.float32) * cot).sum() + sum(jax.tree.leaves(sown))

    return loss


def _value_and_grads(layer, params, state, x):
    return jax.jit(jax.value_and_grad(_loss_of(layer, state), argnums=(0, 1)))(params, x)


# ---- the formulation the layer had: every pair indexed through single scalars ----------

def plain_route_top_k(score, x, kernel, bias, top_k, scale, renormalise=True):
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = score(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if not renormalise:
        return idx, scale * picked, scores
    return idx, scale * picked / (picked.sum(-1, keepdims=True) + 1e-20), scores


def plain_combine_bwd(res, g):
    y, weights, order, inverse, held, n_rows = res
    flat = weights.reshape(-1)

    def d_rows(y, pair):
        d = g[pair // held.shape[1]]
        return ((flat[pair][:, None] * d).astype(y.dtype), (y.astype(jnp.float32) * d).sum(-1))

    d_y, d_w = moe._per_live_chunk(d_rows, n_rows, y, order, over=1)
    return d_y, jnp.where(held, d_w[inverse], 0.0), None, None, None, None


def token_major_sum_over_choices(rows, inverse, held, scale, n_rows):
    """(T, K, H) gathered and summed over axis 1, out of all the rows (at these widths
    every buffer is a small source: the layer's own takes no prefix either)."""
    picked = jnp.where(held[..., None], rows[inverse], jnp.zeros((), rows.dtype)).astype(jnp.float32)
    return (picked if scale is None else picked * jnp.where(held, scale, 0.0)[..., None]).sum(1)


def _plain_scalars(monkeypatch):
    """Part A undone: `take_along_axis`, `flat[pair]` and `d_w[inverse]` back in."""
    combine = jax.custom_vjp(lambda *args: moe._combine_fwd(*args)[0])
    combine.defvjp(moe._combine_fwd, plain_combine_bwd)
    monkeypatch.setattr(moe, "_route_top_k", plain_route_top_k)
    monkeypatch.setattr(moe, "_combine", combine)


# ---- the witness a CPU can hold: no pass over single elements, pair by pair ---------------

def _sub_jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def single_element_passes(jaxpr, at_least: int) -> list[str]:
    """The gathers whose slice is one element and the scatters whose update is one element
    an index, with `at_least` indices or more, in `jaxpr` and every jaxpr under it (`cond`
    branches, `while` bodies, `jit`s, what a `custom_vjp` left) but not inside pallas
    kernels: each as 'primitive shape @ scope'."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            continue
        if name == "gather":
            n, single = eqn.outvars[0].aval.size, math.prod(eqn.params["slice_sizes"]) == 1
        elif name.startswith("scatter"):
            indices, updates = eqn.invars[1].aval, eqn.invars[2].aval
            n = math.prod(indices.shape[:-1])
            single = updates.size == n
        else:
            n, single = 0, False
        if single and n >= at_least:
            found.append(f"{name} {eqn.outvars[0].aval.str_short()} @ {eqn.source_info.name_stack}")
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                found.extend(single_element_passes(sub, at_least))
    return found


def _passes_of(form, monkeypatch):
    monkeypatch.setattr(moe, "ROW_CHUNK", CHUNK)
    layer, params, state, x = _setup(form, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(layer, state), argnums=(0, 1)))(params, x)
    return single_element_passes(jaxpr.jaxpr, TOKENS)


@pytest.mark.parametrize("form", FORMS)
def test_no_pass_gathers_or_scatters_single_elements_pair_by_pair(monkeypatch, form):
    """Whole-row gathers, the loops' whole-chunk writes and the grouped products' metadata
    over a few dozen groups stay; nothing indexes T x K (or a chunk of) single scalars."""
    assert _passes_of(form, monkeypatch) == []


def test_the_witness_sees_the_four_the_layer_had(monkeypatch):
    """With the former formulation back in, the same walk finds its four: the gather of
    `take_along_axis` and the scatter-add of its transpose, `flat[pair]` in the loop's body,
    `d_w[inverse]`."""
    _plain_scalars(monkeypatch)
    found = _passes_of("sigmoid-bias-shared-8of128", monkeypatch)
    assert sorted(f.split()[0] for f in found) == ["gather", "gather", "gather", "scatter-add"], found


# ---- the same work ------------------------------------------------------------------------

def _assert_equal(got, want, what):
    for (path, g), (_, w) in zip(jax.tree.leaves_with_path(got), jax.tree.leaves_with_path(want)):
        assert np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32)), (
            what, jax.tree_util.keystr(path))


def _assert_within(got, want, ulp, what):
    """Each leaf within `ulp` of its largest magnitude: float32's order of summation where
    nothing is rounded below it, one bf16 ulp where a result is."""
    for (path, g), (_, w) in zip(jax.tree.leaves_with_path(got), jax.tree.leaves_with_path(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=ulp, atol=ulp * float(np.abs(w).max()),
                                   err_msg=f"{what} {jax.tree_util.keystr(path)}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", FORMS)
def test_loss_and_gradients_are_the_scalar_indexed_formulations(monkeypatch, form, dtype):
    monkeypatch.setattr(moe, "ROW_CHUNK", CHUNK)
    layer, params, state, x = _setup(form, dtype)
    as_it_is = _value_and_grads(layer, params, state, x)
    monkeypatch.setattr(moe, "_sum_over_choices", token_major_sum_over_choices)
    sums_token_major = _value_and_grads(layer, params, state, x)
    _plain_scalars(monkeypatch)
    plain = _value_and_grads(layer, params, state, x)
    d_params = plain[1][0]
    assert all(float(jnp.abs(d_params[n]).max()) > 0 for n in ("router", "w_gate", "w_up", "w_down"))
    # the select and the two sorts: the same numbers to the last bit
    _assert_equal(sums_token_major, plain, "scalars permuted through the sorts")
    # choice-major sums: the same terms added in another order
    _assert_within(as_it_is, plain, 1e-6 if dtype == jnp.float32 else 2.0 ** -8, "choice-major")
