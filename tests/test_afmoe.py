"""AFMoE (models/afmoe.py) and the held-experts layer (parallel/moe.py)
against the plain float32 reference (benchmarks/reference_afmoe.py), at a
small size on the CPU: logits, loss and gradients with both layer kinds, a
dense and an expert layer, grouped keys and values and a selection bias
that is not zero; the share test (the shares of a layer add up to the
uncut layer); no token dropped under the worst imbalance; the bias rule;
the window; the names the step's trace readers go by; the tiny preset as
a JAXJob through Platform. One chip-only test at published widths."""

from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_afmoe
from benchmarks.families import afmoe as family
from kubeflow_tpu.models.afmoe import (FULL, SLIDING, AfmoeAttention,
                                       AfmoeConfig, AfmoeLM)
from kubeflow_tpu.models.gpt import causal_lm_eval_metrics, causal_lm_loss
from kubeflow_tpu.parallel.moe import (ROUTER_STATE, ROW_CHUNK, HeldExpertsMlp,
                                       route_sigmoid, router_counters)

ROOT = Path(__file__).resolve().parents[1]
IDS = np.asarray(np.random.default_rng(3).integers(1, 512, size=(2, 48)), np.int32)


def _spec(cfg: AfmoeConfig) -> dict:
    return {"layer_types": cfg.layer_types, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "window": cfg.sliding_window, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps, "top_k": cfg.top_k, "route_scale": cfg.route_scale,
            "experts_held": cfg.experts_held or (0, cfg.num_experts)}


def _seeded(cfg: AfmoeConfig, seed: int = 0):
    """Variables with every leaf random: gains that are not 1, a selection
    bias that is not 0 (its spread is that of the scores' own differences,
    so it changes which experts are chosen)."""
    variables = AfmoeLM(cfg).init(jax.random.PRNGKey(seed), IDS)
    leaves, tree = jax.tree.flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    params = tree.unflatten([
        (1.0 + 0.2 * jax.random.normal(k, a.shape)) if a.ndim == 1
        else a + 0.05 * jax.random.normal(k, a.shape) for k, a in zip(keys, leaves)])
    state = jax.tree.map(lambda a: a, variables[ROUTER_STATE])
    for i, name in enumerate(sorted(state)):
        state[name]["moe"]["bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(100 + i), (cfg.num_experts,))
    return params, state


@pytest.mark.parametrize("attention,held", [("dense", None), ("flash", (2, 7))])
def test_logits_loss_and_gradients_match_the_reference(attention, held):
    cfg = AfmoeConfig.tiny(attention=attention, experts_held=held,
                           remat=attention == "flash")
    assert set(cfg.layer_types) == {SLIDING, FULL} and 0 < cfg.num_dense_layers < cfg.num_layers
    assert cfg.num_kv_heads < cfg.num_heads and cfg.head_dim * cfg.num_heads != cfg.hidden_size
    params, state = _seeded(cfg)
    spec = _spec(cfg)
    model = AfmoeLM(cfg)

    def program_loss(p):
        return causal_lm_loss(model.apply({"params": p, ROUTER_STATE: state}, IDS), IDS)

    def reference_loss(p):
        total, n = reference_afmoe.causal_lm_loss_sums(
            family.reference_params(p, state), IDS, IDS, spec, query_block=16, row_block=20)
        return total / n

    got = model.apply({"params": params, ROUTER_STATE: state}, IDS)
    want = reference_afmoe.logits(family.reference_params(params, state), IDS, spec, query_block=16)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    (loss, grads), (ref_loss, ref_grads) = (
        jax.value_and_grad(f)(params) for f in (program_loss, reference_loss))
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    flat, ref_flat = (jax.tree.leaves_with_path(g) for g in (grads, ref_grads))
    for (path, g), (_, r) in zip(flat, ref_flat):
        scale = float(jnp.abs(r).max()) + 1e-6
        assert float(jnp.abs(g - r).max()) <= 2e-3 * scale, jax.tree_util.keystr(path)
    # every parameter is reached, the router through the weights of its choices
    assert all(float(jnp.abs(r).max()) > 0 for _, r in ref_flat)


def test_the_bias_changes_the_choice_and_not_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros((8,)).at[3].set(5.0).at[0].set(-5.0)
    idx0, w0, scores = route_sigmoid(x, kernel, jnp.zeros((8,)), 2, 2.5)
    idx, w, _ = route_sigmoid(x, kernel, bias, 2, 2.5)
    assert bool((idx == 3).any(-1).all()) and not bool((idx == 0).any())
    assert not bool((idx0 == 3).any(-1).all())  # the bias changed S
    picked = jnp.take_along_axis(scores, idx, -1)  # scores without the bias
    np.testing.assert_allclose(w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(-1), 2.5, rtol=1e-6)


def _layer(held, experts=16, k=4, **kw):
    return HeldExpertsMlp(hidden_size=32, expert_dim=16, num_experts=experts, top_k=k,
                          experts_held=held, route_scale=2.0, **kw)


def _whole_layer(seed=0, experts=16, k=4):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 24, 32))
    variables = _layer(None, experts, k).init(jax.random.PRNGKey(seed + 1), x)
    params = dict(variables["params"])
    params["router"] = jax.random.normal(jax.random.PRNGKey(seed + 2), params["router"].shape)
    return x, params, variables[ROUTER_STATE]


def _reference_weights(params) -> dict:
    p = {n: params[n] for n in ("router", "w_gate", "w_up", "w_down")}
    p.update({n: params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")})
    return p


def _reference_layer(x, params, held, k=4, bias=None):
    p = _reference_weights(params)
    if bias is not None:
        p["bias"] = bias
    spec = {"top_k": k, "route_scale": 2.0, "experts_held": held}
    return reference_afmoe.expert_layer(x.reshape(-1, x.shape[-1]), p, spec).reshape(x.shape)


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four shares of a 16-expert layer, each holding four: the routed parts
    summed, with the shared expert (which every chip computes alike) counted
    once, are what the uncut reference gives for the whole layer."""
    x, params, state = _whole_layer()
    shared_only = None
    routed = 0.0
    for lo in range(0, 16, 4):
        share = dict(params, **{n: params[n][lo:lo + 4] for n in ("w_gate", "w_up", "w_down")})
        out = _layer((lo, lo + 4)).apply({"params": share, ROUTER_STATE: state}, x)
        if shared_only is None:
            zeroed = dict(share, **{n: jnp.zeros_like(share[n]) for n in ("w_gate", "w_up", "w_down")})
            shared_only = _layer((lo, lo + 4)).apply({"params": zeroed, ROUTER_STATE: state}, x)
        routed = routed + (out - shared_only)
        np.testing.assert_allclose(  # and each share is its own share of the reference
            out, _reference_layer(x, share, (lo, lo + 4)), atol=2e-5, rtol=2e-5)
    whole = _reference_layer(x, params, (0, 16))
    np.testing.assert_allclose(routed + shared_only, whole, atol=5e-5, rtol=5e-5)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    x, params, state = _whole_layer(experts=8, k=2)
    params["router"] = jnp.zeros_like(params["router"])  # every score one half
    state = dict(state, bias=jnp.zeros((8,)).at[jnp.asarray([2, 5])].set(1.0))
    layer = _layer((0, 8), experts=8, k=2)
    out, new = layer.apply({"params": params, ROUTER_STATE: state}, x, train=True,
                           mutable=[ROUTER_STATE])
    tokens = x.shape[0] * x.shape[1]
    counts = new[ROUTER_STATE]["counts"]
    assert counts.tolist() == [0, 0, tokens, 0, 0, tokens, 0, 0]
    assert int(new[ROUTER_STATE]["rows_here"]) == 2 * tokens  # every pair computed, none dropped
    np.testing.assert_allclose(out, _reference_layer(x, params, (0, 8), k=2, bias=state["bias"]),
                               atol=2e-5, rtol=2e-5)
    counters = router_counters({"layer_1": {"moe": new[ROUTER_STATE]}})
    assert float(counters["moe_load_max_over_mean"]) == pytest.approx(8 / 2)
    assert float(counters["moe_rows_here"]) == 2 * tokens
    # a share that holds neither of the two computes no row, and only the shared expert speaks
    out_none, new_none = _layer((6, 8), experts=8, k=2).apply(
        {"params": dict(params, **{n: params[n][6:8] for n in ("w_gate", "w_up", "w_down")}),
         ROUTER_STATE: state}, x, train=True, mutable=[ROUTER_STATE])
    assert int(new_none[ROUTER_STATE]["rows_here"]) == 0 and bool(jnp.isfinite(out_none).all())


def _one_device_mesh():
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    return build_mesh(MeshConfig(data=1), jax.devices()[:1])


def _trainer(cfg, **kw):
    from kubeflow_tpu.train import Trainer, TrainerConfig

    return Trainer(AfmoeLM(cfg), TrainerConfig(batch_size=2, learning_rate=1e-3, seed=5, **kw),
                   loss_fn=causal_lm_loss, eval_metrics_fn=causal_lm_eval_metrics,
                   mesh=_one_device_mesh())


def test_the_bias_rule_after_one_step_through_the_trainer():
    cfg = AfmoeConfig.tiny()
    trainer = _trainer(cfg)
    state = trainer.init_state(IDS)
    assert ROUTER_STATE in state.extra and "bias" not in str(jax.tree.structure(state.params))
    assert "bias" not in str(jax.tree.structure(state.opt_state))  # no Adam state for it
    state, metrics = trainer.train_step(state, (IDS, IDS))
    tokens = IDS.size
    for name, layer in state.extra[ROUTER_STATE].items():
        counts, bias = np.asarray(layer["moe"]["counts"]), np.asarray(layer["moe"]["bias"])
        assert counts.sum() == tokens * cfg.top_k, name
        np.testing.assert_allclose(bias, 0.001 * np.sign(counts.mean() - counts), atol=1e-9)
    assert float(metrics["moe_bias_abs_max"]) == pytest.approx(0.001)
    expert_layers = cfg.num_layers - cfg.num_dense_layers
    assert float(metrics["moe_rows_here"]) == tokens * cfg.top_k * expert_layers  # all held
    # every pair in use: the per-row loops walk the whole bound, rounded up to a chunk
    assert float(metrics["moe_rows_walked"]) == -(-tokens * cfg.top_k // ROW_CHUNK) * ROW_CHUNK * expert_layers
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    state, metrics = trainer.train_step(state, (IDS, IDS))
    assert 0.001 <= float(metrics["moe_bias_abs_max"]) <= 0.002 + 1e-9


def test_a_model_without_the_collection_has_no_routing_counters():
    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.train import Trainer, TrainerConfig

    trainer = Trainer(GPTLM(GPTConfig.tiny(dropout_rate=0.0)), TrainerConfig(batch_size=2, seed=5),
                      loss_fn=causal_lm_loss, eval_metrics_fn=causal_lm_eval_metrics,
                      mesh=_one_device_mesh())
    state, metrics = trainer.train_step(trainer.init_state(IDS), (IDS, IDS))
    assert set(metrics) == {"loss", "accuracy", "grad_norm"}


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_a_sliding_layer_ignores_keys_a_window_back_and_a_full_layer_does_not(kind):
    cfg = AfmoeConfig.tiny(sliding_window=8)
    module = AfmoeAttention(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, cfg.hidden_size))
    gate = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.num_heads * cfg.head_dim))
    variables = module.init(jax.random.PRNGKey(2), x, gate)
    moved = x.at[0, 0].add(1.0)  # position 0's key and value change
    a, b = module.apply(variables, x, gate), module.apply(variables, moved, gate)
    changed = np.asarray(jnp.abs(a - b).max(-1)[0] > 1e-6)
    if kind == SLIDING:  # position i sees j with i - j < 8
        assert changed[:8].all() and not changed[8:].any()
    else:
        assert changed.all()


def test_the_step_names_put_the_gate_in_block_dense_and_flash_in_the_core():
    """The names JAX writes into the lowered step, through the classifier the
    benchmark's trace readers use: under `remat` too."""
    from benchmarks.layer_metrics import train_moe
    from benchmarks.layer_metrics.train_parts import part_of

    cfg = AfmoeConfig.tiny(attention="flash", remat=True)
    trainer = _trainer(cfg)
    state = trainer.init_state(IDS)
    with jax.set_mesh(trainer.mesh):
        text = trainer._jit_train_step.lower(state, (IDS, IDS)).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(_train_step\)/[^"]*)"', text))
    parts = {n: part_of(n) for n in names}
    gate = [n for n in names if "/attn_gate/" in n]
    assert gate and {parts[n] for n in gate} == {"block_dense"}
    flash = [n for n in names if "/flash_fwd_" in n]
    assert {parts[n] for n in flash} == {"attn_core_fwd"}
    # the block's remat policy keeps the kernel's output and statistic: the backward
    # pass recomputes the block without it (the expert products below it does recompute)
    assert not [n for n in flash if "transpose(" in n or "rematted_computation" in n]
    assert {parts[n] for n in names if re.search(r"/attention/(q_norm|k_norm)/", n)} \
        == {"attn_core_fwd", "attn_core_bwd"}
    assert {parts[n] for n in names if re.search(r"/attention/(query|key|value|attn_out)/", n)} \
        == {"block_dense"}
    scopes = {train_moe.scope_of(n) for n in names}
    assert {"moe.route", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared"} <= scopes
    assert {parts[n] for n in names if train_moe.scope_of(n)} == {"block_dense"}
    assert any("/moe.experts/jit(gmm)" in n for n in names)  # the grouped product keeps the program's names
    # the grouped products, and every loop and branch of the layer, lie INSIDE one of the
    # five scopes (the readers take the segment right after `/moe/`): forward, recomputed
    # forward and backward
    control = [n for n in names if train_moe.scope_of(n)
               and re.search(r"/(jit\(t?gmm\)|while|cond)(/|$)", n)]
    assert {train_moe.scope_of(n) for n in control} == {"moe.dispatch", "moe.experts", "moe.combine"}
    products = [n for n in control if re.search(r"/jit\(t?gmm\)", n)]
    assert products and {train_moe.scope_of(n) for n in products} == {"moe.experts"}
    assert {"rematted_computation" in n for n in products} == {True, False}
    assert any("/jit(tgmm)" in n and "transpose(" in n for n in products)
    # what falls to no scope is what fell to none before the loops (the bias rule's
    # arithmetic, the reshapes at the layer's edges, the sum with the shared expert)
    other = {re.sub(r".*/layer_\d+/moe/", "", n) for n in names if train_moe.scope_of(n) == train_moe.OTHER}
    assert other <= {"add", "convert_element_type", "div", "mul", "reduce_sum", "reshape", "sign", "sub"}


def test_the_tiny_preset_trains_as_a_jaxjob_through_platform(tmp_path):
    from kubeflow_tpu.api.serde import job_from_yaml
    from kubeflow_tpu.api.validation import validate_job
    from kubeflow_tpu.client import Platform, TrainingClient

    sample = job_from_yaml((ROOT / "samples" / "jaxjob_afmoe.yaml").read_text())
    validate_job(sample)
    assert sample.spec.replica_specs["worker"].template.container.command[-1] == "examples.afmoe"
    with Platform(log_dir=str(tmp_path / "logs")) as p:
        client = TrainingClient(p)
        final = client.train("afmoe-tiny", family="afmoe", device="cpu",
                             args=["--steps=12", "--batch-size=8", "--attention=flash", "--remat"],
                             timeout_s=600)
        log = client.get_job_logs("afmoe-tiny")
    losses = [float(v) for v in re.findall(r"step=\d+ .*? loss=([0-9.]+)", log)]
    assert len(losses) >= 2 and losses[-1] < losses[0] and final["final_loss"] < losses[0]
    assert "moe_rows_here=" in log and "moe_load_max_over_mean=" in log and "moe_rows_walked=" in log


@pytest.mark.parametrize("routing,held_bias,rows_range", [
    ("balanced", 0.0, (7373, 9011)),        # k x 16/128 = 1 a token at the balanced load
    ("crowded", 0.045, (11000, 16000)),     # a fifth of the pairs here, as the cell's runs are
    ("every-pair", 10.0, (65536, 65536)),   # every token's eight choices on held experts
])
def test_one_expert_layer_on_the_chip_matches_the_float32_reference(routing, held_bias, rows_range):
    """Chip only (`pytest --noconftest` through the chip tool): one expert
    layer at published widths, 8,192 tokens, the bf16 program against the
    float32 reference given the program's own choice of experts (a bf16
    score can order two near-tied experts the other way; the arithmetic is
    what is judged), output and the gradient of the input, at three fills of
    the layer's rows: a selection bias on the held experts crowds them."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip: the grouped product as Mosaic compiles it is judged")
    h, m, e, k, held = 2048, 1024, 128, 8, (0, 16)
    layer = HeldExpertsMlp(hidden_size=h, expert_dim=m, num_experts=e, top_k=k,
                           experts_held=held, route_scale=2.826, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(28), (1, 8192, h), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(30), (1, 8192, h), jnp.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(29), x)
    params = variables["params"]
    bias = jnp.zeros((e,)).at[held[0]:held[1]].set(held_bias)
    state = dict(variables[ROUTER_STATE], bias=bias)

    def program(p, x):
        out, new = layer.apply({"params": p, ROUTER_STATE: state}, x, train=True, mutable=[ROUTER_STATE])
        return (out.astype(jnp.float32) * cot).sum(), (out, new)

    (_, (out, new)), dx = jax.jit(jax.value_and_grad(program, argnums=1, has_aux=True))(
        params, x.astype(jnp.bfloat16))
    xt = x.astype(jnp.bfloat16).astype(jnp.float32).reshape(-1, h)
    chosen, _, _ = jax.jit(lambda x: route_sigmoid(x, params["router"], bias, k, 2.826))(xt)
    spec = {"top_k": k, "route_scale": 2.826, "experts_held": held}

    def reference(x, p, c):
        out = reference_afmoe.expert_layer(x, dict(p, bias=bias), spec, chosen=c)
        return (out * cot.reshape(-1, h)).sum(), out

    (_, want), want_dx = jax.jit(jax.value_and_grad(reference, has_aux=True))(
        xt, _reference_weights(params), chosen)
    err = float(jnp.abs(out.astype(jnp.float32).reshape(-1, h) - want).max())
    scale = float(jnp.abs(want).max())
    dx_err = float(jnp.abs(dx.astype(jnp.float32).reshape(-1, h) - want_dx).max())
    dx_scale = float(jnp.abs(want_dx).max())
    rows = int(new[ROUTER_STATE]["rows_here"])
    print(f"expert layer on the chip, {routing}: max error {err:.3e} of outputs up to {scale:.3e}; "
          f"d_x {dx_err:.3e} of up to {dx_scale:.3e}; rows here {rows}")
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all()) and bool(jnp.isfinite(dx.astype(jnp.float32)).all())
    assert rows_range[0] <= rows <= rows_range[1]
    assert err <= CHIP_LAYER_ERR_LIMIT * scale
    assert dx_err <= CHIP_LAYER_DX_ERR_LIMIT * dx_scale


#: as a share of the largest output. On the v5e the layer read a largest error
#: of 1.959e-2 on outputs up to 3.947, 5.0e-3 of it, with 8,108 rows here (my
#: chip run, PR 28, call 3): a bf16 step and a quarter of an output between 2
#: and 4. The limit is two such steps at 4: 2**-5 of 4, 2**-7 of the scale.
CHIP_LAYER_ERR_LIMIT = 2.0 ** -7
#: the gradient of the input, as a share of its largest entry. On the v5e it
#: read 3.563e-2 of up to 5.599 at 8,108 rows here, 3.746e-2 of 5.599 at 12,842
#: and 5.742e-2 of 6.947 at all 65,536 (my chip run, PR 31, call 4): 6.4e-3 to
#: 8.3e-3 of the scale, two products deep. The limit is twice the worst.
CHIP_LAYER_DX_ERR_LIMIT = 2.0 ** -6
