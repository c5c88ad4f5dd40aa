"""DeepseekV2LM (`models/deepseek_v2.py`) on the CPU at tiny sizes: YaRN's frequencies
against the hand values, the model against `benchmarks/reference_deepseek_v2.py` on seeded
weights (loss with its balance term, gradient, first update through `Trainer.train_step`;
the float8 control is `tests/benchmarks/test_dsv2_cell.py`'s, at the cell's own limits), the share test (the eight shares of an expert layer, the shared experts
and the balance loss counted once, add up to the uncut reference's whole layer), the sown
balance loss under `nn.remat`, and how the seeded model routes."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_deepseek_v2 as reference
from benchmarks.families import deepseek_v2 as family
from kubeflow_tpu.models import DeepseekV2Config, DeepseekV2LM
from kubeflow_tpu.models.gpt import causal_lm_loss
from kubeflow_tpu.parallel.moe import ROUTER_STATE, HeldExpertsMlp, router_counters
from kubeflow_tpu.parallel.rope import apply_rope, yarn_frequencies, yarn_mscale

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
#: the benchmark's keys at a test's size: 8 experts of which this share holds 4, 2 a token,
#: keys of 16 + 8 and values of 16, an original context of 16 positions so that YaRN blends
CFG = {"vocab_size": 300, "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 16,
       "q_lora_rank": None, "intermediate_size": 64, "first_k_dense_replace": 1,
       "moe_intermediate_size": 16, "n_shared_experts": 2, "num_experts_per_tok": 2,
       "router_width": 8, "num_experts": 4, "experts_held": [2, 6], "routed_scaling_factor": 1,
       "norm_topk_prob": False, "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
       "topk_group": 1, "seq_aux": True, "moe_layer_freq": 1, "aux_loss_alpha": 0.01,
       "rms_norm_eps": 1e-6, "rope_theta": 10000,
       "rope_scaling": dict(YARN, original_max_position_embeddings=16, beta_fast=4),
       "num_dense_layers": 1, "num_shared_experts": 2}
MIX = {"task": "causal_lm", "attention": "dense", "seq_len": 32, "batch": 8,
       "learning_rate": 1e-3, "warmup_steps": 0}


def _rows(n=8, length=32, seed=5):
    return np.asarray(np.random.default_rng(seed).integers(1, 300, size=(n, length)), np.int32)


# ------------------------------------------------------------------------------ YaRN

#: the published numbers: 64 rotary dimensions, theta 10,000, factor 40 over 4,096 positions
FREQS = yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0)
OWN = 10000.0 ** (-2.0 * np.arange(32) / 64)


@pytest.mark.parametrize("what", ["low", "high", "pair_0", "pair_31", "blend", "reference", "plain"])
def test_yarn_frequencies_equal_the_hand_values(what):
    corr = lambda turns: 64 * math.log(4096 / (2 * math.pi * turns)) / (2 * math.log(10000))  # noqa: E731
    if what == "low":    # the pairs below 10 keep their frequency
        assert math.floor(corr(32)) == 10
        np.testing.assert_allclose(FREQS[:11], OWN[:11], rtol=1e-6)
        assert FREQS[11] < OWN[11]
    elif what == "high":  # the pairs from 23 up are slowed forty-fold
        assert math.ceil(corr(1)) == 23
        np.testing.assert_allclose(FREQS[23:], OWN[23:] / 40, rtol=1e-6)
        assert FREQS[22] > OWN[22] / 40
    elif what == "pair_0":
        assert FREQS[0] == 1.0 and FREQS.dtype == np.float32 and FREQS.shape == (32,)
    elif what == "pair_31":
        assert FREQS[31] == pytest.approx(10000.0 ** (-62 / 64) / 40, rel=1e-6)
    elif what == "blend":  # pair 16 is 6/13 of the way
        assert FREQS[16] == pytest.approx(OWN[16] * (7 / 13) + OWN[16] / 40 * (6 / 13), rel=1e-6)
    elif what == "reference":  # the reference computes them from the formulas, on its own
        spec = {"rope": 64, "theta": 10000.0, "yarn": YARN}
        np.testing.assert_allclose(FREQS, reference.yarn_inv_freq(spec), rtol=1e-6)
    else:  # no extension: the rotary frequencies themselves
        np.testing.assert_allclose(yarn_frequencies(64, 10000.0, 1.0, 4096), OWN, rtol=1e-6)


@pytest.mark.parametrize("what", ["mscale", "scale", "gain", "unextended"])
def test_yarn_temperature_and_the_softmax_scale_equal_the_hand_values(what):
    cfg = DeepseekV2Config()
    if what == "mscale":
        assert yarn_mscale(40, 0.707) == pytest.approx(0.1 * 0.707 * math.log(40) + 1) == pytest.approx(1.2608038)
    elif what == "scale":
        assert cfg.qk_head_dim == 192 and cfg.softmax_scale == pytest.approx(0.1147214, rel=1e-6)
        spec = family.reference_spec({**CFG, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": YARN})
        assert reference.softmax_scale(spec) == pytest.approx(cfg.softmax_scale, rel=1e-9)
    elif what == "gain":  # the two keys are equal in this model: cos and sin carry 1, and no other ratio is taken
        assert cfg.rope_mscale == cfg.rope_mscale_all_dim
        with pytest.raises(NotImplementedError, match="carries no gain"):
            DeepseekV2Config(rope_mscale=1.0)
    else:
        assert yarn_mscale(1.0, 0.707) == 1.0
        assert DeepseekV2Config(rope_factor=1.0).softmax_scale == pytest.approx(192 ** -0.5)


@pytest.mark.parametrize("case", ["theta", "freqs", "norm", "per_row"])
def test_apply_rope_rotates_the_array_it_is_given(case):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 24))
    pos = jnp.arange(12)
    if case == "theta":  # its callers' form: theta^(-2i/D) over the whole head
        want = reference_rotate(x, 10000.0 ** (-jnp.arange(0, 24, 2) / 24))
        np.testing.assert_allclose(apply_rope(x, pos, 10000.0), want, atol=1e-6)
    elif case == "freqs":  # a caller that rotates the last 8 of a head slices them
        freqs = yarn_frequencies(8, 10000.0, 40.0, 16, 4.0, 1.0)
        got = apply_rope(x[..., 16:], pos, freqs=freqs)
        np.testing.assert_allclose(got, reference_rotate(x[..., 16:], freqs), atol=1e-6)
        assert got.shape == (2, 12, 3, 8)
    elif case == "norm":  # a rotation: every pair keeps its length, whatever the frequencies
        got = apply_rope(x, pos, freqs=yarn_frequencies(24, 10000.0, 40.0, 16))
        pairs = lambda a: a[..., :12] ** 2 + a[..., 12:] ** 2  # noqa: E731
        np.testing.assert_allclose(pairs(got), pairs(x), rtol=1e-5, atol=1e-6)
    else:  # positions a row, as continuous batching gives them
        rows = jnp.stack([pos, pos + 5])
        got = apply_rope(x, rows, 10000.0)
        np.testing.assert_allclose(got[1], apply_rope(x[1:], pos + 5, 10000.0)[0], atol=1e-6)


def reference_rotate(x, freqs):
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


# ------------------------------------------------ the model against the plain reference

def _program_loss(module, variables, params, x):
    """Cross entropy plus what the expert layers sowed, as `Trainer._loss_of` adds them
    (`init` sows too: the Trainer drops that collection from its state, and so does this)."""
    kept = {k: v for k, v in variables.items() if k != "losses"}
    logits, sown = module.apply({**kept, "params": params}, x, True, mutable=["losses", ROUTER_STATE])
    return causal_lm_loss(logits, x) + sum(jax.tree.leaves(sown["losses"]))


@pytest.fixture(scope="module", params=["dense", "flash"])
def pair(request):
    """(loss and gradient of the program, of the reference, the balance term alone)."""
    mix = dict(MIX, attention=request.param)
    module, x = family.train_model(CFG, mix)["module"], jnp.asarray(_rows(3))
    variables = module.init(jax.random.PRNGKey(0), x)
    spec = family.reference_spec(CFG)

    def reference_loss(p):
        total, weight = reference.causal_lm_loss_sums(p, x, x, spec)
        return total / weight

    got = jax.value_and_grad(functools.partial(_program_loss, module, variables, x=x))(variables["params"])
    ref_params = family.reference_params(variables["params"])
    want = jax.value_and_grad(reference_loss)(ref_params)
    return got, want, float(reference.hidden_states(ref_params, x, spec)[1])


def test_loss_with_its_balance_term_matches_the_reference(pair):
    (got, _), (want, _), balance = pair
    assert float(got) == pytest.approx(float(want), abs=2e-5) and float(got) > 1.0
    # two expert layers, each near 1 at a seeded router: the term is in the loss, and shows
    assert 1.5 < balance < 4.0 and CFG["aux_loss_alpha"] * balance > 1e-2


@pytest.mark.parametrize("group", ["emb", "head", "gf", "g1", "g2", "wq", "wdkv", "gc", "wukv", "wo",
                                   "router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
                                   "shared_down"])
def test_gradient_matches_the_reference(pair, group):
    (_, got), (_, want), _ = pair
    got = family.reference_params(got)
    leaves = [(got[group], want[group])] if group in want else [
        (a[group], b[group]) for a, b in zip(got["layers"], want["layers"]) if group in b]
    assert leaves
    for a, b in leaves:
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-7)


def test_the_routers_gradient_carries_the_balance_term(pair):
    """With the coefficient at 0 the router's gradient is another: the sown loss reaches it."""
    module, x = family.train_model(CFG, MIX)["module"], jnp.asarray(_rows(3))
    variables = module.init(jax.random.PRNGKey(0), x)
    spec = dict(family.reference_spec(CFG), balance_loss=0.0)

    def without(p):
        total, weight = reference.causal_lm_loss_sums(p, x, x, spec)
        return total / weight

    plain = jax.grad(without)(family.reference_params(variables["params"]))
    (_, _), (_, want), _ = pair
    moved = float(jnp.abs(plain["layers"][1]["router"] - want["layers"][1]["router"]).max())
    assert moved > 1e-2 * float(jnp.abs(want["layers"][1]["router"]).max())


@functools.lru_cache(maxsize=None)
def _reference_update():
    """The reference's jitted first step: one compile for the three programs it is held against
    (how the program computes its attention is nothing of the reference's)."""
    return family.reference_update_fn(CFG, MIX)


@pytest.mark.parametrize("attention,remat", [("dense", False), ("flash", False), ("flash", True)])
def test_first_update_through_the_trainer_matches_the_reference(attention, remat):
    """The kind `train_job_update`'s comparison at a test's size: the Trainer's normal step
    against the reference's first step, and the step's counters."""
    from benchmarks.kinds.train_job_update import update_gap
    from kubeflow_tpu.train import Trainer, TrainerConfig

    mix, x = dict(MIX, attention=attention, remat=remat), _rows(8)
    model = family.train_model(CFG, mix)
    trainer = Trainer(model["module"], TrainerConfig(batch_size=8, learning_rate=1e-3, seed=11),
                      loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
    state = trainer.init_state(x)
    before = family.reference_state(state)
    total, count, expected = _reference_update()(before, x, x)
    before, expected = jax.device_get(before), jax.device_get(expected)
    state, metrics = trainer.train_step(state, (x, x))
    assert float(metrics["loss"]) == pytest.approx(float(total) / float(count), abs=2e-5)
    gaps = update_gap(before, expected, jax.device_get(family.reference_state(state)))
    # float32 both sides: Adam's first step is the rate times the gradient's sign, so only
    # gradients at rounding's size can differ
    assert max(gaps.values()) < 0.05, gaps
    # the balance loss as the step counts it: the reference's term times the coefficient
    balance = float(reference.hidden_states(before, jnp.asarray(x), family.reference_spec(CFG))[1])
    assert float(metrics["moe_balance_loss"]) == pytest.approx(CFG["aux_loss_alpha"] * balance, rel=1e-4)
    assert float(metrics["moe_bias_abs_max"]) == 0.0 and float(metrics["moe_rows_here"]) > 0
    assert {"moe_rows_walked", "moe_load_max_over_mean"} <= set(metrics)


# ----------------------------------------------------------------------- the share test

ALPHA = 0.01


def _layer(held):
    return HeldExpertsMlp(hidden_size=32, expert_dim=16, num_experts=16, top_k=4, experts_held=held,
                          score_func="softmax", num_shared_experts=2, bias_update_rate=0.0,
                          renormalise=False, balance_loss=ALPHA)


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Eight shares of a 16-expert layer, each holding two. Every chip computes the two
    shared experts and the balance loss (over all 16 of the router's outputs) alike: counted
    once, the shares' results add up to what the reference gives for the whole layer."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    variables = _layer((0, 16)).init(jax.random.PRNGKey(2), x)
    params = variables["params"]
    held = ("w_gate", "w_up", "w_down")
    ref = {"router": params["router"], **{n: params[n] for n in held},
           **{n: params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")}}
    assert ref["shared_gate"].shape == (32, 2 * 16)  # one SwiGLU of twice the width
    spec = {"top_k": 4, "route_scale": 1.0, "experts_held": (0, 16)}
    rows = [reference.expert_layer(row, ref, spec) for row in x]
    whole = jnp.stack([out for out, _ in rows])
    balance = ALPHA * float(np.mean([float(term) for _, term in rows]))
    shared = jnp.stack([reference.swiglu(row, ref["shared_gate"], ref["shared_up"], ref["shared_down"])
                        for row in x])
    routed = jnp.zeros_like(x)
    for lo in range(0, 16, 2):
        share = dict(params, **{n: params[n][lo:lo + 2] for n in held})
        out, sown = _layer((lo, lo + 2)).apply(
            {"params": share, ROUTER_STATE: variables[ROUTER_STATE]}, x, mutable=["losses"])
        np.testing.assert_allclose(  # each share is its own share of the reference
            out, jnp.stack([reference.expert_layer(
                row, dict(ref, **{n: ref[n][lo:lo + 2] for n in held}),
                dict(spec, experts_held=(lo, lo + 2)))[0] for row in x]), atol=2e-5, rtol=2e-5)
        # the loss every share sows is the whole router's: the same on every chip
        assert float(sown["losses"]["moe_balance"]) == pytest.approx(balance, rel=1e-5)
        routed = routed + (out - shared)
    assert float(jnp.abs(whole - shared).max()) > 1e-3 and float(jnp.abs(shared).max()) > 1e-2
    np.testing.assert_allclose(routed + shared, whole, atol=5e-5, rtol=5e-5)


# --------------------------------------------------- the sown loss through `nn.remat`

@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_the_loss_sown_inside_a_rematted_block_reaches_the_trainers_loss(attention):
    """The blocks' `losses` collection passes through the lifted `nn.remat` as ROUTER_STATE
    does: `Trainer._loss_of` reads the value and the gradient it reads without `remat`."""
    from kubeflow_tpu.train import Trainer, TrainerConfig

    x = _rows(8)
    read = {}
    for remat in (False, True):
        model = family.train_model(CFG, dict(MIX, attention=attention, remat=remat))
        trainer = Trainer(model["module"], TrainerConfig(batch_size=8, learning_rate=1e-3, seed=11),
                          loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
        state = trainer.init_state(x)
        with jax.set_mesh(trainer.mesh):
            (loss, (logits, extra)), grads = jax.jit(jax.value_and_grad(trainer._loss_of, has_aux=True))(
                state.params, state.extra, jnp.asarray(x), jnp.asarray(x), state.rng)
        assert "losses" not in extra  # popped: it never persists into the state
        sown = float(loss) - float(causal_lm_loss(logits.astype(jnp.float32), jnp.asarray(x)))
        kept = sum(float(v["moe"]["balance_loss"]) for v in extra[ROUTER_STATE].values())
        assert sown == pytest.approx(kept, abs=2e-6) and kept > 1e-2
        read[remat] = (float(loss), grads)
    assert read[True][0] == pytest.approx(read[False][0], abs=1e-6)
    for a, b in zip(jax.tree.leaves(read[True][1]), jax.tree.leaves(read[False][1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------------ how it is drawn

def test_the_embedding_is_at_unit_scale_and_each_shares_router_columns_sum_to_nothing():
    module = DeepseekV2LM(DeepseekV2Config.tiny(experts_held=(4, 8)))
    params = module.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    assert float(params["token_embed"]["embedding"].std()) == pytest.approx(1.0, rel=0.03)
    router = np.asarray(params["layer_1"]["moe"]["router"])
    assert np.abs(router.reshape(64, 2, 4).sum(-1)).max() < 1e-7
    assert float(params["layer_0"]["kv_latent"]["norm"]["scale"].min()) == 1.0  # not sharpened
    with pytest.raises(ValueError, match="do not tile"):
        DeepseekV2Config.tiny(experts_held=(0, 3))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_a_seeded_model_routes_a_share_its_balanced_load(seed):
    """At a size where it shows: 32 experts of which this share holds 4, 4 a token, 1,024
    positions, a dense and two expert layers. Every layer's fullest expert stays under 2.5
    times the mean and the share computes its balanced load to a tenth, whatever the seed."""
    model = DeepseekV2LM(DeepseekV2Config(
        vocab_size=2000, hidden_size=256, num_layers=3, num_heads=4, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64, mlp_dim=512, num_experts=32,
        experts_held=(0, 4), top_k=4, expert_dim=64))
    x = jnp.asarray(np.random.default_rng(seed).integers(1, 1999, size=(2, 512)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(seed), x)
    _, updates = model.apply(variables, x, True, mutable=[ROUTER_STATE, "losses"])
    counters = router_counters(updates[ROUTER_STATE])
    balanced = 2 * 1024 * 4 * 4 / 32
    assert float(counters["moe_rows_here"]) == pytest.approx(balanced, rel=0.1)
    assert float(counters["moe_load_max_over_mean"]) < 2.5
    # a balanced router's loss is near 1 a layer: the counter sums the layers'
    assert float(counters["moe_balance_loss"]) == pytest.approx(2 * 0.001, rel=0.1)


@pytest.mark.parametrize("field,value,match", [
    ("attention", "ring", "dense|flash"), ("qk_rope_head_dim", 7, "even"),
    ("num_dense_layers", 9, "num_dense_layers"), ("experts_held", (0, 3), "do not tile")])
def test_the_config_refuses_what_the_block_cannot_be(field, value, match):
    with pytest.raises(ValueError, match=match):
        DeepseekV2Config.tiny(**{field: value})


def test_the_example_trains_and_evaluates_through_fit(capsys):
    """`python -m examples.deepseek_v2`: `Trainer.fit`'s loop, log line and eval pass (eight
    rows: the tests' eight virtual devices)."""
    from examples import deepseek_v2 as example

    final = example.main(["--device=cpu", "--steps=4", "--batch-size=8", "--seq-len=32"])
    assert np.isfinite(final)
    log = capsys.readouterr().out
    assert "moe_balance_loss=" in log and "moe_rows_here=" in log and "eval_loss=" in log
