"""SdarMoeLM (`models/sdar_moe.py`) on the CPU at tiny sizes: `corrupt` against its law,
the model against `benchmarks/reference_sdar.py` on seeded weights (loss, gradient, first
update through `Trainer.train_step`), the share test (the eight shares of an expert layer
add up to the uncut reference's whole layer: softmax router, no shared expert to count
once), what `HeldExpertsMlp` is when the model hands it a softmax router, no shared expert
and no bias rule, and the Trainer's corruption seam (`train.corrupt`, the step's rng)."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sdar
from benchmarks.families import sdar_moe as family
from kubeflow_tpu.models import SdarMoeConfig, SdarMoeLM, sdar_eval_metrics, sdar_loss
from kubeflow_tpu.parallel.moe import ROUTER_STATE, HeldExpertsMlp

#: the benchmark's keys at a test's size: 8 experts of which this share holds 4, 2 a token
CFG = {"vocab_size": 300, "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
       "router_width": 8, "num_experts": 4, "experts_held": [2, 6], "num_experts_per_tok": 2,
       "moe_intermediate_size": 16}
MIX = {"task": "causal_lm", "attention": "dense", "seq_len": 32, "batch": 8, "block_length": 4,
       "mask_rate_min": 0.05, "learning_rate": 1e-3, "warmup_steps": 0}


def _rows(n=8, length=32, seed=5):
    return np.asarray(np.random.default_rng(seed).integers(1, 300, size=(n, length)), np.int32)


# ------------------------------------------------------------------ corrupt and its law

@pytest.fixture(scope="module")
def drawn():
    model = SdarMoeLM(SdarMoeConfig.tiny(block_length=4, mask_rate_min=0.05))
    x = jnp.asarray(np.random.default_rng(0).integers(1, 511, size=(64, 256)), jnp.int32)
    ids, noise = jax.jit(model.corrupt)(jax.random.PRNGKey(3), x, x)
    return model, x, np.asarray(ids), jax.device_get(noise)


def test_corrupt_lays_the_clean_row_first_and_changes_only_masked_positions(drawn):
    model, x, ids, noise = drawn
    assert ids.shape == (64, 512) and set(noise) == {"labels", "masked", "weights"}
    np.testing.assert_array_equal(ids[:, :256], x)
    np.testing.assert_array_equal(noise["labels"], x)
    # the mask id is the vocabulary's last row, which no drawn id is here
    assert model.cfg.vocab_size - 1 == 511 and int(x.max()) < 511
    np.testing.assert_array_equal(ids[:, 256:], np.where(noise["masked"], 511, x))


def test_corrupt_draws_one_rate_a_block_inside_its_range(drawn):
    _, _, _, noise = drawn
    masked, weights = noise["masked"], noise["weights"]
    np.testing.assert_array_equal(weights > 0, masked)
    rate = (1.0 / np.where(masked, weights, 1.0)).reshape(64, 64, 4)  # 1 where nothing is masked
    blocks = masked.reshape(64, 64, 4)
    assert rate[blocks].min() >= 0.05 and rate.max() <= 1.0 and weights.max() <= 20.0 + 1e-4
    # one rate a block of 4: a block's masked positions all read the same
    assert (np.where(blocks, rate, 0.0).max(-1) - np.where(blocks, rate, 1.0).min(-1))[blocks.any(-1)].max() < 1e-6
    # rates uniform on [0.05, 1], tokens masked independently at their block's rate t: the
    # mean is 0.525, a block is masked whole with E[t^4] = 0.2105 and not at all with
    # E[(1 - t)^4] = 0.1629 (one rate for all tokens would give 0.076 and 0.051)
    assert abs(masked.mean() - 0.525) < 0.02
    assert abs(blocks.all(-1).mean() - 0.2105) < 0.03 and abs((~blocks.any(-1)).mean() - 0.1629) < 0.03


def test_corrupt_same_key_same_draw_another_key_another(drawn):
    model, x, ids, noise = drawn
    again, _ = model.corrupt(jax.random.PRNGKey(3), x, x)
    other, _ = model.corrupt(jax.random.PRNGKey(4), x, x)
    np.testing.assert_array_equal(np.asarray(again), ids)
    assert (np.asarray(other) != ids).mean() > 0.1


def test_corrupt_refuses_rows_that_are_no_whole_blocks():
    model = SdarMoeLM(SdarMoeConfig.tiny(block_length=4))
    with pytest.raises(ValueError, match="do not tile"):
        model.corrupt(jax.random.PRNGKey(0), jnp.ones((1, 30), jnp.int32), jnp.ones((1, 30), jnp.int32))
    with pytest.raises(ValueError, match="whole blocks"):
        model.init(jax.random.PRNGKey(0), jnp.ones((1, 60), jnp.int32))


# ------------------------------------------------ the model against the plain reference

@pytest.fixture(scope="module", params=["dense", "flash"])
def pair(request):
    """(model dict, variables, rows, the step's noise, loss and gradient both ways)."""
    mix = dict(MIX, attention=request.param)
    model, x = family.train_model(CFG, mix), _rows(3)
    module = model["module"]
    variables = module.init(jax.random.PRNGKey(0), x)
    rng = jax.random.PRNGKey(7)
    ids, noise = module.corrupt(jax.random.fold_in(rng, 0), jnp.asarray(x), jnp.asarray(x))

    def loss(params):
        return model["loss_fn"](module.apply({**variables, "params": params}, ids), noise)

    masked, weights = family.first_step_noise(CFG, mix, rng, x, x)
    spec = family.reference_spec(CFG, mix)

    def reference_loss(p):
        total, count = reference_sdar.diffusion_loss_sums(p, jnp.asarray(x), masked, weights, spec)
        return total / count

    got = jax.value_and_grad(loss)(variables["params"])
    want = jax.value_and_grad(reference_loss)(family.reference_params(variables["params"]))
    return noise, (masked, weights), got, want


def test_the_family_hands_the_reference_the_programs_own_draw(pair):
    noise, (masked, weights), _, _ = pair
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(noise["masked"]))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(noise["weights"]))


def test_loss_matches_the_reference(pair):
    _, _, (got, _), (want, _) = pair
    assert float(got) == pytest.approx(float(want), abs=2e-5) and float(got) > 1.0


@pytest.mark.parametrize("group", ["emb", "head", "gf", "g1", "g2", "wq", "wk", "wv", "wo", "gq", "gk",
                                   "router", "w_gate", "w_up", "w_down"])
def test_gradient_matches_the_reference(pair, group):
    _, _, (_, got), (_, want) = pair
    got = family.reference_params(got)
    leaves = [(got[group], want[group])] if group in want else [
        (a[group], b[group]) for a, b in zip(got["layers"], want["layers"])]
    for a, b in leaves:
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-7)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_first_update_through_the_trainer_matches_the_reference(attention):
    """The kind `train_job_update`'s comparison at a test's size: the Trainer's normal
    step (its own rng, its own corruption) against the reference's first step."""
    from benchmarks.kinds.train_job_update import update_gap
    from kubeflow_tpu.train import Trainer, TrainerConfig

    mix, x = dict(MIX, attention=attention), _rows(8)
    model = family.train_model(CFG, mix)
    trainer = Trainer(model["module"], TrainerConfig(batch_size=8, learning_rate=1e-3, seed=11),
                      loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
    state = trainer.init_state(x)
    before = family.reference_state(state)
    total, count, expected = family.reference_update_fn(CFG, mix)(before, x, x)
    before, expected = jax.device_get(before), jax.device_get(expected)
    state, metrics = trainer.train_step(state, (x, x))
    assert float(metrics["loss"]) == pytest.approx(float(total) / float(count), abs=2e-5)
    gaps = update_gap(before, expected, jax.device_get(family.reference_state(state)))
    # float32 both sides: Adam's first step is the rate times the gradient's sign, so only
    # gradients at rounding's size can differ
    assert gaps.pop("rng") == 0.0 and max(gaps.values()) < 0.05, gaps
    assert 0.3 < float(metrics["diffusion_masked_share"]) < 0.75
    assert 1.0 <= float(metrics["diffusion_weight_max"]) <= 20.0
    assert float(metrics["moe_bias_abs_max"]) == 0.0 and float(metrics["moe_rows_here"]) > 0
    assert {"moe_rows_walked", "moe_load_max_over_mean"} <= set(metrics)


# ----------------------------------------------------------------------- the share test

def _layer(held, **kw):
    return HeldExpertsMlp(hidden_size=32, expert_dim=16, num_experts=16, top_k=4, experts_held=held,
                          score_func="softmax", num_shared_experts=0, bias_update_rate=0.0, **kw)


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Eight shares of a 16-expert layer, each holding two: with a softmax router and no
    shared expert there is nothing that every chip computes alike, so the shares' results
    simply add up to what the reference gives for the whole layer."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    variables = _layer((0, 16)).init(jax.random.PRNGKey(2), x)
    params = variables["params"]
    assert set(params) == {"router", "w_gate", "w_up", "w_down"}  # no shared_* parameter
    spec = {"top_k": 4, "experts_held": (0, 16)}
    ref = {n: params[n] for n in ("router", "w_gate", "w_up", "w_down")}
    whole = reference_sdar.expert_layer(x.reshape(-1, 32), ref, spec).reshape(x.shape)
    total = jnp.zeros_like(x)
    for lo in range(0, 16, 2):
        share = dict(params, **{n: params[n][lo:lo + 2] for n in ("w_gate", "w_up", "w_down")})
        out = _layer((lo, lo + 2)).apply({**variables, "params": share}, x)
        np.testing.assert_allclose(  # each share is its own share of the reference
            out, reference_sdar.expert_layer(
                x.reshape(-1, 32), {**share}, dict(spec, experts_held=(lo, lo + 2))).reshape(x.shape),
            atol=2e-5, rtol=2e-5)
        total = total + out
    assert float(jnp.abs(whole).max()) > 1e-2
    np.testing.assert_allclose(total, whole, atol=5e-5, rtol=5e-5)


def test_the_softmax_routers_weights_are_the_chosen_probabilities_renormalised():
    from kubeflow_tpu.parallel.moe import route_sigmoid, route_softmax

    x = jax.random.normal(jax.random.PRNGKey(4), (6, 32))
    kernel = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    idx, weights, scores = route_softmax(x, kernel, jnp.zeros((16,)), 4, 1.0)
    probs = jax.nn.softmax(x @ kernel, -1)
    np.testing.assert_allclose(scores, probs, rtol=1e-5)
    np.testing.assert_allclose(scores.sum(-1), 1.0, rtol=1e-5)  # over ALL sixteen
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(np.argsort(-probs, -1)[:, :4], -1))
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)  # over the four chosen
    np.testing.assert_allclose(weights, np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
                               / np.take_along_axis(np.asarray(probs), np.asarray(idx), -1).sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(reference_sdar.route(x, kernel, 4).sum(-1), 1.0, rtol=1e-5)
    assert not np.allclose(route_sigmoid(x, kernel, jnp.zeros((16,)), 4, 1.0)[2].sum(-1), 1.0)


def test_without_a_bias_rule_no_bias_moves_and_the_counters_stay():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    layer = _layer((4, 8))
    variables = layer.init(jax.random.PRNGKey(2), x)
    _, updates = layer.apply(variables, x, True, mutable=[ROUTER_STATE])
    state = updates[ROUTER_STATE]
    assert not np.asarray(state["bias"]).any()
    assert float(state["counts"].sum()) == 2 * 24 * 4 and 0 < int(state["rows_here"]) < 2 * 24 * 4
    lowered = jax.jit(lambda v, x: layer.apply(v, x, True, mutable=[ROUTER_STATE])).lower(variables, x)
    assert "moe.shared" not in lowered.as_text(debug_info=True)
    with_rule = _layer((4, 8)).clone(bias_update_rate=0.01)
    _, updates = with_rule.apply(variables, x, True, mutable=[ROUTER_STATE])
    assert float(jnp.abs(updates[ROUTER_STATE]["bias"]).max()) == pytest.approx(0.01)


# ------------------------------------------------------------------ the initialisers

def test_token_rows_are_at_unit_scale_and_the_mask_ids_row_near_nothing():
    from kubeflow_tpu.models.sdar_moe import MASK_ROW_SCALE, token_rows

    rows = np.asarray(token_rows(jax.random.PRNGKey(0), (500, 64)))
    assert rows[:-1].std() == pytest.approx(1.0, rel=0.02)
    assert rows[-1].std() == pytest.approx(MASK_ROW_SCALE, rel=0.3) and MASK_ROW_SCALE <= 1e-2


def test_each_shares_router_columns_sum_to_nothing():
    from kubeflow_tpu.models.sdar_moe import share_centred_normal

    w = np.asarray(share_centred_normal(4)(jax.random.PRNGKey(1), (256, 32)))
    assert np.abs(w.reshape(256, 8, 4).sum(-1)).max() < 1e-7
    assert w.std() == pytest.approx(0.02 * (3 / 4) ** 0.5, rel=0.03)
    # another share's width is another initialiser: these columns do not sum to nothing by eights
    assert np.abs(w.reshape(256, 4, 8)[:, :, :3].sum(-1)).max() > 0.01
    with pytest.raises(ValueError, match="do not tile"):
        SdarMoeConfig.tiny(num_experts=8, experts_held=(0, 3))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_a_seeded_model_routes_a_share_its_balanced_load(seed):
    """What the three initialisers are for (`models/sdar_moe.py`, Initialisation), at a size
    where it shows: 32 experts of which this share holds 4, 4 a token, 1,024 positions.
    Every layer's fullest expert stays under 2.5 times the mean and the share computes its
    balanced load to a tenth, whatever the seed; plainly drawn, the fullest expert reads 5.6
    to 7.7 times the mean of 8 possible and the share 894 to 1,539 rows of 1,536."""
    from kubeflow_tpu.parallel.moe import router_counters

    model = SdarMoeLM(SdarMoeConfig(vocab_size=2000, hidden_size=256, num_layers=3, num_heads=4, num_kv_heads=2,
                                    head_dim=64, num_experts=32, experts_held=(0, 4), top_k=4, expert_dim=64))
    x = jnp.asarray(np.random.default_rng(seed).integers(1, 1999, size=(2, 256)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((2, 512), jnp.int32))
    ids, _ = model.corrupt(jax.random.PRNGKey(seed + 9), x, x)
    _, updates = model.apply(variables, ids, True, mutable=[ROUTER_STATE])
    counters = router_counters(updates[ROUTER_STATE])
    balanced = 3 * 1024 * 4 * 4 / 32
    assert float(counters["moe_rows_here"]) == pytest.approx(balanced, rel=0.1)
    assert float(counters["moe_load_max_over_mean"]) < 2.5


# ------------------------------------------------------------- the Trainer's corruption

@pytest.fixture(scope="module")
def trained():
    from kubeflow_tpu.train import Trainer, TrainerConfig

    model = family.train_model(CFG, MIX)
    trainer = Trainer(model["module"], TrainerConfig(batch_size=8, learning_rate=1e-3, seed=3),
                      loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
    return trainer, _rows(8)


def test_the_step_corrupts_under_its_scope_before_the_forward_pass(trained):
    trainer, x = trained
    with jax.set_mesh(trainer.mesh):
        text = jax.jit(trainer._train_step).lower(trainer.abstract_state(x), (x, x)).as_text(debug_info=True)
    names = set(re.findall(r'"(jit\(_train_step\)/[^"]*)"', text))
    assert any(n.startswith("jit(_train_step)/train.corrupt/") for n in names)
    assert not any("train.corrupt" in n and ("jvp(" in n or "transpose(" in n) for n in names)
    assert any("/SdarMoeLM/layer_1/attention/" in n for n in names)
    assert any("/SdarMoeLM/layer_0/moe/moe.experts" in n for n in names)


def test_each_step_draws_its_own_noise_and_a_resumed_step_the_same(trained):
    trainer, x = trained
    state = trainer.init_state(x)
    shares = []
    for _ in range(3):
        state, m = trainer.train_step(state, (x, x))
        shares.append(float(m["diffusion_masked_share"]))
    assert len(set(shares)) == 3
    # a job resumed at step 1 draws step 1's noise again: the key is fold_in(rng, step)
    resumed = trainer.init_state(x).replace(step=jnp.ones((), jnp.int32))
    _, m = trainer.train_step(resumed, (x, x))
    assert float(m["diffusion_masked_share"]) == shares[1]


def test_evaluate_reads_the_objective_under_one_fixed_draw(trained):
    from kubeflow_tpu.train.data import Dataset

    trainer, x = trained
    state = trainer.init_state(x)
    data = Dataset(x, x, x, x, num_classes=300)
    first = trainer.evaluate(state, data)
    assert first == trainer.evaluate(state, data)
    assert np.isfinite(first["loss"]) and 0.0 <= first["accuracy"] <= 1.0


def test_loss_and_eval_metrics_weigh_masked_positions_only():
    logits = jnp.zeros((2, 4, 8)).at[:, :, 3].set(5.0)
    y = {"labels": jnp.full((2, 4), 3), "masked": jnp.array([[1, 0, 0, 1], [0, 0, 0, 0]], bool),
         "weights": jnp.array([[2.0, 0, 0, 4.0], [0, 0, 0, 0]])}
    ce = float(-jax.nn.log_softmax(logits[0, 0])[3])
    assert float(sdar_loss(logits, y)) == pytest.approx(6.0 * ce / 8, rel=1e-5)
    per_example, accuracy = sdar_eval_metrics(logits, y)
    np.testing.assert_allclose(per_example, [6.0 * ce / 4, 0.0], rtol=1e-5)
    np.testing.assert_allclose(accuracy, [1.0, 0.0])


def test_the_example_trains_and_evaluates_through_fit(capsys):
    """`python -m examples.sdar_moe`: `Trainer.fit`'s loop, log line and eval pass with a
    model that corrupts its batch (eight rows: the tests' eight virtual devices)."""
    from examples import sdar_moe as example

    final = example.main(["--device=cpu", "--steps=4", "--batch-size=8", "--seq-len=32"])
    assert np.isfinite(final)
    log = capsys.readouterr().out
    assert "diffusion_masked_share=" in log and "moe_rows_here=" in log and "eval_loss=" in log
