"""The runner kind `train_job_update` and what it compares, at a tiny size on the CPU:
`update_gap`'s scale (0 the same step, 1 a state left as it was, 2 the opposite step), its
groups, the reference's first step against Adam written out and the bias rule, the
program's first step through the Trainer against the reference's, what rounding the
reference's operands to bf16 and to float8 does to the reading, and the kind end to end.
Nothing here times anything, and no limit of a cell is set from these readings: a limit
comes from the chip (`traffic/lm-packed-8k.json`)."""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_afmoe, runtime
from benchmarks.families import afmoe as family
from benchmarks.kinds import train_job_update as kind

ROOT = Path(__file__).resolve().parents[2]
#: a sliding and a full layer, the first dense and the second of experts, half of them held
CFG = dict(json.loads((ROOT / "benchmarks/configs/trinity-mini.json").read_text()),
           vocab_size=300, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, intermediate_size=64, num_dense_layers=1,
           layer_types=["sliding_attention", "full_attention"], sliding_window=8, router_width=8,
           num_experts=4, experts_held=[2, 6], num_experts_per_tok=2, moe_intermediate_size=16)
MIX = {"kind": "train_job_update", "task": "causal_lm", "attention": "dense", "seq_len": 24,
       "batch": 8, "pool_batches": 3, "chain_noise": 0.1, "learning_rate": 1e-3,
       "warmup_steps": 0, "descent_steps": 3, "reference_rows_per_call": 8,
       "loss_tolerance": 1e-4, "update_tolerance": 0.1}
IDS = np.asarray(np.random.default_rng(3).integers(1, 300, size=(2, 24)), np.int32)


def _tree(scale: float) -> dict:
    rng = np.random.default_rng(0)
    return {"emb": scale * rng.normal(size=(5, 4)).astype(np.float32),
            "layers": [{"wq": scale * rng.normal(size=(4, 4)).astype(np.float32)} for _ in range(2)]}


@pytest.mark.parametrize("share,reads", [(1.0, 0.0), (0.0, 1.0), (-1.0, 2.0), (0.5, 0.5)])
def test_update_gap_is_the_distance_left_over_the_distance_the_reference_went(share, reads):
    before, step = _tree(1.0), _tree(0.01)
    expected = jax.tree.map(np.add, before, step)
    after = jax.tree.map(lambda b, s: b + np.float32(share) * s, before, step)
    gaps = kind.update_gap(before, expected, after)
    assert set(gaps) == {"emb", "layers/wq", "all"}
    assert all(g == pytest.approx(reads, abs=1e-5) for g in gaps.values())


def test_update_gap_pools_a_name_over_the_layers_and_tells_the_groups_apart():
    before, step = _tree(1.0), _tree(0.01)
    expected = jax.tree.map(np.add, before, step)
    after = jax.tree.map(np.copy, expected)
    after["layers"][1]["wq"] = before["layers"][1]["wq"]  # one layer's leaf left as it was
    gaps = kind.update_gap(before, expected, after)
    moved = [float(np.square(layer["wq"]).sum()) for layer in step["layers"]]
    assert gaps["emb"] == 0.0
    assert gaps["layers/wq"] == pytest.approx(np.sqrt(moved[1] / sum(moved)), rel=1e-5)
    assert 0.0 < gaps["all"] < gaps["layers/wq"]
    # a leaf the reference did not move: 0 if the program left it too, else no number
    still = {"a": np.ones(3, np.float32)}
    assert kind.update_gap(still, still, still) == {"a": 0.0, "all": 0.0}
    assert kind.update_gap(still, still, {"a": np.zeros(3, np.float32)})["a"] == np.inf


@pytest.fixture(scope="module")
def first_step():
    """One device, two rows, float32: the state `init_state` made as the reference takes
    it, the reference's state after its first step, and the program's after its own."""
    from kubeflow_tpu.parallel import MeshConfig, build_mesh
    from kubeflow_tpu.train import Trainer, TrainerConfig

    model = family.train_model(CFG, MIX)
    trainer = Trainer(model["module"],
                      TrainerConfig(batch_size=2, learning_rate=MIX["learning_rate"], warmup_steps=0,
                                    seed=3, compute_dtype=jnp.float32),
                      loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"],
                      mesh=build_mesh(MeshConfig(data=1), jax.devices()[:1]))
    state = trainer.init_state(IDS)
    before = jax.device_get(family.reference_state(state))
    total, weight, expected = family.reference_update_fn(CFG, MIX)(before, IDS, IDS)
    state, _ = trainer.train_step(state, (IDS, IDS))
    return {"before": before, "loss": float(total) / float(weight), "weight": float(weight),
            "expected": jax.device_get(expected),
            "after": jax.device_get(family.reference_state(state))}


def test_the_references_first_step_is_adams_and_the_bias_rule(first_step):
    import optax

    before, after = first_step["before"], first_step["expected"]
    spec = family.reference_spec(CFG)

    def mean_loss(p):
        s, w = reference_afmoe.causal_lm_loss_sums(p, IDS, IDS, spec)
        return s / w

    loss, grads = jax.value_and_grad(mean_loss)(before)
    assert first_step["loss"] == pytest.approx(float(loss), rel=1e-6) and first_step["weight"] == 2 * 23
    tx = optax.adam(MIX["learning_rate"])
    updates, _ = tx.update(grads, tx.init(before), before)
    want = optax.apply_updates(before, updates)
    rate = CFG["load_balance_coeff"]
    for layer, was, wanted in zip(after["layers"], before["layers"], want["layers"]):
        for name in layer:
            if name == "bias":  # no gradient: the rule alone moves it, by the rate or not at all
                step = np.abs(layer[name] - was[name])
                assert step.max() == pytest.approx(rate) and set(np.round(step / rate, 4)) <= {0.0, 1.0}
            else:  # a hundredth of a step's length: where the gradient is of eps's size
                assert np.abs(layer[name] - wanted[name]).max() < 1e-5, name
    assert sum("bias" in layer for layer in after["layers"]) == 1  # the one expert layer
    for name in ("emb", "gf", "head"):
        assert np.abs(after[name] - want[name]).max() < 1e-5, name
    # every weight that has a gradient moved by about the learning rate, whatever its size
    assert np.median(np.abs(after["head"] - before["head"])) == pytest.approx(MIX["learning_rate"], rel=1e-2)


def test_the_programs_first_step_is_the_references_at_a_tiny_size(first_step):
    """Both in float32 on the CPU: the gradients differ by float32 rounding, and Adam's
    first step is the learning rate times the gradient's sign, so what is left is the few
    weights whose gradient is too near 0 to have a sign."""
    gaps = kind.update_gap(first_step["before"], first_step["expected"], first_step["after"])
    assert {"emb", "head", "gf", "layers/router", "layers/bias", "layers/w_gate", "layers/wq",
            "layers/wz", "layers/gq", "layers/g4", "all"} <= set(gaps)
    assert gaps["layers/bias"] == 0.0
    assert max(gaps.values()) < 0.05, gaps


def test_a_lower_precision_of_the_reference_reads_a_wider_gap(first_step, monkeypatch):
    """The control a limit is set against, as the chip run makes it (PERF.md, PR 28): the
    reference's own first step with every product's operands rounded to bf16 and to
    float8 (e4m3), against itself in float32."""
    plain = reference_afmoe._mm
    gaps = {}
    for dtype in (jnp.bfloat16, jnp.float8_e4m3fn):
        monkeypatch.setattr(reference_afmoe, "_mm", lambda a, b, dtype=dtype: plain(
            a.astype(dtype).astype(jnp.float32), b.astype(dtype).astype(jnp.float32)))
        _, _, after = family.reference_update_fn(CFG, MIX)(first_step["before"], IDS, IDS)
        gaps[dtype] = kind.update_gap(first_step["before"], first_step["expected"], jax.device_get(after))
    bf16, fp8 = gaps[jnp.bfloat16]["all"], gaps[jnp.float8_e4m3fn]["all"]
    assert 0.0 < bf16 < 0.5 * fp8 and fp8 > 0.2, gaps


class _NoTrace:
    def poll(self, _elapsed):
        pass

    def stop(self):
        pass


def test_the_kind_runs_end_to_end_at_a_tiny_size(monkeypatch):
    from kubeflow_tpu.train import TrainerConfig

    # the chip's policy computes in bf16; float32 here, as the other tiny comparisons
    monkeypatch.setattr(TrainerConfig, "compute_dtype", jnp.float32)
    lines = []
    env = {"log": lines.append, "builds": runtime.Builds()}
    out = kind.run(CFG, MIX, 2147485999, 0.2, _NoTrace(), env)
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["facts"]["tokens_per_step"] == 8 * 24
    gap_line = next(line for line in lines if line.startswith("update_gap="))
    assert "layers/router=" in gap_line and "layers/bias=0.0000" in gap_line
    checks = next(line for line in lines if "checks=" in line)
    assert "'first_update_matches_reference': True" in checks
    assert "'first_loss_matches_reference': True" in checks
    with pytest.raises(ValueError, match="whole batch"):
        kind.run(CFG, dict(MIX, reference_rows_per_call=4), 1, 0.1, _NoTrace(), env)
