"""What `Trainer.train_step` shows of itself: the host spans `train.enqueue` and
`train.place_batch` (flight recorder and profiler), the device scopes of the compiled
step, and that the benchmark's part classifier reads the same parts off the step's
operation names with and without those scopes (a step executable cached before they
existed is loaded with the old names). A toy GPT on the CPU."""

from __future__ import annotations

import collections
import contextlib
import re

import numpy as np
import pytest

from kubeflow_tpu import tracing
from kubeflow_tpu.tracing import Tracer

SCOPES = ("train.cast", "train.loss", "train.grad_norm", "train.optimizer")
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)


@pytest.fixture(scope="module")
def toy():
    from kubeflow_tpu.models.gpt import GPTLM, GPTConfig, causal_lm_eval_metrics, causal_lm_loss
    from kubeflow_tpu.train import Trainer, TrainerConfig

    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2, num_heads=2, mlp_dim=32,
                    max_len=16, attention="dense", dropout_rate=0.0)
    # eight rows: tests/conftest.py makes eight virtual devices and the batch spans them
    trainer = Trainer(GPTLM(cfg), TrainerConfig(batch_size=8, learning_rate=1e-3, seed=1),
                      loss_fn=causal_lm_loss, eval_metrics_fn=causal_lm_eval_metrics)
    x = np.random.default_rng(0).integers(1, 64, size=(8, 16)).astype(np.int32)
    return trainer, x


def lowered_step(trainer, x, y):
    import jax

    def _train_step(state, batch):  # a new function each time, so jax traces it anew
        return trainer._train_step(state, batch)

    with jax.set_mesh(trainer.mesh):
        return jax.jit(_train_step).lower(trainer.abstract_state(x), (x, y))


def step_op_names(trainer, x) -> dict[str, str]:
    """instruction -> op_name of the compiled step program."""
    return dict(_INSTRUCTION.findall(lowered_step(trainer, x, x).compile().as_text()))


def test_train_step_records_enqueue_with_place_batch_inside(toy):
    trainer, x = toy
    tracer = Tracer()
    tracing.set_tracer(tracer)
    try:
        state = trainer.init_state(x)
        for _ in range(2):
            state, metrics = trainer.train_step(state, (x, x))
    finally:
        tracing.set_tracer(None)
    assert np.isfinite(float(metrics["loss"]))
    # `train.init_state` and what jax built are in the ring too: tests/test_startup_log.py
    spans = [s for s in tracer.snapshot() if s["name"] in ("train.place_batch", "train.enqueue")]
    assert [s["name"] for s in spans] == ["train.place_batch", "train.enqueue"] * 2
    for inner, outer in zip(spans[::2], spans[1::2]):
        assert inner["parent"] == outer["span"] and inner["trace"] == outer["trace"]
        assert outer["ts"] <= inner["ts"] and inner["dur"] <= outer["dur"]
        assert outer["attrs"]["path"] == "jit"  # no warm-started executable here
    # the first step of the toy built its program, the second nothing
    assert ["built" in outer["attrs"] for outer in spans[1::2]] == [True, False]


def test_train_step_annotates_the_profile_with_no_tracer_armed(toy, tmp_path):
    """The benchmark arms no Tracer: the spans must reach a profiler session all the same."""
    import jax

    from tests.test_tracing_profiler import host_events

    trainer, x = toy
    state = trainer.init_state(x)
    state, _ = trainer.train_step(state, (x, x))  # compiled outside the session
    assert not tracing.get_tracer().enabled
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        state, metrics = trainer.train_step(state, (x, x))
        jax.block_until_ready(metrics)
    finally:
        jax.profiler.stop_trace()
    host = host_events(tmp_path)
    (e0, e1, stats), = host["train.enqueue"]
    (p0, p1, _), = host["train.place_batch"]
    assert e0 <= p0 <= p1 <= e1 and stats["path"] == "jit"
    # PJRT's own span of the dispatch lies inside the program's
    assert any(e0 <= s and t <= e1 for s, t, _ in host["PjitFunction(_train_step)"])


def test_the_step_carries_the_four_device_scopes(toy):
    names = set(step_op_names(*toy).values())
    for scope in ("train.loss", "train.grad_norm", "train.optimizer"):
        assert any(scope in n.split("/")[1] for n in names if "/" in n), scope
    assert any(n.startswith("jit(_train_step)/jvp(train.loss)/GPTLM/layer_0/attention/") for n in names)
    assert any(n.startswith("jit(_train_step)/transpose(jvp(train.loss))/GPTLM/layer_1/mlp_up/")
               for n in names)
    # token ids are not cast; an image model's float input is
    from kubeflow_tpu.models import MnistMLP
    from kubeflow_tpu.train import Trainer, TrainerConfig

    mlp = Trainer(MnistMLP(hidden=(8,)), TrainerConfig(batch_size=8, seed=1))
    images = np.zeros((8, 8, 8, 1), np.float16)
    text = lowered_step(mlp, images, np.zeros((8,), np.int32)).as_text(debug_info=True)
    for scope in SCOPES:
        assert f"{scope}" in text, scope
    assert "jit(_train_step)/train.cast/convert_element_type" in text


class JaxWithoutScopes:
    """`jax` for the trainer module alone, with `named_scope` switched off: Flax's own
    module names stay, so the step is named as the parent's was."""

    named_scope = staticmethod(lambda name: contextlib.nullcontext())

    def __getattr__(self, attr):
        import jax

        return getattr(jax, attr)


def test_the_scopes_change_no_instruction_of_the_step(toy, monkeypatch):
    """Scopes are metadata: the step lowered without locations is the same text with and
    without them, so the compile cache's key (which leaves metadata out) is the parent's
    and a cached step still loads. Computing the gradient norm before the update, say,
    would reorder the instructions and cost every warm cache one compile."""
    from kubeflow_tpu.train import trainer as trainer_module

    trainer, x = toy
    with_scopes = lowered_step(trainer, x, x)
    assert "train.optimizer" in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(trainer_module, "jax", JaxWithoutScopes())
    without = lowered_step(trainer, x, x)
    monkeypatch.undo()
    assert "train.optimizer" not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


def test_the_part_classifier_reads_the_same_parts_with_and_without_the_scopes(toy, monkeypatch):
    """Compile the step again with `jax.named_scope` switched off in the trainer alone
    (Flax's module names stay): the instructions are the same, the names are the
    parent's, and every instruction falls into the same part."""
    from benchmarks.layer_metrics.train_parts import PARTS, UNATTRIBUTED, part_of
    from kubeflow_tpu.train import trainer as trainer_module

    trainer, x = toy
    with_scopes = step_op_names(trainer, x)

    monkeypatch.setattr(trainer_module, "jax", JaxWithoutScopes())
    without = step_op_names(trainer, x)
    monkeypatch.undo()

    assert not any(scope in name for name in without.values() for scope in SCOPES)
    assert any(name.startswith("jit(_train_step)/jvp(GPTLM)/layer_0/") for name in without.values())
    # instruction numbers may shift between two compiles, so count by part, and compare
    # one by one where the instruction has the same name in both
    def count(names):
        return collections.Counter(part_of(n) for n in names.values())

    assert count(with_scopes) == count(without) and len(with_scopes) == len(without)
    both = set(with_scopes) & set(without)
    assert len(both) > 0.9 * len(with_scopes)
    moved = {i: (with_scopes[i], without[i]) for i in both
             if part_of(with_scopes[i]) != part_of(without[i])}
    assert not moved, moved
    named = [i for i, n in with_scopes.items() if n.startswith("jit(_train_step)/")]
    assert {part_of(with_scopes[i]) for i in named} == set(PARTS)
    # what the compiler names without the program's stack (a reduction's body, a
    # parameter) resolves to nothing in both
    assert all(part_of(n) == UNATTRIBUTED for n in with_scopes.values()
               if not n.startswith("jit(_train_step)/"))
    # the new scopes split class (iv); the parent's names cannot
    optimizer = {with_scopes[i].split("/")[1] for i in named if part_of(with_scopes[i]) == "optimizer"}
    assert {"train.grad_norm", "train.optimizer"} <= optimizer


def test_a_model_that_publishes_no_corruption_lowers_to_the_step_it_had(toy, monkeypatch):
    """`Trainer._train_step` asks the model for a `corrupt` (the seam a diffusion
    objective draws its noise through): a model without one is traced as if the seam were
    not there, instruction for instruction, so its compile cache still serves it."""
    from kubeflow_tpu.train import trainer as trainer_module

    trainer, x = toy
    with_seam = lowered_step(trainer, x, x)
    assert "train.corrupt" not in with_seam.as_text(debug_info=True)
    monkeypatch.setattr(trainer_module, "_corrupted", lambda model, rng, x, y: (x, y))
    without = lowered_step(trainer, x, x)
    monkeypatch.undo()
    assert with_seam.as_text() == without.as_text()


@pytest.mark.parametrize("what", ["scope", "labels", "rng"])
def test_a_models_corruption_runs_under_its_scope_with_the_steps_rng(toy, what):
    """A GPT that drops a tenth of its input ids: the draw is outside the differentiated
    function under `train.corrupt`, the loss and the accuracy see the `y'` it returns,
    and the key is `fold_in(state.rng, state.step)`: another step, another draw."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from benchmarks.layer_metrics.train_parts import part_of
    from kubeflow_tpu.models.gpt import GPTLM, causal_lm_eval_metrics, causal_lm_loss
    from kubeflow_tpu.train import Trainer, TrainerConfig

    class DroppingGPT(GPTLM):
        @nn.nowrap
        def corrupt(self, rng, x, y):
            kept = jax.random.uniform(rng, x.shape) >= 0.1
            return jnp.where(kept, x, 1), {"labels": y, "kept": kept}

        @staticmethod
        def step_counters(extra, y):
            return {"kept_share": y["kept"].mean(dtype=jnp.float32)}

    base, x = toy
    trainer = Trainer(DroppingGPT(base.model.cfg), TrainerConfig(batch_size=8, learning_rate=1e-3, seed=1),
                      loss_fn=lambda logits, y: causal_lm_loss(logits, y["labels"]),
                      eval_metrics_fn=lambda logits, y: causal_lm_eval_metrics(logits, y["labels"]))
    if what == "scope":
        names = set(step_op_names(trainer, x).values())
        corrupt = {n for n in names if "train.corrupt" in n}
        assert corrupt and all(n.startswith("jit(_train_step)/train.corrupt/") for n in corrupt)
        assert {part_of(n) for n in corrupt} == {"optimizer"}  # outside the differentiated function
        return
    state = trainer.init_state(x)
    state, first = trainer.train_step(state, (x, x))
    state, second = trainer.train_step(state, (x, x))
    if what == "labels":
        assert np.isfinite(float(first["loss"])) and 0.7 < float(first["kept_share"]) < 1.0
    else:
        assert float(first["kept_share"]) != float(second["kept_share"])
        resumed = trainer.init_state(x).replace(step=jnp.ones((), jnp.int32))
        assert float(trainer.train_step(resumed, (x, x))[1]["kept_share"]) == float(second["kept_share"])


# ------------------------------------------------- latent attention's scopes and parts

MLA_SCOPES = ("mla.kv_down", "mla.kv_norm", "mla.kv_up", "mla.rope")


@pytest.fixture(scope="module")
def mla():
    from kubeflow_tpu.models import DeepseekV2Config, DeepseekV2LM
    from kubeflow_tpu.models.gpt import causal_lm_eval_metrics, causal_lm_loss
    from kubeflow_tpu.train import Trainer, TrainerConfig

    trainer = Trainer(DeepseekV2LM(DeepseekV2Config.tiny(vocab_size=64)),
                      TrainerConfig(batch_size=8, learning_rate=1e-3, seed=1),
                      loss_fn=causal_lm_loss, eval_metrics_fn=causal_lm_eval_metrics)
    x = np.random.default_rng(0).integers(1, 64, size=(8, 16)).astype(np.int32)
    return trainer, x, set(step_op_names(trainer, x).values())


def test_the_mla_scopes_change_no_instruction_of_the_step(mla, monkeypatch):
    """`mla.kv_down`, `mla.kv_norm`, `mla.kv_up` and `mla.rope` are metadata, like the
    Trainer's own scopes: the step lowers to the same text with and without them."""
    from kubeflow_tpu.models import deepseek_v2

    trainer, x, _ = mla
    with_scopes = lowered_step(trainer, x, x)
    named = with_scopes.as_text(debug_info=True)
    assert all(scope in named for scope in MLA_SCOPES)
    monkeypatch.setattr(deepseek_v2, "jax", JaxWithoutScopes())
    without = lowered_step(trainer, x, x)
    monkeypatch.undo()
    assert not any(scope in without.as_text(debug_info=True) for scope in MLA_SCOPES)
    assert with_scopes.as_text() == without.as_text()


@pytest.mark.parametrize("under,parts", [
    ("/kv_latent/", {"block_dense"}),                       # down, norm, up: projections
    ("/kv_latent/mla.kv_down/down/", {"block_dense"}),
    ("/kv_latent/mla.kv_norm/norm/", {"block_dense"}),
    ("/kv_latent/mla.kv_up/up/", {"block_dense"}),
    ("/attention/query/", {"block_dense"}),
    ("/attention/attn_out/", {"block_dense"}),
    ("/attention/mla.rope/", {"attn_core_fwd", "attn_core_bwd"}),  # rotation, broadcast, concatenations
    ("/layer_1/moe/moe.route/", {"block_dense"}),           # the balance loss is the router's
])
def test_every_operation_of_the_latent_path_reads_block_dense(mla, under, parts):
    """`train_parts.ATTENTION_CORE` counts what lies under `attention/` and under none of
    its projections: the latent path is a module beside `attention`, so its products land
    with the block's dense work, and the rotation stays with the core."""
    from benchmarks.layer_metrics.train_parts import part_of

    _, _, names = mla
    found = {n for n in names if under in n and re.search(r"/layer_\d+/", n)}
    assert found, under
    assert {part_of(n) for n in found} <= parts, {n: part_of(n) for n in found if part_of(n) not in parts}
    if under == "/kv_latent/":  # forward and backward both, in every layer
        assert any("transpose(" in n for n in found) and any("transpose(" not in n for n in found)
        assert {m.group(0) for n in found for m in [re.search(r"layer_\d+", n)]} == {"layer_0", "layer_1", "layer_2"}
