"""Block rematerialization (jax.checkpoint) — the long-context HBM lever:
numerics identical to the plain path, decode untouched, trains on a mesh."""

import collections
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import BertConfig, BertForSequenceClassification
from kubeflow_tpu.models.afmoe import AfmoeConfig, AfmoeLM
from kubeflow_tpu.models.gpt import GPTConfig, GPTLM, generate
from kubeflow_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeLM
from kubeflow_tpu.parallel import MeshConfig, build_mesh
from kubeflow_tpu.parallel.ring_attention import FLASH_RESIDUAL_NAMES


@pytest.fixture(scope="module")
def gpt_pair():
    plain = GPTLM(GPTConfig.tiny(dropout_rate=0.0, max_len=64))
    remat = GPTLM(GPTConfig.tiny(dropout_rate=0.0, max_len=64, remat=True))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1,
                             plain.cfg.vocab_size, jnp.int32)
    variables = plain.init(jax.random.PRNGKey(0), ids)
    return plain, remat, variables, ids


class TestRemat:
    def test_gpt_forward_and_grads_identical(self, gpt_pair):
        plain, remat, v, ids = gpt_pair
        np.testing.assert_allclose(
            np.asarray(plain.apply(v, ids)), np.asarray(remat.apply(v, ids)),
            atol=1e-6,
        )
        gp = jax.grad(lambda p: (plain.apply({"params": p}, ids) ** 2).sum())(
            v["params"])
        gr = jax.grad(lambda p: (remat.apply({"params": p}, ids) ** 2).sum())(
            v["params"])
        for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_decode_path_unaffected(self, gpt_pair):
        plain, remat, v, ids = gpt_pair
        a = generate(plain, v, ids[:, :5], max_new_tokens=4)
        b = generate(remat, v, ids[:, :5], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bert_remat_matches(self):
        plain = BertForSequenceClassification(
            BertConfig.tiny(dropout_rate=0.0), num_classes=2)
        remat = BertForSequenceClassification(
            BertConfig.tiny(dropout_rate=0.0, remat=True), num_classes=2)
        ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 1, 1024,
                                 jnp.int32)
        v = plain.init(jax.random.PRNGKey(0), ids)
        np.testing.assert_allclose(
            np.asarray(plain.apply(v, ids)), np.asarray(remat.apply(v, ids)),
            atol=1e-6,
        )

    def test_trains_under_mesh_with_ring(self, cpu_devices):
        """remat x ring attention x TP — the long-context training combo."""
        from kubeflow_tpu.models import causal_lm_eval_metrics, causal_lm_loss
        from kubeflow_tpu.train import Trainer, TrainerConfig
        from kubeflow_tpu.train.data import synthetic_lm_dataset

        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=64, remat=True,
                             attention="ring", attention_block=8)
        mesh = build_mesh(MeshConfig(data=2, context=2, model=2),
                          cpu_devices[:8])
        ds = synthetic_lm_dataset(n_train=16, n_test=8, seq_len=32,
                                  vocab_size=cfg.vocab_size)
        trainer = Trainer(
            GPTLM(cfg),
            TrainerConfig(batch_size=8, steps=1, log_every_steps=10**9),
            loss_fn=causal_lm_loss,
            eval_metrics_fn=causal_lm_eval_metrics,
            mesh=mesh,
        )
        state = trainer.init_state(ds.x_train[:8])
        state, m = trainer.train_step(state, (ds.x_train[:8], ds.y_train[:8]))
        assert np.isfinite(float(m["loss"]))


def test_pipelined_models_already_remat(cpu_devices):
    """remat=True on a pipelined config is a no-op BY DESIGN (the gpipe
    ring checkpoints whole stages, subsuming per-layer remat): same
    numerics, no error."""
    from kubeflow_tpu.models import BertPipelineClassifier

    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 1, 1024,
                             jnp.int32)
    a = BertPipelineClassifier(BertConfig.tiny(dropout_rate=0.0),
                               num_stages=2, n_micro=2)
    b = BertPipelineClassifier(BertConfig.tiny(dropout_rate=0.0, remat=True),
                               num_stages=2, n_micro=2)
    v = a.init(jax.random.PRNGKey(0), ids)
    np.testing.assert_allclose(np.asarray(a.apply(v, ids)),
                               np.asarray(b.apply(v, ids)), atol=1e-6)


# ---- the blocks' remat policy keeps the flash kernel's output and statistic ----

#: the three decoders that can run the flash kernel under `remat`, at their
#: `tiny` sizes (every layer has an attention): (model class, its config's
#: preset, the preset's extra arguments)
DECODERS = {
    "gpt": (GPTLM, GPTConfig.tiny, dict(dropout_rate=0.0, max_len=64)),
    "afmoe": (AfmoeLM, AfmoeConfig.tiny, {}),
    "sdar_moe": (SdarMoeLM, SdarMoeConfig.tiny, {}),
}
FLASH_IDS = np.asarray(np.random.default_rng(7).integers(1, 500, size=(2, 48)), np.int32)


def _loss_and_params(name, ids=FLASH_IDS, **cfg_kw):
    """(loss over the parameters, the parameters) of one of DECODERS."""
    cls, preset, kw = DECODERS[name]
    model = cls(preset(**kw, **cfg_kw))
    variables = model.init(jax.random.PRNGKey(0), ids)
    params = variables.pop("params")

    def loss(p):
        out = model.apply({"params": p, **variables}, ids, mutable=list(variables))
        return (out[0] ** 2).mean()
    return loss, params


def _pallas_calls(jaxpr, found=None) -> collections.Counter:
    """The `pallas_call`s of a jaxpr and of every jaxpr inside it, by name."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[str(eqn.params["name"])] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, found)
    return found


def _flash_forwards(calls) -> int:
    return sum(n for name, n in calls.items() if name.startswith("flash_fwd_"))


def _without_the_policy(monkeypatch, name):
    """The block's `nn.remat` with no policy, as it was before PR 33."""
    module = importlib.import_module(DECODERS[name][0].__module__)
    monkeypatch.setattr(module, "FLASH_REMAT_POLICY", None)


@pytest.mark.parametrize("name", DECODERS)
def test_a_rematted_flash_block_runs_the_forward_kernel_once(name, monkeypatch):
    """Under `remat` the backward pass of a block keeps the flash kernel's
    `out` and `lse` and recomputes the rest: one `flash_fwd_*` call an attention
    layer in the gradient's jaxpr (two with no policy), every other kernel as
    often as with no policy, and the same gradient to the last bit."""
    _, preset, kw = DECODERS[name]
    layers = preset(**kw).num_layers
    loss, params = _loss_and_params(name, attention="flash", remat=True)
    kept = _pallas_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    grads = jax.jit(jax.grad(loss))(params)
    plain_loss, _ = _loss_and_params(name, attention="flash", remat=False)
    plain = jax.jit(jax.grad(plain_loss))(params)
    with monkeypatch.context() as patch:
        _without_the_policy(patch, name)
        loss, _ = _loss_and_params(name, attention="flash", remat=True)
        whole = _pallas_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        whole_grads = jax.jit(jax.grad(loss))(params)
    assert (_flash_forwards(kept), _flash_forwards(whole)) == (layers, 2 * layers)
    others = lambda calls: {k: n for k, n in calls.items()  # noqa: E731
                            if not k.startswith("flash_fwd_")}
    assert others(kept) == others(whole)  # the expert layer's products are still recomputed
    for a, b, c in zip(*map(jax.tree.leaves, (grads, whole_grads, plain))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4)


@pytest.mark.parametrize("name", DECODERS)
def test_the_policy_changes_nothing_where_attention_is_dense(name, monkeypatch):
    """A dense block emits no name, so it is recomputed whole, as before."""
    loss, params = _loss_and_params(name, attention="dense", remat=True)
    text = lambda f: re.sub(  # noqa: E731  (the `checkpoint` equation prints its policy)
        r"policy=.*", "policy=", str(jax.make_jaxpr(jax.grad(f))(params)))
    with_policy = text(loss)
    assert "policy=" in with_policy
    assert not any(n in with_policy for n in FLASH_RESIDUAL_NAMES)
    _without_the_policy(monkeypatch, name)
    loss, _ = _loss_and_params(name, attention="dense", remat=True)
    assert text(loss) == with_policy


def test_the_blockwise_fallback_names_nothing_and_still_differentiates():
    """A length the blocks do not tile takes `blockwise_attention`: no kernel,
    no name, the whole block recomputed, the plain path's gradient."""
    ids = FLASH_IDS[:, :30]  # ragged against a block of 8
    loss, params = _loss_and_params("gpt", ids, attention="flash", attention_block=8,
                                    remat=True)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert not _pallas_calls(jaxpr.jaxpr)
    assert not any(n in str(jaxpr) for n in FLASH_RESIDUAL_NAMES)
    plain_loss, _ = _loss_and_params("gpt", ids, attention="flash", attention_block=8,
                                     remat=False)
    for a, b in zip(*(jax.tree.leaves(jax.grad(f)(params)) for f in (loss, plain_loss))):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
