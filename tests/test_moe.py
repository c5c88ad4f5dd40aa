"""MoE / expert-parallel tests (SURVEY.md §2.2 EP row).

Numerics strategy: the dense (no-mesh) path is validated against a brute
-force per-token loop; the expert-sharded path (expert=2 on the 8-device CPU
mesh) must match the dense path bit-for-bit modulo reduction order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel import MeshConfig, build_mesh
from kubeflow_tpu.parallel.moe import MoeMlp, _route


H, F, E, K = 8, 16, 4, 2


def _mk(batch=4, seq=6, seed=0):
    rng = jax.random.PRNGKey(seed)
    x = jax.random.normal(rng, (batch, seq, H), jnp.float32)
    mod = MoeMlp(hidden_size=H, mlp_dim=F, num_experts=E, top_k=K,
                 capacity_factor=4.0)  # ample capacity: no drops
    variables = mod.init(jax.random.PRNGKey(1), x)
    return mod, variables, x


def _brute_force(params, x):
    """Per-token top-k routing computed with plain numpy loops."""
    b, l, h = x.shape
    xt = np.asarray(x, np.float64).reshape(-1, h)
    logits = xt @ np.asarray(params["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[:K]
        gates = probs[t][top] / probs[t][top].sum()
        for gate, e in zip(gates, top):
            y = xt[t] @ np.asarray(params["w_up"][e], np.float64) + np.asarray(
                params["b_up"][e], np.float64
            )
            # flax nn.gelu default is the tanh approximation
            y = 0.5 * y * (1 + np.tanh(np.sqrt(2 / np.pi) * (y + 0.044715 * y**3)))
            y = y @ np.asarray(params["w_down"][e], np.float64) + np.asarray(
                params["b_down"][e], np.float64
            )
            out[t] += gate * y
    return out.reshape(b, l, h)


class TestRouting:
    def test_no_drops_at_ample_capacity(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (12, E))
        combine, dispatch, _ = _route(logits, K, capacity=12 * K)
        # every token keeps exactly K slots with weights summing to 1
        slots = dispatch.sum(axis=(1, 2))
        np.testing.assert_allclose(np.asarray(slots), K)
        np.testing.assert_allclose(
            np.asarray(combine.sum(axis=(1, 2))), 1.0, rtol=1e-5
        )

    def test_capacity_drops_lowest_priority(self):
        # all tokens prefer expert 0 -> only `capacity` of them keep slot 0
        logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (6, 1))
        combine, dispatch, _ = _route(logits, 1, capacity=2)
        kept = np.asarray(dispatch[:, 0, :].sum(axis=-1))
        np.testing.assert_array_equal(kept, [1, 1, 0, 0, 0, 0])

    def test_aux_loss_prefers_balance(self):
        t = 64
        rng = jax.random.PRNGKey(0)
        uniform = jax.random.normal(rng, (t, E)) * 0.01
        skewed = uniform.at[:, 0].add(5.0)  # everything routed to expert 0
        _, _, aux_u = _route(uniform, 1, capacity=t)
        _, _, aux_s = _route(skewed, 1, capacity=t)
        assert float(aux_u) < float(aux_s)
        assert float(aux_u) == pytest.approx(1.0, rel=0.1)


class TestMoeMlp:
    def test_dense_path_matches_brute_force(self):
        mod, variables, x = _mk()
        y = mod.apply(variables, x)
        ref = _brute_force(variables["params"], x)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)

    def test_expert_sharded_matches_dense(self, cpu_devices):
        mod, variables, x = _mk(batch=8, seq=4)
        dense = mod.apply(variables, x)

        mesh = build_mesh(MeshConfig(data=2, fsdp=2, expert=2), cpu_devices[:8])
        with jax.set_mesh(mesh):
            xs = jax.device_put(
                x,
                jax.sharding.NamedSharding(
                    mesh, P(("data", "fsdp", "expert"), None, None)
                ),
            )
            sharded = jax.jit(mod.apply)(variables, xs)
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(dense), rtol=2e-4, atol=2e-4
        )

    def test_local_and_global_dispatch_agree_at_ample_capacity(self, cpu_devices):
        """Per-shard capacity (default) and the GShard-style global pool are
        semantically identical when nothing drops; only the collective shape
        differs (local keeps the routing cumsum shard-local)."""
        mod_l, variables, x = _mk(batch=8, seq=4)
        mod_g = MoeMlp(
            hidden_size=mod_l.hidden_size, mlp_dim=mod_l.mlp_dim,
            num_experts=mod_l.num_experts, top_k=mod_l.top_k,
            capacity_factor=mod_l.capacity_factor, dtype=mod_l.dtype,
            global_dispatch=True,
        )
        mesh = build_mesh(MeshConfig(data=2, fsdp=2, expert=2), cpu_devices[:8])
        with jax.set_mesh(mesh):
            xs = jax.device_put(
                x,
                jax.sharding.NamedSharding(
                    mesh, P(("data", "fsdp", "expert"), None, None)
                ),
            )
            y_local = jax.jit(mod_l.apply)(variables, xs)
            y_global = jax.jit(mod_g.apply)(variables, xs)
        np.testing.assert_allclose(
            np.asarray(y_local), np.asarray(y_global), rtol=2e-4, atol=2e-4
        )

    def test_aux_loss_sown(self):
        mod, variables, x = _mk()
        _, updates = mod.apply(variables, x, mutable=["losses"])
        leaves = jax.tree.leaves(updates["losses"])
        assert len(leaves) == 1 and np.isfinite(float(leaves[0]))


class TestMoeBert:
    def test_bert_moe_trains_on_expert_mesh(self, cpu_devices):
        from kubeflow_tpu.models import BertConfig, BertForSequenceClassification
        from kubeflow_tpu.train import Trainer, TrainerConfig
        from kubeflow_tpu.train.data import synthetic_text_dataset

        cfg = BertConfig.tiny(dropout_rate=0.0, moe_experts=4)
        mesh = build_mesh(MeshConfig(data=2, fsdp=1, expert=2, model=2),
                          cpu_devices[:8])
        bs = 8
        ds = synthetic_text_dataset(n_train=bs * 2, n_test=bs, seq_len=16,
                                    vocab_size=cfg.vocab_size)
        trainer = Trainer(
            BertForSequenceClassification(cfg, num_classes=2),
            TrainerConfig(batch_size=bs, steps=2, log_every_steps=10**9),
            mesh=mesh,
        )
        state = trainer.init_state(ds.x_train[:bs])
        # expert weights must actually be sharded over the expert axis
        wu = state.params["encoder"]["layer_0"]["moe"]["w_up"]
        assert wu.sharding.spec[0] == "expert"
        losses = []
        for _ in range(3):
            state, m = trainer.train_step(
                state, (ds.x_train[:bs], ds.y_train[:bs])
            )
            losses.append(float(m["loss"]))
        assert all(np.isfinite(v) for v in losses)
        assert losses[-1] < losses[0]  # aux + task loss both optimizable


def test_moe_state_checkpoint_roundtrip(tmp_path, cpu_devices):
    """Expert-sharded MoE params must survive orbax save/restore."""
    from kubeflow_tpu.models import BertConfig, BertForSequenceClassification
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_text_dataset

    cfg = BertConfig.tiny(dropout_rate=0.0, moe_experts=4)
    mesh = build_mesh(MeshConfig(data=2, fsdp=1, expert=2, model=2),
                      cpu_devices[:8])
    ds = synthetic_text_dataset(n_train=16, n_test=8, seq_len=16,
                                vocab_size=cfg.vocab_size)
    mk = lambda: Trainer(  # noqa: E731
        BertForSequenceClassification(cfg, num_classes=2),
        TrainerConfig(batch_size=8, steps=1, log_every_steps=10**9,
                      checkpoint_dir=str(tmp_path / "ckpt")),
        mesh=mesh,
    )
    t1 = mk()
    state = t1.init_state(ds.x_train[:8])
    state, _ = t1.train_step(state, (ds.x_train[:8], ds.y_train[:8]))
    t1.checkpointer.save(1, state)
    t1.checkpointer.wait()
    want = np.asarray(state.params["encoder"]["layer_0"]["moe"]["w_up"])

    t2 = mk()
    restored = t2.checkpointer.restore_latest(t2.init_state(ds.x_train[:8]))
    assert restored is not None and restored[0] == 1
    wu = restored[1].params["encoder"]["layer_0"]["moe"]["w_up"]
    np.testing.assert_allclose(np.asarray(wu), want, atol=1e-6)
    assert wu.sharding.spec[0] == "expert"


# ------------------------------------------ the router's raw weights and the balance loss

@pytest.mark.parametrize("top_k,scale", [(2, 1.0), (4, 1.0), (6, 2.5)])
def test_route_without_renormalisation_keeps_the_softmaxs_own_values(top_k, scale):
    """`norm_topk_prob` false: the weights are the chosen probabilities times the scale,
    and sum to less than the scale; renormalised they sum to it."""
    from kubeflow_tpu.parallel.moe import route_softmax

    x = jax.random.normal(jax.random.PRNGKey(4), (10, 32))
    kernel = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    probs = np.asarray(jax.nn.softmax(x @ kernel, -1))
    idx, raw, scores = route_softmax(x, kernel, jnp.zeros((16,)), top_k, scale, renormalise=False)
    np.testing.assert_allclose(scores, probs, rtol=1e-5)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(np.argsort(-probs, -1)[:, :top_k], -1))
    np.testing.assert_allclose(raw, scale * np.take_along_axis(probs, np.asarray(idx), -1), rtol=1e-5)
    assert float(raw.sum(-1).max()) < scale
    same_idx, normed, _ = route_softmax(x, kernel, jnp.zeros((16,)), top_k, scale)
    np.testing.assert_array_equal(same_idx, idx)
    np.testing.assert_allclose(normed.sum(-1), scale, rtol=1e-5)
    np.testing.assert_allclose(normed, raw / (raw.sum(-1, keepdims=True) / scale), rtol=1e-5)


@pytest.mark.parametrize("rows,length,experts,top_k", [(1, 16, 8, 2), (3, 12, 16, 4), (2, 64, 64, 6)])
def test_sequence_balance_loss_equals_a_numpy_loop(rows, length, experts, top_k):
    from kubeflow_tpu.parallel.moe import sequence_balance_loss

    scores = np.asarray(jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(rows), (rows, length, experts)), -1))
    idx = np.argsort(-scores, -1)[..., :top_k]
    want = 0.0
    for r in range(rows):  # f_e = count_e E / (K L), P_e = mean_t p_te, sum_e f_e P_e, mean over rows
        for e in range(experts):
            count = sum(int(e in idx[r, t]) for t in range(length))
            want += count * experts / (top_k * length) * scores[r, :, e].mean() / rows
    got = sequence_balance_loss(jnp.asarray(scores), jnp.asarray(idx), experts)
    assert float(got) == pytest.approx(want, rel=1e-5)
    # a uniform router reads 1; every token on the same K with certainty reads E / K
    uniform = jnp.full((rows, length, experts), 1.0 / experts)
    assert float(sequence_balance_loss(uniform, jnp.asarray(idx), experts)) == pytest.approx(1.0, rel=1e-5)
    certain = jnp.zeros((rows, length, experts)).at[..., :top_k].set(1.0 / top_k)
    first = jnp.broadcast_to(jnp.arange(top_k), (rows, length, top_k))
    assert float(sequence_balance_loss(certain, first, experts)) == pytest.approx(experts / top_k, rel=1e-5)
    # the load carries no gradient: the loss is linear in the scores
    grad = jax.grad(lambda s: sequence_balance_loss(s, jnp.asarray(idx), experts))(jnp.asarray(scores))
    assert float(jnp.abs(grad).max()) > 0 and float((grad * scores).sum()) == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("rate", [0.0, 0.01])
def test_the_layer_sows_its_balance_loss_only_at_a_rate(rate):
    """Rate 0 (the accepted models): nothing is sown and ROUTER_STATE has no `balance_loss`,
    their programs and state trees as they were; at a rate the layer sows rate x the loss
    and keeps the value for the step's counters."""
    from kubeflow_tpu.parallel.moe import (ROUTER_STATE, HeldExpertsMlp, route_softmax,
                                           router_counters, sequence_balance_loss)

    layer = HeldExpertsMlp(hidden_size=32, expert_dim=16, num_experts=8, top_k=2, experts_held=(2, 6),
                           score_func="softmax", num_shared_experts=2, bias_update_rate=0.0,
                           renormalise=False, balance_loss=rate)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(2), x)
    state = {"params": variables["params"], ROUTER_STATE: variables[ROUTER_STATE]}
    _, updates = layer.apply(state, x, True, mutable=[ROUTER_STATE, "losses"])
    counters = router_counters({"layer_1": {"moe": updates[ROUTER_STATE]}})
    if not rate:
        assert "losses" not in updates and "losses" not in variables
        assert set(updates[ROUTER_STATE]) == {"bias", "counts", "rows_here"}
        assert "moe_balance_loss" not in counters
        return
    idx, _, scores = route_softmax(x.reshape(-1, 32), variables["params"]["router"], jnp.zeros((8,)), 2, 1.0, False)
    want = rate * float(sequence_balance_loss(scores.reshape(2, 24, 8), idx.reshape(2, 24, 2), 8))
    assert float(updates["losses"]["moe_balance"]) == pytest.approx(want, rel=1e-5) and want > 0.5 * rate
    assert float(updates[ROUTER_STATE]["balance_loss"]) == pytest.approx(want, rel=1e-5)
    assert float(counters["moe_balance_loss"]) == pytest.approx(want, rel=1e-5)
    # evaluation sows it too where the caller collects it, and moves no state
    _, sown = layer.apply(state, x, False, mutable=["losses"])
    assert float(sown["losses"]["moe_balance"]) == pytest.approx(want, rel=1e-5)
