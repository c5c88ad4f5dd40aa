"""kftpu-partition suite (docs/partitioner.md).

Covers the three-tier derivation (explicit path rules > logical axis
rules > FSDP heuristic), the per-dim spec-fits-mesh fallback, round-trip
compatibility with the legacy sharding.state_pspec wrappers on the REAL
GPT/BERT param trees, the hybrid DCN×ICI mesh guard, layout-invariant
init (deterministic_rng), the bf16-by-default resolution + pinned
numerics gate, and buffer-donation accounting on the lowered step.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel import MeshConfig, Partitioner, build_mesh
from kubeflow_tpu.parallel.mesh import AXIS_FSDP, AXIS_MODEL
from kubeflow_tpu.parallel import partitioner as pt_mod
from kubeflow_tpu.parallel.sharding import (
    fsdp_param_pspec,
    state_pspec,
    state_shardings,
)

pytestmark = pytest.mark.partition


@pytest.fixture(scope="module")
def mesh222():
    return build_mesh(MeshConfig(data=2, fsdp=2, model=2))


class TestDerivation:
    def test_rule_matching_precedence(self, mesh222):
        """Explicit path specs beat the logical tier; the logical tier
        beats the heuristic; unmatched paths fall to the heuristic."""
        explicit = [(r"query/kernel$", P(None, AXIS_FSDP))]
        pt = Partitioner(mesh=mesh222, path_specs=explicit)
        # explicit wins even though the logical tier also matches
        assert pt.spec_for("h0/attn/query/kernel", (64, 64)) == \
            P(None, AXIS_FSDP)
        # logical tier: ("embed","heads") -> (fsdp, model)
        assert pt.spec_for("h0/attn/key/kernel", (64, 64)) == \
            P(AXIS_FSDP, AXIS_MODEL)
        # no tier matches: FSDP heuristic shards the largest divisible dim
        assert pt.spec_for("some/opaque/w", (128, 64)) == P(AXIS_FSDP, None)
        # heuristic's min_size gate: tiny params replicate
        assert pt.spec_for("some/opaque/b", (16,)) == P()

    def test_logical_rules_first_match_wins_and_tensor_alias(self, mesh222):
        pt = Partitioner(mesh=mesh222, logical_rules=(
            ("embed", "tensor"),      # shadows the default embed->fsdp
            ("embed", AXIS_FSDP),
            ("heads", None),
        ))
        assert pt.mesh_axes_for("embed") == AXIS_MODEL  # alias resolved
        assert pt.mesh_axes_for("heads") is None
        assert pt.spec_for("h0/attn/query/kernel", (64, 64)) == \
            P(AXIS_MODEL, None)

    def test_unknown_logical_name_replicates_unless_strict(self, mesh222):
        pt = Partitioner(mesh=mesh222, path_logical=(
            (r"odd/kernel$", ("nosuch", "embed")),))
        assert pt.spec_for("odd/kernel", (64, 64)) == P(None, AXIS_FSDP)
        strict = Partitioner(mesh=mesh222, strict=True, path_logical=(
            (r"odd/kernel$", ("nosuch", "embed")),))
        with pytest.raises(ValueError, match="nosuch"):
            strict.spec_for("odd/kernel", (64, 64))

    def test_spec_fits_mesh_fallback_replicates_per_dim(self, mesh222):
        """A named dim that does not divide its mesh axis REPLICATES
        (per-dim), keeping the dims that do fit — while the legacy
        state_pspec wrapper keeps its all-or-nothing contract (whole
        rule dropped, heuristic takes over)."""
        pt = Partitioner(mesh=mesh222)
        # dim0=6 not divisible by fsdp=2... it is; use 3: 3 % 2 != 0
        spec = pt.spec_for("h0/attn/query/kernel", (3, 64))
        assert spec == P(None, AXIS_MODEL)  # embed dim dropped, heads kept
        # rank mismatch replicates entirely at the explicit tier
        pt2 = Partitioner(mesh=mesh222,
                          path_specs=[(r"w$", P(AXIS_FSDP, AXIS_MODEL))])
        assert pt2.spec_for("deep/w", (8,)) == P()
        # legacy wrapper: non-fitting rule falls through to the heuristic
        legacy = state_pspec("h0/attn/query/kernel", (3, 64 * 128),
                             mesh222,
                             [(r"query/kernel$", P(AXIS_FSDP, None))])
        assert legacy == P(None, AXIS_FSDP)  # heuristic, largest dim

    def test_wrappers_delegate_unchanged(self, mesh222):
        """The thin sharding.py wrappers keep their historical outputs."""
        assert fsdp_param_pspec((128, 64), 2) == P(AXIS_FSDP, None)
        assert fsdp_param_pspec((128, 64), 1) == P()
        assert fsdp_param_pspec((16,), 2) == P()  # min_size gate
        assert state_pspec("a/b", (), mesh222, None) == P()


class TestRoundTripCompat:
    """Partitioner-derived shardings == the legacy state_pspec path on
    the real model trees, both via explicit rules and via the logical
    tier alone (which must subsume the hand-written tables)."""

    def _tree_specs(self, model, sample, mesh, rules):
        kwargs = {}
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), sample, **kwargs))
        params = variables["params"]
        legacy = state_shardings(params, mesh, rules)
        pt = Partitioner(mesh=mesh, path_specs=rules)
        mine = pt.state_shardings(params)
        logical = Partitioner(mesh=mesh).state_shardings(params)
        return params, legacy, mine, logical

    @pytest.mark.parametrize("family", ["gpt", "bert"])
    def test_gpt_bert_param_trees(self, family, mesh222):
        if family == "gpt":
            from kubeflow_tpu.models.gpt import (
                GPTConfig, GPTLM, PARTITION_RULES)

            cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=2, mlp_dim=64, max_len=16)
            model = GPTLM(cfg)
            sample = jnp.ones((2, 8), jnp.int32)
        else:
            from kubeflow_tpu.models.bert import (
                BertConfig, BertForSequenceClassification,
                PARTITION_RULES)

            cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=2, mlp_dim=64, max_len=16)
            model = BertForSequenceClassification(cfg, num_classes=2)
            sample = jnp.ones((2, 8), jnp.int32)
        import re

        params, legacy, mine, logical = self._tree_specs(
            model, sample, mesh222, PARTITION_RULES)
        flat_legacy = jax.tree_util.tree_leaves_with_path(legacy)
        flat_mine = dict(jax.tree_util.tree_leaves_with_path(mine))
        flat_logical = dict(jax.tree_util.tree_leaves_with_path(logical))
        assert flat_legacy, "empty param tree"

        def norm(spec):  # trailing replicated dims are layout-identical
            t = tuple(spec)
            while t and t[-1] is None:
                t = t[:-1]
            return t

        rule_hits = 0
        for path, sh in flat_legacy:
            ps = pt_mod.path_str_of(path)
            # explicit tier: the partitioner with the model's table is
            # the legacy derivation, leaf for leaf
            assert norm(flat_mine[path].spec) == norm(sh.spec), (
                f"explicit-tier mismatch at {ps}: "
                f"{flat_mine[path].spec} != {sh.spec}")
            if not any(re.search(pat, ps) for pat, _ in PARTITION_RULES):
                continue
            if re.search(r"attn_out/kernel$", ps):
                # documented divergence: the legacy partial-rank rule
                # P(model, fsdp) lands fsdp on head_dim; the logical
                # tier places it on the output embed dim (the T5X/
                # Megatron row-parallel shape) — pin the new placement
                assert norm(flat_logical[path].spec) == (
                    AXIS_MODEL, None, AXIS_FSDP)
                continue
            rule_hits += 1
            assert norm(flat_logical[path].spec) == norm(sh.spec), (
                f"logical-tier mismatch at {ps}: "
                f"{flat_logical[path].spec} != {sh.spec}")
        assert rule_hits >= 6, "round-trip test matched too few params"


class TestHybridMesh:
    def test_multislice_shape_and_dcn_guard(self):
        """The hybrid DCN×ICI construction is folded into the
        partitioner: data-like outer axes span slices, and an ICI-class
        axis straddling the DCN boundary is rejected (the
        build_multislice_mesh guard, now reachable via num_slices)."""
        pt = Partitioner(mesh_config=MeshConfig(data=2, fsdp=2, model=2),
                         num_slices=2)
        shape = dict(pt.mesh.shape)
        assert shape["data"] * shape["fsdp"] % 2 == 0
        assert shape["model"] == 2
        # slice boundary inside the model axis: guard fires
        with pytest.raises(ValueError, match="DCN"):
            Partitioner(mesh_config=MeshConfig(data=1, fsdp=1, model=-1),
                        num_slices=2)

    def test_key_fields_move_with_rules(self, mesh222):
        a = Partitioner(mesh=mesh222)
        b = Partitioner(mesh=mesh222, logical_rules=(
            ("embed", "tensor"),) + tuple(
                pt_mod.DEFAULT_LOGICAL_AXIS_RULES))
        assert a.key_fields() != b.key_fields()
        c = Partitioner(mesh=mesh222,
                        path_specs=[(r"x$", P(AXIS_FSDP))])
        assert a.key_fields() != c.key_fields()
        assert a.key_fields() == Partitioner(mesh=mesh222).key_fields()


class TestDeterministicRng:
    def test_sharded_init_bits_match_unsharded(self, mesh222):
        """The fsdp-vs-single root cause, pinned at partitioner level:
        legacy threefry draws DIFFERENT bits when the generator is
        partitioned; under deterministic_rng every layout draws the
        same. (Trainer.init_state runs inside this context — the
        trainer-level proof is test_trainer.py's fsdp-vs-single test.)"""
        from jax.sharding import NamedSharding

        key = jax.random.PRNGKey(42)
        init = jax.nn.initializers.lecun_normal()
        sh = NamedSharding(mesh222, P(AXIS_FSDP, None))
        pt = Partitioner(mesh=mesh222)
        with pt.deterministic_rng():
            plain = jax.jit(lambda: init(key, (128, 64), jnp.float32))()
            constrained = jax.jit(
                lambda: jax.lax.with_sharding_constraint(
                    init(key, (128, 64), jnp.float32), sh))()
        np.testing.assert_array_equal(np.asarray(plain),
                                      np.asarray(constrained))


class TestTrainerIntegration:
    def _ds(self, n=64, features=64):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((n, features)).astype(np.float32)
        y = rng.integers(0, 10, size=n).astype(np.int32)
        return x, y

    def test_grad_specs_match_param_specs(self, mesh222):
        from kubeflow_tpu.models.gpt import (GPTConfig, GPTLM,
                                             PARTITION_RULES)

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, mlp_dim=64, max_len=16)
        params = jax.eval_shape(
            lambda: GPTLM(cfg).init(jax.random.PRNGKey(0),
                                    jnp.ones((2, 8), jnp.int32)))["params"]
        pt = Partitioner(mesh=mesh222, path_specs=PARTITION_RULES)
        specs = dict(jax.tree_util.tree_leaves_with_path(
            pt.grad_specs(params)))
        shards = dict(jax.tree_util.tree_leaves_with_path(
            pt.state_shardings(params)))
        for path, spec in specs.items():
            assert spec == shards[path].spec

    def test_donation_zero_unexpected_copies(self):
        """The fused donated optimizer contract: every state leaf at or
        above the donation threshold — the params/opt-state weights whose
        double-buffering is the HBM cost — aliases an output buffer in
        the lowered single step AND the k-scan: zero unexpected copies
        (sub-threshold bias/scale buffers are backend packing discretion,
        reported as unaliased_small)."""
        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.train import Trainer, TrainerConfig

        t = Trainer(MnistMLP(hidden=(16,)),
                    TrainerConfig(batch_size=8, log_every_steps=10**9),
                    mesh=build_mesh(MeshConfig(data=4, fsdp=2)))
        x, y = self._ds(8)
        stats = t.donation_stats(x, y, fused_k=2)
        for kind, st in stats.items():
            assert st["unexpected_copies"] == 0, (kind, st)
            assert 0 < st["aliased"] <= st["state_leaves"]
            assert st["aliased"] + st["unexpected_copies"] \
                + st["unaliased_small"] == st["state_leaves"]

    def test_donation_holds_on_sharded_gpt_tree(self):
        """The case that motivated the leaf-mapped accounting: on an
        fsdp×model mesh every matmul-class GPT leaf (kernels, embeddings,
        their adam mirrors) still aliases — only sub-page bias/scale
        buffers are left to allocator discretion."""
        from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
        from kubeflow_tpu.train import Trainer, TrainerConfig

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, mlp_dim=64, max_len=16,
                        dropout_rate=0.0)
        rng = np.random.default_rng(5)
        x = rng.integers(1, 64, size=(8, 16)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        t = Trainer(GPTLM(cfg),
                    TrainerConfig(batch_size=8, log_every_steps=10**9),
                    mesh=build_mesh(MeshConfig(data=2, fsdp=2, model=2)))
        (st,) = t.donation_stats(x, y).values()
        assert st["unexpected_copies"] == 0, st["unaliased_big"]
        assert st["aliased"] > st["state_leaves"] // 2

    def test_executable_key_absorbs_rules_and_dtype(self):
        """PR-10's restart-warm guarantee survives: the content key moves
        when the partitioner rules or the resolved compute dtype move,
        and stays put otherwise."""
        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.train import Trainer, TrainerConfig

        x, y = self._ds(8)
        mesh = build_mesh(MeshConfig(data=4, fsdp=2))

        def key_of(**kw):
            cfg = TrainerConfig(batch_size=8, log_every_steps=10**9,
                                compute_dtype=kw.pop("compute_dtype",
                                                     None))
            t = Trainer(MnistMLP(hidden=(16,)), cfg, mesh=mesh, **kw)
            return t._executable_key((x[:8], y[:8]), kind="train_step")

        base = key_of()
        assert base == key_of()
        assert base != key_of(compute_dtype=jnp.bfloat16)
        alt = Partitioner(mesh=mesh, logical_rules=(
            ("embed", "tensor"),) + tuple(
                pt_mod.DEFAULT_LOGICAL_AXIS_RULES))
        assert base != key_of(partitioner=alt)

    def test_bf16_auto_resolution_and_opt_out(self):
        """bf16-by-default policy: MXU-heavy families resolve AUTO to
        bfloat16 on accelerator backends (module compute dtype flipped,
        params f32), CPU keeps f32, and an explicit float32 is the
        documented opt-out everywhere."""
        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
        from kubeflow_tpu.train import Trainer, TrainerConfig

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, mlp_dim=64, max_len=16)
        model = GPTLM(cfg)
        auto = TrainerConfig()
        m2, dt = Trainer.resolve_compute_dtype(model, auto, backend="tpu")
        assert dt == jnp.bfloat16 and m2.cfg.dtype == jnp.bfloat16
        _, dt_cpu = Trainer.resolve_compute_dtype(model, auto,
                                                  backend="cpu")
        assert dt_cpu == jnp.float32
        # explicit f32 opt-out is honored verbatim on any backend
        m3, dt3 = Trainer.resolve_compute_dtype(
            model, TrainerConfig(compute_dtype=jnp.float32),
            backend="tpu")
        assert dt3 == jnp.float32 and m3 is model
        # preference-less models stay f32 under AUTO
        _, dt4 = Trainer.resolve_compute_dtype(MnistMLP(), auto,
                                               backend="tpu")
        assert dt4 == jnp.float32

    def test_bf16_numerics_pinned_against_f32(self):
        """The pinned-numerics gate: the SAME tiny GPT trained with the
        family's resolved bf16 (the accelerator policy, exercised on
        CPU) tracks the f32 loss trajectory within a golden tolerance,
        keeps a finite grad norm every step, and lands eval metrics
        within tolerance of f32's."""
        from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
        from kubeflow_tpu.train import Trainer, TrainerConfig
        from kubeflow_tpu.train.data import Dataset

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, mlp_dim=64, max_len=16,
                        dropout_rate=0.0)
        rng = np.random.default_rng(5)
        x = rng.integers(1, 64, size=(64, 16)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        ds = Dataset(x_train=x, y_train=y, x_test=x[:16], y_test=y[:16],
                     num_classes=64)

        def run(dtype_policy):
            model = GPTLM(cfg)
            tc = TrainerConfig(batch_size=16, steps=6, seed=1,
                               learning_rate=1e-3, log_every_steps=10**9)
            if dtype_policy == "bf16":
                model, dt = Trainer.resolve_compute_dtype(
                    model, TrainerConfig(), backend="tpu")
                assert dt == jnp.bfloat16
                tc.compute_dtype = dt
            else:
                tc.compute_dtype = jnp.float32
            t = Trainer(model, tc)
            state = t.init_state(ds.x_train[:16])
            losses, gnorms = [], []
            for i in range(6):
                b = (ds.x_train[(i % 4) * 16:((i % 4) + 1) * 16],
                     ds.y_train[(i % 4) * 16:((i % 4) + 1) * 16])
                state, m = t.train_step(state, b)
                losses.append(float(m["loss"]))
                gnorms.append(float(m["grad_norm"]))
            ev = t.evaluate(state, ds)
            return losses, gnorms, ev

        f32_losses, f32_gnorms, f32_ev = run("f32")
        bf_losses, bf_gnorms, bf_ev = run("bf16")
        assert all(np.isfinite(g) for g in bf_gnorms), bf_gnorms
        # golden tolerance: bf16 has ~3 decimal digits; a healthy tiny-GPT
        # trajectory stays within 5% relative of f32 step for step
        for a, b in zip(f32_losses, bf_losses):
            assert abs(a - b) / max(abs(a), 1e-6) < 0.05, (
                f32_losses, bf_losses)
        assert abs(f32_ev["loss"] - bf_ev["loss"]) < 0.2
        assert abs(f32_ev["accuracy"] - bf_ev["accuracy"]) < 0.15
