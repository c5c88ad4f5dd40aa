"""Context-parallel attention numerics: ring/ulysses/blockwise/flash must all
match dense attention to tight tolerance, including padding bias and grads."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.bert import dense_attention
from kubeflow_tpu.parallel import MeshConfig, build_mesh
from kubeflow_tpu.parallel.attention_mask import Causal, mask_of
from kubeflow_tpu.parallel.ring_attention import (
    blockwise_attention,
    flash_attention,
    ring_attention,
    ulysses_attention,
)

B, L, H, D = 2, 64, 4, 16


def make_inputs(seed=0, pad_tail=12):
    rng = np.random.RandomState(seed)
    q, k, v = (
        jnp.asarray(rng.normal(0, 1, (B, L, H, D)).astype(np.float32))
        for _ in range(3)
    )
    mask = np.ones((B, L), bool)
    mask[:, L - pad_tail:] = False
    bias = jnp.asarray(np.where(mask[:, None, None, :], 0.0, -1e9).astype(np.float32))
    return q, k, v, bias


def test_blockwise_matches_dense():
    q, k, v, bias = make_inputs()
    expected = dense_attention(q, k, v, bias)
    got = blockwise_attention(q, k, v, bias, block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_blockwise_grads_match_dense():
    q, k, v, bias = make_inputs()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, bias) ** 2).sum()

    def loss_block(q, k, v):
        return (blockwise_attention(q, k, v, bias, block=16) ** 2).sum()

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize(
    "attn,mcfg",
    [
        (ring_attention, MeshConfig(data=1, context=4, model=2)),
        (ulysses_attention, MeshConfig(data=2, context=4, model=1)),
    ],
)
def test_context_parallel_matches_dense(attn, mcfg):
    q, k, v, bias = make_inputs()
    expected = dense_attention(q, k, v, bias)
    mesh = build_mesh(mcfg)
    with jax.set_mesh(mesh):
        got = jax.jit(attn)(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("attn", [ring_attention, ulysses_attention])
def test_context_parallel_grads(attn):
    q, k, v, bias = make_inputs()

    def loss_ref(q, k, v):
        return (dense_attention(q, k, v, bias) ** 2).sum()

    gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    mesh = build_mesh(MeshConfig(data=2, context=4))
    with jax.set_mesh(mesh):

        def loss_cp(q, k, v):
            return (attn(q, k, v, bias) ** 2).sum()

        gc = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gd, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_flash_attention_matches_dense():
    q, k, v, bias = make_inputs()
    expected = dense_attention(q, k, v, bias)
    got = jax.jit(functools.partial(flash_attention, block=16))(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_flash_attention_grad():
    q, k, v, bias = make_inputs()

    def loss_ref(q, k, v):
        return (dense_attention(q, k, v, bias) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, bias, block=16) ** 2).sum()

    gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("mcfg", [
    MeshConfig(data=8),
    MeshConfig(data=1, fsdp=2, model=4),
    MeshConfig(data=2, context=2, model=2),
])
def test_flash_under_a_mesh_runs_per_device(mcfg):
    """Mosaic kernels cannot be partitioned automatically — on the chip a
    flash step under any mesh of more than one device is refused unless
    the call sits in a shard_map. Each device runs the kernel on its batch
    rows and heads with the sequence whole; gradients (dbias summed over
    the head shards) match the unpartitioned kernel."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (8, L, H, D)).astype(np.float32))
               for _ in range(3))
    bias = jnp.asarray(rng.normal(0, 1, (8, 1, 1, L)).astype(np.float32))

    def loss(attn, q, k, v, bias):
        return (attn(q, k, v, bias, causal=True) ** 2).sum()

    flash = functools.partial(flash_attention, block=16)
    # the reference is the same kernel with no mesh: one device, no
    # shard_map (the dense-attention agreement is pinned above)
    want = jax.jit(jax.grad(functools.partial(loss, flash),
                            argnums=(0, 1, 2, 3)))(q, k, v, bias)
    with jax.set_mesh(build_mesh(mcfg)):
        step = jax.jit(jax.grad(functools.partial(loss, flash),
                                argnums=(0, 1, 2, 3)))
        assert "shard_map" in str(jax.make_jaxpr(
            functools.partial(flash, causal=True))(q, k, v, bias))
        got = step(q, k, v, bias)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_bert_with_ring_attention_trains():
    from kubeflow_tpu.models import BertConfig, BertForSequenceClassification
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_text_dataset

    cfg = BertConfig.tiny(dropout_rate=0.0, attention="ring", attention_block=16)
    ds = synthetic_text_dataset(n_train=64, n_test=16, seq_len=32,
                                vocab_size=cfg.vocab_size)
    mesh = build_mesh(MeshConfig(data=2, context=2, model=2))
    trainer = Trainer(
        BertForSequenceClassification(cfg, num_classes=2),
        TrainerConfig(batch_size=8, log_every_steps=10**9),
        mesh=mesh,
    )
    state = trainer.init_state(ds.x_train[:8])
    state, m = trainer.train_step(state, (ds.x_train[:8], ds.y_train[:8]))
    assert np.isfinite(float(m["loss"]))


def test_bert_ring_matches_dense_bert():
    from kubeflow_tpu.models import BertConfig, BertForSequenceClassification
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_text_dataset

    losses = {}
    for kind, mcfg in [
        ("dense", MeshConfig(data=1)),
        ("ring", MeshConfig(data=2, context=4)),
        ("ulysses", MeshConfig(data=2, context=4)),
    ]:
        cfg = BertConfig.tiny(dropout_rate=0.0, attention=kind, attention_block=16)
        ds = synthetic_text_dataset(n_train=32, n_test=8, seq_len=32,
                                    vocab_size=cfg.vocab_size)
        devices = jax.devices()[:1] if kind == "dense" else None
        mesh = build_mesh(mcfg, devices)
        trainer = Trainer(
            BertForSequenceClassification(cfg, num_classes=2),
            TrainerConfig(batch_size=8, log_every_steps=10**9),
            mesh=mesh,
        )
        state = trainer.init_state(ds.x_train[:8])
        _, m = trainer.train_step(state, (ds.x_train[:8], ds.y_train[:8]))
        losses[kind] = float(m["loss"])
    assert losses["dense"] == pytest.approx(losses["ring"], rel=1e-3)
    assert losses["dense"] == pytest.approx(losses["ulysses"], rel=1e-3)


class TestFlashFusedBackward:
    """The pallas backward kernels (dq/dk/dv/dbias from the saved logsumexp)
    must match the dense reference exactly — incl. the bias cotangent and
    the causal path."""

    def _qkvb(self, lq=32, lk=32):
        import jax as _jax

        ks = _jax.random.split(_jax.random.PRNGKey(7), 4)
        q = _jax.random.normal(ks[0], (2, lq, 4, 16), jnp.float32)
        k = _jax.random.normal(ks[1], (2, lk, 4, 16), jnp.float32)
        v = _jax.random.normal(ks[2], (2, lk, 4, 16), jnp.float32)
        bias = _jax.random.normal(ks[3], (2, 1, 1, lk), jnp.float32) * 0.3
        return q, k, v, bias

    def test_grads_incl_bias_match_dense(self):
        import functools as _ft

        from kubeflow_tpu.models.bert import dense_attention

        q, k, v, bias = self._qkvb()

        def loss(attn, q, k, v, bias):
            return (attn(q, k, v, bias) ** 2).sum()

        want = jax.grad(_ft.partial(loss, dense_attention),
                        argnums=(0, 1, 2, 3))(q, k, v, bias)
        got = jax.jit(jax.grad(
            _ft.partial(loss, _ft.partial(flash_attention, block=8)),
            argnums=(0, 1, 2, 3),
        ))(q, k, v, bias)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=name,
            )

    def test_causal_grads_match_dense(self):
        import functools as _ft

        from kubeflow_tpu.models.gpt import causal_dense_attention

        q, k, v, bias = self._qkvb()

        def loss(attn, q, k, v):
            return (attn(q, k, v, bias) ** 2).sum()

        want = jax.grad(
            _ft.partial(loss, causal_dense_attention), argnums=(0, 1, 2)
        )(q, k, v)
        got = jax.jit(jax.grad(
            _ft.partial(
                loss, _ft.partial(flash_attention, block=8, causal=True)
            ),
            argnums=(0, 1, 2),
        ))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=name,
            )

    def test_fused_path_is_taken(self):
        """Divisible shapes must save the lse residual (fused backward)."""
        from kubeflow_tpu.parallel.ring_attention import _flash_fwd

        q, k, v, bias = self._qkvb()
        _, res = _flash_fwd(q, k, v, bias, 8, 8, None)
        assert res[5] is not None  # lse saved -> pallas bwd path
        # ragged shapes fall back to the recomputing path
        _, res = _flash_fwd(q[:, :30], k, v, bias, 8, 8, None)
        assert res[5] is None


class TestFlashBackwardImpls:
    """All backward implementations ("scratch": pallas with
    cross-grid-step VMEM accumulators; "loop": pallas fori_loop per
    output block; "loop2": loop with D recomputed in-kernel from (dO, O)
    instead of the lane-dim-1 dd operand, the r4 Mosaic-NaN fix
    candidate; "xla": residual-consuming einsums, the Mosaic-safe
    default after both r3 pallas variants NaN'd in the r3 hardware
    verdict) must agree with each other and the dense reference, causal
    and full."""

    def _qkvb(self, lq=32, lk=32):
        import jax as _jax

        ks = _jax.random.split(_jax.random.PRNGKey(11), 5)
        q = _jax.random.normal(ks[0], (2, lq, 4, 16), jnp.float32)
        k = _jax.random.normal(ks[1], (2, lk, 4, 16), jnp.float32)
        v = _jax.random.normal(ks[2], (2, lk, 4, 16), jnp.float32)
        bias = _jax.random.normal(ks[3], (2, 1, 1, lk), jnp.float32) * 0.3
        g = _jax.random.normal(ks[4], (2, lq, 4, 16), jnp.float32)
        return q, k, v, bias, g

    @pytest.mark.parametrize("causal", [False, True])
    def test_all_impls_agree(self, causal):
        from kubeflow_tpu.parallel.ring_attention import (
            _flash_backward,
            _flash_forward,
        )

        q, k, v, bias, g = self._qkvb()
        mask = mask_of(causal)
        out, lse = _flash_forward(q, k, v, bias, 8, 8, mask, want_lse=True)
        grads = {
            impl: _flash_backward(q, k, v, bias, out, lse, g, 8, 8, mask,
                                  impl=impl)
            for impl in ("scratch", "loop", "loop2", "ddpre", "xla")
        }
        ref = grads["scratch"]
        for impl in ("loop", "loop2", "ddpre", "xla"):
            for name, x, y in zip(("dq", "dk", "dv", "dbias"),
                                  ref, grads[impl]):
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5,
                    err_msg=f"{impl}:{name}",
                )

    def test_default_is_xla_until_pallas_passes_on_hardware(self):
        from kubeflow_tpu.parallel import ring_attention as ra

        assert ra.FLASH_BWD_IMPL == "xla"

    def test_unknown_impl_fails_fast(self):
        """A typo'd impl (or env override) must raise, not fall through to
        the scratch kernels that NaN on Mosaic."""
        import subprocess
        import sys

        from kubeflow_tpu.parallel.ring_attention import (
            _flash_backward,
            _flash_forward,
        )

        q, k, v, bias, g = (x.astype(jnp.float32) for x in self._qkvb())
        out, lse = _flash_forward(q, k, v, bias, 8, 8, None, want_lse=True)
        with pytest.raises(ValueError, match="unknown flash backward"):
            _flash_backward(q, k, v, bias, out, lse, g, 8, 8, None,
                            impl="Loop2")
        # the env override is validated at import
        proc = subprocess.run(
            [sys.executable, "-c",
             "import kubeflow_tpu.parallel.ring_attention"],
            capture_output=True, text=True, timeout=240,
            env={"KFT_FLASH_BWD_IMPL": "loop3", "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": ".", "PATH": "/usr/bin:/bin",
                 "HOME": "/root"},
        )
        assert proc.returncode != 0
        assert "KFT_FLASH_BWD_IMPL" in proc.stderr


class TestSlidingWindowFlash:
    """window > 0 (Mistral sliding window): flash fwd/bwd vs the dense
    windowed reference across window/block geometries — window smaller
    than a block, spanning blocks, and larger than the sequence (== plain
    causal)."""

    def _qkvbg(self, l=64):
        import jax as _jax

        ks = _jax.random.split(_jax.random.PRNGKey(3), 5)
        q = _jax.random.normal(ks[0], (2, l, 4, 16), jnp.float32)
        k = _jax.random.normal(ks[1], (2, l, 4, 16), jnp.float32)
        v = _jax.random.normal(ks[2], (2, l, 4, 16), jnp.float32)
        bias = _jax.random.normal(ks[3], (2, 1, 1, l), jnp.float32) * 0.3
        g = _jax.random.normal(ks[4], (2, l, 4, 16), jnp.float32)
        return q, k, v, bias, g

    def _dense_ref(self, q, k, v, bias, window):
        from kubeflow_tpu.models.gpt import causal_dense_attention

        return causal_dense_attention(q, k, v, bias[:, :, :, :],
                                      window=window)

    @pytest.mark.parametrize("window,block", [
        (5, 8),     # window inside a block
        (12, 8),    # window spans blocks
        (1, 8),     # degenerate: self-attention only
        (999, 8),   # wider than the sequence == plain causal
        (10, 16),
    ])
    def test_forward_matches_dense_window_reference(self, window, block):
        from kubeflow_tpu.parallel.ring_attention import flash_attention

        q, k, v, bias, _ = self._qkvbg()
        got = flash_attention(q, k, v, bias, block=block, causal=True,
                              window=window)
        want = self._dense_ref(q, k, v, bias, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("impl", ["xla", "loop", "loop2", "ddpre", "scratch"])
    @pytest.mark.parametrize("window", [5, 12])
    def test_all_backward_impls_match_dense_grads(self, impl, window):
        from kubeflow_tpu.parallel import ring_attention as ra
        from kubeflow_tpu.parallel.ring_attention import flash_attention

        q, k, v, bias, g = self._qkvbg()

        def loss_flash(q, k, v, bias):
            return (flash_attention(q, k, v, bias, block=8, causal=True,
                                    window=window) * g).sum()

        def loss_dense(q, k, v, bias):
            return (self._dense_ref(q, k, v, bias, window) * g).sum()

        old = ra.FLASH_BWD_IMPL
        try:
            ra.FLASH_BWD_IMPL = impl
            got = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        finally:
            ra.FLASH_BWD_IMPL = old
        want = jax.grad(loss_dense, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5,
                err_msg=f"{impl}:{name}")

    def test_window_requires_causal(self):
        from kubeflow_tpu.parallel.ring_attention import (
            blockwise_attention,
            flash_attention,
        )

        q, k, v, bias, _ = self._qkvbg(l=16)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, bias, causal=False, window=4)
        with pytest.raises(ValueError, match="causal"):
            blockwise_attention(q, k, v, bias, causal=False, window=4)

    @pytest.mark.parametrize("lq,lk,block,causal,want", [
        (1024, 1024, 128, True, (128, 256)),   # gpt2m-train-1k
        (8192, 8192, 128, True, (512, 512)),   # trinitym-train-8k
        (2048, 2048, 128, True, (256, 512)),   # eight query blocks, four KV blocks
        (4096, 4096, 128, True, (512, 512)),
        (16384, 16384, 128, True, (512, 512)),  # no wider than the widest timed
        (8192, 8192, 256, True, (512, 512)),
        (512, 512, 128, True, (128, 128)),     # never under the caller's block
        (6144, 6144, 128, True, (512, 512)),   # multiples of the caller's block that divide
        (1536, 1536, 128, True, (128, 384)),
        (512, 2048, 128, True, (128, 512)),    # lq != lk
        (64, 64, 8, True, (8, 16)), (256, 256, 8, True, (32, 64)),
        (32, 32, 32, True, (32, 32)),
        # not causal: all the queries a KV block, as the parent walked
        (512, 512, 128, False, (512, 128)),    # BERT at 512
        (256, 256, 128, False, (256, 128)),    # ViT at 256 patches
        (8192, 8192, 128, False, (8192, 512)),
        (32, 64, 16, False, (32, 16)),
    ])
    def test_the_xla_backwards_blocks_follow_the_shapes(self, lq, lk, block, causal, want):
        from kubeflow_tpu.parallel.ring_attention import flash_backward_xla_blocks

        got = flash_backward_xla_blocks(lq, lk, block, block, mask_of(causal))
        assert got == want
        assert lq % got[0] == 0 and lk % got[1] == 0
        assert got[0] % min(block, lq) == 0 and got[1] % block == 0

    @pytest.mark.parametrize("window", [0, 40])
    def test_xla_backward_at_a_block_the_rule_widened_matches_dense_grads(self, window):
        """256 keys at the caller's block of 8: the rule takes 32 x 64, eight
        query blocks and four KV blocks, and the window hides pairs."""
        from kubeflow_tpu.parallel.ring_attention import flash_attention

        q, k, v, bias, g = self._qkvbg(l=256)

        def loss_flash(q, k, v, bias):
            return (flash_attention(q, k, v, bias, block=8, causal=True,
                                    window=window) * g).sum()

        def loss_dense(q, k, v, bias):
            return (self._dense_ref(q, k, v, bias, window) * g).sum()

        got = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        want = jax.grad(loss_dense, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4, err_msg=name)

    @pytest.mark.parametrize("attn", [ring_attention, ulysses_attention])
    @pytest.mark.parametrize("window", [5, 20, 40])
    def test_context_parallel_window_matches_dense(self, attn, window):
        """Ring/Ulysses sliding window vs the dense windowed reference on a
        4-shard context mesh — windows inside one shard (16 local), across
        shards, and spanning most of the sequence. On the ring a static
        window also SHORTENS the ring (fewer ppermute hops)."""
        from kubeflow_tpu.models.gpt import causal_dense_attention

        q, k, v, bias, _ = self._qkvbg()
        want = causal_dense_attention(q, k, v, bias, window=window)
        mesh = build_mesh(MeshConfig(data=2, context=4))
        with jax.set_mesh(mesh):
            got = jax.jit(functools.partial(
                attn, causal=True, window=window))(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_window_grads_match_dense(self):
        from kubeflow_tpu.models.gpt import causal_dense_attention

        q, k, v, bias, g = self._qkvbg()

        def loss_ref(q, k, v, bias):
            return (causal_dense_attention(q, k, v, bias, window=10)
                    * g).sum()

        want = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        mesh = build_mesh(MeshConfig(data=2, context=4))
        with jax.set_mesh(mesh):

            def loss_ring(q, k, v, bias):
                return (ring_attention(q, k, v, bias, causal=True,
                                       window=10) * g).sum()

            got = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2, 3)))(
                q, k, v, bias)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=name)

    def test_ring_hop_count_shrinks_with_window(self):
        from kubeflow_tpu.parallel.ring_attention import _ring_hops

        assert _ring_hops(8, 4096, 0) == 8        # no window: full ring
        assert _ring_hops(8, 4096, 4096) == 2     # one-shard window
        assert _ring_hops(8, 4096, 8192) == 3
        assert _ring_hops(8, 4096, 100) == 2      # sub-shard window
        assert _ring_hops(8, 4096, 10**9) == 8    # huge window: capped
        assert _ring_hops(4, 16, 16 * 3) == 4     # == ring

    def test_ragged_fallback_honors_window(self):
        """Non-block-divisible lengths take the blockwise fallback, which
        must apply the same window."""
        from kubeflow_tpu.parallel.ring_attention import flash_attention

        q, k, v, bias, _ = self._qkvbg(l=30)  # ragged vs block=8
        got = flash_attention(q, k, v, bias, block=8, causal=True, window=7)
        want = self._dense_ref(q, k, v, bias, 7)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def _attention_f32(q, k, v, bias, causal=False, window=0):
    """Plain float32 attention at `highest` -> (out, lse (B*H, Lq, 1)): the
    reference for the forward kernel's two outputs."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    b, lq, h, d = q.shape
    lk = k.shape[1]
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision="highest") / d**0.5
    s = s + bias.astype(jnp.float32)
    if causal:
        rows, cols = jnp.arange(lq)[:, None], jnp.arange(lk)[None, :]
        masked = cols > rows
        if window:
            masked = masked | (rows - cols >= window)
        s = s + jnp.where(masked, -1e9, 0.0)
    out = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, -1), v,
                     precision="highest")
    return out, jax.nn.logsumexp(s, -1).reshape(b * h, lq, 1)


def _qkvb(lq, lk, pad=0, b=2, h=4, d=16, seed=5, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, lq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, lk, h, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, lk, h, d), jnp.float32).astype(dtype)
    bias = jax.random.normal(ks[3], (b, 1, 1, lk), jnp.float32) * 0.3
    if pad:
        bias = bias.at[:, :, :, lk - pad:].set(-1e9)
    return q, k, v, bias


class TestFlashForwardTiling:
    """The forward kernel's two branches (K/V resident in VMEM with the KV
    loop inside the kernel; KV axis on the grid) against float32 attention,
    `out` and `lse` both, at tiles that put the diagonal, the window's edge
    and the padding inside, across and between tiles; and the rule that
    chooses the tile from the call's shapes."""

    # (lq, lk, causal, window, pad, block_q, block_k, heads a step)
    GEOMETRIES = {
        "causal-q-wider": (64, 64, True, 0, 0, 16, 8, 1),
        "causal-k-wider": (64, 64, True, 0, 0, 8, 16, 1),
        "causal-square-padded": (64, 64, True, 0, 9, 16, 16, 1),
        "full-padding-bias": (64, 64, False, 0, 12, 32, 16, 1),
        "window-crosses-tiles": (64, 64, True, 12, 0, 16, 8, 1),
        "window-inside-a-tile": (64, 64, True, 5, 0, 8, 16, 1),
        "window-wider-than-tiles": (64, 64, True, 40, 0, 16, 16, 1),
        "window-self-only": (64, 64, True, 1, 0, 16, 16, 1),
        "lq-under-lk": (32, 64, True, 0, 0, 16, 8, 1),
        "lq-over-lk": (64, 32, True, 0, 0, 16, 8, 1),
        "full-lq-under-lk": (32, 64, False, 0, 7, 16, 32, 1),
        "one-tile": (64, 64, True, 0, 0, 64, 64, 1),
    }

    @pytest.mark.parametrize("resident", [True, False],
                             ids=["resident", "kvgrid"])
    @pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
    def test_out_and_lse_match_float32_attention(self, geometry, resident):
        from kubeflow_tpu.parallel.ring_attention import (
            FlashTiling,
            _flash_forward_tiled,
        )

        lq, lk, causal, window, pad, bq, bk, group = self.GEOMETRIES[geometry]
        q, k, v, bias = _qkvb(lq, lk, pad)
        out, lse = _flash_forward_tiled(
            q, k, v, bias, FlashTiling(resident, bq, bk, group),
            mask_of(causal, window))
        want_out, want_lse = _attention_f32(q, k, v, bias, causal, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("group", [2, 4])
    def test_head_group_shares_its_batch_rows_bias(self, group):
        from kubeflow_tpu.parallel.ring_attention import (
            FlashTiling,
            _flash_forward_tiled,
        )

        q, k, v, bias = _qkvb(64, 64)  # a random bias row a batch row
        out, lse = _flash_forward_tiled(
            q, k, v, bias, FlashTiling(True, 16, 8, group), Causal(7))
        want_out, want_lse = _attention_f32(q, k, v, bias, True, 7)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal,window,vmem_budget,resident", [
        (True, 0, None, True),       # the cell's lengths: resident K/V,
        (False, 0, None, True),      # several query tiles and KV slices
        (True, 300, None, True),
        (True, 0, 600_000, False),   # K/V past the budget: KV on the grid
    ])
    def test_the_rules_choice_runs_each_branch(self, causal, window,
                                               vmem_budget, resident):
        from kubeflow_tpu.parallel import ring_attention as ra

        q, k, v, bias = _qkvb(1024, 1024, pad=0 if causal else 100,
                              b=1, h=2, d=16)
        budget = ({} if vmem_budget is None
                  else {"vmem_budget": vmem_budget})
        tiling = ra.flash_forward_tiling(
            1024, 1024, 16, q.dtype, mask_of(causal, window), heads=2,
            **budget)
        assert tiling.resident is resident
        assert 1024 // tiling.block_q > 1 and 1024 // tiling.block_k > 1
        assert tiling.name.startswith(
            "flash_fwd_resident_q" if resident else "flash_fwd_kvgrid_q")
        out, lse = ra._flash_forward_tiled(q, k, v, bias, tiling,
                                           mask_of(causal, window))
        want_out, want_lse = _attention_f32(q, k, v, bias, causal, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   rtol=2e-5, atol=2e-5)
        if vmem_budget is None:
            # what `_flash_forward` itself runs at these lengths
            got, got_lse = ra._flash_forward(q, k, v, bias, 128, 128,
                                             mask_of(causal, window),
                                             want_lse=True)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(out))
            np.testing.assert_array_equal(np.asarray(got_lse),
                                          np.asarray(lse))

    def test_a_length_that_does_not_tile_falls_back(self):
        from kubeflow_tpu.parallel.ring_attention import _flash_forward

        q, k, v, bias = _qkvb(200, 200, b=1, h=2)
        out, lse = _flash_forward(q, k, v, bias, 128, 128, Causal(),
                                  want_lse=True)
        assert lse is None
        want, _ = _attention_f32(q, k, v, bias, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_backward_consumes_the_new_forwards_lse(self):
        """The forward tiles by the rule (two query tiles, two KV slices
        here), the unchanged backward by the caller's block: `lse` is a
        per-row statistic, so the two geometries are independent."""
        from kubeflow_tpu.models.gpt import causal_dense_attention
        from kubeflow_tpu.parallel.ring_attention import (
            _flash,
            flash_forward_tiling,
        )

        q, k, v, bias = _qkvb(1024, 1024, b=1, h=2, d=16)
        tiling = flash_forward_tiling(1024, 1024, 16, q.dtype, Causal(),
                                      heads=2)
        assert (tiling.block_q, tiling.block_k) != (128, 128)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

        def loss(attn, q, k, v, bias):
            return (attn(q, k, v, bias) * g).sum()

        got = jax.jit(jax.grad(
            functools.partial(loss, lambda *a: _flash(*a, 128, 128, Causal())),
            argnums=(0, 1, 2, 3)))(q, k, v, bias)
        want = jax.grad(functools.partial(loss, causal_dense_attention),
                        argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)

    # (lq, lk, d, dtype, causal, window, block, heads) -> the call's name
    RULE_TABLE = [
        # gpt2m-train-1k's call
        (1024, 1024, 64, jnp.bfloat16, True, 0, 128, 16,
         "flash_fwd_resident_q256_k512"),
        (1024, 1024, 64, jnp.float32, True, 0, 128, 16,
         "flash_fwd_resident_q256_k512"),
        # BERT at 512 and ViT at 256 patches: a head is one tile, four a step
        (512, 512, 64, jnp.bfloat16, False, 0, 128, 12,
         "flash_fwd_resident_q512_k512_g4"),
        (256, 256, 64, jnp.bfloat16, False, 0, 128, 12,
         "flash_fwd_resident_q256_k256_g4"),
        (256, 256, 64, jnp.bfloat16, False, 0, 128, 6,
         "flash_fwd_resident_q256_k256_g2"),
        (4096, 4096, 128, jnp.bfloat16, True, 1024, 128, 32,
         "flash_fwd_resident_q256_k512"),
        (8192, 8192, 64, jnp.bfloat16, True, 0, 128, 16,
         "flash_fwd_resident_q256_k512"),
        # K and V of a head past the budget: 16k positions and up
        (16384, 16384, 64, jnp.bfloat16, True, 0, 128, 16,
         "flash_fwd_kvgrid_q512_k1024"),
        (32768, 32768, 128, jnp.bfloat16, True, 0, 128, 32,
         "flash_fwd_kvgrid_q512_k1024"),
        (32768, 32768, 128, jnp.float32, False, 0, 128, 8,
         "flash_fwd_kvgrid_q512_k1024"),
        # 9 x 128: the only tiles are 128, 384 and 1152
        (1152, 1152, 64, jnp.bfloat16, True, 0, 128, 16,
         "flash_fwd_resident_q128_k384"),
        (512, 2048, 64, jnp.bfloat16, False, 0, 128, 8,
         "flash_fwd_resident_q512_k512"),
        # the tests' own shapes: the caller's block is the granule
        (64, 64, 16, jnp.float32, True, 5, 8, 4,
         "flash_fwd_resident_q64_k64_g4"),
        (32, 64, 16, jnp.float32, False, 0, 16, 3,
         "flash_fwd_resident_q32_k64"),
        (48, 48, 16, jnp.float32, True, 0, 16, 4,
         "flash_fwd_resident_q48_k48_g4"),
    ]

    @pytest.mark.parametrize(
        "lq,lk,d,dtype,causal,window,block,heads,name", RULE_TABLE)
    def test_rule_divides_the_lengths_and_fits_the_budget(
            self, lq, lk, d, dtype, causal, window, block, heads, name):
        from kubeflow_tpu.parallel import ring_attention as ra

        t = ra.flash_forward_tiling(lq, lk, d, dtype, mask_of(causal, window),
                                    block_q=block, block_k=block, heads=heads)
        assert t.name == name
        assert lq % t.block_q == 0 and lk % t.block_k == 0
        # a multiple of the caller's granule: aligned wherever `block` is
        assert t.block_q % min(block, lq) == 0
        assert t.block_k % min(block, lk) == 0
        assert heads % t.heads == 0
        assert (ra._flash_fwd_vmem_bytes(t, lk, d, dtype)
                <= ra.FLASH_FWD_VMEM_BUDGET)

    def test_rule_shrinks_tiles_to_a_smaller_budget(self):
        from kubeflow_tpu.parallel import ring_attention as ra

        args = (4096, 4096, 128, jnp.bfloat16, Causal())
        roomy = ra.flash_forward_tiling(*args)
        assert roomy.resident
        for budget in (3 * 2**20, 2**20, 300_000):
            t = ra.flash_forward_tiling(*args, vmem_budget=budget)
            assert not t.resident
            assert 4096 % t.block_q == 0 and 4096 % t.block_k == 0
            assert t.block_q % 128 == 0 and t.block_k % 128 == 0
            assert (t.block_q, t.block_k) == (128, 128) or (
                ra._flash_fwd_vmem_bytes(t, 4096, 128, jnp.bfloat16)
                <= budget)

    def test_no_environment_knob_chooses_the_tile(self):
        """The tile is a function of the call's shapes: the three
        capture-campaign variables are gone from the module."""
        import inspect

        from kubeflow_tpu.parallel import ring_attention as ra

        assert "KFT_FLASH_BLOCK" not in inspect.getsource(ra)
        assert "KFT_FLASH_DIMSEM" not in inspect.getsource(ra)
        for name in ("FLASH_BLOCK_Q", "FLASH_BLOCK_K", "FLASH_DIMSEM"):
            assert not hasattr(ra, name)


def _dense_visible(lq, lk, causal, window):
    """The mask as `_flash_backward_xla` applies it, dense: row i sees column
    c iff c <= i and, with a window, i - c < window."""
    rows, cols = np.arange(lq)[:, None], np.arange(lk)[None, :]
    seen = np.ones((lq, lk), bool)
    if causal:
        seen = cols <= rows
        if window:
            seen &= rows - cols < window
    return seen


def _grads_f32(q, k, v, bias, g, causal, window):
    """dq, dk, dv, dbias of dense float32 attention at `highest`."""
    def loss(q, k, v, bias):
        out, _ = _attention_f32(q, k, v, bias, causal, window)
        return (out * g.astype(jnp.float32)).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3))(
        *(x.astype(jnp.float32) for x in (q, k, v)), bias)


class TestFlashBackwardLivePairs:
    """The XLA backward walks only the (query block, KV block) pairs the
    causal and window masks leave visible: the rule that lists them, the
    gradients where pairs are really skipped, and that the lowered loop
    computes the rule's share of the square and no more."""

    # (lq, lk, block_q, block_k, causal, window) -> live pairs of all
    PAIR_CASES = [
        # the three calls of ISSUE 29's table
        (8192, 8192, 512, 512, True, 2048, 70, 256),   # trinitym-train-8k, sliding
        (8192, 8192, 512, 512, True, 0, 136, 256),     # trinitym-train-8k, full
        (1024, 1024, 128, 128, True, 0, 36, 64),       # gpt2m-train-1k at 128
        (64, 64, 8, 8, True, 5, 15, 64),      # window smaller than a block
        (64, 64, 8, 8, True, 8, 15, 64),      # equal to a block
        (64, 64, 8, 8, True, 9, 15, 64),      # a block and one more row
        (64, 64, 8, 8, True, 10, 21, 64),     # one element into a third block
        (64, 64, 8, 8, True, 12, 21, 64),     # larger than a block
        (64, 64, 8, 8, True, 1, 8, 64),       # the diagonal alone
        (64, 64, 8, 8, True, 64, 36, 64),     # window == length: plain causal
        (64, 64, 8, 8, True, 999, 36, 64),    # window > length
        (64, 64, 8, 8, True, 0, 36, 64),
        (32, 64, 16, 16, False, 0, 8, 8),     # not causal: every pair
        (32, 64, 8, 8, True, 0, 10, 32),      # lq < lk: KV blocks 4-7 unseen
        (32, 64, 8, 8, True, 12, 9, 32),
        (64, 64, 16, 8, True, 0, 20, 32),     # blocks that differ
        (64, 64, 8, 16, True, 0, 20, 32),
        (64, 64, 32, 8, True, 20, 11, 16),
        (64, 32, 8, 8, True, 0, 26, 32),      # lq > lk
        (64, 64, 64, 64, True, 5, 1, 1),      # one block each
    ]

    @pytest.mark.parametrize(
        "lq,lk,block_q,block_k,causal,window,n_live,n_all", PAIR_CASES)
    def test_pair_is_listed_iff_its_tile_has_a_visible_element(
            self, lq, lk, block_q, block_k, causal, window, n_live, n_all):
        from kubeflow_tpu.parallel.ring_attention import (
            flash_backward_live_pairs,
        )

        pairs = flash_backward_live_pairs(lq, lk, block_q, block_k,
                                          mask_of(causal, window))
        n_q, n_kv = lq // block_q, lk // block_k
        assert (len(pairs), n_q * n_kv) == (n_live, n_all)
        assert len(set(pairs)) == len(pairs)
        seen = _dense_visible(lq, lk, causal, window).reshape(
            n_q, block_q, n_kv, block_k).any(axis=(1, 3))
        assert set(pairs) == {(i, j) for i in range(n_q) for j in range(n_kv)
                              if seen[i, j]}
        # KV block major, a KV block's query blocks an unbroken range: what
        # the backward's inner loop walks
        assert pairs == sorted(pairs, key=lambda p: (p[1], p[0]))
        for j in range(n_kv):
            mine = [i for i, jj in pairs if jj == j]
            assert not mine or mine == list(range(mine[0], mine[-1] + 1))
        if lq < lk and causal:
            assert all(j * block_k < lq for _, j in pairs)

    # (lq, lk, block_q, block_k, causal, window, pad): pairs really skipped
    GRAD_CASES = [
        (64, 64, 8, 8, True, 0, 0),
        (64, 64, 8, 8, True, 8, 0),     # window of one block
        (64, 64, 8, 8, True, 20, 12),   # of two to three, with masked keys
        (64, 64, 16, 8, True, 20, 12),
        (64, 64, 8, 16, True, 5, 0),    # window inside a block
        (64, 64, 32, 16, True, 0, 12),
        (32, 64, 8, 8, True, 0, 12),    # lq < lk: KV blocks no query sees
        (32, 64, 8, 16, True, 12, 0),
        (32, 64, 16, 16, False, 0, 12),  # not causal: the full list
    ]

    @pytest.mark.parametrize(
        "lq,lk,block_q,block_k,causal,window,pad", GRAD_CASES)
    def test_grads_incl_dbias_match_float32_attention(
            self, monkeypatch, lq, lk, block_q, block_k, causal, window, pad):
        from kubeflow_tpu.parallel import ring_attention as ra

        monkeypatch.setattr(
            ra, "flash_backward_xla_blocks",
            lambda *a, **kw: (block_q, block_k))
        q, k, v, bias = _qkvb(lq, lk, pad=pad)
        g = jax.random.normal(jax.random.PRNGKey(29), q.shape, jnp.float32)

        def loss(q, k, v, bias):
            return (ra.flash_attention(q, k, v, bias, block=8, causal=causal,
                                       window=window) * g).sum()

        got = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
        want = _grads_f32(q, k, v, bias, g, causal, window)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-5, atol=3e-5, err_msg=name)
        if lq < lk and causal:  # keys no query sees: exactly zero
            for a in got[1:3]:
                assert not np.asarray(a)[:, lq:].any()
            assert not np.asarray(got[3])[..., lq:].any()

    @pytest.mark.parametrize("lq,lk,block_q,block_k,causal,window", [
        (256, 256, 32, 32, True, 64),    # a band: 20 of 64
        (256, 256, 32, 64, True, 0),     # the half square: 20 of 32
        (128, 256, 32, 32, True, 0),     # lq < lk: 10 of 32
        (64, 128, 32, 32, False, 0),     # not causal: all of it
    ])
    def test_lowered_loop_computes_the_rules_share_of_the_square(
            self, lq, lk, block_q, block_k, causal, window):
        """The loop as it is traced: score tiles of (BH, block_q, block_k),
        as many as the rule lists pairs. A later edit that quietly returns
        to every KV block against all `lq` queries fails here."""
        from kubeflow_tpu.parallel import ring_attention as ra

        b, h, d = 1, 2, 16
        mask = mask_of(causal, window)
        pairs = ra.flash_backward_live_pairs(lq, lk, block_q, block_k, mask)
        rows = jnp.zeros((b * h, lq, d)), jnp.zeros((b * h, lq, 1))
        keys = jnp.zeros((b * h, lk, d))
        closed = jax.make_jaxpr(lambda q, k, v, bias, g, lse, dd: (
            ra._flash_backward_xla(
                q, k, v, bias, g, lse, dd, b=b, h=h, lq=lq, lk=lk, d=d,
                scale=0.25, block_q=block_q, block_k=block_k, mask=mask,
                out_dtypes=(jnp.float32,) * 3, bias_dtype=jnp.float32)))(
            rows[0], keys, keys, jnp.zeros((b, 1, 1, lk)), rows[0], rows[1],
            rows[1])
        (scan,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
        assert scan.params["length"] == lk // block_k
        # the inner loop's trip counts ride the scan as its last operand
        consts = dict(zip(closed.jaxpr.constvars, closed.consts))
        count = np.asarray(consts[scan.invars[-1]])
        (loop,) = [e for e in scan.params["jaxpr"].jaxpr.eqns
                   if e.primitive.name == "while"]
        tiles = [e.outvars[0].aval.shape
                 for e in loop.params["body_jaxpr"].jaxpr.eqns
                 if e.primitive.name == "dot_general"
                 and e.outvars[0].aval.shape[1:] == (block_q, block_k)]
        assert tiles == [(b * h, block_q, block_k)] * 2      # s and dp
        assert int(count.sum()) == len(pairs)
        computed = len(pairs) * block_q * block_k
        if causal:
            assert computed < lq * lk * 0.7
        else:
            assert computed == lq * lk

    def test_backward_is_named_for_its_blocks_and_live_pairs(self):
        """A toy GPT with `attention=flash`: the backward's operations carry
        the scope `flash_bwd_xla_q.._k.._live..of..` under the block's
        `attention` module and inside the transposed function, so the
        benchmark's `attn_core_bwd_ms.train` keeps reading them."""
        from benchmarks.layer_metrics import train_parts
        from kubeflow_tpu.models.gpt import GPTLM, GPTConfig, causal_lm_loss

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, mlp_dim=32, max_len=32,
                        attention="flash", attention_block=8,
                        dropout_rate=0.0)
        model = GPTLM(cfg)
        x = jnp.ones((2, 32), jnp.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), x)

        def loss(params):
            return causal_lm_loss(model.apply(params, x), x)

        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
        names = [n for n in re.findall(r'op_name="([^"]*)"', text)
                 if "flash_bwd_xla_" in n]
        assert names
        scope = re.search(r"flash_bwd_xla_q(\d+)_k(\d+)_live(\d+)of(\d+)",
                          names[0])
        bq, bk, live, total = map(int, scope.groups())
        assert total == (32 // bq) * (32 // bk) and 0 < live <= total
        for n in names:
            assert train_parts.ATTENTION_CORE.search(n), n
            assert train_parts.BACKWARD.search(n), n


def test_flash_forward_on_the_chip_matches_float32_attention():
    """Chip only (`pytest --noconftest` through the chip tool; `conftest.py`
    pins the CPU): the kernel as Mosaic compiles it at `gpt2m-train-1k`'s
    shape against float32 attention at `highest`. Interpret mode has passed
    kernels of this file that NaN on the chip, so this verdict is the one
    that counts. The limits are the parent kernel's errors at this shape and
    these inputs plus a rounding step (`CHIP_*_ERR_LIMIT` below)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip: Mosaic, not the interpreter, is judged")
    from kubeflow_tpu.parallel.ring_attention import (
        _flash_forward,
        flash_forward_tiling,
    )

    q, k, v, bias = _qkvb(1024, 1024, b=8, h=16, d=64, seed=26,
                          dtype=jnp.bfloat16)
    bias = jnp.zeros_like(bias)
    tiling = flash_forward_tiling(1024, 1024, 64, q.dtype, Causal(), heads=16)
    assert tiling.resident
    out, lse = jax.jit(lambda *a: _flash_forward(
        *a, 128, 128, Causal(), want_lse=True))(q, k, v, bias)
    want_out, want_lse = jax.jit(
        lambda *a: _attention_f32(*a, causal=True))(q, k, v, bias)
    out_err = float(jnp.abs(out.astype(jnp.float32) - want_out).max())
    lse_err = float(jnp.abs(lse - want_lse).max())
    print(f"{tiling.name}: out_err {out_err:.3e} lse_err {lse_err:.3e}")
    assert bool(jnp.isfinite(lse).all())
    assert out_err <= CHIP_OUT_ERR_LIMIT and lse_err <= CHIP_LSE_ERR_LIMIT


#: the parent kernel's errors on the v5e at that shape and those inputs were
#: 8.188e-3 (`out`, largest magnitude 3.78) and 7.63e-6 (`lse`, largest 7.87)
#: (my chip run, PR 26, call 2). The limits add bf16's rounding of an output
#: under 4 (half a step of 2**-6) and eight float32 steps of an lse under 8.
CHIP_OUT_ERR_LIMIT = 8.188e-3 + 2.0**-7
CHIP_LSE_ERR_LIMIT = 7.63e-6 + 8 * 2.0**-21


def _grads_f32_by_heads(q, k, v, bias, g, causal, window, heads=4):
    """`_grads_f32` a few heads at a time: at 8k positions the float32
    scores of all 32 heads are 8.6 GB, and their gradient several times that."""
    grad = jax.jit(functools.partial(_grads_f32, causal=causal, window=window))
    parts = [grad(*(x[:, :, i:i + heads] for x in (q, k, v)), bias,
                  g[:, :, i:i + heads]) for i in range(0, q.shape[2], heads)]
    dq, dk, dv = (jnp.concatenate([p[i] for p in parts], axis=2)
                  for i in range(3))
    return dq, dk, dv, sum(p[3] for p in parts)


@pytest.mark.parametrize("shape,window", [
    ((8, 1024, 16, 64), 0),        # gpt2m-train-1k's call
    ((1, 8192, 32, 128), 2048),    # trinitym-train-8k's sliding layers
])
def test_flash_backward_on_the_chip_matches_float32_attention(shape, window):
    """Chip only, like the forward's test above: the XLA backward at the
    rule's blocks, fed the Mosaic forward's `out` and `lse`, against the
    gradients of float32 attention at `highest` on the same bf16 inputs.
    The limits are what was measured on the v5e (`CHIP_BWD_ERR_LIMIT`)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the chip: the program as XLA:TPU compiles it is judged")
    from kubeflow_tpu.parallel.ring_attention import (
        _flash_backward,
        _flash_forward,
    )

    b, l, h, d = shape
    q, k, v, bias = _qkvb(l, l, b=b, h=h, d=d, seed=29, dtype=jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(30), q.shape,
                          jnp.float32).astype(jnp.bfloat16)
    out, lse = jax.jit(lambda *a: _flash_forward(
        *a, 128, 128, Causal(window), want_lse=True))(q, k, v, bias)
    got = jax.jit(lambda *a: _flash_backward(
        *a, 128, 128, Causal(window)))(q, k, v, bias, out, lse, g)
    want = _grads_f32_by_heads(q, k, v, bias, g, True, window)
    for name, a, w in zip(("dq", "dk", "dv", "dbias"), got, want):
        a = a.astype(jnp.float32)
        err, top = float(jnp.abs(a - w).max()), float(jnp.abs(w).max())
        print(f"{shape} window {window} {name}: err {err:.3e} of {top:.3e}")
        assert bool(jnp.isfinite(a).all())
        assert err <= CHIP_BWD_ERR_LIMIT[l][name], (name, err, top)


#: measured on the v5e with these inputs (my chip run, PR 29, call 4), the same
#: to the last digit shown for the parent's whole-square backward, of largest
#: magnitudes 3.8 / 3.9 / 6.3 / 70 at 1k and 4.0 / 3.6 / 6.2 / 59 at 8k. The
#: limits add half a bf16 step of the gradient's largest value (2**-7 under 4,
#: 2**-6 under 8) and, for the float32 `dbias` (a sum over heads x rows of
#: products rounded to bf16), a quarter of the reading.
CHIP_BWD_ERR_LIMIT = {
    1024: {"dq": 1.345e-2 + 2.0**-7, "dk": 1.401e-2 + 2.0**-7,
           "dv": 2.108e-2 + 2.0**-6, "dbias": 6.413e-2 * 1.25},
    8192: {"dq": 1.265e-2 + 2.0**-7, "dk": 1.188e-2 + 2.0**-7,
           "dv": 1.933e-2 + 2.0**-6, "dbias": 5.081e-2 * 1.25},
}


class TestBlockwiseCustomVJP:
    """The FA2-style custom VJP (r5 default — recompute p from saved lse,
    O(L) residuals, no reverse-AD through the online max/exp chain) must be
    gradient-identical to the scan-autodiff path it replaced, for every
    flavor the framework trains through: full / causal / sliding-window,
    f32 and bf16, multi-block and ragged-tail, including dbias."""

    @pytest.mark.parametrize("causal,window", [(False, 0), (True, 0),
                                               (True, 24)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_custom_matches_autodiff(self, causal, window, dtype):
        q, k, v, bias = make_inputs()
        q, k, v, bias = (t.astype(dtype) for t in (q, k, v, bias))

        def loss(q, k, v, bias, vjp):
            return (blockwise_attention(q, k, v, bias, block=16,
                                        causal=causal, window=window,
                                        vjp=vjp).astype(jnp.float32) ** 2
                    ).sum()

        ga = jax.grad(functools.partial(loss, vjp="autodiff"),
                      argnums=(0, 1, 2, 3))(q, k, v, bias)
        gc = jax.grad(functools.partial(loss, vjp="custom"),
                      argnums=(0, 1, 2, 3))(q, k, v, bias)
        # bf16 grads of magnitude ~3 have ulp ~0.02: allow a few ulps of
        # accumulation-order difference between the two backward orderings
        atol, rtol = ((1e-4, 0.0) if dtype == jnp.float32 else (6e-2, 5e-2))
        for name, a, c in zip(("dq", "dk", "dv", "dbias"), ga, gc):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(c, np.float32),
                atol=atol, rtol=rtol, err_msg=name)

    def test_ragged_tail_single_block_fallback(self):
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 60, 2, 16)),
                               jnp.float32) for _ in range(3))
        bias = jnp.zeros((1, 1, 1, 60), jnp.float32)

        def loss(q, k, v, bias, vjp):
            return (blockwise_attention(q, k, v, bias, block=16, causal=True,
                                        vjp=vjp) ** 2).sum()

        ga = jax.grad(functools.partial(loss, vjp="autodiff"),
                      argnums=(0, 1, 2, 3))(q, k, v, bias)
        gc = jax.grad(functools.partial(loss, vjp="custom"),
                      argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, a, c in zip(("dq", "dk", "dv", "dbias"), ga, gc):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=1e-4, err_msg=name)

    def test_env_is_import_time_and_unknown_rejected(self):
        """KFT_BLOCKWISE_VJP is read+validated ONCE at import (a trace-time
        read would silently ignore changes after jit compilation): the
        module constant is the default, a bad env value raises at import
        in a fresh interpreter, and an explicit bad vjp raises here."""
        import os
        import subprocess
        import sys

        from kubeflow_tpu.parallel import ring_attention as ra

        # the constant mirrors whatever env this suite inherited — do not
        # hard-code "custom" or the suite fails under its own documented
        # KFT_BLOCKWISE_VJP=autodiff escape hatch
        assert ra.BLOCKWISE_VJP == os.environ.get("KFT_BLOCKWISE_VJP",
                                                  "custom")
        q, k, v, bias = make_inputs()
        with pytest.raises(ValueError, match="unknown blockwise vjp"):
            blockwise_attention(q, k, v, bias, block=16, vjp="nope")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-c",
             "import kubeflow_tpu.parallel.ring_attention"],
            capture_output=True, text=True, timeout=240,
            env={"KFT_BLOCKWISE_VJP": "nope", "JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin", "HOME": os.environ.get(
                     "HOME", "/root"),
                 "PYTHONPATH": repo},
        )
        assert proc.returncode != 0
        assert "KFT_BLOCKWISE_VJP" in proc.stderr

    def test_ulysses_local_path_uses_custom_vjp_grads(self):
        """The context-parallel local attention (what ring/ulysses train
        through) still matches dense grads with the custom VJP default."""
        q, k, v, bias = make_inputs()

        def loss_dense(q, k, v):
            return (dense_attention(q, k, v, bias) ** 2).sum()

        def loss_block(q, k, v):
            return (blockwise_attention(q, k, v, bias, block=16,
                                        vjp="custom") ** 2).sum()

        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gd, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)


class TestRingCustomVJP:
    """The ring-rotating FA2-style backward (r5 default) must be
    gradient-identical to reverse-AD through the forward ring — including
    the window-truncated-hops case, whose closing ppermute must return
    every dk/dv/dbias accumulator to its home shard."""

    @pytest.mark.parametrize("causal,window", [(False, 0), (True, 0),
                                               (True, 24)])
    def test_ring_custom_matches_autodiff(self, causal, window):
        q, k, v, bias = make_inputs()
        mesh = build_mesh(MeshConfig(data=2, context=4))

        def loss(q, k, v, bias, vjp):
            return (ring_attention(q, k, v, bias, causal=causal,
                                   window=window, vjp=vjp) ** 2).sum()

        with jax.set_mesh(mesh):
            ga = jax.jit(jax.grad(functools.partial(loss, vjp="autodiff"),
                                  argnums=(0, 1, 2, 3)))(q, k, v, bias)
            gc = jax.jit(jax.grad(functools.partial(loss, vjp="custom"),
                                  argnums=(0, 1, 2, 3)))(q, k, v, bias)
        for name, a, c in zip(("dq", "dk", "dv", "dbias"), ga, gc):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), atol=2e-4, err_msg=name)

    def test_ring_custom_with_rope_matches_dense_rope(self):
        """rope sits OUTSIDE the custom-vjp boundary: its backward is
        ordinary AD composed with the ring core's hand-written one."""
        from kubeflow_tpu.parallel.rope import apply_rope

        q, k, v, bias = make_inputs()
        mesh = build_mesh(MeshConfig(data=2, context=4))

        def loss_ring(q, k, v):
            return (ring_attention(q, k, v, bias, causal=True,
                                   rope_theta=10000.0,
                                   vjp="custom") ** 2).sum()

        def loss_dense(q, k, v):
            pos = jnp.arange(L)
            qr, kr = apply_rope(q, pos, 10000.0), apply_rope(k, pos, 10000.0)
            mask = jnp.where(pos[None, :] > pos[:, None], -1e9, 0.0)
            return (dense_attention(
                qr, kr, v, bias + mask[None, None, :, :]) ** 2).sum()

        with jax.set_mesh(mesh):
            gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), gr, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, err_msg=name)


# --------------------------------------- two head sizes and a given scale (latent attention)

from kubeflow_tpu.parallel import ring_attention as ra  # noqa: E402

def _dense_two_sizes(q, k, v, bias, mask, scale):
    """float32 attention with keys wider than values: (out, lse (B, H, L))."""
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision="highest") * scale + bias
    if mask is not None:
        at = jnp.arange(q.shape[1])
        s = jnp.where(mask.hidden(at[:, None], at[None, :]), -jnp.inf, s)
    return (jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, -1), v, precision="highest"),
            jax.nn.logsumexp(s, -1))


def _two_sizes(length=64, d=24, dv=16, seed=7):
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.normal(0, 1, (2, length, 4, d)).astype(np.float32)) for _ in range(2))
    v = jnp.asarray(rng.normal(0, 1, (2, length, 4, dv)).astype(np.float32))
    bias = jnp.asarray(rng.normal(0, 0.3, (2, 1, 1, length)).astype(np.float32))
    return q, k, v, bias


@pytest.mark.parametrize("mask", [Causal(), Causal(24), None], ids=["causal", "window", "none"])
@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv"])
def test_flash_at_unequal_head_sizes_and_a_given_scale_matches_dense(mask, what):
    """q and k 24 wide, v and the output 16, scale 0.3: the pallas forward (interpreted) and
    the XLA backward against float32 attention."""
    q, k, v, bias = _two_sizes()
    scale = 0.3
    if what in ("out", "lse"):
        out, lse = ra._flash_forward(q, k, v, bias, 16, 16, mask, want_lse=True, scale=scale)
        want_out, want_lse = _dense_two_sizes(q, k, v, bias, mask, scale)
        assert out.shape == (2, 64, 4, 16) and lse.shape == (8, 64, 1)
        got, want = (out, want_out) if what == "out" else (lse.reshape(2, 4, 64), want_lse)
        np.testing.assert_allclose(got, want, atol=2e-5)
        return
    argnum = ("dq", "dk", "dv").index(what)

    def loss(attention, *qkv):
        return (attention(*qkv) ** 2).sum()

    flash = lambda q, k, v: flash_attention(q, k, v, bias, block=16, mask=mask, scale=scale)  # noqa: E731
    dense = lambda q, k, v: _dense_two_sizes(q, k, v, bias, mask, scale)[0]  # noqa: E731
    got = jax.jit(jax.grad(functools.partial(loss, flash), argnums=argnum))(q, k, v)
    want = jax.grad(functools.partial(loss, dense), argnums=argnum)(q, k, v)
    assert got.shape == (q, k, v)[argnum].shape
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("mask", [Causal(), Causal(24), None], ids=["causal", "window", "none"])
def test_the_blockwise_fallback_takes_unequal_head_sizes_and_a_scale(mask):
    """40 positions do not tile by 16: forward and backward take the fallback."""
    q, k, v, bias = _two_sizes(length=40)
    out, lse = ra._flash_forward(q, k, v, bias, 16, 16, mask, want_lse=True, scale=0.3)
    assert lse is None and out.shape == (2, 40, 4, 16)
    np.testing.assert_allclose(out, _dense_two_sizes(q, k, v, bias, mask, 0.3)[0], atol=2e-5)
    loss = lambda attention: lambda q, k, v: (attention(q, k, v) ** 2).sum()  # noqa: E731
    got = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, bias, block=16, mask=mask, scale=0.3)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _dense_two_sizes(q, k, v, bias, mask, 0.3)[0]), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4)


@pytest.mark.parametrize("what", ["arrays", "names", "tiling", "count", "pallas_backwards"])
def test_equal_head_sizes_and_no_scale_are_what_they_were(what):
    q, k, v, bias = _two_sizes(d=16, dv=16)
    if what == "arrays":  # None is 1/sqrt(d), to the bit, forward and backward
        def grads(scale):
            return jax.grad(lambda q, k, v: (flash_attention(
                q, k, v, bias, block=16, causal=True, scale=scale) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(grads(None), grads(1.0 / 16 ** 0.5)):
            np.testing.assert_array_equal(a, b)
    elif what == "names":  # the kernels' names gain the head sizes only where they differ
        assert ra.head_sizes_suffix(128, 128) == "" and ra.head_sizes_suffix(192, 128) == "_d192v128"
        equal = jax.jit(lambda *a: ra._flash_forward(*a, 16, 16, Causal())).lower(q, k, v, bias).as_text(debug_info=True)
        assert "flash_fwd_resident_q64_k64_g4" in equal and "_d16v16" not in equal
        q24, k24, v16, _ = _two_sizes()
        text = jax.jit(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, bias, block=16, causal=True, scale=0.3).sum(), argnums=(0, 1, 2))
        ).lower(q24, k24, v16).as_text(debug_info=True)
        assert "flash_fwd_resident_q64_k64_g4_d24v16" in text
        assert "flash_bwd_xla_q16_k16_live10of16_d24v16" in text
    elif what == "tiling":  # the cell's shape counts over the budget: the KV axis on the grid; the accepted shapes as before
        t = ra.flash_forward_tiling(8192, 8192, 192, jnp.bfloat16, Causal(), heads=16, dv=128)
        assert t == ra.FlashTiling(False, 512, 1024) and t.name == "flash_fwd_kvgrid_q512_k1024"
        assert ra.flash_forward_tiling(8192, 8192, 128, jnp.bfloat16, Causal(), heads=32) \
            == ra.FlashTiling(True, 256, 512)
        assert ra.flash_forward_tiling(8192, 8192, 192, jnp.bfloat16, Causal(), heads=16) \
            == ra.FlashTiling(False, 512, 1024)  # values of 192 too
        assert ra.flash_forward_tiling(16384, 16384, 128, jnp.bfloat16, Causal(), heads=32) \
            == ra.FlashTiling(False, 512, 1024)
    elif what == "count":  # K's and V's lanes counted apart: 192 pads to 256, 128 stays
        t = ra.FlashTiling(True, 256, 512)
        two = ra._flash_fwd_vmem_bytes(t, 8192, 192, jnp.bfloat16, 128)
        blocks = 2 * (256 * (256 + 128) * 2 + 8192 * (256 + 128) * 2 + 256 * 128 * 4 + 8 * 8192 * 4)
        assert two == blocks + 3 * 256 * 512 * 4 + 256 * (128 + 256) * 4 == 15_728_640  # 15.0 MiB
        assert two > ra.FLASH_FWD_VMEM_BUDGET
        assert ra._flash_fwd_vmem_bytes(t, 8192, 128, jnp.bfloat16, 128) \
            == ra._flash_fwd_vmem_bytes(t, 8192, 128, jnp.bfloat16) == 11_403_264
    else:  # the four pallas backwards know one head size and their own scale, and say so
        q24, k24, v16, _ = _two_sizes()
        out, lse = ra._flash_forward(q24, k24, v16, bias, 16, 16, Causal(), want_lse=True, scale=0.3)
        for impl in ("loop2", "ddpre", "loop", "scratch"):
            with pytest.raises(NotImplementedError, match="one head size"):
                ra._flash_backward(q24, k24, v16, bias, out, lse, out, 16, 16, Causal(), impl=impl, scale=0.3)
            with pytest.raises(NotImplementedError, match="no given scale"):
                ra._flash_backward(q, k, v, bias, v, lse, v, 16, 16, Causal(), impl=impl, scale=0.3)
