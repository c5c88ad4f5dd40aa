"""GraniteHybridLM (`models/granite_hybrid.py`) and the Mamba-2 mixer (`parallel/ssm.py`)
on the CPU at tiny sizes: the model against `benchmarks/reference_granite.py` on seeded
weights (logits, loss, every gradient, the first update through `Trainer.train_step`), the
chunked scans against the recurrence position by position, the program's scan at several
chunk lengths, the carried state shown load-bearing, the convolution's reach, the seeded
draw and the step counter, the step's operation names as the readers take them, and the
tiny preset through `Trainer.fit`."""

from __future__ import annotations

import functools
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite as reference
from benchmarks.families import granite_hybrid as family
from kubeflow_tpu.models import GraniteHybridConfig, GraniteHybridLM
from kubeflow_tpu.models.gpt import causal_lm_loss
from kubeflow_tpu.parallel.ssm import (SSM_STATE, STEP_INIT, Mamba2Mixer, causal_conv1d,
                                       ssd_chunk, ssd_chunked, ssd_state, ssm_counters)

ROOT = Path(__file__).resolve().parents[1]
#: the benchmark's keys at a test's size: both layer kinds, chunks of 8 over rows of 32
CFG = dict(json.loads((ROOT / "benchmarks/configs/granite-4.0-h-micro.json").read_text()),
           vocab_size=300, hidden_size=32, num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
           num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=64,
           intermediate_size=64, mamba_n_heads=4, mamba_d_head=16, mamba_expand=2, mamba_d_state=8,
           mamba_chunk_size=8)
MIX = {"task": "causal_lm", "attention": "dense", "seq_len": 32, "batch": 8,
       "learning_rate": 1e-3, "warmup_steps": 0}
#: float32 on both sides: what differs is the order of float32 sums
LOSS_ABS, GRAD_RTOL, GRAD_ATOL_SHARE = 2e-5, 1e-4, 2e-5


def _rows(n=8, length=32, seed=5):
    return np.asarray(np.random.default_rng(seed).integers(1, 300, size=(n, length)), np.int32)


def _scan_inputs(seed=1, length=40, heads=4, hp=8, n=6, groups=2, slow=False):
    """x, the steps, a, B, C for one row; `slow` draws small steps and decays, so that a
    state outlives many chunks."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (length, heads, hp))
    dt = jax.nn.softplus(jax.random.normal(k[1], (length, heads)) - (5.0 if slow else 0.0))
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=-1.0 if slow else 0.0, maxval=0.5))
    b, c = jax.random.normal(k[3], (length, groups, n)), jax.random.normal(k[4], (length, groups, n))
    return x, dt, a, b, c


# ------------------------------------------------------------------------- the scans

@pytest.mark.parametrize("chunk", [8, 5, 40])
def test_the_references_chunked_scan_is_the_recurrence_position_by_position(chunk):
    x, dt, a, b, c = _scan_inputs()
    want = reference.ssd_by_position(x, dt, a, b, c)
    np.testing.assert_allclose(reference.ssd(x, dt, a, b, c, chunk), want, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [8, 20, 5, 64])
def test_the_programs_scan_is_the_recurrence_at_any_chunk_length(chunk):
    """Chunks that tile the row, and one longer than it; two groups of heads sharing their
    B and C."""
    x, dt, a, b, c = _scan_inputs()
    want = reference.ssd_by_position(x, dt, a, b, c)
    got, kept = ssd_chunked(x[None], dt[None], a, b[None], c[None], chunk)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=5e-5)
    q = min(chunk, 40)
    assert kept.shape == (1, 40 // q, 4)


@pytest.mark.parametrize("length,chunk,groups", [
    (32, 8, 1), (32, 8, 2), (64, 32, 1), (64, 32, 2),
    (32, 64, 2),    # a row of one chunk, shorter than the chunk
    (512, 256, 1),  # two tiles of the kernels' walk in each chunk
])
def test_the_programs_scan_and_its_gradients_are_the_recurrences(length, chunk, groups):
    """The chunk-parallel scan, its kernels interpreted, and the gradients with respect to
    x, the steps, a, B and C against `jax.grad` of the recurrence position by position."""
    x, dt, a, b, c = _scan_inputs(seed=4, length=length, groups=groups)
    weights = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got, _ = ssd_chunked(x[None], dt[None], a, b[None], c[None], chunk)
    np.testing.assert_allclose(got[0], reference.ssd_by_position(x, dt, a, b, c),
                               rtol=1e-4, atol=5e-5)

    def program(*args):
        x, dt, a, b, c = args
        return (ssd_chunked(x[None], dt[None], a, b[None], c[None], chunk)[0][0] * weights).sum()

    def recurrence(*args):
        return (reference.ssd_by_position(*args) * weights).sum()

    for got, want in zip(jax.grad(program, argnums=range(5))(x, dt, a, b, c),
                         jax.grad(recurrence, argnums=range(5))(x, dt, a, b, c)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * float(jnp.abs(want).max()))


def _chunk_by_position(x, dt, cum, b, c, state):
    """What `ssd_chunk` computes, position by position: in each chunk the recurrence from
    the state it is given, `S_t = exp(cum_t - cum_{t-1}) S_{t-1} + dt_t x_t B_t^T` with
    `cum_{-1} = 0`, and `y_t = S_t C_t`; in `ssd_chunk`'s layouts."""
    bt, chunks, heads, q = dt.shape
    hp, n = state.shape[3:]
    per_group = heads // b.shape[1]
    xs = x.reshape(bt, chunks, q, heads, hp)
    bs, cs = (jnp.repeat(v, per_group, axis=1).reshape(bt, heads, chunks, q, n) for v in (b, c))

    def one(xq, dtq, cumq, bq, cq, s0):  # (Q, P), (Q,), (Q,), (Q, N), (Q, N), (P, N)
        def step(carry, inputs):
            s, before = carry
            xt, dtt, cumt, bt_, ct = inputs
            s = jnp.exp(cumt - before) * s + dtt * xt[:, None] * bt_[None, :]
            return (s, cumt), s @ ct
        return jax.lax.scan(step, (s0, 0.0), (xq, dtq, cumq, bq, cq))[1]

    over = jax.vmap(jax.vmap(jax.vmap(one, in_axes=(1, 0, 0, 0, 0, 0)),   # heads
                             in_axes=(0, 0, 0, 1, 1, 0)),                  # chunks
                    in_axes=(0, 0, 0, 0, 0, 0))                            # rows
    y = over(xs, dt, cum, bs, cs, state)                  # (Bt, c, H, Q, P)
    return y.transpose(0, 1, 3, 2, 4).reshape(x.shape)


def _kernel_inputs(q, groups, seed):
    """x, the steps, their running sums, B, C and a state for `ssd_chunk` and `ssd_state`:
    two rows of two chunks, four heads of 8, a state of 6."""
    bt, chunks, heads, hp, n = 2, 2, 4, 8, 6
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (bt, chunks * q, heads * hp))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bt, chunks, heads, q)) - 1.0)
    cum = jnp.cumsum(-dt * jax.random.uniform(k[2], (heads, 1), minval=0.2, maxval=1.0), -1)
    b, c = (jax.random.normal(kk, (bt, groups, chunks * q, n)) for kk in k[3:5])
    return x, dt, cum, b, c, jax.random.normal(k[5], (bt, chunks, heads, hp, n))


def _assert_vjps_agree(f, want_f, args, names):
    got, pull = jax.vjp(f, *args)
    want, pull_want = jax.vjp(want_f, *args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * float(jnp.abs(want).max()))
    cotangent = jax.random.normal(jax.random.PRNGKey(11), want.shape)
    for name, g, w in zip(names, pull(cotangent), pull_want(cotangent)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4 * float(jnp.abs(w).max()),
                                   err_msg=name)


@pytest.mark.parametrize("q,groups", [(8, 1), (8, 2), (256, 2)])
def test_the_chunk_kernels_backward_is_the_gradient_of_the_recurrence(q, groups):
    """`ssd_chunk`'s hand-written backward (the second kernel, interpreted) against
    `jax.vjp` of the same function written as the recurrence, for every input: x, the
    steps, `cum` as an input of its own, B, C and the state each chunk starts from."""
    _assert_vjps_agree(ssd_chunk, _chunk_by_position, _kernel_inputs(q, groups, q + groups),
                       ("x", "dt", "cum", "b", "c", "state"))


def _state_by_einsum(x, dt, cum, b):
    """What `ssd_state` computes: `sum_s exp(cum_end - cum_s) dt_s x_s B_s^T` a chunk."""
    bt, chunks, heads, q = dt.shape
    per_group = heads // b.shape[1]
    xs = x.reshape(bt, chunks, q, heads, -1)
    bs = jnp.repeat(b, per_group, axis=1).reshape(bt, heads, chunks, q, -1)
    to_end = jnp.exp(cum[..., -1:] - cum) * dt            # (Bt, c, H, Q)
    return jnp.einsum("bchs,bcshp,bhcsn->bchpn", to_end, xs, bs)


@pytest.mark.parametrize("q,groups", [(8, 1), (8, 2), (256, 2)])
def test_the_state_kernels_backward_is_the_gradient_of_the_chunks_state(q, groups):
    """`ssd_state`, each chunk's own state at its end, and its hand-written backward
    (interpreted) against `jax.vjp` of the sum written out, for x, the steps, `cum` and B."""
    x, dt, cum, b, _, _ = _kernel_inputs(q, groups, 2 * q + groups)
    _assert_vjps_agree(ssd_state, _state_by_einsum, (x, dt, cum, b), ("x", "dt", "cum", "b"))


@pytest.mark.parametrize("chunk", [7, 16])
def test_a_chunk_that_does_not_tile_the_row_is_refused(chunk):
    """As the reference refuses it: the program and the reference take the same rows."""
    x, dt, a, b, c = _scan_inputs()
    for scan in (lambda: ssd_chunked(x[None], dt[None], a, b[None], c[None], chunk),
                 lambda: reference.ssd(x, dt, a, b, c, chunk)):
        with pytest.raises(ValueError, match="no multiple of the chunk"):
            scan()


def test_the_programs_scan_at_two_chunk_lengths_agrees_with_itself():
    x, dt, a, b, c = _scan_inputs(seed=3, length=64, slow=True)
    at8, _ = ssd_chunked(x[None], dt[None], a, b[None], c[None], 8)
    at32, _ = ssd_chunked(x[None], dt[None], a, b[None], c[None], 32)
    np.testing.assert_allclose(at8, at32, rtol=1e-4, atol=5e-5)


def test_the_chunk_decay_is_the_share_of_a_state_that_survives_a_chunk():
    x, dt, a, b, c = _scan_inputs(length=32)
    _, kept = ssd_chunked(x[None], dt[None], a, b[None], c[None], 8)
    by_hand = jnp.exp((dt * a).reshape(4, 8, 4).sum(1))       # (chunks, heads)
    np.testing.assert_allclose(kept[0], by_hand, rtol=1e-5)


def test_dropping_the_carried_state_fails_the_comparison():
    """The chunked scan's own fault: each chunk starting from a zero state. Where a state
    outlives a chunk the outputs after the first chunk move by far more than the float32
    tolerance the sound scans meet; the first chunk's do not."""
    x, dt, a, b, c = _scan_inputs(seed=2, length=64, slow=True)
    _, kept = ssd_chunked(x[None], dt[None], a, b[None], c[None], 8)
    assert float(kept.mean()) > 0.5
    want = reference.ssd_by_position(x, dt, a, b, c)
    dropped = reference.ssd(x, dt, a, b, c, 8, carry_state=False)
    np.testing.assert_allclose(dropped[:8], want[:8], rtol=1e-5, atol=2e-5)
    assert float(jnp.abs(dropped[8:] - want[8:]).max()) > 100 * 5e-5


# ------------------------------------------------------------------- the convolution

def test_the_convolution_sees_no_position_after_t_and_none_before_the_row():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 12, 5))
    w, bias = jax.random.normal(k[1], (5, 4)), jax.random.normal(k[2], (5,))
    y = causal_conv1d(x, w, bias)
    # position t from t - 3 .. t: changing positions after 6 moves nothing up to 6
    later = causal_conv1d(x.at[:, 7:].add(10.0), w, bias)
    np.testing.assert_allclose(later[:, :7], y[:, :7], rtol=1e-6)
    assert float(jnp.abs(later[:, 7:] - y[:, 7:]).min()) > 0
    # nothing before the row: the first position is the bias and the last tap alone
    np.testing.assert_allclose(y[:, 0], bias + w[:, 3] * x[:, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[:, 1], bias + w[:, 2] * x[:, 0] + w[:, 3] * x[:, 1], rtol=1e-5, atol=1e-6)
    # the reference's convolution, by another route (a grouped lax convolution)
    np.testing.assert_allclose(reference.causal_conv(x[0], w, bias), y[0], rtol=1e-5, atol=1e-5)


# ------------------------------------------------ the model against the plain reference

@pytest.fixture(scope="module", params=["dense", "flash"])
def pair(request):
    """(logits, loss and gradient of the program; of the reference)."""
    module = family.train_model(CFG, dict(MIX, attention=request.param))["module"]
    x = jnp.asarray(_rows(3))
    variables = module.init(jax.random.PRNGKey(0), x)
    spec = family.reference_spec(CFG)

    def program_loss(params):
        return causal_lm_loss(module.apply({**variables, "params": params}, x), x)

    def reference_loss(p):
        total, weight = reference.causal_lm_loss_sums(p, x, x, spec)
        return total / weight

    ref_params = family.reference_params(variables["params"])
    return ((module.apply(variables, x), jax.value_and_grad(program_loss)(variables["params"])),
            (reference.logits(ref_params, x, spec), jax.value_and_grad(reference_loss)(ref_params)))


def test_logits_and_loss_match_the_reference(pair):
    (logits, (loss, _)), (ref_logits, (ref_loss, _)) = pair
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-4, atol=1e-5)
    assert float(loss) == pytest.approx(float(ref_loss), abs=LOSS_ABS) and float(loss) > 1.0


@pytest.mark.parametrize("group", ["emb", "gf", "g1", "g2", "w_gate", "w_up", "w_down", "w_in", "conv_w",
                                   "conv_b", "dt_bias", "a_log", "d", "g_m", "w_o", "wq", "wk", "wv", "wo"])
def test_gradient_matches_the_reference(pair, group):
    (_, (_, got)), (_, (_, want)) = pair
    got = family.reference_params(got)
    leaves = [(got[group], want[group])] if group in want else [
        (a[group], b[group]) for a, b in zip(got["layers"], want["layers"]) if group in b]
    assert leaves
    for a, b in leaves:
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=GRAD_RTOL,
                                   atol=GRAD_ATOL_SHARE * float(jnp.abs(b).max()) + 1e-9)


def test_the_reference_without_the_carried_state_is_told_from_the_program():
    """At the model's level: with slow decays (A near 0) and larger steps every mixer's
    state outlives its chunk and carries much, and the reference that drops it computes
    logits the program does not.
    (At seeded weights the logits are near uniform and the loss barely moves with them:
    the cell's `update_tolerance` is what sees this fault on the chip.)"""
    module = family.train_model(CFG, MIX)["module"]
    x = jnp.asarray(_rows(3))
    variables = module.init(jax.random.PRNGKey(0), x)
    params = jax.tree_util.tree_map_with_path(
        lambda path, v: v + {"A_log": -7.0, "dt_bias": 3.0}.get(jax.tree_util.keystr(path).split("'")[-2], 0.0),
        variables["params"])
    _, updates = module.apply({**variables, "params": params}, x, True, mutable=[SSM_STATE])
    assert float(GraniteHybridLM.step_counters(updates)["ssm_chunk_decay"]) > 0.9
    logits = module.apply({**variables, "params": params}, x)
    spec, ref_params = family.reference_spec(CFG), family.reference_params(params)
    sound, dropped = (reference.logits(ref_params, x, dict(spec, carry_state=c)) for c in (True, False))
    np.testing.assert_allclose(logits, sound, rtol=1e-4, atol=1e-5)
    # the first chunk of every row is the same; from the second on, far outside the tolerance
    np.testing.assert_allclose(dropped[:, :8], sound[:, :8], rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(dropped[:, 8:] - sound[:, 8:]).max()) > 100 * 1e-5


@functools.lru_cache(maxsize=None)
def _reference_update():
    return family.reference_update_fn(CFG, MIX)


@pytest.mark.parametrize("attention,remat", [("dense", False), ("flash", True)])
def test_first_update_through_the_trainer_matches_the_reference(attention, remat):
    """The kind `train_job_update`'s comparison at a test's size: the Trainer's normal step
    against the reference's first step, and the step's counter."""
    from benchmarks.kinds.train_job_update import update_gap
    from kubeflow_tpu.train import Trainer, TrainerConfig

    mix, x = dict(MIX, attention=attention, remat=remat), _rows(8)
    model = family.train_model(CFG, mix)
    trainer = Trainer(model["module"], TrainerConfig(batch_size=8, learning_rate=1e-3, seed=11),
                      loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
    state = trainer.init_state(x)
    before = family.reference_state(state)
    total, count, expected = _reference_update()(before, x, x)
    before = jax.device_get(before)
    state, metrics = trainer.train_step(state, (x, x))
    assert float(metrics["loss"]) == pytest.approx(float(total) / float(count), abs=LOSS_ABS)
    gaps = update_gap(before, expected, jax.device_get(family.reference_state(state)))
    # float32 both sides: Adam's first step is the rate times the gradient's sign, so only
    # gradients at rounding's size can differ
    assert max(gaps.values()) < 0.05, gaps
    assert 0.0 < float(metrics["ssm_chunk_decay"]) < 1.0


def test_the_references_first_update_is_adam_written_out():
    x = jnp.asarray(_rows(2))
    module = family.train_model(CFG, MIX)["module"]
    params = family.reference_params(module.init(jax.random.PRNGKey(1), x)["params"])
    spec = family.reference_spec(CFG)
    total, count, after = reference.first_update(params, x, x, spec, 1e-3)
    assert float(count) == 2 * 31
    _, _, grads = reference.first_gradient(params, x, x, spec)
    for p, g, a in zip(jax.tree.leaves(params), jax.tree.leaves(grads), jax.tree.leaves(after)):
        np.testing.assert_allclose(a, p - 1e-3 * g / (jnp.abs(g) + 1e-8), rtol=1e-5, atol=1e-7)
    # the family's host step is the same arithmetic on the gradient of the same jitted
    # program; XLA flushes to zero the subnormal squares of gradients under 1e-19 that
    # numpy keeps, which moves such a weight by less than a thousandth of the rate
    _, _, jitted = jax.jit(lambda p, x, y: reference.first_gradient(p, x, y, spec))(params, x, x)
    _, _, host = _reference_update()(params, np.asarray(x), np.asarray(x))
    for p, g, h in zip(jax.tree.leaves(params), jax.tree.leaves(jitted), jax.tree.leaves(host)):
        np.testing.assert_allclose(reference.adam_first_step(p, g, 1e-3), h, rtol=1e-6, atol=1e-3 * 1e-3)


# ------------------------------------------------------------- the draw and the counter

def test_the_mixer_is_seeded_as_mamba2_is_but_for_slower_steps():
    mixer = Mamba2Mixer(hidden_size=32, num_heads=64, head_dim=4, state_size=8)
    params = mixer.init(jax.random.PRNGKey(0), jnp.ones((1, 8, 32)))["params"]
    a = np.exp(np.asarray(params["A_log"]))
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.max() - a.min() > 8
    lo, hi = STEP_INIT
    assert lo * 0.999 <= step.min() and step.max() <= hi * 1.001 and step.max() / step.min() > 10
    assert np.all(np.asarray(params["D"]) == 1.0) and np.all(np.asarray(params["norm_gain"]) == 1.0)
    assert float(jnp.abs(params["conv_weight"]).max()) <= 0.5 and params["conv_weight"].shape == (4 * 64 + 16, 4)
    assert params["in_proj"]["kernel"].shape == (32, 2 * 256 + 16 + 64)


def test_the_embedding_enters_at_unit_scale_and_the_head_is_tied():
    module = GraniteHybridLM(GraniteHybridConfig.tiny())
    params = module.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    assert float(params["token_embed"]["embedding"].std()) * 12 == pytest.approx(1.0, rel=0.05)
    assert "lm_head" not in params


def test_each_sublayer_enters_the_residual_at_the_multiplier_in_bf16_too():
    """bf16 holds 0.22 as 0.2197265625: the multiplier taken in the step's bf16 makes every
    sublayer 0.12 % short, a bias no float32 comparison sees. With the block's input at zero,
    its attention's output drawn and its MLP's at zero, the block returns the attention's
    output times 0.22 rounded once to bf16, whose mean share of error is nil."""
    import flax.linen as nn
    from kubeflow_tpu.models.granite_hybrid import ATTENTION, GraniteAttention, GraniteHybridBlock

    cfg = GraniteHybridConfig.tiny(dtype=jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(3), (4, 32, cfg.hidden_size)).astype(jnp.bfloat16)

    def drawn(next_fun, args, kwargs, context):
        if isinstance(context.module, GraniteAttention):
            return y
        if context.module.name == "mlp_down":
            return jnp.zeros_like(y)
        return next_fun(*args, **kwargs)

    block, x = GraniteHybridBlock(cfg, ATTENTION), jnp.zeros_like(y)
    with nn.intercept_methods(drawn):
        out = block.apply(block.init(jax.random.PRNGKey(0), x, False), x, False)
    exact = y.astype(jnp.float32) * 0.22
    np.testing.assert_array_equal(out, exact.astype(jnp.bfloat16))
    bias = lambda got: float(jnp.mean(got.astype(jnp.float32) / exact - 1))  # noqa: E731
    assert abs(bias(out)) < 1e-4
    assert bias(y * jnp.asarray(0.22, jnp.bfloat16)) < -1e-3      # the multiplier in bf16


def test_the_step_counter_is_the_mean_of_the_mixers_chunk_decays():
    module = GraniteHybridLM(GraniteHybridConfig.tiny())
    x = jnp.asarray(_rows(2, 32))
    variables = module.init(jax.random.PRNGKey(0), x)
    assert set(variables[SSM_STATE]) == {"layer_0", "layer_2"}   # the Mamba-2 layers
    _, updates = module.apply(variables, x, True, mutable=[SSM_STATE])
    kept = [float(updates[SSM_STATE][f"layer_{i}"]["mamba"]["chunk_decay"]) for i in (0, 2)]
    counters = GraniteHybridLM.step_counters(updates)
    assert float(counters["ssm_chunk_decay"]) == pytest.approx(np.mean(kept)) and 0 < min(kept)
    assert ssm_counters({}) == {} and GraniteHybridLM.step_counters({}) == {}


@pytest.mark.parametrize("field,value,match", [
    ("attention", "ring", "dense|flash"), ("layer_types", ("mamba", "moe", "mamba"), "layer_types"),
    ("num_kv_heads", 3, "tile"), ("mamba_groups", 3, "groups")])
def test_the_config_refuses_what_the_block_cannot_be(field, value, match):
    with pytest.raises(ValueError, match=match):
        GraniteHybridConfig.tiny(**{field: value})


def test_the_published_layer_pattern_is_the_default():
    kinds = GraniteHybridConfig(num_layers=40).layer_types
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]


# ------------------------------------------------- the step's names, as the readers read

@pytest.fixture(scope="module")
def step_names():
    from kubeflow_tpu.train import Trainer, TrainerConfig

    model = family.train_model(CFG, dict(MIX, remat=True))
    trainer = Trainer(model["module"], TrainerConfig(batch_size=8, learning_rate=1e-3, seed=1),
                      loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
    x = _rows(8)

    def _train_step(state, batch):
        return trainer._train_step(state, batch)

    with jax.set_mesh(trainer.mesh):
        text = jax.jit(_train_step).lower(trainer.abstract_state(x), (x, x)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_the_mixers_scopes_and_passes_are_in_the_steps_names(step_names):
    """Every scope under `layer_N/mamba` in both Mamba-2 layers; the scan's forward, its
    forward run again under `remat` and its backward each found by `train_ssm.kind_of`;
    all of the mixer in the block's dense part; attention's core where the accepted
    readers look for it."""
    from benchmarks.layer_metrics import train_parts, train_ssm

    for layer in (0, 2):
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj"):
            assert any(f"/layer_{layer}/mamba/{scope}/" in n for n in step_names), (layer, scope)
    kinds = {train_ssm.kind_of(n) for n in step_names}
    assert set(train_ssm.SCAN_KINDS) | {train_ssm.OTHER} <= kinds
    assert {train_parts.part_of(n) for n in step_names if train_ssm.kind_of(n)} == {"block_dense"}
    core = {train_parts.part_of(n) for n in step_names if "/layer_1/attention/" in n
            and not re.search(r"/attention/(query|key|value|attn_out)/", n)}
    assert core == {"attn_core_fwd", "attn_core_bwd"}


def test_the_example_trains_and_evaluates_through_fit(capsys):
    """`python -m examples.granite_hybrid`: `Trainer.fit`'s loop, log line and eval pass (eight
    rows: the tests' eight virtual devices)."""
    from examples import granite_hybrid as example

    final = example.main(["--device=cpu", "--steps=4", "--batch-size=8", "--seq-len=32"])
    assert np.isfinite(final)
    log = capsys.readouterr().out
    assert "ssm_chunk_decay=" in log and "eval_loss=" in log
