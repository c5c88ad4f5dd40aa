"""What PR 32 adds under `benchmarks/`: the hand counts of `flops_blockdiff.py` and of the
SDAR-MoE family's parameters and FLOP a token, the configuration file against the
catalog's keys, the mix against ISSUE 32's, the family between the program and
`reference_sdar.py`, the kind `train_job_update` end to end with the family at a tiny
size, and the readers of `layer_metrics/train_blockdiff.py` on a synthetic trace. On the
CPU, in seconds; nothing here times anything."""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_blockdiff, reference_sdar, runtime
from benchmarks.families import sdar_moe as family
from benchmarks.layer_metrics import train_blockdiff, train_moe, train_parts

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "benchmarks/configs/sdar-30b-a3b.json").read_text())
MIX = json.loads((ROOT / "benchmarks/traffic/lm-blockdiff-4k.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "sdar30b-train-4k"
#: the worst `update_gap` a seed read on the chip (the mix's `update_tolerance_why`)
UPDATE_GAP_WORST = 0.7893
STEP = "jit(_train_step)/"
#: the catalog row's `config` (model-configs guide, `architectures.jsonl`, SDAR-30B-A3B-Chat)
CATALOG = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
           "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
           "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
           "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
           "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
           "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
           "use_sliding_window": False, "vocab_size": 151936}
#: the benchmark's keys at a test's size: 8 experts of which this share holds 4, 2 a token
TINY = dict(CFG, vocab_size=300, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, router_width=8, num_experts=4, experts_held=[2, 6],
            num_experts_per_tok=2, moe_intermediate_size=16)
TINY_MIX = {"kind": "train_job_update", "task": "causal_lm", "attention": "dense", "seq_len": 32,
            "batch": 8, "block_length": 4, "mask_rate_min": 0.05, "pool_batches": 3,
            "chain_noise": 0.1, "learning_rate": 1e-3, "warmup_steps": 0, "descent_steps": 3,
            "reference_rows_per_call": 8, "loss_tolerance": 1e-4, "update_tolerance": 0.1}


# ------------------------------------------------------------------ the hand counts

@pytest.mark.parametrize("seq_len,block,pairs", [
    (4096, 4, 16_793_600),   # 4096 x 4100: of the 67,108,864 of the 8,192 x 8,192 square
    (16, 2, 288), (32, 4, 1152), (64, 16, 5120),
    (4, 4, 32),              # one block: clean on clean 16, noisy on noisy 16
])
def test_visible_pairs_equal_a_hand_count(seq_len, block, pairs):
    assert flops_blockdiff.visible_pairs(seq_len, block) == pairs
    # clean on clean, block-causal + noisy on earlier clean blocks + noisy on its own block
    assert pairs == seq_len * (seq_len + block) // 2 + seq_len * (seq_len - block) // 2 + seq_len * block


def test_visible_pairs_refuse_a_row_of_no_whole_blocks():
    with pytest.raises(ValueError, match="do not tile"):
        flops_blockdiff.visible_pairs(30, 4)


@pytest.mark.parametrize("backward,remat,per_pair", [(False, False, 4), (True, False, 10), (True, True, 14)])
def test_attention_flop_and_bytes_equal_a_hand_count(backward, remat, per_pair):
    # one layer, 32 heads of 128 over 16,793,600 visible pairs
    assert flops_blockdiff.attention_flop(1, 32, 128, 4096, 4, backward, remat) \
        == per_pair * 32 * 128 * 16_793_600
    # 8,192 positions x 32 heads x 128 in bf16 is 67,108,864 B a tensor, 1,048,576 B the statistic
    tensors = {(False, False): 4, (True, False): 8, (True, True): 12}[backward, remat]
    stats = 2 if remat else 1
    assert flops_blockdiff.attention_bytes(1, 32, 128, 4096, backward, remat) \
        == tensors * 67_108_864 + stats * 1_048_576
    assert flops_blockdiff.attention_bytes(2, 3, 5, 7, False, itemsize=4) == 4 * 2 * 14 * 3 * 5 * 4 + 2 * 14 * 3 * 4


def test_the_configurations_parameters_equal_the_issues_hand_count():
    """550,984,960 parameters, counted from the configuration's keys and again from the
    shapes the program makes for it."""
    h, d = CFG["hidden_size"], CFG["head_dim"]
    attention = h * d * (CFG["num_attention_heads"] + 2 * CFG["num_key_value_heads"]) \
        + CFG["num_attention_heads"] * d * h
    assert attention == 18_874_368
    norms = 2 * h + 2 * d
    assert norms == 4_352
    router, experts = h * CFG["router_width"], CFG["num_experts"] * 3 * h * CFG["moe_intermediate_size"]
    assert (router, experts) == (262_144, 75_497_472)
    layer = attention + norms + router + experts
    assert layer == 94_638_336
    total = CFG["num_hidden_layers"] * layer + 2 * CFG["vocab_size"] * h + h
    assert total == 473_191_680 + 77_791_232 + 2_048 == 550_984_960
    module = family.train_model(CFG, MIX)["module"]
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes["params"])) == 550_984_960
    assert not any("shared" in "/".join(map(str, path)) for path, _ in
                   jax.tree_util.tree_leaves_with_path(shapes["params"]))


def test_the_familys_flop_a_token_equals_a_hand_count():
    # a position, a layer: attention 18,874,368 + router 262,144 + one routed expert 4,718,592 = 23,855,104
    assert family.matmul_params_per_position(CFG) == 5 * 23_855_104 == 119_275_520
    # a data token: two positions through the layers, the head (2048 x 18,992 = 38,895,616) once
    weights = 2 * 119_275_520 + 38_895_616
    assert weights == 277_446_656
    # attention: 12 x 32 x 128 x 5 layers x (4096 + 4) visible keys a data token
    assert family.train_flop_per_token(CFG, MIX) == 6 * weights + 12 * 4096 * 5 * 4100 == 2_672_295_936
    # an uncut layer computes all 8 of a position's experts
    whole = dict(CFG, num_experts=128, experts_held=[0, 128])
    assert family.matmul_params_per_position(whole) - family.matmul_params_per_position(CFG) \
        == 5 * 7 * 4_718_592


@pytest.mark.parametrize("rows_here,weights", [
    (40_960, 277_446_656),                       # the balanced load: one held expert a position, 5 x 8,192
    (49_152, 277_446_656 + 2 * 4_718_592),       # 8,192 rows over it: two experts a data token
    (20_480, 277_446_656 - 5 * 4_718_592),       # half of it
])
def test_the_routed_experts_count_at_the_rows_the_run_reports(rows_here, weights):
    got = family.train_flop_per_token(CFG, MIX, {"moe_rows_here": rows_here, "diffusion_masked_share": 0.5})
    assert got == 6 * weights + 12 * 4096 * 5 * 4100
    assert family.train_flop_per_token(CFG, MIX, {}) == family.train_flop_per_token(CFG, MIX)


# ------------------------------------------------- the configuration and the manifest

def test_the_configuration_keeps_the_catalogs_keys_but_the_three_it_cuts():
    cut = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 18992}
    assert sorted(CFG["reduced_why"]) == sorted(cut)
    assert {k: CFG[k] for k in CATALOG} == {**CATALOG, **cut}
    assert CFG["published"] == {k: CATALOG[k] for k in cut}
    assert CFG["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert CFG["family"] == "sdar_moe"
    assert CFG["router_width"] == 128 and CFG["experts_held"] == [0, CFG["num_experts"]]
    assert CFG["vocab_size"] * 8 == CATALOG["vocab_size"] and CFG["num_experts"] * 8 == CATALOG["num_experts"]
    # the floors: four layers and more, eight experts and more, an eighth of the vocabulary
    assert CFG["num_hidden_layers"] >= 4 and CFG["num_experts"] >= 8
    assert (CFG["num_dense_layers"], CFG["num_shared_experts"]) == (0, 0)  # what the accepted readers read
    assert "Eight chips share each layer" in CFG["deployment"]
    assert {"block_length", "noise_schedule", "mask_rate_min", "mask_id", "no_shift", "qk_norm",
            "no_auxiliary_balance_loss", "train_dtypes", "weights"} <= set(CFG["assumed"])


def test_the_mix_is_the_issues():
    assert {k: MIX[k] for k in ("kind", "task", "batch", "seq_len", "block_length", "mask_rate_min", "attention",
                                "remat", "learning_rate", "warmup_steps", "pool_batches", "chain_noise",
                                "descent_steps", "reference_rows_per_call")} == {
        "kind": "train_job_update", "task": "causal_lm", "batch": 1, "seq_len": 4096, "block_length": 4,
        "mask_rate_min": 0.05, "attention": "flash", "remat": True, "learning_rate": 1e-4, "warmup_steps": 0,
        "pool_batches": 24, "chain_noise": 0.1, "descent_steps": 4, "reference_rows_per_call": 1}
    assert "fresh noise" in MIX["descent_steps_why"]
    # the mix's `*_why`: the loss's limit is three times the seeds' worst reading (it separates no
    # precision here); the update's lies between the seeds' worst reading and 1 (a state left as it
    # was), and fails float8 on every seed
    assert 3 * 1.05e-3 <= MIX["loss_tolerance"] <= 3.5e-3 and UPDATE_GAP_WORST < MIX["update_tolerance"] < 1.0
    assert "attention_block" not in MIX


def test_the_manifest_lists_the_configuration_the_cell_and_the_four_metrics():
    """`BENCHMARK.json` as ISSUE 32 asks: the entries whole and within the manifest's
    limits, last in their lists, each reader and each list they name in the tree."""
    entry, cell = MANIFEST["configs"][-1], MANIFEST["workloads"][-1]
    assert entry["name"] == "sdar-30b-a3b" and entry["source"] == CFG["source"]
    assert entry["file"] == "benchmarks/configs/sdar-30b-a3b.json" and (ROOT / entry["file"]).exists()
    assert sorted(entry["reduced"]) == sorted(CFG["reduced_why"])
    assert cell == {"name": CELL, "config": entry["name"], "traffic": "lm-blockdiff-4k", "chips": 1, "why": cell["why"]}
    assert (ROOT / "benchmarks/traffic" / f"{cell['traffic']}.json").exists()
    # the `why` says what each data token costs and what the experts see
    assert all(word in cell["why"] for word in ("4,096", "two positions", "8,192", "512 positions an expert"))
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    lists = {m["name"]: m["workloads"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"] if "workloads" in m}
    accepted = set(lists) - set(train_blockdiff.METRICS)
    without = {"flash_fwd_mxu_share.train", "attn_fwd_visible_mxu_share.train", "attn_bwd_visible_mxu_share.train"}
    assert {n for n in accepted if lists[n][-1] == CELL} == accepted - without and "setup_s" not in lists
    assert not any(CELL in lists[n] for n in without)
    assert set(train_moe.METRICS) & without == {"attn_fwd_visible_mxu_share.train", "attn_bwd_visible_mxu_share.train"}
    added = MANIFEST["per_layer"][-4:]
    assert [m["name"] for m in added] == list(train_blockdiff.METRICS)
    layers = {m["layer"] for m in MANIFEST["per_layer"][:-4]}
    for m in added:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s" and m["layer"] in layers
    assert len(MANIFEST["workloads"]) <= 24 and len(MANIFEST["per_layer"]) <= 128


def test_the_program_config_is_the_files():
    cfg = family._program_config(CFG, MIX)
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k, cfg.expert_dim) == (128, (0, 16), 8, 768)
    assert (cfg.num_layers, cfg.vocab_size) == (5, 18992)
    assert (cfg.block_length, cfg.mask_rate_min, cfg.attention, cfg.remat) == (4, 0.05, "flash", True)
    assert cfg.head_dim * cfg.num_heads == 4096 and cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert family.reference_spec(CFG, MIX)["mask_id"] == cfg.vocab_size - 1 == 18991
    with pytest.raises(ValueError, match="causal_lm"):
        family.train_model(CFG, dict(MIX, task="classification"))
    with pytest.raises(ValueError, match="no warm-up"):
        family.reference_update_fn(CFG, dict(MIX, warmup_steps=10))


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    source = (ROOT / "benchmarks/reference_sdar.py").read_text()
    assert "import kubeflow_tpu" not in source and "from kubeflow_tpu" not in source
    assert reference_sdar.HIGHEST == jax.lax.Precision.HIGHEST and source.count("precision=HIGHEST") >= 3


# ------------------------------------------------------ the reference's own arithmetic

@pytest.mark.parametrize("half,block", [(16, 2), (32, 4), (64, 16)])
def test_the_references_mask_is_the_programs_rule(half, block):
    from kubeflow_tpu.parallel.attention_mask import BlockDiffusion

    at = np.arange(2 * half)
    seen = np.asarray(reference_sdar.visible(jnp.asarray(at)[:, None], jnp.asarray(at)[None, :], half, block))
    np.testing.assert_array_equal(seen, ~BlockDiffusion(half, block).hidden(at[:, None], at[None, :]))
    assert seen.sum() == flops_blockdiff.visible_pairs(half, block)


def test_the_references_first_step_is_adam_written_out():
    ids = np.asarray(np.random.default_rng(3).integers(1, 300, size=(2, 32)), np.int32)
    module = family.train_model(TINY, TINY_MIX)["module"]
    params = family.reference_params(module.init(jax.random.PRNGKey(1), ids)["params"])
    masked, weights = family.first_step_noise(TINY, TINY_MIX, jax.random.PRNGKey(2), ids, ids)
    spec = family.reference_spec(TINY, TINY_MIX)
    total, count, after = reference_sdar.first_update(params, jnp.asarray(ids), masked, weights, spec, 1e-3)
    assert float(count) == 2 * 32

    def mean_loss(p):
        t, c = reference_sdar.diffusion_loss_sums(p, jnp.asarray(ids), masked, weights, spec)
        return t / c

    grads = jax.grad(mean_loss)(params)
    for p, g, a in zip(jax.tree.leaves(params), jax.tree.leaves(grads), jax.tree.leaves(after)):
        np.testing.assert_allclose(a, p - 1e-3 * g / (jnp.abs(g) + 1e-8), rtol=1e-5, atol=1e-7)
    # a position the noise left alone weighs nothing: its label may be anything
    other = np.where(np.asarray(masked), ids, 7)
    assert float(reference_sdar.diffusion_loss_sums(params, jnp.asarray(ids), masked, weights, spec)[0]) > 0
    logits = reference_sdar.logits(params, reference_sdar.noisy_ids(jnp.asarray(other), masked, 299), spec)
    assert logits.shape == (2, 32, 300)


@functools.lru_cache(maxsize=None)
def _first_step_fn(dtype):
    """The family's jitted first step at the tiny size with every product's operands rounded
    to `dtype` (None: float32 as it stands). `_mm` is read when the step is traced, once."""
    update, plain = family.reference_update_fn(TINY, TINY_MIX), reference_sdar._mm

    def rounded(a, b):
        return plain(a.astype(dtype).astype(jnp.float32), b.astype(dtype).astype(jnp.float32))

    def call(before, ids):
        reference_sdar._mm = plain if dtype is None else rounded
        try:
            return update(before, ids, ids)
        finally:
            reference_sdar._mm = plain
    return call


def _first_step_at(dtype, seed):
    """(loss, state before, state after) of the reference's first step under one draw."""
    ids = np.asarray(np.random.default_rng(seed).integers(1, 300, size=(8, 32)), np.int32)
    module = family.train_model(TINY, TINY_MIX)["module"]
    before = {**family.reference_params(module.init(jax.random.PRNGKey(seed), ids)["params"]),
              family.RNG: jax.random.PRNGKey(seed + 1)}
    total, weight, after = _first_step_fn(dtype)(before, ids)
    return float(total) / float(weight), jax.device_get(before), jax.device_get(after)


def test_a_lower_precision_of_the_reference_reads_a_wider_gap():
    """The control the cell's `update_tolerance` is set against, as the chip run makes it
    (`lm-blockdiff-4k.json`): the reference's own first step with every product's operands
    rounded to bf16 and to float8 (e4m3), against itself in float32, under one draw."""
    from benchmarks.kinds.train_job_update import update_gap

    _, before, expected = _first_step_at(None, 3)
    gaps = {dtype: update_gap(before, expected, _first_step_at(dtype, 3)[2])
            for dtype in (jnp.bfloat16, jnp.float8_e4m3fn)}
    bf16, fp8 = gaps[jnp.bfloat16]["all"], gaps[jnp.float8_e4m3fn]["all"]
    assert 0.0 < bf16 < 0.5 * fp8 and fp8 > 0.2, gaps


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_compute_in_the_programs_place_fails_the_cells_update_limit(seed):
    """The nearest precision below the stated one has to come out not `correct`: the
    float8-rounded reference's first loss and state stand where the program's would, and
    the kind's two comparisons (`kinds/train_job_update.py` `checks`, restated: that file
    is the accepted benchmark's) are made at the MIX's own limits, not the tiny mix's. The
    float32 reference in the same place passes both, so it is the precision that fails. It is
    the update's limit that fails it on every seed; the loss's is three times the seeds' worst
    reading on the chip and separates no precision (`lm-blockdiff-4k.json`, `loss_tolerance_why`)."""
    from benchmarks.kinds.train_job_update import update_gap

    def checks(loss, after):
        gaps = update_gap(before, expected, after)
        worst = max((g for g in gaps if g != "all"), key=gaps.get)
        return {"first_loss_matches_reference":
                abs(loss - ref_loss) <= float(MIX["loss_tolerance"]) * max(abs(ref_loss), 1.0),
                "first_update_matches_reference": gaps[worst] <= float(MIX["update_tolerance"])}

    ref_loss, before, expected = _first_step_at(None, seed)
    assert checks(ref_loss, expected) == {"first_loss_matches_reference": True,
                                          "first_update_matches_reference": True}
    fp8_loss, _, fp8_after = _first_step_at(jnp.float8_e4m3fn, seed)
    assert not checks(fp8_loss, fp8_after)["first_update_matches_reference"]
    # a state left as it was reads 1, over the limit too
    assert not checks(ref_loss, before)["first_update_matches_reference"]


class _NoTrace:
    def poll(self, _since):
        pass

    def stop(self):
        pass


def test_the_kind_runs_the_family_end_to_end_at_a_tiny_size(monkeypatch):
    from benchmarks.kinds import train_job_update as kind
    from kubeflow_tpu.train import TrainerConfig

    # the chip's policy computes in bf16; float32 here, as the other tiny comparisons
    monkeypatch.setattr(TrainerConfig, "compute_dtype", jnp.float32)
    lines = []
    # every set-up step draws fresh noise, and over 256 positions with weights up to 20 the
    # objective's own spread (a tenth) hides three steps' descent: rates near 1 here
    mix = dict(TINY_MIX, mask_rate_min=0.9)
    out = kind.run(TINY, mix, 2147485999, 0.2, _NoTrace(), {"log": lines.append, "builds": runtime.Builds()})
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    assert out["facts"]["tokens_per_step"] == 8 * 32  # data tokens, not positions
    assert out["facts"]["flop_per_token"] == family.train_flop_per_token(TINY, mix, out["facts"]["step_counters"])
    assert {"diffusion_masked_share", "diffusion_weight_max", "moe_rows_here", "moe_rows_walked",
            "moe_load_max_over_mean"} <= set(out["facts"]["step_counters"])
    assert 0.9 < out["facts"]["step_counters"]["diffusion_masked_share"] < 1.0
    gap_line = next(line for line in lines if line.startswith("update_gap="))
    assert "layers/router=" in gap_line and "rng=0.0000" in gap_line
    checks = next(line for line in lines if "checks=" in line)
    assert "'first_update_matches_reference': True" in checks and "'first_loss_matches_reference': True" in checks


# ------------------------------------------------------- the readers, synthetic events

def _ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


MODEL = STEP + "jvp(train.loss)/SdarMoeLM/"
BACK = STEP + "transpose(jvp(train.loss))/SdarMoeLM/jvp(train.loss)/SdarMoeLM/checkpoint/"
NAMES = {
    "%draw = fusion()": STEP + "train.corrupt/jit(_uniform)/threefry2x32",
    "%noisy = fusion()": STEP + "train.corrupt/select_n",
    "%flash = custom-call()": MODEL + "layer_1/attention/flash_fwd_resident_q256_k512_blockdiff/pallas_call",
    "%reflash = custom-call()": BACK + "rematted_computation/layer_1/attention/flash_fwd_resident_q256_k512_blockdiff/pallas_call",
    "%attn_bwd = fusion()": BACK + "layer_1/attention/flash_bwd_xla_q512_k512_live80of256_blockdiff/while/body/dot_general",
    "%qkv = fusion()": MODEL + "layer_1/attention/query/dot_general",
    "%gmm = custom-call()": MODEL + "layer_1/moe/moe.experts/jit(gmm)/pallas_call",
    "%adam = fusion()": STEP + "train.optimizer/mul",
}
#: one step of 300 us: (event, offset, duration)
STEP_OPS = [("%draw = fusion()", 0, 6), ("%noisy = fusion()", 6, 4), ("%qkv = fusion()", 10, 30),
            ("%flash = custom-call()", 40, 50), ("%gmm = custom-call()", 90, 40),
            ("%reflash = custom-call()", 130, 50), ("%attn_bwd = fusion()", 180, 100), ("%adam = fusion()", 280, 20)]
READER_CFG = {"num_hidden_layers": 2, "num_attention_heads": 2, "head_dim": 16}
READER_MIX = {"batch": 1, "seq_len": 32, "block_length": 4, "remat": True}


def _ctx(names=NAMES, whole=3, **over):
    ops, modules = [], []
    for i in range(whole + 2):
        origin = 310 * i
        ops += [_ev(name, origin + off, dur) for name, off, dur in STEP_OPS]
        modules.append(_ev("jit__train_step(9)", origin, 300))
    ctx = {"events": {"devices": {0: {"ops": ops, "modules": modules}}, "host": []},
           "op_names": names,
           "facts": {"step_program": r"^jit__train_step\b", "step_counters": {"diffusion_masked_share": 0.53}},
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9},
           "config": READER_CFG, "traffic": READER_MIX}
    ctx.update(over)
    return ctx


def test_corrupt_ms_is_the_time_under_the_scope_and_the_optimizer_part_holds_it():
    ctx = _ctx()
    assert train_blockdiff.corrupt_ms(ctx) == pytest.approx(0.010)
    assert train_blockdiff.masked_share(ctx) == pytest.approx(53.0)
    times = train_parts.part_times_ms(ctx)  # outside the differentiated function: with the optimizer
    assert times["optimizer"] == pytest.approx(0.030) and times["attn_core_fwd"] == pytest.approx(0.050)
    assert times["attn_core_bwd"] == pytest.approx(0.150)


@pytest.mark.parametrize("op_name,under", [
    (STEP + "train.corrupt/select_n", True),
    (STEP + "train.corrupt", True),
    (STEP + "train.loss/train.corrupt_like/mul", False),
    (STEP + "jvp(train.loss)/SdarMoeLM/layer_0/moe/moe.route/mul", False),
    ("jit(other)/train.corrupt/mul", False),
])
def test_the_corrupt_scope_is_matched_as_a_whole_segment_of_the_steps_names(op_name, under):
    assert bool(train_blockdiff.CORRUPT.search(op_name)) is under


def test_the_roofline_shares_are_the_least_time_over_the_parts_time():
    ctx = _ctx()
    read = train_blockdiff.METRICS
    fwd = flops_blockdiff.attention_flop(1, 2, 16, 32, 4, False)
    bwd = flops_blockdiff.attention_flop(1, 2, 16, 32, 4, True, True)
    assert bwd == 14 * 2 * 16 * 32 * 36 and fwd == 4 * 2 * 16 * 32 * 36
    # these peaks make the forward memory-bound: 4 tensors of 64 x 2 x 16 bf16 and the statistic
    moved = flops_blockdiff.attention_bytes(1, 2, 16, 32, False)
    assert moved / 1e9 > fwd / 1e12
    assert read["blockdiff_attn_fwd_roofline_share.train"](ctx) == pytest.approx(100 * 2 * (moved / 1e9) / 50e-6)
    fast = _ctx(peaks={"flops_per_s_bf16": 1e9, "hbm_bytes_per_s": 1e12})
    assert read["blockdiff_attn_fwd_roofline_share.train"](fast) == pytest.approx(100 * 2 * (fwd / 1e9) / 50e-6)
    assert read["blockdiff_attn_bwd_roofline_share.train"](fast) == pytest.approx(100 * 2 * (bwd / 1e9) / 150e-6)
    no_remat = _ctx(peaks=fast["peaks"], traffic=dict(READER_MIX, remat=False))
    assert read["blockdiff_attn_bwd_roofline_share.train"](no_remat) == pytest.approx(
        100 * 2 * (10 * 2 * 16 * 32 * 36 / 1e9) / 150e-6)


@pytest.mark.parametrize("over", [
    {"names": {}},                                                         # a trace without names
    {"names": {k: v for k, v in NAMES.items() if "train.corrupt" not in v and "/attention/" not in v}},
    {"whole": 0},                                                          # no whole step
])
def test_a_program_without_the_mechanism_reads_nothing_and_nothing_raises(over):
    """The parent commit has no `train.corrupt` scope and its kind hands on no
    `diffusion_masked_share`: every reader returns None there, and the line leaves it out."""
    ctx = _ctx(**over)
    ctx["facts"].pop("step_counters")
    assert all(reader(ctx) is None for reader in train_blockdiff.METRICS.values())
    # the accepted cells' mixes have no `block_length`
    other = _ctx(traffic={"batch": 1, "seq_len": 8192, "attention": "flash", "remat": True})
    assert other["traffic"].get("block_length") is None
    assert train_blockdiff.METRICS["blockdiff_attn_fwd_roofline_share.train"](other) is None
    assert train_blockdiff.METRICS["blockdiff_attn_bwd_roofline_share.train"](other) is None
