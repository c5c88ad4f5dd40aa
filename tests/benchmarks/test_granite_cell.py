"""What the Granite 4.0-H cell adds under `benchmarks/`: the parameter count and the hand
counts of `flops_ssm.py` and of the family's FLOP a token, the configuration file against
the catalog's keys, the mix, the manifest's entries looked up by name, the family between
the program and `reference_granite.py` (the forms it refuses, the kind `train_job_update`
end to end at a tiny size, the float8 control at the cell's own limits), and the readers
of `layer_metrics/train_ssm.py` on a synthetic trace. On the CPU; nothing here times
anything."""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_ssm, reference_granite, run, runtime
from benchmarks.families import granite_hybrid as family
from benchmarks.layer_metrics import train_parts, train_ssm

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "benchmarks/configs/granite-4.0-h-micro.json").read_text())
MIX = json.loads((ROOT / "benchmarks/traffic/lm-packed-8k-ssm.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL, CONFIG = "granite4h-train-8k", "granite-4.0-h-micro"
PUBLISHED_KINDS = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
#: the catalog row's `config` (model-configs guide, `architectures.jsonl`, granite-4.0-h-micro)
CATALOG = {"attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
           "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
           "layer_types": PUBLISHED_KINDS, "logits_scaling": 8, "mamba_chunk_size": 256,
           "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
           "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
           "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
           "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
           "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
           "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
           "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192,
           "tie_word_embeddings": True, "vocab_size": 100352}
#: the benchmark's keys at a test's size: both layer kinds, chunks of 8 over rows of 32
TINY = dict(CFG, vocab_size=300, hidden_size=32, num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
            num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=64, intermediate_size=64,
            mamba_n_heads=4, mamba_d_head=16, mamba_expand=2, mamba_d_state=8, mamba_chunk_size=8)
TINY_MIX = {"kind": "train_job_update", "task": "causal_lm", "attention": "dense", "seq_len": 32,
            "batch": 8, "pool_batches": 3, "chain_noise": 0.1, "learning_rate": 1e-3, "warmup_steps": 0,
            "descent_steps": 3, "reference_rows_per_call": 8, "loss_tolerance": 1e-4, "update_tolerance": 0.1}
#: the scan at the cell's shapes: one row of 8,192, 64 heads of 64, state 128, one group, chunks of 256
SHAPE = (1, 8192, 64, 64, 128, 1, 256)
#: what the chip read (the mix's reasons): the worst sound first-loss gap and `update_gap` over
#: nine seeds, the least a block taking the residual multiplier in bf16 read on the loss, the
#: least a missing last layer read on the loss, the least the dropped state read
LOSS_GAP_WORST, LOSS_GAP_BF16_MULTIPLIER_LEAST, LOSS_GAP_MISSING_LAYER_LEAST = 7.55e-5, 3.55e-4, 2.10e-2
UPDATE_GAP_WORST, UPDATE_GAP_NO_CARRY_LEAST = 0.1850, 0.6955


# ------------------------------------------------------------------ the hand counts

def test_the_parameters_equal_a_hand_count():
    """772,160,448: counted from the configuration's keys and again from the shapes the
    program makes for it."""
    h = 2048
    in_proj = h * (2 * 4096 + 2 * 128 + 64)
    mixer = in_proj + 4352 * 4 + 4352 + 4096 * h + 3 * 64 + 4096
    assert (in_proj, mixer) == (17_432_576, 25_847_232)
    mlp = 3 * h * 8192
    mamba_layer, attention_layer = mixer + mlp + 2 * h, 2 * h * h + 2 * h * 512 + mlp + 2 * h
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    assert 9 * mamba_layer + attention_layer == 746_468_288
    total = 9 * mamba_layer + attention_layer + 12_544 * h + h
    assert total == 772_160_448
    module = family.train_model(CFG, MIX)["module"]
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes["params"])) == total
    mamba = shapes["params"]["layer_0"]["mamba"]
    assert mamba["in_proj"]["kernel"].shape == (2048, 8512) and mamba["conv_weight"].shape == (4352, 4)
    attention = shapes["params"]["layer_5"]["attention"]
    assert attention["query"]["kernel"].shape == (2048, 32, 64) and attention["key"]["kernel"].shape == (2048, 8, 64)
    assert [k for k in sorted(shapes["params"]) if k.startswith("layer_") and "attention" in shapes["params"][k]] \
        == ["layer_5"]


def test_the_scans_flop_and_bytes_equal_a_hand_count():
    """Forward: C B^T over the chunks' visible pairs, the products within a chunk, the
    chunk states and the reads of the carried state; about 26.1 GFLOP a layer."""
    pairs = 32 * 256 * 257 // 2
    assert pairs == 1_052_672
    parts = (2 * 1 * pairs * 128, 2 * 64 * pairs * 64, 4 * 64 * 8192 * 128 * 64)
    assert parts == (269_484_032, 8_623_489_024, 17_179_869_184)
    assert flops_ssm.scan_fwd_flop(*SHAPE) == sum(parts) == 26_072_842_240
    assert flops_ssm.scan_bwd_flop(*SHAPE) == 2 * sum(parts)
    # x and y 67,108,864 B each in bf16, the steps 2,097,152 in float32, B and C 2,097,152 each,
    # 32 float32 states of 64 x 64 x 128: 67,108,864
    tensors = 2 * 67_108_864 + 2_097_152 + 2 * 2_097_152
    assert flops_ssm.scan_fwd_bytes(*SHAPE) == tensors + 67_108_864 == 207_618_048
    assert flops_ssm.scan_bwd_bytes(*SHAPE) == 2 * tensors + 67_108_864
    # at the v5e's peaks the least time is the bytes': 126 FLOP a byte against 240
    assert flops_ssm.scan_fwd_flop(*SHAPE) / flops_ssm.scan_fwd_bytes(*SHAPE) < 197e12 / 819e9
    assert flops_ssm.scan_shape(CFG, MIX) == SHAPE


def test_the_familys_flop_a_token_equals_a_hand_count():
    mixer = 2048 * 8512 + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    weights = 9 * mixer + attention + 10 * 3 * 2048 * 8192 + 2048 * 12_544
    assert flops_ssm.matmul_params_per_token(CFG) == weights == 771_883_008
    # attention: (256 + 640) FLOP a visible pair a head, 32 heads, one layer, a token of 8,192
    attention_flop = 896 * 32 * (8192 * 8193 // 2) // 8192
    scans = 9 * 3 * 26_072_842_240 // 8192
    assert (attention_flop, scans) == (117_454_848, 85_933_440)
    assert family.train_flop_per_token(CFG, MIX) == 6 * weights + attention_flop + scans == 4_834_686_336
    assert family.train_flop_per_token(CFG, MIX, {"ssm_chunk_decay": 0.01}) == 4_834_686_336


# ------------------------------------------------- the configuration and the manifest

def test_the_configuration_keeps_the_catalogs_keys_but_the_three_it_cuts():
    cut = {"num_hidden_layers": 10, "layer_types": PUBLISHED_KINDS[:10], "vocab_size": 12_544}
    assert sorted(CFG["reduced_why"]) == sorted(cut)
    assert {k: CFG[k] for k in CATALOG} == {**CATALOG, **cut}
    assert CFG["published"] == {k: CATALOG[k] for k in cut}
    assert CFG["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    assert CFG["family"] == "granite_hybrid"
    # the floors: one whole period, an eighth of the vocabulary
    assert CFG["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert CFG["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert "four pipeline stages" in CFG["deployment"] and "Yeung et al., 2024" in CFG["deployment"]
    assert {"origin", "mixer", "gated_norm", "chunk", "mlp", "multipliers", "train_attention", "train_dtypes",
            "weights"} <= set(CFG["assumed"])


def test_the_mix_is_the_cells():
    assert {k: MIX[k] for k in ("kind", "task", "batch", "seq_len", "attention", "remat", "learning_rate",
                                "warmup_steps", "pool_batches", "chain_noise", "descent_steps",
                                "reference_rows_per_call")} == {
        "kind": "train_job_update", "task": "causal_lm", "batch": 1, "seq_len": 8192, "attention": "flash",
        "remat": True, "learning_rate": 1e-4, "warmup_steps": 0, "pool_batches": 24, "chain_noise": 0.1,
        "descent_steps": 4, "reference_rows_per_call": 1}
    # the traffic is `lm-packed-8k`'s; the tolerances and their readings are this model's
    other = json.loads((ROOT / "benchmarks/traffic/lm-packed-8k.json").read_text())
    assert all(MIX[k] == other[k] for k in other if not k.endswith("_why") and k not in (
        "notes", "loss_tolerance", "update_tolerance"))
    # each limit with the readings it lies between, taken on the chip, with room on both sides
    assert "v5e" in MIX["loss_tolerance_why"] and "no carried state" in MIX["update_tolerance_why"]
    assert 3 * LOSS_GAP_WORST <= MIX["loss_tolerance"] <= LOSS_GAP_MISSING_LAYER_LEAST / 3
    assert MIX["loss_tolerance"] < LOSS_GAP_BF16_MULTIPLIER_LEAST
    assert 1.5 * UPDATE_GAP_WORST < MIX["update_tolerance"] < UPDATE_GAP_NO_CARRY_LEAST / 1.5 < 1.0


def test_the_manifest_lists_the_configuration_the_cell_and_the_four_metrics():
    """Each entry of this cell found by name, whole and within the manifest's limits."""
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry["source"] == CFG["source"] and entry["file"] == "benchmarks/configs/granite-4.0-h-micro.json"
    assert sorted(entry["reduced"]) == sorted(CFG["reduced_why"])
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "lm-packed-8k-ssm", "chips": 1, "why": cell["why"]}
    assert all(word in cell["why"] for word in ("8,192", "Mamba-2", "32 chunks of 256", "remat"))
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    lists = {m["name"]: m.get("workloads") for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    joined = {"train_tokens_per_s", "step_ms.train", "dispatch_ms.train", "mfu.train", "device_idle_share.train",
              "hbm_peak_share.train", "attn_core_fwd_ms.train", "attn_core_bwd_ms.train", "block_dense_ms.train",
              "embed_head_ms.train", "optimizer_ms.train", "unattributed_share.train", "enqueue_ms.train",
              "place_batch_ms.train"}
    assert all(CELL in lists[name] for name in joined | set(train_ssm.METRICS))
    # not the six that read `setup_s` by phase: their lists are pinned to the cells before this one
    assert not any(CELL in lists[name] for name in ("import_s.train", "init_state_s.train", "step_trace_s.train",
                                                    "step_backend_s.train", "programs_compiled.train",
                                                    "build_s.train"))
    assert lists["setup_s"] is None
    readers = run.layer_readers()
    for name in train_ssm.METRICS:
        (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert readers[name] is train_ssm.METRICS[name]
    assert next(w for w in MANIFEST["workloads"] if w["name"] == CELL)["chips"] == 1


def test_the_program_config_is_the_files():
    cfg = family._program_config(CFG, MIX)
    assert (cfg.num_layers, cfg.vocab_size, cfg.hidden_size, cfg.mlp_dim) == (10, 12_544, 2048, 8192)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, cfg.mamba_groups) == (64, 64, 128, 1)
    assert (cfg.mamba_conv, cfg.mamba_chunk, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4, 256, 32, 8, 64)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.015625, 8.0)
    assert (cfg.attention, cfg.remat, cfg.norm_eps) == ("flash", True, 1e-5)
    assert cfg.layer_types == tuple(CFG["layer_types"])
    with pytest.raises(ValueError, match="causal_lm"):
        family.train_model(CFG, dict(MIX, task="classification"))
    with pytest.raises(ValueError, match="no warm-up"):
        family.reference_update_fn(CFG, dict(MIX, warmup_steps=10))


@pytest.mark.parametrize("key,value", [("num_local_experts", 62), ("position_embedding_type", "rope"),
                                       ("mamba_proj_bias", True), ("tie_word_embeddings", False),
                                       ("mamba_expand", 3)])
def test_the_family_refuses_a_form_the_program_does_not_build(key, value):
    """The family's MoE form, rotary positions, a biased projection, an untied head, an
    inner width that is not the heads': stated in a file, they are refused, not ignored."""
    with pytest.raises(ValueError, match=key):
        family._program_config(dict(CFG, **{key: value}), MIX)


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    source = (ROOT / "benchmarks/reference_granite.py").read_text()
    assert "import kubeflow_tpu" not in source and "from kubeflow_tpu" not in source
    assert reference_granite.HIGHEST == jax.lax.Precision.HIGHEST and source.count("precision=HIGHEST") >= 3
    assert "def ssd_by_position" in source and "carry_state" in source


# ------------------------------------------------------ the kind and the float8 control

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
    out = kind.run(TINY, TINY_MIX, 2147485999, 0.2, _NoTrace(), {"log": lines.append, "builds": runtime.Builds()})
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    assert out["facts"]["tokens_per_step"] == 8 * 32
    assert out["facts"]["flop_per_token"] == family.train_flop_per_token(TINY, TINY_MIX)
    assert 0.0 < out["facts"]["step_counters"]["ssm_chunk_decay"] < 1.0
    gap_line = next(line for line in lines if line.startswith("update_gap="))
    assert all(f"layers/{group}=" in gap_line for group in ("w_in", "conv_w", "a_log", "dt_bias", "g_m", "wq"))


def _first_step(seed, dtype=None):
    """(loss, state before, state after) of the reference's first step at the tiny size,
    every product's operands rounded to `dtype` (None: float32 as it stands)."""
    ids = np.asarray(np.random.default_rng(seed).integers(1, 300, size=(8, 32)), np.int32)
    module = family.train_model(TINY, TINY_MIX)["module"]
    before = family.reference_params(module.init(jax.random.PRNGKey(seed), ids)["params"])
    plain_mm, plain_einsum = reference_granite._mm, reference_granite._einsum
    if dtype is not None:
        rounded = lambda f: lambda *a: f(*a[:-2], *(v.astype(dtype).astype(jnp.float32) for v in a[-2:]))  # noqa: E731
        reference_granite._mm, reference_granite._einsum = rounded(plain_mm), rounded(plain_einsum)
    try:
        total, weight, after = reference_granite.first_update(before, ids, ids, family.reference_spec(TINY), 1e-3)
    finally:
        reference_granite._mm, reference_granite._einsum = plain_mm, plain_einsum
    return float(total) / float(weight), jax.device_get(before), jax.device_get(after)


@pytest.mark.parametrize("seed", [1, 2])
def test_float8_compute_in_the_programs_place_fails_the_cells_update_limit(seed):
    """The nearest precision below the stated one has to come out not `correct`: the
    float8-rounded reference's first state stands where the program's would, and the kind's
    comparison is made at the MIX's own limit; the float32 reference in the same place
    passes, and a state left as it was fails."""
    from benchmarks.kinds.train_job_update import update_gap

    def worst(after):
        gaps = update_gap(before, expected, after)
        return max(v for g, v in gaps.items() if g != "all")

    _, before, expected = _first_step(seed)
    assert worst(expected) == 0.0
    assert worst(_first_step(seed, jnp.float8_e4m3fn)[2]) > MIX["update_tolerance"]
    assert worst(before) > MIX["update_tolerance"]


# ------------------------------------------------------- the readers, synthetic events

def _ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


STEP = "jit(_train_step)/"
MODEL = STEP + "jvp(train.loss)/GraniteHybridLM/"
BACK = STEP + "transpose(jvp(train.loss))/GraniteHybridLM/jvp(train.loss)/GraniteHybridLM/checkpoint/"
NAMES = {
    "%in = fusion()": MODEL + "layer_0/mamba/ssm.in_proj/in_proj/dot_general",
    "%scan = fusion()": MODEL + "layer_0/mamba/ssm.scan/while/body/closed_call/checkpoint/bgkts,bsgkp->btgkp/dot_general",
    "%again = fusion()": BACK + "rematted_computation/layer_0/mamba/ssm.scan/while/body/closed_call/exp",
    "%scan_bwd = fusion()": BACK + "layer_0/mamba/ssm.scan/while/body/closed_call/checkpoint/transpose",
    "%norm_bwd = fusion()": BACK + "layer_1/mamba/ssm.gate_norm/mul",
    "%mlp = fusion()": MODEL + "layer_0/mlp_gate/dot_general",
    "%adam = fusion()": STEP + "train.optimizer/mul",
}
#: one step of 300 us: (event, offset, duration)
STEP_OPS = [("%in = fusion()", 0, 20), ("%scan = fusion()", 20, 50), ("%mlp = fusion()", 70, 30),
            ("%again = fusion()", 100, 40), ("%scan_bwd = fusion()", 140, 100), ("%norm_bwd = fusion()", 240, 30),
            ("%adam = fusion()", 270, 30)]
READER_CFG = dict(TINY, layer_types=["mamba", "mamba"])
READER_MIX = {"batch": 1, "seq_len": 32, "remat": True}
FAST = {"flops_per_s_bf16": 1e9, "hbm_bytes_per_s": 1e12}


def _ctx(names=NAMES, whole=3, ops=STEP_OPS, **over):
    events, modules = [], []
    for i in range(whole + 2):
        origin = 310 * i
        events += [_ev(name, origin + off, dur) for name, off, dur in ops]
        modules.append(_ev("jit__train_step(9)", origin, 300))
    ctx = {"events": {"devices": {0: {"ops": events, "modules": modules}}, "host": []},
           "op_names": names,
           "facts": {"step_program": r"^jit__train_step\b", "step_counters": {"ssm_chunk_decay": 0.0137}},
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9},
           "config": READER_CFG, "traffic": READER_MIX}
    ctx.update(over)
    return ctx


def test_the_mixer_and_scan_times_and_the_parts_that_hold_them():
    ctx = _ctx()
    times = train_ssm.mixer_times(ctx)
    assert times["recomputed"] == 1      # layer 0's scan ran again under `transpose(`
    assert train_ssm.ssm_ms(ctx) == pytest.approx(0.020 + 0.050 + 0.040 + 0.100 + 0.030)
    assert train_ssm.ssm_scan_ms(ctx) == pytest.approx(0.050 + 0.040 + 0.100)
    assert train_ssm.ssm_chunk_decay(ctx) == pytest.approx(1.37)
    # the mixer is the block's dense work, inside `block_dense_ms.train`
    assert train_parts.part_times_ms(ctx)["block_dense"] == pytest.approx(train_ssm.ssm_ms(ctx) + 0.030)


@pytest.mark.parametrize("op_name,kind", [
    (MODEL + "layer_3/mamba/ssm.scan/while/body/closed_call/exp", train_ssm.FWD),
    (BACK + "rematted_computation/layer_3/mamba/ssm.scan/while/body/closed_call/exp", train_ssm.RECOMPUTED),
    (BACK + "layer_3/mamba/ssm.scan/while/body/closed_call/checkpoint/transpose", train_ssm.BWD),
    (BACK + "layer_3/mamba/ssm.conv/add", train_ssm.OTHER),
    (MODEL + "layer_3/mamba/div", train_ssm.OTHER),
    (MODEL + "layer_3/mamba/ssm.scanner/exp", train_ssm.OTHER),
    (MODEL + "layer_3/mamba_like/ssm.scan/exp", None),
    (MODEL + "layer_5/attention/flash_fwd_resident_q256_k512/pallas_call", None),
    ("jit(other)/layer_3/mamba/ssm.scan/exp", None),
])
def test_the_scopes_are_matched_as_whole_segments_of_the_steps_names(op_name, kind):
    assert train_ssm.kind_of(op_name) == kind


def test_the_roofline_share_is_the_least_time_over_the_scans_time():
    shape = flops_ssm.scan_shape(READER_CFG, READER_MIX)
    fwd, bwd = flops_ssm.scan_fwd_flop(*shape), flops_ssm.scan_bwd_flop(*shape)
    fwd_b, bwd_b = flops_ssm.scan_fwd_bytes(*shape), flops_ssm.scan_bwd_bytes(*shape)
    # two Mamba-2 layers forward and backward, and layer 0's forward again: 190 us a step
    flop, moved = 2 * (fwd + bwd) + fwd, 2 * (fwd_b + bwd_b) + fwd_b
    read = train_ssm.METRICS["ssm_scan_roofline_share.train"]
    assert read(_ctx()) == pytest.approx(100 * max(flop / 1e12, moved / 1e9) / 190e-6)
    assert read(_ctx(peaks=FAST)) == pytest.approx(100 * max(flop / 1e9, moved / 1e12) / 190e-6)
    # without the recomputed forward in the trace none is counted
    ops = [op for op in STEP_OPS if op[0] != "%again = fusion()"]
    assert read(_ctx(ops=ops, peaks=FAST)) == pytest.approx(100 * (2 * (fwd + bwd) / 1e9) / 150e-6)


@pytest.mark.parametrize("over", [
    {"names": {}},                                                         # a trace without names
    {"names": {k: v for k, v in NAMES.items() if "/mamba/" not in v}},    # a program without mixers
    {"whole": 0},                                                          # no whole step
])
def test_a_program_without_the_mechanism_reads_nothing_and_nothing_raises(over):
    """The parent commit has no `layer_N/mamba` module and its kind hands on no
    `ssm_chunk_decay`: every reader returns None there, and the line leaves it out."""
    ctx = _ctx(**over)
    ctx["facts"].pop("step_counters")
    assert all(reader(ctx) is None for reader in train_ssm.METRICS.values())
    # an accepted cell's configuration has no state-space keys
    other = _ctx(config={"num_hidden_layers": 5, "num_attention_heads": 32, "head_dim": 128})
    assert train_ssm.METRICS["ssm_scan_roofline_share.train"](other) is None
