"""What PR 34 adds under `benchmarks/`: the hand counts of `flops_mla.py` and of the
DeepSeek-V2 family's parameters and FLOP a token, the configuration file against the
catalog's keys, the mix against ISSUE 34's, the family between the program and
`reference_deepseek_v2.py`, the kind `train_job_update` end to end with the family at a
tiny size, the float8 control at the cell's own limits, and the readers of
`layer_metrics/train_mla.py` on a synthetic trace. On the CPU, in seconds; nothing here
times anything."""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_mla, reference_deepseek_v2, runtime
from benchmarks.families import deepseek_v2 as family
from benchmarks.layer_metrics import train_mla, train_moe, train_parts

#: the worst `update_gap` a seed read on the chip (the mix's `update_tolerance_why`)
UPDATE_GAP_WORST = 0.2795
#: the worst relative gap of the first loss a run read on the chip, and the least the float32
#: reference read without its last layer (the mix's `loss_tolerance_why`: float8's readings lie
#: among the sound ones, so the missing layer is the fault the loss's limit is set against)
LOSS_GAP_WORST, LOSS_GAP_MISSING_LAYER_LEAST = 2.40e-5, 3.58e-4
ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "benchmarks/configs/deepseek-v2-lite.json").read_text())
MIX = json.loads((ROOT / "benchmarks/traffic/lm-packed-8k-mla.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "dsv2lite-train-8k"
STEP = "jit(_train_step)/"
#: the catalog row's `config` (model-configs guide, `architectures.jsonl`, DeepSeek-V2-Lite)
CATALOG = {"attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 10944, "kv_lora_rank": 512, "max_position_embeddings": 163840,
           "model_type": "deepseek_v2", "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
           "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
           "num_attention_heads": 16, "num_experts_per_tok": 6, "num_hidden_layers": 27,
           "num_key_value_heads": 16, "q_lora_rank": None, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                            "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"},
           "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax", "seq_aux": True,
           "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
           "vocab_size": 102400}
#: the benchmark's keys at a test's size: 8 experts of which this share holds 4, 2 a token
TINY = dict(CFG, vocab_size=300, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=16, intermediate_size=64,
            moe_intermediate_size=16, router_width=8, num_experts=4, n_routed_experts=4, experts_held=[2, 6],
            num_experts_per_tok=2, aux_loss_alpha=0.01,
            rope_scaling=dict(CFG["rope_scaling"], original_max_position_embeddings=16, beta_fast=4))
TINY_MIX = {"kind": "train_job_update", "task": "causal_lm", "attention": "dense", "seq_len": 32,
            "batch": 8, "pool_batches": 3, "chain_noise": 0.1, "learning_rate": 1e-3, "warmup_steps": 0,
            "descent_steps": 3, "reference_rows_per_call": 8, "loss_tolerance": 1e-4, "update_tolerance": 0.1}


# ------------------------------------------------------------------ the hand counts

@pytest.mark.parametrize("seq_len,pairs", [(8192, 33_558_528), (4, 10), (1, 1), (1024, 524_800)])
def test_visible_pairs_equal_a_hand_count(seq_len, pairs):
    assert flops_mla.visible_pairs(seq_len) == pairs == seq_len * (seq_len + 1) // 2


@pytest.mark.parametrize("backward,per_pair,tensors", [(False, 640, (2, 2)), (True, 1664, (4, 4))])
def test_attention_flop_and_bytes_equal_a_hand_count(backward, per_pair, tensors):
    """One layer, 16 heads, keys of 192 and values of 128 over 33,558,528 visible pairs:
    forward scores 2 x 192 and context 2 x 128; backward scores again, dQ and dK at 192, dV
    and dP at 128; no recomputed forward in either."""
    flop = flops_mla.attention_bwd_flop if backward else flops_mla.attention_fwd_flop
    moved = flops_mla.attention_bwd_bytes if backward else flops_mla.attention_fwd_bytes
    assert per_pair == (2 * (3 * 192 + 2 * 128) if backward else 2 * 192 + 2 * 128)
    assert flop(1, 16, 192, 128, 33_558_528) == per_pair * 16 * 33_558_528
    # 8,192 positions x 16 heads: a tensor at 192 is 50,331,648 B in bf16, at 128 33,554,432 B,
    # the float32 row statistic 524,288 B
    at_qk, at_v = tensors
    assert moved(1, 16, 192, 128, 8192) == at_qk * 50_331_648 + at_v * 33_554_432 + 524_288
    assert moved(2, 3, 5, 7, 11, itemsize=4) == 2 * 11 * 3 * ((at_qk * 5 + at_v * 7) * 4 + 4)


def test_the_configurations_parameters_equal_the_issues_hand_count():
    """635,466,752 parameters, counted from the configuration's keys and again from the
    shapes the program makes for it."""
    h = CFG["hidden_size"]
    attention = flops_mla.attention_params(CFG)
    assert attention == 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048 == 13_762_560
    norms = 2 * h + CFG["kv_lora_rank"]  # two block norms and the latent's
    router, shared = h * CFG["router_width"], 3 * h * 2 * CFG["moe_intermediate_size"]
    experts = CFG["num_experts"] * 3 * h * CFG["moe_intermediate_size"]
    assert (router, shared, experts) == (131_072, 17_301_504, 8 * 8_650_752)
    expert_layer = attention + norms + router + shared + experts
    dense_layer = attention + norms + 3 * h * CFG["intermediate_size"]
    assert (expert_layer, dense_layer) == (100_405_760, 81_007_104)
    total = dense_layer + 5 * expert_layer + 2 * CFG["vocab_size"] * h + h
    assert total == 635_466_752 and 2 * CFG["vocab_size"] * h == 52_428_800
    # the floor, should five expert layers not fit: the dense one and four
    assert total - expert_layer == 535_060_992
    module = family.train_model(CFG, MIX)["module"]
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes["params"])) == 635_466_752
    moe = shapes["params"]["layer_1"]["moe"]
    assert moe["shared_gate"]["kernel"].shape == (2048, 2816) and moe["w_gate"].shape == (8, 2048, 1408)
    latent = shapes["params"]["layer_0"]["kv_latent"]
    assert latent["down"]["kernel"].shape == (2048, 576) and latent["up"]["kernel"].shape == (512, 16, 256)
    assert shapes["params"]["layer_0"]["attention"]["query"]["kernel"].shape == (2048, 16, 192)
    assert "moe" not in shapes["params"]["layer_0"] and "mlp_gate" in shapes["params"]["layer_0"]


def test_the_familys_flop_a_token_equals_a_hand_count():
    # six attentions, the dense SwiGLU, five expert layers (router, shared, 6 x 8 / 64 of an expert), the head
    moe = 131_072 + 17_301_504 + 6 * 8 * 8_650_752 // 64
    weights = 6 * 13_762_560 + 67_239_936 + 5 * moe + 2048 * 12_800
    assert flops_mla.matmul_params_per_token(CFG) == weights == 295_632_896
    # attention: (640 + 1,664) FLOP a visible pair a head, 16 heads, six layers, a token of 8,192
    attention = 2304 * 16 * 33_558_528 * 6 // 8192
    assert attention == 906_080_256
    assert family.train_flop_per_token(CFG, MIX) == 6 * weights + attention == 2_679_877_632
    # an uncut layer computes all 6 of a token's experts
    whole = dict(CFG, num_experts=64, experts_held=[0, 64])
    assert flops_mla.matmul_params_per_token(whole) - weights == 5 * (6 * 8_650_752 - 6 * 8 * 8_650_752 // 64)


@pytest.mark.parametrize("rows_here,weights", [
    (30_720, 295_632_896),                        # the balanced load: 5 x 8,192 x 6 x 8 / 64
    (38_912, 295_632_896 + 8_650_752),            # 8,192 rows over it: one expert a token more
    (15_360, 295_632_896 - 5 * 3 * 8_650_752 // 8),   # half of it
])
def test_the_routed_experts_count_at_the_rows_the_run_reports(rows_here, weights):
    got = family.train_flop_per_token(CFG, MIX, {"moe_rows_here": rows_here, "moe_balance_loss": 0.005})
    assert got == 6 * weights + 906_080_256
    assert family.train_flop_per_token(CFG, MIX, {}) == family.train_flop_per_token(CFG, MIX)


# ------------------------------------------------- the configuration and the manifest

def test_the_configuration_keeps_the_catalogs_keys_but_the_three_it_cuts():
    cut = {"num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 12800}
    assert sorted(CFG["reduced_why"]) == sorted(cut)
    assert {k: CFG[k] for k in CATALOG} == {**CATALOG, **cut}
    assert CFG["published"] == {k: CATALOG[k] for k in cut}
    assert CFG["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"
    assert CFG["family"] == "deepseek_v2"
    assert CFG["router_width"] == 64 and CFG["experts_held"] == [0, CFG["n_routed_experts"]]
    assert CFG["vocab_size"] * 8 == CATALOG["vocab_size"] and CFG["n_routed_experts"] * 8 == CATALOG["n_routed_experts"]
    # the floors: the leading dense layer and four more at least, eight experts, an eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4 and CFG["n_routed_experts"] >= 8
    # what the accepted readers read, under their names
    assert (CFG["num_dense_layers"], CFG["num_experts"], CFG["num_shared_experts"]) == (1, 8, 2)
    assert "Eight chips share each layer" in CFG["deployment"]
    assert {"origin", "aux_loss_alpha", "rotation", "yarn", "shared_experts", "train_attention",
            "train_dtypes", "weights"} <= set(CFG["assumed"])
    assert CFG["aux_loss_alpha"] == 0.001


def test_the_mix_is_the_issues():
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
    # the loss's limit lies between the runs' worst reading and a missing layer's least, three times of room on
    # both sides (so under the accepted LM cells'); the update's between the runs' worst reading and 1
    assert 3 * LOSS_GAP_WORST <= MIX["loss_tolerance"] <= LOSS_GAP_MISSING_LAYER_LEAST / 3 < other["loss_tolerance"]
    assert 1.5 * UPDATE_GAP_WORST < MIX["update_tolerance"] <= 0.5 < 1.0
    assert "my chip run" in MIX["loss_tolerance_why"] and "float8" in MIX["update_tolerance_why"]


def test_the_manifest_lists_the_configuration_the_cell_and_the_four_metrics():
    """`BENCHMARK.json` as ISSUE 34 asks: the entries whole and within the manifest's
    limits, each reader and each list they name in the tree."""
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "deepseek-v2-lite")
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmarks/configs/deepseek-v2-lite.json" and (ROOT / entry["file"]).exists()
    assert sorted(entry["reduced"]) == sorted(CFG["reduced_why"])
    assert cell == {"name": CELL, "config": entry["name"], "traffic": "lm-packed-8k-mla", "chips": 1, "why": cell["why"]}
    assert (ROOT / "benchmarks/traffic" / f"{cell['traffic']}.json").exists()
    # the `why` says what the kernels see and what the experts see
    assert all(word in cell["why"] for word in ("8,192", "192|128", "768 tokens an expert", "attention shows more"))
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    lists = {m["name"]: m["workloads"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"] if "workloads" in m}
    joined = {"train_tokens_per_s", "step_ms.train", "dispatch_ms.train", "mfu.train", "device_idle_share.train",
              "hbm_peak_share.train", "attn_core_fwd_ms.train", "attn_core_bwd_ms.train", "block_dense_ms.train",
              "embed_head_ms.train", "optimizer_ms.train", "unattributed_share.train", "enqueue_ms.train",
              "place_batch_ms.train", "moe_ms.train", "moe_route_ms.train", "expert_mm_roofline_share.train"}
    assert {n for n, cells in lists.items() if CELL in cells} == joined | set(train_mla.METRICS)
    # not the shares whose readers count 4 x head_dim a pair by `layer_types`, nor GPT-2's
    assert not any(CELL in lists[n] for n in ("attn_fwd_visible_mxu_share.train", "attn_bwd_visible_mxu_share.train",
                                              "flash_fwd_mxu_share.train"))
    assert "setup_s" not in lists
    added = [m for m in MANIFEST["per_layer"] if m["name"] in train_mla.METRICS]
    assert [m["name"] for m in added] == list(train_mla.METRICS)
    layers = {m["layer"] for m in MANIFEST["per_layer"] if m["name"] not in train_mla.METRICS}
    for m in added:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s" and m["layer"] in layers
    assert len(MANIFEST["workloads"]) == 5 <= 24 and len(MANIFEST["per_layer"]) <= 128
    assert not any(w["chips"] != 1 for w in MANIFEST["workloads"])


def test_the_program_config_is_the_files():
    cfg = family._program_config(CFG, MIX)
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k, cfg.expert_dim) == (64, (0, 8), 6, 1408)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.vocab_size, cfg.mlp_dim) == (6, 1, 12800, 10944)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank) == (128, 64, 128, 512)
    assert (cfg.num_shared_experts, cfg.route_scale, cfg.renormalise, cfg.balance_loss) == (2, 1.0, False, 0.001)
    assert (cfg.attention, cfg.remat, cfg.norm_eps, cfg.rope_theta) == ("flash", True, 1e-6, 10000.0)
    assert cfg.softmax_scale == pytest.approx(0.1147214, rel=1e-6)
    assert reference_deepseek_v2.softmax_scale(family.reference_spec(CFG)) == pytest.approx(cfg.softmax_scale)
    with pytest.raises(ValueError, match="causal_lm"):
        family.train_model(CFG, dict(MIX, task="classification"))
    with pytest.raises(ValueError, match="no warm-up"):
        family.reference_update_fn(CFG, dict(MIX, warmup_steps=10))


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
                                       ("topk_method", "group_limited_greedy"), ("seq_aux", False)])
def test_the_family_refuses_a_form_the_program_does_not_build(key, value):
    """The full-size model's query compression and grouped choice, a sigmoid router, a
    batch-wise loss: stated in a file, they are refused, not ignored."""
    with pytest.raises(ValueError, match=key):
        family._program_config(dict(CFG, **{key: value}), MIX)


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    source = (ROOT / "benchmarks/reference_deepseek_v2.py").read_text()
    assert "import kubeflow_tpu" not in source and "from kubeflow_tpu" not in source
    assert reference_deepseek_v2.HIGHEST == jax.lax.Precision.HIGHEST and source.count("precision=HIGHEST") >= 3
    assert "def yarn_inv_freq" in source  # YaRN from the formulas, not from the program's function


# ------------------------------------------------------ the reference's own arithmetic

def test_the_references_first_step_is_adam_written_out():
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 300, size=(2, 32)), jnp.int32)
    module = family.train_model(TINY, TINY_MIX)["module"]
    params = family.reference_params(module.init(jax.random.PRNGKey(1), ids)["params"])
    spec = family.reference_spec(TINY)
    total, count, after = reference_deepseek_v2.first_update(params, ids, ids, spec, 1e-3)
    assert float(count) == 2 * 31

    def mean_loss(p):
        t, c = reference_deepseek_v2.causal_lm_loss_sums(p, ids, ids, spec)
        return t / c

    grads = jax.grad(mean_loss)(params)
    for p, g, a in zip(jax.tree.leaves(params), jax.tree.leaves(grads), jax.tree.leaves(after)):
        np.testing.assert_allclose(a, p - 1e-3 * g / (jnp.abs(g) + 1e-8), rtol=1e-5, atol=1e-7)
    # the quotient is the cross entropy's mean plus the coefficient times the balance term
    plain, _ = reference_deepseek_v2.causal_lm_loss_sums(params, ids, ids, dict(spec, balance_loss=0.0))
    balance = float(reference_deepseek_v2.hidden_states(params, ids, spec)[1])
    assert float(total - plain) / float(count) == pytest.approx(0.01 * balance, rel=1e-4) and balance > 1.5
    assert reference_deepseek_v2.logits(params, ids, spec).shape == (2, 32, 300)


def test_the_shared_rotary_key_is_one_a_position():
    """`k_pe` is (L, rope), repeated over the heads by hand: every head's key ends in it."""
    spec = family.reference_spec(TINY)
    assert reference_deepseek_v2.rotate(jnp.ones((32, 1, 8)), spec).shape == (32, 1, 8)
    # position 0 is not turned; a slowed pair at position 31 is turned by less than its own frequency would
    turned = reference_deepseek_v2.rotate(jnp.ones((32, 1, 8)), spec)
    np.testing.assert_allclose(turned[0], 1.0, atol=1e-6)
    slow = np.asarray(reference_deepseek_v2.yarn_inv_freq(spec))
    own = 10000.0 ** (-2.0 * np.arange(4) / 8)
    assert slow[0] == pytest.approx(own[0]) and slow[-1] == pytest.approx(own[-1] / 40)


@functools.lru_cache(maxsize=None)
def _first_step_fn(dtype):
    """The family's jitted first step at the tiny size with every product's operands rounded
    to `dtype` (None: float32 as it stands). `_mm` is read when the step is traced, once."""
    update, plain = family.reference_update_fn(TINY, TINY_MIX), reference_deepseek_v2._mm

    def rounded(a, b):
        return plain(a.astype(dtype).astype(jnp.float32), b.astype(dtype).astype(jnp.float32))

    def call(before, ids):
        reference_deepseek_v2._mm = plain if dtype is None else rounded
        try:
            return update(before, ids, ids)
        finally:
            reference_deepseek_v2._mm = plain
    return call


def _first_step_at(dtype, seed):
    """(loss, state before, state after) of the reference's first step under one draw."""
    ids = np.asarray(np.random.default_rng(seed).integers(1, 300, size=(8, 32)), np.int32)
    module = family.train_model(TINY, TINY_MIX)["module"]
    before = family.reference_params(module.init(jax.random.PRNGKey(seed), ids)["params"])
    total, weight, after = _first_step_fn(dtype)(before, ids)
    return float(total) / float(weight), jax.device_get(before), jax.device_get(after)


def test_a_lower_precision_of_the_reference_reads_a_wider_gap():
    """The control the cell's `update_tolerance` is set against, as the chip run makes it
    (`lm-packed-8k-mla.json`): the reference's own first step with every product's operands
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
    the kind's two comparisons (`kinds/train_job_update.py` `checks`, restated: that file is
    the accepted benchmark's) are made at the MIX's own limits, not the tiny mix's. The
    float32 reference in the same place passes both, so it is the precision that fails."""
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
    out = kind.run(TINY, TINY_MIX, 2147485999, 0.2, _NoTrace(), {"log": lines.append, "builds": runtime.Builds()})
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    assert out["facts"]["tokens_per_step"] == 8 * 32
    assert out["facts"]["flop_per_token"] == family.train_flop_per_token(TINY, TINY_MIX, out["facts"]["step_counters"])
    assert {"moe_balance_loss", "moe_rows_here", "moe_rows_walked", "moe_load_max_over_mean"} \
        <= set(out["facts"]["step_counters"])
    # two expert layers near 1 each, times the tiny coefficient
    assert out["facts"]["step_counters"]["moe_balance_loss"] == pytest.approx(0.02, rel=0.2)
    gap_line = next(line for line in lines if line.startswith("update_gap="))
    assert all(f"layers/{group}=" in gap_line for group in ("router", "wdkv", "gc", "wukv", "shared_gate"))
    checks = next(line for line in lines if "checks=" in line)
    assert "'first_update_matches_reference': True" in checks and "'first_loss_matches_reference': True" in checks


# ------------------------------------------------------- the readers, synthetic events

def _ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


MODEL = STEP + "jvp(train.loss)/DeepseekV2LM/"
BACK = STEP + "transpose(jvp(train.loss))/DeepseekV2LM/jvp(train.loss)/DeepseekV2LM/checkpoint/"
KERNEL = "flash_fwd_kvgrid_q512_k1024_d192v128/pallas_call"
NAMES = {
    "%down = fusion()": MODEL + "layer_1/kv_latent/mla.kv_down/down/dot_general",
    "%norm = fusion()": MODEL + "layer_1/kv_latent/mla.kv_norm/norm/mul",
    "%up_bwd = fusion()": BACK + "layer_1/kv_latent/mla.kv_up/up/dot_general",
    "%rope = fusion()": MODEL + "layer_1/attention/mla.rope/concatenate",
    "%flash = custom-call()": MODEL + "layer_1/attention/" + KERNEL,
    "%lse_copy = copy()": BACK + "layer_1/attention/" + KERNEL.replace("pallas_call", "reshape"),
    "%attn_bwd = fusion()": BACK + "layer_1/attention/flash_bwd_xla_q512_k512_live136of256_d192v128/while/body/dot_general",
    "%fold = fusion()": BACK + "layer_1/attention/transpose",
    "%gmm = custom-call()": MODEL + "layer_1/moe/moe.experts/jit(gmm)/pallas_call",
    "%adam = fusion()": STEP + "train.optimizer/mul",
}
#: one step of 300 us: (event, offset, duration)
STEP_OPS = [("%down = fusion()", 0, 6), ("%norm = fusion()", 6, 4), ("%rope = fusion()", 10, 10),
            ("%flash = custom-call()", 20, 50), ("%gmm = custom-call()", 70, 40), ("%lse_copy = copy()", 110, 10),
            ("%attn_bwd = fusion()", 120, 100), ("%fold = fusion()", 220, 30), ("%up_bwd = fusion()", 250, 20),
            ("%adam = fusion()", 270, 30)]
READER_CFG = {"num_hidden_layers": 2, "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
              "v_head_dim": 16, "kv_lora_rank": 8}
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
           "facts": {"step_program": r"^jit__train_step\b", "step_counters": {"moe_balance_loss": 0.0051}},
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9},
           "config": READER_CFG, "traffic": READER_MIX}
    ctx.update(over)
    return ctx


def test_the_latent_ms_is_the_time_under_the_three_scopes_and_the_parts_hold_it():
    ctx = _ctx()
    assert train_mla.mla_latent_ms(ctx) == pytest.approx(0.030)  # down 6 + norm 4 + up's backward 20
    assert train_mla.moe_balance_loss(ctx) == pytest.approx(0.0051)
    times = train_parts.part_times_ms(ctx)  # the latent path is the block's dense work, not the core's
    assert times["block_dense"] == pytest.approx(0.030 + 0.040)
    assert times["attn_core_fwd"] == pytest.approx(0.060) and times["attn_core_bwd"] == pytest.approx(0.140)


@pytest.mark.parametrize("op_name,kind", [
    (MODEL + "layer_0/attention/" + KERNEL, train_mla.FWD),
    (BACK + "rematted_computation/layer_0/attention/" + KERNEL, train_mla.RECOMPUTED),
    (BACK + "layer_0/attention/" + KERNEL.replace("pallas_call", "reshape"), None),  # a copy under the kernel's name
    (BACK + "layer_3/attention/flash_bwd_xla_q512_k512_live136of256_d192v128/while/body/exp", train_mla.BWD),
    (BACK + "layer_3/attention/flash_bwd_xla_q512_k512_live136of256_d192v128", train_mla.BWD),
    (BACK + "layer_3/attention/transpose", None),
    (MODEL + "layer_2/kv_latent/mla.kv_up/up/dot_general", train_mla.LATENT_PATH),
    (MODEL + "layer_2/kv_latent/mla.kv_upper/up/dot_general", None),
    (MODEL + "layer_2/attention/mla.rope/mul", None),
    ("jit(other)/layer_2/kv_latent/mla.kv_up/up/dot_general", None),
])
def test_the_kernels_and_scopes_are_matched_as_whole_segments_of_the_steps_names(op_name, kind):
    assert train_mla.kind_of(op_name) == kind


def test_the_roofline_shares_are_the_least_time_over_the_kernels_own_time():
    read = train_mla.METRICS
    pairs = 32 * 33 // 2
    fwd = flops_mla.attention_fwd_flop(1, 2, 24, 16, pairs)
    bwd = flops_mla.attention_bwd_flop(1, 2, 24, 16, pairs)
    assert fwd == (2 * 24 + 2 * 16) * 2 * pairs and bwd == 2 * (3 * 24 + 2 * 16) * 2 * pairs
    # these peaks make the forward memory-bound: q and k at 24, v and the output at 16, the statistic
    moved = flops_mla.attention_fwd_bytes(1, 2, 24, 16, 32)
    assert moved / 1e9 > fwd / 1e12
    # the kernel's own 50 us, not the part's 60 (the rotation's 10 are not the kernel's)
    assert read["mla_attn_fwd_roofline_share.train"](_ctx()) == pytest.approx(100 * 2 * (moved / 1e9) / 50e-6)
    fast = _ctx(peaks=FAST)
    assert read["mla_attn_fwd_roofline_share.train"](fast) == pytest.approx(100 * 2 * (fwd / 1e9) / 50e-6)
    # the backward: the time under its scope (100 of the part's 140), and no forward in the count
    assert read["mla_attn_bwd_roofline_share.train"](fast) == pytest.approx(100 * 2 * (bwd / 1e9) / 100e-6)


def test_the_backwards_count_adds_a_forward_only_where_the_trace_shows_one():
    """A program that runs the forward kernel again in its backward pass (the parent of
    PR 33 did): the event under `transpose(` is timed and counted with the backward."""
    names = dict(NAMES, **{"%reflash = custom-call()": BACK + "rematted_computation/layer_1/attention/" + KERNEL})
    ops = [op for op in STEP_OPS if op[0] != "%lse_copy = copy()"] + [("%reflash = custom-call()", 110, 10)]
    ctx = _ctx(names=names, ops=ops, peaks=FAST)
    assert train_mla.kernel_times(ctx)["recomputed"] == 1.0
    pairs = 32 * 33 // 2
    flop = 2 * flops_mla.attention_bwd_flop(1, 2, 24, 16, pairs) + flops_mla.attention_fwd_flop(1, 2, 24, 16, pairs)
    assert train_mla.METRICS["mla_attn_bwd_roofline_share.train"](ctx) == pytest.approx(100 * (flop / 1e9) / 110e-6)
    assert train_mla.kernel_times(_ctx())["recomputed"] == 0.0


@pytest.mark.parametrize("over", [
    {"names": {}},                                                         # a trace without names
    {"names": {k: v for k, v in NAMES.items() if "mla." not in v and "/attention/" not in v}},
    {"whole": 0},                                                          # no whole step
])
def test_a_program_without_the_mechanism_reads_nothing_and_nothing_raises(over):
    """The parent commit has no `mla.*` scope and its kind hands on no `moe_balance_loss`:
    every reader returns None there, and the line leaves it out."""
    ctx = _ctx(**over)
    ctx["facts"].pop("step_counters")
    assert all(reader(ctx) is None for reader in train_mla.METRICS.values())
    # the accepted cells' configurations have no latent: their flash kernels are not this file's
    other = _ctx(config={"num_hidden_layers": 5, "num_attention_heads": 32, "head_dim": 128})
    assert train_mla.METRICS["mla_attn_fwd_roofline_share.train"](other) is None
    assert train_mla.METRICS["mla_attn_bwd_roofline_share.train"](other) is None
    assert set(train_mla.METRICS) & set(train_moe.METRICS) == set()
