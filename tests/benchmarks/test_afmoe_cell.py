"""What PR 28 adds under `benchmarks/`: the hand counts of `flops_moe.py` and of the AFMoE
family's FLOP a token, the family's reference against the program at a tiny size, the
configuration file against the catalog's keys, and the readers of
`layer_metrics/train_moe.py` on a synthetic trace. On the CPU, in seconds; nothing here
times anything."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks import flops_moe
from benchmarks.families import afmoe as family
from benchmarks.layer_metrics import train_moe, train_parts

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "benchmarks/configs/trinity-mini.json").read_text())
MIX = json.loads((ROOT / "benchmarks/traffic/lm-packed-8k.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "trinitym-train-8k"
STEP = "jit(_train_step)/"


# ------------------------------------------------------------------ the hand counts

@pytest.mark.parametrize("seq_len,window,pairs", [
    (8192, 0, 33_558_528),      # 8192 x 8193 / 2
    (8192, 2048, 14_681_088),   # 2048 x 2049 / 2 = 2,098,176 for the first 2,048 rows, then 6,144 x 2,048
    (2048, 2048, 2_098_176),    # the window never bites
    (4, 2, 7),                  # 1 + 2 + 2 + 2
    (4, 9, 10),
])
def test_visible_pairs_equal_a_hand_count(seq_len, window, pairs):
    assert flops_moe.visible_pairs(seq_len, window) == pairs


def test_attention_and_expert_counts_equal_a_hand_count():
    # one layer, 32 heads of 128 over the full causal triangle at 8,192: 4 x 4096 x 33,558,528
    assert flops_moe.attention_fwd_flop(1, 32, 128, 33_558_528) == 549_822_922_752
    assert flops_moe.attention_bwd_flop(1, 32, 128, 33_558_528) == 1_374_557_306_880
    assert flops_moe.attention_fwd_flop(2, 3, 5, 7) == 4 * 2 * 3 * 5 * 7
    assert flops_moe.swiglu_params(2048, 1024) == 6_291_456
    # 8,192 tokens x 8 choices x 16 of 128 experts: one row a token at the balanced load
    assert flops_moe.grouped_rows(8192, CFG) == 8192
    # three products of 2 x 2048 x 1024 a row: 12,582,912 FLOP a row and pass
    assert flops_moe.expert_products_flop(8192, 2048, 1024, 1) == 103_079_215_104
    assert flops_moe.expert_products_flop(8192, 2048, 1024, 4) == 412_316_860_416
    # elements: gate and up each read 8192 x 2048 rows and 16 x 2048 x 1024 weights and write 8192 x 1024
    # (58,720,256 each); down reads 8192 x 1024 and the weights and writes 8192 x 2048 (58,720,256)
    assert flops_moe.expert_products_bytes(8192, 16, 2048, 1024, 1) == 3 * 58_720_256 * 2
    assert flops_moe.expert_products_bytes(1, 1, 2, 3, 2, itemsize=4) == 2 * (2 * (2 + 6 + 3) + (3 + 6 + 2)) * 4


def test_the_familys_flop_a_token_equals_a_hand_count():
    # attention: 2048 x 128 x (3 x 32 + 2 x 4) = 27,262,976 a layer, 136,314,880 in five
    # dense layer: 3 x 2048 x 6144 = 37,748,736
    # an expert layer: router 262,144 + shared 6,291,456 + one routed expert a token 6,291,456 = 12,845,056; four: 51,380,224
    # head: 25,024 x 2048 = 51,249,152
    assert flops_moe.matmul_params_per_token(CFG) \
        == 136_314_880 + 37_748_736 + 51_380_224 + 51_249_152 == 276_692_992
    assert family.visible_pairs_by_layer(CFG, 8192) == [14_681_088] * 3 + [33_558_528, 14_681_088]
    # 4 x 14,681,088 + 33,558,528 = 92,282,880 pairs a row: 11,265 keys a token
    assert family.train_flop_per_token(CFG, MIX) \
        == 6 * 276_692_992 + 12 * 4096 * 11_265 == 2_213_855_232
    # an uncut layer computes all 8 of a token's experts
    whole = dict(CFG, num_experts=128, experts_held=[0, 128])
    assert flops_moe.matmul_params_per_token(whole) - flops_moe.matmul_params_per_token(CFG) \
        == 4 * 7 * 6_291_456


@pytest.mark.parametrize("rows_here,weights", [
    (32_768, 276_692_992),                     # the balanced load: one held expert a token
    (36_864, 276_692_992 + 6_291_456 // 2),    # an eighth over it, over four layers: half an expert a token
    (16_384, 276_692_992 - 2 * 6_291_456),     # half of it
])
def test_the_routed_experts_count_at_the_rows_the_run_reports(rows_here, weights):
    got = family.train_flop_per_token(CFG, MIX, {"moe_rows_here": rows_here, "moe_bias_abs_max": 0.02})
    assert got == 6 * weights + 12 * 4096 * 11_265
    assert family.train_flop_per_token(CFG, MIX, {}) == family.train_flop_per_token(CFG, MIX)


# ------------------------------------------------- the configuration and the manifest

def test_the_configuration_keeps_the_catalogs_keys_but_the_five_it_cuts():
    published = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
                 "moe_intermediate_size": 1024, "num_attention_heads": 32, "num_key_value_heads": 4,
                 "num_experts_per_tok": 8, "num_shared_experts": 1, "sliding_window": 2048,
                 "route_scale": 2.826, "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
                 "global_attn_every_n_layers": 4, "score_func": "sigmoid", "model_type": "afmoe"}
    assert {k: CFG[k] for k in published} == published
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "trinity-mini")
    assert sorted(entry["reduced"]) == sorted(CFG["reduced_why"]) == sorted(
        ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"])
    assert CFG["num_hidden_layers"] == len(CFG["layer_types"]) == 5 and CFG["num_dense_layers"] == 1
    assert CFG["layer_types"][1:].count("full_attention") == 1  # a whole period after the dense layer
    assert CFG["router_width"] == 128 and CFG["experts_held"] == [0, CFG["num_experts"]] == [0, 16]
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert len(CFG["assumed"]) >= 8 and entry["source"] == CFG["source"]


def test_the_cell_and_its_metrics_are_in_the_manifest():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("trinity-mini", "lm-packed-8k", 1)
    assert all(word in cell["why"] for word in ("8,192", "remat", "1/8")) and len(cell["why"]) <= 200
    assert (MIX["seq_len"], MIX["batch"], MIX["remat"], MIX["attention"]) == (8192, 1, True, "flash")
    # no tile of a kernel in the traffic; the kind that compares the first update, its limit
    # between the seeds' worst reading and the float8 control's (the mix's `update_tolerance_why`)
    assert "attention_block" not in MIX and MIX["kind"] == "train_job_update"
    assert 0.554 < MIX["update_tolerance"] < 0.978 and MIX["loss_tolerance"] == 3e-4
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in train_moe.METRICS:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "train_tokens_per_s"
    assert [m["name"] for m in MANIFEST["per_layer"]][-len(train_moe.METRICS):] == list(train_moe.METRICS)
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    assert reported == set(entries) - {"flash_fwd_mxu_share.train"}  # its reader reads GPT-2's keys


def test_the_program_config_is_the_files(monkeypatch):
    cfg = family._program_config(CFG, attention="flash", remat=True)
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k, cfg.expert_dim) == (128, (0, 16), 8, 1024)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.vocab_size) == (5, 1, 25024)
    assert cfg.layer_types == tuple(CFG["layer_types"]) and cfg.head_dim * cfg.num_heads == 4096
    assert family.train_model(CFG, MIX)["module"].cfg.remat is True
    with pytest.raises(ValueError, match="causal_lm"):
        family.train_model(CFG, dict(MIX, task="classification"))


# --------------------------------------------- the reference against the program, tiny

def test_reference_agrees_with_the_program_at_a_tiny_size():
    """The family's two functions as `kinds/train_job.py` calls them, float32 on the CPU:
    both layer kinds, a dense and three expert layers of which this share holds half."""
    import jax

    cfg = dict(CFG, vocab_size=300, hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=64, num_dense_layers=1,
               layer_types=CFG["layer_types"][:4], sliding_window=8, router_width=8, num_experts=4,
               experts_held=[2, 6], num_experts_per_tok=2, moe_intermediate_size=16)
    mix = {"task": "causal_lm", "attention": "dense", "seq_len": 24}
    ids = np.asarray(np.random.default_rng(5).integers(1, 300, size=(3, 24)), np.int32)
    model = family.train_model(cfg, mix)
    variables = model["module"].init(jax.random.PRNGKey(0), ids)
    got = model["loss_fn"](model["module"].apply(variables, ids), ids)
    total, rows = family.reference_loss_fn(cfg, mix)(
        family.reference_params(variables["params"]), ids, ids)
    assert float(rows) == 3 * 23
    assert float(got) == pytest.approx(float(total) / float(rows), abs=1e-4)


# ------------------------------------------------------- the readers, synthetic events

def _ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


MODEL = STEP + "jvp(train.loss)/AfmoeLM/"
BACK = STEP + "transpose(jvp(train.loss))/AfmoeLM/jvp(train.loss)/AfmoeLM/checkpoint/"
NAMES = {
    "%route = fusion()": MODEL + "layer_1/moe/moe.route/dot_general",
    "%sort = sort()": MODEL + "layer_1/moe/moe.dispatch/jit(argsort)/sort",
    "%gmm = custom-call()": MODEL + "layer_1/moe/moe.experts/jit(gmm)/pallas_call",
    "%tgmm = custom-call()": BACK + "layer_1/moe/moe.experts/jit(tgmm)/pallas_call",
    "%regmm = custom-call()": BACK + "rematted_computation/layer_1/moe/moe.experts/jit(gmm)/pallas_call",
    "%combine = fusion()": BACK + "layer_1/moe/moe.combine/mul",
    "%shared = fusion()": MODEL + "layer_1/moe/moe.shared/shared_up/dot_general",
    "%cast = convert()": MODEL + "layer_1/moe/convert_element_type",
    "%flash = custom-call()": MODEL + "layer_1/attention/flash_fwd_resident_q256_k512/pallas_call",
    "%reflash = custom-call()": BACK + "rematted_computation/layer_1/attention/flash_fwd_resident_q256_k512/pallas_call",
    "%attn_bwd = fusion()": BACK + "layer_1/attention/while/body/closed_call/dot_general",
    "%gate = fusion()": MODEL + "layer_1/attn_gate/dot_general",
    "%dense_mlp = fusion()": MODEL + "layer_0/mlp_up/dot_general",
}
#: one step of 400 us: (event, offset, duration)
STEP_OPS = [("%gate = fusion()", 0, 20), ("%flash = custom-call()", 20, 40), ("%dense_mlp = fusion()", 60, 30),
            ("%route = fusion()", 90, 10), ("%sort = sort()", 100, 15), ("%cast = convert()", 115, 5),
            ("%gmm = custom-call()", 120, 30), ("%shared = fusion()", 150, 20),
            ("%regmm = custom-call()", 170, 30), ("%tgmm = custom-call()", 200, 60), ("%combine = fusion()", 260, 25),
            ("%reflash = custom-call()", 285, 40), ("%attn_bwd = fusion()", 325, 60)]
SCOPES_US = {"moe.route": 10, "moe.dispatch": 15, train_moe.OTHER: 5, "moe.experts": 120,
             "moe.shared": 20, "moe.combine": 25}
TINY = {"num_hidden_layers": 2, "num_dense_layers": 1, "hidden_size": 64, "moe_intermediate_size": 32,
        "num_experts": 2, "router_width": 8, "num_experts_per_tok": 4, "num_attention_heads": 2,
        "head_dim": 16, "sliding_window": 8, "layer_types": ["sliding_attention", "full_attention"]}


def _ctx(names=NAMES, whole=3, **over):
    ops, modules = [], []
    for i in range(whole + 2):
        origin = 410 * i
        ops += [_ev(name, origin + off, dur) for name, off, dur in STEP_OPS]
        modules.append(_ev("jit__train_step(9)", origin, 400))
    ctx = {"events": {"devices": {0: {"ops": ops, "modules": modules}}, "host": []},
           "op_names": names, "facts": {"step_program": r"^jit__train_step\b"},
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e10},
           "config": TINY, "traffic": {"batch": 1, "seq_len": 32, "remat": True}}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("op_name,scope", [
    (MODEL + "layer_3/moe/moe.experts/jit(gmm)/pallas_call", "moe.experts"),
    (BACK + "rematted_computation/layer_3/moe/moe.route/jit(take_along_axis)/gather", "moe.route"),
    (MODEL + "layer_3/moe/moe.combine", "moe.combine"),
    (MODEL + "layer_3/moe/convert_element_type", train_moe.OTHER),
    (MODEL + "layer_3/moe/moe.shared/shared_down/dot_general", "moe.shared"),
    (MODEL + "layer_0/mlp_up/dot_general", None),
    (MODEL + "layer_3/attention/query/dot_general", None),
    ("jit(other)/layer_3/moe/moe.route/mul", None), (None, None), ("", None),
])
def test_scope_of_an_operation_name(op_name, scope):
    assert train_moe.scope_of(op_name) == scope


def test_the_expert_layers_times_by_scope_add_up_to_the_layers_time():
    ctx = _ctx()
    assert train_moe.scope_times_ms(ctx) == pytest.approx({k: v / 1e3 for k, v in SCOPES_US.items()})
    assert train_moe.moe_ms(ctx) == pytest.approx(0.195)
    assert train_moe.moe_route_ms(ctx) == pytest.approx(0.055)  # all but the products
    # the expert layer is block_dense to the five parts, and nothing is counted twice
    times = train_parts.part_times_ms(ctx)
    assert times["block_dense"] == pytest.approx(0.020 + 0.030 + 0.195)
    assert times["attn_core_fwd"] == pytest.approx(0.040) and times["attn_core_bwd"] == pytest.approx(0.100)


def test_the_roofline_share_is_the_least_time_over_the_time_under_the_products():
    ctx = _ctx()
    rows = 32 * 4 * 2 // 8  # tokens x choices x held / width
    flop = flops_moe.expert_products_flop(rows, 64, 32, 4)  # one expert layer, remat: 4 passes
    moved = flops_moe.expert_products_bytes(rows, 2, 64, 32, 4)
    assert moved / 1e10 > flop / 1e12  # these peaks make it memory-bound
    assert train_moe.expert_mm_roofline_share(ctx) == pytest.approx(100 * (moved / 1e10) / 120e-6)
    fast = _ctx(peaks={"flops_per_s_bf16": 1e9, "hbm_bytes_per_s": 1e12})
    assert train_moe.expert_mm_roofline_share(fast) == pytest.approx(100 * (flop / 1e9) / 120e-6)
    # a kind that hands on the step's counters: the rows the run computed, not the balanced load's
    told = _ctx(facts={"step_program": r"^jit__train_step\b", "step_counters": {"moe_rows_here": 3 * rows}})
    assert train_moe.expert_mm_roofline_share(told) == pytest.approx(
        100 * (flops_moe.expert_products_bytes(3 * rows, 2, 64, 32, 4) / 1e10) / 120e-6)
    no_remat = _ctx(traffic={"batch": 1, "seq_len": 32})
    assert train_moe.expert_mm_roofline_share(no_remat) == pytest.approx(
        100 * (flops_moe.expert_products_bytes(rows, 2, 64, 32, 3) / 1e10) / 120e-6)


def test_the_attention_shares_count_visible_pairs_and_the_recomputed_forward():
    ctx = _ctx()
    pairs = flops_moe.visible_pairs(32, 8) + flops_moe.visible_pairs(32)
    fwd = flops_moe.attention_fwd_flop(1, 2, 16, pairs)
    bwd = flops_moe.attention_bwd_flop(1, 2, 16, pairs)
    read = train_moe.METRICS
    assert read["attn_fwd_visible_mxu_share.train"](ctx) == pytest.approx(100 * fwd / 40e-6 / 1e12)
    assert read["attn_bwd_visible_mxu_share.train"](ctx) == pytest.approx(100 * (bwd + fwd) / 100e-6 / 1e12)
    no_remat = _ctx(traffic={"batch": 1, "seq_len": 32})
    assert read["attn_bwd_visible_mxu_share.train"](no_remat) == pytest.approx(100 * bwd / 100e-6 / 1e12)


@pytest.mark.parametrize("over", [
    {"names": {}},                                              # a trace without names
    {"names": {k: v for k, v in NAMES.items() if "/moe/" not in v}},  # a program without the layer
    {"whole": 0},                                               # no whole step
])
def test_a_program_without_the_layer_reads_nothing_and_nothing_raises(over):
    ctx = _ctx(**over)
    for name in ("moe_ms.train", "moe_route_ms.train", "expert_mm_roofline_share.train"):
        assert train_moe.METRICS[name](ctx) is None
    # GPT-2's and BERT's configurations have none of the family's keys
    gpt2 = json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text())
    other = _ctx(config=gpt2, traffic={"batch": 8, "seq_len": 1024, "attention": "flash"})
    assert all(train_moe.METRICS[name](other) is None for name in train_moe.METRICS
               if "share" in name)
