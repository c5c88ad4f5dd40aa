"""The benchmark's own arithmetic and wiring, on the CPU, in seconds. Nothing here times
anything, describes a TPU topology or starts a process."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import numpy as np
import pytest

from benchmarks import flops, generator, run, trace

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
RECORDED = Path(__file__).with_name("v5e_trace_cut.json")


def test_manifest_keeps_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [x["name"] for x in metrics + MANIFEST["workloads"] + MANIFEST["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    assert all(0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
               for m in MANIFEST["end_to_end"])
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    assert all(len(w["why"]) <= 200 and w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert {c["name"] for c in MANIFEST["configs"]} == {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(data.get("reduced_why", {})), c["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_name_of_a_cell_resolves_to_a_file(cell):
    loaded = run.load_cell(MANIFEST, cell)
    kind = importlib.import_module(f"benchmarks.kinds.{loaded['traffic_data']['kind']}")
    family = importlib.import_module(f"benchmarks.families.{loaded['config_data']['family']}")
    assert callable(kind.run) and family is not None
    readers = run.layer_readers()
    end_to_end = {m["name"] for m in run.metrics_of(MANIFEST, "end_to_end", cell)}
    per_layer = run.metrics_of(MANIFEST, "per_layer", cell)
    assert "setup_s" in end_to_end and len(end_to_end) >= 2 and per_layer
    for m in per_layer:
        assert m["name"] in readers, f"no reader in layer_metrics/ for {m['name']}"
        assert m["moves"] in end_to_end, f"{m['name']} moves {m['moves']}, not reported in {cell}"


@pytest.mark.parametrize("name", ["lm-packed-1k", "cls-finetune-128"])
def test_training_pools_follow_the_seed(name):
    mix = dict(json.loads((ROOT / f"benchmarks/traffic/{name}.json").read_text()),
               pool_batches=2, batch=4)
    x, y = generator.train_pool(mix, 30522, 11)
    x2, _ = generator.train_pool(mix, 30522, 11)
    x3, _ = generator.train_pool(mix, 30522, 12)
    assert x.shape == (8, mix["seq_len"]) and x.dtype == np.int32
    assert np.array_equal(x, x2) and not np.array_equal(x, x3) and x.max() < 30522
    if mix["task"] == "causal_lm":
        assert x.min() >= 1 and np.array_equal(x, y)
        chained = (x[:, :-1].astype(np.int64) * 31 + 17) % 30521 + 1 == x[:, 1:]
        assert 0.85 < chained.mean() < 0.95  # chain_noise 0.1
    else:
        real = (x != 0).sum(1)
        assert (real >= mix["seq_len"] // 2).all() and set(np.unique(y)) <= {0, 1}
        assert all((row[:n] != 0).all() and (row[n:] == 0).all() for row, n in zip(x, real))


def test_flop_counts_equal_a_hand_count():
    gpt = json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text())
    bert = json.loads((ROOT / "benchmarks/configs/bert-base.json").read_text())
    from benchmarks.families import bert as bert_family
    from benchmarks.families import gpt2 as gpt2_family

    # GPT-2 medium: a block multiplies 4 * 1024^2 + 2 * 1024 * 4096 = 12,582,912 weights, 24
    # blocks 301,989,888, the tied head 50257 * 1024 = 51,463,168: 353,453,056 in all
    assert flops.block_matmul_params(24, 1024, 4096) == 301_989_888
    assert gpt2_family.train_flop_per_token(gpt, {"seq_len": 1024}) \
        == 6 * 353_453_056 + 12 * 24 * 1024 * 1024 == 2_422_708_224
    # BERT-base: 4 * 768^2 + 2 * 768 * 3072 = 7,077,888 a block, 84,934,656 in 12
    assert bert_family.train_flop_per_token(bert, {"seq_len": 128}) \
        == 6 * 84_934_656 + 12 * 12 * 768 * 128 == 523_763_712


def _ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


def test_trace_reduction_on_a_synthetic_trace():
    """Two steps of 400 us with 100 us between them, the second's fusion overlapped by an
    async copy; the host was in `shard_args` for the gap."""
    ops = [_ev("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128] %p0), kind=kLoop", 0, 300),
           _ev('%attention.2 = (bf16[4,8]{1,0}) custom-call(bf16[4,8] %q), custom_call_target="tpu_custom_call"', 300, 100),
           _ev("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128] %p0), kind=kLoop", 500, 300),
           _ev("%copy-start.3 = f32[2]{0} copy-start(f32[2] %x)", 600, 250),
           _ev('%attention.2 = (bf16[4,8]{1,0}) custom-call(bf16[4,8] %q), custom_call_target="tpu_custom_call"', 850, 50)]
    modules = [_ev("jit__train_step(123)", 0, 400), _ev("jit__train_step(123)", 500, 400)]
    host = [_ev("PjitFunction(_train_step)", 380, 90), _ev("shard_args", 395, 100),
            _ev("PjitFunction(_train_step)", 880, 110)]
    events = {"devices": {0: {"ops": ops, "modules": modules}}, "host": host}
    r = trace.reduce(events)
    assert r["busy_s"] == pytest.approx(800e-6) and r["window_s"] == pytest.approx(900e-6)
    assert r["longest_gap_s"] == pytest.approx(100e-6)
    assert r["device_ops"][0] == ["fusion.1 fusion bf16[8,128]", pytest.approx(600e-6)]
    assert r["device_ops"][1][0] == "copy-start.3 copy-start f32[2]"
    assert r["device_ops"][2] == ["attention.2 tpu_custom_call (bf16[4,8],..)", pytest.approx(150e-6)]
    assert r["idle_gaps"] == [["shard_args", pytest.approx(100e-6)]]
    assert trace.reduce({"devices": {0: {"ops": [], "modules": []}}, "host": host}) == {}
    no_host = trace.reduce({"devices": events["devices"], "host": []})
    assert no_host["idle_gaps"][0][0] == trace.NOTHING_TRACED

    from benchmarks.layer_metrics import device, train
    ctx = {"events": events, "reduced": r, "memory_peak_bytes": 4e9,
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes": 16e9},
           "facts": {"step_program": r"^jit__train_step\b", "tokens_per_step": 100,
                     "dispatch_span": r"^PjitFunction\(_train_step\)$", "flop_per_token": 2_000_000}}
    assert train.step_ms(ctx) == pytest.approx(0.4)
    assert train.dispatch_ms(ctx) == pytest.approx(0.1)
    assert train.mfu(ctx) is None  # two events: both may be clipped, no whole step is known
    assert device.idle_share(ctx) == pytest.approx(100 / 9)
    assert device.hbm_peak_share(ctx) == pytest.approx(25.0)


@pytest.mark.parametrize("head_us,tail_us", [(400, 400), (150, 60), (1, 399)])
def test_mfu_counts_whole_steps_only_when_the_profiler_clips_the_edges(head_us, tail_us):
    """Steps of 400 us, one every 410 us. The window opens `head_us` before the first
    step's end and closes `tail_us` into the last step, so the edge events are clipped as
    the profiler clips them. 100 tokens * 2 MFLOP a step every 410 us is 0.4878 TFLOP/s
    whatever the clipping; counting the two edge events as steps would read up to a half more."""
    from benchmarks.layer_metrics import train

    modules = [_ev("jit__train_step(9)", 0, head_us)]
    modules += [_ev("jit__train_step(9)", head_us + 10 + 410 * i, 400) for i in range(4)]
    modules.append(_ev("jit__train_step(9)", head_us + 10 + 410 * 4, tail_us))
    modules.append(_ev("jit_other(3)", 5, 2))
    ctx = {"events": {"devices": {0: {"ops": [], "modules": modules}}, "host": []},
           "peaks": {"flops_per_s_bf16": 1e12},
           "facts": {"step_program": r"^jit__train_step\b", "tokens_per_step": 100, "flop_per_token": 2_000_000}}
    assert train.mfu(ctx) == pytest.approx(100 * 200e6 / 410e-6 / 1e12)
    assert train.step_ms(ctx) == pytest.approx(0.4)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded v5e trace was brought back from the chip")
def test_trace_reduction_on_a_recorded_v5e_trace():
    cut = json.loads(RECORDED.read_text())
    r = trace.reduce(cut["events"])
    assert r["busy_s"] == pytest.approx(cut["expect"]["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(cut["expect"]["window_s"], rel=1e-9)
    assert [n for n, _ in r["device_ops"]] == cut["expect"]["device_ops"]
    assert 0.0 < r["busy_s"] <= r["window_s"]


def test_run_refuses_a_machine_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert exit_.value.code not in (0, None) and "no CPU mode" in str(exit_.value.code)
    assert capsys.readouterr().out == ""  # no result line
    with pytest.raises(SystemExit):
        run.load_cell(MANIFEST, "no-such-cell")


@pytest.mark.parametrize("family_name", ["gpt2", "bert"])
def test_reference_agrees_with_the_program_at_a_tiny_size(family_name):
    """The plain forward and the program's module on the same seeded weights, float32 on
    the CPU: the gap is float32 rounding, so 1e-4 on a loss of order 1 is wide."""
    import jax

    family = importlib.import_module(f"benchmarks.families.{family_name}")
    ids = np.asarray(np.random.default_rng(5).integers(1, 300, size=(3, 24)), np.int32)
    if family_name == "gpt2":
        cfg = {"vocab_size": 300, "n_embd": 32, "n_layer": 2, "n_head": 4, "n_inner": 64,
               "n_positions": 32, "layer_norm_epsilon": 1e-5, "resid_pdrop": 0.0}
        mix = {"task": "causal_lm", "attention": "dense"}
        y = ids
    else:
        cfg = {"vocab_size": 300, "hidden_size": 32, "num_hidden_layers": 2,
               "num_attention_heads": 4, "intermediate_size": 64, "max_position_embeddings": 32,
               "hidden_dropout_prob": 0.0, "pad_token_id": 0, "layer_norm_eps": 1e-12}
        mix = {"task": "classification", "num_classes": 2}
        ids[0, 18:] = 0  # padding, so the mask is exercised
        y = np.asarray([0, 1, 1], np.int32)
    model = family.train_model(cfg, mix)
    variables = model["module"].init(jax.random.PRNGKey(0), ids)
    got = model["loss_fn"](model["module"].apply(variables, ids), y)
    total, rows = family.reference_loss_fn(cfg, mix)(
        family.reference_params(variables["params"]), ids, y)
    assert float(got) == pytest.approx(float(total) / float(rows), abs=1e-4)
