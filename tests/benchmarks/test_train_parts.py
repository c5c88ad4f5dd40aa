"""The readers this PR adds under `benchmarks/`: the classifier of a step's parts, the
metrics of `layer_metrics/train_parts.py` on synthetic events and on a recorded v5e cut
that carries the program's names, the reader of those names out of an `.xplane.pb`,
and the flash kernel's FLOP count. On the CPU, in seconds; nothing here times anything."""

from __future__ import annotations

import itertools
import json
import os
from pathlib import Path

import pytest

from benchmarks import flops_attention, trace, trace_names
from benchmarks.layer_metrics import train_parts
from benchmarks.layer_metrics.train_parts import PARTS, UNATTRIBUTED, part_of

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).with_name("v5e_named_step_cut.json")
STEP = "jit(_train_step)/"


# ------------------------------------------------------------------ the classifier

@pytest.mark.parametrize("op_name,part", [
    # GPT-2, the parent's names: the flash kernels and the dense core
    ("jit(_train_step)/jvp(GPTLM)/layer_8/attention/pallas_call", "attn_core_fwd"),
    ("jit(_train_step)/transpose(jvp(GPTLM))/layer_8/attention/pallas_call", "attn_core_bwd"),
    ("jit(_train_step)/transpose(jvp(GPTLM))/layer_3/attention/while/body/closed_call/bqk,bkd->bqd/dot_general",
     "attn_core_bwd"),
    ("jit(_train_step)/jvp(GPTLM)/layer_0/attention/blhd,bmhd->bhlm/dot_general", "attn_core_fwd"),
    ("jit(_train_step)/jvp(GPTLM)/layer_0/attention/reduce_max", "attn_core_fwd"),
    # the projections around the core are the block's dense work, forward and backward
    ("jit(_train_step)/jvp(GPTLM)/layer_0/attention/query/dot_general", "block_dense"),
    ("jit(_train_step)/transpose(jvp(GPTLM))/layer_23/attention/attn_out/dot_general", "block_dense"),
    ("jit(_train_step)/jvp(GPTLM)/layer_11/attention/key/add", "block_dense"),
    ("jit(_train_step)/transpose(jvp(GPTLM))/layer_11/attention/value/reduce_sum", "block_dense"),
    ("jit(_train_step)/jvp(GPTLM)/layer_5/mlp_up/dot_general", "block_dense"),
    ("jit(_train_step)/transpose(jvp(GPTLM))/layer_5/ln_mlp/mul", "block_dense"),
    ("jit(_train_step)/jvp(GPTLM)/layer_5/tanh", "block_dense"),
    # BERT: the encoder's blocks sit one module deeper
    ("jit(_train_step)/jvp(BertForSequenceClassification)/encoder/layer_6/attention/bhlm,bmhd->blhd/dot_general",
     "attn_core_fwd"),
    ("jit(_train_step)/transpose(jvp(BertForSequenceClassification))/encoder/layer_6/attention/div",
     "attn_core_bwd"),
    ("jit(_train_step)/jvp(BertForSequenceClassification)/encoder/layer_6/attention/key/dot_general",
     "block_dense"),
    # embeddings, the tied head and the loss: differentiated, under no block
    ("jit(_train_step)/jvp(GPTLM)/token_embed/jit(_take)/gather", "embed_head"),
    ("jit(_train_step)/jvp(GPTLM)/token_embed.attend/dot_general", "embed_head"),
    ("jit(_train_step)/transpose(jvp(GPTLM))/token_embed.attend/dot_general", "embed_head"),
    ("jit(_train_step)/jvp(GPTLM)/ln_final/rsqrt", "embed_head"),
    ("jit(_train_step)/jvp()/reduce_sum", "embed_head"),
    ("jit(_train_step)/transpose(jvp())/div", "embed_head"),
    ("jit(_train_step)/jvp(jit(take_along_axis))/gather", "embed_head"),
    ("jit(_train_step)/transpose(jvp(BertForSequenceClassification))/pooler/dot_general", "embed_head"),
    ("jit(_train_step)/jvp(BertForSequenceClassification)/encoder/embeddings/ln_embed/mul", "embed_head"),
    # outside the differentiated function: cast, gradient norm, optimizer
    ("jit(_train_step)/mul", "optimizer"),
    ("jit(_train_step)/jit(_threefry_fold_in)/slice", "optimizer"),
    ("jit(_train_step)/convert_element_type", "optimizer"),
    # the same operations under this PR's scopes: a fresh compile
    ("jit(_train_step)/train.optimizer/mul", "optimizer"),
    ("jit(_train_step)/train.grad_norm/reduce_sum", "optimizer"),
    ("jit(_train_step)/train.cast/convert_element_type", "optimizer"),
    ("jit(_train_step)/jvp(train.loss)/GPTLM/layer_8/attention/pallas_call", "attn_core_fwd"),
    ("jit(_train_step)/transpose(jvp(train.loss))/GPTLM/layer_8/attention/pallas_call", "attn_core_bwd"),
    ("jit(_train_step)/jvp(train.loss)/GPTLM/layer_8/attention/query/dot_general", "block_dense"),
    ("jit(_train_step)/transpose(jvp(train.loss))/GPTLM/token_embed.attend/dot_general", "embed_head"),
    ("jit(_train_step)/jvp(train.loss)/reduce_sum", "embed_head"),
    # nothing to go by
    (None, UNATTRIBUTED), ("", UNATTRIBUTED), ("reduce_sum", UNATTRIBUTED),
    ("state.params['layer_0']['attention']['query']['kernel']", UNATTRIBUTED),
    ("jit(init)/jvp(GPTLM)/layer_0/attention/exp", UNATTRIBUTED),
])
def test_part_of_an_operation_name(op_name, part):
    assert part_of(op_name) == part


# ------------------------------------------------------- the readers, synthetic events

def _ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


NAMES = {
    "%flash_fwd = custom-call()": STEP + "jvp(GPTLM)/layer_0/attention/pallas_call",
    "%flash_bwd = custom-call()": STEP + "transpose(jvp(GPTLM))/layer_0/attention/pallas_call",
    "%bwd_body = fusion()": STEP + "transpose(jvp(GPTLM))/layer_0/attention/while/body/closed_call/exp",
    "%qkv = fusion()": STEP + "jvp(GPTLM)/layer_0/attention/query/dot_general",
    "%mlp_bwd = fusion()": STEP + "transpose(jvp(GPTLM))/layer_0/mlp_up/dot_general",
    "%head = fusion()": STEP + "jvp(GPTLM)/token_embed.attend/dot_general",
    "%head_bwd = fusion()": STEP + "transpose(jvp(GPTLM))/token_embed.attend/dot_general",
    "%adam = fusion()": STEP + "mul",
    # "%while.1 = while()" and "%copy-done.7 = copy-done()" carry no name
}
#: one step, 400 us of which 380 busy: (event, offset into the step, duration)
STEP_OPS = [("%qkv = fusion()", 0, 50), ("%flash_fwd = custom-call()", 50, 80), ("%head = fusion()", 130, 30),
            ("%head_bwd = fusion()", 160, 20),
            # the flash backward's loop: a `while` of 100 us with no name, 2 x 40 us of body inside it
            ("%while.1 = while()", 180, 100), ("%bwd_body = fusion()", 185, 40), ("%bwd_body = fusion()", 230, 40),
            ("%flash_bwd = custom-call()", 280, 20), ("%mlp_bwd = fusion()", 300, 50),
            ("%copy-done.7 = copy-done()", 350, 10), ("%adam = fusion()", 380, 20)]  # 360-380 idle
EXPECT_US = {"attn_core_fwd": 80, "attn_core_bwd": 100, "block_dense": 100, "embed_head": 50,
             "optimizer": 20, UNATTRIBUTED: 30}
PERIOD_US = 410


def _ctx(head_us, tail_us, whole=4, names=NAMES, **over):
    """`whole` steps of 400 us, one every 410, between a first step of which the profiler
    saw the last `head_us` and a last one of which it saw the first `tail_us`."""
    def step(origin, lo, hi):
        out = []
        for name, off, dur in STEP_OPS:
            a, b = max(off, lo), min(off + dur, hi)
            if b > a:
                out.append(_ev(name, origin + a, b - a))
        return out

    first = head_us - 400  # where the clipped first step began, on a clock that starts at 0
    ops = step(first, 400 - head_us, 400)
    modules = [_ev("jit__train_step(9)", 0, head_us)]
    for i in range(1, whole + 1):
        ops += step(first + PERIOD_US * i, 0, 400)
        modules.append(_ev("jit__train_step(9)", first + PERIOD_US * i, 400))
    ops += step(first + PERIOD_US * (whole + 1), 0, tail_us)
    modules.append(_ev("jit__train_step(9)", first + PERIOD_US * (whole + 1), tail_us))
    modules.append(_ev("jit_other(3)", 5, 2))
    host = [_ev("train.enqueue", 10 + 400 * i, 90 + i) for i in range(5)]
    host += [_ev("train.place_batch", 12 + 400 * i, 7) for i in range(5)]
    host += [_ev("PjitFunction(_train_step)", 30 + 400 * i, 60) for i in range(5)]
    ctx = {"events": {"devices": {0: {"ops": ops, "modules": modules}}, "host": host},
           "op_names": names, "peaks": {"flops_per_s_bf16": 1e12},
           "facts": {"step_program": r"^jit__train_step\b"},
           "config": {"n_layer": 1, "n_head": 2, "n_embd": 64},
           "traffic": {"attention": "flash", "batch": 2, "seq_len": 128}}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("head_us,tail_us", [(400, 400), (150, 60), (1, 399)])
def test_parts_count_whole_steps_only_when_the_profiler_clips_the_edges(head_us, tail_us):
    """However the edge steps are clipped, a whole step reads the same: forward and
    backward of the attention core apart, the projections with the block, the tied
    head's two fusions with the embeddings, the nameless `while` and copy unattributed
    (the body inside the `while` keeps its own part), and it all adds up to the busy time."""
    ctx = _ctx(head_us, tail_us)
    times = train_parts.part_times_ms(ctx)
    assert times == pytest.approx({k: v / 1e3 for k, v in EXPECT_US.items()})
    for part in PARTS:
        assert train_parts.METRICS[f"{part}_ms.train"](ctx) == pytest.approx(EXPECT_US[part] / 1e3)
    assert train_parts.unattributed_share(ctx) == pytest.approx(100 * 30 / 380)
    # parts + unattributed = busy: the union of the operations over the same whole steps
    dev = ctx["events"]["devices"][0]
    starts = sorted(e["start_ns"] for e in dev["modules"] if e["name"].startswith("jit__train_step"))
    inside = [e for e in dev["ops"] if starts[1] <= e["start_ns"] < starts[-1]]
    busy_ms = sum(b - a for a, b in trace.union_intervals(inside)) / 1e6
    assert sum(times.values()) * 4 == pytest.approx(busy_ms) == pytest.approx(4 * 0.380)


def test_flash_share_is_the_kernels_flop_over_the_forward_cores_time():
    ctx = _ctx(400, 400)
    flop = 1 * flops_attention.flash_fwd_flop(2, 2, 128, 32)  # one layer, one call a step
    assert train_parts.flash_fwd_mxu_share(ctx) == pytest.approx(100 * flop / 80e-6 / 1e12)
    dense = _ctx(400, 400, traffic={"attention": "dense", "batch": 2, "seq_len": 128})
    assert train_parts.flash_fwd_mxu_share(dense) is None
    assert train_parts.METRICS["attn_core_fwd_ms.train"](dense) == pytest.approx(0.080)


def test_host_span_readers_take_the_median_of_the_programs_spans():
    ctx = _ctx(400, 400)
    assert train_parts.METRICS["enqueue_ms.train"](ctx) == pytest.approx(0.092)
    assert train_parts.METRICS["place_batch_ms.train"](ctx) == pytest.approx(0.007)
    ctx["events"]["host"] = [e for e in ctx["events"]["host"] if e["name"].startswith("Pjit")]
    # the parent's program has no such span: the metric is left out, nothing raises
    assert train_parts.METRICS["enqueue_ms.train"](ctx) is None
    assert train_parts.METRICS["place_batch_ms.train"](ctx) is None


@pytest.mark.parametrize("names", [{}, {"%qkv = fusion()": "jit(other)/mul"}])
def test_a_trace_without_names_reads_as_uncovered_not_as_zero(names):
    ctx = _ctx(400, 400, names=names)
    assert train_parts.unattributed_share(ctx) == pytest.approx(100.0)
    for part in PARTS:
        assert train_parts.METRICS[f"{part}_ms.train"](ctx) is None
    assert train_parts.flash_fwd_mxu_share(ctx) is None


def test_fewer_than_one_whole_step_reads_nothing():
    ctx = _ctx(400, 400, whole=0)  # two events: both may be clipped
    assert train_parts.part_times_ms(ctx) is None
    assert train_parts.unattributed_share(ctx) == 100.0
    assert all(train_parts.METRICS[f"{p}_ms.train"](ctx) is None for p in PARTS)


def test_overlapping_operations_are_charged_once_each_instant():
    ops = [_ev("a", 0, 100), _ev("b", 20, 30), _ev("c", 90, 30), _ev("d", 200, 10)]
    got = train_parts.exclusive_ns(ops, 10e3, 205e3, lambda name: name)
    # a: 10-20, 50-90; b: 20-50; c (started last) takes 90-120 from a; d is cut at 205
    assert got == pytest.approx({"a": 50e3, "b": 30e3, "c": 30e3, "d": 5e3})
    assert sum(got.values()) == pytest.approx(
        sum(min(b, 205e3) - max(a, 10e3) for a, b in trace.union_intervals(ops)))


def test_every_new_metric_has_its_reader_and_its_cells():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in manifest["per_layer"]}
    both = ["gpt2m-train-1k", "bertb-train-128"]
    for name in train_parts.METRICS:
        assert entries[name]["moves"] == "train_tokens_per_s"
        assert entries[name]["workloads"] == (["gpt2m-train-1k"] if "flash" in name else both)
    assert [m["name"] for m in manifest["per_layer"]][-len(train_parts.METRICS):] == list(train_parts.METRICS)


# ---------------------------------------------------------------- the hand count

def test_flash_flop_equals_a_hand_count():
    # gpt2-medium at 8 x 1024: 16 heads of 64. Scores: 1024 x 1024 x 64 multiply-adds = 2 x 67,108,864
    # FLOP a head; the context product as much again: 268,435,456 a head, x 16 heads x 8 rows
    assert flops_attention.flash_fwd_flop(8, 16, 1024, 64) == 268_435_456 * 16 * 8 == 34_359_738_368
    assert flops_attention.flash_fwd_flop(1, 1, 2, 3) == 4 * 2 * 2 * 3


# ------------------------------------------------- names out of an .xplane.pb's bytes

def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names: dict, events: dict, lines=b"") -> bytes:
    """An `XPlane`: `stat_names` id -> name, `events` id -> (name, [(stat id, field, value)])."""
    body = _field(1, 7) + _field(2, name) + lines
    for key, (ev_name, stats) in events.items():
        md = _field(1, key) + _field(2, ev_name) + _field(4, "display")
        md += b"".join(_field(5, _field(1, sid) + _field(f, v)) for sid, f, v in stats)
        body += _field(4, _field(1, key) + _field(2, md))
    for key, stat_name in stat_names.items():
        body += _field(5, _field(1, key) + _field(2, _field(1, key) + _field(2, stat_name)))
    return body


def test_op_names_are_read_from_the_event_metadata_of_the_device_planes(tmp_path):
    stat_names = {3: "hlo_category", 9: "tf_op", 12: "jit(_train_step)/mul", 14: "flops"}
    device = _plane("/device:TPU:0", stat_names, {
        -5: ("%fusion.1 = f32[8]{0} fusion()", [(3, 5, "fusion"), (14, 3, 77), (9, 5, "jit(_train_step)/jvp(GPTLM)/layer_0/mlp_up/dot_general")]),
        2: ("%fusion.2 = f32[8]{0} fusion()", [(9, 7, 12)]),  # the value is a reference to a stat's name
        3: ("%copy-done.3 = f32[8]{0} copy-done()", [(3, 5, "copy-done")]),  # no op_name
        4: ("%fusion.4 = f32[8]{0} fusion()", [(9, 6, b"jit(_train_step)/jvp()/exp")]),
    }, lines=_field(3, _field(2, "XLA Ops") + _field(4, _field(1, 2) + _field(2, 1000) + _field(3, 500))))
    host = _plane("/host:CPU", {9: "tf_op"}, {1: ("PjitFunction(_train_step)", [(9, 5, "not a device")])})
    other = _plane("/device:TPU:1", {1: "tf_op"}, {1: ("%fusion.9 = f32[8]{0} fusion()", [(1, 5, "jit(_train_step)/add")])})
    path = tmp_path / "a" / "x.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(_field(1, device) + _field(1, host) + _field(1, other) + _field(4, "host-name"))
    assert trace_names.op_names(str(path)) == {
        "%fusion.1 = f32[8]{0} fusion()": "jit(_train_step)/jvp(GPTLM)/layer_0/mlp_up/dot_general",
        "%fusion.2 = f32[8]{0} fusion()": "jit(_train_step)/mul",
        "%fusion.4 = f32[8]{0} fusion()": "jit(_train_step)/jvp()/exp",
        "%fusion.9 = f32[8]{0} fusion()": "jit(_train_step)/add",
    }
    # the newest trace under a directory, and a test's names before any file
    older = tmp_path / "b" / "y.xplane.pb"
    older.parent.mkdir()
    older.write_bytes(b"")
    os.utime(older, (1, 1))
    assert trace_names.newest_trace(tmp_path) == path
    assert trace_names.newest_trace(tmp_path / "b") == older and trace_names.op_names(str(older)) == {}
    assert trace_names.newest_trace(tmp_path / "a" / "none") is None
    assert trace_names.of_run({"op_names": {"x": "y"}}) == {"x": "y"}
    with pytest.raises(ValueError, match="not an .xplane.pb"):
        list(trace_names.fields(memoryview(b"\x0b\x00")))  # wire type 3: a group


def test_a_profile_written_by_jax_here_parses_and_has_no_device_plane(tmp_path):
    """The reader against the real writer: a CPU session's file has host planes only, so
    there are no names, and nothing raises."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((8, 8))))
    finally:
        jax.profiler.stop_trace()
    path = trace_names.newest_trace(tmp_path)
    assert path is not None and trace_names.op_names(str(path)) == {}
    planes = [bytes(dict(trace_names.fields(p))[2]).decode()
              for n, p in trace_names.fields(memoryview(path.read_bytes())) if n == 1]
    assert "/host:CPU" in planes


# ------------------------------------------------------------- the recorded v5e cut

@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded v5e cut with names was brought back from the chip")
def test_parts_of_a_recorded_v5e_step_with_the_programs_names():
    cut = json.loads(RECORDED.read_text())
    table = cut["names"]
    starts = itertools.accumulate(since for _, since, _ in cut["ops"])
    ops = [{"name": table[i][0], "start_ns": float(s), "dur_ns": float(d)}
           for (i, _, d), s in zip(cut["ops"], starts)]
    modules = [{"name": cut["step_event"], "start_ns": float(s), "dur_ns": float(d)} for s, d in cut["modules"]]
    host = [{"name": n, "start_ns": float(s), "dur_ns": float(d)} for n, s, d in cut["host"]]
    ctx = {"events": {"devices": {0: {"ops": ops, "modules": modules}}, "host": host},
           "op_names": {name: op for name, op in table if op},
           "facts": {"step_program": r"^jit__train_step\b"}, "peaks": {"flops_per_s_bf16": 197e12},
           "config": json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text()),
           "traffic": json.loads((ROOT / "benchmarks/traffic/lm-packed-1k.json").read_text())}
    got = {name: reader(ctx) for name, reader in train_parts.METRICS.items()}
    assert got == pytest.approx(cut["expect"], rel=1e-9)
    times = train_parts.part_times_ms(ctx)
    # the cut holds one whole step: its parts and the unattributed time are its busy time
    starts = sorted(m["start_ns"] for m in modules)
    inside = [e for e in ops if e["start_ns"] + e["dur_ns"] > starts[1] and e["start_ns"] < starts[-1]]
    clipped = [{"start_ns": max(e["start_ns"], starts[1]),
                "dur_ns": min(e["start_ns"] + e["dur_ns"], starts[-1]) - max(e["start_ns"], starts[1])}
               for e in inside]
    busy_ms = sum(b - a for a, b in trace.union_intervals(clipped)) / 1e6
    assert sum(times.values()) == pytest.approx(busy_ms, rel=1e-9)
    assert busy_ms <= (starts[-1] - starts[1]) / 1e6
    # and it reads what the whole traced run read on the chip, to the run's own spread
    for name, value in cut["whole_run"].items():
        assert got[name] == pytest.approx(value, rel=0.02), name
    assert 0 < got["flash_fwd_mxu_share.train"] < 100 and got["unattributed_share.train"] < 5
    # this cut is of a fresh compile, so it also carries the scopes this PR adds (those
    # that are still a fusion's root after XLA has fused: the gradient norm's sums are not)
    scopes = {op.split("/")[1] for _, op in table if op.startswith("jit(_train_step)/")}
    assert {"train.optimizer", "jvp(train.loss)", "transpose(jvp(train.loss))"} <= scopes
