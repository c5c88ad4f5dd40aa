"""Per-layer metrics of the parts of a training step, read from the device trace by the
names the program gave its operations (`trace_names.py`), and of the host's own spans
inside `Trainer.train_step`.

A step's device time falls into five parts, by what JAX and Flax write into an
operation's `op_name` today (the patterns below, in one place): the attention core,
forward and backward (under a block's `attention` module but under none of its
projections); the rest of a transformer block; embeddings, head and loss (inside the
differentiated function, under no block); and what the step does outside the
differentiated function (input cast, gradient norm, optimizer). A fusion carries one
name, its root's, and all its time goes to that part. An operation with no name, or
none the patterns know, is unattributed, and `unattributed_share.train` says how much
of the busy time that is.

Times are summed over the whole steps of the traced window (second step event's start to
the last one's start, as `mfu.train` counts them) and divided by their number. Where
operations overlap on the `XLA Ops` line, each instant goes to the one that started
last, so the parts and the unattributed time add up to the busy time exactly."""

from __future__ import annotations

import re

import numpy as np

from benchmarks import flops_attention, trace, trace_names
from benchmarks.layer_metrics.train import _step_events

# ---- the patterns of each family of names, all of them here ------------------------
#: a transformer block of `models/gpt.py` and `models/bert.py`
BLOCK = re.compile(r"/layer_\d+/")
#: under a block's attention module and under none of its projection submodules
ATTENTION_CORE = re.compile(r"/layer_\d+/attention/(?!(?:query|key|value|attn_out)/)")
#: inside the function `jax.value_and_grad` differentiates
DIFFERENTIATED = re.compile(r"(?:^|/)(?:jvp|transpose)\(")
#: its backward pass
BACKWARD = re.compile(r"(?:^|/)transpose\(")
#: the step program itself: class (iv) is what it does outside the differentiated function
STEP_PROGRAM = re.compile(r"^jit\(_train_step\)/")

PARTS = ("attn_core_fwd", "attn_core_bwd", "block_dense", "embed_head", "optimizer")
UNATTRIBUTED = "unattributed"


def part_of(op_name: str | None) -> str:
    """The part an operation's `op_name` puts it in."""
    if not op_name or not STEP_PROGRAM.search(op_name):
        return UNATTRIBUTED
    if not DIFFERENTIATED.search(op_name):
        return "optimizer"
    if ATTENTION_CORE.search(op_name):
        return "attn_core_bwd" if BACKWARD.search(op_name) else "attn_core_fwd"
    return "block_dense" if BLOCK.search(op_name) else "embed_head"


def _whole_steps(ctx) -> tuple[float, float, int] | None:
    starts = sorted(e["start_ns"] for e in _step_events(ctx))
    return (starts[1], starts[-1], len(starts) - 2) if len(starts) >= 3 else None


def exclusive_ns(ops, t0: float, t1: float, key_of) -> dict:
    """Nanoseconds of [t0, t1) under each `key_of(event name)`. Where operations overlap
    (a `while` and its body, a copy beside a fusion) each instant goes to the one that
    started last, so the values add up to the union of the intervals: the busy time."""
    total: dict = {}
    keys: dict = {}
    open_ops: list[tuple[float, object]] = []  # (end, key) of the operations covering `at`
    at = t0

    def charge(at: float, to: float) -> float:
        while open_ops and at < to:
            end, key = open_ops[-1]
            if end <= at:
                open_ops.pop()
                continue
            total[key] = total.get(key, 0.0) + min(end, to) - at
            at = min(end, to)
        return max(at, to)

    for ev in sorted(ops, key=lambda e: e["start_ns"]):
        start, end = max(ev["start_ns"], t0), min(ev["start_ns"] + ev["dur_ns"], t1)
        if end <= start:
            continue
        name = ev["name"]
        if name not in keys:
            keys[name] = key_of(name)
        at = charge(at, start)
        open_ops.append((end, keys[name]))
    charge(at, t1)
    return total


def part_times_ms(ctx) -> dict[str, float] | None:
    """Device milliseconds a whole step spends in each part, `UNATTRIBUTED` among them;
    None where the trace holds no whole step."""
    if "_part_times_ms" not in ctx:
        whole = _whole_steps(ctx)
        out = None
        if whole:
            t0, t1, steps = whole
            names = trace_names.of_run(ctx)
            dev = ctx["events"]["devices"][min(ctx["events"]["devices"])]
            total = exclusive_ns(dev["ops"], t0, t1, lambda name: part_of(names.get(name)))
            out = {part: total.get(part, 0.0) / steps / 1e6 for part in PARTS + (UNATTRIBUTED,)}
        ctx["_part_times_ms"] = out
    return ctx["_part_times_ms"]


def _part_ms(part: str):
    def reader(ctx):
        times = part_times_ms(ctx)
        return times[part] if times and times[part] > 0 else None
    return reader


def unattributed_share(ctx):
    """Share of the busy time in operations that resolve to no part; 100 when nothing
    resolves, so a trace without names never reads as covered."""
    times = part_times_ms(ctx)
    busy = sum(times.values()) if times else 0.0
    return 100.0 * times[UNATTRIBUTED] / busy if busy else 100.0


def flash_fwd_mxu_share(ctx):
    """FLOP of the forward flash kernel's calls in a step (`flops_attention.py`: one call
    a layer, the causal half not taken off, as `mfu.train` counts) over the forward
    attention core's time, over the chip's bf16 peak. Only where the mix runs the flash
    kernel."""
    if ctx["traffic"].get("attention") != "flash":
        return None
    times = part_times_ms(ctx)
    if not times or not times["attn_core_fwd"]:
        return None
    cfg, mix = ctx["config"], ctx["traffic"]
    flop = cfg["n_layer"] * flops_attention.flash_fwd_flop(
        mix["batch"], cfg["n_head"], mix["seq_len"], cfg["n_embd"] // cfg["n_head"])
    return 100.0 * flop / (times["attn_core_fwd"] / 1e3) / ctx["peaks"]["flops_per_s_bf16"]


def _host_span_ms(name: str):
    rx = f"^{re.escape(name)}$"

    def reader(ctx):
        spans = trace.durations_ms(ctx["events"]["host"], rx)
        return float(np.median(spans)) if spans else None
    return reader


METRICS = {
    **{f"{part}_ms.train": _part_ms(part) for part in PARTS},
    "unattributed_share.train": unattributed_share,
    "flash_fwd_mxu_share.train": flash_fwd_mxu_share,
    "enqueue_ms.train": _host_span_ms("train.enqueue"),
    "place_batch_ms.train": _host_span_ms("train.place_batch"),
}
