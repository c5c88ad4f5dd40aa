"""Per-layer metrics of the `train.trainer` layer and of its step's share of the chip's
peak, read from the device trace of a `train_job` run."""

from __future__ import annotations

import re

import numpy as np

from benchmarks import trace


def _step_events(ctx):
    dev = ctx["events"]["devices"][min(ctx["events"]["devices"])]
    return [e for e in dev["modules"] if re.search(ctx["facts"]["step_program"], e["name"])]


def step_ms(ctx):
    """Median duration of the step program's events on the `XLA Modules` line."""
    steps = _step_events(ctx)
    return float(np.median([e["dur_ns"] / 1e6 for e in steps])) if steps else None


def dispatch_ms(ctx):
    """Median duration of the host's `PjitFunction(<step>)` spans: what it costs the
    host to enqueue one step."""
    spans = trace.durations_ms(ctx["events"]["host"], ctx["facts"]["dispatch_span"])
    return float(np.median(spans)) if spans else None


def mfu(ctx):
    """FLOP of the whole steps of the traced window over the time they took, over the
    chip's bf16 peak. The profiler clips the step it starts in and the step it stops in,
    so the first event's start is not a step's start and the last event is not a whole
    step: the time runs from the second event's start to the last event's start, which
    is every step but those two, whole, with the gaps between them."""
    starts = sorted(e["start_ns"] for e in _step_events(ctx))
    if len(starts) < 3:
        return None
    tokens_per_s = (len(starts) - 2) * ctx["facts"]["tokens_per_step"] / ((starts[-1] - starts[1]) / 1e9)
    return 100.0 * tokens_per_s * ctx["facts"]["flop_per_token"] / ctx["peaks"]["flops_per_s_bf16"]


METRICS = {"step_ms.train": step_ms, "dispatch_ms.train": dispatch_ms, "mfu.train": mfu}
