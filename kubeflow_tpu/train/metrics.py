"""Metrics emission in the sweep-collector contract.

Reference parity: Katib's metrics collector tails stdout and regex-parses
`name=value` lines (pkg/webhook/v1beta1/pod/inject_webhook.go + file
metricscollector — unverified, SURVEY.md §2.4). Trainers here print the same
shape, so the in-tree sweep engine (kubeflow_tpu/sweep) and any log-scraper
can collect objectives without instrumentation.
"""

from __future__ import annotations

import json
import re
import sys
import time

# The collector's parse regex: `<name>=<float>` tokens on a line.
METRIC_RE = re.compile(
    r"([A-Za-z_][A-Za-z0-9_./-]*)=(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)"
)


def emit(step: int | None = None, file=None, **metrics: float | str) -> str:
    """Print one metrics line: `step=3 loss=0.123 accuracy=0.98`. String
    values (the start-up device line's `platform`, `device_kind`) print
    JSON-quoted, which the numeric collector regex skips."""
    parts = []
    if step is not None:
        parts.append(f"step={step}")
    for k, v in metrics.items():
        parts.append(f"{k}={json.dumps(v)}" if isinstance(v, str)
                     else f"{k}={float(v):.6g}")
    line = " ".join(parts)
    print(line, file=file or sys.stdout, flush=True)
    return line


def parse_line(line: str) -> dict[str, float]:
    """Collector side: extract all name=value pairs from one log line."""
    return {m.group(1): float(m.group(2)) for m in METRIC_RE.finditer(line)}


class TfEventsWriter:
    """Scalar tfevents emission for TensorBoard (SURVEY.md §5.1: the
    reference's TensorBoard story — Tensorboard CR + tfevent collectors).
    Uses tensorboard's own writer, no TF dependency."""

    def __init__(self, logdir: str):
        from tensorboard.summary.writer.event_file_writer import EventFileWriter

        self._writer = EventFileWriter(logdir)
        self.logdir = logdir

    def scalars(self, step: int, **metrics: float) -> None:
        from tensorboard.compat.proto.event_pb2 import Event
        from tensorboard.compat.proto.summary_pb2 import Summary

        summary = Summary(
            value=[
                Summary.Value(tag=k, simple_value=float(v))
                for k, v in metrics.items()
            ]
        )
        self._writer.add_event(
            Event(step=step, wall_time=time.time(), summary=summary)
        )

    def flush(self) -> None:
        self._writer.flush()

    def close(self) -> None:
        self._writer.close()


class Timer:
    """Wall-clock throughput meter (images/sec, steps/sec)."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self._t0 = time.perf_counter()
        self._items = 0
        self._steps = 0

    def tick(self, items: int = 0, steps: int = 1) -> None:
        self._items += items
        self._steps += steps

    @property
    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    @property
    def items_per_sec(self) -> float:
        return self._items / max(self.elapsed, 1e-9)

    @property
    def steps_per_sec(self) -> float:
        return self._steps / max(self.elapsed, 1e-9)
