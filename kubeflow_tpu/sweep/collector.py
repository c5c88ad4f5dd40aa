"""Metrics collection from trial logs — the sidecar-collector analogue.

Reference parity (unverified cites, SURVEY.md §2.4): katib's mutating pod
webhook injects a sidecar that tails stdout and regex-parses `metric=value`
pairs into the observation log (pkg/webhook/v1beta1/pod/inject_webhook.go,
cmd/metricscollector/v1beta1/file-metricscollector). Here there is no
sidecar to inject: the pod runtime already captures every pod's stdout to a
log file, and the collector parses it post-hoc (or live, for early
stopping) with the same regex contract.

The trainer's metrics_lib.emit prints exactly this format
(`step=120 loss=0.41 accuracy=0.88 ...`), so in-tree models are collectable
with zero configuration.
"""

from __future__ import annotations

import re

from kubeflow_tpu.sweep.api import Metric, Observation

# katib's file-metricscollector default filter, era-dependent:
# ([\w|-]+)\s*=\s*((-?\d+)(\.\d+)?([Ee][+-]?\d+)?) — extended with [./] in
# names for namespaced metrics like eval/loss.
METRIC_RE = re.compile(
    r"([\w./|-]+)\s*=\s*([+-]?\d+(?:\.\d+)?(?:[Ee][+-]?\d+)?)(?![\w.])"
)


def parse_metrics(text: str, names: set[str] | None = None) -> dict[str, list[float]]:
    """All `name=value` observations in log order, optionally filtered to
    `names`. Returns {metric: [v0, v1, ...]} timelines."""
    out: dict[str, list[float]] = {}
    for line in text.splitlines():
        for m in METRIC_RE.finditer(line):
            name, val = m.group(1), m.group(2)
            if names is not None and name not in names:
                continue
            try:
                out.setdefault(name, []).append(float(val))
            except ValueError:
                continue
    return out


def final_metrics_from_log(text: str) -> dict[str, float]:
    """Latest `final_*` scalars of a worker log (the train() helpers'
    contract)."""
    return {name: vals[-1] for name, vals in parse_metrics(text).items()
            if name.startswith("final_")}


def observation_from_log(
    text: str, objective_metric: str, additional: list[str] | None = None
) -> Observation:
    """Build a trial Observation (latest/min/max per metric) from a log."""
    names = {objective_metric, *(additional or [])}
    timelines = parse_metrics(text, names)
    return _observation(timelines)


def _observation(timelines: dict[str, list[float]]) -> Observation:
    obs = Observation()
    for name in sorted(timelines):
        vals = timelines[name]
        obs.metrics.append(
            Metric(name=name, latest=vals[-1], min=min(vals), max=max(vals))
        )
    return obs


# ---------------------------------------------------------------- tfevents

def parse_tfevents_points(
    logdir: str, names: set[str] | None = None
) -> dict[str, list[tuple[int, float]]]:
    """Step-ordered (step, value) pairs per scalar tag — the point-preserving
    sibling of parse_tfevents (the tbviewer charts need real step x-axes)."""
    import os

    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader,
    )

    points: dict[str, list[tuple[int, float]]] = {}
    if not os.path.isdir(logdir):
        return {}
    files = sorted(
        os.path.join(root, f)
        for root, _, fs in os.walk(logdir)
        for f in fs
        if "tfevents" in f
    )
    for path in files:
        for ev in EventFileLoader(path).Load():
            for val in ev.summary.value:
                if names is not None and val.tag not in names:
                    continue
                if val.HasField("simple_value"):
                    v = float(val.simple_value)
                elif val.HasField("tensor") and val.tensor.float_val:
                    v = float(val.tensor.float_val[0])
                else:
                    continue
                points.setdefault(val.tag, []).append((ev.step, v))
    # stable key-sort: duplicate steps (restarted runs re-logging a step)
    # keep write order, so "latest" stays the newest write, not the largest
    # value; NaNs never enter the comparison
    return {
        t: sorted(p, key=lambda q: q[0]) for t, p in points.items()
    }


def parse_tfevents(logdir: str, names: set[str] | None = None) -> dict[str, list[float]]:
    """Scalar timelines from a tfevents dir (katib's tfevent-metricscollector
    parity, cmd/metricscollector/v1beta1/tfevent-metricscollector). Handles
    both simple_value and tensor-encoded scalars; step-ordered."""
    return {
        tag: [v for _, v in pts]
        for tag, pts in parse_tfevents_points(logdir, names).items()
    }


def observation_from_tfevents(
    logdir: str, objective_metric: str, additional: list[str] | None = None
) -> Observation:
    names = {objective_metric, *(additional or [])}
    return _observation(parse_tfevents(logdir, names))
