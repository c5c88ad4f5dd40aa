"""The reduction from a profiler trace to numbers. It works on plain event lists
(`{"name", "start_ns", "dur_ns"}`), so a test can hand it a synthetic trace; `load` turns
an `.xplane.pb` into those lists with nothing but JAX.

A v5e trace: plane `/device:TPU:<n>` with the lines `XLA Modules` (one event a dispatched
program), `XLA Ops` (one event an operation; their union is the busy time), `Steps` and
`Async XLA Ops`; plane `/host:CPU` with one line a thread (`PjitFunction(<name>)`,
`shard_args`, PJRT's own spans)."""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NOTHING_TRACED = "host: nothing traced"
#: a host span longer than this says nothing about a gap of microseconds inside it, and
#: would make every look-up scan the whole trace
LONGEST_HOST_SPAN_NS = 0.5e9


def load(path, chips: int) -> dict:
    """{"devices": {index: {"ops": [...], "modules": [...]}}, "host": [...]} of the first
    `chips` devices."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            dev = out["devices"].setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend(_events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(_events(line))
    return out


def _events(line):
    return [{"name": e.name, "start_ns": float(e.start_ns), "dur_ns": float(e.duration_ns)}
            for e in line.events]


def short_name(name: str, limit: int = 96) -> str:
    """An `XLA Ops` event is named by its whole HLO line: keep the result's name and
    shape, and the custom call's target where there is one."""
    head = name.split(" = ", 1)
    text = head[0].lstrip("%")
    if len(head) == 2:
        shape = head[1].split("{", 1)[0].split(" ", 1)[0]
        if shape.startswith("("):  # a tuple of results: its first member stands for it
            shape += ",..)"
        op = re.search(r"\)?\s*([a-z][a-z0-9\-]*)\(", head[1])
        target = re.search(r'custom_call_target=\\?"([^"\\]+)', head[1])
        text += f" {target.group(1) if target else (op.group(1) if op else '')} {shape}"
    return text[:limit]


def union_intervals(events) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted((ev["start_ns"], ev["start_ns"] + ev["dur_ns"]) for ev in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _host_owner(host_sorted, starts, gap, longest_ns):
    """The host span that covers most of the gap; among equals the shortest, which is the
    innermost."""
    g0, g1 = gap
    best, best_key = NOTHING_TRACED, (0.0, 0.0)
    i = bisect.bisect_left(starts, g0 - longest_ns)
    while i < len(host_sorted) and host_sorted[i]["start_ns"] < g1:
        ev = host_sorted[i]
        cover = min(g1, ev["start_ns"] + ev["dur_ns"]) - max(g0, ev["start_ns"])
        key = (cover, -ev["dur_ns"])
        if cover > 0 and key > best_key:
            best, best_key = ev["name"], key
        i += 1
    return best


def reduce(trace: dict, top: int = 10, attribute: int = 400) -> dict:
    """busy_s and window_s (averaged over the devices), the operations that took most
    device time, and the idle time summed by what the host was doing in each gap (the
    `attribute` longest gaps of each device are looked up, the rest go under one name)."""
    devices = trace["devices"]
    if not devices or not any(d["ops"] for d in devices.values()):
        return {}
    host = sorted((e for e in trace["host"] if 0 < e["dur_ns"] <= LONGEST_HOST_SPAN_NS),
                  key=lambda e: e["start_ns"])
    starts = [e["start_ns"] for e in host]
    longest = max((e["dur_ns"] for e in host), default=0.0)
    op_s: dict[str, float] = defaultdict(float)
    gap_s: dict[str, float] = defaultdict(float)
    busy = window = longest_gap = 0.0
    n = 0
    for dev in devices.values():
        if not dev["ops"]:
            continue
        n += 1
        spans = union_intervals(dev["ops"])
        busy += sum(e - s for s, e in spans)
        window += spans[-1][1] - spans[0][0]
        short: dict[str, str] = {}  # thousands of events share a few hundred names
        for ev in dev["ops"]:
            name = ev["name"]
            if name not in short:
                short[name] = short_name(name)
            op_s[short[name]] += ev["dur_ns"]
        gaps = sorted(((b[0] - a[1], (a[1], b[0])) for a, b in zip(spans, spans[1:])),
                      reverse=True)
        if gaps:
            longest_gap = max(longest_gap, gaps[0][0])
        for length, gap in gaps[:attribute]:
            gap_s[_host_owner(host, starts, gap, longest)] += length
        rest = sum(length for length, _ in gaps[attribute:])
        if rest:
            gap_s[f"gaps beyond the {attribute} longest"] += rest
    rank = lambda d: [[k, v / n / 1e9] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy / n / 1e9, "window_s": window / n / 1e9,
            "longest_gap_s": longest_gap / 1e9,
            "device_ops": rank(op_s), "idle_gaps": rank(gap_s)}


def durations_ms(events, pattern: str) -> list[float]:
    rx = re.compile(pattern)
    return [e["dur_ns"] / 1e6 for e in events if rx.search(e["name"])]
