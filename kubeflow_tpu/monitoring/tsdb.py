"""Bounded in-memory time-series store — the monitoring plane's memory.

The platform already *counts* everything (the kftpu_* families in
/metrics), but counters answer "how many ever", not "how fast lately" —
and an autoscaler or SLO monitor consumes rates, deltas, and
quantiles-over-windows, never raw totals. This module is the smallest
store that answers those queries without a dependency or an unbounded
buffer:

  - one fixed-capacity ring per series (collections.deque, exactly the
    FlightRecorder design): recording past a full ring evicts the oldest
    sample and counts it in `dropped` — the store never grows and never
    blocks, which is what makes an always-on sampling tick safe;
  - a bounded series *set* too: a label explosion (a runaway per-pod
    gauge) rejects new series loudly (`series_rejected_total`) instead
    of eating the process;
  - queries are windowed: rate()/delta() for counters (reset-aware:
    only positive increments count, so a restarted process cannot
    produce a negative rate), quantile()/mean()/latest() for gauges and
    latency samples.

Samples arrive two ways: `sample_platform` scrapes the EXISTING
`kftpu_*` exposition on a tick (one build path with /metrics — see
sampler.py), and hot-path producers (the serving engine's decode-tick /
TTFT hooks) record directly — a perf_counter read plus a deque append
(per 2011.03641 the monitoring plane must stay off the hot path; what
it costs on the chip is not measured).
"""

from __future__ import annotations

import time
from collections import deque

from kubeflow_tpu.analysis.lockcheck import make_lock


class _Series:
    """One named ring of (ts, value) samples."""

    __slots__ = ("name", "ring", "capacity", "total", "dropped")

    def __init__(self, name: str, capacity: int):
        self.name = name
        self.capacity = capacity
        self.ring: deque = deque(maxlen=capacity)
        self.total = 0
        self.dropped = 0

    def append(self, ts: float, value: float) -> None:
        self.total += 1
        if len(self.ring) == self.capacity:
            self.dropped += 1
        self.ring.append((ts, value))


class TimeSeriesStore:
    """Fixed-capacity per-series sample windows with windowed queries.

    All methods are thread-safe under one lock; queries copy the window
    they need and compute outside nothing (windows are small by
    construction), so holds stay short.
    """

    def __init__(self, capacity_per_series: int = 512,
                 max_series: int = 1024):
        if capacity_per_series < 2:
            raise ValueError(
                f"capacity_per_series must be >= 2 (a rate needs two "
                f"samples), got {capacity_per_series}")
        self.capacity_per_series = int(capacity_per_series)
        self.max_series = int(max_series)
        self._mu = make_lock("monitoring.TimeSeriesStore._mu")
        self._series: dict[str, _Series] = {}
        self.samples_total = 0
        self.series_rejected_total = 0
        #: recording gate (the Tracer.armed contract applied to
        #: samples): False freezes the rings — hot-path producers
        #: (engine decode-tick/TTFT hooks) degrade to a no-op, so
        #: reading a captured incident window can never evict it
        #: (Platform.stop_slo flips this; start_slo re-arms)
        self.armed = True

    # ------------------------------------------------------------ recording

    def record(self, name: str, value, ts: float | None = None) -> bool:
        """Append one sample; returns False when disarmed (frozen
        store), or when the series set is full and `name` is new
        (counted in series_rejected_total) — never an exception: the
        monitoring plane must not fail its producers."""
        if not self.armed:
            return False
        ts = time.time() if ts is None else float(ts)
        v = float(value)
        with self._mu:
            s = self._series.get(name)
            if s is None:
                if len(self._series) >= self.max_series:
                    self.series_rejected_total += 1
                    return False
                s = self._series[name] = _Series(
                    name, self.capacity_per_series)
            s.append(ts, v)
            self.samples_total += 1
        return True

    def record_many(self, samples: dict, ts: float | None = None) -> int:
        """Record a batch at one timestamp (the sampling tick's shape);
        returns how many were accepted."""
        ts = time.time() if ts is None else float(ts)
        return sum(1 for name, v in samples.items()
                   if self.record(name, v, ts=ts))

    # -------------------------------------------------------------- queries

    def names(self) -> list[str]:
        with self._mu:
            return sorted(self._series)

    def window(self, name: str, window_s: float,
               now: float | None = None) -> list[tuple[float, float]]:
        """Samples of `name` with ts in (now - window_s, now], oldest
        first (empty for an unknown series)."""
        now = time.time() if now is None else float(now)
        lo = now - float(window_s)
        with self._mu:
            s = self._series.get(name)
            if s is None:
                return []
            return [(ts, v) for ts, v in s.ring if lo < ts <= now]

    def latest(self, name: str) -> float | None:
        with self._mu:
            s = self._series.get(name)
            return s.ring[-1][1] if s is not None and s.ring else None

    def delta(self, name: str, window_s: float,
              now: float | None = None) -> float:
        """Counter increase over the window: the sum of POSITIVE
        increments between consecutive samples (a monotonic reset —
        process restart — contributes the post-reset value, never a
        negative step), plus the step from the last pre-window sample
        when one exists so a slow tick cannot hide an increment on the
        window edge."""
        now = time.time() if now is None else float(now)
        lo = now - float(window_s)
        with self._mu:
            s = self._series.get(name)
            samples = list(s.ring) if s is not None else []
        prev = None
        for ts, v in samples:
            if ts <= lo:
                prev = v
        total = 0.0
        for ts, v in samples:
            if not (lo < ts <= now):
                continue
            if prev is not None:
                step = v - prev
                total += step if step > 0 else v if step < 0 else 0.0
            prev = v
        return total

    def rate(self, name: str, window_s: float,
             now: float | None = None) -> float:
        """Counter rate per second over the window (delta / window)."""
        w = float(window_s)
        return self.delta(name, w, now=now) / w if w > 0 else 0.0

    def quantile(self, name: str, q: float, window_s: float,
                 now: float | None = None) -> float:
        """Nearest-rank quantile over the window's sample VALUES (0 when
        empty) — the honest form for latency series (a quantile is always
        a value that occurred)."""
        values = sorted(v for _, v in self.window(name, window_s, now=now))
        if not values:
            return 0.0
        idx = max(0, min(len(values) - 1,
                         int(round(q * (len(values) - 1)))))
        return values[idx]

    def mean(self, name: str, window_s: float,
             now: float | None = None) -> float:
        values = [v for _, v in self.window(name, window_s, now=now)]
        return sum(values) / len(values) if values else 0.0

    # ------------------------------------------------------------ reporting

    def stats(self) -> dict:
        """Volume + loss accounting (the kftpu_slo_samples_* families):
        a ring sized too small for the sample rate is visible as
        samples_dropped_total, exactly like the flight recorder's."""
        with self._mu:
            return {
                "series": len(self._series),
                "capacity_per_series": self.capacity_per_series,
                "max_series": self.max_series,
                "samples_total": self.samples_total,
                "samples_dropped_total": sum(
                    s.dropped for s in self._series.values()),
                "series_rejected_total": self.series_rejected_total,
            }
