"""Per-layer metrics of the device itself: the share of the traced window in which no
operation ran, and the peak of device memory against the chip's."""

from __future__ import annotations


def idle_share(ctx):
    r = ctx["reduced"]
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])


def hbm_peak_share(ctx):
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]


# a name ends in the kind of cell that reports it, because `BENCHMARK.json` gives each name
# the one end-to-end metric it should move: a serving cell adds its names in a file of its own
METRICS = {"device_idle_share.train": idle_share, "hbm_peak_share.train": hbm_peak_share}
