"""kubeflow_tpu.profiling — trace analytics over the flight recorder.

The answer layer on top of tracing/ (docs/profiling.md): step-time
breakdowns with an explicit stall remainder, goodput per job incarnation
with restart overhead attributed along the causal chain, control-plane
latency percentiles, and golden-pinnable restart trace shapes.

Surfaces: `GET /debug/profile` (apiserver), the `profile` CLI subcommand
and the `kftpu_prof_*` /metrics families (observability.py) — all
reading report.build_profile, so they agree by construction.
"""

from kubeflow_tpu.profiling.analytics import (
    PROF_BUCKETS,
    REQUEST_PHASES,
    aggregate_requests,
    aggregate_steps,
    ancestry,
    control_plane_stats,
    goodput,
    percentile,
    request_breakdown,
    request_shape,
    restart_chains,
    restart_shape,
    scaler_shape,
    step_breakdown,
)
from kubeflow_tpu.profiling.report import (
    ProfileError,
    build_profile,
    load_trace_dir,
    platform_spans,
    profile_platform,
    render_text,
)

__all__ = [
    "PROF_BUCKETS",
    "REQUEST_PHASES",
    "ProfileError",
    "aggregate_requests",
    "aggregate_steps",
    "ancestry",
    "build_profile",
    "control_plane_stats",
    "goodput",
    "load_trace_dir",
    "percentile",
    "platform_spans",
    "profile_platform",
    "render_text",
    "request_breakdown",
    "request_shape",
    "restart_chains",
    "restart_shape",
    "scaler_shape",
    "step_breakdown",
]
