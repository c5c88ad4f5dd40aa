"""The KFTPU_* environment-variable registry — ONE place where every
platform env-var name is spelled out.

The pod env contract crosses a process boundary: the controller side
*injects* these variables (envcontract.synthesize_env, jobcontroller pod
creation, chaos.pod_env) and the worker side *reads* them (trainer,
tracing.init_worker_from_env, health.HeartbeatWriter.from_env). A typo'd
or renamed literal on either side doesn't fail loudly — the reader just
sees "unset" and silently degrades (no heartbeats, no trace flush, no
profile). Centralizing the names makes injector/reader drift impossible,
and the KFTPU-ENV lint rule (kubeflow_tpu/analysis) enforces that no
module outside this registry spells a ``KFTPU_*`` string literal.

Import the constant, never inline the string:

    from kubeflow_tpu.utils.envvars import ENV_TRACE_DIR
    os.environ.get(ENV_TRACE_DIR, "")

Stdlib-free on purpose: imported by the earliest-loading modules
(tracing, health) without dragging anything in.
"""

from __future__ import annotations

# --------------------------------------------------------------- pod contract

#: directory worker processes flush their trace spans into
ENV_TRACE_DIR = "KFTPU_TRACE_DIR"
#: parent SpanContext carried into a pod ("traceid-spanid")
ENV_TRACEPARENT = "KFTPU_TRACEPARENT"
#: per-incarnation heartbeat file one worker writes (liveness lease)
ENV_HEARTBEAT_FILE = "KFTPU_HEARTBEAT_FILE"
#: chaos carrier for seeded heartbeat-write drops ("rate:seed:count")
ENV_HEARTBEAT_DROP = "KFTPU_HB_DROP"
#: jax.profiler trace output dir (per-process; JAXJob profile toggle)
ENV_PROFILE_DIR = "KFTPU_PROFILE_DIR"
#: persistent XLA compile-cache directory (utils/compile_cache.py). The
#: jobcontroller injects a per-platform path that SURVIVES gang restarts,
#: so a restarted incarnation replays its train-step executables from the
#: cache instead of paying a full re-trace+recompile (docs/perf.md)
ENV_COMPILE_CACHE_DIR = "KFTPU_COMPILE_CACHE_DIR"
#: tfevents scalar output dir for TensorBoard
ENV_EVENT_DIR = "KFTPU_EVENT_DIR"
#: AF_UNIX socket path a serving pod worker binds (podworker/podclient)
ENV_POD_SOCKET = "KFTPU_POD_SOCKET"
#: the pod worker's replica name (trace service, heartbeat identity)
ENV_POD_NAME = "KFTPU_POD_NAME"
#: path to the JSON engine spec a pod worker builds its batcher from
ENV_POD_SPEC = "KFTPU_POD_SPEC"
#: wire transport a pod worker serves on: "unix" (default) or "tcp"
ENV_POD_TRANSPORT = "KFTPU_POD_TRANSPORT"
#: file a TCP pod worker atomically writes its bound 127.0.0.1 port to
#: (the controller polls it the way it polls the AF_UNIX socket path)
ENV_POD_PORT_FILE = "KFTPU_POD_NET_PORT_FILE"

# ------------------------------------------------------------- platform state

#: root for controller-side state (hostfiles, heartbeats, pod logs)
ENV_STATE_DIR = "KFTPU_STATE_DIR"
#: PVC mount root: pvc://volume/sub -> $KFTPU_PVC_ROOT/volume/sub
ENV_PVC_ROOT = "KFTPU_PVC_ROOT"
#: file-backed object-store emulator root (gs://, s3:// resolve under it)
ENV_OBJECT_STORE_EMULATOR = "KFTPU_OBJECT_STORE_EMULATOR"

# ----------------------------------------------------------- developer tools

#: "1" arms the runtime lock-order/race detector (analysis/lockcheck.py)
ENV_LOCKCHECK = "KFTPU_LOCKCHECK"
#: "1" regenerates the lint baseline instead of failing on findings
ENV_UPDATE_LINT_BASELINE = "KFTPU_UPDATE_LINT_BASELINE"
#: "1" regenerates golden files (metrics exposition) instead of diffing
ENV_UPDATE_GOLDEN = "KFTPU_UPDATE_GOLDEN"
#: exhaustive-BFS depth bound for the protocol model checker
#: (analysis/protocheck — docs/analysis.md "Protocol model checking")
ENV_MODELCHECK_DEPTH = "KFTPU_MODELCHECK_DEPTH"
#: seed for the random-walk frontier the model checker runs past the
#: exhaustive bound (analysis/protocheck)
ENV_MODELCHECK_SEED = "KFTPU_MODELCHECK_SEED"
#: JSONL path the wire/KV/ledger protocol event-log hooks append to when
#: armed (off when unset; analysis/protocheck conformance checking)
ENV_PROTOLOG = "KFTPU_PROTOLOG"

# ------------------------------------------------------------ chip scheduler

#: chips per slice in the shared chip ledger's inventory — the slice-
#: aware bin-packing granularity (scheduler/chipsched.py; Platform
#: construction reads it, docs/scheduler.md)
ENV_SCHED_CHIPS_PER_SLICE = "KFTPU_SCHED_CHIPS_PER_SLICE"
#: Retry-After hint (seconds) a chip-claim deny carries back to the
#: caller (the activator's Retry-After idiom, scheduler edition)
ENV_SCHED_RETRY_AFTER_S = "KFTPU_SCHED_RETRY_AFTER_S"

# ------------------------------------------------------------ SLO monitoring

#: sampling-tick interval in seconds for the SLO monitor's background
#: scrape of the kftpu_* families (Platform.start_slo; docs/slo.md)
ENV_SLO_TICK_S = "KFTPU_SLO_TICK_S"
#: per-series ring capacity of the SLO monitor's time-series store
#: (monitoring/tsdb.py — samples past it evict oldest, counted)
ENV_SLO_CAPACITY = "KFTPU_SLO_CAPACITY"

#: every name defined above, for tooling that wants the full contract
ALL_ENV_VARS = tuple(
    v for k, v in sorted(globals().items())
    if k.startswith("ENV_") and isinstance(v, str)
)
