"""Persistent XLA compile cache + serialized executables — ONE config path.

Two layers of compile reuse, shared by serving cold-start (serving/aot.py)
and training gang-restart (train/trainer.py warm_start):

  1. The **persistent XLA compilation cache**: `enable_persistent_cache`
     points jax's backend-compile cache at a directory (thresholds zeroed —
     a restarted process must hit for EVERY executable, however small).
     A re-traced program whose HLO matches a cached entry skips the XLA
     compiler entirely; the `/jax/compilation_cache/cache_misses`
     monitoring counter (install_compile_listener / compile_counts) is the
     proof both the serving AOT tests and the restart-warm test
     (tests/test_hotpath.py) assert on.
  2. **Serialized executables**: `save_executable` / `load_executable`
     persist a jitted program's COMPILED form (jax.experimental.
     serialize_executable) keyed by `executable_key(...)` — reloading
     skips trace AND compile, the strongest restart-warm path. Keys must
     cover everything that changes the program: model-config hash, mesh
     shape, batch shapes/dtypes, compute dtype, jax version.

Why restart-warm matters (ROADMAP item 5, papers 1909.09756 / 2011.03641):
every gang restart previously paid a full re-trace+recompile of the train
step — orchestration overhead capping goodput while the chips idle. With
one cache directory that survives restarts, a restarted incarnation
performs zero backend compilations of the train step.

Where the cache lives — ONE resolver (`resolve_cache_dir`) for the
trainer, the model server, the serving pod worker and the job controller:
`JAX_COMPILATION_CACHE_DIR`, when set, is the directory and nothing here
ever points jax anywhere else (pods inherit the parent's environment, so
the variable reaches every worker by itself); otherwise the platform's
processes share `DEFAULT_CACHE_DIR`, an absolute path inside the checkout
— the path is part of nothing's key but must not move with the cwd, and
pods run with their own.

Process-global metrics land in /metrics as the kftpu_train_compile_*
families (observability.py); `reset_compile_metrics` is the test hook.

The start-up log. Besides the counts, the listener keeps what jax tells
it of every program it traces, lowers, compiles or loads from the cache:
which, when, for how long, and what the persistent cache did. That goes
to the counters, to a bounded process-global log (`startup_log`: a few
dozen entries a process, none on a step's path, so it is kept always),
and as `compile.*` spans to a Tracer where one is armed. `region` marks
the trainer's start-up phases in the same log; `kubeflow_tpu/train`
installs the listener and notes its own import as the first entry.
docs/observability.md ("The trainer's spans") has the names.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from pathlib import Path

from kubeflow_tpu.tracing import armed_tracer, current_context, get_tracer
from kubeflow_tpu.utils.envvars import ENV_COMPILE_CACHE_DIR

#: suffix of serialized-executable artifacts inside <cache_dir>/executables
EXECUTABLE_SUFFIX = ".kfexec"

#: size bound for the executables dir — the shared cache deliberately
#: survives restarts and nothing else ever deletes from it, so without a
#: cap a long-lived platform accumulates one artifact per distinct
#: (model, shape, dtype, knobs, jax version) forever. Oldest-mtime
#: artifacts are evicted after each save; reloads touch mtime, so the
#: sweep is LRU in practice. (The XLA persistent-cache entries beside it
#: are jax's own; bound those with jax's cache-size flags where needed.)
EXECUTABLE_DIR_MAX_BYTES = 2 << 30

_MU = threading.Lock()
#: process-global counters (kftpu_train_compile_* in /metrics). Backend
#: miss/request counts come from the jax monitoring listener; the
#: executable reload/save counts from load_/save_executable.
_METRICS = {
    "requests_total": 0,          # backend compiles that consulted the cache
    "backend_misses_total": 0,    # backend compiles the XLA compiler ran
    "executable_reloads_total": 0,  # deserialized pre-compiled executables
    "executable_saves_total": 0,    # executables serialized for later runs
    "cache_hits_total": 0,        # programs the persistent cache served
    "cache_retrieval_seconds_total": 0.0,  # reading and loading those
    "trace_seconds_total": 0.0,   # Python traced to jaxprs
    "lower_seconds_total": 0.0,   # jaxprs lowered to MLIR modules
    "backend_seconds_total": 0.0,  # XLA compiling, or the cache loading
}
_LISTENER_INSTALLED = False

#: jax.monitoring's duration events -> the phase of making a program ready
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: entries the start-up log keeps: a cold start of the largest job logs a
#: few dozen; past it the oldest is dropped and counted, as the flight
#: recorder does
STARTUP_LOG_CAPACITY = 512
_LOG: deque = deque(maxlen=STARTUP_LOG_CAPACITY)
_LOG_DROPPED = 0
#: `compile.backend` entries ever logged in this process. `Trainer.
#: train_step` reads it before and after a dispatch, without the lock: an
#: int that only grows
_BUILT = 0


class _ThreadState(threading.local):
    """What the listener pairs by thread: how many of jax's timed blocks
    are open, what the persistent cache did inside the open backend block,
    and the open region."""

    depth = 0
    cache = "off"
    retrieval_s = 0.0
    region = ""


_THREAD = _ThreadState()


#: jax's own variable for the persistent cache directory; where it is set
#: the cache lives there and this module never re-points jax
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: where the platform's processes keep the cache when the environment
#: names no directory: anchored to the package location, not to the cwd
DEFAULT_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".kubeflow_tpu" / "compile-cache")


def resolve_cache_dir(explicit: str = "", *, default: bool = False) -> str:
    """The effective cache dir. `JAX_COMPILATION_CACHE_DIR` wins over
    everything; then an explicit config value; then the pod env contract
    (ENV_COMPILE_CACHE_DIR, injected by the jobcontroller); then, for
    callers that always cache (`default=True`: the job controller and the
    model server), DEFAULT_CACHE_DIR — else "" (caching off)."""
    return (os.environ.get(ENV_JAX_CACHE_DIR, "")
            or explicit
            or os.environ.get(ENV_COMPILE_CACHE_DIR, "")
            or (DEFAULT_CACHE_DIR if default else ""))


def enable_persistent_cache(cache_dir: str | Path) -> None:
    """Point jax's persistent backend-compile cache at `cache_dir` and
    zero the size/time thresholds (the default thresholds skip caching
    cheap compiles — a restarted incarnation must hit the cache for EVERY
    executable, however small). Also installs the miss-counting listener
    so compile_counts() deltas are meaningful from the first compile.
    With `JAX_COMPILATION_CACHE_DIR` set, jax already holds the directory
    and `jax_compilation_cache_dir` is left alone.

    jax LATCHES the cache state at the first compile: a process that
    compiled anything before this call (e.g. a trainer whose init ran
    first) has the cache pinned "disabled/not initialized", and a later
    config update alone leaves every subsequent write silently skipped —
    reads would miss and NO miss event would ever fire, making a
    zero-miss assertion vacuously true. reset_cache() drops the latch so
    the next compile re-initializes against the directory just set."""
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cc,
    )

    if not os.environ.get(ENV_JAX_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax_cc.reset_cache()
    install_compile_listener()


def install_compile_listener() -> None:
    """Keep what jax.monitoring tells of every program made ready: count
    backend compile requests/misses/hits and the seconds by phase
    process-globally, and write each trace, lowering and backend compile
    (or cache load) to the start-up log and to an armed Tracer.
    Idempotent; safe to call before any cache is enabled (the cache's
    events simply don't fire and a build reads `cache="off"`)."""
    global _LISTENER_INSTALLED
    with _MU:
        if _LISTENER_INSTALLED:
            return
        _LISTENER_INSTALLED = True
    import jax.monitoring as mon

    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)
    mon.register_scalar_listener(_on_block_entered)


def _on_event(event: str, **kwargs) -> None:
    # the cache's events fire inside the backend block, on its thread
    if event == "/jax/compilation_cache/cache_misses":
        with _MU:
            _METRICS["backend_misses_total"] += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        # jax fires this with no cache directory too (the key is made, the
        # read finds nothing to read from); a hit says so before the block
        # closes
        import jax

        if jax.config.jax_compilation_cache_dir:
            _THREAD.cache = "miss"
        with _MU:
            _METRICS["requests_total"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _THREAD.cache = "hit"
        with _MU:
            _METRICS["cache_hits_total"] += 1


def _on_block_entered(event: str, value, **kwargs) -> None:
    """jax records a scalar (the start time) as it enters each timed block.
    A trace or a lowering entered inside another block is part of that
    one's seconds (a jit called while its caller is traced, a lowering
    rule that traces jnp code: hundreds to a model); a backend block
    starts with no word from the cache."""
    phase = _PHASES.get(event)
    if phase is not None:
        _THREAD.depth += 1
        if phase == "backend":
            _THREAD.cache, _THREAD.retrieval_s = "off", 0.0


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        if event == _RETRIEVAL_EVENT:
            _THREAD.retrieval_s = float(seconds)
        return
    # not below 0: the listener may have been installed inside a block
    _THREAD.depth = max(_THREAD.depth - 1, 0)
    if _THREAD.depth and phase != "backend":
        return
    fields = {"program": program_name(str(kwargs.get("fun_name", "")))}
    if phase == "backend":
        fields["cache"] = _THREAD.cache
        if _THREAD.cache == "hit":
            fields["retrieval_s"] = _THREAD.retrieval_s
    fields["region"] = _THREAD.region
    _record(f"compile.{phase}", time.time() - seconds, float(seconds),
            fields, phase=phase)


def program_name(fun_name: str) -> str:
    """One name for one program whatever phase reports it: jax names a
    trace by the function (`_train_step`) and a lowering or a build by the
    module (`jit(_train_step)`), which the HLO and the device trace spell
    `jit__train_step`. The log keeps that last spelling for all three."""
    module = fun_name if fun_name.endswith(")") else f"jit({fun_name})"
    return re.sub(r"[^\w.-]", "_", module).rstrip("_")


def _record(name: str, start: float, seconds: float, fields: dict, *,
            phase: str = "", span: bool = True) -> None:
    """One entry of the start-up log, a build's counters (`phase`), and
    where a Tracer is armed a span under whatever span is open on this
    thread."""
    global _LOG_DROPPED, _BUILT
    with _MU:
        if phase:
            _METRICS[f"{phase}_seconds_total"] += seconds
            _METRICS["cache_retrieval_seconds_total"] += fields.get(
                "retrieval_s", 0.0)
            _BUILT += phase == "backend"
        if len(_LOG) == STARTUP_LOG_CAPACITY:
            _LOG_DROPPED += 1
        _LOG.append({"name": name, "start": start, "seconds": seconds,
                     **fields})
    tracer = armed_tracer(get_tracer()) if span else None
    if tracer is not None:
        tracer.record_span(
            name, start, seconds,
            parent=current_context() or tracer.default_parent, **fields)


def note_import(name: str, start: float, seconds: float) -> None:
    """A package's own import as an entry (`train.import`: `start` and
    `seconds` span its `__init__` from the first line to the last)."""
    _record(name, start, seconds, {})


@contextmanager
def region(name: str):
    """A start-up phase of the trainer: `get_tracer().span(name)`, plus an
    entry `{"name", "start", "seconds"}` in the start-up log on exit; what
    is built inside carries `region=name`. Yields the span."""
    outer, _THREAD.region = _THREAD.region, name
    start, t0 = time.time(), time.perf_counter()
    try:
        with get_tracer().span(name) as sp:
            yield sp
    finally:
        _THREAD.region = outer
        # the live span above is the tracer's; the log alone gets this one
        _record(name, start, time.perf_counter() - t0, {}, span=False)


def startup_log() -> list[dict]:
    """The log's entries, oldest first (copies: the caller may keep them)."""
    with _MU:
        return [dict(e) for e in _LOG]


def startup_log_dropped() -> int:
    """Entries the bounded log has dropped."""
    return _LOG_DROPPED


def programs_built() -> int:
    """Programs the backend made ready in this process so far, compiled or
    loaded from the persistent cache. Lock-free: `Trainer.train_step`
    reads it around every dispatch."""
    return _BUILT


def compile_counts() -> dict[str, int | float]:
    """Snapshot of the process-global counters — subtract two snapshots
    to get the misses/requests/seconds a code region caused (the
    zero-backend-compilations assertion pattern; observability.
    render_metrics prints them as kftpu_train_compile_*)."""
    with _MU:
        return dict(_METRICS)


def reset_compile_metrics() -> None:
    """Test hook: zero the counters and empty the start-up log (the
    listener stays installed)."""
    global _LOG_DROPPED, _BUILT
    with _MU:
        for k, v in _METRICS.items():
            _METRICS[k] = type(v)()
        _LOG.clear()
        _LOG_DROPPED = _BUILT = 0


def executable_key(**parts) -> str:
    """Deterministic content key for a serialized executable. Callers pass
    everything that changes the compiled program (model-config hash, mesh
    shape, batch shapes/dtypes, compute dtype, optimizer knobs, fused step
    count); jax version and backend are always folded in — a cache dir
    shared across upgrades must never replay a stale binary."""
    import jax

    parts = dict(parts)
    parts["jax_version"] = jax.__version__
    parts["backend"] = jax.default_backend()
    blob = "\x1f".join(f"{k}={parts[k]!r}" for k in sorted(parts))
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def executable_path(cache_dir: str | Path, key: str) -> Path:
    return Path(cache_dir) / "executables" / f"{key}{EXECUTABLE_SUFFIX}"


def save_executable(cache_dir: str | Path, key: str, compiled) -> Path | None:
    """Serialize a compiled executable (jax.experimental
    .serialize_executable) under its key. Returns the path, or None when
    the backend refuses to serialize it (the persistent backend cache
    still covers the restart — degraded, not broken). Writes are atomic
    (tmp+rename) so a killed pod never leaves a torn artifact for the
    next one."""
    from jax.experimental.serialize_executable import serialize

    path = executable_path(cache_dir, key)
    try:
        payload, in_tree, out_tree = serialize(compiled)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(path.suffix + f".tmp.{os.getpid()}")
        with open(tmp, "wb") as fh:
            pickle.dump((payload, in_tree, out_tree), fh)
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001 — serialization support varies by
        # backend; a failed save must never fail training, and the
        # persistent backend cache above still makes the restart warm
        return None
    with _MU:
        _METRICS["executable_saves_total"] += 1
    _evict_lru(path.parent, keep=path)
    return path


def _evict_lru(exec_dir: Path,
               keep: Path | None = None,
               max_bytes: int | None = None) -> None:
    """Drop oldest-mtime executables until the dir fits the size bound
    (the entry just saved is never the victim). Best-effort: a racing
    pod deleting the same file is fine."""
    limit = EXECUTABLE_DIR_MAX_BYTES if max_bytes is None else max_bytes
    try:
        entries = []
        for p in exec_dir.glob(f"*{EXECUTABLE_SUFFIX}"):
            st = p.stat()
            entries.append((st.st_mtime, st.st_size, p))
        total = sum(size for _, size, _ in entries)
        for _, size, p in sorted(entries):
            if total <= limit:
                break
            if keep is not None and p == keep:
                continue
            p.unlink()
            total -= size
    except OSError:
        return


def load_executable(cache_dir: str | Path, key: str, execution_devices):
    """Deserialize a previously saved executable — trace AND compile are
    both skipped. `execution_devices` are the devices of the mesh the
    program was compiled over (`mesh.devices.flat`): left to its default
    jax binds the executable to EVERY device of the backend, and a
    one-chip job on a four-chip host then cannot call what it loaded.
    Returns the loaded callable, or None when absent / unreadable / built
    by an incompatible jax (key covers version, but a torn write or
    backend drift still degrades gracefully to None)."""
    from jax.experimental.serialize_executable import deserialize_and_load

    path = executable_path(cache_dir, key)
    if not path.exists():
        return None
    try:
        with open(path, "rb") as fh:
            payload, in_tree, out_tree = pickle.load(fh)
        loaded = deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=list(execution_devices))
    except Exception:  # noqa: BLE001 — a corrupt artifact must degrade to
        # a normal (cache-warm) compile, never crash the incarnation
        try:
            path.unlink()  # quarantine-by-removal: don't retry it forever
        except OSError:
            pass
        return None
    try:
        os.utime(path)  # a hit is a use: keep it young for the LRU sweep
    except OSError:
        pass  # kftpu: allow=KFTPU-EXCEPT (best-effort mtime touch)
    with _MU:
        _METRICS["executable_reloads_total"] += 1
    return loaded
