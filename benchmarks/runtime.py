"""What every runner kind needs around JAX: the chip check, the compile cache, the count of
programs built, the peaks, the profiler window and the device line. Nothing here knows a
model, a traffic mix or a metric."""

from __future__ import annotations

import json
import os
import shutil
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
#: fixed: the path is part of the cache's key, so a directory that moves never hits
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
#: seconds of the measured window that the traced run hands to the profiler
TRACE_SECONDS = 3.0

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def process_age_s() -> float:
    """Seconds since the kernel started this process (10 ms steps), so that set-up counts
    the interpreter's start and the imports too."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        up = float(Path("/proc/uptime").read_text().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


class Builds:
    """Counts programs built (compiled by the backend or loaded from the persistent
    cache: either way a program was not ready) and splits them into hits and misses."""

    def __init__(self):
        import jax

        self.built = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == _BUILD_EVENT:
            self.built += 1

    def _on_event(self, event, **_kw):
        if event == _HIT_EVENT:
            self.hits += 1
        elif event == _MISS_EVENT:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"built": self.built, "cache_hits": self.hits, "cache_misses": self.misses}


def since(now: dict, then: dict) -> dict:
    return {k: now[k] - then[k] for k in now}


def require_chips(chips: int) -> dict:
    """The device line of a run, or SystemExit: no CPU mode, no unknown chip, no run on
    fewer chips than the cell asks for."""
    try:
        import jax

        devices = jax.devices()
    except Exception as exc:  # noqa: BLE001 — jax raises RuntimeError subclasses at start-up
        raise SystemExit(f"benchmark: no accelerator: {type(exc).__name__}: {exc}")
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"benchmark: platform is {d.platform!r}, not a TPU; there is no CPU mode")
    peaks = json.loads((HERE / "peaks.json").read_text())
    if d.device_kind not in peaks:
        raise SystemExit(f"benchmark: device_kind {d.device_kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, jax sees {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "peaks": peaks[d.device_kind]}


def enable_compile_cache() -> str:
    """JAX's persistent cache, every program kept however small or quick. Where the
    environment names the directory jax already holds it and nothing is set here."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = str(CACHE_DIR)
        Path(path).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_stats(chip: int) -> dict:
    import jax

    return jax.devices()[chip].memory_stats() or {}


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest chip, from the runtime's own statistics alone. On the v5e
    they keep two books: `bytes_in_use` is arrays (weights, optimizer state, batches), and
    `bytes_reserved` is what loaded programs hold for their temporaries, which stays held
    between steps. The chip holds both at once, so the peak is the sum of the two peaks;
    where the two were not reached at the same moment (set-up's reference holds a copy of
    some weights before the step program is loaded) it reads high by that copy at most."""
    def peak(stats: dict) -> int:
        return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))

    return max(peak(memory_stats(chip)) for chip in range(chips))


class TraceWindow:
    """Hands TRACE_SECONDS of the measured window to the profiler: `poll(now)` from the
    runner's loop starts it a little into the window and stops it when its time is up.
    The Python tracer stays off: it slows the host code it would describe."""

    def __init__(self, enabled: bool, workload: str, start_after_s: float = 1.0):
        self.enabled = enabled
        self.dir = TRACE_DIR / workload
        self.start_after_s = start_after_s
        self.t_start = self.t_stop = None  # perf_counter stamps around the profiler's time

    def poll(self, since_window_start_s: float) -> None:
        if not self.enabled or self.t_stop is not None:
            return
        import jax

        if self.t_start is None:
            if since_window_start_s >= self.start_after_s:
                shutil.rmtree(self.dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(self.dir), profiler_options=opts)
                self.t_start = time.perf_counter()
        elif time.perf_counter() - self.t_start >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if self.t_start is not None and self.t_stop is None:
            import jax

            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def xplane(self) -> Path | None:
        found = sorted(self.dir.rglob("*.xplane.pb")) if self.t_stop is not None else []
        return found[-1] if found else None
