"""Per-layer metrics of set-up, read from the program's own start-up log
(`kubeflow_tpu.utils.compile_cache.startup_log`): the package's import, the trainer's
`init_state`, and every program jax traced, lowered, compiled or loaded from the
persistent cache in this process, the reference's beside the program's. They are host
seconds and counts, not device times: the log is kept whether or not a run is traced, and
read here after the window like every per-layer metric. A program without the log (a
parent from before it) reads nothing."""

from __future__ import annotations

import re

from kubeflow_tpu.utils import compile_cache


def _log() -> list[dict]:
    read = getattr(compile_cache, "startup_log", None)  # the parent's has none
    return read() if read is not None else []


def _seconds(entries) -> float | None:
    """Their seconds summed, or None where there are none."""
    found = [e["seconds"] for e in entries]
    return float(sum(found)) if found else None


def _named(log, *names):
    return [e for e in log if e["name"] in names]


def _step(ctx, log, *names):
    """The entries of the cell's step program (`facts["step_program"]`, the pattern that
    finds it on the device's `XLA Modules` line: the log spells programs the same way)."""
    return [e for e in _named(log, *names) if re.search(ctx["facts"]["step_program"], e.get("program", ""))]


def import_s(ctx):
    """`import kubeflow_tpu.train`, first line to last of its `__init__`."""
    return _seconds(_named(_log(), "train.import"))


def init_state_s(ctx):
    """The `train.init_state` regions: host seconds until the state's program is built and
    enqueued (the device may still be running it)."""
    return _seconds(_named(_log(), "train.init_state"))


def step_trace_s(ctx):
    """Tracing and lowering the step program: Python-bound, and no cache saves it."""
    return _seconds(_step(ctx, _log(), "compile.trace", "compile.lower"))


def step_backend_s(ctx):
    """The step program's backend block: XLA's compile on a miss, the persistent cache's
    retrieval and load on a hit."""
    return _seconds(_step(ctx, _log(), "compile.backend"))


def programs_compiled(ctx):
    """Programs the backend compiled because the persistent cache did not serve them: 0 on
    a warm reading of a seen seed."""
    log = _log()
    return float(sum(e.get("cache") != "hit" for e in _named(log, "compile.backend"))) if log else None


def build_s(ctx):
    """All the seconds of traces, lowerings and backend blocks in the process."""
    return _seconds(_named(_log(), "compile.trace", "compile.lower", "compile.backend"))


METRICS = {"import_s.train": import_s, "init_state_s.train": init_state_s,
           "step_trace_s.train": step_trace_s, "step_backend_s.train": step_backend_s,
           "programs_compiled.train": programs_compiled, "build_s.train": build_s}
