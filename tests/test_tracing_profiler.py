"""The program's spans on the profiler's clock (tracing/core.py): every span used as
a context manager also opens a `jax.profiler.TraceAnnotation` while a profiler
session records — armed, disarmed or NOOP — and costs nothing a test can see outside
one. One CPU session, a second or two; nothing here times anything."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from kubeflow_tpu.tracing import NOOP_TRACER, Tracer
from kubeflow_tpu.tracing import core as tracing_core

ROOT = Path(__file__).resolve().parents[1]


def host_events(trace_dir) -> dict:
    """name -> [(start_ns, end_ns, stats)] of the trace's `/host:CPU` plane."""
    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """One profiler session with every kind of span opened in it, and what the armed
    tracer's ring held afterwards."""
    import jax

    armed, disarmed = Tracer(), Tracer()
    disarmed.armed = False
    with armed.span("t.before_session"):
        pass
    trace_dir = tmp_path_factory.mktemp("xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with armed.span("t.armed", step=3, path="jit", blob=b"not a stat") as outer:
            with NOOP_TRACER.span("t.noop", rows=8):
                with disarmed.span("t.disarmed"):
                    pass
            outer.set_attribute("late", 1.5)
        with NOOP_TRACER.span("t.noop_late") as sp:
            sp.set_attribute("path", "executable").set_attribute("long", "x" * 500)
        manual = armed.start_span("t.never_entered")
        manual.end()
        armed.event("t.event")
        armed.record_span("t.after_the_fact", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    with NOOP_TRACER.span("t.after_session"):
        pass
    return {"host": host_events(trace_dir), "ring": armed.snapshot()}


@pytest.mark.parametrize("name", ["t.armed", "t.noop", "t.disarmed", "t.noop_late"])
def test_every_context_manager_span_reaches_the_host_plane(session, name):
    assert len(session["host"].get(name, [])) == 1, sorted(session["host"])


def test_spans_nest_on_the_profilers_clock_as_they_were_opened(session):
    (a0, a1, _), = session["host"]["t.armed"]
    (n0, n1, _), = session["host"]["t.noop"]
    (d0, d1, _), = session["host"]["t.disarmed"]
    assert a0 <= n0 <= d0 <= d1 <= n1 <= a1
    assert session["host"]["t.noop_late"][0][0] >= a1


def test_numbers_and_short_strings_become_stats_of_the_event(session):
    stats = session["host"]["t.armed"][0][2]
    assert stats["step"] == 3 and stats["path"] == "jit" and float(stats["late"]) == 1.5
    assert "blob" not in stats
    assert session["host"]["t.noop"][0][2]["rows"] == 8
    late = session["host"]["t.noop_late"][0][2]
    assert late["path"] == "executable" and "long" not in late


@pytest.mark.parametrize("name", ["t.never_entered", "t.event", "t.after_the_fact",
                                  "t.before_session", "t.after_session"])
def test_only_spans_entered_inside_the_session_are_annotated(session, name):
    assert name not in session["host"]


def test_the_armed_tracer_records_into_its_ring_as_before(session):
    ring = {s["name"]: s for s in session["ring"]}
    assert set(ring) == {"t.before_session", "t.armed", "t.never_entered", "t.event",
                         "t.after_the_fact"}
    assert ring["t.armed"]["attrs"] == {"step": 3, "path": "jit", "blob": b"not a stat",
                                        "late": 1.5}
    assert ring["t.armed"]["dur"] > 0


def test_outside_a_session_the_noop_tracer_hands_out_its_shared_span():
    assert NOOP_TRACER.span("x", a=1) is NOOP_TRACER.span("y")
    disarmed = Tracer()
    disarmed.armed = False
    assert disarmed.span("x") is NOOP_TRACER.span("y")
    assert tracing_core._live_annotation() is None


def test_tracing_core_imports_and_spans_work_without_jax():
    """The control plane imports tracing and never jax: nothing here may pull it in."""
    code = (
        "import sys\n"
        "from kubeflow_tpu.tracing import NOOP_TRACER, Tracer\n"
        "t = Tracer()\n"
        "with t.span('a', step=1) as a:\n"
        "    with NOOP_TRACER.span('b') as b:\n"
        "        b.set_attribute('k', 1)\n"
        "    a.set_attribute('k', 'v')\n"
        "snap = t.snapshot()\n"
        "assert [s['name'] for s in snap] == ['a'] and snap[0]['attrs'] == {'step': 1, 'k': 'v'}\n"
        "assert 'jax' not in sys.modules, 'tracing pulled jax in'\n"
        "print('ok')\n"
    )
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr
