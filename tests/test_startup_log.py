"""The start-up log of `utils/compile_cache.py`: what jax tells of every program it traces,
lowers, compiles or loads from the persistent cache, kept in a bounded process-global log,
in the `kftpu_train_compile_*` counters and, where a Tracer is armed, as `compile.*` spans
under the span that caused them; the trainer's regions in the same log, and `built` on the
`train.enqueue` that had to build its program. Toy programs on the CPU: counts, names and
parents, never a time."""

from __future__ import annotations

import json
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from kubeflow_tpu import tracing
from kubeflow_tpu.tracing import Tracer
from kubeflow_tpu.utils import compile_cache as cc

ROOT = Path(__file__).resolve().parents[1]
PHASES = ["compile.trace", "compile.lower", "compile.backend"]


@pytest.fixture(autouse=True)
def fresh_log():
    """The log is the process's: other files' builds are in it until it is emptied."""
    cc.install_compile_listener()
    cc.reset_compile_metrics()
    yield
    cc.reset_compile_metrics()


@pytest.fixture
def jax_cache_config():
    """Tests that re-point or switch off jax's persistent cache leave it as they found it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jax_cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    jax_cc.reset_cache()


@pytest.fixture
def armed():
    tracer = Tracer()
    tracing.set_tracer(tracer)
    yield tracer
    tracing.set_tracer(None)


def new_program(name: str, scale: float = 3.0):
    """A jitted function jax has not seen: a new function object is traced anew."""
    import jax

    def f(x):
        return (x * scale + 1).sum()

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def toy_trainer():
    from kubeflow_tpu.models import MnistMLP
    from kubeflow_tpu.train import Trainer, TrainerConfig

    # eight rows: tests/conftest.py makes eight virtual devices and the batch spans them
    trainer = Trainer(MnistMLP(hidden=(8,)), TrainerConfig(batch_size=8, seed=1))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=16).astype(np.int32)
    return trainer, x, y


def entries(name: str, log=None) -> list[dict]:
    return [e for e in (cc.startup_log() if log is None else log) if e["name"] == name]


def test_a_jitted_function_leaves_its_three_phases_under_one_program_name():
    import jax.numpy as jnp

    x = jnp.arange(4.0)
    cc.reset_compile_metrics()  # `arange` is a program too
    new_program("probe_phases")(x)
    log = cc.startup_log()
    assert [e["name"] for e in log] == PHASES
    assert {e["program"] for e in log} == {"jit_probe_phases"}
    for e in log:
        assert e["seconds"] >= 0.0 and e["start"] > 0.0 and e["region"] == ""
    # the Span convention: start is the wall clock at the block's entry, so phases follow on
    assert log[0]["start"] <= log[1]["start"] <= log[2]["start"]
    assert "cache" not in log[0] and "cache" not in log[1] and log[2]["cache"] in ("off", "miss", "hit")
    assert cc.programs_built() == 1


@pytest.mark.parametrize("told, program", [
    ("_train_step", "jit__train_step"), ("jit(_train_step)", "jit__train_step"),
    ("<lambda>", "jit__lambda"), ("jit(<lambda>)", "jit__lambda"),
    ("init_state", "jit_init_state"), ("pmap(step)", "pmap_step")])
def test_a_trace_and_a_build_of_one_function_are_one_program(told, program):
    """jax names a trace by the function and a lowering or a build by the module; the log
    spells both as the HLO module and the device trace do."""
    assert cc.program_name(told) == program


def test_the_persistent_cache_reads_miss_then_hit_with_its_retrieval_seconds(tmp_path, jax_cache_config):
    import jax
    import jax.numpy as jnp

    cc.enable_persistent_cache(tmp_path)
    x = jnp.arange(8.0)
    f = new_program("probe_cached", scale=7.0)
    cc.reset_compile_metrics()
    f(x)
    (cold,) = entries("compile.backend")
    assert cold["cache"] == "miss" and "retrieval_s" not in cold
    jax.clear_caches()  # the simulated restart: the program is traced and lowered again
    f(x)
    log = cc.startup_log()
    assert [e["name"] for e in log] == PHASES * 2
    warm = entries("compile.backend", log)[1]
    assert warm["cache"] == "hit" and warm["retrieval_s"] > 0.0
    assert warm["retrieval_s"] <= warm["seconds"]  # the retrieval lies inside the backend block
    counts = cc.compile_counts()
    assert counts["cache_hits_total"] == 1 and counts["backend_misses_total"] == 1
    assert counts["requests_total"] == 2
    assert counts["cache_retrieval_seconds_total"] == pytest.approx(warm["retrieval_s"])


def test_a_build_that_consulted_no_cache_reads_off(jax_cache_config):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as jax_cc

    jax.config.update("jax_enable_compilation_cache", False)
    jax_cc.reset_cache()
    new_program("probe_uncached")(jnp.arange(4.0))
    assert entries("compile.backend")[-1]["cache"] == "off"
    assert cc.compile_counts()["requests_total"] == 0


def test_nested_jits_and_lowering_rules_are_part_of_the_outer_blocks_seconds():
    """A jit called while its caller is traced, and a lowering rule that traces jnp code
    (threefry's), fire their own events: hundreds to a model. The log keeps the outermost."""
    import jax
    import jax.numpy as jnp

    inner = new_program("probe_inner")

    def outer(key, x):
        return inner(x) + jax.random.normal(key, x.shape).sum() + jnp.where(x > 0, x, 0.0).sum()

    key, x = jax.random.PRNGKey(0), jnp.arange(4.0)
    cc.reset_compile_metrics()
    jax.jit(outer)(key, x)
    assert [(e["name"], e["program"]) for e in cc.startup_log()] == [(p, "jit_outer") for p in PHASES]


def test_the_log_is_bounded_and_counts_what_it_drops():
    over = 5
    for i in range(cc.STARTUP_LOG_CAPACITY + over):
        cc.note_import(f"probe.{i}", float(i), 0.0)
    log = cc.startup_log()
    assert len(log) == cc.STARTUP_LOG_CAPACITY and cc.startup_log_dropped() == over
    assert log[0]["name"] == f"probe.{over}" and log[-1]["name"] == f"probe.{cc.STARTUP_LOG_CAPACITY + over - 1}"


def test_the_counters_sum_the_log_by_phase():
    import jax.numpy as jnp

    x = jnp.arange(4.0)
    cc.reset_compile_metrics()
    for i in range(3):
        new_program(f"probe_sum_{i}", scale=float(i))(x)
    counts = cc.compile_counts()
    for phase in ("trace", "lower", "backend"):
        assert counts[f"{phase}_seconds_total"] == pytest.approx(
            sum(e["seconds"] for e in entries(f"compile.{phase}")))
    assert cc.programs_built() == 3


def test_reset_compile_metrics_clears_the_log_and_the_counters():
    import jax.numpy as jnp

    new_program("probe_reset")(jnp.arange(4.0))
    cc.note_import("probe.import", 1.0, 2.0)
    assert cc.startup_log() and cc.programs_built() and cc.compile_counts()["backend_seconds_total"] > 0
    cc.reset_compile_metrics()
    assert cc.startup_log() == [] and cc.programs_built() == 0 and cc.startup_log_dropped() == 0
    counts = cc.compile_counts()
    assert set(counts.values()) == {0} and isinstance(counts["trace_seconds_total"], float)
    assert isinstance(counts["requests_total"], int)


def test_train_import_is_the_first_entry_of_a_process():
    code = ("import json, sys, kubeflow_tpu.train\n"
            "from kubeflow_tpu.utils import compile_cache as cc\n"
            "print(json.dumps([cc.startup_log()[0], sorted(m for m in sys.modules if m.startswith(('orbax', 'google.api_core')))]))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                         timeout=300, check=True).stdout
    first, heavy = json.loads(out.strip().splitlines()[-1])
    assert first["name"] == "train.import" and first["seconds"] > 0.0
    assert set(first) == {"name", "start", "seconds"}
    # what only a job that checkpoints needs is imported where a checkpoint is made (train/checkpoint.py `_ocp`)
    assert heavy == []


def test_init_state_leaves_one_region_whose_builds_carry_it():
    trainer, x, _ = toy_trainer()
    cc.reset_compile_metrics()
    trainer.init_state(x[:8])
    log = cc.startup_log()
    (region,) = entries("train.init_state", log)
    assert log[-1] == region  # written on exit, after what it holds
    builds = [e for e in log if e["name"] in PHASES]
    assert builds and {e["region"] for e in builds} == {"train.init_state"}
    # the state's program has a name of its own, not `<lambda>`
    assert [e["name"] for e in builds if e["program"] == "jit_init_state"] == PHASES
    for e in builds:
        assert region["start"] <= e["start"] and e["start"] + e["seconds"] <= region["start"] + region["seconds"] + 1e-3
    # what the next step builds is outside the region
    state = trainer.init_state(x[:8])
    trainer.train_step(state, (x[:8], np.zeros((8,), np.int32)))
    assert {e["region"] for e in cc.startup_log() if e.get("program") == "jit__train_step"} == {""}


def test_an_armed_tracer_receives_the_builds_under_the_enclosing_init_state(armed, tmp_path):
    trainer, x, _ = toy_trainer()
    trainer.init_state(x[:8])
    spans = armed.snapshot()
    (region,) = [s for s in spans if s["name"] == "train.init_state"]
    children = [s for s in spans if s["name"] in PHASES]
    assert {s["name"] for s in children} == set(PHASES)
    for s in children:
        assert s["parent"] == region["span"] and s["trace"] == region["trace"]
        assert s["attrs"]["program"].startswith("jit_")
        assert ("cache" in s["attrs"]) == (s["name"] == "compile.backend")
    # one span an entry of the log, the region's live span apart
    assert len(children) == len([e for e in cc.startup_log() if e["name"] in PHASES])
    # and the ring written out is the restart's story: the region with its children
    events = json.loads(Path(tracing.write_chrome_trace(str(tmp_path / "t.json"), spans)).read_text())["traceEvents"]
    by_id = {e["args"]["span_id"]: e for e in events if e["ph"] == "X"}
    built = [e for e in by_id.values() if e["name"] == "compile.backend" and e["args"]["program"] == "jit_init_state"]
    assert built and by_id[built[0]["args"]["parent_id"]]["name"] == "train.init_state"


def test_a_noop_or_disarmed_tracer_receives_nothing_and_the_log_holds_them_all_the_same():
    import jax.numpy as jnp

    assert not tracing.get_tracer().enabled
    x = jnp.arange(4.0)
    cc.reset_compile_metrics()
    new_program("probe_noop")(x)
    assert tracing.get_tracer().snapshot() == []
    disarmed = Tracer()
    disarmed.armed = False
    tracing.set_tracer(disarmed)
    try:
        new_program("probe_disarmed")(x)
        with cc.region("probe.region"):
            pass
    finally:
        tracing.set_tracer(None)
    assert disarmed.snapshot() == []
    assert [e["name"] for e in cc.startup_log()] == PHASES * 2 + ["probe.region"]


def test_train_enqueue_carries_built_on_the_step_that_built_and_on_no_other(armed):
    trainer, x, y = toy_trainer()
    state = trainer.init_state(x[:8])
    for _ in range(3):
        state, _ = trainer.train_step(state, (x[:8], y[:8]))
    state, _ = trainer.train_step(state, (x, y))  # sixteen rows: another program
    state, _ = trainer.train_step(state, (x, y))
    enqueues = [s for s in armed.snapshot() if s["name"] == "train.enqueue"]
    assert ["built" in s["attrs"] for s in enqueues] == [True, False, False, True, False]
    assert all(s["attrs"]["path"] == "jit" for s in enqueues)
    first = enqueues[0]
    assert first["attrs"]["built"] >= 1
    # what it built hangs under it
    under = [s for s in armed.snapshot() if s["parent"] == first["span"] and s["name"] == "compile.backend"]
    assert len(under) == first["attrs"]["built"]
    assert "jit__train_step" in {s["attrs"]["program"] for s in under}


class CountingLock:
    """`threading.Lock` that counts how often it was taken."""

    def __init__(self):
        self.lock, self.taken = threading.Lock(), 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_step_that_builds_nothing_takes_no_lock_and_appends_nothing(monkeypatch):
    trainer, x, y = toy_trainer()
    state = trainer.init_state(x[:8])
    state, _ = trainer.train_step(state, (x[:8], y[:8]))
    assert not tracing.get_tracer().enabled
    lock = CountingLock()
    monkeypatch.setattr(cc, "_MU", lock)
    before, built = len(cc.startup_log()), cc.programs_built()
    taken = lock.taken  # reading the log takes it
    for _ in range(100):
        state, metrics = trainer.train_step(state, (x[:8], y[:8]))
    assert lock.taken == taken and cc.programs_built() == built
    assert len(cc.startup_log()) == before
    assert np.isfinite(float(metrics["loss"]))


def test_a_step_that_builds_inside_a_profiler_session_says_so_on_its_annotation(tmp_path):
    """`built` reaches the open `train.enqueue` annotation as a stat: a recompile in a traced
    window is on the device's clock, on the span over the gap it caused."""
    import jax

    from tests.test_tracing_profiler import host_events

    trainer, x, y = toy_trainer()
    state = trainer.init_state(x[:8])
    state, _ = trainer.train_step(state, (x[:8], y[:8]))  # built outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        state, _ = trainer.train_step(state, (x[:8], y[:8]))
        state, metrics = trainer.train_step(state, (x, y))  # sixteen rows: built here
        jax.block_until_ready(metrics)
    finally:
        jax.profiler.stop_trace()
    first, second = sorted(host_events(tmp_path)["train.enqueue"])
    assert "built" not in first[2] and int(second[2]["built"]) >= 1


def test_warm_start_reports_its_seconds_by_phase(tmp_path, jax_cache_config):
    trainer, x, y = toy_trainer()
    trainer.init_state(x[:8])
    info = trainer.warm_start(x[:8], y[:8], cache_dir=str(tmp_path))
    assert info["enabled"] and info["compiled"] == "train_step" and info["backend_misses"] >= 1
    step = [e for e in cc.startup_log() if e.get("program") == "jit__train_step"]
    assert [e["name"] for e in step] == PHASES
    for phase in ("trace", "lower", "backend"):
        assert info[f"{phase}_s"] >= next(e["seconds"] for e in step if e["name"] == f"compile.{phase}") > 0.0
    assert info["cache_hits"] == 0


def test_a_job_traced_through_the_pod_env_lists_what_it_built_under_what_caused_it(
        tmp_path, jax_cache_config, monkeypatch):
    """docs/observability.md's recipe: `fit` installs the tracer of `KFTPU_TRACE_DIR` before
    `init_state`, and the flushed ring names each build, the cache's word and its cause."""
    from kubeflow_tpu.models import MnistMLP
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import Dataset
    from kubeflow_tpu.utils.envvars import ENV_TRACE_DIR

    rng = np.random.default_rng(3)
    x = rng.standard_normal((32, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=32).astype(np.int32)
    trainer = Trainer(MnistMLP(hidden=(8,)), TrainerConfig(
        batch_size=16, steps=2, log_every_steps=10**9, compile_cache_dir=str(tmp_path / "cc")))
    monkeypatch.setenv(ENV_TRACE_DIR, str(tmp_path / "traces"))
    try:
        trainer.fit(Dataset(x, y, x[:16], y[:16], num_classes=10))
        path = tracing.flush()
    finally:
        tracing.set_tracer(None)
    spans = tracing.load_chrome_trace(path)
    by_id = {s["span"]: s for s in spans}
    cause = {s["attrs"]["program"]: by_id[s["parent"]]["name"] for s in spans if s["name"] == "compile.backend"}
    assert cause["jit_init_state"] == "train.init_state" and cause["jit__train_step"] == "train.compile"
    assert {s["attrs"]["cache"] for s in spans if s["name"] == "compile.backend"} == {"miss"}
    (compile_span,) = [s for s in spans if s["name"] == "train.compile"]
    assert compile_span["attrs"]["compiled"] == "train_step" and compile_span["attrs"]["backend_s"] > 0
    # both regions are in the log too, in the order they ran
    regions = [e["name"] for e in cc.startup_log() if e["name"] in ("train.init_state", "train.compile")]
    assert regions == ["train.init_state", "train.compile"]
