"""The readers of `benchmarks/layer_metrics/train_setup.py` on a hand-made start-up log, on
an empty one and on a program that has none, and their `BENCHMARK.json` entries looked up by
name. The seconds here are made up: nothing is timed on the CPU."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks import run
from benchmarks.layer_metrics import train_setup
from kubeflow_tpu.utils import compile_cache

MANIFEST = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
TRAIN_CELLS = ["gpt2m-train-1k", "bertb-train-128", "trinitym-train-8k", "sdar30b-train-4k", "dsv2lite-train-8k"]
#: what the kinds hand the readers (`kinds/train_job.py`, `facts`)
CTX = {"facts": {"step_program": r"^jit__train_step\b"}}


def build(program, trace, lower, backend, cache, region=""):
    return [{"name": "compile.trace", "program": program, "start": 1.0, "seconds": trace, "region": region},
            {"name": "compile.lower", "program": program, "start": 2.0, "seconds": lower, "region": region},
            {"name": "compile.backend", "program": program, "start": 3.0, "seconds": backend, "cache": cache,
             "region": region}]


#: a run on a new seed with the cache warm: the state's program compiles, the reference's and the
#: step are served; a second program whose name only starts like the step's is not the step
LOG = ([{"name": "train.import", "start": 0.0, "seconds": 17.5}]
       + build("jit__threefry_seed", 0.01, 0.02, 0.04, "hit", "train.init_state")
       + [{"name": "compile.trace", "program": "jit_build", "start": 1.0, "seconds": 2.0, "region": "train.init_state"}]
       + build("jit_init_state", 2.5, 1.5, 21.0, "miss", "train.init_state")
       + [{"name": "train.init_state", "start": 20.0, "seconds": 27.5}]
       + build("jit_reference_loss", 1.0, 0.5, 3.0, "hit")
       + build("jit__train_step", 5.0, 2.0, 4.25, "hit")
       + build("jit__train_step_fused", 9.0, 9.0, 9.0, "off")
       + [{"name": "train.init_state", "start": 90.0, "seconds": 0.5}])

EXPECTED = {"import_s.train": 17.5, "init_state_s.train": 28.0, "step_trace_s.train": 7.0,
            "step_backend_s.train": 4.25, "programs_compiled.train": 2.0,
            "build_s.train": 0.07 + 2.0 + 25.0 + 4.5 + 11.25 + 27.0}


@pytest.fixture
def log(monkeypatch):
    """The program's log replaced by a list the test fills."""
    entries: list[dict] = []
    monkeypatch.setattr(compile_cache, "startup_log", lambda: [dict(e) for e in entries])
    return entries


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_on_a_hand_made_log(log, name):
    log.extend(LOG)
    assert train_setup.METRICS[name](CTX) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_an_empty_log_reads_nothing(log, name):
    assert train_setup.METRICS[name](CTX) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_from_before_the_log_reads_nothing_and_does_not_raise(monkeypatch, name):
    """The driver lays these readers over the parent's checkout, whose `compile_cache` has no log."""
    monkeypatch.delattr(compile_cache, "startup_log")
    assert train_setup.METRICS[name](CTX) is None


def test_a_log_without_the_step_reads_the_rest(log):
    """A warm-started step is reloaded as an executable: jax traces and builds nothing for it."""
    log.extend(e for e in LOG if e.get("program") != "jit__train_step")
    assert train_setup.step_trace_s(CTX) is None and train_setup.step_backend_s(CTX) is None
    assert train_setup.import_s(CTX) == 17.5 and train_setup.programs_compiled(CTX) == 2.0


def test_a_seen_seed_on_a_warm_cache_compiles_nothing(log):
    log.extend(dict(e, cache="hit") if "cache" in e else e for e in LOG)
    assert train_setup.programs_compiled(CTX) == 0.0


def test_the_readers_read_the_programs_own_log():
    """Not a copy of it: what the listener writes is what a reader finds."""
    compile_cache.reset_compile_metrics()
    try:
        compile_cache.note_import("train.import", 5.0, 1.25)
        with compile_cache.region("train.init_state"):
            pass
        assert train_setup.import_s(CTX) == 1.25
        assert train_setup.init_state_s(CTX) == pytest.approx(compile_cache.startup_log()[-1]["seconds"])
        assert train_setup.build_s(CTX) is None and train_setup.programs_compiled(CTX) == 0.0
    finally:
        compile_cache.reset_compile_metrics()


@pytest.mark.parametrize("name, unit, source, layer", [
    ("import_s.train", "s", "program_span", "train.trainer"),
    ("init_state_s.train", "s", "program_span", "train.trainer"),
    ("step_trace_s.train", "s", "program_span", "train.trainer"),
    ("step_backend_s.train", "s", "program_span", "utils.compile_cache"),
    ("programs_compiled.train", "count", "program_counter", "utils.compile_cache"),
    ("build_s.train", "s", "program_span", "utils.compile_cache")])
def test_each_new_manifest_entry_by_name_with_its_reader_and_its_cells(name, unit, source, layer):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source, "layer": layer,
                     "moves": "setup_s", "workloads": TRAIN_CELLS}
    assert run.layer_readers()[name] is train_setup.METRICS[name]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(entry["workloads"]) <= cells
    # every cell that lists it reports the end-to-end metric it moves
    (moved,) = [m for m in MANIFEST["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
