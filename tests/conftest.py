"""Test harness config.

Tests run on CPU with 8 virtual devices so every sharding/mesh test exercises
real multi-device SPMD without TPU hardware (the driver separately dry-runs
multi-chip via __graft_entry__.dryrun_multichip). Must run before jax imports.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compile cache for the INFERENCE-ONLY suites (threshold
# zeroed so tiny test programs qualify): those files compile the SAME
# tiny-GPT decode/prefill programs over and over from different tests,
# and content-keyed dedup converts the repeats to cache hits — measured
# on the continuous+generate subset: 276s no-cache vs 232s COLD cache
# (intra-run dedup alone) vs 130s warm, identical pass/fail sets. The
# cache is NOT enabled suite-wide: on this jaxlib, replaying a cached
# donated TRAINING executable into a checkpoint-resumed fit loop
# corrupts the heap (malloc double-linked-list aborts in
# test_checkpoint_resume — reproduced, minimized to fit(resume=True)
# under a zero-threshold cache; inference programs never trip it), so
# training suites stay uncached and the fixture below flips the cache
# per test file. The dir is repo-local and gitignored; entries are keyed
# by HLO content + jax version, so staleness across code changes is
# structural.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(__file__)),
    ".kubeflow_tpu", "test-compile-cache")

#: test files safe and beneficial under the cache: inference-only
#: suites plus training suites that NEVER restore a checkpoint into a
#: fit loop (the minimized corruption vector needs fit(resume=True) —
#: train-without-restore ran clean across full cached suite runs). The
#: compile-cache suites (hotpath/AOT/prof/partitioner) manage cache
#: config or pin compile counts themselves and every checkpoint-using
#: file is deliberately NOT listed. test_decode.py is allowlisted by the
#: same reasoning as test_fleet.py: pure inference (no Checkpointer, no
#: fit loop), recompiling the same tiny-GPT chunk/decode/splice programs
#: across engines.
_COMPILE_CACHE_FILES = frozenset((
    "test_continuous.py",
    "test_gpt_generate.py",
    "test_decode.py",
    "test_soak.py",
    "test_fleet.py",
    "test_slo.py",
    "test_serving.py",
    "test_serving_agent.py",
    "test_serving_grpc.py",
    "test_serving_rollouts.py",
    "test_serving_runtimes.py",
    "test_composed_16dev.py",
    "test_composed_64dev.py",
    "test_composed_realdim.py",
    "test_conv_im2col.py",
    "test_data_shards.py",
    "test_gpt.py",
    "test_gpt_moe.py",
    "test_gpt_pp.py",
    "test_llama.py",
    "test_models_bert.py",
    "test_models_resnet.py",
    "test_oneshot.py",
    "test_parallel_mesh.py",
    "test_pipeline.py",
    "test_pipeline_controlflow.py",
    "test_pipeline_grads.py",
    "test_pipeline_viz.py",
    "test_remat.py",
    "test_ring_attention.py",
    "test_speculative.py",
    "test_vit.py",
))

# A config update wins over whatever JAX_PLATFORMS the caller exported.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    return jax.devices("cpu")


#: the process's startup cache config, restored whenever the cache flips
#: OFF (hardcoding jax's defaults would silently drift across upgrades)
_CACHE_DEFAULTS = {
    "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
    "jax_persistent_cache_min_compile_time_secs":
        jax.config.jax_persistent_cache_min_compile_time_secs,
    "jax_persistent_cache_min_entry_size_bytes":
        jax.config.jax_persistent_cache_min_entry_size_bytes,
}


@pytest.fixture(autouse=True)
def serving_compile_cache(request):
    """Flip the persistent compile cache on for the inference-only files
    in _COMPILE_CACHE_FILES and off elsewhere (see the module comment:
    cached TRAINING executables replayed into a resumed fit corrupt the
    heap on this jaxlib, so the cache is file-scoped, not global).
    reset_cache() drops jax's latched cache object on every flip — the
    next compile re-initializes from the current config (the PR-10
    latch lesson; utils/compile_cache.enable_persistent_cache does the
    same for tests that point the cache at their own dirs)."""
    try:
        fname = os.path.basename(str(request.node.path))
    except Exception:
        fname = ""
    want = (fname in _COMPILE_CACHE_FILES
            and not os.environ.get("KFTPU_TEST_NO_COMPILE_CACHE"))
    # compare against the LIVE config, not our own bookkeeping: a test
    # that re-points the cache at its own dir (the AOT/hotpath pattern)
    # must not leave later allowlisted tests writing into its tmp dir,
    # and a dir some test chose for itself is left alone
    cur = jax.config.jax_compilation_cache_dir
    if want and cur != _COMPILE_CACHE_DIR:
        from jax.experimental.compilation_cache import (
            compilation_cache as _jax_cc,
        )

        jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _jax_cc.reset_cache()
    elif not want and cur == _COMPILE_CACHE_DIR:
        from jax.experimental.compilation_cache import (
            compilation_cache as _jax_cc,
        )

        for k, v in _CACHE_DEFAULTS.items():
            jax.config.update(k, v)
        _jax_cc.reset_cache()
    yield


@pytest.fixture(autouse=True)
def lockcheck_armed(request):
    """Every chaos/health drill runs with the runtime lock-order detector
    live (kubeflow_tpu/analysis/lockcheck.py, docs/analysis.md): seeded
    fault injection exercises the threaded control plane's nastiest
    interleavings, so this is exactly where a lock-order inversion (a
    potential deadlock) or a wedged-long hold would first show. Zero
    cycles is an acceptance contract, not a nice-to-have. The fleet
    drills join the set: N engine tickers + router callbacks + one shared
    paged-KV pool lock is exactly the nesting the detector exists for.
    The hotpath drills too: the AsyncLoader's producer/consumer condition
    pair is a brand-new cross-thread lock site on the trainer hot path.
    Scoped by marker so the rest of the suite runs with the detector's
    production default (disabled passthrough)."""
    if not (request.node.get_closest_marker("chaos")
            or request.node.get_closest_marker("health")
            or request.node.get_closest_marker("fleet")
            or request.node.get_closest_marker("hotpath")
            or request.node.get_closest_marker("partition")
            or request.node.get_closest_marker("slo")
            or request.node.get_closest_marker("soak")
            or request.node.get_closest_marker("decode")
            or request.node.get_closest_marker("pods")
            or request.node.get_closest_marker("sched")):
        yield
        return
    from kubeflow_tpu.analysis import lockcheck

    # Pre-armed (KFTPU_LOCKCHECK=1 full-suite run): ACCUMULATE — neither
    # reset() (it would wipe findings recorded by earlier tests before the
    # at-exit dump sees them) nor disable() (the user armed the whole run).
    # The per-drill assert then covers the whole graph so far, which is the
    # contract the env var asked for.
    was_enabled = lockcheck.is_enabled()
    if not was_enabled:
        lockcheck.reset()
        lockcheck.enable()
    try:
        yield
    finally:
        rep = lockcheck.report()
        if not was_enabled:
            lockcheck.disable()
        assert not rep["cycles"], lockcheck.format_report(rep)


class ProtoLog:
    """Handle the `protolog` fixture yields: the armed event-log path
    plus the conformance check the drill runs on what it recorded."""

    def __init__(self, path: str):
        self.path = str(path)

    def events(self) -> list:
        from kubeflow_tpu.analysis.protocheck import read_log
        return read_log(self.path)

    def counts(self) -> dict:
        """Replay the recorded log through every protocol trace
        acceptor; raises TraceRejected on an unacceptable run."""
        from kubeflow_tpu.analysis.protocheck import check_trace
        return check_trace(self.events())


@pytest.fixture
def protolog(tmp_path, monkeypatch):
    """Arm the protocheck event log (kubeflow_tpu/analysis/protocheck/
    eventlog.py) for one drill. Exported via the environment so worker
    SUBPROCESSES inherit it — the recorded trace interleaves both sides
    of the wire in file-append order. At teardown the trace is replayed
    through the model trace acceptors: a drill that passes while its
    trace is rejected means the protocol models drifted from the
    implementation (or the implementation broke in a way the drill
    missed) — either way a finding (docs/analysis.md "Protocol model
    checking")."""
    from kubeflow_tpu.utils.envvars import ENV_PROTOLOG

    path = tmp_path / "protocol-events.jsonl"
    monkeypatch.setenv(ENV_PROTOLOG, str(path))
    log = ProtoLog(path)
    yield log
    if path.exists():
        log.counts()  # raises TraceRejected on a non-conformant run
