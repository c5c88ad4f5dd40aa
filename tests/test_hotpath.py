"""Training hot path (ISSUE 10, docs/perf.md "MFU hunt"): restart-warm
compile cache + async host input pipeline.

Covers the edge contracts the perf machinery rides on:

  - AsyncLoader: order/content equivalence, producer-exception re-raise on
    the consuming thread, early-consumer-exit thread join (no daemon
    leak), bounded-queue backpressure — all under KFTPU_LOCKCHECK=1 via
    the conftest hotpath arming (zero lock-order cycles is an acceptance
    contract);
  - utils/compile_cache: key stability, executable save/load round trip,
    corrupt-artifact degradation;
  - Trainer.warm_start: cold compiles + serializes, a simulated gang
    restart reloads with ZERO backend compilations, numerics identical,
    the train.compile span lands in the worker trace;
  - profiling/analytics: the data_wait/data_assemble split and the
    restart-overhead compile/restore/schedule split stay sum-exact.
"""

import os
import threading
import time

import numpy as np
import pytest

from kubeflow_tpu.train.data import (
    AsyncLoader,
    loader_metrics_snapshot,
)

pytestmark = pytest.mark.hotpath


# --------------------------------------------------------------- AsyncLoader


class TestAsyncLoader:
    def test_order_and_content_match_inline(self):
        """The thread moves work, never semantics: results are exactly
        transform(x) for x in src, in order."""
        src = list(range(20))
        with AsyncLoader(src, transform=lambda i: i * i, size=2) as it:
            assert list(it) == [i * i for i in src]

    def test_exhaustion_joins_thread(self):
        loader = AsyncLoader(range(4), transform=lambda i: i, size=2)
        assert list(loader) == [0, 1, 2, 3]
        loader.close()
        assert not loader._thread.is_alive()

    def test_producer_exception_reraises_on_consumer(self):
        """A loader-thread exception surfaces on the CONSUMING thread at
        the position it occurred — batches before it still arrive."""
        def boom(i):
            if i == 2:
                raise ValueError("assembly failed at 2")
            return i

        loader = AsyncLoader(range(5), transform=boom, size=2)
        try:
            got = []
            with pytest.raises(ValueError, match="assembly failed at 2"):
                for v in loader:
                    got.append(v)
            assert got == [0, 1]
            main_tid = threading.get_ident()
            assert loader._thread.ident != main_tid  # really cross-thread
        finally:
            loader.close()
        assert not loader._thread.is_alive()
        assert loader_metrics_snapshot()["errors_total"] >= 1

    def test_early_consumer_exit_joins_cleanly(self):
        """A consumer that stops after 2 of 1000 batches must leave no
        running thread — even with the producer blocked on a full queue
        (the epoch-abandonment path in Trainer._fit_loop)."""
        slow = AsyncLoader(range(1000), transform=lambda i: i, size=2)
        got = [next(slow), next(slow)]
        assert got == [0, 1]
        slow.close()
        assert not slow._thread.is_alive()
        # close is idempotent and safe after exhaustion
        slow.close()
        assert loader_metrics_snapshot()["live_loaders"] == 0

    def test_next_after_close_terminates(self):
        """A straggling next() after close() must stop — the buffered
        backlog is dropped, never served as stale pre-close batches, and
        the consumer never blocks on the dead producer."""
        loader = AsyncLoader(range(100), transform=lambda i: i, size=2)
        next(loader)
        loader.close()
        t0 = time.monotonic()
        rest = list(loader)
        assert time.monotonic() - t0 < 5.0
        assert rest == []

    def test_natural_exhaustion_clears_live_gauge(self):
        """A loader drained to exhaustion WITHOUT close() must not read
        as a thread leak — the producer's own exit clears the gauge."""
        from kubeflow_tpu.utils.retry import poll_until

        loader = AsyncLoader(range(3), transform=lambda i: i, size=2)
        assert list(loader) == [0, 1, 2]
        # no close(): the producer thread exits on its own
        poll_until(
            lambda: loader_metrics_snapshot()["live_loaders"] == 0 or None,
            timeout_s=10.0, describe="producer exit clears live gauge",
        )

    def test_bounded_queue_backpressure(self):
        """The producer never runs more than `size` items ahead of the
        consumer — unbounded readahead would hide memory blowups."""
        produced = []

        def track(i):
            produced.append(i)
            return i

        loader = AsyncLoader(range(100), transform=track, size=3)
        try:
            next(loader)
            time.sleep(0.2)  # give the producer every chance to run away
            # 1 consumed + 3 buffered + 1 in flight
            assert len(produced) <= 5
        finally:
            loader.close()

    def test_stats_split_wait_vs_assemble(self):
        """pop_stats carries the queue-wait vs host-assemble split the
        trainer stamps on train.data_load spans."""
        def slow_fetch(i):
            time.sleep(0.01)
            return i

        loader = AsyncLoader(range(3), transform=slow_fetch, size=2)
        try:
            next(loader)
            st = loader.pop_stats()
            assert st["assemble_s"] >= 0.009  # the producer-side work
            assert st["wait_s"] >= 0.0
        finally:
            loader.close()


# ------------------------------------------------------------- compile cache


class TestCompileCache:
    def test_executable_key_covers_inputs(self):
        from kubeflow_tpu.utils import compile_cache as cc

        k1 = cc.executable_key(model="m", batch=((4, 8), "float32"))
        k2 = cc.executable_key(model="m", batch=((4, 8), "float32"))
        k3 = cc.executable_key(model="m", batch=((8, 8), "float32"))
        k4 = cc.executable_key(model="m2", batch=((4, 8), "float32"))
        assert k1 == k2
        assert len({k1, k3, k4}) == 3

    def test_save_load_roundtrip_skips_compile(self, tmp_path):
        """A reloaded executable runs without a single backend compile
        request — the restart-warm primitive."""
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.utils import compile_cache as cc

        f = jax.jit(lambda a: (a * 2 + 1).sum())
        x = jnp.arange(16, dtype=jnp.float32)
        compiled = f.lower(x).compile()
        key = cc.executable_key(probe="roundtrip")
        # a ONE-device program among the suite's eight devices: the loader
        # is told the program's own devices, or jax binds it to all eight
        # and the call fails ("expected 8 shards") — a one-chip job on a
        # four-chip host at its first warm restart
        devices = list(x.sharding.device_set)
        assert len(devices) == 1 and len(jax.devices()) == 8
        assert cc.load_executable(tmp_path, key, devices) is None  # absent
        assert cc.save_executable(tmp_path, key, compiled) is not None
        before = cc.compile_counts()
        loaded = cc.load_executable(tmp_path, key, devices)
        assert loaded is not None
        assert float(loaded(x)) == float(f(x))
        after = cc.compile_counts()
        assert after["backend_misses_total"] == before["backend_misses_total"]
        assert after["executable_reloads_total"] \
            == before["executable_reloads_total"] + 1

    def test_executable_dir_lru_eviction(self, tmp_path):
        """The shared cache dir survives restarts and nothing else deletes
        from it — the post-save sweep must bound it, evicting oldest-mtime
        first and never the entry just saved."""
        import os

        from kubeflow_tpu.utils import compile_cache as cc

        exec_dir = tmp_path / "executables"
        exec_dir.mkdir()
        for i, age in enumerate((300, 200, 100)):
            p = exec_dir / f"old{i}{cc.EXECUTABLE_SUFFIX}"
            p.write_bytes(b"x" * 400)
            st = p.stat()
            os.utime(p, (st.st_atime - age, st.st_mtime - age))
        newest = exec_dir / f"new{cc.EXECUTABLE_SUFFIX}"
        newest.write_bytes(b"x" * 400)
        cc._evict_lru(exec_dir, keep=newest, max_bytes=900)
        names = sorted(p.name for p in exec_dir.iterdir())
        assert newest.name in names
        assert f"old0{cc.EXECUTABLE_SUFFIX}" not in names  # oldest went
        assert sum(p.stat().st_size for p in exec_dir.iterdir()) <= 900

    def test_corrupt_artifact_degrades_to_none(self, tmp_path):
        import jax

        from kubeflow_tpu.utils import compile_cache as cc

        key = cc.executable_key(probe="corrupt")
        path = cc.executable_path(tmp_path, key)
        path.parent.mkdir(parents=True)
        path.write_bytes(b"torn write of a dying pod")
        assert cc.load_executable(tmp_path, key, jax.devices()[:1]) is None
        assert not path.exists()  # quarantined by removal, not retried

    def test_one_device_mesh_among_eight_reloads_and_steps(
            self, tmp_path, tiny_data):
        """The trainer's own warm restart on a mesh smaller than the
        backend: the reloaded step executable must be callable."""
        import jax

        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.parallel import MeshConfig, build_mesh
        from kubeflow_tpu.train import Trainer, TrainerConfig

        x, y = tiny_data

        def trainer():
            return Trainer(
                MnistMLP(hidden=(8,)),
                TrainerConfig(batch_size=16, log_every_steps=10**9,
                              compile_cache_dir=str(tmp_path)),
                mesh=build_mesh(MeshConfig(), jax.devices()[:1]))

        t1 = trainer()
        assert "train_step" in t1.warm_start(x[:16], y[:16])["compiled"]
        jax.clear_caches()
        t2 = trainer()
        state = t2.init_state(x[:16])
        info = t2.warm_start(x[:16], y[:16])
        assert info["reloaded"] == "train_step", info
        state, m = t2.train_step(state, (x[:16], y[:16]))
        assert np.isfinite(float(m["loss"]))
        assert t2._step_compiled is not None  # the reload was NOT dropped

    def test_cache_dir_resolution(self, tmp_path, monkeypatch):
        """One resolver: JAX_COMPILATION_CACHE_DIR wins over everything;
        without it an explicit value, then the pod env contract, then —
        for the callers that always cache — an absolute path inside the
        checkout that does not move with the cwd."""
        import subprocess
        import sys

        from kubeflow_tpu.utils import compile_cache as cc
        from kubeflow_tpu.utils.envvars import ENV_COMPILE_CACHE_DIR

        monkeypatch.delenv(cc.ENV_JAX_CACHE_DIR, raising=False)
        monkeypatch.delenv(ENV_COMPILE_CACHE_DIR, raising=False)
        assert cc.resolve_cache_dir() == ""  # standalone trainer: off
        assert cc.resolve_cache_dir("explicit") == "explicit"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        default = os.path.join(repo, ".kubeflow_tpu", "compile-cache")
        assert cc.resolve_cache_dir(default=True) == default
        assert os.path.isabs(cc.DEFAULT_CACHE_DIR)
        monkeypatch.setenv(ENV_COMPILE_CACHE_DIR, "/pod/contract")
        assert cc.resolve_cache_dir() == "/pod/contract"
        assert cc.resolve_cache_dir("explicit") == "explicit"
        monkeypatch.setenv(cc.ENV_JAX_CACHE_DIR, "/from/jax/env")
        assert cc.resolve_cache_dir("explicit", default=True) \
            == "/from/jax/env"
        # identical from two different working directories
        code = ("from kubeflow_tpu.utils.compile_cache import "
                "resolve_cache_dir; print(resolve_cache_dir(default=True))")
        env = {k: v for k, v in os.environ.items()
               if k not in (cc.ENV_JAX_CACHE_DIR, ENV_COMPILE_CACHE_DIR)}
        env["PYTHONPATH"] = repo
        seen = {
            subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True,
                           check=True).stdout.strip()
            for cwd in (repo, str(tmp_path))}
        assert seen == {default}

    def test_env_dir_is_never_overridden_in_code(self, tmp_path,
                                                 monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, enabling the cache zeroes
        the thresholds but leaves jax_compilation_cache_dir alone; without
        it the directory is set."""
        import jax

        from kubeflow_tpu.utils import compile_cache as cc

        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real_update(k, v))[1])
        monkeypatch.setenv(cc.ENV_JAX_CACHE_DIR, str(tmp_path / "env"))
        cc.enable_persistent_cache(cc.resolve_cache_dir("elsewhere"))
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_entry_size_bytes" in updates
        updates.clear()
        monkeypatch.delenv(cc.ENV_JAX_CACHE_DIR)
        cc.enable_persistent_cache(str(tmp_path / "explicit"))
        assert "jax_compilation_cache_dir" in updates
        assert jax.config.jax_compilation_cache_dir \
            == str(tmp_path / "explicit")

    def test_jobcontroller_leaves_injection_to_the_environment(
            self, tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, pods inherit it with the
        rest of the environment: no KFTPU_COMPILE_CACHE_DIR is injected."""
        from kubeflow_tpu.controller.fakecluster import FakeCluster
        from kubeflow_tpu.controller.jobcontroller import JobController
        from kubeflow_tpu.utils import compile_cache as cc
        from kubeflow_tpu.utils.envvars import ENV_COMPILE_CACHE_DIR
        from tests.test_tracing import make_job

        monkeypatch.setenv(cc.ENV_JAX_CACHE_DIR, str(tmp_path / "env"))
        cluster = FakeCluster()
        ctrl = JobController(cluster)
        assert ctrl.compile_cache_dir == str(tmp_path / "env")
        job = make_job(tmp_path, "envjob", "pass", replicas=1)
        cluster.create("jobs", job)
        ctrl.reconcile(f"{job.metadata.namespace}/{job.metadata.name}")
        (pod,) = cluster.list("pods")
        assert ENV_COMPILE_CACHE_DIR not in pod.env

    def test_jobcontroller_injects_cache_dir(self, tmp_path):
        """The pod env contract carries KFTPU_COMPILE_CACHE_DIR, and the
        path is NOT per-incarnation — surviving restarts is the point."""
        from kubeflow_tpu.controller.fakecluster import FakeCluster
        from kubeflow_tpu.controller.jobcontroller import JobController
        from kubeflow_tpu.utils.envvars import ENV_COMPILE_CACHE_DIR
        from tests.test_tracing import make_job

        cluster = FakeCluster()
        ctrl = JobController(cluster,
                             compile_cache_dir=str(tmp_path / "cc"))
        job = make_job(tmp_path, "warmjob", "pass", replicas=2)
        cluster.create("jobs", job)
        ctrl.reconcile(f"{job.metadata.namespace}/{job.metadata.name}")
        pods = cluster.list("pods")
        assert len(pods) == 2
        for p in pods:
            assert p.env[ENV_COMPILE_CACHE_DIR] == str(tmp_path / "cc")


# ------------------------------------------------------- trainer warm start


@pytest.fixture(scope="module")
def tiny_data():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((64, 32)).astype(np.float32)
    y = rng.integers(0, 10, size=64).astype(np.int32)
    return x, y


@pytest.fixture(autouse=True)
def _restore_compile_cache_config():
    """warm_start flips the PROCESS-GLOBAL jax compilation-cache config;
    later tests in a shared tier-1 process must see the prior state."""
    import jax

    saved = {
        k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


class TestTrainerWarmStart:
    def _trainer(self, cache_dir):
        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.train import Trainer, TrainerConfig

        return Trainer(
            MnistMLP(hidden=(8,)),
            TrainerConfig(batch_size=16, log_every_steps=10**9,
                          compile_cache_dir=str(cache_dir)),
        )

    def test_restart_reloads_with_zero_backend_compiles(
            self, tmp_path, tiny_data):
        import jax

        from kubeflow_tpu.utils import compile_cache as cc

        x, y = tiny_data
        saved = jax.config.jax_compilation_cache_dir
        try:
            t1 = self._trainer(tmp_path)
            s1 = t1.init_state(x[:16])
            info1 = t1.warm_start(x[:16], y[:16])
            assert info1["enabled"] and "train_step" in info1["compiled"]
            # the cold incarnation counted misses: the counter and the
            # cache are live, so the warm zero below means something
            assert info1["backend_misses"] > 0
            s1, m1 = t1.train_step(s1, (x[:16], y[:16]))

            jax.clear_caches()  # the simulated gang restart
            before = cc.compile_counts()
            t2 = self._trainer(tmp_path)
            info2 = t2.warm_start(x[:16], y[:16])
            assert "train_step" in info2["reloaded"]
            assert info2["backend_misses"] == 0
            s2 = t2.init_state(x[:16])
            s2, m2 = t2.train_step(s2, (x[:16], y[:16]))
            after = cc.compile_counts()
            # the warm TRAIN STEP itself compiled nothing; init_state's
            # build rides the persistent cache (requests, zero misses)
            assert float(m1["loss"]) == pytest.approx(float(m2["loss"]))
            assert after["executable_reloads_total"] \
                > before["executable_reloads_total"]
        finally:
            jax.config.update("jax_compilation_cache_dir", saved)

    def test_fit_emits_train_compile_span(self, tmp_path, tiny_data):
        """fit() with a cache dir wraps warm_start in a train.compile
        span — the phase profiling/analytics splits restart overhead by."""
        from kubeflow_tpu.train.data import Dataset
        from kubeflow_tpu.tracing import Tracer, set_tracer

        x, y = tiny_data
        ds = Dataset(x, y, x[:16], y[:16], num_classes=10)
        tracer = Tracer(capacity=512)
        set_tracer(tracer)
        try:
            t = self._trainer(tmp_path / "cc")
            t.config.steps = 2
            t.fit(ds)
        finally:
            set_tracer(None)
        spans = [s for s in tracer.snapshot()
                 if s["name"] == "train.compile"]
        assert len(spans) == 1
        assert spans[0]["attrs"]["enabled"] is True
        assert spans[0]["attrs"]["backend_requests"] >= 0
        # the data_load spans carry the async split attrs
        dl = [s for s in tracer.snapshot()
              if s["name"] == "train.data_load"]
        assert dl and all("wait_s" in s["attrs"] for s in dl[:-1])

    def test_fit_async_loader_leaves_no_threads(self, tmp_path, tiny_data):
        """Every fit() exit path joins the loader (steps boundary lands
        mid-epoch here) — live_loaders must return to zero."""
        from kubeflow_tpu.train.data import Dataset

        x, y = tiny_data
        ds = Dataset(x, y, x[:16], y[:16], num_classes=10)
        t = self._trainer(tmp_path / "cc2")
        t.config.steps = 3  # mid-epoch stop (4 batches/epoch)
        t.fit(ds)
        assert loader_metrics_snapshot()["live_loaders"] == 0


# ---------------------------------------------------------- analytics splits


def _span(name, ts, dur, pid=1, parent="", span="", **attrs):
    return {"name": name, "trace": "t", "span": span or name + str(ts),
            "parent": parent, "ts": ts, "dur": dur, "pid": pid, "tid": 1,
            "attrs": attrs}


class TestAnalyticsSplits:
    def test_data_wait_assemble_sum_exact(self):
        from kubeflow_tpu.profiling import step_breakdown

        spans = [
            _span("train.data_load", 0.0, 0.10, seq=0,
                  wait_s=0.03, assemble_s=0.09),
            _span("train.step", 0.10, 0.20, step=0),
            # no attr (inline loader): all assemble
            _span("train.data_load", 0.30, 0.05, seq=1),
            _span("train.step", 0.35, 0.20, step=1),
        ]
        s0, s1 = step_breakdown(spans)
        assert s0["data_wait"] == pytest.approx(0.03)
        assert s0["data_assemble"] == pytest.approx(0.07)
        assert s1["data_wait"] == 0.0
        assert s1["data_assemble"] == pytest.approx(0.05)
        for s in (s0, s1):
            assert s["data_wait"] + s["data_assemble"] \
                == pytest.approx(s["data_load"], abs=1e-9)
            assert s["data_load"] + s["compute"] + s["checkpoint"] \
                + s["stall"] == pytest.approx(s["wall"], abs=1e-9)

    def test_wait_attr_clamped_to_span(self):
        """A buggy/raced wait_s larger than the span itself can never
        push the split past what the cycle was charged."""
        from kubeflow_tpu.profiling import step_breakdown

        spans = [
            _span("train.data_load", 0.0, 0.04, seq=0, wait_s=9.9),
            _span("train.step", 0.05, 0.10, step=0),
        ]
        (s,) = step_breakdown(spans)
        assert s["data_wait"] == pytest.approx(0.04)
        assert s["data_assemble"] == pytest.approx(0.0)

    def test_restart_overhead_split_sum_exact(self):
        """compile + restore + rendezvous + schedule == overhead for the
        gang-restart chain, with each phase from its own span."""
        from kubeflow_tpu.profiling import restart_chains

        kill = _span("chaos.pod_kill", 0.0, 0.0, span="k")
        exit_ = _span("pod.exit", 0.1, 0.0, span="e", parent="k",
                      exit_code=137)
        rs = _span("job.gang_restart", 0.2, 0.0, span="r", parent="e",
                   restart=1, key="default/j")
        create = _span("job.create_pods", 0.3, 0.1, span="c",
                       restart=1, key="default/j")
        rdv = _span("rendezvous", 0.4, 0.2, span="v", parent="c", pid=9)
        compile_ = _span("train.compile", 0.6, 0.5, span="tc",
                         parent="c", pid=9)
        restore = _span("checkpoint.restore", 1.1, 0.3, span="cr",
                        parent="c", pid=9)
        step = _span("train.step", 1.5, 0.1, span="s1", parent="c",
                     pid=9, step=0)
        (ch,) = restart_chains(
            [kill, exit_, rs, create, rdv, compile_, restore, step])
        assert ch["overhead_s"] == pytest.approx(1.5)  # kill -> first step
        assert ch["compile_s"] == pytest.approx(0.5)
        assert ch["restore_s"] == pytest.approx(0.3)
        assert ch["rendezvous_s"] == pytest.approx(0.2)
        assert ch["schedule_s"] == pytest.approx(0.5)
        assert ch["compile_s"] + ch["restore_s"] + ch["rendezvous_s"] \
            + ch["schedule_s"] == pytest.approx(ch["overhead_s"], abs=2e-6)

    def test_restart_split_without_compile_span(self):
        """A pre-cache worker (no train.compile span) attributes its
        whole gap to schedule — the split degrades, never crashes."""
        from kubeflow_tpu.profiling import restart_chains

        kill = _span("chaos.pod_kill", 0.0, 0.0, span="k")
        rs = _span("job.gang_restart", 0.2, 0.0, span="r", parent="k",
                   restart=1)
        create = _span("job.create_pods", 0.3, 0.1, span="c", restart=1)
        step = _span("train.step", 1.0, 0.1, span="s1", parent="c",
                     pid=9, step=0)
        (ch,) = restart_chains([kill, rs, create, step])
        assert ch["compile_s"] == 0.0 and ch["restore_s"] == 0.0
        assert ch["schedule_s"] == pytest.approx(ch["overhead_s"])
