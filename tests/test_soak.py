"""kftpu-storm suite — the closed autoscaling loop + production-day soak
(docs/autoscaling.md).

Covers: the zero-live-replica demand-signal guards (the signal never
returns 0 with work or arrivals waiting; an empty fleet sheds with the
wake stamp instead of crashing), FleetScaler scale-up cooldown /
scale-down stability hysteresis, the LOSS-FREE drain contract (graceful
drain completes in place with zero requeues; a drain-timeout polite
kill chain-resumes every in-flight request token-identical to solo
generation with scratch-requeue fraction 0), scale-to-zero and
wake-on-arrival, hang detection, the frozen-scaler chaos mode, the
golden-pinned scaler decision trace shape
(tests/golden/trace_shape_scaler.txt), the activator's cold-start-EWMA
Retry-After hint, SLO monitoring across scaler activity (stop_slo →
start_slo preserves the captured window; a scaled-to-zero fleet reports
zero-valued series, not missing ones), the ISVC controller's
fleet-demand autoscale wiring, and the composed days, each run once
with one case a contract: a short seeded production day (healthy, and
with the scaler frozen), the same day on real TCP pods, and the
chip-constrained diurnal storm (healthy, and with the ledger frozen)."""

import os
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.fleet import (
    FleetOverloaded,
    FleetRouter,
    FleetScaler,
    PagedKVPool,
    ScalerConfig,
)
from kubeflow_tpu.tracing import Tracer

pytestmark = pytest.mark.soak

GOLDEN_SHAPE = Path(__file__).resolve().parent / "golden" / \
    "trace_shape_scaler.txt"


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96)
    model = GPTLM(cfg)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _prompt(seed, n, vocab=512):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, size=(n,)).astype(np.int32)


def _mk_engine(lm, pool=None, rows=2):
    model, variables = lm
    return ContinuousBatcher(model, variables, max_rows=rows,
                             default_max_new_tokens=6, paged_kv=pool,
                             prefill_chunk=4 if pool is not None else 0)


def _tick_until(router, scaler=None, n=200):
    for _ in range(n):
        busy = False
        for rep in list(router.replicas):
            if rep.alive:
                busy = rep.engine.tick() or busy
        if scaler is not None:
            scaler.evaluate()
        if not busy and router.queue_depth() == 0:
            return
    raise AssertionError("fleet did not drain")


# --------------------------------------------- demand-signal zero guards


class TestDemandGuards:
    def test_nonempty_queue_never_demands_zero(self, lm):
        """Satellite contract: the signal never returns 0 while anything
        is queued — even with every replica draining (the EWMA has no
        live engine updating it there; the floor is pinned)."""
        router = FleetRouter([_mk_engine(lm)])
        router.submit(_prompt(1, 6), max_new_tokens=4)
        assert router.demand_replicas() >= 1
        router.begin_drain(0)  # serving set now empty, backlog remains
        assert router.demand_replicas() >= 1
        router.cancel_drain(0)
        router.run_until_idle()
        # alive + idle keeps the historical floor of 1 (test_fleet pins)
        assert router.demand_replicas() == 1

    def test_arrival_on_empty_fleet_demands_one(self, lm):
        """Wake-on-arrival: a submit that finds no admittable replica is
        shed with Retry-After AND stamps the wake signal, so the next
        demand read is >= 1 — never 0 with an arrival waiting."""
        router = FleetRouter([_mk_engine(lm)])
        router.begin_drain(0)
        router.remove_replica(0)
        assert router.replicas == []
        assert router.demand_replicas() == 0  # truly idle: zero is legal
        with pytest.raises(FleetOverloaded) as exc:
            router.submit(_prompt(2, 4), max_new_tokens=2)
        assert exc.value.retry_after_s > 0
        assert router.wake_pending() == 1
        assert router.demand_replicas() == 1
        router.clear_wake()
        assert router.demand_replicas() == 0

    def test_draining_replica_excluded_from_picks(self, lm):
        """A draining replica keeps ticking its seated work but admits
        nothing: new submits land on the survivor."""
        a, b = _mk_engine(lm), _mk_engine(lm)
        router = FleetRouter([("a", a), ("b", b)])
        router.begin_drain("a")
        req = router.submit(_prompt(3, 5), max_new_tokens=3)
        assert req.replica == "b"
        router.run_until_idle()
        assert req.result(timeout=1).size == 3

    def test_remove_replica_refuses_live_work(self, lm):
        router = FleetRouter([_mk_engine(lm)])
        router.submit(_prompt(4, 5), max_new_tokens=3)
        with pytest.raises(ValueError, match="drain"):
            router.remove_replica(0)
        router.begin_drain(0)
        with pytest.raises(ValueError, match="carries work"):
            router.remove_replica(0)
        router.run_until_idle()
        router.remove_replica(0)
        assert router.replicas == []


# ------------------------------------------------------------ the scaler


def _scripted_scaler(lm, demands, config, tracer=None):
    """A scaler driven by a scripted demand sequence (the demand MATH is
    covered by test_fleet/test_slo; these drills pin the LOOP)."""
    router = FleetRouter([_mk_engine(lm)], tracer=tracer)
    seq = iter(demands)
    last = [1]

    def scripted():
        last[0] = next(seq, last[0])
        return last[0]

    router.demand_replicas = scripted
    scaler = FleetScaler(router, lambda: _mk_engine(lm), config,
                         tracer=tracer)
    return router, scaler


class TestFleetScaler:
    def test_scale_up_cooldown_and_step_bound(self, lm):
        router, scaler = _scripted_scaler(
            lm, [8] * 10,
            ScalerConfig(min_replicas=1, max_replicas=6,
                         scale_up_cooldown_evals=2, max_step_up=2))
        scaler.evaluate()
        assert len(router._admittable()) == 3  # +2 (step bound)
        scaler.evaluate()
        assert len(router._admittable()) == 3  # cooldown holds
        scaler.evaluate()
        assert len(router._admittable()) == 5
        for _ in range(3):
            scaler.evaluate()
        # clamped at max_replicas even though demand says 8
        assert len(router._admittable()) == 6
        assert scaler.target_replicas == 6

    def test_scale_down_needs_stable_low_demand(self, lm):
        """Hysteresis: a one-eval demand dip (a chaos-induced spike
        ending) cannot drain anything; a stable low demand drains ONE
        replica per decision."""
        router, scaler = _scripted_scaler(
            lm, [3, 3, 1, 3, 1, 1, 1, 1, 1, 1],
            ScalerConfig(min_replicas=1, max_replicas=4,
                         scale_up_cooldown_evals=1,
                         scale_down_stable_evals=3, max_step_up=3))
        scaler.evaluate()  # -> 3
        assert len(router._admittable()) == 3
        scaler.evaluate()
        scaler.evaluate()  # dip to 1 (1 low eval)
        scaler.evaluate()  # back to 3: dip forgotten
        assert len(router._admittable()) == 3
        assert scaler.metrics["scale_downs_total"] == 0
        for _ in range(3):  # three consecutive lows
            scaler.evaluate()
        assert scaler.metrics["scale_downs_total"] == 1
        assert sum(1 for r in router.replicas if r.draining) == 1

    def test_graceful_drain_completes_without_requeue(self, lm):
        """The graceful half of the drain contract: in-flight work on
        the draining replica finishes IN PLACE (zero requeues), then the
        empty shell is reaped and recycled through on_release."""
        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        a, b = _mk_engine(lm, pool), _mk_engine(lm, pool)
        released = []
        router = FleetRouter([("a", a), ("b", b)])
        scaler = FleetScaler(
            router, lambda: _mk_engine(lm, pool),
            ScalerConfig(min_replicas=1, max_replicas=2,
                         scale_down_stable_evals=1,
                         drain_grace_evals=50),
            on_release=released.append)
        reqs = [router.submit(_prompt(10 + i, 6), max_new_tokens=4)
                for i in range(4)]
        router.demand_replicas = lambda: 1  # force scale-down pressure
        scaler.evaluate()
        assert scaler.metrics["scale_downs_total"] == 1
        _tick_until(router, scaler)
        for r in reqs:
            assert r.result(timeout=1).size == 4
        assert router.metrics["requests_requeued_total"] == 0
        assert scaler.metrics["drains_completed_total"] == 1
        assert scaler.metrics["drain_kills_total"] == 0
        assert len(released) == 1
        assert len(router.replicas) == 1

    def test_drain_timeout_polite_kill_is_loss_free(self, lm):
        """THE acceptance drill: a drain finished as a polite kill with
        in-flight decodes chain-resumes every request onto the survivor
        — token-identical to solo generation, scratch-requeue fraction
        0, resumed counters advancing."""
        model, variables = lm
        # solo reference: the exact greedy tokens each prompt produces
        prompts = [_prompt(40 + i, 6) for i in range(3)]
        solo_pool = PagedKVPool(block_size=4, capacity_blocks=256)
        solo = ContinuousBatcher(model, variables, max_rows=3,
                                 default_max_new_tokens=6,
                                 paged_kv=solo_pool, prefill_chunk=4)
        expect = []
        for p in prompts:
            h = solo.submit(p, max_new_tokens=6)
            solo.run_until_idle()
            expect.append(h.result(timeout=0).tolist())

        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        a = _mk_engine(lm, pool, rows=3)
        b = _mk_engine(lm, pool, rows=3)
        router = FleetRouter([("a", a), ("b", b)])
        scaler = FleetScaler(
            router, lambda: _mk_engine(lm, pool),
            ScalerConfig(min_replicas=1, max_replicas=2,
                         scale_down_stable_evals=1,
                         drain_grace_evals=0))  # grace 0: kill next eval
        # seat all three on replica a mid-decode (b is made HEAVIER
        # with direct long-budget traffic so the least-loaded routing
        # lands the drill prompts on a, and the least-loaded drain
        # victim is a — the replica actually holding the drill's work)
        for i in range(2):
            b.submit(_prompt(80 + i, 5), max_new_tokens=24)
        handles = [router.submit(p, max_new_tokens=6) for p in prompts]
        assert all(h.replica == "a" for h in handles)
        for _ in range(9):
            a.tick()  # chunks admitted, first decode steps taken
        assert all(len(h.tokens) > 0 for h in handles)
        base_resumed = router.metrics["requeues_resumed_total"]
        router.demand_replicas = lambda: 1
        scaler.evaluate()   # begins draining a (the least loaded)
        assert next(r for r in router.replicas if r.name == "a").draining
        scaler.evaluate()   # grace 0 -> polite kill -> chain resume
        assert scaler.metrics["drain_kills_total"] == 1
        _tick_until(router, scaler)
        for h, exp in zip(handles, expect):
            assert h.result(timeout=1).tolist() == exp
        requeued = router.metrics["requests_requeued_total"]
        resumed = router.metrics["requeues_resumed_total"] - base_resumed
        assert requeued >= 1
        # scratch-requeue fraction 0: every rescue resumed from its
        # surviving chain (zero re-prefill, zero re-decode)
        assert resumed == requeued
        assert router.metrics["requeue_resumed_tokens_total"] >= 1

    def test_scale_to_zero_and_wake_on_arrival(self, lm):
        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        router = FleetRouter([_mk_engine(lm, pool)])
        scaler = FleetScaler(
            router, lambda: _mk_engine(lm, pool),
            ScalerConfig(min_replicas=0, max_replicas=2,
                         idle_to_zero_evals=3, scale_up_cooldown_evals=1))
        for _ in range(5):
            scaler.evaluate()
        assert router.replicas == []
        assert scaler.metrics["scale_to_zero_total"] == 1
        # wake-on-arrival: shed with a hint, then the loop answers
        with pytest.raises(FleetOverloaded):
            router.submit(_prompt(60, 5), max_new_tokens=3)
        scaler.evaluate()
        assert scaler.metrics["scale_from_zero_total"] == 1
        assert len(router._admittable()) == 1
        req = router.submit(_prompt(60, 5), max_new_tokens=3)  # re-dial
        router.run_until_idle()
        assert req.result(timeout=1).size == 3

    def test_hang_detection_kills_and_replaces(self, lm):
        """A replica holding work whose engine makes no progress is
        declared hung and politely killed; its requests land on a
        survivor (spawned first when it was the last replica)."""
        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        router = FleetRouter([_mk_engine(lm, pool)])
        scaler = FleetScaler(
            router, lambda: _mk_engine(lm, pool),
            ScalerConfig(min_replicas=1, max_replicas=3,
                         hang_detect_evals=3))
        req = router.submit(_prompt(70, 6), max_new_tokens=4)
        # the hang: the engine is never ticked (SIGSTOP analogue); only
        # the scaler evaluates
        for _ in range(4):
            scaler.evaluate()
        assert scaler.metrics["hangs_detected_total"] == 1
        # replacement exists and carries the requeued request
        assert len(router._admittable()) >= 1
        _tick_until(router, scaler)
        assert req.result(timeout=1).size == 4
        assert req.error is None
        assert router.metrics["requests_requeued_total"] >= 1

    def test_fleet_wide_stall_never_hang_kills(self, lm):
        """Systemic-stall guard (found by the /verify drive): when NO
        replica is progressing (the driver stopped ticking — a global
        wedge, not one bad replica), the hang watch must not serially
        kill healthy replicas; that burns every request's requeue
        budget and converts the stall into drops. Peer progress is
        required to indict a hang (the health.py straggler contract,
        fleet edition) — and once one replica advances, the genuinely
        stalled peers ARE indicted and their work rescued."""
        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        engines = [_mk_engine(lm, pool) for _ in range(3)]
        router = FleetRouter(list(engines))
        scaler = FleetScaler(
            router, lambda: _mk_engine(lm, pool),
            ScalerConfig(min_replicas=1, max_replicas=3,
                         hang_detect_evals=3))
        reqs = [router.submit(_prompt(100 + i, 5), max_new_tokens=3)
                for i in range(6)]
        for _ in range(10):  # nobody ticks: systemic, not a hang
            scaler.evaluate()
        assert scaler.metrics["hangs_detected_total"] == 0
        assert router.metrics["requests_failed_total"] == 0
        assert len(router._alive()) == 3
        # one replica starts progressing: the stalled peers are now
        # indictable against it, and their requests land on it
        for _ in range(6):
            router.replicas[0].engine.tick()
            scaler.evaluate()
        assert scaler.metrics["hangs_detected_total"] >= 1
        _tick_until(router, scaler)
        for r in reqs:
            assert r.result(timeout=1).size == 3
        assert router.metrics["requests_failed_total"] == 0

    def test_frozen_scaler_evaluates_but_never_acts(self, lm):
        router, scaler = _scripted_scaler(
            lm, [5] * 4, ScalerConfig(max_replicas=5))
        scaler.freeze()
        for _ in range(4):
            scaler.evaluate()
        assert len(router._admittable()) == 1
        assert scaler.metrics["frozen_evaluations_total"] == 4
        assert scaler.metrics["scale_ups_total"] == 0
        scaler.thaw()
        scaler.evaluate()
        assert scaler.metrics["scale_ups_total"] == 1

    def test_undrain_is_the_cheapest_scale_up(self, lm):
        """Demand returning before a drain finishes cancels the drain
        instead of cold-starting a new engine."""
        builds = []

        def factory():
            builds.append(1)
            return _mk_engine(lm)

        a, b = _mk_engine(lm), _mk_engine(lm)
        router = FleetRouter([("a", a), ("b", b)])
        # both replicas hold un-ticked work so the drain cannot complete
        # before demand returns (b lighter -> b is the drain victim)
        a.submit(_prompt(90, 6), max_new_tokens=20)
        a.submit(_prompt(91, 6), max_new_tokens=20)
        b.submit(_prompt(92, 6), max_new_tokens=4)
        demands = iter([1, 1, 2])
        last = [2]

        def scripted():
            last[0] = next(demands, last[0])
            return last[0]

        router.demand_replicas = scripted
        scaler = FleetScaler(
            router, factory,
            ScalerConfig(min_replicas=1, max_replicas=2,
                         scale_down_stable_evals=2,
                         scale_up_cooldown_evals=1,
                         drain_grace_evals=50, hang_detect_evals=50))
        scaler.evaluate()
        scaler.evaluate()
        assert sum(1 for r in router.replicas if r.draining) == 1
        scaler.evaluate()  # demand 2 -> undrain instead of cold start
        assert sum(1 for r in router.replicas if r.draining) == 0
        assert len(router._admittable()) == 2
        assert builds == []  # no cold start paid
        router.run_until_idle()


# ---------------------------------------------------- golden trace shape


class TestScalerTraceShape:
    def test_scaler_decisions_golden_shape(self, lm):
        """Attributability acceptance: every fleet.scale_up/scale_down
        event parent-links to the scaler.evaluate that triggered it —
        pinned as request_shape-style structural text
        (KFTPU_UPDATE_GOLDEN=1 regenerates)."""
        from kubeflow_tpu.profiling import scaler_shape

        tracer = Tracer(capacity=512)
        router, scaler = _scripted_scaler(
            lm, [3, 1, 1, 1, 0, 0, 0, 0],
            ScalerConfig(min_replicas=0, max_replicas=4,
                         scale_up_cooldown_evals=2,
                         scale_down_stable_evals=3,
                         idle_to_zero_evals=6, max_step_up=2),
            tracer=tracer)
        for _ in range(8):
            scaler.evaluate()
        shape = scaler_shape(tracer.snapshot())
        if os.environ.get("KFTPU_UPDATE_GOLDEN"):
            GOLDEN_SHAPE.write_text(shape)
        assert shape == GOLDEN_SHAPE.read_text()
        # and the fleet really is at zero through graceful drains only
        assert router.replicas == []
        assert scaler.metrics["drain_kills_total"] == 0


# ------------------------------------------- activator cold-start hints


class TestActivatorColdStartHint:
    def _act(self, cluster, **kw):
        from kubeflow_tpu.serving.activator import Activator

        return Activator(SimpleNamespace(cluster=cluster), **kw)

    def test_uncalibrated_falls_back_to_static(self):
        from kubeflow_tpu.controller.fakecluster import FakeCluster

        act = self._act(FakeCluster(), retry_after_s=9.0)
        assert act.retry_after_hint_s() == 9
        _code, _b, _ct, headers = act._unavailable("x")
        assert headers == {"Retry-After": "9"}

    def test_ewma_derives_hint_capped_by_static(self):
        from kubeflow_tpu.controller.fakecluster import FakeCluster

        act = self._act(FakeCluster(), retry_after_s=10.0)
        for _ in range(3):
            act.observe_cold_start(0.6)
        # ceil(0.6 * 1.25) = 1 — proportional, well under the static 10
        assert act.retry_after_hint_s() == 1
        act.observe_cold_start(120.0)  # pathological cold start
        assert act.retry_after_hint_s() == 10  # operator ceiling holds

    def test_handle_observes_completed_cold_start(self):
        """The hold path calibrates: a cold start that completes feeds
        the EWMA even when the subsequent proxy fails (the observation
        is about activation, not the backend)."""
        import threading

        from kubeflow_tpu.api.common import ObjectMeta
        from kubeflow_tpu.controller.fakecluster import FakeCluster
        from kubeflow_tpu.serving.api import (
            InferenceService,
            InferenceServiceSpec,
            PredictorRuntime,
            PredictorSpec,
            ReplicaEndpoint,
        )

        cluster = FakeCluster()
        cluster.create("inferenceservices", InferenceService(
            metadata=ObjectMeta(name="warm"),
            spec=InferenceServiceSpec(predictor=PredictorSpec(
                runtime=PredictorRuntime.CUSTOM,
                model_class="tests.serving_fixtures:DoubleModel"))))
        act = self._act(cluster, activation_timeout_s=5.0,
                        retry_after_s=10.0)

        def become_ready():
            time.sleep(0.25)
            isvc = cluster.get("inferenceservices", "default/warm",
                               copy_obj=True)
            isvc.status.endpoints = [ReplicaEndpoint(
                url="http://127.0.0.1:9", ready=True)]  # unreachable
            cluster.update("inferenceservices", isvc)

        threading.Thread(target=become_ready, daemon=True).start()
        code, _body, _ct, _h = act.handle(
            "POST", "/default/warm/v1/models/warm:predict", b"{}",
            "application/json")
        assert code in (502, 503)  # proxy target is a dead port
        assert act.cold_start_ewma_s > 0.0
        assert act.retry_after_hint_s() <= 10


# -------------------------------------- SLO monitoring x scaler activity


class TestSLOAcrossScaler:
    def test_stop_start_slo_preserves_captured_window(self):
        """The armed-gate contract across a scaler incident: stop_slo
        freezes the captured window (hot-path producers no-op, nothing
        evicts), start_slo re-arms the SAME store with history intact."""
        from kubeflow_tpu.client import Platform

        p = Platform(log_dir=".kubeflow_tpu/test-soak-slo/pod-logs")
        try:
            p.start_slo(sample_interval_s=3600.0)
            for i in range(5):
                p.slo_tsdb.record("serving.decode_tick_s", 0.01 * i,
                                  ts=time.time() - 5 + i)
            assert len(p.slo_tsdb.window(
                "serving.decode_tick_s", 3600.0)) == 5
            p.stop_slo()
            assert p.slo_tsdb.record("serving.decode_tick_s", 9.9) \
                is False  # frozen: the incident window cannot be evicted
            assert len(p.slo_tsdb.window(
                "serving.decode_tick_s", 3600.0)) == 5
            monitor = p.start_slo()  # re-arm, no overrides
            assert monitor is p.slo_monitor
            assert p.slo_tsdb.record("serving.decode_tick_s", 0.05)
            window = p.slo_tsdb.window("serving.decode_tick_s", 3600.0)
            assert len(window) == 6  # history preserved + live again
        finally:
            p.stop_slo()

    def test_report_on_scaled_to_zero_fleet_is_zero_valued(self, lm):
        """A platform whose fleet scaled to zero reports ZERO-valued
        fleet series and SLO states — never missing ones (dashboards
        and the burn math must see an empty fleet, not a gap)."""
        from kubeflow_tpu.client import Platform
        from kubeflow_tpu.monitoring import (
            build_slo_report,
            default_slos,
            sample_platform,
        )

        p = Platform(log_dir=".kubeflow_tpu/test-soak-slo0/pod-logs")
        try:
            router = FleetRouter([_mk_engine(lm)])
            p.register_fleet("default/soakzero", router)
            p.start_slo(sample_interval_s=3600.0)
            router.begin_drain(0)
            router.remove_replica(0)  # scaled to zero, list empty
            sample_platform(p, p.slo_tsdb)
            report = build_slo_report(p)
            assert [s["name"] for s in report["slos"]] == [
                c.name for c in default_slos()]
            for name in ("kftpu_fleet_replicas_alive",
                         "kftpu_fleet_demand_replicas",
                         "kftpu_fleet_queue_depth"):
                assert p.slo_tsdb.latest(name) == 0.0, name
            assert report["alerts"] == []
            # the exposition itself renders the scaler families
            # zero-valued on a scalerless platform
            from kubeflow_tpu.observability import render_metrics

            text = render_metrics(p)
            assert "kftpu_scaler_evaluations_total 0" in text
            assert "kftpu_scaler_target_replicas 0" in text
        finally:
            p.stop_slo()


# -------------------------------------------------- ISVC controller wiring


class TestISVCFleetAutoscale:
    def _setup(self, demand, monitor=None):
        from kubeflow_tpu.api.common import ObjectMeta
        from kubeflow_tpu.controller.fakecluster import FakeCluster
        from kubeflow_tpu.serving.api import (
            AutoscalingSpec,
            InferenceService,
            InferenceServiceSpec,
            PredictorRuntime,
            PredictorSpec,
        )
        from kubeflow_tpu.serving.controller import (
            InferenceServiceController,
        )

        cluster = FakeCluster()
        isvc = InferenceService(
            metadata=ObjectMeta(name="fleetsvc"),
            spec=InferenceServiceSpec(
                predictor=PredictorSpec(
                    runtime=PredictorRuntime.CUSTOM,
                    model_class="tests.serving_fixtures:DoubleModel",
                    replicas=1),
                autoscaling=AutoscalingSpec(
                    min_replicas=0, max_replicas=4,
                    scale_interval_s=0.0, scale_to_zero_grace_s=0.05)))
        cluster.create("inferenceservices", isvc)

        class StubRouter:
            def __init__(self):
                self.demand = demand
                self.burn_calls = 0

            def demand_replicas(self):
                return self.demand

            def demand_replicas_burn(self, mon):
                self.burn_calls += 1
                return self.demand

            def queue_depth(self):
                return 0

        router = StubRouter()
        platform = SimpleNamespace(
            fleet_routers={"default/fleetsvc": router},
            slo_monitor=monitor)
        ctrl = InferenceServiceController(cluster, platform=platform)
        return cluster, ctrl, router

    def test_demand_signal_sizes_the_replica_set(self):
        cluster, ctrl, _router = self._setup(demand=3)
        isvc = cluster.get("inferenceservices", "default/fleetsvc",
                           copy_obj=True)
        ctrl._autoscale(isvc, "default/fleetsvc", [])
        cur = cluster.get("inferenceservices", "default/fleetsvc")
        assert cur.spec.predictor.replicas == 3
        events = [e for e in cluster.events_for("default/fleetsvc")
                  if e.reason == "Autoscaled"]
        assert events and "fleet demand 3" in events[-1].message

    def test_burn_aware_signal_used_when_monitor_live(self):
        cluster, ctrl, router = self._setup(
            demand=2, monitor=object())
        isvc = cluster.get("inferenceservices", "default/fleetsvc",
                           copy_obj=True)
        ctrl._autoscale(isvc, "default/fleetsvc", [])
        assert router.burn_calls == 1
        cur = cluster.get("inferenceservices", "default/fleetsvc")
        assert cur.spec.predictor.replicas == 2

    def test_idle_floor_demand_scales_to_zero_after_grace(self):
        """A REAL FleetRouter floors demand at 1 while any replica
        serves (its own scale-in floor) — the controller must not read
        that floor as traffic, or scaleToZeroGraceS never elapses and
        the serverless contract is silently dead (found in review: a
        demand=0 stub masked it)."""
        cluster, ctrl, router = self._setup(demand=2)
        key = "default/fleetsvc"
        isvc = cluster.get("inferenceservices", key, copy_obj=True)
        ctrl._autoscale(isvc, key, [])
        router.demand = 1  # the alive-floor reading of an IDLE fleet
        isvc = cluster.get("inferenceservices", key, copy_obj=True)
        ctrl._autoscale(isvc, key, [])
        # inside the idle grace: one replica held
        assert cluster.get("inferenceservices", key) \
            .spec.predictor.replicas == 1
        time.sleep(0.08)  # grace window elapses with no queued work
        isvc = cluster.get("inferenceservices", key, copy_obj=True)
        ctrl._autoscale(isvc, key, [])
        assert cluster.get("inferenceservices", key) \
            .spec.predictor.replicas == 0


# ---------------------------------------------- the composed days, each once
#
# A day is tens of seconds, so each runs ONCE in a module-scoped fixture
# and every contract on its record is a parametrised case of its own: a
# broken contract names itself. Counts, booleans and identities only — a
# record's seconds are never read, and neither is the `serving_decode_tick`
# objective, whose signal is the duration of a tick on this machine's CPU.

CLOCKED_SLO = "serving_decode_tick"


def _alerts(rec) -> list[str]:
    return [a for a in rec["slo"]["alerts"] if a != CLOCKED_SLO]


def _ttft_alert_fired(rec) -> bool:
    """The TTFT objective is thresholded in scheduler ticks (a count)."""
    return ("serving_ttft_p99" in rec["slo"]["alerts"]
            and rec["slo"]["states"]["serving_ttft_p99"]["fired"] is True)


def _every_ttft_window_burning(rec) -> bool:
    """The bad fraction of tick-count TTFTs is past the budget line on
    every configured window, not on one."""
    burns = rec["slo"]["states"]["serving_ttft_p99"]["burn_rates"]
    return bool(burns) and all(b >= 1.0 for b in burns.values())


def _counts(rec) -> dict:
    """What a failing case prints: the record's scalars and counters."""
    flat = {k: v for k, v in rec.items() if not isinstance(v, (dict, list))}
    for k in ("scaler", "sched", "batch", "churn", "ckpt", "partition"):
        if k in rec:
            flat[k] = rec[k]
    flat["alerts"] = rec["slo"]["alerts"]
    return flat


def _cases(contracts: dict):
    return pytest.mark.parametrize("contract", list(contracts))


PROD_DAY = {
    "zero_drops": lambda r: r["dropped"] == 0,
    "every_admission_completed":
        lambda r: r["completed"] == r["n_requests"] > 30,
    "a_replica_was_killed": lambda r: r["kills_injected"] >= 1,
    "the_kill_requeued_work": lambda r: r["requeued"] >= 1,
    "a_replica_hung": lambda r: r["hang_injected"] is True,
    "the_hang_was_detected":
        lambda r: r["scaler"]["hangs_detected_total"] >= 1,
    "the_scaler_scaled_up": lambda r: r["scaler"]["scale_ups_total"] >= 1,
    "a_drain_completed":
        lambda r: r["scaler"]["drains_completed_total"] >= 1,
    "scale_to_zero_reached": lambda r: r["scale_to_zero_reached"] is True,
    "recovered_from_zero": lambda r: r["recovered_from_zero"] is True,
    "torn_checkpoint_fell_back": lambda r: r["ckpt"]["fallback_ok"] is True,
    "alert_quiet": lambda r: _alerts(r) == [],
    "churn_goodput_above_half": lambda r: r["churn"]["goodput_mean"] > 0.5,
    # the ONE report carried the request breakdown for every traced
    # request (build_slo_report is the single build path)
    "report_holds_the_requests":
        lambda r: r["report"]["requests"]["count"] > 0,
}

#: a scaler that evaluates and never acts (`frozen=True`) while the same
#: waves arrive: the backlog is served late through one replica, never
#: lost, and the tick-count TTFT objective must fire
FROZEN_DAY = {
    "ran_frozen": lambda r: r["frozen"] is True,
    "no_scale_up": lambda r: r["scaler"]["scale_ups_total"] == 0,
    "zero_drops": lambda r: r["dropped"] == 0,
    "every_admission_completed": lambda r: r["completed"] == r["n_requests"],
    "ttft_alert_fired": _ttft_alert_fired,
    "every_ttft_window_burning": _every_ttft_window_burning,
}

SHORT_DAY = dict(day_ticks=120, max_replicas=4, churn_jobs=3)


class TestProdDaySoak:
    """A short production day end to end: zero drops through scale
    events, kills and the hang; scale-to-zero reached and recovered
    through the wake path; the torn checkpoint fell back to the
    verified step; the SLO report stays alert-quiet — and the same day
    with the scaler frozen fires the alert and still drops nothing."""

    @pytest.fixture(scope="class")
    def day(self):
        from kubeflow_tpu.soak import SoakConfig, run_prod_day

        return run_prod_day(SoakConfig(**SHORT_DAY))

    @pytest.fixture(scope="class")
    def frozen_day(self):
        from kubeflow_tpu.soak import SoakConfig, run_prod_day

        return run_prod_day(SoakConfig(**SHORT_DAY), frozen=True)

    @_cases(PROD_DAY)
    def test_short_seeded_day_holds(self, day, contract):
        assert PROD_DAY[contract](day), _counts(day)

    @_cases(FROZEN_DAY)
    def test_frozen_scaler_day_holds(self, frozen_day, contract):
        assert FROZEN_DAY[contract](frozen_day), _counts(frozen_day)


PODS_DAY = {
    "zero_drops": lambda r: r["dropped"] == 0,          # EXACT, the headline
    "single_copy_streams": lambda r: r["token_overruns"] == 0,
    "every_admission_completed":
        lambda r: r["completed"] == r["n_requests"] > 10,
    "a_pod_was_sigkilled": lambda r: r["kills_injected"] >= 1,
    "the_sigstopped_pod_was_declared_dead":
        lambda r: bool(r["hang_injected"] and r["hang_victim_dead"]),
    "partition_injected":
        lambda r: r["partition"]["injected_tick"] is not None,
    "healed_only_after_replacement":
        lambda r: r["partition"]["healed_after_replacement"] is True,
    "worker_survived_partition":
        lambda r: r["partition"]["worker_survived_partition"] is True,
    # the fenced claim delivered late work after the heal and ALL of it
    # was refused — the zero-duplicate proof
    "every_late_delivery_refused":
        lambda r: r["partition"]["refused"] == r["partition"]["late_events"],
    "fenced_probe_answered": lambda r: "probe_error" not in r["partition"],
    "torn_checkpoint_fell_back": lambda r: r["ckpt"]["fallback_ok"] is True,
    "one_partition_counted":
        lambda r: r["pod_metrics"]["net_partitions_injected_total"] == 1,
    "supervisor_redialed":
        lambda r: r["pod_metrics"]["net_reconnects_total"] >= 1,
    # SIGKILL + wedge + partition
    "three_pod_deaths": lambda r: r["pod_metrics"]["kills_total"] >= 3,
}


class TestProdDayPodsSoak:
    """The production day re-composed on a spawn_pod TCP fleet
    (run_prod_day_pods): the SIGKILL is discovered through the wire,
    the SIGSTOP is indicted by heartbeat age (or converted by the
    op-timeout detector — the drill gates the outcome, not the winner),
    and the mid-peak partition heals only AFTER the scaler replaced the
    victim, whose fenced claim then has every late delivery refused."""

    @pytest.fixture(scope="class")
    def day(self):
        from kubeflow_tpu.soak import PodSoakConfig, run_prod_day_pods

        cache = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".kubeflow_tpu", "test-compile-cache")
        return run_prod_day_pods(PodSoakConfig(compile_cache_dir=cache))

    @_cases(PODS_DAY)
    def test_seeded_day_on_real_tcp_pods_holds(self, day, contract):
        assert PODS_DAY[contract](day), {**_counts(day),
                                         "pod_metrics": day["pod_metrics"]}


def _serving_alerts(rec) -> list[str]:
    return [a for a in rec["slo"]["serving_alerts"] if a != CLOCKED_SLO]


STORM = {
    "zero_drops": lambda r: r["dropped"] == 0,
    "every_admission_completed": lambda r: r["completed"] == r["n_requests"],
    "no_serving_slo_violation": lambda r: _serving_alerts(r) == [],
    "alert_quiet": lambda r: _alerts(r) == [],
    # the forced-preemption geometry did force a preemption, and the
    # evicted gang came back once the trough handed its chips back
    "the_ledger_preempted": lambda r: r["sched"]["preemptions_total"] >= 1,
    "a_gang_was_seen_evicted": lambda r: r["batch"]["preemptions_seen"] >= 1,
    "the_evicted_gang_resumed":
        lambda r: r["batch"]["resumed"] >= 1 and bool(
            r["batch"]["resume_ticks"]),
    "the_ledger_counted_the_resume":
        lambda r: r["sched"]["resumes_total"] >= 1,
    "every_gang_bound_at_the_end":
        lambda r: r["batch"]["resumed"] == r["batch"]["preemptions_seen"],
    # eviction rode the restart path, not a delete-and-recreate bypass
    "eviction_rode_the_gang_restart_path":
        lambda r: any(c >= 1 for c in r["batch"]["restart_counts"].values()),
    # DRF quota: the victim gang was borrowing over its entitlement and
    # the serving claim reclaimed it
    "a_gang_borrowed": lambda r: r["sched"]["quota_borrows_total"] >= 1,
    "serving_reclaimed": lambda r: r["sched"]["quota_reclaims_total"] >= 1,
    "peak_needed_the_preempted_chips": lambda r: r["replicas_peak"] >= 3,
    "report_holds_the_requests":
        lambda r: r["report"]["requests"]["count"] > 0,
}

#: a ledger that stops granting (`frozen=True`; releases still work): the
#: fleet is pinned at its base replica through both peaks, cannot preempt,
#: serves the backlog late and loses nothing; the batch leg is untouched
FROZEN_STORM = {
    "ran_frozen": lambda r: r["frozen"] is True,
    "fleet_pinned_at_one_replica": lambda r: r["replicas_peak"] == 1,
    "scale_ups_were_denied": lambda r: r["chip_denies"] >= 1,
    "the_ledger_counted_the_denies":
        lambda r: r["sched"]["denies_total"] >= 1,
    "no_preemption": lambda r: r["sched"]["preemptions_total"] == 0,
    "batch_goodput_untouched": lambda r: r["batch"]["goodput_min"] == 1.0,
    "zero_drops": lambda r: r["dropped"] == 0,
    "every_admission_completed": lambda r: r["completed"] == r["n_requests"],
    "ttft_alert_fired": _ttft_alert_fired,
    "every_ttft_window_burning": _every_ttft_window_burning,
}


class TestDiurnalStorm:
    """The production day's waves on a chip-CONSTRAINED cluster
    (docs/scheduler.md): the peak cannot fit without preempting batch
    training. The shared ledger must actually preempt (a real JAXJob
    gang evicted through the gang-restart path), the gang must resume
    once the trough hands the chips back, the quota borrow and reclaim
    cycle must run, and serving must ride through with zero drops and
    no serving SLO violation — and with the ledger frozen the TTFT
    alert fires, nothing is preempted and still nothing drops."""

    @pytest.fixture(scope="class")
    def storm(self):
        from kubeflow_tpu.soak import StormConfig, run_diurnal_storm

        return run_diurnal_storm(StormConfig())

    @pytest.fixture(scope="class")
    def frozen_storm(self):
        from kubeflow_tpu.soak import StormConfig, run_diurnal_storm

        return run_diurnal_storm(StormConfig(), frozen=True)

    @_cases(STORM)
    def test_seeded_storm_holds(self, storm, contract):
        assert STORM[contract](storm), _counts(storm)

    @_cases(FROZEN_STORM)
    def test_frozen_ledger_storm_holds(self, frozen_storm, contract):
        assert FROZEN_STORM[contract](frozen_storm), _counts(frozen_storm)
