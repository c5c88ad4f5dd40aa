"""Control-plane scale-out suite (docs/architecture.md "Control-plane
scaling"): the sharded/filtered watch path, the keyed worker pool's
per-key ordering contract, and the status-write group commit.

The recovery drills here deliberately run against FILTERED subscriptions
— overflow->relist and WatchClosed->resubscribe existed before this
layer, but a filter that silently dropped them (or a relist that ignored
the filter) would be invisible to the unfiltered drills in
test_chaos_drills.py.
"""

import queue
import threading
import time

import pytest

from kubeflow_tpu.api.common import ObjectMeta
from kubeflow_tpu.controller.base import ControllerBase, KeyedWorkQueuePool
from kubeflow_tpu.controller.fakecluster import (
    EventType,
    FakeCluster,
    Pod,
    PodPhase,
    WatchClosed,
    WatchPoller,
    matches_labels,
)
from kubeflow_tpu.controller.statusbuffer import (
    StatusWriteBuffer,
    pod_status_copier,
)

pytestmark = pytest.mark.cplane


def _pod(name, labels=None):
    return Pod(metadata=ObjectMeta(name=name, labels=dict(labels or {})))


def _job_obj(name):
    # any object with metadata works for non-pod kinds in the store
    return Pod(metadata=ObjectMeta(name=name))


class TestFilteredWatch:
    def test_kind_filter_excludes_other_kinds(self):
        c = FakeCluster()
        sub = c.watch(kinds=("pods",))
        c.create("jobs", _job_obj("j1"))
        c.create("pods", _pod("p1"))
        etype, kind, obj = sub.get(timeout=1.0)
        assert (kind, obj.metadata.name) == ("pods", "p1")
        with pytest.raises(queue.Empty):
            sub.get(timeout=0.05)
        c.unwatch(sub)

    def test_label_selector_presence_and_equality(self):
        c = FakeCluster()
        present = c.watch(kinds=("pods",), label_selector={"team": None})
        exact = c.watch(kinds=("pods",), label_selector={"team": "a"})
        c.create("pods", _pod("p-none"))
        c.create("pods", _pod("p-a", {"team": "a"}))
        c.create("pods", _pod("p-b", {"team": "b"}))
        got = [present.get(timeout=1.0)[2].metadata.name for _ in range(2)]
        assert got == ["p-a", "p-b"]
        with pytest.raises(queue.Empty):
            present.get(timeout=0.05)
        assert exact.get(timeout=1.0)[2].metadata.name == "p-a"
        with pytest.raises(queue.Empty):
            exact.get(timeout=0.05)
        for s in (present, exact):
            c.unwatch(s)

    def test_empty_value_selector_is_equality_not_presence(self):
        # k8s `labelSelector=team=` means equality-to-EMPTY — the hub's
        # live-tail match and the Python relist match must agree on it
        # (a presence/equality conflation would make one subscription
        # deliver different object sets before and after an overflow)
        c = FakeCluster()
        eq_empty = c.watch(kinds=("pods",), label_selector={"team": ""})
        c.create("pods", _pod("empty", {"team": ""}))
        c.create("pods", _pod("valued", {"team": "a"}))
        assert eq_empty.get(timeout=1.0)[2].metadata.name == "empty"
        with pytest.raises(queue.Empty):
            eq_empty.get(timeout=0.05)
        replay = c.watch(kinds=("pods",), label_selector={"team": ""})
        assert replay.get(timeout=0.5)[2].metadata.name == "empty"
        with pytest.raises(queue.Empty):
            replay.get(timeout=0.05)
        for s in (eq_empty, replay):
            c.unwatch(s)

    def test_metachar_labels_cannot_forge_or_hide_matches(self):
        # '=', ',', ';', ':' in label values are escaped on the wire, so
        # a hostile value can neither forge a hub-side selector match nor
        # corrupt neighboring labels
        c = FakeCluster()
        sub = c.watch(kinds=("pods",), label_selector={"app": "a"})
        c.create("pods", _pod("hostile", {"app": "b", "x": "y,app=a"}))
        with pytest.raises(queue.Empty):
            sub.get(timeout=0.05)
        c.create("pods", _pod("real", {"app": "a", "w": "v=1;k:2"}))
        assert sub.get(timeout=1.0)[2].metadata.name == "real"
        c.unwatch(sub)

    def test_per_kind_selectors(self):
        # a controller's real shape: ALL of its own kind, only labeled pods
        c = FakeCluster()
        sub = c.watch(selectors={"jobs": None, "pods": {"owned": None}})
        c.create("jobs", _job_obj("j1"))
        c.create("pods", _pod("stray"))
        c.create("pods", _pod("mine", {"owned": "1"}))
        got = [sub.get(timeout=1.0)[:2][1] for _ in range(2)]
        assert got == ["jobs", "pods"]
        with pytest.raises(queue.Empty):
            sub.get(timeout=0.05)
        c.unwatch(sub)

    def test_irrelevant_storm_cannot_overflow_filtered_sub(self):
        # the whole point of server-side filtering: the hub never buffers
        # other kinds, so a storm of them can't push this stream into
        # overflow->relist
        class Small(FakeCluster):
            WATCH_CAPACITY = 8

        c = Small()
        sub = c.watch(kinds=("pods",))
        c.create("pods", _pod("p1"))
        for i in range(10 * Small.WATCH_CAPACITY):
            c.create("jobs", _job_obj(f"j{i}"))
        # were the jobs buffered, this stream would have overflowed and
        # relisted; instead the single pod event is still queued intact
        etype, kind, obj = sub.get(timeout=1.0)
        assert (etype, kind) == (EventType.ADDED, "pods")
        with pytest.raises(queue.Empty):
            sub.get(timeout=0.05)
        c.unwatch(sub)

    def test_overflow_relist_respects_filter(self):
        class Small(FakeCluster):
            WATCH_CAPACITY = 8

        c = Small()
        sub = c.watch(kinds=("pods",), label_selector={"keep": None})
        for i in range(Small.WATCH_CAPACITY * 3):
            c.create("pods", _pod(f"keep-{i:03d}", {"keep": "1"}))
            c.create("pods", _pod(f"drop-{i:03d}"))
        seen = {}
        while True:
            try:
                etype, kind, obj = sub.get(timeout=0.2)
            except queue.Empty:
                break
            assert kind == "pods"
            assert matches_labels(obj, {"keep": None}), obj.metadata.name
            seen[obj.key] = etype
        # overflow forced at least one relist; post-relist every matching
        # object is represented exactly once and nothing else leaked in
        assert len(seen) == Small.WATCH_CAPACITY * 3
        c.unwatch(sub)

    def test_watch_closed_resubscribe_keeps_filters(self):
        c = FakeCluster()
        errors = [0]

        def count():
            errors[0] += 1

        wp = WatchPoller(c, timeout=0.2, count_error=count,
                         selectors={"pods": {"keep": None}})
        c.create("pods", _pod("keep-0", {"keep": "1"}))
        assert wp.get()[2].metadata.name == "keep-0"
        # kill the stream at the hub: the poller must resubscribe with
        # the SAME filters, relist, and keep filtering
        c._hub.unsubscribe(wp.q._sub_id)
        c.create("pods", _pod("drop-0"))
        c.create("pods", _pod("keep-1", {"keep": "1"}))
        deadline = time.monotonic() + 10.0
        got = []
        while time.monotonic() < deadline and len(got) < 2:
            ev = wp.get()
            if ev is not None:
                got.append(ev[2].metadata.name)
        assert errors[0] >= 1  # the dead stream was counted, not absorbed
        assert sorted(set(got)) == ["keep-0", "keep-1"]


class TestKeyedPool:
    def test_route_is_stable_and_total_len(self):
        pool = KeyedWorkQueuePool(4, base_delay_s=0.001, max_delay_s=0.1)
        try:
            assert pool._route("a/b") is pool._route("a/b")
            for k in ("a/1", "a/2", "a/3", "b/1", "b/2"):
                pool.add(k)
            assert len(pool) == 5
            assert sum(pool.depths()) == 5
        finally:
            pool.shutdown()
            for q in pool.queues:
                q.close()

    def test_per_key_ordering_two_keys_interleave(self):
        """The ordering contract: with N workers, passes for DISTINCT keys
        run concurrently, while any ONE key's passes never overlap (so its
        event order can never be observed reordered)."""
        cluster = FakeCluster()
        active: dict[str, bool] = {}
        overlapped = []
        concurrent_pairs = []
        mu = threading.Lock()
        done = []

        class C(ControllerBase):
            ERROR_EVENT_KIND = "pods"
            WATCH_KINDS = ("pods",)

            def kind_filter(self, etype, kind, obj):
                return obj.key if kind == "pods" else None

            def resync_keys(self):
                return ()

            def reconcile(self, key):
                with mu:
                    if active.get(key):
                        overlapped.append(key)  # same-key overlap: bug
                    if any(k != key for k, v in active.items() if v):
                        concurrent_pairs.append(key)
                    active[key] = True
                time.sleep(0.002)  # widen the overlap window
                with mu:
                    active[key] = False
                    done.append(key)
                return None

        ctrl = C(cluster, "ordering", workers=4)
        ctrl.start()
        try:
            # two HOT keys, many passes each: 15 MODIFIED events per pod
            # keep both keys continuously enqueued, so dirty-replay +
            # keyed routing must serialize per key while the two keys
            # overlap freely across workers
            pods = [_pod("hot-0"), _pod("hot-1")]
            for p in pods:
                cluster.create("pods", p)
            # waves: both keys get an event, then a gap longer than the
            # 2ms pass, so level-triggered dedupe can't collapse the storm
            # into one pass per key and every wave reconciles both keys
            # at the same time
            for i in range(15):
                for p in pods:
                    cluster.read_modify_write(
                        "pods", p.key,
                        lambda o, i=i: setattr(o.status, "message", str(i)))
                time.sleep(0.008)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and len(done) < 8:
                time.sleep(0.01)
        finally:
            ctrl.stop()
        assert not overlapped, f"same-key passes overlapped: {overlapped}"
        assert len(done) >= 8  # both keys reconciled repeatedly
        assert {k for k in done} == {"default/hot-0", "default/hot-1"}
        # distinct keys DID run concurrently (the pool isn't serial)
        assert concurrent_pairs, "expected cross-key concurrency"

    def test_single_key_never_reorders(self):
        """All events for one key funnel to one queue/worker; the native
        dirty-replay then guarantees pass N sees state >= pass N-1's. Drive
        one pod through ordered status values and record the observed
        sequence inside reconcile."""
        cluster = FakeCluster()
        seen = []

        class C(ControllerBase):
            ERROR_EVENT_KIND = "pods"
            WATCH_KINDS = ("pods",)

            def kind_filter(self, etype, kind, obj):
                return obj.key if kind == "pods" else None

            def resync_keys(self):
                return ()

            def reconcile(self, key):
                pod = self.cluster.get("pods", key)
                if pod is not None:
                    seen.append(int(pod.status.message or "0"))
                return None

        pod = _pod("one")
        cluster.create("pods", pod)
        ctrl = C(cluster, "mono", workers=4)
        ctrl.start()
        try:
            for i in range(1, 40):
                cluster.read_modify_write(
                    "pods", pod.key,
                    lambda p, i=i: setattr(p.status, "message", str(i)))
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and (
                    not seen or seen[-1] < 39):
                time.sleep(0.01)
        finally:
            ctrl.stop()
        assert seen and seen[-1] == 39
        # level-triggered passes may coalesce events, but what one key's
        # serialized passes observe can only move forward
        assert seen == sorted(seen), seen


class TestStatusWriteBuffer:
    def test_basic_write_and_incarnation_guard(self):
        c = FakeCluster()
        pod = _pod("p1")
        c.create("pods", pod)
        buf = StatusWriteBuffer(c)

        def run(p):
            p.status.phase = PodPhase.RUNNING

        assert buf.write(pod.key, pod.metadata.uid, run) is True
        assert c.get("pods", pod.key).status.phase == PodPhase.RUNNING
        # wrong incarnation: declined, store untouched
        assert buf.write(pod.key, "uid-stale", lambda p: setattr(
            p.status, "phase", PodPhase.FAILED)) is False
        assert c.get("pods", pod.key).status.phase == PodPhase.RUNNING
        # missing pod
        assert buf.write("default/ghost", "", run) is False
        buf.close()

    def test_mutator_decline_and_ordering(self):
        c = FakeCluster()
        pod = _pod("p1")
        c.create("pods", pod)
        buf = StatusWriteBuffer(c)
        buf.write(pod.key, "", lambda p: setattr(p.status, "message", "a"))
        buf.write(pod.key, "", lambda p: setattr(
            p.status, "message", p.status.message + "b"))
        assert c.get("pods", pod.key).status.message == "ab"
        assert buf.write(pod.key, "", lambda p: False) is False
        buf.close()

    def test_concurrent_writers_coalesce_and_all_apply(self):
        c = FakeCluster()
        n = 200
        for i in range(n):
            c.create("pods", _pod(f"p{i:03d}"))
        buf = StatusWriteBuffer(c)
        results = []
        mu = threading.Lock()

        def writer(lo, hi):
            for i in range(lo, hi):
                ok = buf.write(
                    f"default/p{i:03d}", "",
                    lambda p: setattr(p.status, "phase", PodPhase.RUNNING))
                with mu:
                    results.append(ok)

        threads = [threading.Thread(target=writer,
                                    args=(i * 50, (i + 1) * 50))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        buf.close()
        assert len(results) == n and all(results)
        running = [p for p in c.list("pods")
                   if p.status.phase == PodPhase.RUNNING]
        assert len(running) == n
        m = buf.metrics
        assert m["writes_total"] == n
        # every write acked through a flush; under 4 concurrent writers at
        # least SOME flushes combined more than one op
        assert m["flushes_total"] <= m["writes_total"]

    def test_chaos_conflict_routes_through_single_op_path(self):
        class OneShotConflictChaos:
            def __init__(self):
                self.fired = 0

            def on_update(self, kind, key):
                from kubeflow_tpu.controller.fakecluster import ConflictError
                if self.fired == 0:
                    self.fired += 1
                    raise ConflictError("injected")

        c = FakeCluster()
        pod = _pod("p1")
        c.create("pods", pod)
        c.chaos = OneShotConflictChaos()
        buf = StatusWriteBuffer(c)
        ok = buf.write(pod.key, pod.metadata.uid,
                       lambda p: setattr(p.status, "phase",
                                         PodPhase.RUNNING))
        assert ok is True  # retried through the classic path and applied
        assert buf.metrics["conflict_fallbacks_total"] == 1
        assert c.get("pods", pod.key).status.phase == PodPhase.RUNNING
        buf.close()

    def test_status_copier_shares_payload_but_not_status(self):
        pod = _pod("p1", {"team": "a"})
        pod.command = ["python", "-c", "pass"]
        cp = pod_status_copier(pod)
        assert cp.command is pod.command  # untouched payload shared
        assert cp.status is not pod.status
        assert cp.metadata.annotations is not pod.metadata.annotations
        cp.status.phase = PodPhase.RUNNING
        assert pod.status.phase == PodPhase.PENDING  # original untouched

    def test_event_ctx_carries_writer_span(self):
        """The MODIFIED event published by a coalesced write must carry
        the WRITER'S span context (not the flusher's), or reconcile spans
        lose their causal parent across the buffer."""
        from kubeflow_tpu.tracing import Tracer, consume_delivered_context

        c = FakeCluster()
        tracer = Tracer(capacity=64)
        c.tracer = tracer
        pod = _pod("p1")
        c.create("pods", pod)
        sub = c.watch(kinds=("pods",), replay=False)
        buf = StatusWriteBuffer(c)
        with tracer.span("writer.op") as sp:
            buf.write(pod.key, "",
                      lambda p: setattr(p.status, "phase",
                                        PodPhase.RUNNING))
            want = sp.context
        etype, kind, obj = sub.get(timeout=1.0)
        ctx = consume_delivered_context()
        assert etype == EventType.MODIFIED
        assert ctx is not None and ctx.span_id == want.span_id
        buf.close()
        c.unwatch(sub)
        c.tracer = None


class TestBatchUpdate:
    def test_semantics_match_read_modify_write(self):
        c = FakeCluster()
        for i in range(3):
            c.create("pods", _pod(f"p{i}"))
        res = c.batch_update("pods", [
            ("default/p0",
             lambda p: setattr(p.status, "phase", PodPhase.RUNNING), None),
            ("default/ghost", lambda p: None, None),
            ("default/p2", lambda p: False, None),
        ])
        assert res[0] is not None and res[1] is None and res[2] is None
        assert c.get("pods", "default/p0").status.phase == PodPhase.RUNNING
        # versions bumped only for applied ops
        assert (c.get("pods", "default/p0").metadata.resource_version
                > c.get("pods", "default/p2").metadata.resource_version)

    def test_stale_snapshot_writer_still_conflicts(self):
        # batch_update must not weaken optimistic concurrency for OTHER
        # writers: a snapshot taken before the batch conflicts after it
        from kubeflow_tpu.controller.fakecluster import ConflictError

        c = FakeCluster()
        pod = _pod("p1")
        c.create("pods", pod)
        snap = c.get("pods", pod.key, copy_obj=True)
        c.batch_update("pods", [
            (pod.key,
             lambda p: setattr(p.status, "phase", PodPhase.RUNNING), None),
        ])
        snap.status.message = "stale"
        with pytest.raises(ConflictError):
            c.update("pods", snap)


STORM_LABEL = "kubeflow-tpu.org/cplane-storm"
STORM_PODS, STORM_GANG = 1000, 100


def _storm_pod(i):
    return _pod(f"storm-{i:05d}", {STORM_LABEL: "1"})


class TestComposedStorm:
    """The three pieces above composed the way the platform runs them: a
    controller on a label-filtered watch and the keyed pool takes a wave
    of pods to Running through the status-write buffer while another
    controller's filtered watch looks on, then one gang's worth of pods
    is deleted and recreated. Counts only."""

    @pytest.fixture(scope="class")
    def storm(self):
        from kubeflow_tpu.utils.retry import poll_until

        cluster = FakeCluster()
        buffer = StatusWriteBuffer(cluster, kind="pods")
        marked = []
        marked_mu = threading.Lock()

        class StormController(ControllerBase):
            ERROR_EVENT_KIND = "pods"
            WATCH_SELECTORS = {"pods": {STORM_LABEL: None}}

            def kind_filter(self, etype, kind, obj):
                if kind == "pods" and STORM_LABEL in obj.metadata.labels:
                    return obj.key
                return None

            def resync_keys(self):
                return ()

            def reconcile(self, key):
                pod = self.cluster.get("pods", key)
                if pod is None or pod.status.phase != PodPhase.PENDING:
                    return None
                uid = pod.metadata.uid

                def to_running(p):
                    if p.status.phase != PodPhase.PENDING:
                        return False
                    p.status.phase = PodPhase.RUNNING

                if buffer.write(key, uid, to_running):
                    with marked_mu:
                        marked.append(uid)
                return None

        bystander = WatchPoller(
            cluster, timeout=0.05, count_error=lambda: None,
            selectors={"pods": {"kubeflow-tpu.org/job-name": None}})
        # the wave lands BEFORE the controller starts: the informer's
        # replay delivers all of it at once
        for i in range(STORM_PODS):
            cluster.create("pods", _storm_pod(i))
        ctrl = StormController(cluster, "cplane", workers=4)
        ctrl.start()
        try:
            poll_until(lambda: len(marked) >= STORM_PODS or None,
                       timeout_s=120.0, describe="pods to Running")
            # let the wave's MODIFIED backlog drain, or its passes would
            # be counted against the restart
            prev = -1
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                cur = ctrl.metrics["reconcile_total"]
                if cur == prev and len(ctrl.wq) == 0:
                    break
                prev = cur
                time.sleep(0.05)
            wave = len(marked)
            passes0 = ctrl.metrics["reconcile_total"]
            for i in range(STORM_GANG):
                cluster.delete("pods", f"default/storm-{i:05d}")
            for i in range(STORM_GANG):
                cluster.create("pods", _storm_pod(i))
            poll_until(
                lambda: len(marked) >= STORM_PODS + STORM_GANG or None,
                timeout_s=120.0, describe="gang restart reconverged")
            restart_passes = ctrl.metrics["reconcile_total"] - passes0
        finally:
            ctrl.stop()
            buffer.close()
        seen = 0
        while bystander.get() is not None:
            seen += 1
        phases = [cluster.get("pods", f"default/storm-{i:05d}").status.phase
                  for i in range(STORM_PODS)]
        return {"wave": wave, "marked": list(marked), "phases": phases,
                "restart_passes": restart_passes, "bystander_events": seen}

    CONTRACTS = {
        "every_pod_running_at_the_end":
            lambda s: all(p == PodPhase.RUNNING for p in s["phases"]),
        # no update lost and none applied twice: one mark an incarnation
        "the_wave_marked_each_pod_once": lambda s: s["wave"] == STORM_PODS,
        "one_mark_per_incarnation":
            lambda s: len(s["marked"]) == STORM_PODS + STORM_GANG
            == len(set(s["marked"])),
        # a restarted pod is three events (deleted, added, and the
        # status write's modified); level-triggered passes may coalesce
        # them but nothing may amplify them
        "no_reconcile_amplification":
            lambda s: STORM_GANG <= s["restart_passes"] <= 3 * STORM_GANG,
        "the_other_controllers_watch_saw_nothing":
            lambda s: s["bystander_events"] == 0,
    }

    @pytest.mark.parametrize("contract", list(CONTRACTS))
    def test_storm_through_the_composed_path(self, storm, contract):
        s = storm
        assert self.CONTRACTS[contract](s), {
            k: v for k, v in s.items() if k not in ("marked", "phases")}
