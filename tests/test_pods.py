"""kftpu-pods suite — cross-process pod-backed serving replicas
(kubeflow_tpu/serving/fleet/{wire,podworker,podclient}.py, docs/serving.md
"Pod-backed replicas").

Every replica here is a REAL subprocess: a podworker hosting one
ContinuousBatcher behind the length-prefixed AF_UNIX wire protocol. The
drills cover the full failure matrix the tier ships with — SIGKILL
mid-decode (zero drops, chain-resume rescue), SIGSTOP (heartbeat-age hang
indictment and scaler replacement), torn frames (retry + submit
idempotency), deadline propagation (504 across the wire), the
admission-window kill (a pod dying between admission and seating), and
the digest-checked paged-KV handoff codec. Runs under the lock-order
detector (conftest arms it for the `pods` marker).

Workers share the repo-local persistent compile cache (the conftest
inference-cache reasoning applies: pure inference, no fit loop), so the
N subprocess spawns in this file compile the tiny-GPT programs once.
"""

import os
import signal
import socket
import time

import numpy as np
import pytest

from kubeflow_tpu.serving.fleet import (
    FleetRouter,
    PagedKVPool,
    make_prompts,
    run_loadtest_sync,
    spawn_pod,
    wire_pod_deaths,
)
from kubeflow_tpu.serving.fleet.podclient import (
    PodClient,
    attach_router_death,
    next_fence_epoch,
    pod_metrics_snapshot,
)
from kubeflow_tpu.serving.fleet.scaler import FleetScaler, ScalerConfig
from kubeflow_tpu.serving.fleet.wire import (
    PodDead,
    PodDeadlineExpired,
    PodWireError,
    deserialize_chain,
    serialize_chain,
)
from kubeflow_tpu.utils.retry import Deadline

pytestmark = pytest.mark.pods

VOCAB = 64
PROMPT = 4
PREFIX = 2
NEW = 4

#: the conftest inference compile cache — workers are fresh processes,
#: so without it every spawn in this file recompiles the same programs
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".kubeflow_tpu", "test-compile-cache")


def _spec(**over) -> dict:
    warm = make_prompts(1, seed=99, vocab=VOCAB, prompt_len=PROMPT,
                        shared_prefix=PREFIX)
    spec = {
        "model": {"vocab_size": VOCAB, "hidden_size": 32, "num_layers": 1,
                  "num_heads": 2, "mlp_dim": 64, "dropout_rate": 0.0,
                  "max_len": PREFIX + PROMPT + NEW + 24},
        "seed": 0, "init_seed": 7, "max_rows": 2,
        "default_max_new_tokens": NEW, "eos_token_id": None,
        "prefill_chunk": 0,
        "pool": {"block_size": 4, "capacity_blocks": 256},
        "warmup_prompts": [[int(t) for t in p] for p in warm],
        "warmup_new_tokens": NEW, "warmup_repeats": 1,
        "warmup_resume": True,
        "compile_cache_dir": _CACHE_DIR,
        "max_queue": 64,
    }
    spec.update(over)
    return spec


def _run_to_done(client, handles, timeout_s: float = 60.0) -> None:
    deadline = Deadline(timeout_s)
    while any(not h.done.is_set() for h in handles):
        client.tick()
        assert not deadline.expired(), "pod never finished the handles"


@pytest.fixture(scope="module")
def state_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("pods"))


@pytest.fixture(scope="module")
def pod(state_dir):
    """One long-lived worker shared by the non-destructive drills."""
    home = PagedKVPool(block_size=4, capacity_blocks=256)
    c = spawn_pod("shared-0", _spec(), state_dir, home_pool=home)
    yield c
    c.kill(timeout_s=5.0)


def _prompt(seed: int) -> np.ndarray:
    return make_prompts(1, seed=seed, vocab=VOCAB, prompt_len=PROMPT,
                        shared_prefix=PREFIX)[0]


class TestChainCodec:
    """The digest-keyed handoff serialization — pure, no subprocess."""

    def _chain_material(self, n: int, seed: int = 3):
        rng = np.random.default_rng(seed)
        ids = rng.integers(1, VOCAB, size=n).astype(np.int32)
        kv = {"l0/k": rng.standard_normal((n, 2, 4)).astype(np.float32),
              "l0/v": rng.standard_normal((n, 2, 4)).astype(np.float32)}
        return ids, kv

    def test_round_trip_bit_exact(self):
        src = PagedKVPool(block_size=4, capacity_blocks=64)
        dst = PagedKVPool(block_size=4, capacity_blocks=64)
        ids, kv = self._chain_material(10)
        refs = src.insert(ids, kv)
        ser = serialize_chain(src, refs)
        chain = deserialize_chain(dst, ser)
        assert not chain.frozen and chain.length == 10
        got_ids, got_kv = dst.gather(chain.refs)
        np.testing.assert_array_equal(got_ids, ids)
        for path in kv:
            np.testing.assert_array_equal(got_kv[path], kv[path])
        # the receiving pool re-derived the SAME content digests the
        # sender claimed — the cross-process identity the router's
        # adoption-by-digest relies on
        assert [d.hex() for d in chain.refs] == ser["refs"]
        chain.release()

    def test_corrupt_payload_refused(self):
        src = PagedKVPool(block_size=4, capacity_blocks=64)
        ids, kv = self._chain_material(10)
        ser = serialize_chain(src, src.insert(ids, kv))
        # flip one byte of one K/V leaf: sha256 over the raw arrays
        torn = {**ser, "kv": {**ser["kv"]}}
        path = sorted(torn["kv"])[0]
        b64 = torn["kv"][path]["b64"]
        torn["kv"][path] = {**torn["kv"][path],
                            "b64": ("A" if b64[0] != "A" else "B")
                            + b64[1:]}
        with pytest.raises(PodWireError):
            deserialize_chain(PagedKVPool(4, 64), torn)
        # a tampered digest list is caught even when the bytes verify
        lied = {**ser, "refs": ["00" * 20] + ser["refs"][1:]}
        with pytest.raises(PodWireError):
            deserialize_chain(PagedKVPool(4, 64), lied)

    def test_partial_insert_yields_frozen_chain(self):
        """A receiving pool already holding a LONGER partial with the
        same content prefix stops the re-insert early: the codec must
        hand back a FROZEN chain (the engine's resume validation then
        refuses it → scratch fallback), never silently-wrong K/V."""
        src = PagedKVPool(block_size=4, capacity_blocks=64)
        dst = PagedKVPool(block_size=4, capacity_blocks=64)
        ids, kv = self._chain_material(10)  # 2 full blocks + 2-pos tail
        ser = serialize_chain(src, src.insert(ids, kv))
        longer_ids = np.concatenate([ids, ids[:1]])  # 3-pos tail sibling
        longer_kv = {p: np.concatenate([a, a[:1]]) for p, a in kv.items()}
        held = dst.insert(longer_ids, longer_kv)
        chain = deserialize_chain(dst, ser)
        assert chain.frozen
        chain.release()
        dst.release(held)


class TestPodLifecycle:
    def test_spawn_serve_deterministic(self, pod):
        """hello handshake happened (pid, defaults), greedy decode is
        reproducible across submits, counters mirror the worker."""
        assert pod.worker_pid is not None and pod.worker_pid > 0
        assert pod.default_max_new_tokens == NEW
        p = _prompt(11)
        h1 = pod.submit(p, max_new_tokens=NEW)
        _run_to_done(pod, [h1])
        assert h1.error is None and len(h1.tokens) == NEW
        h2 = pod.submit(p, max_new_tokens=NEW)
        _run_to_done(pod, [h2])
        assert h2.tokens == h1.tokens  # greedy + seeded init weights
        assert pod.step_count > 0
        assert pod.prefill_tokens_total > 0
        assert pod._queue == [] and pod._rows == []
        assert pod.heartbeat_age() is not None
        assert pod.heartbeat_age() < 30.0

    def test_deadline_propagates_to_worker_504(self, pod):
        """A spent Deadline rides the envelope; the WORKER refuses with
        504 and the client surfaces PodDeadlineExpired + the metric —
        budget enforcement is end-to-end, not client-side guesswork."""
        base = pod_metrics_snapshot()["deadline_rejects_total"]
        d = Deadline(1e-9)
        time.sleep(0.01)
        with pytest.raises(PodDeadlineExpired):
            pod.call("heartbeat", deadline=d)
        assert pod_metrics_snapshot()["deadline_rejects_total"] == base + 1
        # the pod is fine — only the budget was refused
        assert pod.call("heartbeat")["ok"]

    def test_torn_frame_retried_submit_idempotent(self, pod):
        """A reply torn mid-frame (send landed, read truncated) is
        retried by the wire policy; the worker dedupes the re-sent rid
        so the row seats ONCE and the decode emits exactly its budget —
        the redelivery-not-duplication half of the outbox contract."""

        class OneTear:
            def __init__(self):
                self.left = 1

            def on_wire_op(self):
                if self.left:
                    self.left -= 1
                    return "torn"
                return None

        base = pod_metrics_snapshot()["wire_retries_total"]
        pod.chaos = OneTear()
        try:
            h = pod.submit(_prompt(12), max_new_tokens=NEW)
        finally:
            pod.chaos = None
        _run_to_done(pod, [h])
        assert h.error is None
        assert len(h.tokens) == NEW  # seated once, never twice
        assert pod_metrics_snapshot()["wire_retries_total"] > base

    def test_chain_handoff_resume_across_pods(self, pod, state_dir):
        """The cross-process rescue primitive end-to-end: pod A decodes
        with keep_chain, its chain crosses the wire into the HOME pool,
        and pod B resumes from it — token-identical to A's own run."""
        p = _prompt(13)
        straight = pod.submit(p, max_new_tokens=NEW)
        _run_to_done(pod, [straight])
        base = pod_metrics_snapshot()["handoff_bytes_total"]
        h = pod.submit(p, max_new_tokens=NEW, keep_chain=True)
        _run_to_done(pod, [h])
        assert h.chain is not None and not h.chain.frozen
        assert h.chain.pool is pod.paged_kv  # adopted into the HOME pool
        assert pod_metrics_snapshot()["handoff_bytes_total"] > base
        other = spawn_pod("resume-1", _spec(), state_dir,
                          home_pool=pod.paged_kv)
        try:
            keep = int(h.chain.length) - int(p.size) + 1
            assert 0 < keep <= len(h.tokens)
            r = other.submit(p, max_new_tokens=NEW,
                             resume_from=(h.chain, h.tokens[:keep]))
            _run_to_done(other, [r])
            assert r.error is None and r.resumed
            assert r.tokens == straight.tokens
        finally:
            other.kill(timeout_s=5.0)

    def test_drain_then_reap(self, state_dir):
        """Graceful teardown: drain ticks until the worker AND the local
        handle table are empty, then kill reaps the process."""
        c = spawn_pod("drain-0", _spec(), state_dir,
                      home_pool=PagedKVPool(4, 64))
        hs = [c.submit(_prompt(20 + i), max_new_tokens=NEW)
              for i in range(3)]
        assert c.drain(timeout_s=60.0)
        for h in hs:
            assert h.done.is_set() and h.error is None
            assert len(h.tokens) == NEW
        c.kill(timeout_s=5.0)
        assert c.dead
        assert c.proc.poll() is not None  # reaped, not orphaned

    def test_orphaned_worker_reaped_on_spawner_death(self, tmp_path):
        """A SIGKILLed spawner runs no teardown (a timed-out test
        runner, an OOM kill) — the worker's kernel pdeathsig watchdog
        must reap it anyway, never leaving a parked pod behind."""
        import json
        import subprocess
        import sys

        from kubeflow_tpu.utils.envvars import (
            ENV_POD_NAME,
            ENV_POD_SOCKET,
            ENV_POD_SPEC,
        )

        spec_path = tmp_path / "spec.json"
        spec_path.write_text(json.dumps(_spec()))
        # an intermediary interpreter spawns the worker then exits at
        # once: the worker is orphaned before it even finishes importing
        launcher = (
            "import os, subprocess, sys\n"
            "env = dict(os.environ)\n"
            f"env[{ENV_POD_SPEC!r}] = {str(spec_path)!r}\n"
            f"env[{ENV_POD_SOCKET!r}] = {str(tmp_path / 'w.sock')!r}\n"
            f"env[{ENV_POD_NAME!r}] = 'orphan-0'\n"
            "env['JAX_PLATFORMS'] = 'cpu'\n"
            "p = subprocess.Popen([sys.executable, '-m',"
            " 'kubeflow_tpu.serving.fleet.podworker'], env=env,"
            " stderr=subprocess.DEVNULL)\n"
            "print(p.pid, flush=True)\n"
        )
        out = subprocess.run([sys.executable, "-c", launcher],
                             capture_output=True, text=True, timeout=60)
        worker_pid = int(out.stdout.strip())
        deadline = Deadline(30.0)
        while True:
            try:
                os.kill(worker_pid, 0)
            except ProcessLookupError:
                break  # reaped by the kernel, as armed
            if deadline.expired():
                os.kill(worker_pid, signal.SIGKILL)
                pytest.fail("orphaned worker outlived its spawner")
            time.sleep(0.1)


def _kill_drill(state_dir, tmp_dir, tag, transport="unix",
                chaos_profile=None, seed=31):
    """The acceptance drill in miniature: a prefill pod and two decode
    pods behind the router, the seeded mix, decode pod 0 SIGKILLed by
    PID at tick 3. With `chaos_profile` the seeded fault plan of that
    name is armed on the decode pods' client sockets after the
    handshakes (so start-up never spends the fault budget). The
    protocol event log is armed for the run and replayed through the
    model acceptors. Returns the counts."""
    from kubeflow_tpu.utils.envvars import ENV_PROTOLOG

    home = PagedKVPool(block_size=4, capacity_blocks=512)
    spec = _spec()
    roles = ((f"{tag}-pf-0", "prefill"), (f"{tag}-dc-0", "decode"),
             (f"{tag}-dc-1", "decode"))
    log = tmp_dir / f"{tag}-protocol-events.jsonl"
    clients = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(ENV_PROTOLOG, str(log))
        try:
            for n, _r in roles:
                clients.append(spawn_pod(n, spec, state_dir, home_pool=home,
                                         connect=False, transport=transport))
            for c in clients:
                c.connect()
            if chaos_profile is not None:
                from kubeflow_tpu.chaos import ChaosEngine, FaultPlan

                chaos = ChaosEngine(FaultPlan.from_seed(
                    seed, profile=chaos_profile))
                for c in clients[1:]:
                    c.chaos = chaos
            router = FleetRouter([(c.name, c, role)
                                  for c, (_n, role) in zip(clients, roles)])
            wire_pod_deaths(router)
            victim = clients[1]
            prompts = make_prompts(6, seed=seed, vocab=VOCAB,
                                   prompt_len=PROMPT, shared_prefix=PREFIX)
            killed = {"done": False}

            def on_tick(tick, _rtr):
                if not killed["done"] and tick >= 3:
                    killed["done"] = True
                    try:
                        os.kill(victim.worker_pid, signal.SIGKILL)
                    except ProcessLookupError:
                        # the fault plan's partition already exhausted
                        # the retries and the client reaped the worker
                        pass

            base = pod_metrics_snapshot()
            report = run_loadtest_sync(
                router, prompts, seed=seed, mean_gap_ticks=1.0,
                new_tokens=NEW, kill_replica=None, on_tick=on_tick)
            now = pod_metrics_snapshot()
            (vrep,) = [r for r in router.replicas if r.engine is victim]
            rec = {
                **report.summary(),
                **{k: now[k] - base[k] for k in (
                    "wire_retries_total", "net_reconnects_total",
                    "net_duplicate_acks_refused_total", "kills_total",
                    "handoff_bytes_total")},
                "requests": len(prompts),
                "kill_sent": killed["done"],
                "victim_alive": vrep.alive,
                "replica_kills": router.metrics["replica_kills_total"],
                "handoffs": router.metrics["prefill_handoffs_total"],
            }
        finally:
            for c in clients:
                try:
                    c.kill(timeout_s=2.0)
                except (RuntimeError, OSError):  # teardown best-effort
                    pass
    # the recorded trace is an ACCEPTED run of the protocol models
    # (check_trace raises TraceRejected otherwise) — both protocols the
    # drill exercises left real events behind
    from kubeflow_tpu.analysis.protocheck import check_trace, read_log

    rec["trace_counts"] = check_trace(read_log(str(log)))
    return rec


#: what a SIGKILL mid-decode must leave, on any transport, faults or not
KILL_DRILL = {
    "kill_sent": lambda r: r["kill_sent"] is True,
    "zero_drops": lambda r: r["dropped"] == 0,
    "every_admission_completed": lambda r: r["completed"] == r["requests"],
    "victim_marked_dead":
        lambda r: r["victim_alive"] is False and r["replica_kills"] >= 1,
    "a_pod_death_counted": lambda r: r["kills_total"] >= 1,
    "trace_accepted_by_the_protocol_models":
        lambda r: r["trace_counts"]["wire"] > 0
        and r["trace_counts"]["kv"] > 0,
}
#: on a healthy wire: the rescue is a chain resume, every prompt is
#: handed off by digest, and nothing is retried, redialed or refused
HEALTHY_WIRE = {
    **KILL_DRILL,
    "the_kill_requeued_work": lambda r: r["requeued"] >= 1,
    # rescued by resuming the home-pool chain, not a scratch re-decode
    "resumed_from_the_home_pool_chain":
        lambda r: r["resumed"] >= 1 and r["resumed_tokens"] >= 1,
    "every_prompt_handed_off": lambda r: r["handoffs"] == r["requests"],
    "chains_crossed_the_wire": lambda r: r["handoff_bytes_total"] > 0,
    "no_wire_retry": lambda r: r["wire_retries_total"] == 0,
    "no_redial": lambda r: r["net_reconnects_total"] == 0,
    "no_duplicate_refused":
        lambda r: r["net_duplicate_acks_refused_total"] == 0,
}
#: under the seeded `wire` plan (resets, torn frames, deadline delays,
#: and the net family: black holes, half-open replies, duplicate
#: deliveries, a partition): every fault is absorbed by a retry or a
#: redial, never by a drop — and never silently. Whether the victim
#: still carries rows when the SIGKILL lands is the plan's to decide
#: (its partition may have fenced the pod first), so no requeue is asked
FAULTY_WIRE = {
    **KILL_DRILL,
    "faults_left_fingerprints":
        lambda r: r["wire_retries_total"] + r["net_reconnects_total"] >= 1,
}


class TestRouterIntegration:
    @pytest.fixture(scope="class")
    def unix_drill(self, state_dir, tmp_path_factory):
        return _kill_drill(state_dir, tmp_path_factory.mktemp("unix-drill"),
                           "ux")

    @pytest.fixture(scope="class")
    def tcp_drill(self, state_dir, tmp_path_factory):
        return _kill_drill(state_dir, tmp_path_factory.mktemp("tcp-drill"),
                           "tx", transport="tcp")

    @pytest.fixture(scope="class")
    def faulty_tcp_drill(self, state_dir, tmp_path_factory):
        return _kill_drill(state_dir, tmp_path_factory.mktemp("fx-drill"),
                           "fx", transport="tcp", chaos_profile="wire",
                           seed=11)

    @pytest.mark.parametrize("contract", list(HEALTHY_WIRE))
    def test_sigkill_mid_decode_zero_drop_chain_resume(self, unix_drill,
                                                       contract):
        assert HEALTHY_WIRE[contract](unix_drill), unix_drill

    @pytest.mark.parametrize("contract", list(HEALTHY_WIRE))
    def test_sigkill_over_tcp_matches_the_unix_contract(self, tcp_drill,
                                                        contract):
        assert HEALTHY_WIRE[contract](tcp_drill), tcp_drill

    @pytest.mark.parametrize("contract", list(FAULTY_WIRE))
    def test_sigkill_under_seeded_wire_and_net_faults(self, faulty_tcp_drill,
                                                      contract):
        assert FAULTY_WIRE[contract](faulty_tcp_drill), faulty_tcp_drill

    def test_admission_window_kill_repicks(self, state_dir):
        """The regression ISSUE 16 names: a pod dying BETWEEN admission
        and seating (the router picked it; the submit hits a corpse).
        The dispatch loop must flip the replica, re-pick a survivor
        under the same admission, and lose nothing — not raise out of
        submit, not leak the request."""
        home = PagedKVPool(block_size=4, capacity_blocks=256)
        spec = _spec()
        clients = [spawn_pod(n, spec, state_dir, home_pool=home,
                             connect=False) for n in ("adm-0", "adm-1")]
        try:
            for c in clients:
                c.connect()
            router = FleetRouter([(c.name, c) for c in clients])
            wire_pod_deaths(router)
            # the kill lands in the admission window: the process dies
            # NOW, the client only discovers it inside router.submit
            os.kill(clients[0].worker_pid, signal.SIGKILL)
            reqs = [router.submit(_prompt(40 + i), max_new_tokens=NEW)
                    for i in range(4)]
            survivor = clients[1]
            deadline = Deadline(60.0)
            while any(not r.done.is_set() for r in reqs):
                survivor.tick()
                assert not deadline.expired()
            for r in reqs:
                assert r.error is None
                assert r.result(timeout=1).size == NEW
            assert router.metrics["requests_failed_total"] == 0
            (corpse,) = [r for r in router.replicas
                         if r.engine is clients[0]]
            assert not corpse.alive
        finally:
            for c in clients:
                c.kill(timeout_s=2.0)

    def test_sigstop_hang_indicted_by_heartbeat_and_replaced(
            self, state_dir):
        """SIGSTOP is the failure SIGKILL drills can't see: the process
        keeps its socket and its mirrored counters — only the
        per-tick heartbeat stops. The scaler's hang watch (ScalerConfig
        .heartbeat_max_age_s) must indict the wedged pod by beat age,
        kill it, spawn a replacement through engine_factory, and the
        requeued request must complete on the replacement."""
        home = PagedKVPool(block_size=4, capacity_blocks=256)
        spec = _spec()
        a = spawn_pod("stop-0", spec, state_dir, home_pool=home)
        router = FleetRouter([(a.name, a)])
        wire_pod_deaths(router)
        spawned = []

        def factory():
            c = spawn_pod(f"stop-repl-{len(spawned)}", spec, state_dir,
                          home_pool=home)
            attach_router_death(c, router)
            spawned.append(c)
            return c

        scaler = FleetScaler(
            router, factory,
            ScalerConfig(min_replicas=1, max_replicas=2,
                         hang_detect_evals=10 ** 6,  # heartbeat-only
                         heartbeat_max_age_s=1.0),
            threaded=True)
        try:
            req = router.submit(_prompt(50), max_new_tokens=NEW)
            a.tick()  # a beat exists; the row is seated
            os.kill(a.worker_pid, signal.SIGSTOP)
            time.sleep(1.3)  # the beat goes stale past the ceiling
            deadline = Deadline(120.0)
            while scaler.metrics["hangs_detected_total"] < 1:
                scaler.evaluate()
                assert not deadline.expired(), "hang never indicted"
                time.sleep(0.05)
            assert req.result(timeout=60).size == NEW
            assert req.error is None
            assert router.metrics["requests_requeued_total"] >= 1
            assert a.dead  # the corpse was reaped, not leaked
            assert len(spawned) == 1
        finally:
            for c in [a] + spawned:
                try:
                    c.stop()
                    c.kill(timeout_s=2.0)
                except (RuntimeError, OSError):  # teardown best-effort
                    pass


class TestNetTransport:
    """kftpu-net: the same framing over TCP, and the failure family only
    a real network socket can express — severed connections replayed
    exactly once, stale epochs refused in both directions, and a
    partition's split-brain neutralized by the fence (docs/serving.md
    "Network failure matrix")."""

    def test_tcp_severed_connection_replays_idempotently(self, state_dir):
        """An ECONNRESET under an ESTABLISHED connection mid-decode: the
        connection supervisor redials (counted as a reconnect) and the
        retry layer replays the tick verb — rid dedup plus cumulative
        acks make the replay exact, so the stream is token-identical to
        an unsevered run of the same prompt."""
        c = spawn_pod("tcp-0", _spec(), state_dir,
                      home_pool=PagedKVPool(4, 256), transport="tcp")
        try:
            assert c._transport is not None and c._transport.kind == "tcp"
            straight = c.submit(_prompt(31), max_new_tokens=NEW)
            _run_to_done(c, [straight])
            base = pod_metrics_snapshot()
            h = c.submit(_prompt(31), max_new_tokens=NEW)
            c.tick()  # at least one round-trip lands on the doomed socket
            c._transport.sock.shutdown(socket.SHUT_RDWR)  # the reset
            _run_to_done(c, [h])
            assert h.error is None
            assert h.tokens == straight.tokens  # replayed, never doubled
            now = pod_metrics_snapshot()
            assert now["net_reconnects_total"] > \
                base["net_reconnects_total"]
            assert now["wire_retries_total"] > base["wire_retries_total"]
        finally:
            c.kill(timeout_s=5.0)

    def test_stale_epoch_refused_both_directions(self, state_dir,
                                                 protolog):
        """Epoch fencing end to end: a successor client born with a
        higher fence epoch adopts the worker via hello; the
        predecessor's next frame is answered 410 — it fences itself and
        is disowned WITHOUT killing the process (which now serves the
        successor's claim), and even its bypass-fence probe stays
        refused. The successor decodes untouched throughout."""
        a = spawn_pod("epoch-0", _spec(), state_dir,
                      home_pool=PagedKVPool(4, 256), transport="tcp")
        b = None
        try:
            first = a.submit(_prompt(40), max_new_tokens=NEW)
            _run_to_done(a, [first])
            # the worker serves one connection at a time — step aside so
            # the successor's dial is the next accept
            with a._wire_mu:
                a._close_socket()
            b = PodClient("epoch-0", a.socket_path, proc=None,
                          heartbeat_path=a.heartbeat_path,
                          transport="tcp", port_file=a.port_file,
                          epoch=next_fence_epoch())
            b.paged_kv = a.paged_kv
            b.connect(timeout_s=60.0)
            base = pod_metrics_snapshot()["net_fenced_frames_total"]
            with b._wire_mu:
                b._close_socket()  # let the stale client redial
            # worker-side refusal: the stale client's tick comes back
            # 410 — terminal, fenced, disowned, and the process spared
            assert a.tick() is False
            assert a.fenced and a.dead and a._disowned
            assert a.proc.poll() is None  # belongs to the successor now
            assert pod_metrics_snapshot()["net_fenced_frames_total"] \
                > base
            # even the bypass-fence heal probe is refused: the worker's
            # adopted epoch outranks this claim forever
            with pytest.raises(PodDead):
                a.fenced_poll(timeout_s=5.0)
            # the successor's claim is untouched by all of the above
            r = b.submit(_prompt(40), max_new_tokens=NEW)
            _run_to_done(b, [r])
            assert r.error is None
            assert r.tokens == first.tokens
        finally:
            if b is not None:
                b._close_socket()
            a._disowned = False  # drill teardown: reap the survivor
            a._kill_process()
        # the fence is visible in the trace: an epoch adoption that
        # purged, and at least one refused stale frame — and the whole
        # log is an accepted run
        events = protolog.events()
        assert any(e.get("ev") == "adopt" and e.get("purged")
                   for e in events)
        assert any(e.get("ev") == "refuse_stale" for e in events)
        assert protolog.counts()["wire"] > 0

    def test_partition_heal_split_brain_refused(self, state_dir,
                                                protolog):
        """The split-brain drill: a partition makes the host unreachable
        mid-decode, the retry budget burns out, and the death FENCES
        instead of killing — the worker keeps running on the far side.
        After the heal, the fenced claim's late deliveries are read
        back and every one is refused: the handle the fleet already
        failed over never grows another token."""
        c = spawn_pod("part-0", _spec(), state_dir,
                      home_pool=PagedKVPool(4, 256), transport="tcp",
                      op_timeout_s=2.0)
        try:
            h = c.submit(_prompt(41), max_new_tokens=NEW)
            c.tick()  # the row is seated; maybe a token or two landed
            ntoks = len(h.tokens)
            base = pod_metrics_snapshot()
            c.set_partitioned(True)
            assert c.tick() is False  # retries exhausted -> pod death
            assert c.dead and c.fenced
            assert c.proc.poll() is None  # the worker SURVIVED
            assert h.done.is_set() and h.error is not None  # requeue
            c.set_partitioned(False)  # the heal
            probe = c.fenced_poll(timeout_s=5.0)
            assert probe["late_events"] >= 1  # the outbox held stale work
            assert probe["refused"] == probe["late_events"]  # ALL refused
            assert len(h.tokens) == ntoks  # not one late token applied
            now = pod_metrics_snapshot()
            assert now["net_partitions_injected_total"] == \
                base["net_partitions_injected_total"] + 1
            assert now["net_fenced_frames_total"] > \
                base["net_fenced_frames_total"]
            assert now["wire_retries_exhausted_total"] > \
                base["wire_retries_exhausted_total"]
        finally:
            c.partitioned = False  # drill teardown: reap the survivor
            c._kill_process()
        # nothing the partition did put an unacceptable event in the
        # trace — the refused late deliveries never logged as delivered
        assert protolog.counts()["wire"] > 0

    def test_chain_handoff_resume_across_tcp_pods(self, state_dir):
        """The cross-pod rescue primitive rides the TCP wire unchanged:
        pod A decodes with keep_chain, its chain crosses the network
        into the HOME pool, and pod B resumes from it — token-identical
        to A's own straight run."""
        home = PagedKVPool(block_size=4, capacity_blocks=256)
        a = spawn_pod("tcp-res-0", _spec(), state_dir, home_pool=home,
                      transport="tcp")
        b = None
        try:
            p = _prompt(13)
            straight = a.submit(p, max_new_tokens=NEW)
            _run_to_done(a, [straight])
            h = a.submit(p, max_new_tokens=NEW, keep_chain=True)
            _run_to_done(a, [h])
            assert h.chain is not None and not h.chain.frozen
            b = spawn_pod("tcp-res-1", _spec(), state_dir,
                          home_pool=home, transport="tcp")
            keep = int(h.chain.length) - int(p.size) + 1
            assert 0 < keep <= len(h.tokens)
            r = b.submit(p, max_new_tokens=NEW,
                         resume_from=(h.chain, h.tokens[:keep]))
            _run_to_done(b, [r])
            assert r.error is None and r.resumed
            assert r.tokens == straight.tokens
        finally:
            a.kill(timeout_s=5.0)
            if b is not None:
                b.kill(timeout_s=5.0)


# ------------------------------------------------ trace-conformance teeth


class TestTraceConformance:
    def test_hand_corrupted_trace_rejected(self, state_dir, protolog):
        """Falsifiability of the conformance gate itself: record ONE
        clean single-pod run, then duplicate one delivered token frame
        in the log — the wire acceptor must reject the corrupted copy
        (single-copy breached: the exact duplication the cumulative-ack
        filter exists to prevent), while the pristine recording stays
        an accepted run."""
        from kubeflow_tpu.analysis.protocheck import (
            TraceRejected,
            check_trace,
        )

        c = spawn_pod("conf-0", _spec(), state_dir,
                      home_pool=PagedKVPool(4, 256))
        try:
            h = c.submit(_prompt(40), max_new_tokens=NEW)
            _run_to_done(c, [h])
        finally:
            c.kill(timeout_s=2.0)
        events = protolog.events()
        assert protolog.counts()["wire"] > 0  # pristine: accepted
        frames = [e for e in events
                  if e.get("ev") == "deliver" and e.get("kind") == "token"]
        assert frames  # the run really delivered tokens
        corrupted = events + [dict(frames[0])]
        with pytest.raises(TraceRejected):
            check_trace(corrupted)
