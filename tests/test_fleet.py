"""kftpu-fleet suite (serving/fleet, docs/serving.md): paged-KV block
table semantics (refcounts, COW, LRU), chunked-prefill equivalence
(token-identical to one-shot on the tiny GPT), prefix reuse (second
shared-prefix request prefills only the suffix), and the router drills —
least-loaded routing, SLO admission shedding, and the seeded replica-kill
drill whose acceptance bar is ZERO dropped requests. The drills run with
the lock-order detector armed (conftest.lockcheck_armed)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.gpt import GPTConfig, GPTLM, generate
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.fleet import (
    FleetOverloaded,
    FleetRouter,
    PagedKVPool,
    make_prompts,
    run_loadtest,
    run_loadtest_sync,
)

pytestmark = pytest.mark.fleet


@pytest.fixture(scope="module")
def lm():
    cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96)
    model = GPTLM(cfg, pad_token_id=-1)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 5), jnp.int32))
    return model, variables


def _prompt(seed, n, vocab=512):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, vocab, jnp.int32))


def _want(lm, p, budget):
    model, variables = lm
    return np.asarray(generate(
        model, variables, p[None, :], max_new_tokens=budget))[0]


# ------------------------------------------------------------- paged KV


def _fake_kv(ids):
    """Per-position stand-in K/V: value == position index, so gathered
    prefixes are verifiable by content."""
    n = len(ids)
    return {"layer_0/attention/cached_key":
            np.arange(n, dtype=np.float32).reshape(n, 1, 1)}


class TestPagedKVPool:
    def test_match_walks_identical_chain_only(self):
        pool = PagedKVPool(block_size=4, capacity_blocks=32)
        a = np.arange(1, 13, dtype=np.int32)           # 3 full blocks
        refs = pool.insert(a, _fake_kv(a))
        assert len(refs) == 3
        m = pool.match(a)
        assert m.length == 12
        np.testing.assert_array_equal(
            m.kv["layer_0/attention/cached_key"][:, 0, 0], np.arange(12))
        # divergence INSIDE block 2: only block 1 matches
        b = a.copy()
        b[5] += 1
        m2 = pool.match(b)
        assert m2.length == 4
        pool.release(m.blocks)
        pool.release(m2.blocks)
        pool.release(refs)
        assert all(c == 0 for c in pool.refcounts().values())

    def test_partial_tail_match_and_insert(self):
        pool = PagedKVPool(block_size=4, capacity_blocks=32)
        a = np.arange(1, 11, dtype=np.int32)           # 2 full + tail of 2
        pool.insert(a, _fake_kv(a))
        # same 8-prefix, tail extends the CACHED partial's 2 tokens
        b = np.concatenate([a, np.asarray([99, 98], np.int32)])
        m = pool.match(b)
        assert m.length == 10                           # 8 full + 2 partial
        assert m.kv["layer_0/attention/cached_key"].shape[0] == 10

    def test_cow_on_extending_a_shared_partial(self):
        pool = PagedKVPool(block_size=4, capacity_blocks=32)
        a = np.arange(1, 11, dtype=np.int32)            # partial tail [9, 10]
        refs_a = pool.insert(a, _fake_kv(a))            # holder #1
        tail = refs_a[-1]
        assert pool.refcounts()[tail] == 1
        # a second holder shares the tail, then extends it: the extension
        # must NOT mutate the block holder #1 still references
        m = pool.match(a)
        assert m.blocks[-1] == tail
        new_ref = pool.extend(
            tail, np.asarray([42, 43], np.int32),
            {"layer_0/attention/cached_key":
             np.asarray([[[100.0]], [[101.0]]], np.float32)})
        assert new_ref != tail
        assert pool.metrics["cow_copies_total"] == 1
        # the original partial still matches holder #1's exact prompt
        m2 = pool.match(a)
        assert m2.length == 10 and m2.blocks[-1] == tail

    def test_insert_path_counts_cow_past_live_partial(self):
        pool = PagedKVPool(block_size=4, capacity_blocks=32)
        a = np.arange(1, 11, dtype=np.int32)
        pool.insert(a, _fake_kv(a))                     # live partial tail
        b = np.arange(1, 13, dtype=np.int32)            # completes the block
        pool.insert(b, _fake_kv(b))
        assert pool.metrics["cow_copies_total"] == 1

    def test_eviction_lru_spares_referenced_and_parents(self):
        pool = PagedKVPool(block_size=2, capacity_blocks=3)
        a = np.arange(1, 7, dtype=np.int32)             # 3 blocks, at cap
        refs_a = pool.insert(a, _fake_kv(a))
        b = np.asarray([9, 8, 7, 6], np.int32)          # 2 more blocks
        refs_b = pool.insert(b, _fake_kv(b))
        # everything is referenced: over capacity but NOTHING evictable —
        # pinned chains never leave
        assert pool.metrics["blocks_evicted_total"] == 0
        assert len(pool) == 5
        # b retires: its now-unreferenced chain evicts leaf-first back to
        # capacity, while a's still-referenced chain survives untouched
        pool.release(refs_b)
        assert pool.metrics["blocks_evicted_total"] == 2
        assert set(refs_a) <= set(pool.refcounts())
        assert len(pool) == 3
        # a retires too: fresh inserts now evict a's LRU chain as needed
        pool.release(refs_a)
        c = np.asarray([5, 5, 5, 5], np.int32)
        refs_c = pool.insert(c, _fake_kv(c))
        assert len(pool) == 3
        assert set(refs_c) <= set(pool.refcounts())

    def test_eviction_under_decode_growth_pressure(self, lm):
        """ISSUE 13 drill: a pool sized BELOW aggregate demand, its
        inventory fragmented across retired chains, under live
        decode-growth pressure (rows appending generated-token KV at
        every block boundary). Eviction must drain ONLY unreferenced
        leaf blocks — never a live decode row's chain — and the rows'
        tokens stay exactly solo generate's (a dropped live block would
        corrupt the resumed gather/extend path). Refcounts drain to
        zero at retire and fresh pressure can then reclaim everything."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=12)
        # fragment the reuse inventory: distinct retired chains fill the
        # pool to capacity, all unreferenced (evict-on-demand stock)
        eng0 = ContinuousBatcher(model, variables, max_rows=2,
                                 paged_kv=pool)
        for i in range(3):
            eng0.submit(_prompt(80 + i, 11), max_new_tokens=6)
        eng0.run_until_idle()
        assert len(pool) == pool.capacity_blocks
        assert all(c == 0 for c in pool.refcounts().values())
        inventory = set(pool.refcounts())
        # live decode growth: two in-flight rows whose chains (prompt +
        # generated, ~5 blocks each) plus the inventory exceed capacity —
        # every boundary allocation forces an eviction decision
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool)
        pa, pb = _prompt(90, 10), _prompt(91, 10)
        ra = eng.submit(pa, max_new_tokens=8)
        rb = eng.submit(pb, max_new_tokens=8)
        evicted0 = pool.metrics["blocks_evicted_total"]
        while eng.tick():
            live = set()
            counts = pool.refcounts()
            # the O(1) pinned counter stays exact against a full scan
            # through every grow/share/evict transition of the drill
            assert pool.blocks_in_use() == sum(
                1 for c in counts.values() if c > 0)
            for ch in eng._row_chains.values():
                if ch is not None:
                    refs = set(ch.refs)
                    # every live chain block is still in the table AND
                    # still referenced — eviction never touched it
                    assert refs <= set(counts)
                    assert all(counts[d] > 0 for d in refs)
                    live |= refs
            # whatever left the pool came out of the unreferenced stock
            assert len(pool) <= pool.capacity_blocks + len(live)
        assert pool.metrics["blocks_evicted_total"] > evicted0, \
            "no eviction pressure — the drill sized the pool too large"
        # some fragmented inventory was sacrificed to the live rows
        assert not inventory <= set(pool.refcounts())
        np.testing.assert_array_equal(ra.result(timeout=1),
                                      _want(lm, pa, 8))
        np.testing.assert_array_equal(rb.result(timeout=1),
                                      _want(lm, pb, 8))
        # refcount drain: retire released every hold, the pool is back
        # at capacity, and fresh pressure can reclaim ALL of it
        assert all(c == 0 for c in pool.refcounts().values())
        assert len(pool) <= pool.capacity_blocks
        big = _prompt(99, 44)                       # 11 blocks in one go
        refs = pool.insert(big, {
            "layer_0/attention/cached_key":
            np.zeros((44, 1, 1), np.float32)})
        assert set(refs) <= set(pool.refcounts())
        assert len(pool) <= pool.capacity_blocks


# ------------------------------------------------------ chunked prefill


class TestChunkedPrefill:
    @pytest.mark.parametrize("plen,chunk", [(5, 3), (8, 4), (17, 4)])
    def test_token_identical_to_one_shot(self, lm, plen, chunk):
        """The equivalence contract: chunked admission produces EXACTLY
        the one-shot prefill's tokens (greedy rows bit-exact), at chunk
        boundaries and remainders alike."""
        model, variables = lm
        p = _prompt(20 + plen, plen)
        want = _want(lm, p, 12)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                prefill_chunk=chunk)
        req = eng.submit(p, max_new_tokens=12)
        eng.run_until_idle()
        np.testing.assert_array_equal(req.result(timeout=1), want)

    def test_mixed_chunked_rows_match_solo(self, lm):
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=3,
                                prefill_chunk=4)
        jobs = []
        for seed, plen, budget in ((41, 4, 10), (42, 19, 8), (43, 9, 14),
                                   (44, 23, 6), (45, 6, 9)):
            p = _prompt(seed, plen)
            jobs.append((p, budget, eng.submit(p, max_new_tokens=budget)))
        eng.run_until_idle()
        for p, budget, req in jobs:
            np.testing.assert_array_equal(
                req.result(timeout=1), _want(lm, p, budget))

    def test_decode_rows_advance_during_long_admission(self, lm):
        """The stall bound: while a long prompt admits chunk-by-chunk, an
        in-flight decode row keeps emitting every tick — chunked prefill
        interleaves instead of blocking the engine for the whole
        prompt."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                prefill_chunk=4)
        fast = eng.submit(_prompt(50, 4), max_new_tokens=40)
        eng.tick()                       # admit + first decode
        long_req = eng.submit(_prompt(51, 33), max_new_tokens=4)
        while long_req.t_first is None:
            before = len(fast.tokens)
            eng.tick()
            assert len(fast.tokens) == before + 1, (
                "decode row stalled for a whole tick during chunked "
                "admission")
        eng.run_until_idle()
        np.testing.assert_array_equal(
            long_req.result(timeout=1), _want(lm, _prompt(51, 33), 4))

    def test_guards(self, lm):
        model, variables = lm
        with pytest.raises(ValueError, match="bucketed"):
            ContinuousBatcher(model, variables, prefill_chunk=4,
                              prefill_buckets=(8, 16))
        # speculative x chunked COMPOSES now (tests/test_decode.py pins
        # token-identity); only the bucket/rolling hazards stay refused
        eng = ContinuousBatcher(model, variables, prefill_chunk=4,
                                draft_module=model,
                                draft_variables=variables)
        assert eng.prefill_chunk == 4 and eng.draft_module is not None
        rolled = GPTLM(GPTConfig.tiny(dropout_rate=0.0, max_len=96,
                                      attention_window=8,
                                      kv_cache_capacity=16))
        rvars = rolled.init(jax.random.PRNGKey(0),
                            jnp.ones((1, 5), jnp.int32))
        with pytest.raises(ValueError, match="full KV cache"):
            ContinuousBatcher(rolled, rvars, paged_kv=PagedKVPool())


# -------------------------------------------------------- prefix reuse


class TestPrefixReuse:
    def test_second_shared_prefix_request_prefills_only_suffix(self, lm):
        """The reuse proof: request B sharing A's 12-token system prompt
        computes ONLY its 4-token suffix (the shared-block fraction of
        prefill work disappears), with outputs exactly solo generate's."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=64)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool)
        sys_p = _prompt(60, 12)
        a = np.concatenate([sys_p, _prompt(61, 4)])
        b = np.concatenate([sys_p, _prompt(62, 4)])
        ra = eng.submit(a, max_new_tokens=8)
        eng.run_until_idle()
        assert eng.prefill_tokens_total == a.size
        assert eng.prefill_tokens_reused == 0
        rb = eng.submit(b, max_new_tokens=8)
        eng.run_until_idle()
        assert eng.prefill_tokens_total == a.size + 4   # suffix only
        assert eng.prefill_tokens_reused == 12
        np.testing.assert_array_equal(ra.result(timeout=1),
                                      _want(lm, a, 8))
        np.testing.assert_array_equal(rb.result(timeout=1),
                                      _want(lm, b, 8))
        # retired rows release their block refs — nothing stays pinned
        assert all(c == 0 for c in pool.refcounts().values())

    def test_full_match_still_computes_last_position(self, lm):
        """A fully-cached prompt must still run its LAST position through
        the model — the first token needs logits — so reuse is capped at
        len-1."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=64)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool)
        p = _prompt(63, 12)
        eng.submit(p, max_new_tokens=6)
        eng.run_until_idle()
        t0 = eng.prefill_tokens_total
        r2 = eng.submit(p, max_new_tokens=6)
        eng.run_until_idle()
        assert eng.prefill_tokens_total - t0 == 1
        np.testing.assert_array_equal(r2.result(timeout=1),
                                      _want(lm, p, 6))

    def test_reuse_composes_with_chunked_prefill(self, lm):
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=64)
        mk = lambda: ContinuousBatcher(  # noqa: E731
            model, variables, max_rows=2, paged_kv=pool, prefill_chunk=4)
        sys_p = _prompt(64, 16)
        a = np.concatenate([sys_p, _prompt(65, 6)])
        eng = mk()
        eng.submit(a, max_new_tokens=6)
        eng.run_until_idle()
        # a SECOND engine (fleet replica shape) reuses the pool's blocks
        eng2 = mk()
        b = np.concatenate([sys_p, _prompt(66, 6)])
        rb = eng2.submit(b, max_new_tokens=6)
        eng2.run_until_idle()
        assert eng2.prefill_tokens_reused == 16
        assert eng2.prefill_tokens_total == 6
        np.testing.assert_array_equal(rb.result(timeout=1),
                                      _want(lm, b, 6))


# -------------------------------------------------------------- router


class TestFleetRouter:
    def test_least_loaded_routing(self, lm):
        model, variables = lm
        router = FleetRouter([ContinuousBatcher(model, variables,
                                                max_rows=2)
                              for _ in range(2)])
        # park a heavy request without ticking: replica 0 carries load
        r1 = router.submit(_prompt(70, 8), max_new_tokens=30)
        r2 = router.submit(_prompt(71, 8), max_new_tokens=30)
        assert {r1.replica, r2.replica} == {"replica-0", "replica-1"}
        router.run_until_idle()
        assert r1.result(timeout=1).size == 30

    def test_admission_shed_carries_retry_after(self, lm):
        model, variables = lm
        router = FleetRouter(
            [ContinuousBatcher(model, variables, max_rows=2)],
            ttft_slo_s=0.01, service_rate_tokens_per_s=10.0)
        with pytest.raises(FleetOverloaded) as exc:
            router.submit(_prompt(72, 8), max_new_tokens=8)
        assert exc.value.retry_after_s > 0
        assert router.metrics["requests_shed_total"] == 1
        assert router.metrics["requests_admitted_total"] == 0

    def test_estimator_opens_admission_until_calibrated(self, lm):
        model, variables = lm
        router = FleetRouter(
            [ContinuousBatcher(model, variables, max_rows=2)],
            ttft_slo_s=0.01)  # no rate yet -> no shedding
        req = router.submit(_prompt(73, 6), max_new_tokens=4)
        router.run_until_idle()
        assert req.result(timeout=1).size == 4
        assert router.service_rate_tokens_per_s > 0  # calibrated now

    def test_demand_signal_tracks_backlog(self, lm):
        model, variables = lm
        router = FleetRouter(
            [ContinuousBatcher(model, variables, max_rows=2)],
            ttft_slo_s=0.05, service_rate_tokens_per_s=100.0)
        assert router.demand_replicas() == 1
        router.ttft_slo_s = 1e9  # admit freely, then read the signal
        for i in range(6):
            router.submit(_prompt(80 + i, 8), max_new_tokens=20)
        router.ttft_slo_s = 0.05
        assert router.demand_replicas() > 1
        router.ttft_slo_s = 0.0
        router.run_until_idle()
        assert router.demand_replicas() == 1

    def test_replica_kill_requeues_zero_drops(self, lm):
        """The fleet drill (threaded): seeded load on 3 replicas, one
        killed while carrying work — every request completes, tokens
        exactly solo generate's (requeued greedy rows re-decode
        identically), zero drops."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        router = FleetRouter(
            [ContinuousBatcher(model, variables, max_rows=2,
                               paged_kv=pool, prefill_chunk=4)
             for _ in range(3)])
        prompts = [_prompt(90 + i, 6 + (i % 3)) for i in range(9)]
        router.start()
        try:
            handles = [router.submit(p, max_new_tokens=10)
                       for p in prompts]
            # kill a replica that is actually carrying work
            victim = handles[0].replica
            deadline = time.monotonic() + 10
            while (handles[0].t_first is None
                   and time.monotonic() < deadline):
                time.sleep(0.005)  # kftpu: allow=KFTPU-SLEEP (test pacing)
            router.kill_replica(victim)
            for h in handles:
                assert h.done.wait(30), "request dropped after kill"
        finally:
            router.stop()
        assert router.metrics["requests_completed_total"] == len(prompts)
        assert router.metrics["requests_failed_total"] == 0
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.result(timeout=1),
                                          _want(lm, p, 10))

    @pytest.fixture(scope="class")
    def kill_drill(self, lm):
        """The seeded tick-driven drill, run once: seeded arrivals on 3
        replicas sharing one pool, replica 1 killed at tick 5, with the
        monitoring plane attached (the engines' decode-tick samples and
        the fleet's failure counter flow into one TSDB, the zero-drop
        objective evaluated over it afterwards)."""
        from tests.serving_fixtures import ZeroDropWatch

        model, variables = lm
        watch = ZeroDropWatch()
        pool = PagedKVPool(block_size=4, capacity_blocks=256)
        router = FleetRouter(
            [ContinuousBatcher(model, variables, max_rows=2,
                               paged_kv=pool, prefill_chunk=4,
                               tsdb=watch.tsdb)
             for _ in range(3)])
        prompts = make_prompts(12, seed=7, vocab=512, prompt_len=4,
                               shared_prefix=8)
        report = run_loadtest_sync(
            router, prompts, seed=7, mean_gap_ticks=0.7, new_tokens=6,
            kill_at_tick=5, kill_replica=1, on_tick=watch.on_tick)
        return {"report": report, "router": router, "tsdb": watch.tsdb,
                "zero_drop": watch.verdict()}

    KILL_DRILL = {
        "zero_drops": lambda d: d["report"].dropped == 0,
        "every_admission_completed": lambda d: d["report"].completed == 12,
        "the_kill_requeued_work": lambda d: d["report"].requeued >= 1,
        "one_replica_killed":
            lambda d: d["router"].metrics["replica_kills_total"] == 1,
        "prefix_reuse_engaged":
            lambda d: d["report"].prefill_tokens_reused > 0,
        "a_first_token_for_every_request":
            lambda d: len(d["report"].ttft_s) == 12,
        # the monitored half: the failure counter was sampled on every
        # tick, never moved, and the zero-drop objective stayed quiet
        # through the kill; the engines fed the decode-tick series
        "failure_counter_sampled": lambda d: d["zero_drop"]["samples"] > 0,
        "zero_drop_objective_quiet":
            lambda d: d["zero_drop"]["fired"] is False
            and d["zero_drop"]["alerts"] == [],
        "decode_ticks_sampled": lambda d: len(
            d["tsdb"].window("serving.decode_tick_s", 3600.0)) > 0,
    }

    @pytest.mark.parametrize("contract", list(KILL_DRILL))
    def test_seeded_sync_kill_drill_holds(self, kill_drill, contract):
        """Asserted on counts: seeded arrivals, kill mid-run, zero
        drops, all complete, prefix reuse measurably engaged."""
        d = kill_drill
        assert self.KILL_DRILL[contract](d), (
            d["report"].summary(), dict(d["router"].metrics), d["zero_drop"])

    def test_activator_pick_is_queue_depth_aware(self, lm):
        """The satellite: with a fleet load view wired, the activator's
        ready-endpoint pick goes least-loaded instead of round-robin."""
        from types import SimpleNamespace

        from kubeflow_tpu.serving.activator import Activator
        from kubeflow_tpu.serving.api import (
            InferenceService,
            InferenceServiceSpec,
            InferenceServiceStatus,
            PredictorSpec,
            ReplicaEndpoint,
        )
        from kubeflow_tpu.api.common import ObjectMeta

        loads = {"http://a": 40, "http://b": 3, "http://c": 11}
        act = Activator(SimpleNamespace(), load_view=lambda: loads)
        isvc = InferenceService(
            metadata=ObjectMeta(name="m"),
            spec=InferenceServiceSpec(predictor=PredictorSpec()),
            status=InferenceServiceStatus(endpoints=[
                ReplicaEndpoint(url=u, ready=True) for u in loads]),
        )
        assert all(act._pick_endpoint(isvc) == "http://b"
                   for _ in range(5))
        # view failure degrades to round-robin, never a 500
        act.load_view = lambda: (_ for _ in ()).throw(RuntimeError())
        assert act._pick_endpoint(isvc) in loads

    def test_fleet_model_server_timing_and_shed(self, lm, tmp_path):
        """End-to-end through the HTTP surface: a fleet-backed predictor
        serves v1 with the engine's timing block; an admission shed
        surfaces as 503 + Retry-After; ServingClient.predict_timed reads
        both (the streaming-aware helper satellite)."""
        import json as _json
        import urllib.error
        import urllib.request
        from types import SimpleNamespace

        from kubeflow_tpu.serving.client import ServingClient
        from kubeflow_tpu.serving.model import JaxModel, save_predictor
        from kubeflow_tpu.serving.server import ModelServer

        model, variables = lm
        p0 = _prompt(95, 8)[None, :]
        d = save_predictor(
            tmp_path / "fleet-gpt", "gpt-lm", dict(variables),
            p0.astype(np.int32),
            generate={"continuous": True, "fleet_replicas": 2,
                      "prefill_chunk": 4, "paged_kv_block": 4,
                      "max_new_tokens": 6, "pad_token_id": -1},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        m = JaxModel("fleet-gpt", d)
        m.load()
        assert m._fleet is not None and len(m._fleet.replicas) == 2
        srv = ModelServer([m], port=0).start()
        try:
            url = f"{srv.url}/v1/models/fleet-gpt:predict"
            req = urllib.request.Request(
                url, data=_json.dumps({"instances": p0.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                body = _json.loads(r.read())
            np.testing.assert_array_equal(
                np.asarray(body["predictions"])[0], _want(lm, p0[0], 6))
            assert body["timing"]["ttft_s"] >= 0
            assert body["timing"]["tokens_per_s"] > 0
            # the streaming-aware client helper reads the same block
            client = ServingClient.__new__(ServingClient)
            client._endpoint = lambda name, ns: srv.url
            out, timing = ServingClient.predict_timed(
                client, "fleet-gpt", p0.tolist())
            assert timing.ttft_s == out["timing"]["ttft_s"]
            assert timing.attempts == 1 and timing.wall_s > 0
            # force an admission shed: 503 + Retry-After on the wire
            m._fleet.ttft_slo_s = 1e-9
            m._fleet._rate = 1.0
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    urllib.request.Request(
                        url,
                        data=_json.dumps(
                            {"instances": p0.tolist()}).encode(),
                        headers={"Content-Type": "application/json"}),
                    timeout=30)
            assert exc.value.code == 503
            assert int(exc.value.headers["Retry-After"]) >= 1
        finally:
            srv.stop()

    def test_threaded_loadtest_report(self, lm):
        model, variables = lm
        router = FleetRouter([ContinuousBatcher(model, variables,
                                                max_rows=2)
                              for _ in range(2)])
        prompts = make_prompts(6, seed=3, vocab=512, prompt_len=(4, 8))
        report = run_loadtest(router, prompts, seed=3, mean_gap_s=0.002,
                              new_tokens=5, timeout_s=60)
        s = report.summary()
        assert s["dropped"] == 0 and s["completed"] == 6
        assert s["ttft_p99_s"] >= s["ttft_p50_s"] > 0
        assert s["tokens_out"] == 30
