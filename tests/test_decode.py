"""kftpu-decode suite (ISSUE 13, docs/serving.md "Disaggregated
prefill/decode"): the paged pool as the SINGLE KV substrate for the
request lifetime — decode rows appending generated-token KV into block
chains (allocate-on-boundary, COW-safe sharing), block-budgeted
admission, chain adoption/gather by digest, speculative x chunked
prefill composition pinned token-identical to non-speculative greedy,
and the disaggregated prefill/decode tier: long prompts never occupy a
decode slot, and a replica kill mid-decode RESUMES from the surviving
chain instead of re-decoding from scratch. Runs with the lock-order
detector armed (conftest.lockcheck_armed — N tickers + router callbacks
+ one shared pool lock is exactly the nesting it exists for)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.gpt import GPTConfig, GPTLM, generate
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.fleet import (
    FleetRouter,
    PagedKVPool,
    make_prompts,
    run_loadtest_sync,
)

pytestmark = pytest.mark.decode


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


# ------------------------------------------------- decode chain growth


class TestDecodeChains:
    def test_chain_spans_whole_lifetime(self, lm):
        """The tentpole's core claim: after a request retires, the pool
        holds its PROMPT and its GENERATED tokens (chain length =
        prompt + new - 1; the newest token's KV is written by the next
        dispatch, which never comes). A follow-on conversation turn —
        prompt = previous prompt + completion — then matches deep into
        the generated chain, not just the old prompt."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=64)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool)
        p = _prompt(10, 12)
        r = eng.submit(p, max_new_tokens=8)
        eng.run_until_idle()
        out = r.result(timeout=1)
        np.testing.assert_array_equal(out, _want(lm, p, 8))
        # retired: nothing pinned, but the lifetime blocks stay cached
        assert all(c == 0 for c in pool.refcounts().values())
        assert pool.blocks_in_use() == 0
        lifetime = p.size + 8 - 1
        assert len(pool) == -(-lifetime // 4)  # ceil
        # follow-on turn: reuse reaches past the prompt into the
        # generated suffix
        p2 = np.concatenate([p, out[:6]])
        eng2 = ContinuousBatcher(model, variables, max_rows=2,
                                 paged_kv=pool)
        r2 = eng2.submit(p2, max_new_tokens=4)
        eng2.run_until_idle()
        np.testing.assert_array_equal(r2.result(timeout=1),
                                      _want(lm, p2, 4))
        assert eng2.prefill_tokens_reused > p.size

    def test_identical_rows_share_growing_chains(self, lm):
        """Two rows greedily decoding the SAME prompt extend the same
        partial tail every tick — the extend path must SHARE the
        identical extension (refcount bump), never republish over it
        (the overwrite would orphan the other row's refcount and a
        later sole-holder extend would drop a live block)."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=64)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool)
        p = _prompt(11, 9)
        ra = eng.submit(p, max_new_tokens=10)
        rb = eng.submit(p, max_new_tokens=10)
        eng.run_until_idle()
        want = _want(lm, p, 10)
        np.testing.assert_array_equal(ra.result(timeout=1), want)
        np.testing.assert_array_equal(rb.result(timeout=1), want)
        assert all(c == 0 for c in pool.refcounts().values())

    def test_block_budget_defers_admission_until_blocks_free(self, lm):
        """Block-budgeted admission: with the pool the working-set
        ledger, a request only admits when its prompt+budget blocks fit
        — the second request WAITS for the first to retire instead of
        over-filling the pool, and the pinned set never exceeds
        capacity."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=7)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool, block_budget=True)
        pa, pb = _prompt(12, 10), _prompt(13, 10)
        ra = eng.submit(pa, max_new_tokens=8)   # 18 tokens -> 5 blocks
        rb = eng.submit(pb, max_new_tokens=8)
        eng.tick()
        # only one fits: the second stays queued, no slot-squatting
        assert ra.slot >= 0 and rb.slot == -1
        peak = 0
        while eng.tick():
            peak = max(peak, pool.blocks_in_use())
        assert peak <= pool.capacity_blocks
        np.testing.assert_array_equal(ra.result(timeout=1),
                                      _want(lm, pa, 8))
        np.testing.assert_array_equal(rb.result(timeout=1),
                                      _want(lm, pb, 8))

    def test_block_budget_rejects_impossible_request(self, lm):
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=3)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                paged_kv=pool, block_budget=True)
        with pytest.raises(ValueError, match="beyond the pool"):
            eng.submit(_prompt(14, 10), max_new_tokens=8)


# ------------------------------------------------- adoption by digest


class TestChainAdoption:
    def test_adopt_gather_release_roundtrip(self):
        pool = PagedKVPool(block_size=4, capacity_blocks=32)
        ids = np.arange(1, 11, dtype=np.int32)
        kv = {"layer_0/attention/cached_key":
              np.arange(10, dtype=np.float32).reshape(10, 1, 1)}
        refs = pool.insert(ids, kv)
        # a second process-side consumer re-acquires the chain BY DIGEST
        pool.adopt(refs)
        got_ids, got_kv = pool.gather(refs)
        np.testing.assert_array_equal(got_ids, ids)
        np.testing.assert_array_equal(
            got_kv["layer_0/attention/cached_key"][:, 0, 0],
            np.arange(10))
        assert pool.chain_info(refs) == (10, 2)
        pool.release(refs)
        assert pool.blocks_in_use() > 0     # adopter still holds
        pool.release(refs)
        assert pool.blocks_in_use() == 0

    def test_adopt_missing_block_raises(self):
        pool = PagedKVPool(block_size=4, capacity_blocks=32)
        with pytest.raises(KeyError):
            pool.adopt([b"nope"])


# -------------------------------------- speculative x chunked prefill


class TestSpecChunkedComposition:
    @pytest.mark.parametrize("plen,budget", [(5, 10), (17, 8), (23, 6)])
    def test_token_identical_to_plain_greedy(self, lm, plen, budget):
        """ISSUE 13 tentpole (b): speculative decode composed with
        chunked prefill stays TOKEN-IDENTICAL to the non-speculative
        greedy path — the draft prefills over the same chunk schedule
        and only ever shapes acceptance speed."""
        model, variables = lm
        p = _prompt(30 + plen, plen)
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                prefill_chunk=4, draft_module=model,
                                draft_variables=variables, gamma=3)
        req = eng.submit(p, max_new_tokens=budget)
        eng.run_until_idle()
        np.testing.assert_array_equal(req.result(timeout=1),
                                      _want(lm, p, budget))

    def test_composes_with_paged_reuse(self, lm):
        """spec x chunked x paged: the second shared-prefix request
        seeds the target from the pool and computes only its suffix —
        tokens still exactly solo generate's."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=128)
        mk = lambda: ContinuousBatcher(  # noqa: E731
            model, variables, max_rows=2, prefill_chunk=4, paged_kv=pool,
            draft_module=model, draft_variables=variables, gamma=3)
        sys_p = _prompt(40, 12)
        a = np.concatenate([sys_p, _prompt(41, 4)])
        b = np.concatenate([sys_p, _prompt(42, 4)])
        eng = mk()
        ra = eng.submit(a, max_new_tokens=8)
        eng.run_until_idle()
        eng2 = mk()
        rb = eng2.submit(b, max_new_tokens=8)
        eng2.run_until_idle()
        assert eng2.prefill_tokens_reused == sys_p.size
        assert eng2.prefill_tokens_total == 4
        np.testing.assert_array_equal(ra.result(timeout=1),
                                      _want(lm, a, 8))
        np.testing.assert_array_equal(rb.result(timeout=1),
                                      _want(lm, b, 8))
        assert all(c == 0 for c in pool.refcounts().values())

    def test_spec_rows_advance_during_chunked_admission(self, lm):
        """The stall bound survives the composition: while a long
        prompt admits chunk-by-chunk (target + draft), an in-flight
        speculative row keeps emitting every round."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                prefill_chunk=4, draft_module=model,
                                draft_variables=variables, gamma=3)
        fast = eng.submit(_prompt(50, 4), max_new_tokens=40)
        eng.tick()
        long_req = eng.submit(_prompt(51, 30), max_new_tokens=4)
        while long_req.t_first is None:
            before = len(fast.tokens)
            eng.tick()
            if fast.done.is_set():
                break
            assert len(fast.tokens) > before, (
                "speculative row stalled during chunked admission")
        eng.run_until_idle()
        np.testing.assert_array_equal(
            long_req.result(timeout=1), _want(lm, _prompt(51, 30), 4))


# -------------------------------------------------- disaggregated tier


def _disagg(lm, pool, prefill=1, decode=2):
    model, variables = lm

    def mk(**kw):
        return ContinuousBatcher(model, variables, max_rows=2,
                                 paged_kv=pool, prefill_chunk=4, **kw)

    reps = ([(f"prefill-{i}", mk(max_chunks_per_tick=2), "prefill")
             for i in range(prefill)]
            + [(f"decode-{i}", mk(), "decode") for i in range(decode)])
    return FleetRouter(reps)


class TestDisaggregatedTier:
    def test_long_prompts_never_occupy_a_decode_slot(self, lm):
        """The tier contract: every prompt prefills on the prefill tier
        (budget-1 + keep_chain), the chain hands off through the shared
        pool, and the decode tier computes ZERO prompt positions —
        outputs exactly solo generate's."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=512)
        router = _disagg(lm, pool)
        prompts = [_prompt(60 + i, 10 + 4 * (i % 3)) for i in range(6)]
        handles = [router.submit(p, max_new_tokens=8) for p in prompts]
        router.run_until_idle()
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.result(timeout=1),
                                          _want(lm, p, 8))
        assert router.metrics["prefill_handoffs_total"] == 6
        decode_computed = sum(
            r.engine.prefill_tokens_total for r in router.replicas
            if r.role == "decode")
        assert decode_computed == 0
        assert all(c == 0 for c in pool.refcounts().values())

    @pytest.fixture(scope="class")
    def kill_drill(self, lm):
        """ISSUE 13's seeded kill drill, run once: a prefill replica
        and two decode replicas on one pool, decode-0 killed at tick 12
        while it carries rows; the fleet's failure counter is sampled
        every tick and the zero-drop objective evaluated afterwards."""
        from tests.serving_fixtures import ZeroDropWatch

        watch = ZeroDropWatch()
        pool = PagedKVPool(block_size=4, capacity_blocks=512)
        router = _disagg(lm, pool)
        prompts = make_prompts(10, seed=7, vocab=512, prompt_len=6,
                               shared_prefix=8)
        report = run_loadtest_sync(
            router, prompts, seed=7, mean_gap_ticks=0.8, new_tokens=8,
            kill_at_tick=12, kill_replica="decode-0", on_tick=watch.on_tick)
        return {
            **report.summary(),
            "handoffs": router.metrics["prefill_handoffs_total"],
            "decode_tier_prefill_tokens": sum(
                r.engine.prefill_tokens_total for r in router.replicas
                if r.role == "decode"),
            "zero_drop": watch.verdict(),
        }

    KILL_DRILL = {
        "zero_drops": lambda s: s["dropped"] == 0,
        "every_admission_completed": lambda s: s["completed"] == 10,
        "the_kill_requeued_work": lambda s: s["requeued"] >= 1,
        "resumed_from_surviving_kv":
            lambda s: s["resumed"] >= 1 and s["resumed_tokens"] >= 1,
        # the PR-9 baseline re-decoded every requeue from scratch
        "fewer_scratch_redecodes_than_requeues":
            lambda s: s["requeued"] - s["resumed"] < s["requeued"],
        # the tier contract holds through the kill: every prompt was
        # prefilled on the prefill tier and handed off by digest, and
        # the decode tier computed no prompt position
        "every_prompt_handed_off": lambda s: s["handoffs"] == 10,
        "decode_tier_prefilled_nothing":
            lambda s: s["decode_tier_prefill_tokens"] == 0,
        "zero_drop_objective_quiet":
            lambda s: s["zero_drop"]["samples"] > 0
            and s["zero_drop"]["fired"] is False
            and s["zero_drop"]["alerts"] == [],
    }

    @pytest.mark.parametrize("contract", list(KILL_DRILL))
    def test_kill_mid_decode_resumes_from_surviving_chain(
            self, kill_drill, contract):
        """ISSUE 13 acceptance: the seeded kill drill shows dropped=0
        AND >=1 request resumed from surviving KV blocks, with the
        re-decoded-from-scratch count STRICTLY below the PR-9 baseline
        (which re-decoded every requeue)."""
        assert self.KILL_DRILL[contract](kill_drill), kill_drill

    def test_tier_wipe_degrades_to_capable_survivors(self, lm):
        """Roles are routing policy, not capability: killing the ONLY
        prefill replica leaves the decode tier prefilling for itself —
        requests still complete exactly, none dropped."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=512)
        router = _disagg(lm, pool, prefill=1, decode=2)
        router.kill_replica("prefill-0")
        p = _prompt(70, 12)
        h = router.submit(p, max_new_tokens=6)
        router.run_until_idle()
        np.testing.assert_array_equal(h.result(timeout=1),
                                      _want(lm, p, 6))

    def test_disagg_guards(self, lm):
        model, variables = lm
        mk = lambda **kw: ContinuousBatcher(  # noqa: E731
            model, variables, max_rows=2, **kw)
        # no shared pool: the handoff has no medium
        with pytest.raises(ValueError, match="shared paged_kv"):
            FleetRouter([("p", mk(paged_kv=PagedKVPool()), "prefill"),
                         ("d", mk(paged_kv=PagedKVPool()), "decode")])
        with pytest.raises(ValueError, match="shared paged_kv"):
            FleetRouter([("p", mk(), "prefill"), ("d", mk(), "decode")])
        pool = PagedKVPool()
        with pytest.raises(ValueError, match="decode-capable"):
            FleetRouter([("p", mk(paged_kv=pool), "prefill")])
        with pytest.raises(ValueError, match="unknown replica role"):
            FleetRouter([("x", mk(), "verifier")])
        # scale-out holds the same invariants: a decode-capable replica
        # OFF the shared pool would crash the handoff/resume dispatch
        router = FleetRouter([("p", mk(paged_kv=pool), "prefill"),
                              ("d", mk(paged_kv=pool), "decode")])
        with pytest.raises(ValueError, match="shared paged_kv"):
            router.add_replica(mk())
        with pytest.raises(ValueError, match="shared paged_kv"):
            router.add_replica(mk(paged_kv=PagedKVPool()), role="decode")
        with pytest.raises(ValueError, match="unknown replica role"):
            router.add_replica(mk(paged_kv=pool), role="verifier")
        rep = router.add_replica(mk(paged_kv=pool), role="decode")
        assert rep.role == "decode" and len(router.replicas) == 3

    def test_frozen_prefill_chain_takes_chainless_fallback(self, lm):
        """A prompt that is a strict PREFIX of an in-flight request's
        (ending mid-block) publishes a FROZEN chain — insert stops at
        the covered-by-live-sibling boundary. The handoff must take the
        chainless fallback (frozen chains can never reach resume_from:
        the engine refuses them, and on the engine-thread callback that
        refusal would strand the client forever). Both requests still
        complete exactly; only the unfrozen chain counts a handoff."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=512)
        router = _disagg(lm, pool, prefill=1, decode=1)
        a = _prompt(75, 10)
        b = a[:9]          # strict prefix, partial tail [8:9)
        streamed = []
        ha = router.submit(a, max_new_tokens=8)
        hb = router.submit(b, max_new_tokens=6,
                           on_token=lambda _h, t: streamed.append(int(t)))
        # FIFO chunking publishes A first; B's publish then finds A's
        # LIVE partial [8:10) covering its [8:9) tail -> B freezes
        router.run_until_idle()
        np.testing.assert_array_equal(ha.result(timeout=1),
                                      _want(lm, a, 8))
        want_b = _want(lm, b, 6)
        np.testing.assert_array_equal(hb.result(timeout=1), want_b)
        assert router.metrics["prefill_handoffs_total"] == 1
        # the fallback re-decodes B's first token, but the client stream
        # carries each position once
        assert streamed == [int(t) for t in want_b]
        assert all(c == 0 for c in pool.refcounts().values())

    def test_kill_between_handoff_and_seating_still_resumes(self, lm):
        """ISSUE 13 edge: the decode replica dies while the handed-off
        request is still QUEUED on it (never seated). The engine's
        _fail_all transfers the chain, and the router must judge the
        rescue by ITS OWN token record (the client already streamed the
        prefill leg's first token) — the surviving chain resumes, and
        the client's stream carries no duplicate."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=512)
        router = _disagg(lm, pool, prefill=1, decode=2)
        pre = router.replicas[0].engine
        p = _prompt(76, 10)
        streamed = []
        h = router.submit(p, max_new_tokens=6,
                          on_token=lambda _h, t: streamed.append(int(t)))
        # drive ONLY the prefill engine: the handoff lands the request
        # on decode-0's queue, where it is never seated
        for _ in range(12):
            pre.tick()
            if router.metrics["prefill_handoffs_total"]:
                break
        assert router.metrics["prefill_handoffs_total"] == 1
        router.kill_replica("decode-0")
        router.run_until_idle()
        want = _want(lm, p, 6)
        np.testing.assert_array_equal(h.result(timeout=1), want)
        assert router.metrics["requeues_resumed_total"] == 1
        # no re-prefill on the rescue, and no duplicated first token
        assert streamed == [int(t) for t in want]
        assert all(c == 0 for c in pool.refcounts().values())

    def test_mixed_mode_kill_also_resumes(self, lm):
        """The resume rescue is not disagg-only: a mixed fleet's kill
        requeue resumes from the chain too (TTFT preserved — the
        client's already-received tokens stay received)."""
        model, variables = lm
        pool = PagedKVPool(block_size=4, capacity_blocks=512)
        router = FleetRouter(
            [ContinuousBatcher(model, variables, max_rows=2,
                               paged_kv=pool, prefill_chunk=4)
             for _ in range(3)])
        prompts = make_prompts(12, seed=7, vocab=512, prompt_len=4,
                               shared_prefix=8)
        report = run_loadtest_sync(router, prompts, seed=7,
                                   mean_gap_ticks=0.7, new_tokens=6,
                                   kill_at_tick=5, kill_replica=1)
        s = report.summary()
        assert s["dropped"] == 0 and s["completed"] == 12
        assert s["requeued"] >= 1 and s["resumed"] >= 1
        assert router.metrics["requeue_resumed_tokens_total"] \
            == s["resumed_tokens"]
