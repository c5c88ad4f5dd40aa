"""Continuous batching engine (serving/continuous.py): per-row exactness
vs generate(), iteration-level scheduling (slots readmit mid-flight), and
the threaded serving mode."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.gpt import GPTConfig, GPTLM, generate
from kubeflow_tpu.serving.continuous import ContinuousBatcher


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


class TestExactness:
    def test_mixed_rows_match_solo_greedy_decode(self, lm):
        """The defining property: every row of a mixed batch — different
        prompt lengths, different budgets, rows admitted while others are
        mid-flight — yields EXACTLY generate()'s solo greedy decode."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=3)
        jobs = []
        for seed, plen, budget in ((1, 4, 12), (2, 7, 20), (3, 5, 6),
                                   (4, 9, 16), (5, 3, 24), (6, 6, 9)):
            p = _prompt(seed, plen)
            jobs.append((p, budget, eng.submit(p, max_new_tokens=budget)))
        eng.run_until_idle()
        for p, budget, req in jobs:
            want = np.asarray(generate(
                model, variables, p[None, :], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(req.result(timeout=1), want)

    def test_eos_retires_row_early(self, lm):
        model, variables = lm
        p = _prompt(7, 5)
        plain = np.asarray(generate(model, variables, p[None, :],
                                    max_new_tokens=16))[0]
        eos = int(plain[4])  # provably emitted by step 5
        # the FIRST occurrence wins (it may precede step 5: greedy decode
        # numerics vary across jax/XLA versions and repeated tokens are
        # common on the tiny fixture) — same contract as the engine-list
        # eos test in test_gpt_generate.py
        first = int(np.argmax(plain == eos))
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                eos_token_id=eos)
        req = eng.submit(p, max_new_tokens=16)
        eng.run_until_idle()
        out = req.result(timeout=1)
        assert out[-1] == eos and len(out) == first + 1  # stopped AT eos
        np.testing.assert_array_equal(out, plain[:first + 1])

    def test_moe_rows_match_solo_decode(self):
        """MoE models serve through the engine EXACTLY (VERDICT r4 #6):
        decode routes dropless (parallel/moe.py), so a row's output never
        depends on which other rows share the batch — pinned per row
        against solo generate() with mixed in-flight depths."""
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96, moe_experts=4,
                             moe_top_k=2)
        model = GPTLM(cfg, pad_token_id=-1)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 4), jnp.int32))
        eng = ContinuousBatcher(model, variables, max_rows=2)
        jobs = []
        for seed, plen, budget in ((31, 4, 10), (32, 7, 14), (33, 5, 6),
                                   (34, 6, 8)):
            p = _prompt(seed, plen)
            jobs.append((p, budget, eng.submit(p, max_new_tokens=budget)))
        eng.run_until_idle()
        for p, budget, req in jobs:
            want = np.asarray(generate(
                model, variables, p[None, :], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(req.result(timeout=1), want)

    def test_budget_validated(self, lm):
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(_prompt(1, 80), max_new_tokens=32)


class TestScheduling:
    def test_interleaving_beats_sequential_dispatch_count(self, lm):
        """N requests through R rows must take far fewer decode dispatches
        than N solo decodes — the whole point of iteration-level
        scheduling (each dispatch advances up to R rows at once)."""
        model, variables = lm
        budget, n_req, rows = 16, 8, 4
        eng = ContinuousBatcher(model, variables, max_rows=rows)
        for seed in range(n_req):
            eng.submit(_prompt(seed + 10, 5), max_new_tokens=budget)
        eng.run_until_idle()
        sequential_steps = n_req * (budget - 1)  # generate(): n-1 steps each
        assert eng.step_count <= sequential_steps // 2, (
            eng.step_count, sequential_steps)

    def test_slot_readmission_mid_flight(self, lm):
        """A short row retires and its slot admits a queued request while
        the long row is still decoding — pinned by the dispatch count:
        short(4) + queued(4) overlap the long row's 24 steps entirely, so
        the total stays ~24, far below the 32 a blocking batch would
        need."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2)
        long_req = eng.submit(_prompt(20, 5), max_new_tokens=24)
        eng.submit(_prompt(21, 5), max_new_tokens=4)
        eng.submit(_prompt(22, 5), max_new_tokens=4)  # queued: no free row
        eng.run_until_idle()
        assert long_req.result(timeout=1).shape == (24,)
        assert eng.step_count <= 26  # 23 (long) + admission slack


class TestBucketedPrefill:
    def test_outputs_exact_and_executables_bounded(self, lm):
        """Bucketed prefill: assorted prompt lengths share per-bucket
        executables (compile cache bounded by the bucket list, not by
        distinct lengths) and every output still equals solo greedy
        decode."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                prefill_buckets=(8, 16))
        jobs = []
        for seed, plen in ((90, 3), (91, 5), (92, 8), (93, 11), (94, 16),
                           (95, 6)):
            p = _prompt(seed, plen)
            jobs.append((p, eng.submit(p, max_new_tokens=9)))
        eng.run_until_idle()
        for p, req in jobs:
            want = np.asarray(generate(
                model, variables, p[None, :], max_new_tokens=9))[0]
            np.testing.assert_array_equal(req.result(timeout=1), want)
        # 6 distinct lengths -> at most 2 prefill executables
        assert set(eng._prefill_cache) <= {8, 16}

    def test_oversized_prompt_and_rolling_refused(self, lm):
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                prefill_buckets=(8,))
        with pytest.raises(ValueError, match="largest prefill bucket"):
            eng.submit(_prompt(96, 12), max_new_tokens=4)
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96,
                             attention_window=6, kv_cache_capacity=12)
        rolling = GPTLM(cfg, pad_token_id=-1)
        rv = rolling.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
        with pytest.raises(ValueError, match="rolling"):
            ContinuousBatcher(rolling, rv, prefill_buckets=(8,))


class TestResilience:
    def test_over_budget_prompt_rejected_at_submit(self):
        """Rolling-cache prefill budget is the CALLER's error at submit
        time — not a trace-time exception killing the engine thread."""
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96,
                             attention_window=6, kv_cache_capacity=12)
        model = GPTLM(cfg, pad_token_id=-1)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 4), jnp.int32))
        eng = ContinuousBatcher(model, variables, max_rows=2)
        with pytest.raises(ValueError, match="prefill budget"):
            eng.submit(_prompt(80, 8), max_new_tokens=4)  # budget = 7

    def test_poisoned_tick_fails_requests_not_the_engine(self, lm):
        """An exception inside a serving-thread tick must unblock the
        carried requests with the error AND leave the engine serving
        fresh requests — not die silently while clients hang."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2).start()
        try:
            boom = {"armed": True}
            orig = eng._prefill

            def exploding(ids):
                if boom["armed"]:
                    boom["armed"] = False
                    raise RuntimeError("injected prefill failure")
                return orig(ids)

            eng._prefill = exploding
            bad = eng.submit(_prompt(81, 5), max_new_tokens=6)
            with pytest.raises(RuntimeError, match="injected"):
                bad.result(timeout=30)
            # the engine survived: a fresh request completes correctly
            p = _prompt(82, 5)
            good = eng.submit(p, max_new_tokens=6)
            want = np.asarray(generate(
                model, variables, p[None, :], max_new_tokens=6))[0]
            np.testing.assert_array_equal(good.result(timeout=60), want)
        finally:
            eng.stop()


class TestRollingCacheEngine:
    def test_engine_over_rolling_cache_model(self):
        """Continuous batching composes with the rolling KV cache: row
        splices carry C-slot buffers and outputs still match solo greedy
        decode (which itself matches the full-cache model)."""
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96,
                             attention_window=6, kv_cache_capacity=14)
        model = GPTLM(cfg, pad_token_id=-1)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 5), jnp.int32))
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                steps_per_tick=3)
        jobs = [(p, b, eng.submit(p, max_new_tokens=b))
                for p, b in ((_prompt(70, 5), 20), (_prompt(71, 8), 12),
                             (_prompt(72, 4), 25))]
        eng.run_until_idle()
        for p, budget, req in jobs:
            want = np.asarray(generate(
                model, variables, p[None, :], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(req.result(timeout=1), want)


class TestMultiStepTicks:
    def test_exactness_and_dispatch_amortization(self, lm):
        """steps_per_tick=4: outputs stay EXACTLY solo greedy decode
        (mid-scan retirement discards the tail) while dispatches shrink
        ~4x — one host round-trip amortized over four tokens a row."""
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2,
                                steps_per_tick=4)
        jobs = []
        for seed, plen, budget in ((60, 4, 13), (61, 7, 6), (62, 5, 21),
                                   (63, 6, 10)):
            p = _prompt(seed, plen)
            jobs.append((p, budget, eng.submit(p, max_new_tokens=budget)))
        eng.run_until_idle()
        for p, budget, req in jobs:
            want = np.asarray(generate(
                model, variables, p[None, :], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(req.result(timeout=1), want)
        eng1 = ContinuousBatcher(model, variables, max_rows=2)
        for seed, plen, budget in ((60, 4, 13), (61, 7, 6), (62, 5, 21),
                                   (63, 6, 10)):
            eng1.submit(_prompt(seed, plen), max_new_tokens=budget)
        eng1.run_until_idle()
        assert eng.step_count * 2 < eng1.step_count

    def test_sampling_keys_consistent_across_tick_sizes(self, lm):
        """The per-step key schedule is position-based, so the SAME request
        key yields the SAME sampled sequence whether ticks carry 1 or 4
        steps."""
        model, variables = lm
        key = jax.random.PRNGKey(9)
        p = _prompt(64, 5)
        outs = []
        for t in (1, 4):
            eng = ContinuousBatcher(model, variables, max_rows=2,
                                    steps_per_tick=t, top_k=8)
            req = eng.submit(p, max_new_tokens=12, temperature=0.9,
                             key=key)
            eng.run_until_idle()
            outs.append(req.result(timeout=1))
        np.testing.assert_array_equal(outs[0], outs[1])


class TestServingIntegration:
    def test_gpt_lm_predictor_with_continuous_engine(self, tmp_path, lm):
        """generate config {continuous: true} routes the gpt-lm predictor
        through the engine: concurrent predicts from separate threads
        share the rows and every output matches the plain jit predictor."""
        from kubeflow_tpu.serving.model import JaxModel, save_predictor

        model, variables = lm
        d = save_predictor(
            tmp_path / "gpt-cb", "gpt-lm", dict(variables),
            np.zeros((1, 6), np.int32),
            generate={"max_new_tokens": 8, "continuous": True,
                      "continuous_rows": 3},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        jm = JaxModel("gpt-cb", d)
        jm.load()
        assert jm._engine is not None
        try:
            outs = {}

            def client(seed):
                p = _prompt(seed, 6)[None, :]
                outs[seed] = (p, np.asarray(jm(p)["predictions"]))

            threads = [threading.Thread(target=client, args=(s,))
                       for s in range(40, 45)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert len(outs) == 5
            for p, got in outs.values():
                want = np.asarray(generate(model, variables, p,
                                           max_new_tokens=8))
                np.testing.assert_array_equal(got, want)
        finally:
            jm._engine.stop()

    def test_engine_metrics_on_server(self, tmp_path, lm):
        """/metrics exposes the engine's scheduler gauges for
        continuous-batching models."""
        import urllib.request

        from kubeflow_tpu.serving.model import JaxModel, save_predictor
        from kubeflow_tpu.serving.server import ModelServer

        model, variables = lm
        d = save_predictor(
            tmp_path / "gpt-m", "gpt-lm", dict(variables),
            np.zeros((1, 6), np.int32),
            generate={"max_new_tokens": 4, "continuous": True,
                      "continuous_rows": 2},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        jm = JaxModel("gpt-m", d)
        jm.load()
        try:
            srv = ModelServer(port=0)
            srv.register(jm)
            srv.start()
            try:
                jm(np.asarray(_prompt(90, 6))[None, :])
                with urllib.request.urlopen(
                        f"{srv.url}/metrics", timeout=10) as r:
                    text = r.read().decode()
                assert 'kfserving_engine_rows_total{model="gpt-m"} 2' in text
                assert "kfserving_engine_decode_dispatches_total" in text
                assert "kfserving_engine_queue_depth" in text
            finally:
                srv.stop()
        finally:
            jm._engine.stop()

    def test_continuous_rejects_beam_config(self, tmp_path, lm):
        from kubeflow_tpu.serving.model import JaxModel, save_predictor

        model, variables = lm
        d = save_predictor(
            tmp_path / "gpt-bad", "gpt-lm", dict(variables),
            np.zeros((1, 6), np.int32),
            generate={"max_new_tokens": 8, "continuous": True,
                      "num_beams": 4},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        with pytest.raises(ValueError, match="beam"):
            JaxModel("gpt-bad", d).load()


class TestSampling:
    def test_sampling_deterministic_per_key_and_mixes_with_greedy(self, lm):
        """Sampling rows draw with per-request keys (same key -> same
        output) while greedy rows in the SAME batch still match solo
        greedy decode exactly."""
        model, variables = lm
        key = jax.random.PRNGKey(42)
        p_greedy, p_sample = _prompt(50, 6), _prompt(51, 6)

        def run():
            eng = ContinuousBatcher(model, variables, max_rows=2, top_k=8)
            rg = eng.submit(p_greedy, max_new_tokens=10)
            rs = eng.submit(p_sample, max_new_tokens=10,
                            temperature=0.8, key=key)
            eng.run_until_idle()
            return rg.result(timeout=1), rs.result(timeout=1)

        g1, s1 = run()
        g2, s2 = run()
        want = np.asarray(generate(
            model, variables, p_greedy[None, :], max_new_tokens=10))[0]
        np.testing.assert_array_equal(g1, want)  # greedy row unaffected
        np.testing.assert_array_equal(s1, s2)    # same key -> same draw
        np.testing.assert_array_equal(g1, g2)

    def test_different_keys_vary(self, lm):
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=2, top_k=0,
                                seed=7)
        p = _prompt(52, 6)
        reqs = [eng.submit(p, max_new_tokens=16, temperature=1.0)
                for _ in range(4)]
        eng.run_until_idle()
        outs = {tuple(r.result(timeout=1).tolist()) for r in reqs}
        assert len(outs) > 1  # auto-derived per-request keys differ


class TestPlatformE2E:
    def test_continuous_predictor_through_platform(self, tmp_path, lm):
        """Continuous batching through the WHOLE platform: storage pull ->
        server pod (subprocess) -> concurrent v1 predicts -> every client
        gets exactly its solo greedy decode."""
        import json as _json
        import urllib.request

        from kubeflow_tpu.client import Platform
        from kubeflow_tpu.controller.fakecluster import ObjectMeta
        from kubeflow_tpu.serving.api import (
            InferenceService,
            InferenceServiceSpec,
            PredictorRuntime,
            PredictorSpec,
        )
        from kubeflow_tpu.serving.client import ServingClient
        from kubeflow_tpu.serving.controller import (
            ISVC_LABEL,
            PORT_ANNOTATION,
        )
        from kubeflow_tpu.serving.model import save_predictor

        model, variables = lm
        src = save_predictor(
            tmp_path / "src", "gpt-lm", dict(variables),
            np.zeros((1, 6), np.int32),
            generate={"max_new_tokens": 5, "continuous": True,
                      "continuous_rows": 3, "continuous_steps_per_tick": 2},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        with Platform(log_dir=str(tmp_path / "logs")) as p:
            sc = ServingClient(p)
            sc.create(InferenceService(
                metadata=ObjectMeta(name="llm-cb"),
                spec=InferenceServiceSpec(predictor=PredictorSpec(
                    runtime=PredictorRuntime.JAX,
                    storage_uri=f"file://{src}",
                    device="cpu",
                )),
            ))
            sc.wait_ready("llm-cb", timeout_s=180)
            pods = p.cluster.list(
                "pods",
                lambda q: q.metadata.labels.get(ISVC_LABEL) == "llm-cb",
            )
            port = pods[0].metadata.annotations[PORT_ANNOTATION]
            outs = {}

            def client(seed):
                prm = _prompt(seed, 6)[None, :]
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/models/llm-cb:predict",
                    data=_json.dumps(
                        {"instances": np.asarray(prm).tolist()}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                body = _json.loads(
                    urllib.request.urlopen(req, timeout=120).read())
                outs[seed] = (prm, np.asarray(body["predictions"]))

            threads = [threading.Thread(target=client, args=(s,))
                       for s in range(100, 104)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
        assert len(outs) == 4
        for prm, got in outs.values():
            want = np.asarray(generate(model, variables, prm,
                                       max_new_tokens=5))
            np.testing.assert_array_equal(got, want)


class TestServingMode:
    def test_threaded_engine_serves_concurrent_clients(self, lm):
        model, variables = lm
        eng = ContinuousBatcher(model, variables, max_rows=4).start()
        try:
            results = {}

            def client(seed):
                p = _prompt(seed, 6)
                req = eng.submit(p, max_new_tokens=10)
                results[seed] = (p, req.result(timeout=60))

            threads = [threading.Thread(target=client, args=(s,))
                       for s in range(30, 36)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=90)
            assert len(results) == 6
            for p, got in results.values():
                want = np.asarray(generate(
                    model, variables, p[None, :], max_new_tokens=10))[0]
                np.testing.assert_array_equal(got, want)
        finally:
            eng.stop()


class TestSpeculative:
    """Speculative decoding INSIDE the engine (VERDICT r4 #5): per-row
    draft/verify with row-local cache_index rewind under the full cache."""

    @pytest.fixture(scope="class")
    def spec(self):
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96)
        target = GPTLM(cfg, pad_token_id=-1)
        tvars = target.init(jax.random.PRNGKey(0),
                            jnp.ones((1, 5), jnp.int32))
        # distinct draft (different seed => imperfect agreement: rows
        # genuinely diverge in accepted length every round)
        dvars = target.init(jax.random.PRNGKey(9),
                            jnp.ones((1, 5), jnp.int32))
        return target, tvars, dvars

    def test_rows_match_solo_speculative_and_greedy(self, spec):
        """Defining property: every row of a mixed spec batch equals BOTH
        solo speculative_generate AND plain greedy generate() (speculative
        is target-exact), with rows at different depths mid-flight."""
        from kubeflow_tpu.models.speculative import speculative_generate

        target, tvars, dvars = spec
        eng = ContinuousBatcher(target, tvars, max_rows=3,
                                draft_module=target, draft_variables=dvars,
                                gamma=3)
        jobs = []
        for seed, plen, budget in ((1, 4, 12), (2, 7, 20), (3, 5, 6),
                                   (4, 9, 16), (5, 3, 24), (6, 6, 9)):
            p = _prompt(seed, plen)
            jobs.append((p, budget, eng.submit(p, max_new_tokens=budget)))
        eng.run_until_idle()
        for p, budget, req in jobs:
            got = req.result(timeout=1)
            want = np.asarray(generate(
                target, tvars, p[None, :], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(got, want)
            solo, _ = speculative_generate(
                target, tvars, target, dvars, jnp.asarray(p)[None, :],
                max_new_tokens=budget, gamma=3)
            np.testing.assert_array_equal(got, np.asarray(solo)[0])

    def test_dispatch_count_drops_vs_plain_continuous(self, spec):
        """Self-draft (perfect agreement) pins the mechanics: every round
        accepts gamma tokens, so the spec engine needs far fewer
        dispatches than the plain engine at the same budgets."""
        target, tvars, _ = spec
        prompts = [_prompt(s, 5) for s in range(4)]
        plain = ContinuousBatcher(target, tvars, max_rows=2)
        for p in prompts:
            plain.submit(p, max_new_tokens=16)
        plain.run_until_idle()
        spec_eng = ContinuousBatcher(target, tvars, max_rows=2,
                                     draft_module=target,
                                     draft_variables=tvars, gamma=3)
        reqs = [spec_eng.submit(p, max_new_tokens=16) for p in prompts]
        spec_eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            want = np.asarray(generate(
                target, tvars, p[None, :], max_new_tokens=16))[0]
            np.testing.assert_array_equal(r.result(timeout=1), want)
        # self-draft: each round emits gamma+1=4 tokens/row vs 1 for plain
        assert spec_eng.step_count * 3 <= plain.step_count, (
            spec_eng.step_count, plain.step_count)

    def test_spec_refusals(self, spec):
        target, tvars, dvars = spec
        # temperature > 0 rows are ACCEPTED since the r5 rowwise
        # rejection-sampling extension (TestSpeculativeSampledRows);
        # engine-level top_k remains refused with a draft
        with pytest.raises(ValueError, match="steps_per_tick"):
            ContinuousBatcher(target, tvars, max_rows=2, steps_per_tick=4,
                              draft_module=target, draft_variables=dvars)
        with pytest.raises(ValueError, match="prefill_buckets"):
            ContinuousBatcher(target, tvars, max_rows=2,
                              prefill_buckets=(16,),
                              draft_module=target, draft_variables=dvars)
        with pytest.raises(ValueError, match="gamma"):
            eng = ContinuousBatcher(target, tvars, max_rows=2,
                                    draft_module=target,
                                    draft_variables=dvars, gamma=8)
            # 5 + 85 + 9 > 96
            eng.submit(_prompt(1, 5), max_new_tokens=85)
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96,
                             attention_window=8, kv_cache_capacity=24)
        rolling = GPTLM(cfg, pad_token_id=-1)
        rvars = rolling.init(jax.random.PRNGKey(0),
                             jnp.ones((1, 5), jnp.int32))
        with pytest.raises(ValueError, match="rolling"):
            ContinuousBatcher(rolling, rvars, max_rows=2,
                              draft_module=rolling, draft_variables=rvars)

    def test_eos_mid_round_retires_exactly(self, spec):
        """EOS landing inside an accepted block must stop the row AT the
        eos token, matching generate(..., eos)'s trimmed output."""
        target, tvars, dvars = spec
        p = _prompt(7, 5)
        plain = np.asarray(generate(target, tvars, p[None, :],
                                    max_new_tokens=16))[0]
        eos = int(plain[4])
        first = int(np.argmax(plain == eos))  # first occurrence wins
        eng = ContinuousBatcher(target, tvars, max_rows=2, eos_token_id=eos,
                                draft_module=target, draft_variables=dvars,
                                gamma=3)
        req = eng.submit(p, max_new_tokens=16)
        eng.run_until_idle()
        out = req.result(timeout=1)
        assert out[-1] == eos and len(out) == first + 1
        np.testing.assert_array_equal(out, plain[:first + 1])

    def test_predictor_with_continuous_draft_dir(self, tmp_path, spec):
        """generate config {continuous: true, continuous_draft_dir: ...}
        routes the predictor through the SPECULATIVE engine; outputs
        equal the plain greedy predictor (target-exactness end-to-end
        through the serving surface)."""
        from kubeflow_tpu.serving.model import JaxModel, save_predictor

        target, tvars, dvars = spec
        ddir = save_predictor(
            tmp_path / "draft", "gpt-lm", {"params": dvars["params"]},
            np.zeros((1, 6), np.int32),
            generate={"max_new_tokens": 8},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        d = save_predictor(
            tmp_path / "gpt-spec", "gpt-lm", dict(tvars),
            np.zeros((1, 6), np.int32),
            generate={"max_new_tokens": 8, "continuous": True,
                      "continuous_rows": 2,
                      "continuous_draft_dir": str(ddir),
                      "speculative_gamma": 3},
            size="tiny", config={"dropout_rate": 0.0, "max_len": 96},
        )
        jm = JaxModel("gpt-spec", d)
        jm.load()
        assert jm._engine is not None and jm._engine.draft_module is not None
        try:
            p = _prompt(77, 6)[None, :]
            got = np.asarray(jm(p)["predictions"])
            want = np.asarray(generate(target, tvars, p, max_new_tokens=8))
            np.testing.assert_array_equal(got, want)
        finally:
            jm._engine.stop()


class TestSpeculativeSampledRows:
    """Sampled rows (temperature > 0) inside the speculative engine —
    the rowwise Leviathan/Chen rejection scheme, mixing freely with
    greedy rows in one executable."""

    @pytest.fixture(scope="class")
    def spec(self):
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=96)
        target = GPTLM(cfg, pad_token_id=-1)
        tvars = target.init(jax.random.PRNGKey(0),
                            jnp.ones((1, 5), jnp.int32))
        dvars = target.init(jax.random.PRNGKey(9),
                            jnp.ones((1, 5), jnp.int32))
        return target, tvars, dvars

    def test_self_draft_sampled_rows_accept_everything(self, spec):
        """p_d == p_t (draft IS the target) makes the acceptance ratio
        exactly 1: every proposal accepted, regardless of the uniforms."""
        target, tvars, _ = spec
        eng = ContinuousBatcher(target, tvars, max_rows=2,
                                draft_module=target, draft_variables=tvars,
                                gamma=3)
        req = eng.submit(_prompt(1, 5), max_new_tokens=12, temperature=1.0)
        eng.run_until_idle()
        assert len(req.result(timeout=1)) == 12
        # all-accept => ceil((12-1)/4) spec dispatches + 1 prefill-ish
        # round; the scheduling metric proves gamma-token strides
        assert eng.step_count <= 3

    def test_greedy_rows_stay_exact_when_mixed_with_sampled(self, spec):
        """The r5-session-1 contract survives the sampling extension:
        greedy rows in a batch that ALSO carries sampled rows still equal
        solo generate()."""
        target, tvars, dvars = spec
        eng = ContinuousBatcher(target, tvars, max_rows=3,
                                draft_module=target, draft_variables=dvars,
                                gamma=3)
        greedy_jobs = []
        for seed, plen, budget in ((1, 4, 12), (3, 5, 6)):
            p = _prompt(seed, plen)
            greedy_jobs.append((p, budget,
                                eng.submit(p, max_new_tokens=budget)))
        sampled = eng.submit(_prompt(2, 6), max_new_tokens=15,
                             temperature=0.9)
        eng.run_until_idle()
        for p, budget, req in greedy_jobs:
            want = np.asarray(generate(
                target, tvars, p[None, :], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(req.result(timeout=1), want)
        assert len(sampled.result(timeout=1)) == 15

    def test_all_greedy_batches_keep_specialized_executable(self, spec):
        """ADVICE r5: _spec_step is jit-specialized on a STATIC
        any-sampled flag. An all-greedy speculative deployment dispatches
        the cheap executable — no (R, G+1, V) softmaxes, no per-draft
        categorical draws ever traced — and its tokens are IDENTICAL to
        the general executable's greedy rows (which compute the sampling
        machinery and discard it via where(temps>0)). The first sampled
        admission retraces exactly once, like a new prefill bucket."""
        target, tvars, dvars = spec
        greedy_spec = ((1, 4, 8), (3, 5, 5))
        eng = ContinuousBatcher(
            target, tvars, max_rows=3, draft_module=target,
            draft_variables=dvars, gamma=3)
        # phase 1 — all-greedy batch: dispatches the SPECIALIZED
        # executable only
        jobs = [eng.submit(_prompt(seed, plen), max_new_tokens=budget)
                for seed, plen, budget in greedy_spec]
        eng.run_until_idle()
        specialized = [np.asarray(r.result(timeout=1)) for r in jobs]
        cheap_traced = getattr(eng._spec_step, "_cache_size", None)
        if cheap_traced is not None:
            assert eng._spec_step._cache_size() == 1
        # phase 2 — mix change: the SAME greedy prompts re-submitted
        # alongside a sampled row dispatch the general executable
        # (exactly one retrace, like a new prefill bucket)
        jobs = [eng.submit(_prompt(seed, plen), max_new_tokens=budget)
                for seed, plen, budget in greedy_spec]
        eng.submit(_prompt(2, 6), max_new_tokens=8, temperature=0.9)
        eng.run_until_idle()
        general = [np.asarray(r.result(timeout=1)) for r in jobs]
        if cheap_traced is not None:
            assert eng._spec_step._cache_size() == 2
        # identical tokens both ways — the specialization is purely a
        # cost specialization, never a semantic one
        for a, b in zip(specialized, general):
            np.testing.assert_array_equal(a, b)

    def test_sampled_rows_deterministic_per_key(self, spec):
        target, tvars, dvars = spec

        def run(key_seed):
            eng = ContinuousBatcher(
                target, tvars, max_rows=2, draft_module=target,
                draft_variables=dvars, gamma=2)
            req = eng.submit(_prompt(4, 5), max_new_tokens=10,
                             temperature=0.8,
                             key=jax.random.PRNGKey(key_seed))
            eng.run_until_idle()
            return req.result(timeout=1)

        a, b, c = run(7), run(7), run(8)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_sampled_row_distribution_matches_direct_sampling(self):
        """Two-sample TV check: the SECOND emitted token of an engine
        sampled-spec row (produced by the first rejection round through a
        mismatched draft) vs direct target sampling, N=400 requests
        through ONE engine (rows recycle; per-request keys)."""
        cfg = GPTConfig.tiny(dropout_rate=0.0, max_len=32, vocab_size=8,
                             hidden_size=16, num_heads=2, mlp_dim=32,
                             num_layers=1)
        target = GPTLM(cfg, pad_token_id=-1)
        prompt = np.array([3, 5, 1], np.int32)
        tvars = target.init(jax.random.PRNGKey(10), prompt[None, :])
        dvars = target.init(jax.random.PRNGKey(11), prompt[None, :])
        eng = ContinuousBatcher(target, tvars, max_rows=4,
                                draft_module=target, draft_variables=dvars,
                                gamma=2)
        n = 400
        reqs = [eng.submit(prompt, max_new_tokens=2, temperature=1.0,
                           key=jax.random.PRNGKey(1000 + i))
                for i in range(n)]
        eng.run_until_idle()
        toks = np.stack([r.result(timeout=5) for r in reqs])  # (n, 2)
        ref = jax.jit(jax.vmap(lambda key: generate(
            target, tvars, jnp.asarray(prompt)[None, :], 2,
            temperature=1.0, rng=key)[0]))(
                jax.random.split(jax.random.PRNGKey(13), n))
        ref = np.asarray(ref)
        for pos in (0, 1):
            hs = np.bincount(toks[:, pos], minlength=8) / n
            hr = np.bincount(ref[:, pos], minlength=8) / n
            tv = 0.5 * np.abs(hs - hr).sum()
            assert tv < 0.12, (pos, tv, hs, hr)


    def test_top_k_refused_only_for_sampled_submit(self, spec):
        """Engine-level top_k + draft still CONSTRUCTS and serves greedy
        traffic (deployed greedy configs must not break at load); the
        refusal fires at submit() for sampled rows only."""
        target, tvars, dvars = spec
        eng = ContinuousBatcher(target, tvars, max_rows=2, top_k=5,
                                draft_module=target, draft_variables=dvars)
        req = eng.submit(_prompt(6, 4), max_new_tokens=6)  # greedy: fine
        with pytest.raises(ValueError, match="top_k"):
            eng.submit(_prompt(7, 4), max_new_tokens=6, temperature=0.7)
        eng.run_until_idle()
        want = np.asarray(generate(
            target, tvars, _prompt(6, 4)[None, :], max_new_tokens=6))[0]
        np.testing.assert_array_equal(req.result(timeout=1), want)
