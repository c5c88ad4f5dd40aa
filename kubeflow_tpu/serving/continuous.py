"""Continuous batching for generative serving — iteration-level scheduling.

SURVEY §2.5 (KServe model server): the reference's serving runtimes process
one request batch at a time, so concurrent generative requests serialize
whole decodes behind each other. TPU redesign of that surface: decode
throughput is HBM-bandwidth-bound — every decode step streams the full
weight set regardless of how many rows ride it — so a half-empty batch
wastes exactly the bandwidth the chip is bound by. The engine (Orca-style
iteration-level scheduling; row slots instead of vLLM paging) keeps ONE
static-shape decode executable hot and splices sequences in and out
BETWEEN steps:

  - admission: a queued prompt prefills into a free row (per-prompt-length
    prefill executable, batch-1), and a jitted row-splice writes that
    row's cache slice + per-row index into the live batch cache
    (models/gpt.py keeps cache_index/pos_index per-row (B,) for exactly
    this)
  - every tick advances ALL in-flight rows one token in one dispatch —
    rows at different depths, one executable
  - rows retire on EOS or their token budget; the slot readmits the next
    queued request without stalling the other rows

Greedy rows are EXACTLY generate()'s greedy decode for that prompt alone —
per-row position masking keeps rows independent. (MoE models stay
independent too: the decode path routes DROPLESS — parallel/moe.py — so
no capacity dispatch couples rows.) Sampling rows (per-request
temperature, engine-level top_k) draw
on-device via per-row keys folded from the request key and the row's step
count — deterministic per key, and greedy/sampling rows mix freely in one
batch.

Speculative mode (draft_module/draft_variables/gamma): each tick runs ONE
fused dispatch — gamma chained batch-R draft steps propose, the target
verifies every row's (last + proposals) block in one (R, gamma+1) pass,
and each row rewinds to ITS accepted length through the per-row
cache_index/pos_index vectors (the solo speculative rewind applied
rowwise; models/gpt.py's block write lands each row's verify block at its
own depth). Greedy rows stay target-greedy-exact; temperature>0 rows run
the rowwise Leviathan/Chen rejection scheme (accept with min(1, p_t/p_d),
residual resample, bonus token from p_t — target-distribution-exact),
and both kinds mix in the one executable. Rows emit 1..gamma+1 tokens
per dispatch, the decode-throughput lever on dispatch-floored links.
Rolling caches, prefill buckets, and engine-level top_k on sampled rows
are refused (hazards documented at the guards).
"""

from __future__ import annotations

import threading
import time

from kubeflow_tpu.analysis.lockcheck import make_lock
from kubeflow_tpu.tracing.core import armed_tracer, current_context
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


def _eos_tuple(eos) -> tuple[int, ...] | None:
    """Normalize an eos spec (int | sequence | None) to a tuple of stop
    ids for host-side retire checks — mirrors models.gpt.eos_id_array."""
    if eos is None:
        return None
    if isinstance(eos, (list, tuple, np.ndarray)):
        ids = tuple(int(x) for x in np.asarray(eos).reshape(-1))
        return ids or None
    return (int(eos),)


@dataclass
class _InFlight:
    slot: int
    max_new_tokens: int
    eos_token_id: tuple[int, ...] | None
    temperature: float = 0.0
    key: object = None  # jax PRNG key for sampling rows
    tokens: list = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    error: str | None = None
    # streaming/timing surface (the fleet tier and the load-test harness
    # read these): submit/first-token/done timestamps plus optional
    # callbacks — on_token(req, tok) per emitted token, on_done(req) once
    # at retire OR failure. Callbacks run on the ENGINE thread: keep them
    # cheap and never call back into this engine under its lock.
    t_submit: float = 0.0
    t_first: float | None = None
    t_done: float | None = None
    on_token: object = None
    on_done: object = None
    # request-tracing state (docs/slo.md): trace_ctx is the `request`
    # root span's pre-allocated identity — engine spans (queue wait,
    # prefill chunks, decode window) parent to it as they complete, and
    # the root itself is recorded retroactively at finish() when this
    # engine OWNS it (own_root; a fleet-submitted request's root belongs
    # to the router). Retro recording means no open Span ever rides the
    # ticker thread — an error path cannot leak one.
    trace_ctx: object = None
    parent_ctx: object = None
    own_root: bool = False
    request_id: str = ""
    _tracer: object = None
    _tsdb: object = None
    t_submit_wall: float = 0.0
    t_first_wall: float | None = None
    # paged-KV lifetime state (fleet.pagedkv.SequenceChain): `chain` is
    # set when ownership TRANSFERS to the handle's consumer — a
    # keep_chain retire (the disaggregated prefill→decode handoff) or a
    # replica-kill _fail_all (the resume-from-KV requeue). `resumed`
    # rows continue a chain mid-decode: their pre-fed tokens never
    # re-fire callbacks and their engine-side TTFT is not a first token.
    chain: object = None
    resumed: bool = False
    _resume: object = None          # (SequenceChain, tokens) until seated
    _keep_chain: bool = False

    def push(self, tok: int) -> None:
        """Engine-side token emission — the ONE append path, so TTFT is
        stamped exactly when the first token exists."""
        if not self.tokens:
            self.t_first = time.perf_counter()
            self.t_first_wall = time.time()
        self.tokens.append(tok)
        if self.on_token is not None:
            self.on_token(self, tok)

    def finish(self, error: str | None = None) -> None:
        self.error = error if self.error is None else self.error
        self.t_done = time.perf_counter()
        if self._tsdb is not None and self.error is None \
                and self.ttft_s is not None and not self.resumed:
            # resumed rows have no first token — their t_first marks the
            # resume point and must not pollute the TTFT SLO series
            self._tsdb.record("serving.ttft_s", self.ttft_s)
        tr = self._tracer
        if tr is not None:
            if self.t_first is not None:
                attrs = {"tokens": len(self.tokens)}
                if self.resumed:
                    attrs["resumed"] = True
                if self.error is not None:
                    # a killed replica's partial decode window: real time
                    # spent, tokens discarded by the requeue contract
                    attrs["error"] = self.error
                tr.record_span(
                    "engine.decode", self.t_first_wall,
                    self.t_done - self.t_first, parent=self.trace_ctx,
                    **attrs)
            if self.own_root:
                tr.record_span(
                    "request", self.t_submit_wall,
                    self.t_done - self.t_submit, context=self.trace_ctx,
                    parent=self.parent_ctx,
                    request_id=self.request_id,
                    outcome="failed" if self.error else "completed",
                    tokens=len(self.tokens))
        self.done.set()
        if self.on_done is not None:
            self.on_done(self)

    @property
    def ttft_s(self) -> float | None:
        return None if self.t_first is None else self.t_first - self.t_submit

    @property
    def tokens_per_s(self) -> float | None:
        if self.t_first is None or self.t_done is None:
            return None
        dt = self.t_done - self.t_first
        return len(self.tokens) / dt if dt > 0 else float("inf")

    def result(self, timeout: float | None = None) -> np.ndarray:
        if not self.done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error is not None:
            raise RuntimeError(f"generation failed: {self.error}")
        return np.asarray(self.tokens, np.int32)


@dataclass
class _PendingPrefill:
    """A seated row whose prompt is still prefilling (chunked admission):
    the batch-1 row cache being built, the next position to compute, and
    the pool refs backing any reused prefix. With a draft model the
    draft's own batch-1 cache marches through the same chunk schedule
    (d_cache/d_pos) — admission completes when BOTH are done. A resume
    row (`resume`) has its target cache fully seeded from the pool and
    only waits on the draft (no draft: it never pends at all)."""

    req: _InFlight
    ids: np.ndarray
    pos: int
    cache: object
    last_logits: object = None
    match_refs: list = field(default_factory=list)
    d_cache: object = None
    d_pos: int = 0
    resume: bool = False


class ContinuousBatcher:
    """Fixed-row continuous-batching decode engine over a GPTLM.

    submit() enqueues a prompt and returns a handle whose .result() blocks
    for the generated ids; tick() runs one scheduling round (admit + one
    decode step); run_until_idle() drains everything (the synchronous mode
    tests and the bench use); start()/stop() run ticks on a daemon thread
    (the serving mode).
    """

    def __init__(self, module, variables, max_rows: int = 8,
                 default_max_new_tokens: int = 32,
                 eos_token_id=None, top_k: int = 0,
                 seed: int = 0, steps_per_tick: int = 1,
                 prefill_buckets: tuple[int, ...] | None = None,
                 draft_module=None, draft_variables=None, gamma: int = 4,
                 prefill_chunk: int = 0, paged_kv=None,
                 block_budget: bool = False, max_chunks_per_tick: int = 1,
                 tracer=None, tsdb=None):
        # tracer (tracing.Tracer): per-request spans — queue wait, one
        # span per prefill chunk (reused-vs-computed counts), decode
        # window, and a `request` root when no fleet owns one. tsdb
        # (monitoring.TimeSeriesStore): decode-tick and TTFT samples
        # for the SLO burn-rate monitor. Both default off at zero cost
        # on the tick path (docs/slo.md).
        self.tracer = tracer
        self.tsdb = tsdb
        cfg = module.cfg
        # chunked prefill (prefill_chunk > 0): long prompts admit in
        # fixed-token chunks interleaved with decode ticks — at most ONE
        # chunk of prefill work per tick, so a 4k-token prompt never
        # stalls in-flight decode rows more than one chunk budget. The
        # per-row block-write path (models/gpt.py vmapped
        # dynamic_update_slice at each row's cache_index) makes the
        # chunked cache identical to a one-shot prefill's, so the first
        # token — and every token after it — is token-identical.
        # paged_kv (fleet.PagedKVPool): the pool is the KV substrate for
        # the WHOLE row lifetime — the matched prefix K/V seeds the row
        # cache at admission (only the suffix runs through the model),
        # and every decode dispatch appends its freshly-written K/V to
        # the row's block chain (docs/serving.md). block_budget=True
        # additionally gates admission on the pool's free-block count
        # (prompt + budget blocks must fit the working set) instead of
        # row slots alone. max_chunks_per_tick lifts the one-chunk
        # stall bound for PURE-PREFILL replicas (the disaggregated
        # tier's prefill role has no decode rows to starve).
        self.prefill_chunk = int(prefill_chunk)
        self.paged_kv = paged_kv
        self.block_budget = bool(block_budget) and paged_kv is not None
        self.max_chunks_per_tick = int(max_chunks_per_tick)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}")
        if self.max_chunks_per_tick < 1:
            raise ValueError(
                f"max_chunks_per_tick must be >= 1, got "
                f"{max_chunks_per_tick}")
        if self.prefill_chunk or paged_kv is not None:
            what = ("prefill_chunk" if self.prefill_chunk else "paged_kv")
            if prefill_buckets is not None:
                raise ValueError(
                    f"{what} replaces bucketed prefill — the chunk walk "
                    "already bounds the executable count; configure one")
            if getattr(cfg, "kv_cache_capacity", 0):
                raise ValueError(
                    f"{what} requires the full KV cache: ring-slot "
                    "identity is ambiguous for seeded/partial prefixes")
        # MoE models are row-independent at decode since the decode path
        # routes DROPLESS (parallel/moe.py, VERDICT r4 #6): no capacity,
        # no cross-row drop coupling — so the engine serves them exactly.
        # Speculative mode (VERDICT r4 #5): a draft model proposes gamma
        # tokens per row, the target verifies all rows' proposals in ONE
        # (R, gamma+1) pass, and each row rewinds to ITS accepted length —
        # the solo speculative rewind applied rowwise via the per-row
        # cache_index vectors. Greedy rows stay EXACTLY the target's
        # greedy decode (acceptance is argmax-match), so mixing row depths
        # changes nothing. One spec round per tick, all inside one
        # executable (draft scan + verify fused).
        self.draft_module = draft_module
        self.draft_variables = draft_variables
        self.gamma = int(gamma)
        if draft_module is not None:
            for m, name in ((module, "target"), (draft_module, "draft")):
                if getattr(m.cfg, "kv_cache_capacity", 0):
                    raise ValueError(
                        f"{name} uses a rolling KV cache — speculative "
                        "rewind makes ring-slot identity ambiguous")
            if draft_module.cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft must share the target vocabulary")
            if prefill_buckets is not None:
                raise ValueError(
                    "speculative engine does not support prefill_buckets "
                    "yet: the draft prefill would need the same pad-rewind")
            if steps_per_tick != 1:
                raise ValueError(
                    "speculative engine runs one spec round per tick "
                    "(gamma amortizes the dispatch); steps_per_tick must "
                    "be 1")
            if self.gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
        self.module = module
        self.variables = variables
        self.max_rows = int(max_rows)
        self.max_len = int(cfg.max_len)
        # rolling-cache models bound the prefill length (models/gpt.py
        # capacity law); validate at submit() so a too-long prompt is the
        # CALLER's error, not a trace-time exception on the engine thread
        cap = int(getattr(cfg, "kv_cache_capacity", 0) or 0)
        self.max_prompt_len = (
            cap - int(cfg.attention_window) + 1 if cap else self.max_len)
        # bucketed prefill: pad prompts right to the smallest bucket and
        # rewind the per-row index to the true length inside the jitted
        # prefill — ONE executable per bucket instead of one per distinct
        # prompt length (unbounded compile cache in production). The
        # stale pad rows are invisible under the full cache's position
        # mask; a ROLLING cache cannot tell stale newer writes from valid
        # older ones (same hazard as speculative rewind), so buckets are
        # refused there.
        if prefill_buckets is not None:
            if cap:
                raise ValueError(
                    "prefill_buckets requires the full KV cache: the pad "
                    "rewind makes rolling ring-slot identity ambiguous")
            buckets = tuple(sorted(int(x) for x in prefill_buckets))
            if not buckets or buckets[0] < 1:
                raise ValueError(f"bad prefill_buckets {prefill_buckets}")
            self.prefill_buckets = buckets
        else:
            self.prefill_buckets = None
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.eos_token_id = _eos_tuple(eos_token_id)
        self.top_k = int(top_k)  # static: one decode executable
        # decode steps per dispatch: scheduling stays iteration-level at
        # granularity T, but T tokens amortize one host round-trip (what a
        # round-trip costs per tick on the chip is not measured — ROADMAP
        # S2). Rows retiring mid-scan just discard their tail.
        self.steps_per_tick = max(1, int(steps_per_tick))
        self._seed = int(seed)
        self._submitted = 0
        self._lock = make_lock("continuous.ContinuousBatcher._lock")
        self._queue: list[tuple[np.ndarray, _InFlight]] = []
        self._rows: list[_InFlight | None] = [None] * self.max_rows
        self._toks = np.zeros((self.max_rows,), np.int32)
        self._prefill_cache: dict[int, object] = {}  # prompt_len -> jitted
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self.step_count = 0  # decode dispatches (the scheduling metric)

        # live batch cache: created by one R-row dummy decode step
        _, cache = module.apply(
            variables, jnp.zeros((self.max_rows, 1), jnp.int32),
            decode=True, mutable=["cache"])
        self._cache = cache["cache"]
        # chunked/seeded admission state: slot -> in-progress prefill;
        # ticker-private like _rows. _row_chains holds each DECODING
        # row's pool block chain (SequenceChain) — the pool-side twin of
        # the row's cache slice, grown per dispatch, released at retire
        # (or transferred to the handle on keep_chain/kill).
        self._pending: dict[int, _PendingPrefill] = {}
        self._row_chains: dict[int, object] = {}
        self._chunk_order: list[int] = []  # FIFO of pending slots
        self._chunk_fns: dict[int, object] = {}  # suffix len -> jitted
        self._draft_chunk_fns: dict[int, object] = {}
        self._row_template = None  # lazy batch-1 np zero cache twin
        self._draft_row_template = None
        # per-row cache depth (prompt + cache-written decode positions):
        # host-side truth like _toks — the spec step's rewind base AND
        # the paged chain-append's extraction start
        self._depths = np.zeros((self.max_rows,), np.int32)
        #: prefill-unit accounting (the prefix-reuse proof reads these):
        #: tokens the model actually computed vs tokens seeded for free
        self.prefill_tokens_total = 0
        self.prefill_tokens_reused = 0
        if draft_module is not None:
            _, dcache = draft_module.apply(
                draft_variables, jnp.zeros((self.max_rows, 1), jnp.int32),
                decode=True, mutable=["cache"])
            self._dcache = dcache["cache"]
            self._draft_prefill_cache: dict[int, object] = {}

        def _splice(big, row, i):
            """Write batch-1 row-cache `row` into slot i of the live
            cache — every leaf's leading dim is the row dim."""
            def leaf(b, r):
                return jax.lax.dynamic_update_slice(
                    b, r.astype(b.dtype), (i,) + (0,) * (b.ndim - 1))
            return jax.tree.map(leaf, big, row)

        self._splice = jax.jit(_splice)
        top_k_ = self.top_k

        def _pick(logits, temps, keys):
            """Per-row next token: argmax where temperature == 0, else a
            categorical draw with that row's key (top_k is engine-static
            so everything stays one executable)."""
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            if top_k_ > 0:
                kth = jax.lax.top_k(scaled, top_k_)[0][..., -1:]
                scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
            sampled = jax.vmap(jax.random.categorical)(
                keys, scaled).astype(jnp.int32)
            return jnp.where(temps > 0, sampled, greedy)

        T = self.steps_per_tick
        paged = paged_kv is not None

        def _one(cache_col, toks, active, temps, keys):
            from kubeflow_tpu.models.gpt import set_cache_indices

            logits, new_cache = module.apply(
                {**variables, "cache": cache_col},
                toks[:, None], decode=True, mutable=["cache"])
            nxt = _pick(logits[:, 0].astype(jnp.float32), temps, keys)
            # free rows keep decoding garbage (their slot is overwritten
            # wholesale on admission) — but their index must not creep past
            # max_len, so park it at 0
            return nxt, set_cache_indices(new_cache["cache"], active=active)

        def _step(cache_col, toks, active, temps, base_keys, starts,
                  depths):
            """T chained decode steps in ONE dispatch; returns the (T, R)
            emitted tokens. Rows that retire mid-scan decode on — their
            tail is discarded on the host (iteration-level scheduling at
            granularity T). With a paged pool the dispatch ALSO gathers
            the freshly-written K/V window [depths, depths+T) per row
            (models/gpt.gather_kv_rows) — the chain-append extraction
            rides the step executable instead of costing a second
            dispatch on the tick path."""
            def body(carry, j):
                cache_col, toks = carry
                keys = jax.vmap(jax.random.fold_in)(base_keys, starts + j)
                nxt, cache_col = _one(cache_col, toks, active, temps, keys)
                return (cache_col, nxt), nxt

            (cache_col, _), out = jax.lax.scan(
                body, (cache_col, toks), jnp.arange(T))
            if paged:
                from kubeflow_tpu.models.gpt import gather_kv_rows

                return out, cache_col, gather_kv_rows(cache_col, depths, T)
            return out, cache_col

        self._step = jax.jit(_step)

        if draft_module is not None:
            G = self.gamma
            from kubeflow_tpu.models.gpt import set_cache_indices

            # per-row index rewrite shared with models/gpt.py (one owner
            # of the cache-index contract); inactive rows park at 0
            def _set_row_indices(cache, values, active):
                return set_cache_indices(cache, values, active)

            def _spec_step(t_cache, d_cache, toks, active, depths, temps,
                           base_keys, any_sampled):
                """One speculative round for ALL rows in one dispatch:
                draft proposes G tokens/row (G chained batch-R steps),
                target verifies (R, G+1) in one pass, each row accepts
                its own prefix and rewinds to its own depth. Greedy rows
                (temp == 0) accept on argmax-match; sampled rows run the
                Leviathan/Chen rejection per row — accept with
                min(1, p_t/p_d), residual resample at the first
                rejection, bonus token from p_t (the solo
                models/speculative.py scheme applied rowwise; greedy and
                sampled rows mix in ONE executable via where(temps>0)).
                Per-(row, round, step) keys fold the request key with
                depth*(G+3)+j — depth strictly increases per round, so
                keys never repeat. Returns the (R, G+1) emission buffer
                and per-row accept counts.

                `any_sampled` is STATIC (jit retraces when the greedy/
                sampled mix changes, exactly like prefill buckets
                retrace per bucket): an all-greedy batch specializes to
                the cheap executable — no (R, G+1, V) softmaxes, no
                per-draft-step categorical draws, no residual clip/
                normalize/resample — so greedy-only speculative
                deployments keep paying only argmax (ADVICE r5).
                Greedy rows' tokens are IDENTICAL either way: the mixed
                executable computes the sampling machinery and discards
                it rowwise via where(temps>0); the specialized one just
                never computes it (pinned by test_continuous)."""
                t_cache = _set_row_indices(t_cache, depths, active)
                d_cache = _set_row_indices(d_cache, depths, active)
                tp = jnp.maximum(temps, 1e-6)[:, None]       # (R, 1)
                key_base = depths * (G + 3)

                def draft_step(carry, j):
                    cache, tok = carry
                    logits, new = draft_module.apply(
                        {**draft_variables, "cache": cache}, tok[:, None],
                        decode=True, mutable=["cache"])
                    row = logits[:, -1].astype(jnp.float32)  # (R, V)
                    greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
                    if not any_sampled:
                        return (new["cache"], greedy), greedy
                    keys = jax.vmap(jax.random.fold_in)(
                        base_keys, key_base + j)
                    sampled = jax.vmap(jax.random.categorical)(
                        keys, row / tp).astype(jnp.int32)
                    nxt = jnp.where(temps > 0, sampled, greedy)
                    probs = jax.nn.softmax(row / tp, axis=-1)
                    return (new["cache"], nxt), (nxt, probs)

                (d_cache, p_last), ys = jax.lax.scan(
                    draft_step, (d_cache, toks), jnp.arange(G))
                if any_sampled:
                    props, d_probs = ys
                    d_probs = d_probs.transpose(1, 0, 2)     # (R, G, V)
                else:
                    props = ys
                props = props.T                              # (R, G)
                # extra draft write (solo speculative does the same) so an
                # all-accepted round leaves no unwritten draft row
                (d_cache, _), _ = draft_step((d_cache, p_last),
                                             jnp.int32(G + 2))
                inp = jnp.concatenate([toks[:, None], props], axis=1)
                logits, t_adv = module.apply(
                    {**variables, "cache": t_cache}, inp,
                    decode=True, mutable=["cache"])
                t_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # --- acceptance: argmax-match (greedy) | rejection ----
                ok_greedy = props == t_tokens[:, :G]
                if any_sampled:
                    p_t = jax.nn.softmax(
                        logits.astype(jnp.float32) / tp[..., None], axis=-1
                    )                                        # (R, G+1, V)
                    pt_x = jnp.take_along_axis(
                        p_t[:, :G], props[..., None], axis=-1)[..., 0]
                    pd_x = jnp.take_along_axis(
                        d_probs, props[..., None], axis=-1)[..., 0]
                    u_keys = jax.vmap(jax.random.fold_in)(
                        base_keys, key_base + G)
                    u = jax.vmap(
                        lambda k: jax.random.uniform(k, (G,)))(u_keys)
                    ok_sampled = u < jnp.minimum(
                        1.0, pt_x / jnp.maximum(pd_x, 1e-30))
                    ok = jnp.where(temps[:, None] > 0, ok_sampled,
                                   ok_greedy)
                else:
                    ok = ok_greedy
                agree = jnp.cumprod(ok.astype(jnp.int32), axis=1)
                a = agree.sum(axis=1)                        # (R,)
                # --- correction token ---------------------------------
                corr_greedy = jnp.take_along_axis(
                    t_tokens, a[:, None], axis=1)
                if any_sampled:
                    residual = jnp.clip(p_t[:, :G] - d_probs, 0.0)
                    rs = residual.sum(-1, keepdims=True)
                    res_norm = jnp.where(
                        rs > 0, residual / jnp.maximum(rs, 1e-30),
                        p_t[:, :G])
                    corr_rows = jnp.concatenate(
                        [res_norm, p_t[:, G:]], axis=1)      # (R, G+1, V)
                    picked = jnp.take_along_axis(
                        corr_rows, a[:, None, None], axis=1)[:, 0]
                    c_keys = jax.vmap(jax.random.fold_in)(
                        base_keys, key_base + G + 1)
                    corr_sampled = jax.vmap(jax.random.categorical)(
                        c_keys, jnp.log(jnp.maximum(picked, 1e-30))
                    ).astype(jnp.int32)[:, None]
                    corr = jnp.where(temps[:, None] > 0, corr_sampled,
                                     corr_greedy)
                else:
                    corr = corr_greedy
                padded = jnp.concatenate(
                    [props, jnp.zeros((props.shape[0], 1), jnp.int32)],
                    axis=1)
                upd = jnp.where(
                    jnp.arange(G + 1)[None, :] < a[:, None], padded, corr)
                new_depths = depths + a + 1
                t_cache = _set_row_indices(
                    t_adv["cache"], new_depths, active)
                d_cache = _set_row_indices(d_cache, new_depths, active)
                if paged:
                    from kubeflow_tpu.models.gpt import gather_kv_rows

                    win = gather_kv_rows(t_cache, depths, G + 1)
                    return upd, a, t_cache, d_cache, win
                return upd, a, t_cache, d_cache

            self._spec_step = jax.jit(_spec_step, static_argnums=(7,))

        def _pick_first(logits, temp, key):
            return _pick(logits[None].astype(jnp.float32),
                         jnp.asarray([temp], jnp.float32), key[None])[0]

        self._pick_first = jax.jit(_pick_first)

    # ---------------------------------------------------------------- API

    def submit(self, prompt_ids, max_new_tokens: int | None = None,
               eos_token_id=None, temperature: float = 0.0,
               key=None, on_token=None, on_done=None,
               trace_ctx=None, request_id: str = "",
               keep_chain: bool = False, resume_from=None) -> _InFlight:
        # keep_chain: retire transfers the row's paged block chain to the
        # handle (handle.chain) instead of releasing it — the
        # disaggregated prefill replica's publish side. resume_from =
        # (SequenceChain, tokens): seat the row by SEEDING its cache from
        # the chain (no prefill compute) with `tokens` already emitted —
        # the decode replica's adopt side AND the kill-requeue resume;
        # max_new_tokens still bounds the TOTAL tokens, resumed included.
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        budget = int(max_new_tokens or self.default_max_new_tokens)
        if ids.size < 1:
            raise ValueError("empty prompt")
        if resume_from is not None:
            if self.paged_kv is None:
                raise ValueError("resume_from requires a paged_kv pool")
            chain, toks = resume_from
            if chain.frozen:
                raise ValueError("cannot resume from a frozen chain")
            if chain.pool is not self.paged_kv:
                raise ValueError(
                    "resume chain lives in a different pool than this "
                    "engine's")
            if not toks:
                raise ValueError("resume_from needs >= 1 emitted token")
            if chain.length != ids.size + len(toks) - 1:
                raise ValueError(
                    f"resume chain covers {chain.length} positions, "
                    f"expected prompt {ids.size} + {len(toks)} tokens "
                    f"- 1 = {ids.size + len(toks) - 1}")
            if len(toks) >= budget:
                raise ValueError(
                    "resume tokens already meet max_new_tokens")
        if self.block_budget and resume_from is None:
            import math

            need = math.ceil((ids.size + budget)
                             / self.paged_kv.block_size)
            if need > self.paged_kv.capacity_blocks:
                raise ValueError(
                    f"prompt {ids.size} + budget {budget} needs {need} "
                    f"KV blocks, beyond the pool's capacity "
                    f"{self.paged_kv.capacity_blocks}")
        if self.draft_module is not None:
            if temperature > 0 and self.top_k > 0:
                # greedy rows ignore top_k, so greedy-only deployments
                # with a configured top_k keep constructing/serving; the
                # refusal fires only where it matters — a SAMPLED row,
                # whose rejection scheme must accept against the draft's
                # ACTUAL proposal distribution (a top_k-truncated
                # p_d/p_t pair needs both sides renormalized
                # consistently; not implemented)
                raise ValueError(
                    "sampled rows in the speculative engine do not "
                    "compose with engine-level top_k")
            lim = min(self.max_len, self.draft_module.cfg.max_len)
            if ids.size + budget + self.gamma + 1 > lim:
                raise ValueError(
                    f"prompt {ids.size} + max_new_tokens {budget} + "
                    f"gamma+1 {self.gamma + 1} exceeds max_len {lim} "
                    "(a verify block may overshoot the budget)")
        elif ids.size + budget > self.max_len:
            raise ValueError(
                f"prompt {ids.size} + max_new_tokens {budget} exceeds "
                f"max_len {self.max_len}")
        if ids.size > self.max_prompt_len:
            raise ValueError(
                f"prompt {ids.size} exceeds the rolling cache's prefill "
                f"budget {self.max_prompt_len} (capacity - window + 1)")
        if (self.prefill_buckets is not None
                and ids.size > self.prefill_buckets[-1]):
            raise ValueError(
                f"prompt {ids.size} exceeds the largest prefill bucket "
                f"{self.prefill_buckets[-1]}")
        with self._lock:
            self._submitted += 1
            if key is None:
                # per-request key: engine seed folded with a monotonically
                # advancing submit counter (same contract as the sampling
                # predictor's per-request keys)
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self._seed), self._submitted)
            req = _InFlight(slot=-1, max_new_tokens=budget,
                            eos_token_id=(self.eos_token_id
                                          if eos_token_id is None
                                          else _eos_tuple(eos_token_id)),
                            temperature=float(temperature), key=key,
                            t_submit=time.perf_counter(),
                            on_token=on_token, on_done=on_done)
            req.t_submit_wall = time.time()
            req._tsdb = self.tsdb
            req._keep_chain = bool(keep_chain)
            if resume_from is not None:
                chain, toks = resume_from
                req._resume = (chain, [int(t) for t in toks])
                req.resumed = True
            tr = armed_tracer(self.tracer)
            if tr is not None:
                req._tracer = tr
                req.request_id = request_id
                if trace_ctx is not None:
                    # the fleet router owns the `request` root span; the
                    # engine only contributes phase spans under it
                    req.trace_ctx = trace_ctx
                else:
                    req.own_root = True
                    req.parent_ctx = current_context()
                    req.trace_ctx = tr.allocate_context(
                        parent=req.parent_ctx)
                    if not req.request_id:
                        from kubeflow_tpu.serving.requestid import (
                            get_request_id,
                        )

                        req.request_id = get_request_id()
            self._queue.append((ids, req))
        return req

    def _prefill(self, ids: np.ndarray):
        if self.prefill_buckets is None:
            fn = self._prefill_cache.get(ids.size)
            if fn is None:
                def prefill(x):
                    logits, cache = self.module.apply(
                        self.variables, x, decode=True, mutable=["cache"])
                    return logits[:, -1], cache["cache"]
                fn = self._prefill_cache[ids.size] = jax.jit(prefill)
            return fn(ids[None, :])
        # bucketed: pad right, take logits at the TRUE last position, and
        # rewind cache_index/pos_index to the true length — pad rows stay
        # invisible under the position mask
        bucket = next((b for b in self.prefill_buckets if b >= ids.size),
                      None)
        if bucket is None:
            raise ValueError(
                f"prompt {ids.size} exceeds the largest prefill bucket "
                f"{self.prefill_buckets[-1]}")
        fn = self._prefill_cache.get(bucket)
        if fn is None:
            def prefill(x, true_len):
                logits, cache = self.module.apply(
                    self.variables, x, decode=True, mutable=["cache"])
                last = jax.lax.dynamic_index_in_dim(
                    logits, true_len - 1, axis=1, keepdims=False)

                def rewind(path, leaf):
                    name = getattr(path[-1], "key", "")
                    if name in ("cache_index", "pos_index"):
                        return jnp.full_like(leaf, true_len)
                    return leaf

                return last, jax.tree_util.tree_map_with_path(
                    rewind, cache["cache"])
            fn = self._prefill_cache[bucket] = jax.jit(prefill)
        padded = np.zeros((bucket,), np.int32)
        padded[:ids.size] = ids
        return fn(padded[None, :], jnp.int32(ids.size))

    def _draft_prefill(self, ids: np.ndarray):
        fn = self._draft_prefill_cache.get(ids.size)
        if fn is None:
            def prefill(x):
                _, cache = self.draft_module.apply(
                    self.draft_variables, x, decode=True, mutable=["cache"])
                return cache["cache"]
            fn = self._draft_prefill_cache[ids.size] = jax.jit(prefill)
        return fn(ids[None, :])

    # -------------------------------------------- chunked/seeded prefill

    def _apply_chunk(self, cache, chunk: np.ndarray):
        """One prefill chunk through the model on a batch-1 row cache:
        (last-position logits, advanced cache). Jitted per chunk length —
        with prefill_chunk set the executable count is bounded by
        chunk + remainder lengths, the production compile-cache story
        bucketed prefill approximated."""
        fn = self._chunk_fns.get(chunk.size)
        if fn is None:
            def apply(cache, x):
                logits, new = self.module.apply(
                    {**self.variables, "cache": cache}, x,
                    decode=True, mutable=["cache"])
                return logits[:, -1], new["cache"]
            fn = self._chunk_fns[chunk.size] = jax.jit(apply)
        return fn(cache, chunk[None, :])

    def _row_cache_template(self):
        from kubeflow_tpu.serving.fleet.pagedkv import make_row_template

        if self._row_template is None:
            self._row_template = make_row_template(self._cache)
        return self._row_template

    def _draft_row_cache_template(self):
        from kubeflow_tpu.serving.fleet.pagedkv import make_row_template

        if self._draft_row_template is None:
            self._draft_row_template = make_row_template(self._dcache)
        return self._draft_row_template

    def _begin_prefill(self, slot: int, ids: np.ndarray,
                       req: _InFlight) -> None:
        """Seat a row on the chunked/seeded admission path: reuse any
        pooled prefix, then either finish the suffix now (prefill_chunk
        == 0) or leave the row pending for chunk-per-tick advancement.
        With a draft model the draft's batch-1 cache prefills over the
        SAME chunk schedule (the pool stores only target K/V, so the
        draft computes every position — it only shapes acceptance
        speed, never the emitted tokens)."""
        from kubeflow_tpu.serving.fleet.pagedkv import seed_row_cache

        template = self._row_cache_template()
        cache = None
        pos, refs = 0, []
        if self.paged_kv is not None:
            m = self.paged_kv.match(ids)
            # at least one position must run through the model — the row
            # needs the last position's logits to pick its first token
            pos = min(m.length, ids.size - 1)
            if pos > 0:
                # seed_row_cache copies every leaf itself — seeding from
                # the template directly spares the hot reuse path a whole
                # wasted row-cache memcpy per admission
                cache = seed_row_cache(template, m.kv, pos)
                refs = m.blocks
                self.prefill_tokens_reused += pos
            elif m.blocks:
                self.paged_kv.release(m.blocks)
        if cache is None:
            # leaves are np arrays: fresh copy per admission
            cache = jax.tree.map(np.copy, template)
        if pos > 0 and req._tracer is not None:
            # the prefix-reuse ledger's trace form: these positions were
            # seeded from the paged pool, never computed
            req._tracer.event("engine.prefill_seed", parent=req.trace_ctx,
                              tokens_reused=pos)
        pend = _PendingPrefill(req=req, ids=ids, pos=pos, cache=cache,
                               match_refs=refs)
        if self.draft_module is not None:
            pend.d_cache = jax.tree.map(np.copy,
                                        self._draft_row_cache_template())
        self._pending[slot] = pend
        self._chunk_order.append(slot)
        if not self.prefill_chunk:
            while slot in self._pending:  # suffix in one pass
                self._advance_prefill(slot)

    def _admit_resume(self, slot: int, ids: np.ndarray,
                      req: _InFlight) -> None:
        """Seat a row by RESUMING its paged chain: the pool's gathered
        K/V seeds the whole cache (zero prefill compute — the
        disaggregated handoff / kill-requeue admission), the emitted
        tokens are pre-fed without re-firing callbacks, and decode
        continues from the chain's end. With a draft model the draft
        cache still prefills (chunked) over the known token history —
        draft state isn't pooled, but it never changes emitted tokens."""
        from kubeflow_tpu.serving.fleet.pagedkv import seed_row_cache

        chain, toks = req._resume
        full_ids = (np.concatenate([ids, np.asarray(toks[:-1], np.int32)])
                    if len(toks) > 1 else ids)
        _, kv = self.paged_kv.gather(chain.refs)
        cache = seed_row_cache(self._row_cache_template(), kv,
                               chain.length)
        req.tokens = list(toks)      # pre-fed: callbacks never re-fire
        req.t_first = time.perf_counter()   # the resume point, not TTFT
        req.t_first_wall = time.time()
        if req._tracer is not None:
            req._tracer.event(
                "engine.resume", parent=req.trace_ctx,
                resumed_positions=int(chain.length),
                tokens_resumed=len(toks), slot=slot)
        if self.draft_module is not None:
            pend = _PendingPrefill(req=req, ids=full_ids,
                                   pos=len(full_ids), cache=cache,
                                   resume=True)
            pend.d_cache = jax.tree.map(
                np.copy, self._draft_row_cache_template())
            self._pending[slot] = pend
            self._chunk_order.append(slot)
            self._row_chains[slot] = chain
            req._resume = None
            if not self.prefill_chunk:
                while slot in self._pending:
                    self._advance_prefill(slot)
            return
        self._cache = self._splice(self._cache, cache, jnp.int32(slot))
        self._toks[slot] = int(toks[-1])
        self._depths[slot] = chain.length
        self._row_chains[slot] = chain
        req._resume = None

    def _apply_draft_chunk(self, cache, chunk: np.ndarray):
        """One draft-prefill chunk on a batch-1 draft row cache (cache
        only — the draft's logits are never needed at admission)."""
        fn = self._draft_chunk_fns.get(chunk.size)
        if fn is None:
            def apply(cache, x):
                _, new = self.draft_module.apply(
                    {**self.draft_variables, "cache": cache}, x,
                    decode=True, mutable=["cache"])
                return new["cache"]
            fn = self._draft_chunk_fns[chunk.size] = jax.jit(apply)
        return fn(cache, chunk[None, :])

    def _advance_prefill(self, slot: int) -> None:
        """Run ONE chunk unit (or the whole remaining work when chunking
        is off) of a pending row: a target chunk while the prompt suffix
        remains, plus a draft chunk while the draft cache lags; completes
        admission when both are done."""
        pend = self._pending[slot]
        whole = not self.prefill_chunk
        if pend.pos < len(pend.ids):
            take = (len(pend.ids) - pend.pos if whole
                    else min(self.prefill_chunk, len(pend.ids) - pend.pos))
            chunk = pend.ids[pend.pos:pend.pos + take]
            # the FIRST computed chunk (no logits yet) carries the seeded
            # reuse count — reused-vs-computed per chunk off the ledger
            reused = pend.pos if pend.last_logits is None else 0
            w0, p0 = time.time(), time.perf_counter()
            pend.last_logits, pend.cache = self._apply_chunk(pend.cache,
                                                             chunk)
            if pend.req._tracer is not None:
                pend.req._tracer.record_span(
                    "engine.prefill_chunk", w0, time.perf_counter() - p0,
                    parent=pend.req.trace_ctx, tokens_computed=take,
                    tokens_reused=reused, pos=pend.pos + take)
            pend.pos += take
            self.prefill_tokens_total += take
        if pend.d_cache is not None and pend.d_pos < len(pend.ids):
            take = (len(pend.ids) - pend.d_pos if whole
                    else min(self.prefill_chunk,
                             len(pend.ids) - pend.d_pos))
            chunk = pend.ids[pend.d_pos:pend.d_pos + take]
            w0, p0 = time.time(), time.perf_counter()
            pend.d_cache = self._apply_draft_chunk(pend.d_cache, chunk)
            if pend.req._tracer is not None:
                # distinct name: request_breakdown charges it to the
                # prefill phase but its tokens never enter the
                # reused-vs-computed prompt ledger (draft work is
                # acceptance fuel, not prompt prefill)
                pend.req._tracer.record_span(
                    "engine.draft_prefill_chunk", w0,
                    time.perf_counter() - p0, parent=pend.req.trace_ctx,
                    tokens_computed=take, pos=pend.d_pos + take)
            pend.d_pos += take
        if pend.pos >= len(pend.ids) and (
                pend.d_cache is None or pend.d_pos >= len(pend.ids)):
            self._finish_prefill(slot)

    def _finish_prefill(self, slot: int) -> None:
        """Admission completes: publish the prompt's K/V to the paged
        pool (becoming the row's lifetime block chain), splice the row
        cache into the live batch, emit the first token. Resume rows
        skip publish and first-token — their chain and tokens already
        exist."""
        pend = self._pending.pop(slot)
        self._chunk_order.remove(slot)
        req = pend.req
        if pend.resume:
            # chain already held in _row_chains; tokens pre-fed
            self._cache = self._splice(
                self._cache, pend.cache, jnp.int32(slot))
            if pend.d_cache is not None:
                self._dcache = self._splice(
                    self._dcache, pend.d_cache, jnp.int32(slot))
            self._toks[slot] = int(req.tokens[-1])
            self._depths[slot] = len(pend.ids)
            return
        if self.paged_kv is not None:
            from kubeflow_tpu.serving.fleet.pagedkv import (
                SequenceChain,
                extract_prompt_kv,
            )

            kv = extract_prompt_kv(pend.cache, len(pend.ids))
            held = self.paged_kv.insert(pend.ids, kv)
            # insert's refs cover (and extend) the admission match's
            self.paged_kv.release(pend.match_refs)
            # expect_length marks chains that could not cover the whole
            # prompt (insert stopped at a covered-by-sibling boundary)
            # as frozen: release-only, never appended or resumed
            self._row_chains[slot] = SequenceChain(
                self.paged_kv, held, expect_length=len(pend.ids))
        self._cache = self._splice(
            self._cache, pend.cache, jnp.int32(slot))
        if pend.d_cache is not None:
            self._dcache = self._splice(
                self._dcache, pend.d_cache, jnp.int32(slot))
        self._depths[slot] = len(pend.ids)
        first = self._pick_first(
            pend.last_logits[0], req.temperature,
            jax.random.fold_in(req.key, 0))
        req.push(int(first))
        self._toks[slot] = int(first)
        if self._finished(req):
            self._retire(slot)

    def _retire(self, slot: int) -> None:
        req = self._rows[slot]
        self._rows[slot] = None
        chain = self._row_chains.pop(slot, None)
        if chain is not None:
            if req._keep_chain:
                # ownership to the handle's consumer — the disaggregated
                # router adopts the chain for the decode tier
                req.chain = chain
            else:
                chain.release()
        req.finish()

    def _blocks_fit(self, ids: np.ndarray, req: _InFlight) -> bool:
        """Block-budgeted admission check: does the pool's free-block
        count cover this request's worst-case growth (prompt + budget;
        a resume chain already pins its blocks, so only the remaining
        budget counts)? Conservative — prefix reuse can only need
        less."""
        import math

        bs = self.paged_kv.block_size
        if req._resume is not None:
            chain, _ = req._resume
            need = math.ceil(max(
                ids.size + req.max_new_tokens - chain.length, 0) / bs)
        else:
            need = math.ceil((ids.size + req.max_new_tokens) / bs)
        return self.paged_kv.available_blocks() >= need

    def _append_decode_kv(self, win, active: np.ndarray,
                          window: int, counts=None) -> None:
        """Grow each alive row's pool block chain with the positions the
        decode dispatch just wrote: the paged pool stays the KV substrate
        for the WHOLE lifetime, so a killed replica's rows can resume
        from their surviving chains and follow-on turns match into the
        generated suffix. `win` is the gathered per-row K/V window the
        step dispatch itself returned (the extraction rides the decode
        executable — no second dispatch). Rows that retired mid-dispatch
        already released their chain; frozen chains never grow."""
        rows = [slot for slot in range(self.max_rows)
                if active[slot] and self._rows[slot] is not None
                and slot in self._row_chains
                and not self._row_chains[slot].frozen]
        if not rows:
            return
        win = jax.device_get(win)
        for slot in rows:
            n = window if counts is None else int(counts[slot])
            req = self._rows[slot]
            k = len(req.tokens)
            # position p holds the KV of sequence token p; the window
            # [d, d+n) maps to emitted tokens [k-n-1, k-1) (the dispatch
            # INPUTS — the newest token's KV lands next dispatch)
            ids_seg = req.tokens[k - n - 1:k - 1]
            self._row_chains[slot].append(
                ids_seg, {p: a[slot, :n] for p, a in win.items()})

    def tick(self) -> bool:
        """One scheduling round: admit queued prompts into free rows, then
        advance every in-flight row steps_per_tick tokens. Returns True if
        any work remains.

        Locking: tick() is single-ticker by contract (run_until_idle OR
        the serving thread); rows/cache/toks are ticker-private. The lock
        guards ONLY the shared queue, so submit() from request threads
        never waits behind device dispatches."""
        # ---- admission: prefill into free rows ---------------------------
        chunked = self.prefill_chunk > 0 or self.paged_kv is not None
        for slot in range(self.max_rows):
            if self._rows[slot] is not None:
                continue
            with self._lock:
                if not self._queue:
                    break
                if self.block_budget \
                        and not self._blocks_fit(*self._queue[0]):
                    # block-budgeted admission: the pool's free-block
                    # count, not the row slot, is the admission token —
                    # FIFO preserved (head waits, nothing jumps it)
                    break
                ids, req = self._queue.pop(0)
            # seat the row BEFORE device work: a prefill failure must find
            # the request in _rows so _fail_all unblocks its caller
            req.slot = slot
            self._rows[slot] = req
            if req._tracer is not None:
                req._tracer.record_span(
                    "engine.queue_wait", req.t_submit_wall,
                    time.perf_counter() - req.t_submit,
                    parent=req.trace_ctx, slot=slot)
            if req._resume is not None:
                # resume admission: seed the whole cache from the paged
                # chain — zero prefill compute, decode continues
                self._admit_resume(slot, ids, req)
                continue
            if chunked:
                # chunked/seeded path: pooled prefix reuse + (with
                # prefill_chunk) chunk-per-tick interleaving below
                self._begin_prefill(slot, ids, req)
                continue
            w0, p0 = time.time(), time.perf_counter()
            last_logits, row_cache = self._prefill(ids)
            if req._tracer is not None:
                req._tracer.record_span(
                    "engine.prefill_chunk", w0, time.perf_counter() - p0,
                    parent=req.trace_ctx, tokens_computed=ids.size,
                    tokens_reused=0)
            self.prefill_tokens_total += ids.size
            self._cache = self._splice(
                self._cache, row_cache, jnp.int32(slot))
            if self.draft_module is not None:
                self._dcache = self._splice(
                    self._dcache, self._draft_prefill(ids), jnp.int32(slot))
            self._depths[slot] = ids.size
            first = self._pick_first(
                last_logits[0], req.temperature,
                jax.random.fold_in(req.key, 0))
            req.push(int(first))
            self._toks[slot] = int(first)
            # the prefill's first token may already finish the row
            if self._finished(req):
                self._retire(slot)
        # ---- chunked prefill: one chunk unit per tick (FIFO over pending
        # rows) so admission work interleaves with — never starves — the
        # decode dispatch below (the one-chunk-budget stall bound). A
        # pure-prefill replica (the disaggregated tier) raises
        # max_chunks_per_tick: it has no decode rows to starve.
        chunks = self.max_chunks_per_tick
        while self._chunk_order and chunks > 0:
            self._advance_prefill(self._chunk_order[0])
            chunks -= 1
        active = np.array(
            [r is not None and s not in self._pending
             for s, r in enumerate(self._rows)])
        if not active.any():
            with self._lock:
                return bool(self._queue) or bool(self._pending)
        if self.draft_module is not None:
            return self._spec_tick(active)
        # ---- T decode steps for every in-flight row ----------------------
        temps, base_keys = self._row_sampling_state()
        starts = np.array(
            [len(r.tokens) if r is not None else 0
             for r in self._rows], np.int32)
        depths0 = self._depths.copy()  # pre-dispatch: the append window
        # one read per tick: start_slo's live-attach assigns self.tsdb
        # from another thread, and a torn double-read would record an
        # absolute perf_counter value as a decode-tick sample
        tsdb = self.tsdb
        t_dec = time.perf_counter() if tsdb is not None else 0.0
        res = self._step(
            self._cache, jnp.asarray(self._toks),
            jnp.asarray(active), jnp.asarray(temps), base_keys,
            jnp.asarray(starts), jnp.asarray(depths0))
        win = None
        if self.paged_kv is not None:
            out, self._cache, win = res
        else:
            out, self._cache = res
        self.step_count += 1  # dispatches (the scheduling metric)
        out = np.asarray(out)  # (T, R)
        if tsdb is not None:
            # the decode-tick SLO series (docs/slo.md): one sample per
            # dispatch, measured to the host-visible sync (np.asarray).
            # Cost is one perf_counter read + a deque append
            tsdb.record("serving.decode_tick_s",
                        time.perf_counter() - t_dec)
        for slot, req in enumerate(self._rows):
            if req is None or slot in self._pending:
                continue  # pending rows decoded garbage; discard
            for j in range(out.shape[0]):
                req.push(int(out[j, slot]))
                self._toks[slot] = int(out[j, slot])
                if self._finished(req):
                    self._retire(slot)  # discard the scan tail
                    break
        if self.paged_kv is not None:
            self._append_decode_kv(win, active, out.shape[0])
        self._depths[active] += out.shape[0]
        with self._lock:
            pending = bool(self._queue)
        return pending or any(r is not None for r in self._rows)

    def _spec_tick(self, active: np.ndarray) -> bool:
        """One speculative round for every in-flight row (one dispatch):
        each row emits between 1 and gamma+1 tokens — its own accepted
        prefix plus the correction. Greedy rows are target-greedy-exact;
        sampled rows run the rowwise rejection scheme."""
        temps, base_keys = self._row_sampling_state()
        # STATIC any-sampled flag: an all-greedy batch dispatches the
        # specialized executable with no rejection-sampling machinery;
        # the first sampled admission retraces once (like a new prefill
        # bucket) and the mixed executable serves from then on
        tsdb = self.tsdb  # one read: live-attach races a torn pair
        t_dec = time.perf_counter() if tsdb is not None else 0.0
        res = self._spec_step(
            self._cache, self._dcache, jnp.asarray(self._toks),
            jnp.asarray(active), jnp.asarray(self._depths),
            jnp.asarray(temps), base_keys, bool((temps > 0).any()))
        win = None
        if self.paged_kv is not None:
            upd, a, self._cache, self._dcache, win = res
        else:
            upd, a, self._cache, self._dcache = res
        self.step_count += 1  # dispatches (the scheduling metric)
        upd = np.asarray(upd)                               # (R, gamma+1)
        a = np.asarray(a)                                   # (R,)
        if tsdb is not None:
            tsdb.record("serving.decode_tick_s",
                        time.perf_counter() - t_dec)
        for slot, req in enumerate(self._rows):
            if req is None or slot in self._pending:
                continue  # pending rows' round output is garbage
            self._depths[slot] += int(a[slot]) + 1
            for j in range(int(a[slot]) + 1):
                req.push(int(upd[slot, j]))
                self._toks[slot] = int(upd[slot, j])
                if self._finished(req):
                    self._retire(slot)  # discard the round's tail
                    break
        if self.paged_kv is not None:
            # each alive row accepted a+1 tokens: its verify pass wrote
            # valid K/V at [depth0, depth0 + a + 1) — append exactly that
            self._append_decode_kv(win, active, self.gamma + 1,
                                   counts=a + 1)
        with self._lock:
            pending = bool(self._queue)
        return pending or any(r is not None for r in self._rows)

    def _row_sampling_state(self):
        """(temps (R,) f32, base_keys (R, 2)) marshalled from the row
        table — the ONE definition both decode paths (plain tick and
        _spec_tick) feed their executables."""
        zero = jax.random.PRNGKey(0)
        temps = np.array(
            [r.temperature if r is not None else 0.0
             for r in self._rows], np.float32)
        base_keys = jnp.stack([
            r.key if r is not None and r.temperature > 0 else zero
            for r in self._rows])
        return temps, base_keys

    @staticmethod
    def _finished(req: _InFlight) -> bool:
        if len(req.tokens) >= req.max_new_tokens:
            return True
        return (req.eos_token_id is not None
                and req.tokens[-1] in req.eos_token_id)

    def run_until_idle(self) -> None:
        while self.tick():
            pass

    # ------------------------------------------------------- serving mode

    def start(self) -> "ContinuousBatcher":
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                busy = self.tick()
            except Exception as exc:  # noqa: BLE001 — the engine must
                # survive a poisoned round: fail every request it was
                # carrying (their threads unblock with the error instead
                # of hanging to timeout) and keep serving fresh ones
                self._fail_all(f"{type(exc).__name__}: {exc}")
                busy = False
            if not busy:
                self._stop.wait(0.002)  # idle: poll the queue cheaply

    def _fail_all(self, reason: str) -> None:
        with self._lock:
            queued = [req for _, req in self._queue]
            self._queue.clear()

        def hand_off(req, chain) -> None:
            # a usable chain TRANSFERS to the handle only when the FLEET
            # ROUTER is listening (it wired this engine and its on_done
            # requeue resumes-or-releases every transferred chain — the
            # zero-redecode rescue); a direct consumer's on_done has no
            # such contract, so its chain releases and the blocks become
            # reuse inventory instead of leaking pins
            if chain is None:
                return
            if req is not None and req.on_done is not None \
                    and getattr(self, "_fleet_managed", False) \
                    and not chain.frozen:
                req.chain = chain
            else:
                chain.release()

        if self.paged_kv is not None:
            for pend in self._pending.values():
                self.paged_kv.release(pend.match_refs)
            for slot, chain in self._row_chains.items():
                hand_off(self._rows[slot], chain)
            for req in queued:
                if req._resume is not None:
                    chain, _ = req._resume
                    req._resume = None
                    hand_off(req, chain)
        self._pending.clear()
        self._chunk_order.clear()
        self._row_chains.clear()
        for req in queued + [r for r in self._rows if r is not None]:
            if req._resume is not None:
                # a seated-but-unqueued resume cannot exist; queued ones
                # were handled above — clear defensively
                req._resume = None
            req.finish(error=reason)
        self._rows = [None] * self.max_rows

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
