"""CPU-proxy perf workloads — host-side regressions caught WITHOUT a TPU.

Counts and host-phase ratios only: a time, a rate or a utilization of the
device comes from a chip run, never from here. These workloads run the
same code paths the real benches exercise — traced MLP train steps,
continuous-serve decode ticks, a reconcile storm on FakeCluster — on CPU
with fixed seeds, and express every phase as a RATIO to an in-run anchor
measured by the same machinery:

  - mlp_train anchors data_load / stall to the jit step's own compute
    time (a machine running everything 2x slower moves numerator and
    denominator together; a code change that slows ONLY the input
    pipeline moves the ratio);
  - reconcile_storm anchors reconcile percentiles to a calibration unit
    (the median of a fixed FakeCluster get loop — the same store lock +
    deepcopy machinery a reconcile pass runs through);
  - serve_ticks anchors per-dispatch engine time to a fixed jit matmul.

Ratios are gated against checked-in budgets (tests/golden/
prof_budgets.json; `KFTPU_UPDATE_PROF_BUDGETS=1` regenerates) with
generous multipliers, so `make test` fails on an injected 2x slowdown
while machine-speed drift passes. The test-only chaos hook
(KFTPU_PROF_CHAOS="phase:N") REPEATS the phase's deterministic work N
times — no sleeps, so the injection scales with the machine exactly like
a real regression would.

Phase medians (not means) across steps make single-GC-pause outliers
irrelevant on both the budget-regen and the gate side.
"""

from __future__ import annotations

import os
import time
from functools import partial

from kubeflow_tpu.utils.envvars import ENV_PROF_CHAOS

#: default allowed measured/budget ratio per workload (a phase fails the
#: gate when measured_rel > budget_rel * ratio + GATE_SLACK)
DEFAULT_MAX_RATIO = 1.5
#: absolute slack added to every allowance: tiny phases (stall on an idle
#: CPU) have huge relative noise but bounded absolute effect
GATE_SLACK = 0.08


def chaos_repeats(phase: str) -> int:
    """Work-repeat factor for a phase from the test-only chaos hook env
    (KFTPU_PROF_CHAOS="data_load:2,reconcile:2"). 1 = untouched."""
    raw = os.environ.get(ENV_PROF_CHAOS, "")
    for term in raw.split(","):
        name, _, factor = term.partition(":")
        if name.strip() == phase and factor:
            try:
                return max(1, int(round(float(factor))))
            except ValueError:
                continue
    return 1


def chaos_flag(phase: str) -> bool:
    """Presence test for phases whose injection is a MODE, not a work
    multiplier — KFTPU_PROF_CHAOS="scaler_freeze:1" arms the frozen
    autoscaler (the factor is ignored; listing the phase turns it on)."""
    raw = os.environ.get(ENV_PROF_CHAOS, "")
    return any(term.partition(":")[0].strip() == phase
               for term in raw.split(",") if term.strip())


def _median(values: list[float]) -> float:
    vs = sorted(values)
    return vs[len(vs) // 2] if vs else 0.0


def _best_of(fn, gated_phase: str, runs: int = 2) -> dict:
    """Run a workload `runs` times and keep the run with the LOWEST gated
    ratio — scheduler/GC noise only ever inflates a run, while a real
    regression (or the chaos hook) inflates every run, so best-of-N
    narrows the gate's noise band without blunting its teeth."""
    best = None
    for _ in range(runs):
        rec = fn()
        if rec.get("skipped"):
            return rec  # environment can't run it — no second attempt
        if best is None or rec["rel"][gated_phase] \
                < best["rel"][gated_phase]:
            best = rec
    return best


def _min_phases(fn, phases: tuple[str, ...], runs: int = 2,
                attach: dict | None = None) -> dict:
    """Per-PHASE min over `runs` runs (the mlp_train rationale applied
    across whole-workload repetitions): each timing phase lands at its
    own noise floor. Count phases are deterministic and identical across
    runs, so taking the first record for everything else is exact.
    `attach` maps a phase to top-level record keys that must travel WITH
    that phase's winning run (serve_fleet's `slo` sub-dict rides
    slo_decode_burn — the acceptance record must not show run 1's burn
    rates next to run 2's gated value)."""
    recs = [fn() for _ in range(runs)]
    best = recs[0]
    for rec in recs[1:]:
        for p in phases:
            if rec["rel"][p] < best["rel"][p]:
                best["rel"][p] = rec["rel"][p]
                if p in rec.get("phases_s", {}):
                    best["phases_s"][p] = rec["phases_s"][p]
                for key in (attach or {}).get(p, ()):
                    if key in rec:
                        best[key] = rec[key]
    return best


# ------------------------------------------------------------- mlp_train


def _mlp_step():
    """One cached jit SGD step for a fixed MLP (no mesh machinery).
    Sized so the step costs MORE than
    one host fetch: the async-input gate needs an overlap-feasible
    balance (a fetch that dwarfs compute can never be hidden)."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        onehot = jax.nn.one_hot(y, 10)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    @jax.jit
    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return params, loss

    return step


_MLP_STEP = None

#: mlp_train geometry. The host fetch is deliberately matmul-DOMINATED
#: (an augmentation matrix multiply, BLAS-class like the jit step) so the
#: fetch/compute balance — which decides how much input cost the async
#: loader can hide — tracks the machine's matmul speed on BOTH sides and
#: stays comparable across machines; the memory-bound take/normalize part
#: is kept small via the pool size.
_MLP_POOL = 512
_MLP_BATCH = 384
_MLP_IN = 1024
_MLP_HIDDEN = 512


def mlp_train(steps: int = 16, batch: int = _MLP_BATCH,
              pool: int = _MLP_POOL) -> dict:
    """Fixed-seed MLP train loop traced with the REAL span names
    (train.data_load / train.step) and broken down by the REAL analytics
    engine — the cpu-proxy twin of the trainer hot loop. Two loops per
    run over the SAME fetch work:

      - the inline (sync) loop: every fetch on the step critical path —
        gates `data_load` (traced fetch vs its raw un-spanned twin, ~1.0:
        span machinery overhead, machine-invariant) and `stall`;
      - the async loop: the same fetches through train/data.AsyncLoader —
        gates `data_load_async`, the critical-path input cost REMAINING
        after the background thread hides the assembly, in the same
        raw-fetch units. This is the tightened input budget: sync pays
        ~1.0 fetch units per step, the async pipeline must stay near
        zero, and the data_load:2 chaos repeat (producer work doubled —
        now slower than the step) overflows back onto the critical path
        and fails both gates.
    """
    global _MLP_STEP
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.profiling.analytics import step_breakdown
    from kubeflow_tpu.tracing import Tracer
    from kubeflow_tpu.train.data import AsyncLoader

    if _MLP_STEP is None:
        _MLP_STEP = _mlp_step()
    rng = np.random.default_rng(0)
    base = rng.standard_normal((pool, 784)).astype(np.float32)
    mix = rng.standard_normal((784, _MLP_IN)).astype(np.float32) * 0.05
    labels = rng.integers(0, 10, size=pool).astype(np.int32)
    params = {
        "w1": jnp.asarray(
            rng.standard_normal((_MLP_IN, _MLP_HIDDEN)).astype(np.float32)
            * 0.05),
        "b1": jnp.zeros((_MLP_HIDDEN,), jnp.float32),
        "w2": jnp.asarray(
            rng.standard_normal((_MLP_HIDDEN, 10)).astype(np.float32)
            * 0.05),
        "b2": jnp.zeros((10,), jnp.float32),
    }
    repeats = chaos_repeats("data_load")
    buf = np.empty_like(base)  # reused: allocator churn is not the phase

    def fetch(i: int):
        # the deterministic host-side input-pipeline work the gate
        # watches: shuffle + whole-pool normalize (into a preallocated
        # buffer) + augmentation matmul per step. The matmul allocates
        # its (batch, in_dim) output each call — identical allocation in
        # the raw twin below, so it cancels out of the gated ratio
        x = y = None
        for _ in range(repeats):
            perm = np.random.default_rng(1000 + i).permutation(pool)
            np.take(base, perm, axis=0, out=buf)
            mu = buf.mean(axis=0)
            sd = buf.std(axis=0)
            np.subtract(buf, mu, out=buf)
            np.divide(buf, sd + 1e-6, out=buf)
            x = buf[:batch] @ mix
            y = labels[perm[:batch]]
        return x, y

    def raw_fetch_once() -> float:
        # the identical numpy kernels, UN-spanned and UN-chaosed (fixed
        # perm, repeats ignored): the data_load anchor. Numerator and
        # denominator share kernels and buffers, so machine-speed noise
        # cancels almost exactly, while the chaos repeat — and any
        # regression in the span/accounting path the traced loop runs
        # through — moves only the numerator.
        perm = np.random.default_rng(999).permutation(pool)
        t0 = time.perf_counter()
        np.take(base, perm, axis=0, out=buf)
        mu = buf.mean(axis=0)
        sd = buf.std(axis=0)
        np.subtract(buf, mu, out=buf)
        np.divide(buf, sd + 1e-6, out=buf)
        buf[:batch] @ mix
        return time.perf_counter() - t0

    # warmup outside the trace: jit compile must not pollute step 0
    wx, wy = fetch(-1)
    params, loss = _MLP_STEP(params, wx, wy)
    float(loss)
    import gc

    # two traced runs, per-phase MIN of the in-run medians: scheduler /
    # frequency noise only inflates a run, a real regression (or the
    # chaos hook) inflates both — same rationale as _best_of, applied
    # per phase so numerator and denominator are each at their floor
    runs: list[dict[str, float]] = []
    n_steps = 0
    for _ in range(2):
        tracer = Tracer(capacity=8 * steps)
        # same GC posture every run: earlier workloads' garbage otherwise
        # triggers collections inside the numpy fetch and skews data_load
        gc.collect()
        for i in range(steps):
            with tracer.span("train.data_load", seq=i):
                x, y = fetch(i)
            with tracer.span("train.step", step=i):
                params, loss = _MLP_STEP(params, x, y)
                float(loss)  # host read: the true per-step sync
        per_step = step_breakdown(tracer.snapshot())
        n_steps = len(per_step)
        rec = {
            p: _median([s[p] for s in per_step])
            for p in ("data_load", "compute", "stall")
        }
        # async loop: SAME fetch work, assembled on the loader thread —
        # through the real AsyncLoader and the real wait_s/assemble_s
        # span-attr path the trainer uses, so the analytics split
        # (data_wait/data_assemble) is exercised, not simulated
        atracer = Tracer(capacity=8 * steps)
        gc.collect()
        loader = AsyncLoader(range(steps), transform=fetch, size=2,
                             name="cpu_proxy.mlp")
        try:
            for i in range(steps):
                with atracer.span("train.data_load", seq=i) as sp:
                    x, y = next(loader)
                    st = loader.pop_stats()
                    sp.set_attribute("wait_s", st["wait_s"])
                    sp.set_attribute("assemble_s", st["assemble_s"])
                with atracer.span("train.step", step=i):
                    params, loss = _MLP_STEP(params, x, y)
                    float(loss)
        finally:
            loader.close()
        async_steps = step_breakdown(atracer.snapshot())
        rec["data_load_async"] = _median(
            [s["data_load"] for s in async_steps])
        rec["data_wait_async"] = _median(
            [s["data_wait"] for s in async_steps])
        runs.append(rec)
    data = min(r["data_load"] for r in runs)
    compute = min(r["compute"] for r in runs)
    stall = min(r["stall"] for r in runs)
    adata = min(r["data_load_async"] for r in runs)
    awaits = min(r["data_wait_async"] for r in runs)
    # the data_load anchor: min over medians-of-8 raw fetches, sampled
    # after each traced run (either window may catch interference)
    gc.collect()
    fetch_unit = min(
        _median([raw_fetch_once() for _ in range(8)]) for _ in range(3))
    return {
        "workload": "mlp_train",
        "steps": n_steps,
        "anchor": "raw_fetch/compute",
        "anchor_s": round(fetch_unit, 6),
        "phases_s": {"data_load": round(data, 6),
                     "data_load_async": round(adata, 6),
                     "compute": round(compute, 6),
                     "stall": round(stall, 6)},
        "async_data_wait_s": round(awaits, 6),
        # data_load vs the raw twin of its own kernels (ratio ~= 1 + span
        # machinery overhead, machine-invariant); the async loop's
        # critical-path remainder in the SAME units; stall vs the jit step
        "rel": {"data_load": (round(data / fetch_unit, 4)
                              if fetch_unit else 0.0),
                "data_load_async": (round(adata / fetch_unit, 4)
                                    if fetch_unit else 0.0),
                "stall": round(stall / compute, 4) if compute else 0.0},
    }


# ---------------------------------------------------------- grad_overlap


def grad_overlap(layers: int = 8, dim: int = 384, batch: int = 256,
                 steps: int = 6) -> dict:
    """Comm/compute-overlap gate (ROADMAP item 5, the `mlp_train` blind
    spot the re-anchor names): the SAME per-layer backward + per-layer
    gradient-communication work run two ways —

      - overlapped: each layer's gradient is handed to a dedicated comm
        engine the moment backward produces it, and the engine works
        while the remaining backward keeps running — the schedule the
        trainer's per-rule `with_sharding_constraint`s
        (partitioner.constrain_grads) let XLA's latency-hiding scheduler
        build on TPU, where the collective rides the ICI engine in
        parallel with the MXU. On this CPU proxy the engine is a worker
        thread driving device-1 dispatches (jax CPU executes
        concurrently across host threads — measured, same mechanism the
        AsyncLoader gate uses), and only the post-backward residual
        drain lands on the critical path (`train.comm` span);
      - serialized: the full backward completes first, then every
        layer's comm runs on the critical path — the no-overlap schedule
        (one big all-reduce after backward).

    Gated: ``overlap_ratio`` = overlapped/serialized step wall (in-run,
    machine-invariant — both sides run identical kernels in the same
    process). The chaos hook ``KFTPU_PROF_CHAOS="grad_overlap:2"``
    FORCES SERIALIZATION of the overlapped loop (the engine is joined
    after every hand-off; work unchanged, pipelining destroyed), driving
    the ratio to ~1.0 — and must fail the gate. Which gradients get a
    collective comes from a REAL Partitioner's rule-derived specs over a
    transformer-shaped param tree, so the workload consumes the same
    derivation the trainer does.
    """
    import queue
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.parallel.partitioner import (
        Partitioner,
        record_comm,
    )
    from kubeflow_tpu.profiling.analytics import step_breakdown
    from kubeflow_tpu.tracing import Tracer

    forced_serial = chaos_repeats("grad_overlap") > 1
    devs = jax.devices()
    comm_dev = devs[1 % len(devs)]
    rng = np.random.default_rng(11)
    # transformer-shaped param paths: the partitioner's logical rules
    # decide which grads are sharded (and therefore owe a collective)
    pt = Partitioner()
    paths = [f"h{i}/attn/query/kernel" for i in range(layers)]
    specs = [pt.spec_for(p, (dim, dim)) for p in paths]
    comm_layers = [i for i, s in enumerate(specs)
                   if any(a is not None for a in tuple(s))]
    Ws = [jnp.asarray(rng.standard_normal((dim, dim)).astype(np.float32)
                      * 0.05) for _ in range(layers)]
    mix = jax.device_put(
        jnp.asarray(rng.standard_normal((dim, dim)).astype(np.float32)
                    * 0.05), comm_dev)
    cot0 = jnp.asarray(rng.standard_normal((batch, dim))
                       .astype(np.float32))

    @jax.jit
    def bwd(cot, w):
        # one layer of "remaining backward": produces this layer's grad
        # and the next cotangent (a dependent chain, like real reverse-mode)
        g = cot.T @ (cot @ w)
        return jnp.tanh(cot @ w.T), g

    @jax.jit
    def comm_op(g, m):
        # the all-reduce stand-in: device-1 work proportional to the
        # gradient, off the backward's device
        return jnp.tanh(g @ m) @ m

    def comm_submit(g):
        # async hand-off to the comm device: the transfer starts now
        return comm_op(jax.device_put(g, comm_dev), mix)

    # warmup: compile + first transfers outside every timed window
    c, g = bwd(cot0, Ws[0])
    jax.block_until_ready(comm_submit(g))
    jax.block_until_ready(c)

    def run_overlapped(tracer, i):
        """Backward on the main thread; comm engine thread drains a
        queue of grads as they appear. Returns the step wall time."""
        work: queue.Queue = queue.Queue()
        done: list = []

        def engine():
            while True:
                item = work.get()
                if item is None:
                    return
                done.append(jax.block_until_ready(comm_submit(item)))

        t = threading.Thread(target=engine, name="kftpu-comm-engine",
                             daemon=True)
        t0 = time.perf_counter()
        t.start()
        with tracer.span("train.step", step=i):
            cot = cot0
            for l in range(layers):
                cot, g = bwd(cot, Ws[l])
                if l in comm_layers:
                    work.put(g)
                    if forced_serial:
                        # chaos: wait for the engine to finish THIS
                        # gradient before the next backward layer —
                        # work identical, overlap destroyed
                        while not work.empty() or len(done) < sum(
                                1 for x in comm_layers if x <= l):
                            time.sleep(0)
            jax.block_until_ready(cot)
        with tracer.span("train.comm", step=i):
            # residual: whatever the engine has not finished by the time
            # backward ends is un-overlapped comm on the critical path
            work.put(None)
            t.join()
        return time.perf_counter() - t0

    def run_serialized(tracer, i):
        t0 = time.perf_counter()
        with tracer.span("train.step", step=i):
            cot = cot0
            grads = []
            for l in range(layers):
                cot, g = bwd(cot, Ws[l])
                if l in comm_layers:
                    grads.append(g)
            jax.block_until_ready(cot)
            jax.block_until_ready(grads)
        with tracer.span("train.comm", step=i):
            for g in grads:
                jax.block_until_ready(comm_submit(g))
        return time.perf_counter() - t0

    import gc

    recs = []
    for _ in range(2):
        gc.collect()
        otr, str_ = Tracer(capacity=8 * steps), Tracer(capacity=8 * steps)
        over = _median([run_overlapped(otr, i) for i in range(steps)])
        seri = _median([run_serialized(str_, i) for i in range(steps)])
        ocomm = _median([s["comm"] for s in step_breakdown(otr.snapshot())
                         if s["comm"] > 0] or [0.0])
        scomm = _median([s["comm"] for s in step_breakdown(str_.snapshot())
                         if s["comm"] > 0] or [0.0])
        recs.append({"over": over, "serial": seri,
                     "ocomm": ocomm, "scomm": scomm})
    # per-phase min across runs (the mlp_train rationale): noise only
    # ever inflates; the chaos hook inflates BOTH runs' overlapped side
    over = min(r["over"] for r in recs)
    seri = min(r["serial"] for r in recs)
    ocomm = min(r["ocomm"] for r in recs)
    scomm = min(r["scomm"] for r in recs)
    ratio = over / seri if seri else 0.0
    record_comm(ocomm, overlap_ratio=ratio)
    return {
        "workload": "grad_overlap",
        "layers": layers,
        "comm_layers": len(comm_layers),
        "steps": steps,
        "anchor": "serialized_step",
        "anchor_s": round(seri, 6),
        "phases_s": {"step_overlapped": round(over, 6),
                     "step_serialized": round(seri, 6),
                     "comm_residual": round(ocomm, 6),
                     "comm_serialized": round(scomm, 6)},
        "rel": {
            # the gated in-run ratio: <1 means the engine genuinely hid
            # comm behind the remaining backward; forced serialization
            # (the chaos teeth) drives it to ~1
            "overlap_ratio": round(ratio, 4),
        },
    }


# ----------------------------------------------------- train_restart_warm


def train_restart_warm(batch: int = 128, features: int = 64) -> dict:
    """Restart-warm compile gate (ROADMAP item 5; the restart-recompile
    cost of 2011.03641): a COLD incarnation of the real Trainer sets up
    against an empty persistent compile cache, a gang restart is
    simulated (jax.clear_caches drops every in-memory jit/compile cache,
    exactly what a new worker process starts without), and the WARM
    incarnation must

      - perform ZERO backend compilations of the train step (the
        /jax/compilation_cache/cache_misses counter the serving AOT
        tests pin, here via utils/compile_cache.compile_counts), and
      - finish setup-to-first-step in a small fraction of the cold
        incarnation's — warm/cold is an in-run ratio of the same
        machinery on the same machine, so the budget is machine-speed
        invariant.

    Setup-to-first-step is the exact window gang-restart overhead pays
    per worker: init_state + warm_start (the train.compile phase) + the
    first optimizer step completing."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from kubeflow_tpu.utils import compile_cache as cc

    try:
        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.train import Trainer, TrainerConfig
    except ImportError as e:
        return {"workload": "train_restart_warm", "skipped": str(e),
                "rel": {}, "phases_s": {}}

    rng = np.random.default_rng(7)
    x = rng.standard_normal((batch, features)).astype(np.float32)
    y = rng.integers(0, 10, size=batch).astype(np.int32)
    cache_dir = tempfile.mkdtemp(prefix="kftpu-restart-warm-")
    # the workload owns the process-global compile-cache config only for
    # its duration — later workloads/tests must see the prior state
    saved = {
        "jax_compilation_cache_dir":
            jax.config.jax_compilation_cache_dir,
        "jax_persistent_cache_min_compile_time_secs":
            jax.config.jax_persistent_cache_min_compile_time_secs,
        "jax_persistent_cache_min_entry_size_bytes":
            jax.config.jax_persistent_cache_min_entry_size_bytes,
    }

    def incarnation() -> tuple[float, float, dict]:
        """One worker lifetime: build the trainer, warm-start the step
        executables against the shared cache, run the first step.
        Returns (init_s, compile_s, warm_start info): compile_s — the
        warm_start + first-step window — is the part of restart overhead
        the compile cache exists to erase, and what the ratio gates;
        init_s (state build, whose backend compile also rides the cache)
        is reported for the full setup picture."""
        trainer = Trainer(
            MnistMLP(hidden=(32,)),
            TrainerConfig(batch_size=batch, log_every_steps=10**9,
                          compile_cache_dir=cache_dir),
        )
        t0 = time.perf_counter()
        # same order as Trainer.fit: cache live BEFORE the first compile,
        # so the state-build program is cached/hit too (enabling later
        # would leave it unwritten in cold and a guaranteed miss in warm)
        cc.enable_persistent_cache(cache_dir)
        state = trainer.init_state(x)
        t1 = time.perf_counter()
        info = trainer.warm_start(x, y)
        state, m = trainer.train_step(state, (x, y))
        float(m["loss"])  # host read: first step actually completed
        return t1 - t0, time.perf_counter() - t1, info

    import gc

    gc.collect()
    jax.clear_caches()  # a fresh process has no in-memory caches
    try:
        before = cc.compile_counts()
        cold_init, cold_s, cold_info = incarnation()
        cold_misses = (cc.compile_counts()["backend_misses_total"]
                       - before["backend_misses_total"])
        # --- simulated gang restart: in-memory caches gone, persistent
        # cache + serialized executables survive (they are the DISK the
        # jobcontroller's injected KFTPU_COMPILE_CACHE_DIR points at)
        jax.clear_caches()
        gc.collect()
        before = cc.compile_counts()
        warm_init, warm_s, warm_info = incarnation()
        warm_misses = (cc.compile_counts()["backend_misses_total"]
                       - before["backend_misses_total"])
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        for k, v in saved.items():
            jax.config.update(k, v)
        # drop the latched cache object too — it points at the deleted
        # temp dir; the next compile re-initializes from restored config
        from jax.experimental.compilation_cache import (
            compilation_cache as jax_cc,
        )

        jax_cc.reset_cache()
    return {
        "workload": "train_restart_warm",
        "cold_s": round(cold_s, 6),
        "warm_s": round(warm_s, 6),
        "cold_init_s": round(cold_init, 6),
        "warm_init_s": round(warm_init, 6),
        "cold_compiled": cold_info.get("compiled", ""),
        "warm_reloaded": warm_info.get("reloaded", ""),
        # cold MUST count misses: it proves the miss counter and the
        # persistent cache are live, so warm's zero is a real hit rate
        # and not a dead-cache vacuity (the gate test asserts this)
        "cold_backend_compiles": cold_misses,
        "anchor": "cold_compile_phase",
        "anchor_s": round(cold_s, 6),
        "phases_s": {"warm_compile": round(warm_s, 6)},
        "rel": {
            # in-run ratio: machine-invariant by construction
            "warm_cold_ratio": round(warm_s / cold_s, 4) if cold_s else 0.0,
            # a COUNT over the WHOLE warm incarnation (state build +
            # warm_start + first step) — any backend compile is a
            # regression of the restart-warm contract (budget 0, gated
            # on the absolute slack alone)
            "warm_backend_compiles": warm_misses,
        },
    }


# ------------------------------------------------------------ serve_ticks


def serve_ticks(rows: int = 4, n_requests: int = 6, prompt_len: int = 12,
                new_tokens: int = 8) -> dict:
    """Continuous-batching decode ticks on a tiny fixed-seed GPT: the
    per-dispatch engine time (scheduling + splice + decode step) in units
    of a fixed jit matmul — the serving analogue of the step breakdown."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, mlp_dim=128, dropout_rate=0.0,
                    max_len=prompt_len + new_tokens + 2)
    model = GPTLM(cfg)
    rng = np.random.default_rng(2)
    prompts = rng.integers(1, cfg.vocab_size,
                           size=(n_requests, prompt_len)).astype(np.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(prompts[:1]))
    eng = ContinuousBatcher(model, variables, max_rows=rows,
                            default_max_new_tokens=new_tokens)
    # warmup: compile prefill + decode + splice once, outside the timing
    eng.submit(prompts[0], max_new_tokens=2)
    eng.run_until_idle()
    step0 = eng.step_count
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    eng.run_until_idle()
    dt = time.perf_counter() - t0
    dispatches = max(eng.step_count - step0, 1)
    toks = sum(len(r.result(timeout=0)) for r in reqs if r.done.is_set())
    unit = _calibration_unit()
    per_dispatch = dt / dispatches
    return {
        "workload": "serve_ticks",
        "dispatches": dispatches,
        "tokens": toks,
        "anchor": "matmul_unit",
        "anchor_s": round(unit, 6),
        "phases_s": {"tick": round(per_dispatch, 6)},
        "rel": {"tick": round(per_dispatch / unit, 4) if unit else 0.0},
    }


_CALIBRATION_UNIT = None


def _calibration_unit() -> float:
    """Median seconds of a fixed 256x256 jit matmul + host read — the
    machine-speed normalizer for workloads without an in-run compute
    anchor. Cached per process (the gate compares one process's run)."""
    global _CALIBRATION_UNIT
    if _CALIBRATION_UNIT is not None:
        return _CALIBRATION_UNIT
    import jax
    import jax.numpy as jnp
    import numpy as np

    a = jnp.asarray(np.random.default_rng(3)
                    .standard_normal((256, 256)).astype(np.float32))
    f = jax.jit(lambda m: (m @ m).sum())
    float(f(a))  # compile
    samples = []
    for _ in range(20):
        t0 = time.perf_counter()
        float(f(a))
        samples.append(time.perf_counter() - t0)
    _CALIBRATION_UNIT = _median(samples)
    return _CALIBRATION_UNIT


# ------------------------------------------------------------ serve_fleet


def _arm_decode_chaos(engines, repeats: int) -> None:
    """KFTPU_PROF_CHAOS="decode_tick:N": repeat each engine's per-tick
    device dispatches (decode scan + prefill chunk) N times — work
    repeated, never slept, so the injection scales with the machine
    exactly like a real engine regression. The calibration anchor does
    NOT pass through these wrappers, so the gate's teeth bite."""
    if repeats <= 1:
        return
    import jax

    def wrap(fn):
        def run(*args, **kwargs):
            # pure jitted calls: same inputs, state unchanged. Each call
            # is SERIALIZED (block before the next dispatch) — XLA's CPU
            # client otherwise executes the independent duplicates on
            # idle pool threads in parallel and the injected work
            # disappears from the wall clock.
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            for _ in range(repeats - 1):
                jax.block_until_ready(fn(*args, **kwargs))
            return out
        return run

    for eng in engines:
        eng._step = wrap(eng._step)
        eng._apply_chunk = wrap(eng._apply_chunk)


#: decode-tick SLO threshold = this headroom x an IN-RUN healthy tick
#: median measured on an un-chaos-wrapped engine after warmup (the
#: mlp_train in-run-anchor trick): the untouched tree's samples sit at
#: ~1.0x the anchor, the decode_tick:2 chaos at ~2.0x, so the alert
#: FIRES under injected slowdown and stays quiet otherwise regardless
#: of machine speed (the falsifiable-teeth acceptance;
#: tests/test_prof_gate.py)
DECODE_SLO_HEADROOM = 1.4


def serve_fleet(replicas: int = 3, rows: int = 2, n_requests: int = 24,
                prompt_len: int = 12, shared_prefix: int = 8,
                new_tokens: int = 6, block: int = 4, chunk: int = 4,
                seed: int = 5) -> dict:
    """The fleet drill as a perf workload (docs/serving.md): N replica
    engines sharing one paged-KV pool behind the router, seeded open-loop
    tick-driven load with a mid-run replica kill. Everything the timed
    phase does is engine work, so arrivals/kill scheduled in TICK units
    make the TTFT-over-anchor ratio machine-speed invariant. Gated:

      - ttft_p99     p99 TTFT in calibration-matmul units (the serving
                     latency SLO, with the kill's requeue cost inside it)
      - reuse_computed_frac   computed prefill tokens / total prefill
                     positions during the load phase — a COUNT ratio; a
                     prefix-reuse regression drives it toward 1.0
      - dropped      requests lost across the replica kill — budget 0;
                     the zero-drop requeue contract, gated
      - slo_decode_burn   the decode-tick SLO's long-window burn rate
                     over the monitoring TSDB (docs/slo.md) — 0 on a
                     healthy tree (budget 0 + slack), driven to its cap
                     by the decode_tick:2 chaos, so the burn-rate
                     monitor itself has gated teeth

    The run is fully monitored: engines trace every request (the
    breakdown summary rides the record) and feed decode-tick samples to
    a TSDB whose recording sits INSIDE the gated steady window — the
    decode_tick budget passing WITH sampling live is the monitor's
    off-the-hot-path claim in falsifiable form (2011.03641).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.monitoring import SLOConfig, SLOMonitor, TimeSeriesStore
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    from kubeflow_tpu.serving.fleet import (
        FleetRouter,
        PagedKVPool,
        make_prompts,
        run_loadtest_sync,
    )
    from kubeflow_tpu.tracing import Tracer

    repeats = chaos_repeats("decode_tick")
    window = 40  # steady-state decode ticks in the dedicated window
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, mlp_dim=128, dropout_rate=0.0,
                    max_len=prompt_len + new_tokens + window + 12)
    model = GPTLM(cfg)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((1, prompt_len), jnp.int32))
    # the SLO threshold is anchored BEFORE the run (the unit is cached
    # per process, so later rel computations reuse this same value)
    unit = _calibration_unit()
    pool = PagedKVPool(block_size=block, capacity_blocks=512)
    tracer = Tracer(capacity=8192, service="serve_fleet")
    tsdb = TimeSeriesStore(capacity_per_series=2048)
    engines = [
        ContinuousBatcher(model, variables, max_rows=rows,
                          default_max_new_tokens=new_tokens,
                          paged_kv=pool, prefill_chunk=chunk)
        for _ in range(replicas)
    ]
    router = FleetRouter(engines)
    # make_prompts' prompt_len is the BODY length; the shared prefix
    # prepends, so total = prompt_len (the configured budget)
    body_len = prompt_len - shared_prefix
    prompts = make_prompts(n_requests, seed=seed, vocab=cfg.vocab_size,
                           prompt_len=body_len,
                           shared_prefix=shared_prefix)
    # warmup OUTSIDE the timed window: compile every executable the load
    # phase dispatches (chunk prefill, decode step, splice, first-token
    # pick) on every replica — the gate measures serving, not XLA.
    # Tracing/TSDB attach AFTER it: warmup requests must pollute neither
    # the request breakdown nor the decode-tick SLO series (a warmup
    # tick carries compile time — a guaranteed false bad-sample).
    warm = make_prompts(replicas, seed=seed + 1, vocab=cfg.vocab_size,
                        prompt_len=body_len,
                        shared_prefix=shared_prefix)
    for eng, w in zip(engines, warm):
        eng.submit(w, max_new_tokens=2)
        eng.run_until_idle()
        # second pass with the SAME prompt: full pool match -> suffix-1
        # prefill — the shape a post-kill requeue dispatches (its blocks
        # are already pooled). Without this, the requeued request pays a
        # chunk-1 compile INSIDE the timed phase and owns p99.
        eng.submit(w, max_new_tokens=2)
        eng.run_until_idle()
    # in-run healthy decode anchor for the SLO threshold: fill replica
    # 0's rows and median-time UNWRAPPED full-load ticks — the chaos
    # hook arms only after this, so the threshold is immune to the
    # injection while the monitored samples are not
    eng0 = engines[0]
    for p in make_prompts(rows, seed=seed + 3, vocab=cfg.vocab_size,
                          prompt_len=body_len,
                          shared_prefix=shared_prefix):
        eng0.submit(p, max_new_tokens=24)
    for _ in range(rows * (prompt_len // chunk + 2)):
        eng0.tick()
        if not eng0._pending and all(eng0._rows):
            break
    # measure through the SAME machinery the monitored samples use (a
    # scratch TSDB on the engine's own decode-tick hook), so anchor and
    # samples are the identical quantity — a full-tick stopwatch here
    # would fold in per-tick host overhead the samples don't carry and
    # blunt the teeth
    anchor_tsdb = TimeSeriesStore()
    eng0.tsdb = anchor_tsdb
    for _ in range(12):
        eng0.tick()
    eng0.tsdb = None
    healthy_tick = _median(
        [v for _, v in anchor_tsdb.window("serving.decode_tick_s",
                                          3600.0)])
    eng0.run_until_idle()
    _arm_decode_chaos(engines, repeats)
    router.tracer = tracer
    for eng in engines:
        eng.tracer = tracer
        eng.tsdb = tsdb
    import gc

    gc.collect()

    def sample_counters(_tick, rtr):
        # the zero-drop SLO's input: the fleet failure counter becomes a
        # TSDB series once per loadtest tick (the on_tick sampling hook)
        tsdb.record("fleet.requests_failed_total",
                    rtr.metrics["requests_failed_total"])

    t0_wall = time.time()
    report = run_loadtest_sync(
        router, prompts, seed=seed, mean_gap_ticks=0.6,
        new_tokens=new_tokens, kill_at_tick=8, kill_replica=1,
        on_tick=sample_counters)
    summary = report.summary()
    # snapshot the LOAD phase's request spans before the steady-state
    # rows below add theirs: the breakdown summary states what the
    # seeded drill proved (requests traced == requests submitted)
    load_spans = tracer.snapshot()
    # the report's prefill ledger is a per-run DELTA (warmup excluded)
    computed = report.prefill_tokens_total
    reused = report.prefill_tokens_reused
    # steady-state decode window on the survivors: fill every row, let
    # the chunked admissions complete, then time `window` round-robin
    # passes of IDENTICAL decode work. The mean over identical ticks is
    # far less noisy than a p99 sample — this phase is what gives the
    # decode_tick chaos its teeth, while ttft_p99 pins the latency SLO.
    alive = [r.engine for r in router.replicas if r.alive]
    steady = [eng.submit(p, max_new_tokens=window + 8)
              for eng in alive for p in make_prompts(
                  rows, seed=seed + 2, vocab=cfg.vocab_size,
                  prompt_len=body_len, shared_prefix=shared_prefix)]
    for _ in range(rows * (prompt_len // chunk + 2)):
        for eng in alive:
            eng.tick()
        if all(not e._pending and all(e._rows) for e in alive):
            break
    gc.collect()
    t0 = time.perf_counter()
    for _ in range(window):
        for eng in alive:
            eng.tick()
    decode_tick = (time.perf_counter() - t0) / window
    for eng in alive:  # drain the window rows untimed
        eng.run_until_idle()
    assert all(h.done.is_set() for h in steady)
    ttft_p99 = summary["ttft_p99_s"]

    # ---- SLO evaluation over the TSDB the run filled (docs/slo.md):
    # the decode-tick objective's threshold is anchored in calibration
    # units (machine-invariant like the gate itself); both windows must
    # burn for the alert to fire. Whole-run long window + last-quarter
    # short window, integer-rounded so burn keys stay stable.
    import math

    from kubeflow_tpu.profiling.analytics import (
        aggregate_requests,
        request_breakdown,
    )

    now = time.time()
    span_s = float(math.ceil(now - t0_wall) + 1)
    slo_threshold = DECODE_SLO_HEADROOM * healthy_tick
    decode_slo = SLOConfig(
        "serving_decode_tick", metric="serving.decode_tick_s",
        kind="above", threshold=slo_threshold, budget=0.25,
        windows=((span_s, 1.0), (max(float(math.ceil(span_s / 4)), 1.0),
                                 1.0)))
    drop_slo = SLOConfig(
        "serving_zero_drop", metric="fleet.requests_failed_total",
        kind="increase", budget=0.0, windows=((span_s, 1.0),))
    monitor = SLOMonitor(tsdb, (decode_slo, drop_slo))
    alerts = monitor.evaluate(now=now)
    states = {s["name"]: s for s in monitor.describe()}
    burn_long = states["serving_decode_tick"]["burn_rates"][
        SLOMonitor._wkey(span_s)]
    breakdown = aggregate_requests(request_breakdown(load_spans))
    return {
        "workload": "serve_fleet",
        "replicas": replicas,
        "requests": n_requests,
        "completed": summary["completed"],
        "dropped_count": summary["dropped"],
        "requeued": summary["requeued"],
        "replica_killed": True,
        "ticks": report.ticks,
        "prefill_tokens_computed": computed,
        "prefill_tokens_reused": reused,
        "anchor": "matmul_unit",
        "anchor_s": round(unit, 6),
        "phases_s": {"ttft_p50": summary["ttft_p50_s"],
                     "ttft_p99": ttft_p99,
                     "decode_tick": round(decode_tick, 6)},
        "rel": {
            "ttft_p99": round(ttft_p99 / unit, 4) if unit else 0.0,
            "decode_tick": round(decode_tick / unit, 4) if unit else 0.0,
            # COUNT ratios — machine-invariant by construction
            "reuse_computed_frac": round(
                computed / max(computed + reused, 1), 4),
            "dropped": summary["dropped"],
            # the burn-rate row: 0.0 healthy (budget 0 + slack), driven
            # to the cap by the decode_tick chaos — the SLO monitor's
            # own gated teeth
            "slo_decode_burn": round(min(burn_long, 10.0), 4),
        },
        "slo": {
            "decode_tick": {
                "fired": states["serving_decode_tick"]["fired"],
                "burn_rates": states["serving_decode_tick"]["burn_rates"],
                "threshold_s": round(slo_threshold, 6),
                "healthy_tick_s": round(healthy_tick, 6),
                "samples": states["serving_decode_tick"]["samples"],
            },
            "zero_drop": {
                "fired": states["serving_zero_drop"]["fired"],
                "burn_rates": states["serving_zero_drop"]["burn_rates"],
            },
            "alerts": [a.slo for a in alerts],
        },
        "request_breakdown": breakdown,
        "monitor_samples": tsdb.stats()["samples_total"],
        "tokens_per_s_total": summary["tokens_per_s_total"],
    }


# ------------------------------------------------------------ serve_disagg


def serve_disagg(rows: int = 2, n_requests: int = 18,
                 long_body: int = 20, short_body: int = 4,
                 shared_prefix: int = 8, new_tokens: int = 6,
                 block: int = 4, chunk: int = 4, seed: int = 9) -> dict:
    """The disaggregated prefill/decode tier vs the mixed fleet, SAME
    long-prompt-heavy mix (docs/serving.md "Disaggregated prefill/
    decode"): two four-replica fleets serve identical seeded arrivals —
    (a) the BASELINE: 4 mixed replicas, every engine interleaving
    chunked prefill with its decode rows; (b) the DISAGG tier: 2 prefill
    replicas (chunks only, stall bound lifted via max_chunks_per_tick)
    publishing finished chains through the shared paged pool + 2 decode
    replicas adopting chains by digest and decoding from the first
    generated position. Both phases kill one decode-serving replica
    mid-run. Gated:

      - ttft_p99 / decode_tick      disagg tier, calibration-matmul
                                    units. decode_tick is the median
                                    DISPATCH time on the decode tier
                                    during the load — sampled through
                                    the same engine tsdb hook the SLO
                                    monitor reads, so the decode_tick:2
                                    chaos doubles exactly what the gate
                                    measures
      - ttft_p99_vs_fleet /         the acceptance ratios: the disagg
        decode_tick_vs_fleet        tier at or below the mixed fleet on
                                    the same mix. decode_tick_vs_fleet
                                    compares median FULL-TICK wall on
                                    decode-serving engines (the row's
                                    inter-token latency — in the mixed
                                    fleet those ticks interleave chunk
                                    work; on the decode tier they never
                                    do: long prompts never occupy a
                                    decode slot)
      - dropped                     budget 0 — zero-drop across the kill
      - requeue_scratch_frac        requeues that re-decoded from
                                    scratch / requeues: the resume-from-
                                    KV rescue must carry the kill
                                    (PR-9's baseline behavior was 1.0)

    KFTPU_PROF_CHAOS="decode_tick:2" doubles every engine's per-tick
    dispatches in BOTH phases — the absolute decode_tick/ttft rows fail
    while the vs_fleet ratios stay put — and the decode-tick SLO monitor
    watching the disagg tier must stay alert-quiet on an untouched tree
    (tests/test_prof_gate.py pins both sides).
    """
    import gc
    import math

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.monitoring import SLOConfig, SLOMonitor, TimeSeriesStore
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    from kubeflow_tpu.serving.fleet import (
        FleetRouter,
        PagedKVPool,
        make_prompts,
        run_loadtest_sync,
    )

    repeats = chaos_repeats("decode_tick")
    long_len = shared_prefix + long_body
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, mlp_dim=128, dropout_rate=0.0,
                    max_len=long_len + new_tokens + 22)  # + anchor rows
    model = GPTLM(cfg)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    unit = _calibration_unit()
    # the long-prompt-heavy mix: 2/3 long, 1/3 short, all sharing the
    # system prefix — identical prompts and arrival offsets per phase
    longs = make_prompts(n_requests, seed=seed, vocab=cfg.vocab_size,
                         prompt_len=long_body, shared_prefix=shared_prefix)
    shorts = make_prompts(n_requests, seed=seed + 1, vocab=cfg.vocab_size,
                          prompt_len=short_body,
                          shared_prefix=shared_prefix)
    prompts = [shorts[i] if i % 3 == 2 else longs[i]
               for i in range(n_requests)]

    def run_phase(disagg: bool):
        pool = PagedKVPool(block_size=block, capacity_blocks=1024)

        def mk(**kw):
            return ContinuousBatcher(
                model, variables, max_rows=rows,
                default_max_new_tokens=new_tokens,
                paged_kv=pool, prefill_chunk=chunk, **kw)

        if disagg:
            sampled = [mk() for _ in range(2)]
            reps = ([(f"prefill-{i}", mk(max_chunks_per_tick=rows),
                      "prefill") for i in range(2)]
                    + [(f"decode-{i}", e, "decode")
                       for i, e in enumerate(sampled)])
            kill = "decode-0"
        else:
            sampled = [mk() for _ in range(4)]
            reps = sampled
            kill = 1
        router = FleetRouter(reps)
        engines = [r.engine for r in router.replicas]
        # warmup OUTSIDE every timed window: compile each engine's chunk
        # fns (full + remainder + the pool-match suffix-1 shape), decode
        # step, splice, first-token pick, and the paged chain-append
        # extraction window — the gate measures serving, not XLA
        for eng in engines:
            for w in (longs[0], shorts[0]):
                eng.submit(w, max_new_tokens=2)
                eng.run_until_idle()
                eng.submit(w, max_new_tokens=2)
                eng.run_until_idle()
        # in-run healthy decode anchor (the serve_fleet trick): median
        # UNWRAPPED decode-tick samples on a decode-serving engine,
        # through the same tsdb hook the monitored samples use — armed
        # BEFORE the chaos wrap so the SLO threshold is injection-immune
        eng0 = sampled[0]
        for p in make_prompts(rows, seed=seed + 3, vocab=cfg.vocab_size,
                              prompt_len=long_body,
                              shared_prefix=shared_prefix):
            eng0.submit(p, max_new_tokens=new_tokens + 14)
        for _ in range(rows * (long_len // chunk + 2)):
            eng0.tick()
            if not eng0._pending and all(eng0._rows):
                break
        anchor_tsdb = TimeSeriesStore()
        eng0.tsdb = anchor_tsdb
        for _ in range(12):
            eng0.tick()
        eng0.tsdb = None
        healthy_tick = _median(
            [v for _, v in anchor_tsdb.window("serving.decode_tick_s",
                                              3600.0)])
        eng0.run_until_idle()
        _arm_decode_chaos(engines, repeats)
        tsdb = TimeSeriesStore(capacity_per_series=4096)
        for eng in sampled:
            eng.tsdb = tsdb
        # per-tick wall samples on the decode-SERVING engines: a tick
        # counts when the engine entered it with >=1 active decode row —
        # in the mixed fleet those ticks interleave chunk work (the cost
        # the disagg split removes from the decode path), on the disagg
        # decode tier they never do
        samples: list[float] = []

        def timed(eng):
            orig = eng.tick

            def run():
                busy_decode = any(
                    r is not None and s not in eng._pending
                    for s, r in enumerate(eng._rows))
                t0 = time.perf_counter()
                busy = orig()
                dt = time.perf_counter() - t0
                if busy_decode:
                    samples.append(dt)
                return busy

            return run

        for eng in sampled:
            eng.tick = timed(eng)

        def sample_counters(_tick, rtr):
            tsdb.record("fleet.requests_failed_total",
                        rtr.metrics["requests_failed_total"])

        # load-phase delta base: warmup + anchor traffic must not count
        # toward the "decode tier computed zero prompt tokens" proof
        decode_prefill0 = sum(
            r.engine.prefill_tokens_total for r in router.replicas
            if r.role == "decode")
        gc.collect()
        t0_wall = time.time()
        report = run_loadtest_sync(
            router, prompts, seed=seed, mean_gap_ticks=1.0,
            new_tokens=new_tokens, kill_at_tick=10, kill_replica=kill,
            on_tick=sample_counters)
        decode_prefill = sum(
            r.engine.prefill_tokens_total for r in router.replicas
            if r.role == "decode") - decode_prefill0
        return {
            "router": router,
            "summary": report.summary(),
            "tick_median": _median(samples),
            "dispatch_median": _median(
                [v for _, v in tsdb.window("serving.decode_tick_s",
                                           3600.0)]),
            "tsdb": tsdb,
            "healthy_tick": healthy_tick,
            "t0_wall": t0_wall,
            "decode_prefill": decode_prefill,
        }

    fleet = run_phase(disagg=False)
    gc.collect()
    dis = run_phase(disagg=True)

    # ---- SLO evaluation over the DISAGG tier's TSDB (the PR-12 monitor
    # must stay alert-quiet through the drill; the decode_tick:2 chaos
    # drives it past the in-run threshold on every window)
    now = time.time()
    span_s = float(math.ceil(now - dis["t0_wall"]) + 1)
    slo_threshold = DECODE_SLO_HEADROOM * dis["healthy_tick"]
    monitor = SLOMonitor(dis["tsdb"], (
        SLOConfig("serving_decode_tick", metric="serving.decode_tick_s",
                  kind="above", threshold=slo_threshold, budget=0.25,
                  windows=((span_s, 1.0),
                           (max(float(math.ceil(span_s / 4)), 1.0), 1.0))),
        SLOConfig("serving_zero_drop",
                  metric="fleet.requests_failed_total",
                  kind="increase", budget=0.0, windows=((span_s, 1.0),)),
    ))
    alerts = monitor.evaluate(now=now)
    states = {s["name"]: s for s in monitor.describe()}

    ds, fs = dis["summary"], fleet["summary"]
    d_router = dis["router"]
    requeued = max(ds["requeued"], 1)
    return {
        "workload": "serve_disagg",
        "replicas": 4,
        "requests": n_requests,
        "completed": ds["completed"],
        "dropped_count": ds["dropped"],
        "fleet_dropped_count": fs["dropped"],
        "requeued": ds["requeued"],
        "resumed": ds["resumed"],
        "resumed_tokens": ds["resumed_tokens"],
        "handoffs": d_router.metrics["prefill_handoffs_total"],
        "decode_tier_prefill_tokens": dis["decode_prefill"],
        "replica_killed": True,
        "anchor": "matmul_unit",
        "anchor_s": round(unit, 6),
        "phases_s": {
            "ttft_p99": ds["ttft_p99_s"],
            "decode_tick": round(dis["dispatch_median"], 6),
            "decode_tick_wall": round(dis["tick_median"], 6),
            "fleet_ttft_p99": fs["ttft_p99_s"],
            "fleet_decode_tick_wall": round(fleet["tick_median"], 6),
        },
        "rel": {
            "ttft_p99": round(ds["ttft_p99_s"] / unit, 4) if unit else 0.0,
            "decode_tick": round(dis["dispatch_median"] / unit, 4)
            if unit else 0.0,
            # the acceptance ratios: disagg at or below the mixed fleet
            # on the SAME mix — in-run, machine-invariant
            "ttft_p99_vs_fleet": round(
                ds["ttft_p99_s"] / max(fs["ttft_p99_s"], 1e-12), 4),
            "decode_tick_vs_fleet": round(
                dis["tick_median"] / max(fleet["tick_median"], 1e-12), 4),
            # COUNT rows — exact, tight-gated
            "dropped": ds["dropped"] + fs["dropped"],
            "requeue_scratch_frac": round(
                (ds["requeued"] - ds["resumed"]) / requeued, 4),
        },
        "slo": {
            "decode_tick": {
                "fired": states["serving_decode_tick"]["fired"],
                "burn_rates": states["serving_decode_tick"]["burn_rates"],
                "threshold_s": round(slo_threshold, 6),
                "healthy_tick_s": round(dis["healthy_tick"], 6),
                "samples": states["serving_decode_tick"]["samples"],
            },
            "zero_drop": {
                "fired": states["serving_zero_drop"]["fired"],
                "burn_rates": states["serving_zero_drop"]["burn_rates"],
            },
            "alerts": [a.slo for a in alerts],
        },
        "tokens_per_s_total": ds["tokens_per_s_total"],
    }


# -------------------------------------------------------------- serve_pods


def serve_pods(n_requests: int = 10, body: int = 6, shared_prefix: int = 4,
               new_tokens: int = 5, block: int = 4, kill_tick: int = 6,
               seed: int = 11, transport: str = "unix") -> dict:
    """Cross-process pod-backed replicas under a REAL kill
    (docs/serving.md "Pod-backed replicas"): one prefill + two decode
    pods, each a genuine subprocess behind the AF_UNIX wire protocol,
    serve the seeded mix — paged-KV chains crossing the process boundary
    on every handoff and decode leg — while one decode pod takes an
    os.kill SIGKILL mid-run. The router's token record + the client-side
    recovery chain must carry the kill with zero drops and at least one
    chain-resume rescue. Gated:

      - ttft_p99 / decode_tick      calibration-matmul units. decode_tick
                                    is the median CLIENT-side tick
                                    round-trip on a decode pod holding
                                    rows — one wire envelope + the
                                    worker's engine tick — so the
                                    decode_tick:N chaos (shipped to the
                                    workers in their SPEC, never read
                                    from the env) inflates exactly what
                                    the gate measures
      - dropped                     budget 0, slack-only — one lost
                                    request across the SIGKILL fails
      - kill_unrescued              0 when the kill was rescued by >= 1
                                    chain-resume requeue, 1 otherwise —
                                    an exact count row, so a drill whose
                                    kill lands on an idle pod (nothing
                                    proven) fails the gate rather than
                                    passing silently
      - requeue_scratch_frac        requeues that re-decoded from
                                    scratch / requeues — the home-pool
                                    recovery chain must make the requeue
                                    a resume, not a re-prefill
      - wire_retries                retried wire ops during the load
                                    (budget 0): KFTPU_PROF_CHAOS="wire:1"
                                    arms the seeded WireFault plan
                                    (resets, deadline delays, torn
                                    frames) on the decode clients and
                                    MUST fail this row — the teeth —
                                    while an untouched tree retries
                                    nothing

    transport="tcp" is the multi-host axis (`serve_pods_tcp` in the
    budget file): the same drill dialed over 127.0.0.1 TCP, with two
    extra COUNT rows — net_reconnects (supervisor redials after an
    established connection, budget 0) and dup_acks_refused (redelivered
    events the cumulative-ack filter dropped, budget 0). The
    KFTPU_PROF_CHAOS="net:1" teeth arm the seeded NetFault plan
    (black-holes, half-open replies, duplicate deliveries, a partition
    window) on the decode clients and MUST fail those rows while an
    untouched tree redials and refuses nothing.
    """
    import gc
    import shutil
    import signal
    import tempfile

    from kubeflow_tpu.serving.fleet import (
        FleetRouter,
        PagedKVPool,
        make_prompts,
        run_loadtest_sync,
        spawn_pod,
        wire_pod_deaths,
    )
    from kubeflow_tpu.serving.fleet.podclient import pod_metrics_snapshot

    repeats = chaos_repeats("decode_tick")
    wire_teeth = chaos_flag("wire")
    net_teeth = chaos_flag("net")
    unit = _calibration_unit()
    vocab = 256
    prompts = make_prompts(n_requests, seed=seed, vocab=vocab,
                           prompt_len=body, shared_prefix=shared_prefix)
    # worker-side warmup: SAME shapes as the load (compile keys), but
    # DIFFERENT content — warmup chains in a worker pool must not become
    # covering siblings of the handoff re-inserts
    warm = make_prompts(2, seed=seed + 7, vocab=vocab, prompt_len=body,
                        shared_prefix=shared_prefix)
    spec = {
        "model": {"vocab_size": vocab, "hidden_size": 64, "num_layers": 2,
                  "num_heads": 2, "mlp_dim": 128, "dropout_rate": 0.0,
                  "max_len": shared_prefix + body + new_tokens + 16},
        "seed": 0, "init_seed": seed, "max_rows": 2,
        "default_max_new_tokens": new_tokens, "eos_token_id": None,
        "prefill_chunk": 0,
        "pool": {"block_size": block, "capacity_blocks": 512},
        "warmup_prompts": [[int(t) for t in p] for p in warm],
        "warmup_new_tokens": new_tokens, "warmup_repeats": 1,
        "warmup_resume": True,
        "chaos_decode_repeats": repeats,
        "max_queue": 64,
    }
    # persistent XLA cache at a STABLE temp path: the three workers (and
    # every later run in the same gate session) share compiles, so cold
    # start is paid once per machine, not once per spawn. Warmup runs
    # before the load either way — the cache moves only un-gated startup
    # wall time, never the measured phases.
    spec["compile_cache_dir"] = os.path.join(
        tempfile.gettempdir(), "kftpu-prof-pods-xla-cache")
    state_dir = tempfile.mkdtemp(prefix="kftpu-serve-pods-")
    home = PagedKVPool(block_size=block, capacity_blocks=1024)
    roles = (("prefill-0", "prefill"), ("decode-0", "decode"),
             ("decode-1", "decode"))
    clients = []
    try:
        # spawn all three CONCURRENTLY (connect=False), then complete the
        # handshakes — total cold start is one worker's warmup, not three
        for name, _role in roles:
            clients.append(spawn_pod(name, spec, state_dir,
                                     home_pool=home, connect=False,
                                     transport=transport))
        for c in clients:
            c.connect()
        chaos_eng = None
        if wire_teeth or net_teeth:
            from kubeflow_tpu.chaos import ChaosEngine, FaultPlan

            # armed AFTER connect so startup handshakes never spend the
            # fault budget; decode clients only — the tick/submit path
            # the drill measures. wire:1 draws the WireFault plan (the
            # "wire" profile also carries the net draws); net:1 alone
            # draws only the NetFault plan
            profile = "wire" if wire_teeth else "net"
            chaos_eng = ChaosEngine(FaultPlan.from_seed(seed,
                                                        profile=profile))
            for c in clients[1:]:
                c.chaos = chaos_eng
        router = FleetRouter([(c.name, c, role)
                              for c, (_n, role) in zip(clients, roles)])
        wire_pod_deaths(router)
        victim = clients[1]

        # client-side decode-tick samples: the wire round-trip of a tick
        # driven while the client holds seated rows — the pod tier's
        # inter-token latency as the ROUTER experiences it
        samples: list[float] = []

        def timed(c):
            orig = c.tick

            def run():
                busy_rows = bool(c._rows)
                t0 = time.perf_counter()
                busy = orig()
                dt = time.perf_counter() - t0
                if busy_rows and not c.dead:
                    samples.append(dt)
                return busy

            return run

        for c in clients[1:]:
            c.tick = timed(c)

        killed = {"done": False}

        def on_tick(tick, _rtr):
            if not killed["done"] and tick >= kill_tick:
                killed["done"] = True
                # the real thing: SIGKILL the worker PROCESS mid-decode;
                # the client discovers it through the wire, the router
                # through on_death
                try:
                    os.kill(victim.worker_pid, signal.SIGKILL)
                except ProcessLookupError:
                    # a chaos-driven wire death (the net:1 partition
                    # exhausting the retry budget) already reaped it
                    pass

        pod_base = pod_metrics_snapshot()
        gc.collect()
        report = run_loadtest_sync(
            router, prompts, seed=seed, mean_gap_ticks=1.0,
            new_tokens=new_tokens, kill_replica=None, on_tick=on_tick)
        pod_now = pod_metrics_snapshot()
        rs = report.summary()
        wire_retries = (pod_now["wire_retries_total"]
                        - pod_base["wire_retries_total"])
        net_reconnects = (pod_now["net_reconnects_total"]
                          - pod_base["net_reconnects_total"])
        dup_acks = (pod_now["net_duplicate_acks_refused_total"]
                    - pod_base["net_duplicate_acks_refused_total"])
        requeued = max(rs["requeued"], 1)
        rescued = rs["requeued"] >= 1 and rs["resumed"] >= 1
        rec = {
            "workload": ("serve_pods_tcp" if transport == "tcp"
                         else "serve_pods"),
            "transport": transport,
            "pods": len(clients),
            "requests": n_requests,
            "completed": rs["completed"],
            "dropped_count": rs["dropped"],
            "requeued": rs["requeued"],
            "resumed": rs["resumed"],
            "resumed_tokens": rs["resumed_tokens"],
            "handoffs": router.metrics["prefill_handoffs_total"],
            "pod_kills": (pod_now["kills_total"]
                          - pod_base["kills_total"]),
            "handoff_bytes": (pod_now["handoff_bytes_total"]
                              - pod_base["handoff_bytes_total"]),
            "wire_chaos_armed": wire_teeth,
            "net_chaos_armed": net_teeth,
            "net_reconnects": net_reconnects,
            "dup_acks_refused": dup_acks,
            "replica_killed": killed["done"],
            "anchor": "matmul_unit",
            "anchor_s": round(unit, 6),
            "phases_s": {
                "ttft_p99": rs["ttft_p99_s"],
                "decode_tick": round(_median(samples), 6),
            },
            "rel": {
                "ttft_p99": round(rs["ttft_p99_s"] / unit, 4)
                if unit else 0.0,
                "decode_tick": round(_median(samples) / unit, 4)
                if unit else 0.0,
                # COUNT rows — exact, tight-gated
                "dropped": rs["dropped"],
                "kill_unrescued": 0 if rescued else 1,
                "requeue_scratch_frac": round(
                    (rs["requeued"] - rs["resumed"]) / requeued, 4),
                "wire_retries": wire_retries,
            },
            "tokens_per_s_total": rs["tokens_per_s_total"],
        }
        if transport == "tcp":
            # the multi-host rows (COUNTs, budget 0): a redial after an
            # established connection or a refused redelivery on an
            # untouched tree is a regression; the net:1 teeth inflate
            # both on command
            rec["rel"]["net_reconnects"] = net_reconnects
            rec["rel"]["dup_acks_refused"] = dup_acks
        return rec
    finally:
        for c in clients:
            try:
                c.kill(timeout_s=2.0)
            except (RuntimeError, OSError):  # teardown best-effort
                pass
        shutil.rmtree(state_dir, ignore_errors=True)


# --------------------------------------------------------------- prod_day


def prod_day() -> dict:
    """The production-day soak as the tier-1 gate workload (ROADMAP
    item 6; kubeflow_tpu/soak is the engine, docs/autoscaling.md the
    guide): diurnal waves against a FleetScaler-autoscaled fleet
    (scale-to-zero + wake-on-arrival through the cold-start path),
    training churn on a real control plane, seeded replica kills, one
    pod hang, one torn checkpoint — ONE report (build_slo_report +
    SLOMonitor.evaluate over the calibrated default_slos set). Gated:

      - ttft_p99                p99 time-to-first-token in SCHEDULER
                                TICKS (admission→first token) — the
                                machine-invariant, fleet-size-fair
                                latency unit of the tick-driven drill
      - dropped                 budget 0 EXACT across the whole day:
                                scale events, drains, kills, the hang —
                                nothing may lose a request
      - goodput_gap             1 − mean running/desired pod ratio of
                                the churn leg (a COUNT ratio)
      - restart_overhead_frac   non-running pod-ticks over total — the
                                restart-overhead budget
      - slo_burn                worst serving-SLO long-window burn from
                                THE report — ~0.1 healthy, driven past
                                its cap by KFTPU_PROF_CHAOS=
                                "scaler_freeze:1" (the scaler stops
                                reacting while the waves continue; the
                                burn-rate alert must fire AND fail the
                                gate — tests/test_prof_gate.py pins it)
    """
    from kubeflow_tpu.soak import SoakConfig, run_prod_day

    unit = _calibration_unit()
    rec = run_prod_day(SoakConfig(), frozen=chaos_flag("scaler_freeze"))
    burn = rec["slo"]["worst_serving_burn"]
    return {
        "workload": "prod_day",
        "frozen_scaler": rec["frozen"],
        "requests": rec["n_requests"],
        "completed": rec["completed"],
        "dropped_count": rec["dropped"],
        "shed_retries": rec["shed_retries"],
        "requeued": rec["requeued"],
        "resumed": rec["resumed"],
        "kills_injected": rec["kills_injected"],
        "hang_injected": rec["hang_injected"],
        "ticks": rec["ticks"],
        "replicas_peak": rec["replicas_peak"],
        "scaler": rec["scaler"],
        "scale_to_zero_reached": rec["scale_to_zero_reached"],
        "recovered_from_zero": rec["recovered_from_zero"],
        "ckpt_fallback_ok": rec["ckpt"].get("fallback_ok", False),
        "churn": rec["churn"],
        "slo": rec["slo"],
        "report_requests": rec["report"]["requests"],
        "ttft_threshold_ticks": rec["ttft_threshold_ticks"],
        "ttft_bad_frac": rec["ttft_bad_frac"],
        "anchor": "scheduler_tick",
        "anchor_s": round(unit, 6),
        "phases_s": {"ttft_p99_wall": rec["ttft_p99_s"],
                     "decode_tick": rec["decode_tick_s"]},
        "rel": {
            "ttft_p99": rec["ttft_p99_ticks"],
            "dropped": rec["dropped"],
            "goodput_gap": round(1.0 - rec["churn"]["goodput_mean"], 4),
            "restart_overhead_frac":
                rec["churn"]["restart_overhead_frac"],
            "slo_burn": round(min(burn, 10.0), 4),
        },
    }


# --------------------------------------------------------- diurnal_storm


def diurnal_storm() -> dict:
    """The chip-constrained day as the tier-1 scheduler gate (ROADMAP
    item 3; kubeflow_tpu/scheduler is the subsystem, docs/scheduler.md
    the guide): the prod_day diurnal waves re-run on a cluster where
    peak serving demand CANNOT fit without preempting batch training —
    two real JAXJob gangs bound through the shared ChipScheduler
    ledger, the FleetScaler's peak scale-up evicting the youngest/
    borrowing gang via the gang-restart path, the trough handing the
    chips back and the gang resuming. Gated:

      - ttft_p99             p99 TTFT in SCHEDULER TICKS — preemption
                             must keep serving latency flat (healthy
                             ~3 ticks; sched_freeze pins the fleet at
                             one replica and drives it ~15x)
      - dropped              budget 0 EXACT — preemption and quota
                             denial may delay, never lose, a request
      - serving_alerts       COUNT of fired serving_* SLO alerts,
                             budget 0 EXACT: zero serving SLO
                             violations through the whole storm
      - slo_burn             worst serving-SLO long-window burn —
                             driven past its cap by KFTPU_PROF_CHAOS=
                             "sched_freeze:1" (the ledger stops
                             granting while the waves continue; the
                             burn-rate alert must fire AND fail the
                             gate — tests/test_prof_gate.py pins it)
      - preempt_to_resume    mean eviction→re-bound latency of the
                             preempted gang in TICKS (the tick loop
                             nudges admission, so this counts how long
                             serving actually held the chips)
      - goodput_gap          1 − mean bound-chips/total-gang-chips
                             ratio of the batch leg — the batch
                             goodput floor (preemption costs bounded
                             goodput, starvation fails the gate)
      - drain_overrun_frac   extra ticks past the scheduled day over
                             day_ticks — a frozen scheduler serves the
                             backlog late through one replica and
                             overruns the day wide
    """
    from kubeflow_tpu.soak import StormConfig, run_diurnal_storm

    unit = _calibration_unit()
    rec = run_diurnal_storm(StormConfig(),
                            frozen=chaos_flag("sched_freeze"))
    burn = rec["slo"]["worst_serving_burn"]
    return {
        "workload": "diurnal_storm",
        "frozen_scheduler": rec["frozen"],
        "requests": rec["n_requests"],
        "completed": rec["completed"],
        "dropped_count": rec["dropped"],
        "shed_retries": rec["shed_retries"],
        "requeued": rec["requeued"],
        "ticks": rec["ticks"],
        "day_ticks": rec["day_ticks"],
        "replicas_peak": rec["replicas_peak"],
        "capacity_chips": rec["capacity_chips"],
        "chips_per_slice": rec["chips_per_slice"],
        "scaler": rec["scaler"],
        "chip_denies": rec["chip_denies"],
        "sched": rec["sched"],
        "batch": rec["batch"],
        "slo": rec["slo"],
        "report_requests": rec["report"]["requests"],
        "ttft_threshold_ticks": rec["ttft_threshold_ticks"],
        "ttft_bad_frac": rec["ttft_bad_frac"],
        "preempt_to_resume_ticks_max":
            rec["preempt_to_resume_ticks_max"],
        "anchor": "scheduler_tick",
        "anchor_s": round(unit, 6),
        "phases_s": {"preempt_to_resume_wall":
                     (max(rec["preempt_to_resume_s"], default=0.0)),
                     "healthy_tick": rec["healthy_tick_s"]},
        "rel": {
            "ttft_p99": rec["ttft_p99_ticks"],
            "dropped": rec["dropped"],
            "serving_alerts": float(len(rec["slo"]["serving_alerts"])),
            "slo_burn": round(min(burn, 10.0), 4),
            "preempt_to_resume": rec["preempt_to_resume_ticks_mean"],
            "goodput_gap": round(
                1.0 - rec["batch"]["goodput_mean"], 4),
            "drain_overrun_frac": round(
                max(0, rec["ticks"] - rec["day_ticks"])
                / rec["day_ticks"], 4),
        },
    }


# -------------------------------------------------------- reconcile_storm


def reconcile_storm(n_pods: int = 200, gets_per_pass: int = 8,
                    timeout_s: float = 60.0) -> dict:
    """N-pod reconcile storm on a bare FakeCluster: one ADDED event per
    pod drives one reconcile pass through the real informer -> workqueue
    -> native-driver path, each pass doing a fixed amount of store-read
    work. Reconcile-duration percentiles come from the REAL reconcile
    spans (ControllerBase emits them) and are normalized by a calibration
    loop over the same get machinery."""
    from kubeflow_tpu.controller.base import ControllerBase
    from kubeflow_tpu.controller.fakecluster import FakeCluster, Pod
    from kubeflow_tpu.api.common import ObjectMeta
    from kubeflow_tpu.profiling.analytics import control_plane_stats
    from kubeflow_tpu.tracing import Tracer
    from kubeflow_tpu.utils.retry import poll_until

    repeats = chaos_repeats("reconcile")

    class StormController(ControllerBase):
        ERROR_EVENT_KIND = "pods"

        def kind_filter(self, etype, kind, obj):
            if kind == "pods" and obj.metadata.name.startswith("storm-"):
                return obj.key
            return None

        def resync_keys(self):
            return ()

        def reconcile(self, key):
            # read-only convergent pass: fixed get work, no write-back —
            # the storm stays exactly one pass per ADDED event
            for _ in range(repeats):
                for _ in range(gets_per_pass):
                    self.cluster.get("pods", key, copy_obj=True)
            return None

    cluster = FakeCluster()
    tracer = Tracer(capacity=8 * n_pods)
    cluster.tracer = tracer

    # calibration: the same store-lock + deepcopy path a pass runs through.
    # Collect first — garbage left by earlier workloads otherwise triggers
    # gen-0 GC passes inside the deepcopy loop and skews the unit ~40%
    import gc

    ref = Pod(metadata=ObjectMeta(name="storm-calibration"))
    cluster.create("pods", ref)

    # min over medians-of-40 blocks: transient interference (a lingering
    # thread from a previous workload, a GC pass) inflates SOME blocks;
    # a real store regression inflates all of them, so min still scales
    def store_unit_blocks(n: int) -> float:
        medians = []
        for _ in range(n):
            gc.collect()
            samples = []
            for _ in range(40):
                t0 = time.perf_counter()
                cluster.get("pods", ref.key, copy_obj=True)
                samples.append(time.perf_counter() - t0)
            medians.append(_median(samples))
        return min(medians)

    unit_before = store_unit_blocks(3)

    # one worker: the gate watches per-PASS cost, and a second worker only
    # adds store-lock contention noise to the median it is gated on
    # bulk wave lands BEFORE the controller starts: the informer's initial
    # list+watch replay delivers all N at once, so the gated median
    # measures pass cost, not creator-vs-informer lock contention (which
    # is bimodal run-to-run and would blunt the gate)
    for i in range(n_pods):
        cluster.create("pods", Pod(metadata=ObjectMeta(
            name=f"storm-{i:04d}")))
    live_wave = max(n_pods // 10, 1)
    ctrl = StormController(cluster, "storm", workers=1)
    gc.collect()  # same GC posture for the measured passes as the unit
    ctrl.start()
    try:
        poll_until(
            lambda: ctrl.metrics["reconcile_total"] >= n_pods + 1 or None,
            timeout_s=timeout_s, describe="reconcile storm drained",
        )
        # small LIVE wave, each create under a span: the published events
        # carry its context, so reconcile passes parent-link to it and
        # the watch-delivery percentiles are measured, not vacuous
        for i in range(live_wave):
            with tracer.span("storm.submit", i=i):
                cluster.create("pods", Pod(metadata=ObjectMeta(
                    name=f"storm-live-{i:04d}")))
        poll_until(
            lambda: (ctrl.metrics["reconcile_total"]
                     >= n_pods + live_wave + 1) or None,
            timeout_s=timeout_s, describe="live wave drained",
        )
    finally:
        ctrl.stop()
        cluster.tracer = None
    # re-sample after the drain: the unit wants the machine's UNLOADED
    # store speed, and either window may have caught interference
    unit = min(unit_before, store_unit_blocks(2)) * gets_per_pass
    stats = control_plane_stats(tracer.snapshot())["reconcile"]["storm"]
    return {
        "workload": "reconcile_storm",
        "passes": stats["count"],
        "pods": n_pods,
        "anchor": "store_get_unit",
        "anchor_s": round(unit, 6),
        "phases_s": {"reconcile_p50": stats["p50_s"],
                     "reconcile_p99": stats["p99_s"]},
        # only the MEDIAN is gated: a 200-sample p99 is ~the 2nd-worst
        # sample (GC/scheduler noise), reported for operators but too
        # jittery to gate `make test` on
        "rel": {
            "reconcile_p50": round(stats["p50_s"] / unit, 4) if unit else 0.0,
        },
        "reconcile_p99_units": (round(stats["p99_s"] / unit, 4)
                                if unit else 0.0),
        "watch_delay_p99_s": stats["watch_delay_p99_s"],
    }


# ----------------------------------------------------------- cplane_storm


#: ownership label of the cplane-storm controller's pods
STORM_LABEL = "kubeflow-tpu.org/cplane-storm"

#: frozen PRE-REFACTOR measurement of cplane_storm's exact scenario (10k
#: pods, 8 bystander informers, 100-pod gang restart) on the single-lock
#: store with unfiltered watch fan-out and per-pod conflict-retried status
#: writes — captured at the PR-8 base commit, recorded here so every
#: budget regen carries the before/after pair. per-pod units
#: (time-to-Running / store-get unit) is the machine-invariant number;
#: jobs/sec is the same run's absolute throughput on the capture machine.
BASELINE_SINGLE_LOCK = {
    "jobs_per_s_to_running": 697.7,
    "to_running_units_per_pod": 48.17,
    "passes_per_gang_restart": 269,
}

#: the platform's OTHER pods-watching controllers, as (name, ownership
#: label) — the fan-out the sharded watch path exists to neutralize. Each
#: bystander informer subscribes pods-with-its-label (server-side): a
#: storm of someone else's pods never reaches it. Pre-refactor, every one
#: of these received and discarded every event client-side, and at 10k
#: pods that discard work was the control-plane ceiling.
BYSTANDER_CONTROLLERS = (
    ("job", "kubeflow-tpu.org/job-name"),
    ("tensorboard", "kubeflow-tpu.org/tensorboard"),
    ("inferenceservice", "kubeflow-tpu.org/inferenceservice"),
    ("experiment", "kubeflow-tpu.org/experiment-name"),
    ("notebook", "kubeflow-tpu.org/notebook"),
    ("pvcviewer", "kubeflow-tpu.org/pvcviewer"),
    ("autoscaler", "kubeflow-tpu.org/autoscaled"),
    ("pipelinerun", "kubeflow-tpu.org/pipelinerun"),
)


def cplane_storm(n_pods: int = 10000, gang_size: int = 100,
                 workers: int = 4, timeout_s: float = 300.0) -> dict:
    """10k-pod control-plane tier (ROADMAP item 3): N pods driven to
    Running through the FULL scaled path — label-filtered watch fan-out,
    keyed worker pool, coalesced status writes — in the platform's real
    subscriber shape (one owning controller + 8 bystander informers),
    reporting jobs/sec-to-Running and reconcile passes per gang restart.

    Untraced on purpose (production posture; the 200-pod storm keeps the
    traced percentiles): this workload gates THROUGHPUT. The gated ratio
    is per-pod time-to-Running in store-get units, so the budget is
    machine-speed invariant; the absolute jobs/sec lands in the budget
    regen next to the frozen pre-refactor single-lock baseline
    (docs/perf.md "Control-plane scale-out")."""
    import threading

    from kubeflow_tpu.api.common import ObjectMeta
    from kubeflow_tpu.controller.base import ControllerBase
    from kubeflow_tpu.controller.fakecluster import (
        FakeCluster, Pod, PodPhase, WatchPoller)
    from kubeflow_tpu.controller.statusbuffer import StatusWriteBuffer
    from kubeflow_tpu.utils.retry import poll_until

    repeats = chaos_repeats("reconcile")
    cluster = FakeCluster()
    buffer = StatusWriteBuffer(cluster, kind="pods")
    marked = [0]
    marked_mu = threading.Lock()

    class StormController(ControllerBase):
        ERROR_EVENT_KIND = "pods"
        # server-side push-down: only pods carrying the storm label ever
        # reach this informer's buffer
        WATCH_SELECTORS = {"pods": {STORM_LABEL: None}}

        def kind_filter(self, etype, kind, obj):
            if kind == "pods" and STORM_LABEL in obj.metadata.labels:
                return obj.key
            return None

        def resync_keys(self):
            return ()

        def reconcile(self, key):
            pod = None
            for _ in range(repeats):
                pod = self.cluster.get("pods", key)
            if pod is None or pod.status.phase != PodPhase.PENDING:
                return None
            uid = pod.metadata.uid

            def to_running(p):
                if p.status.phase != PodPhase.PENDING:
                    return False
                p.status.phase = PodPhase.RUNNING
                p.status.node = "local-node"
                p.status.start_time = time.time()

            if buffer.write(key, uid, to_running):
                with marked_mu:
                    marked[0] += 1
            return None

    # bystander informers: the other controllers' watch loops, doing what
    # an informer does with a delivered event (resolve + map + discard).
    # With server-side selectors they receive nothing for storm pods —
    # that absence is the measured win, so they must actually be running.
    stop_bystanders = threading.Event()

    def bystander(label: str):
        wp = WatchPoller(cluster, timeout=0.1, count_error=lambda: None,
                         selectors={"pods": {label: None}})
        while not stop_bystanders.is_set():
            ev = wp.get()
            if ev is not None:
                etype, kind, obj = ev
                obj.metadata.labels.get(label)  # the controller's map step

    bystander_threads = [
        threading.Thread(target=bystander, args=(label,),
                         name=f"bystander-{name}", daemon=True)
        for name, label in BYSTANDER_CONTROLLERS
    ]

    import gc

    # calibration twin of the 200-pod storm: the same store-lock + deepcopy
    # machinery, measured as min over medians-of-40 blocks
    ref = Pod(metadata=ObjectMeta(name="calibration"))
    cluster.create("pods", ref)

    def store_unit_blocks(n: int) -> float:
        medians = []
        for _ in range(n):
            gc.collect()
            samples = []
            for _ in range(40):
                t0 = time.perf_counter()
                cluster.get("pods", ref.key, copy_obj=True)
                samples.append(time.perf_counter() - t0)
            medians.append(_median(samples))
        return min(medians)

    unit_before = store_unit_blocks(3)

    def storm_pod(i: int) -> Pod:
        return Pod(metadata=ObjectMeta(name=f"storm-{i:05d}",
                                       labels={STORM_LABEL: "1"}))

    # bulk wave BEFORE the controller starts (informer replay delivers all
    # N at once), same rationale as reconcile_storm
    for i in range(n_pods):
        cluster.create("pods", storm_pod(i))
    for t in bystander_threads:
        t.start()
    ctrl = StormController(cluster, "cplane", workers=workers)
    gc.collect()
    t0 = time.perf_counter()
    ctrl.start()
    try:
        poll_until(lambda: marked[0] >= n_pods or None,
                   timeout_s=timeout_s, describe="pods to Running")
        dt = time.perf_counter() - t0

        # gang restart: kill + recreate one gang's worth of pods (new
        # incarnations), count reconcile passes to reconverge — the
        # passes-per-restart convergence-efficiency signal. Let the
        # initial wave's MODIFIED backlog drain first or its passes
        # pollute the restart count.
        drain_deadline = time.monotonic() + timeout_s
        prev = -1
        while time.monotonic() < drain_deadline:
            cur = ctrl.metrics["reconcile_total"]
            if cur == prev and len(ctrl.wq) == 0:
                break
            prev = cur
            time.sleep(0.05)
        passes0 = ctrl.metrics["reconcile_total"]
        for i in range(gang_size):
            cluster.delete("pods", f"default/storm-{i:05d}")
        for i in range(gang_size):
            cluster.create("pods", storm_pod(i))
        poll_until(lambda: marked[0] >= n_pods + gang_size or None,
                   timeout_s=timeout_s, describe="gang restart reconverged")
        restart_passes = ctrl.metrics["reconcile_total"] - passes0
    finally:
        stop_bystanders.set()
        ctrl.stop()
        buffer.close()
    unit = min(unit_before, store_unit_blocks(2))
    per_pod = dt / n_pods
    return {
        "workload": "cplane_storm",
        "pods": n_pods,
        "workers": workers,
        "bystanders": len(BYSTANDER_CONTROLLERS),
        "seconds_to_running": round(dt, 3),
        "jobs_per_s_to_running": round(n_pods / dt, 1),
        "passes_per_gang_restart": restart_passes,
        "coalesced_writes": buffer.metrics["coalesced_writes_total"],
        "flushes": buffer.metrics["flushes_total"],
        "shard_lock_waits": sum(cluster.lock_wait_counts().values()),
        "anchor": "store_get_unit",
        "anchor_s": round(unit, 9),
        "phases_s": {"to_running_per_pod": round(per_pod, 9)},
        # gated: per-pod convergence cost in store-get units (machine-
        # invariant), and passes per restarted pod (a COUNT — catches
        # reconcile-amplification regressions no timing gate can)
        "rel": {
            "to_running": round(per_pod / unit, 4) if unit else 0.0,
            "passes_per_pod_restart": round(
                restart_passes / gang_size, 4),
        },
    }


# ----------------------------------------------------------------- harness

WORKLOADS = ("mlp_train", "grad_overlap", "train_restart_warm",
             "serve_ticks", "serve_fleet", "serve_disagg", "serve_pods",
             "serve_pods_tcp", "prod_day", "diurnal_storm",
             "reconcile_storm", "cplane_storm")


def run_all(only: str = "") -> list[dict]:
    """Run every workload (an exact workload name runs just that one;
    any other `only` filters by substring), best-of-2 on each
    workload's primary gated phase."""
    fns = {
        "mlp_train": mlp_train,  # per-phase min-of-2 internally
        "grad_overlap": lambda: _best_of(grad_overlap, "overlap_ratio"),
        "train_restart_warm": lambda: _best_of(train_restart_warm,
                                               "warm_cold_ratio"),
        "serve_ticks": serve_ticks,
        "serve_fleet": lambda: _min_phases(
            serve_fleet, ("ttft_p99", "decode_tick", "slo_decode_burn"),
            attach={"slo_decode_burn": ("slo",)}),
        "serve_disagg": lambda: _min_phases(
            serve_disagg, ("ttft_p99", "decode_tick",
                           "ttft_p99_vs_fleet", "decode_tick_vs_fleet"),
            attach={"decode_tick": ("slo",)}),
        "serve_pods": lambda: _min_phases(
            serve_pods, ("ttft_p99", "decode_tick")),
        "serve_pods_tcp": lambda: _min_phases(
            partial(serve_pods, transport="tcp"),
            ("ttft_p99", "decode_tick")),
        "prod_day": lambda: _min_phases(
            prod_day, ("ttft_p99", "slo_burn", "goodput_gap",
                       "restart_overhead_frac"),
            attach={"slo_burn": ("slo",),
                    "ttft_p99": ("ttft_bad_frac",)}),
        "diurnal_storm": lambda: _min_phases(
            diurnal_storm, ("ttft_p99", "slo_burn",
                            "preempt_to_resume", "goodput_gap"),
            attach={"slo_burn": ("slo",),
                    "preempt_to_resume": ("batch", "sched")}),
        "reconcile_storm": lambda: _best_of(reconcile_storm,
                                            "reconcile_p50"),
        "cplane_storm": lambda: _best_of(cplane_storm, "to_running"),
    }
    if only in fns:
        # exact workload name: run just it ("serve_pods" must not drag
        # "serve_pods_tcp" along now that transports are an axis)
        return [fns[only]()]
    return [fns[name]() for name in WORKLOADS
            if not only or only in name]


# ------------------------------------------------------------------- gate


def make_budgets(results: list[dict]) -> dict:
    """Budget-file shape from measured results (the
    KFTPU_UPDATE_PROF_BUDGETS=1 regen path)."""
    budgets: dict = {}
    for rec in results:
        if rec.get("skipped"):
            # record WHY there is no baseline: when a later environment
            # (e.g. a jax upgrade) can run the workload, the gate treats
            # this marker as "unbudgeted by circumstance, regen when you
            # can" instead of failing every untouched tree
            budgets[rec["workload"]] = {"skipped_on_regen": rec["skipped"]}
            continue
        budgets[rec["workload"]] = {
            "rel": dict(rec["rel"]),
            "max_ratio": DEFAULT_MAX_RATIO,
            # the engine tick mixes python scheduling with jit dispatch —
            # its anchor (a bare matmul) tracks it less tightly than the
            # in-run anchors, so it gets a looser multiplier. serve_fleet:
            # ttft_p99 must stay under the decode_tick:2 chaos multiplier
            # (~1.8x, the dispatch fraction of a tick) or the teeth
            # wouldn't bite; the count ratios are exact and get tight
            # multipliers (dropped gates on the +0.08 slack alone: any
            # drop is a violation).
            "ratios": ({"tick": 3.0}
                       if rec["workload"] == "serve_ticks" else
                       # slo_decode_burn: a healthy tree burns only tail
                       # noise (well under the 1.0 firing line), while
                       # the decode_tick:2 chaos pushes the majority of
                       # samples past the in-run threshold (burn >> 1) —
                       # the 2.0 ratio leaves room for healthy noise and
                       # still fails the chaos run by a wide margin
                       # decode_tick 1.4: engine dispatches are small
                       # (~1ms) and scheduler noise moves them 15-25%
                       # run to run on a busy box, while the
                       # decode_tick:2 chaos doubles them (~2x the
                       # regen baseline) — 1.4 + slack clears healthy
                       # noise and still fails the chaos run wide
                       {"ttft_p99": 1.4, "decode_tick": 1.4,
                        "reuse_computed_frac": 1.25, "dropped": 1.0,
                        "slo_decode_burn": 2.0}
                       if rec["workload"] == "serve_fleet" else
                       # serve_disagg: the vs_fleet rows are in-run
                       # ratios of two medians measured by identical
                       # machinery — tight multipliers hold them at or
                       # below the mixed-fleet shape; the count rows
                       # (dropped, scratch-requeue fraction) gate on
                       # slack alone, so one dropped request or one
                       # full re-decode past the regen baseline fails.
                       # decode_tick's absolute row gets 1.5: the disagg
                       # decode tier's dispatches are the smallest
                       # timed unit in the suite (~1.5 matmul units) and
                       # scheduler noise moves them ~30% run to run,
                       # while the decode_tick:2 chaos lands at ~2x the
                       # regen baseline — 1.5 + slack keeps the teeth
                       # biting with margin on both sides
                       {"ttft_p99": 1.4, "decode_tick": 1.5,
                        "ttft_p99_vs_fleet": 1.2,
                        "decode_tick_vs_fleet": 1.2,
                        "dropped": 1.0, "requeue_scratch_frac": 1.0}
                       if rec["workload"] == "serve_disagg" else
                       # serve_pods: the count rows (dropped,
                       # kill_unrescued, wire_retries, scratch-requeue
                       # fraction) gate on slack alone — one dropped
                       # request, an unproven kill, or a single retried
                       # wire op past the regen baseline fails (the
                       # KFTPU_PROF_CHAOS="wire:1" teeth land squarely
                       # on wire_retries, a COUNT — so the wide timing
                       # ratios below never dull the teeth). The timing
                       # rows cross FOUR schedulable entities (client +
                       # three worker processes), so the kernel's
                       # placement of workers vs the anchor matmul
                       # moves rel ~2x run-to-run where the in-process
                       # fleets move 15-25% — 2.5 + slack covers the
                       # observed cross-run envelope while a real
                       # regression (a serialization stall, a retry
                       # storm) lands 4-10x
                       # serve_pods_tcp adds the multi-host COUNT rows
                       # (net_reconnects, dup_acks_refused, both
                       # budget 0 — the net:1 teeth's landing zone);
                       # everything else mirrors serve_pods
                       {"ttft_p99": 2.5, "decode_tick": 2.5,
                        "dropped": 1.0, "kill_unrescued": 1.0,
                        "requeue_scratch_frac": 1.0,
                        "wire_retries": 1.0, "net_reconnects": 1.0,
                        "dup_acks_refused": 1.0}
                       if rec["workload"] in ("serve_pods",
                                              "serve_pods_tcp") else
                       # prod_day: ttft_p99 is a TICK COUNT from the
                       # seeded schedule (healthy ~5, frozen-scaler
                       # ~35) — 2.0 + the tick slack below clears
                       # scheduling variance while the freeze stays
                       # 3x past the allowance; dropped gates on slack
                       # alone (one lost request fails); the churn
                       # ratios are count-based; slo_burn mirrors
                       # serve_fleet's slo_decode_burn teeth (healthy
                       # ~0.1, freeze driven to the 10.0 cap)
                       {"ttft_p99": 2.0, "dropped": 1.0,
                        "goodput_gap": 2.0,
                        "restart_overhead_frac": 2.0,
                        "slo_burn": 2.0}
                       if rec["workload"] == "prod_day" else
                       # diurnal_storm: ttft_p99 and preempt_to_resume
                       # are TICK COUNTS from the seeded schedule
                       # (healthy ttft ~3, sched_freeze ~45+ with the
                       # fleet pinned at one replica; resume ~60, a
                       # whole peak-to-trough arc) — 2.0 + the tick
                       # slacks below clear scheduling wobble while
                       # the freeze stays far past the allowance;
                       # dropped and serving_alerts gate on slack
                       # alone (one lost request or ONE fired
                       # serving_* alert fails — the zero-violations
                       # acceptance); slo_burn mirrors prod_day's
                       # teeth (healthy ~0.25, freeze at the 10.0
                       # cap); goodput_gap is the batch floor (one
                       # preemption costs ~0.13 of the day — 1.5
                       # tolerates a second eviction's worth, a
                       # starved gang lands ~0.5+); drain_overrun
                       # healthy ~0 (the backlog clears in-day),
                       # frozen ~0.35 of a day late
                       {"ttft_p99": 2.0, "dropped": 1.0,
                        "serving_alerts": 1.0, "slo_burn": 2.0,
                        "preempt_to_resume": 2.0,
                        "goodput_gap": 1.5,
                        "drain_overrun_frac": 1.5}
                       if rec["workload"] == "diurnal_storm" else
                       # warm_backend_compiles is an exact COUNT with a
                       # zero budget: ONE backend compile in the warm
                       # incarnation fails the gate (slack only); the
                       # in-run warm/cold timing ratio keeps the default
                       {"warm_backend_compiles": 1.0}
                       if rec["workload"] == "train_restart_warm" else
                       # forced serialization (the chaos teeth) lands at
                       # ~1.0; the allowance must sit BELOW that or the
                       # teeth cannot bite, and above the regen budget's
                       # noise band — 1.2x + slack does both for a
                       # healthy (<0.75) overlap ratio
                       {"overlap_ratio": 1.2}
                       if rec["workload"] == "grad_overlap" else {}),
            # per-phase slack override: the default absolute slack would
            # swamp a near-zero budget (0.02*1.5 + 0.08 tolerates a 5x
            # regression of the async win) — tighten it so a partial
            # re-inlining of host input work fails, not just a blowup.
            # grad_overlap: the forced-serial chaos lands ~0.9, so the
            # allowance must stay clearly below that — the default slack
            # would close half the gap between a healthy ratio and the
            # serialized one
            "slacks": ({"data_load_async": 0.03}
                       if rec["workload"] == "mlp_train" else
                       {"overlap_ratio": 0.03}
                       if rec["workload"] == "grad_overlap" else
                       # burn tail-noise band: healthy runs land ~0.1-0.2
                       # (a few samples past the in-run threshold), the
                       # chaos runs at 3+ — the widened slack tolerates a
                       # noisy machine's tail without closing the gap
                       {"slo_decode_burn": 0.3}
                       if rec["workload"] == "serve_fleet" else
                       # prod_day slacks: ttft_p99 is a small tick
                       # count (~5) — absolute slack of a few ticks
                       # absorbs a one-tick queue wobble without
                       # closing the gap to the frozen ~35; slo_burn
                       # and the churn ratios get the serve_fleet-
                       # style noise bands
                       {"ttft_p99": 3.0, "slo_burn": 0.3,
                        "goodput_gap": 0.1,
                        "restart_overhead_frac": 0.05}
                       if rec["workload"] == "prod_day" else
                       # diurnal_storm slacks: tick-count rows get
                       # absolute tick bands (ttft ~3 healthy vs ~45
                       # frozen; resume ~60 moves with where in the
                       # wave the eviction lands — 40 ticks of slack
                       # still fails a scheduler that holds the gang
                       # past a second peak); drain_overrun healthy
                       # is ~0 so the slack IS the band (frozen
                       # ~0.35 stays well past it)
                       {"ttft_p99": 3.0, "slo_burn": 0.3,
                        "preempt_to_resume": 40.0,
                        "goodput_gap": 0.1,
                        "drain_overrun_frac": 0.15}
                       if rec["workload"] == "diurnal_storm" else {}),
        }
        if rec["workload"] == "cplane_storm":
            # the acceptance record: this tree's throughput next to the
            # frozen pre-refactor single-lock capture (ISSUE 8 asks for
            # both numbers in every regen) — informational, the gate runs
            # on the machine-invariant "rel" ratios above
            budgets["cplane_storm"]["jobs_per_s_at_regen"] = rec[
                "jobs_per_s_to_running"]
            budgets["cplane_storm"]["baseline_single_lock"] = dict(
                BASELINE_SINGLE_LOCK)
    return budgets


def check_budgets(results: list[dict], budgets: dict) -> list[str]:
    """Gate: each measured phase ratio must stay inside its budget times
    the allowed multiplier. Returns violation strings (empty = pass).
    Missing budgets are violations too — a new workload cannot silently
    run ungated."""
    violations: list[str] = []
    for rec in results:
        if rec.get("skipped"):
            continue  # environment can't run it — reported, not gated
        b = budgets.get(rec["workload"])
        if b is None:
            violations.append(
                f"{rec['workload']}: no checked-in budget "
                "(regen with KFTPU_UPDATE_PROF_BUDGETS=1)")
            continue
        if "skipped_on_regen" in b and "rel" not in b:
            # the checked-in budgets were generated on an env that could
            # not run this workload; now it CAN — there is no baseline to
            # gate against, and bricking `make test` on an env upgrade
            # would punish the wrong change. Ungated until regenerated.
            continue
        default_ratio = b.get("max_ratio", DEFAULT_MAX_RATIO)
        for phase, rel in sorted(rec["rel"].items()):
            budget_rel = b.get("rel", {}).get(phase)
            if budget_rel is None:
                violations.append(
                    f"{rec['workload']}.{phase}: no budget for phase")
                continue
            ratio = b.get("ratios", {}).get(phase, default_ratio)
            slack = b.get("slacks", {}).get(phase, GATE_SLACK)
            allowed = budget_rel * ratio + slack
            if rel > allowed:
                violations.append(
                    f"{rec['workload']}.{phase}: measured {rel:.3f} > "
                    f"allowed {allowed:.3f} "
                    f"(budget {budget_rel:.3f} x {ratio})")
    return violations
