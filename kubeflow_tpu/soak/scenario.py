"""The production-day scenario engine — seeded, tick-driven, composed.

Everything here exists elsewhere in isolation: the fleet load harness
(serving/fleet/loadtest.py), the chaos fault plans, the control
plane's churn, liveness, the SLO monitor. This module
composes them into ONE drill, because the seams between subsystems only
fail when the subsystems run together (the way PR 1's drills found the
`gang._bind` wedge — at platform scale this time):

  - **diurnal traffic**: a seeded arrival process whose rate follows a
    two-peak day with a mid-afternoon trough to ZERO — the trough forces
    scale-to-zero, the evening peak forces the wake-on-arrival cold
    start, and the ramps force real scale-up/scale-down decisions;
  - **the autoscaled fleet**: a FleetScaler (serving/fleet/scaler.py)
    drives replica count from `demand_replicas_burn` each tick — every
    scale event in the drill is the closed loop acting, not a script;
  - **training churn**: a real FakeCluster + controller + status-write
    buffer runs job churn beside the traffic (pods to Running through
    the real informer→workqueue path), with seeded pod kills whose
    re-convergence cost is the restart-overhead budget, and one torn
    checkpoint exercised through the verified-restore fallback;
  - **faults**: seeded replica kills (zero-drop requeue under an
    autoscaling fleet), one pod hang (a replica silently stops ticking;
    the scaler's liveness watch must declare it and politely kill it),
    and the torn checkpoint above;
  - **one report**: `build_slo_report` + `SLOMonitor.evaluate()` over
    `calibrated_default_slos()` — the default objective set with its
    latency thresholds re-anchored to in-run healthy measurements so
    the verdict does not move with the machine's speed.

Ticks are the schedule unit (arrivals, faults, scaler cadence); wall
time is real, so the TSDB and the SLO windows behave exactly as in
production. docs/autoscaling.md walks the whole loop.
"""

from __future__ import annotations

import dataclasses
import random
import shutil
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from kubeflow_tpu.monitoring import (
    SLOMonitor,
    TimeSeriesStore,
    default_slos,
)
from kubeflow_tpu.serving.fleet import (
    FleetOverloaded,
    FleetRouter,
    FleetScaler,
    PagedKVPool,
    ScalerConfig,
    make_prompts,
)

#: The TTFT objective is thresholded in SCHEDULER TICKS, not wall
#: seconds: one loop tick advances every live replica one engine tick —
#: the simulated-concurrency unit — so a request's (first-token tick −
#: arrival tick) is machine-speed invariant AND fleet-size fair. Wall
#: seconds would invert reality here: serializing N engines in one loop
#: makes a BIGGER fleet slower per tick, so a frozen one-replica fleet
#: looked FASTER on the wall clock than the healthy autoscaled one
#: (found driving the freeze teeth). A reacting scaler holds queues to
#: a few ticks (healthy p99 ~5 with the threshold at 16); a frozen
#: scaler under the same waves runs a peak-long backlog (mean ~16,
#: p99 ~38, bad fraction ~10x the 5% budget) — tests/test_soak.py
#: pins both sides.
TTFT_SLO_TICKS = 16.0
#: loose on purpose: soak decode dispatches interleave with churn
#: controller threads and the scaler. The signal is a tick's duration
#: on this machine's clock, so no test reads this objective's alert
DECODE_SLO_HEADROOM = 3.0

#: the churn leg's pod ownership label
SOAK_LABEL = "kubeflow-tpu.org/soak-train"


@dataclass(frozen=True)
class SoakConfig:
    """One day, in ticks. The defaults are sized so the whole drill —
    warmup, day, drain — runs in tens of seconds on CPU while still
    forcing every transition: multi-replica peaks, a scale-to-zero
    trough, a wake-on-arrival cold start, kills, one hang, and churn."""

    seed: int = 17
    day_ticks: int = 240
    #: diurnal peaks in arrivals/tick (trough is 0 by construction):
    #: both sit well past one replica's ~0.5 req/tick capacity and
    #: under the max_replicas fleet's — a frozen scaler MUST fall
    #: behind, a reacting one must keep up
    peak1_rate: float = 1.6
    peak2_rate: float = 1.8
    #: serving geometry (the serve_fleet shape, slightly smaller)
    rows: int = 3
    prompt_body: int = 4
    shared_prefix: int = 4
    new_tokens: int = 4
    block: int = 4
    chunk: int = 4
    max_replicas: int = 5
    #: seeded fault schedule, as day fractions
    kill_at: tuple = (0.33, 0.72)
    hang_at: float = 0.62
    hang_ticks: int = 10
    #: scaler cadence knobs (evaluations == ticks here)
    scale_up_cooldown_evals: int = 2
    scale_down_stable_evals: int = 8
    idle_to_zero_evals: int = 12
    drain_grace_evals: int = 8
    hang_detect_evals: int = 5
    #: SLO monitor evaluation cadence (ticks) — the scaler's burn-aware
    #: demand reads the monitor's last pass (the PR-12 contract), so
    #: this is also how fast a latency burn can raise the fleet
    slo_eval_every: int = 3
    #: control-plane churn: jobs arriving through the day
    churn_jobs: int = 6
    churn_pods_per_job: int = 2
    churn_job_ticks: int = 40
    churn_kill_at: tuple = (0.4, 0.66)
    #: post-day drain bound (a frozen scaler serves the whole backlog
    #: through one replica — bounded, not infinite)
    max_drain_ticks: int = 6000


def arrival_rate(tick: int, cfg: SoakConfig) -> float:
    """The diurnal profile: morning ramp to peak 1, a trough to ZERO
    (scale-to-zero territory), an evening peak 2, then night. Returns
    arrivals per tick."""
    f = tick / cfg.day_ticks
    if f < 0.04:
        return 0.25  # early trickle: first request wakes nothing (one
        # replica is up) but calibrates the service rate
    if f < 0.22:
        return 0.3 + (cfg.peak1_rate - 0.3) * (f - 0.04) / 0.18
    if f < 0.34:
        return cfg.peak1_rate
    if f < 0.40:
        return cfg.peak1_rate * (0.40 - f) / 0.06
    if f < 0.58:
        return 0.0  # the trough: the fleet must reach zero here
    if f < 0.66:
        return cfg.peak2_rate * (f - 0.58) / 0.08
    if f < 0.84:
        return cfg.peak2_rate
    return 0.0  # night


def calibrated_default_slos(ttft_threshold_s: float,
                            decode_threshold_s: float):
    """`default_slos()` with the two latency thresholds re-anchored to
    in-run healthy measurements (everything else — names, kinds,
    budgets, windows, the goodput ratio threshold and the zero-drop
    contract — stays the platform default). Absolute CPU latencies are
    machine-dependent; the OBJECTIVE SET is not."""
    out = []
    for cfg in default_slos():
        if cfg.name == "serving_ttft_p99":
            cfg = dataclasses.replace(cfg, threshold=ttft_threshold_s)
        elif cfg.name == "serving_decode_tick":
            cfg = dataclasses.replace(cfg, threshold=decode_threshold_s)
        out.append(cfg)
    return tuple(out)


# --------------------------------------------------------------- churn leg


class _ChurnLeg:
    """Training-job churn on a real control plane: labeled pods driven
    to Running by a real controller (informer → keyed workqueue →
    status-write buffer), jobs arriving/completing through the day,
    seeded pod kills restarting incarnations. goodput(tick) is the
    running/desired pod ratio — 1.0 converged, dented by kills — and
    the dents sum into the restart-overhead budget."""

    def __init__(self, cfg: SoakConfig, rng: random.Random):
        from kubeflow_tpu.controller.base import ControllerBase
        from kubeflow_tpu.controller.fakecluster import (
            FakeCluster,
            PodPhase,
        )
        from kubeflow_tpu.controller.statusbuffer import StatusWriteBuffer

        self.cfg = cfg
        self.cluster = FakeCluster()
        self.buffer = StatusWriteBuffer(self.cluster, kind="pods")
        self._phase_running = PodPhase.RUNNING
        self._phase_pending = PodPhase.PENDING
        buffer = self.buffer

        class ChurnController(ControllerBase):
            ERROR_EVENT_KIND = "pods"
            WATCH_SELECTORS = {"pods": {SOAK_LABEL: None}}

            def kind_filter(self, etype, kind, obj):
                if kind == "pods" and SOAK_LABEL in obj.metadata.labels:
                    return obj.key
                return None

            def resync_keys(self):
                return ()

            def reconcile(self, key):
                pod = self.cluster.get("pods", key)
                if pod is None or pod.status.phase != PodPhase.PENDING:
                    return None

                def to_running(p):
                    if p.status.phase != PodPhase.PENDING:
                        return False
                    p.status.phase = PodPhase.RUNNING
                    p.status.node = "soak-node"

                buffer.write(key, pod.metadata.uid, to_running)
                return None

        self.ctrl = ChurnController(self.cluster, "soaktrain", workers=1)
        # job j -> (create tick, complete tick); spread across the day,
        # every job finishing inside it
        span = cfg.day_ticks - cfg.churn_job_ticks - 5
        self.schedule = sorted(
            rng.randrange(1, max(span, 2)) for _ in range(cfg.churn_jobs))
        self.kill_ticks = sorted(
            int(f * cfg.day_ticks) for f in cfg.churn_kill_at)
        self._live: dict[int, int] = {}  # job -> completion tick
        self._next_job = 0
        self._restarted = 0
        self.pod_ticks = 0
        self.overhead_pod_ticks = 0
        self.goodput_samples: list[float] = []

    def start(self) -> "_ChurnLeg":
        self.ctrl.start()
        return self

    def _pod(self, job: int, idx: int):
        from kubeflow_tpu.api.common import ObjectMeta
        from kubeflow_tpu.controller.fakecluster import Pod

        return Pod(metadata=ObjectMeta(
            name=f"soak-{job:02d}-{idx}", labels={SOAK_LABEL: "1"}))

    def step(self, tick: int) -> float:
        """Advance the churn by one tick; returns the goodput sample
        (1.0 when no training work is live)."""
        cfg = self.cfg
        while (self._next_job < len(self.schedule)
               and self.schedule[self._next_job] <= tick):
            job = self._next_job
            for i in range(cfg.churn_pods_per_job):
                self.cluster.create("pods", self._pod(job, i))
            self._live[job] = tick + cfg.churn_job_ticks
            self._next_job += 1
        for job, done in list(self._live.items()):
            if done <= tick:
                for i in range(cfg.churn_pods_per_job):
                    try:
                        self.cluster.delete(
                            "pods", f"default/soak-{job:02d}-{i}")
                    except KeyError:
                        pass
                del self._live[job]
        if self.kill_ticks and self.kill_ticks[0] <= tick and self._live:
            # the fault: kill one running pod of a live job — delete +
            # recreate is the restart incarnation; reconvergence cost
            # lands in the overhead ledger below
            self.kill_ticks.pop(0)
            job = next(iter(self._live))
            key = f"default/soak-{job:02d}-0"
            try:
                self.cluster.delete("pods", key)
                self.cluster.create("pods", self._pod(job, 0))
                self._restarted += 1
            except KeyError:
                pass
        desired = len(self._live) * cfg.churn_pods_per_job
        if desired == 0:
            return 1.0
        running = len(self.cluster.list(
            "pods",
            lambda p: SOAK_LABEL in p.metadata.labels
            and p.status.phase == self._phase_running))
        running = min(running, desired)
        self.pod_ticks += desired
        self.overhead_pod_ticks += desired - running
        sample = running / desired
        self.goodput_samples.append(sample)
        return sample

    def finish(self) -> dict:
        self.ctrl.stop()
        self.buffer.close()
        mean = (sum(self.goodput_samples) / len(self.goodput_samples)
                if self.goodput_samples else 1.0)
        return {
            "jobs": len(self.schedule),
            "pod_restarts": self._restarted,
            "goodput_mean": round(mean, 4),
            "goodput_min": round(min(self.goodput_samples, default=1.0),
                                 4),
            "restart_overhead_frac": round(
                self.overhead_pod_ticks / max(self.pod_ticks, 1), 4),
        }


def _torn_checkpoint() -> dict:
    """The torn-checkpoint seam, composed into the day: save two
    verified steps, corrupt the newest (the chaos torn-save shape),
    and prove restore falls back to the previous VERIFIED step with the
    corrupt one quarantined (docs/health.md)."""
    from kubeflow_tpu.chaos import corrupt_newest_checkpoint
    from kubeflow_tpu.train.checkpoint import Checkpointer

    d = tempfile.mkdtemp(prefix="kftpu-soak-ckpt-")
    try:
        ck = Checkpointer(d, max_to_keep=4, async_save=False)
        x = np.arange(8, dtype=np.float32)
        ck.save(1, {"x": x})
        ck.save(2, {"x": x * 2})
        corrupted = corrupt_newest_checkpoint(d)
        step, restored = ck.restore_latest({"x": x})
        ck.close()
        ok = (corrupted == 2 and step == 1
              and bool(np.allclose(restored["x"], x)))
        return {"fallback_ok": ok, "corrupted_step": corrupted,
                "restored_step": step}
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------- the day


def run_prod_day(cfg: SoakConfig | None = None, frozen: bool = False,
                 tracer=None) -> dict:
    """Run one production day (module docstring). `frozen=True` is the
    scaler_freeze chaos mode: the scaler evaluates but acts on nothing
    while the waves continue — the SLO burn alert must catch it.
    Returns the raw drill record (seconds + counts); tests read the
    counts."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.monitoring.report import build_slo_report_from_spans
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    from kubeflow_tpu.tracing import Tracer

    cfg = cfg or SoakConfig()
    rng = random.Random(f"kftpu-soak-{cfg.seed}")
    prompt_len = cfg.shared_prefix + cfg.prompt_body
    gpt_cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2, mlp_dim=128, dropout_rate=0.0,
                        max_len=prompt_len + cfg.new_tokens + 18)
    model = GPTLM(gpt_cfg)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pool = PagedKVPool(block_size=cfg.block, capacity_blocks=1024)
    tsdb = TimeSeriesStore(capacity_per_series=4096)
    tracer = tracer if tracer is not None else Tracer(
        capacity=16384, service="prod_day")
    warm_prompt = make_prompts(1, seed=cfg.seed + 90,
                               vocab=gpt_cfg.vocab_size,
                               prompt_len=cfg.prompt_body,
                               shared_prefix=cfg.shared_prefix)[0]

    def build_warm_engine():
        # build + WARM before serving (the readiness-probe contract):
        # chunk prefill, decode step, splice, and the pool-match
        # suffix-1 shape all compile here, so a replica never serves
        # its first request through XLA. Monitoring attaches via
        # router.add_replica (_wire_engine), AFTER the warm traffic —
        # warm ticks carry compile time and must not poison the SLO
        # series.
        eng = ContinuousBatcher(
            model, variables, max_rows=cfg.rows,
            default_max_new_tokens=cfg.new_tokens,
            paged_kv=pool, prefill_chunk=cfg.chunk)
        for _ in range(2):
            eng.submit(warm_prompt, max_new_tokens=2)
            eng.run_until_idle()
        return eng

    # standby pool: every replica the day can consume is compiled and
    # warmed BEFORE the day starts — the AOT / restart-warm cold-start
    # contract (serving/aot.py, utils/compile_cache.py): production
    # scale-up cost is scheduling + activation, not XLA, so the soak's
    # cold starts must not be dominated by per-engine jit either. The
    # factory pops a standby and GRACEFULLY-drained engines recycle
    # back (the scaler's on_release hook) — only kills and hangs
    # consume the pool for good, so it is sized for max concurrency
    # plus one replacement per injected fault. An exhausted pool
    # rebuilds on demand; that genuinely slow cold start shows in the
    # EWMA.
    standby = [build_warm_engine()
               for _ in range(cfg.max_replicas + len(cfg.kill_at) + 1)]
    in_day_builds = [0]

    def engine_factory():
        if standby:
            return standby.pop()
        in_day_builds[0] += 1
        return build_warm_engine()

    # ---- the fleet: one warm replica up, scaler owning the rest
    first = engine_factory()
    router = FleetRouter([("scaled-base", first)], max_requeues=5,
                         tracer=tracer)

    # ---- in-run anchors: healthy decode tick through the SAME tsdb
    # hook the monitored samples use, measured
    # on full rows before any monitoring attaches
    for p in make_prompts(cfg.rows, seed=cfg.seed + 91,
                          vocab=gpt_cfg.vocab_size,
                          prompt_len=cfg.prompt_body,
                          shared_prefix=cfg.shared_prefix):
        first.submit(p, max_new_tokens=cfg.new_tokens + 12)
    for _ in range(cfg.rows * (prompt_len // cfg.chunk + 2)):
        first.tick()
        if not first._pending and all(first._rows):
            break
    anchor_tsdb = TimeSeriesStore()
    saved_tsdb, first.tsdb = first.tsdb, anchor_tsdb
    for _ in range(12):
        first.tick()
    first.tsdb = saved_tsdb
    healthy_tick = sorted(
        v for _, v in anchor_tsdb.window("serving.decode_tick_s",
                                         3600.0))
    healthy_tick = healthy_tick[len(healthy_tick) // 2]
    first.run_until_idle()
    # monitoring attaches only now: anchor + warm traffic stayed out of
    # the SLO series; scale-up replicas inherit both via add_replica
    router.wire_monitoring(tsdb=tsdb)

    # admission shedding is LAST-RESORT here (threshold far past the
    # demand signal's reaction point — shedding hides latency from the
    # TTFT objective, the blindspot this drill's first runs exposed);
    # the demand signal runs on the explicit working-set capacity
    # target, and the TTFT OBJECTIVE is thresholded in ticks (module
    # comment)
    admission_slo_s = 500.0 * healthy_tick
    decode_threshold = DECODE_SLO_HEADROOM * healthy_tick
    router.ttft_slo_s = admission_slo_s
    router.retry_after_s = max(8.0 * healthy_tick, 1e-4)
    router.demand_tokens_per_replica = float(
        cfg.rows * (prompt_len + cfg.new_tokens))
    monitor = SLOMonitor(tsdb, calibrated_default_slos(
        TTFT_SLO_TICKS, decode_threshold))
    scaler = FleetScaler(
        router, engine_factory,
        ScalerConfig(
            min_replicas=0, max_replicas=cfg.max_replicas,
            scale_up_cooldown_evals=cfg.scale_up_cooldown_evals,
            scale_down_stable_evals=cfg.scale_down_stable_evals,
            idle_to_zero_evals=cfg.idle_to_zero_evals,
            drain_grace_evals=cfg.drain_grace_evals,
            hang_detect_evals=cfg.hang_detect_evals),
        monitor=monitor, tracer=tracer,
        on_release=standby.append)
    if frozen:
        scaler.freeze()

    # ---- seeded schedules
    prompts = make_prompts(
        int(cfg.day_ticks * max(cfg.peak1_rate, cfg.peak2_rate)) + 64,
        seed=cfg.seed, vocab=gpt_cfg.vocab_size,
        prompt_len=cfg.prompt_body, shared_prefix=cfg.shared_prefix)
    kill_ticks = sorted(int(f * cfg.day_ticks) for f in cfg.kill_at)
    hang_tick = int(cfg.hang_at * cfg.day_ticks)
    churn = _ChurnLeg(cfg, rng).start()

    handles: dict[int, object] = {}
    retries: list[tuple[int, int]] = []  # (due tick, prompt idx)
    shed_retries = 0
    recent_ttfts: list[float] = []   # wall seconds (informational)
    ttft_ticks: list[int] = []       # scheduler ticks (the SLO unit)
    arrival_tick: dict[int, int] = {}
    first_tok_tick: dict[int, int] = {}
    retry_wait_ticks: dict[int, int] = {}
    cur_tick = [0]
    collected: set[int] = set()
    hung: dict[str, int] = {}  # replica name -> resume tick
    n_submitted = 0
    kills_done = 0
    hang_done = False
    replicas_peak = 1
    ckpt = {}

    def _note_first_token(idx: int):
        def cb(_freq, _tok):
            # client-perceived first token, in scheduler ticks: the
            # `delivered` high-water mark guarantees this fires once
            # per position even across requeue re-decodes
            first_tok_tick.setdefault(idx, cur_tick[0])
        return cb

    def submit(idx: int, tick: int) -> None:
        nonlocal shed_retries
        try:
            handles[idx] = router.submit(
                prompts[idx], max_new_tokens=cfg.new_tokens,
                on_token=_note_first_token(idx))
            # TTFT counts from the SUCCESSFUL admission (the LoadReport
            # contract: client Retry-After backoff is accounted apart
            # from TTFT, never folded into it)
            arrival_tick[idx] = tick
        except FleetOverloaded as exc:
            # the client honors Retry-After (serving/client.py contract)
            # in tick units: back off proportionally, re-dial, never
            # give up — "dropped" means dropped, not "shed and tired"
            shed_retries += 1
            delay = min(max(1, round(exc.retry_after_s
                                     / max(healthy_tick, 1e-9))), 25)
            retry_wait_ticks[idx] = retry_wait_ticks.get(idx, 0) + delay
            retries.append((tick + delay, idx))

    def one_tick(tick: int, arrivals: int) -> None:
        nonlocal n_submitted, kills_done, hang_done, replicas_peak
        cur_tick[0] = tick
        # faults first (the drill order: the world breaks, then serves)
        if kill_ticks and kill_ticks[0] <= tick:
            admittable = [r for r in router._admittable()
                          if r.name not in hung]
            if len(admittable) >= 2:
                kill_ticks.pop(0)
                kills_done += 1
                router.kill_replica(
                    admittable[rng.randrange(len(admittable))].name)
        if not hang_done and tick >= hang_tick:
            admittable = [r for r in router._admittable()
                          if r.name not in hung]
            if admittable:
                victim = admittable[0]
                hung[victim.name] = tick + cfg.hang_ticks
                hang_done = True
        for name, until in list(hung.items()):
            if until <= tick:
                del hung[name]  # SIGCONT: the replica ticks again
        # arrivals + due retries
        for _ in range(arrivals):
            if n_submitted < len(prompts):
                submit(n_submitted, tick)
                n_submitted += 1
        for due, idx in list(retries):
            if due <= tick:
                retries.remove((due, idx))
                submit(idx, tick)
        # serve: one round-robin pass over live, un-hung replicas
        # (a hung replica is SIGSTOPped — alive, silent)
        for rep in list(router.replicas):
            if rep.alive and rep.name not in hung:
                rep.engine.tick()
        # the monitoring plane: one TTFT sample per COMPLETED request,
        # in scheduler ticks (module comment — the machine-invariant,
        # fleet-size-fair latency unit), counted from the SUCCESSFUL
        # admission (the LoadReport contract: client Retry-After
        # backoff is accounted apart, in retry_wait_ticks — shed
        # volume is its own signal in the record, never folded into
        # TTFT). The burn math then reads "fraction of requests over
        # the threshold" against the 5% budget — the per-event form of
        # the p99 objective; a single slow request is one bad sample,
        # never a sticky window artifact.
        for idx, h in list(handles.items()):
            if idx not in collected and h.done.is_set() \
                    and h.error is None:
                collected.add(idx)
                if h.ttft_s is not None:
                    recent_ttfts.append(h.ttft_s)
                if idx in first_tok_tick:
                    dt = first_tok_tick[idx] - arrival_tick[idx]
                    ttft_ticks.append(dt)
                    tsdb.record(
                        'kftpu_fleet_ttft_seconds{quantile="0.99"}',
                        float(dt))
        tsdb.record("kftpu_fleet_requests_failed_total",
                    router.metrics["requests_failed_total"])
        tsdb.record("kftpu_prof_goodput_ratio", churn.step(tick))
        if tick % cfg.slo_eval_every == 0:
            monitor.evaluate()  # the burn the scaler's demand reads
        scaler.evaluate()
        replicas_peak = max(replicas_peak, len(router._admittable()))

    t0 = time.perf_counter()
    tick = 0
    try:
        for tick in range(cfg.day_ticks):
            if not ckpt and tick >= cfg.day_ticks // 2:
                ckpt = _torn_checkpoint()  # the mid-day torn save
            one_tick(tick, _arrivals(arrival_rate(tick, cfg), rng))
        # night drain: no new arrivals; retries and backlog must all
        # complete (a frozen scaler pays this through one replica)
        while tick < cfg.day_ticks + cfg.max_drain_ticks:
            tick += 1
            if (not retries
                    and all(h.done.is_set() for h in handles.values())
                    and len(handles) + len(retries) >= n_submitted):
                break
            one_tick(tick, 0)
    finally:
        wall_s = time.perf_counter() - t0
        churn_stats = churn.finish()
        for rep in router.replicas:
            rep.engine.stop()

    # every submitted index ends in exactly one place: a handle (served
    # or failed) or the retry list (shed and never re-admitted) — both
    # non-completions count as drops, nothing double-counts
    dropped = sum(
        1 for h in handles.values()
        if h.error is not None or not h.done.is_set()
    ) + len(retries)

    # ---- THE report: one build path with /debug/slo and the CLI
    report = build_slo_report_from_spans(tracer.snapshot(),
                                         monitor=monitor)
    states = {s["name"]: s for s in report["slos"]}
    worst_burn = 0.0
    for name in ("serving_ttft_p99", "serving_decode_tick",
                 "serving_zero_drop"):
        rates = states.get(name, {}).get("burn_rates", {})
        if rates:
            worst_burn = max(worst_burn, max(rates.values()))
    def _p99(values):
        s = sorted(values)
        return s[min(len(s) - 1, int(len(s) * 0.99))] if s else 0.0

    ttft_p99 = _p99(recent_ttfts)
    ttft_p99_ticks = _p99(ttft_ticks)
    decode_samples = sorted(
        v for _, v in tsdb.window("serving.decode_tick_s", 10 ** 6))
    m = scaler.metrics

    return {
        "seed": cfg.seed,
        "frozen": frozen,
        "ticks": tick + 1,
        "day_ticks": cfg.day_ticks,
        "wall_s": round(wall_s, 3),
        "n_requests": n_submitted,
        "completed": len(collected),
        "dropped": dropped,
        "shed_retries": shed_retries,
        "requeued": router.metrics["requests_requeued_total"],
        "resumed": router.metrics["requeues_resumed_total"],
        "retry_wait_ticks_p99": _p99(list(retry_wait_ticks.values())),
        "kills_injected": kills_done,
        "hang_injected": hang_done,
        "replicas_peak": replicas_peak,
        "in_day_engine_builds": in_day_builds[0],
        "scaler": dict(m),
        "scale_to_zero_reached": m["scale_to_zero_total"] >= 1,
        "recovered_from_zero": m["scale_from_zero_total"] >= 1,
        "cold_start_ewma_s": round(scaler.cold_start_ewma_s, 4),
        "ttft_p99_s": round(ttft_p99, 6),
        "ttft_p99_ticks": float(ttft_p99_ticks),
        "ttft_mean_ticks": round(
            sum(ttft_ticks) / len(ttft_ticks), 3) if ttft_ticks else 0.0,
        "ttft_max_ticks": float(max(ttft_ticks, default=0)),
        "ttft_bad_frac": round(
            sum(1 for t in ttft_ticks if t > TTFT_SLO_TICKS)
            / max(len(ttft_ticks), 1), 4),
        "ttft_threshold_ticks": TTFT_SLO_TICKS,
        "admission_slo_s": round(admission_slo_s, 6),
        "healthy_tick_s": round(healthy_tick, 6),
        "decode_tick_s": round(
            decode_samples[len(decode_samples) // 2], 6)
        if decode_samples else 0.0,
        "churn": churn_stats,
        "ckpt": ckpt,
        "slo": {
            "alerts": [a["slo"] for a in report["alerts"]],
            "worst_serving_burn": round(worst_burn, 4),
            "states": {
                name: {"fired": st["fired"],
                       "burn_rates": st["burn_rates"],
                       "samples": st["samples"]}
                for name, st in states.items()
            },
        },
        "report": {
            "requests": report["requests"],
            "tsdb": report["tsdb"],
        },
    }


def _arrivals(rate: float, rng: random.Random) -> int:
    """Seeded per-tick arrival count for a fractional rate."""
    n = int(rate)
    if rng.random() < rate - n:
        n += 1
    return n


# ------------------------------------------------------- the diurnal storm


@dataclass(frozen=True)
class StormConfig(SoakConfig):
    """The chip-constrained day (docs/scheduler.md): the prod-day waves
    re-run on a cluster where peak serving demand CANNOT fit without
    preempting batch training. 12 chips, 4 per slice: two 4-chip batch
    gangs hold 8, the base serving replica 1 — three free. The evening
    peak demands more replicas than the free pool covers, so the shared
    ledger's preemption-then-grant evicts the youngest (borrowed) gang;
    the trough and the night release chips and the gang gang-restarts
    back in. Every number below is sized so both transitions MUST
    happen on the seeded schedule."""

    capacity_chips: int = 12
    chips_per_slice: int = 4
    batch_gangs: int = 2
    batch_workers: int = 2
    #: 2x2 = 4 chips = one whole slice per gang
    batch_topology: str = "2x2"
    #: higher evening peak + heavier replicas than the free pool:
    #: serving claims 2 chips per replica, so only TWO replicas fit
    #: beside the gangs (8 + 2x2 = 12) — the third claim of either
    #: peak must evict a batch gang (preemption-then-grant), and
    #: max_replicas is reachable only over preempted chips
    peak2_rate: float = 3.4
    serving_chips_per_replica: int = 2
    max_replicas: int = 4
    #: post-drain bound on waiting for the evicted gang's rebind
    resume_wait_ticks: int = 2000


class _BatchGangLeg:
    """Batch training gangs on a real control plane, drawing from the
    SAME chip ledger as the serving fleet: a FakeCluster + GangScheduler
    + JobController stack whose jobs reserve whole slices through
    `ChipScheduler.claim_gang`. Pods are never started (no runtime —
    the leg measures scheduling, not training): a gang is "running" when
    its podgroup is admitted and bound. A scheduler eviction marks the
    pods FAILED with the PREEMPTED exit class; the job controller's
    gang-restart path recreates them and the gang re-admits when the
    serving fleet releases chips — preempt-to-resume is measured in
    ticks by polling the podgroup phase."""

    def __init__(self, cfg: StormConfig, tracer, workdir: str):
        import os

        from kubeflow_tpu.controller.fakecluster import FakeCluster
        from kubeflow_tpu.controller.gang import (
            GangScheduler,
            topology_chips,
        )
        from kubeflow_tpu.controller.jobcontroller import JobController
        from kubeflow_tpu.scheduler.chipsched import ChipScheduler

        self.cfg = cfg
        self.cluster = FakeCluster()
        self.cluster.capacity_chips = cfg.capacity_chips
        self.cluster.tracer = tracer
        #: THE shared inventory: the gang scheduler admits through it
        #: and the FleetScaler claims replica chips from it
        self.ledger = ChipScheduler(
            capacity_fn=lambda: self.cluster.capacity_chips,
            tracer_fn=lambda: self.cluster.tracer,
            chips_per_slice=cfg.chips_per_slice)
        self.gang = GangScheduler(self.cluster, chipsched=self.ledger)
        self.jc = JobController(
            self.cluster, workers=1,
            heartbeat_dir=os.path.join(workdir, "heartbeats"),
            compile_cache_dir=os.path.join(workdir, "compile-cache"))
        self.gang_chips = topology_chips(cfg.batch_topology)
        self.job_keys = [
            f"default/storm-batch-{i}" for i in range(cfg.batch_gangs)]
        self._bound: dict[str, bool] = {}
        self._evicted_at: dict[str, int] = {}
        self.preemptions_seen = 0
        self.resume_ticks: list[int] = []
        self.goodput_samples: list[float] = []

    def start(self) -> "_BatchGangLeg":
        from kubeflow_tpu.api.common import (
            ContainerSpec,
            ObjectMeta,
            PodTemplateSpec,
            ReplicaSpec,
            RestartPolicy,
            RunPolicy,
            SchedulingPolicy,
        )
        from kubeflow_tpu.api.jobs import (
            JAXJob,
            JAXJobSpec,
            REPLICA_WORKER,
        )

        self.jc.start()
        self.gang.start()
        for i in range(self.cfg.batch_gangs):
            job = JAXJob(
                metadata=ObjectMeta(name=f"storm-batch-{i}"),
                spec=JAXJobSpec(
                    replica_specs={REPLICA_WORKER: ReplicaSpec(
                        replicas=self.cfg.batch_workers,
                        # the preemption contract: exit 143 (128+SIGTERM)
                        # is retryable BY CONSTRUCTION under ExitCode
                        restart_policy=RestartPolicy.EXIT_CODE,
                        template=PodTemplateSpec(
                            container=ContainerSpec(
                                command=["python", "-c", "pass"])))},
                    run_policy=RunPolicy(
                        backoff_limit=64,
                        scheduling_policy=SchedulingPolicy(
                            slice_topology=self.cfg.batch_topology)),
                ))
            self.cluster.create("jobs", job)
        return self

    def wait_bound(self, timeout_s: float = 30.0) -> None:
        """Block until every gang is admitted (the pre-day steady
        state; the storm's transitions are measured from here)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            self.gang._try_schedule_safe()
            if all(self._pg_bound(k) for k in self.job_keys):
                return
            time.sleep(0.01)
        raise TimeoutError(
            f"batch gangs failed to bind: "
            f"{[(k, self._pg_bound(k)) for k in self.job_keys]}")

    def _pg_bound(self, key: str) -> bool:
        pg = self.cluster.get("podgroups", key)
        return pg is not None and pg.phase == "Running"

    def nudge(self) -> None:
        """One synchronous scheduling pass — the tick loop calls this
        after the scaler may have released chips, so a rebind lands on
        the tick that freed the capacity (the gang thread's 0.5s poll
        would smear the resume latency across wall time)."""
        self.gang._try_schedule_safe()

    def step(self, tick: int) -> float:
        """Poll gang state; returns the chips-weighted goodput sample
        (bound batch chips / total batch chips)."""
        for key in self.job_keys:
            bound = self._pg_bound(key)
            was = self._bound.get(key, False)
            if was and not bound:
                # the only unbind in this leg is a scheduler eviction
                self._evicted_at[key] = tick
                self.preemptions_seen += 1
            elif bound and not was and key in self._evicted_at:
                self.resume_ticks.append(
                    tick - self._evicted_at.pop(key))
            self._bound[key] = bound
        total = self.gang_chips * len(self.job_keys)
        sample = (sum(self.gang_chips for k in self.job_keys
                      if self._bound.get(k)) / total) if total else 1.0
        self.goodput_samples.append(sample)
        return sample

    def all_bound(self) -> bool:
        return all(self._bound.get(k) for k in self.job_keys)

    def finish(self) -> dict:
        self.gang.stop()
        self.jc.stop()
        restarts = {}
        for key in self.job_keys:
            job = self.cluster.get("jobs", key)
            restarts[key] = job.status.restart_count if job else -1
        mean = (sum(self.goodput_samples) / len(self.goodput_samples)
                if self.goodput_samples else 1.0)
        return {
            "gangs": len(self.job_keys),
            "gang_chips": self.gang_chips,
            "preemptions_seen": self.preemptions_seen,
            "resume_ticks": list(self.resume_ticks),
            "resumed": len(self.resume_ticks),
            "restart_counts": restarts,
            "goodput_mean": round(mean, 4),
            "goodput_min": round(
                min(self.goodput_samples, default=1.0), 4),
        }


def run_diurnal_storm(cfg: StormConfig | None = None,
                      frozen: bool = False, tracer=None) -> dict:
    """One chip-constrained production day (StormConfig docstring):
    the prod-day serving waves with the fleet's replica chips claimed
    from the SAME ledger two batch training gangs occupy. The peaks
    force preemption-then-grant (a batch gang is evicted through the
    gang-restart path), the trough and the night force the resume —
    gated on p99 TTFT, zero drops, ZERO serving SLO violations,
    preempt-to-resume latency in ticks, and the batch goodput floor.
    `frozen=True` is the sched_freeze chaos mode: the ledger stops
    granting (admission-only outage — releases still work) while the
    waves continue, so the fleet is pinned at one replica through both
    peaks and the SLO burn alert must catch it."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.monitoring.report import build_slo_report_from_spans
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    from kubeflow_tpu.tracing import Tracer

    cfg = cfg or StormConfig()
    rng = random.Random(f"kftpu-storm-{cfg.seed}")
    prompt_len = cfg.shared_prefix + cfg.prompt_body
    gpt_cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2, mlp_dim=128, dropout_rate=0.0,
                        max_len=prompt_len + cfg.new_tokens + 18)
    model = GPTLM(gpt_cfg)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pool = PagedKVPool(block_size=cfg.block, capacity_blocks=1024)
    tsdb = TimeSeriesStore(capacity_per_series=4096)
    tracer = tracer if tracer is not None else Tracer(
        capacity=16384, service="diurnal_storm")
    warm_prompt = make_prompts(1, seed=cfg.seed + 90,
                               vocab=gpt_cfg.vocab_size,
                               prompt_len=cfg.prompt_body,
                               shared_prefix=cfg.shared_prefix)[0]

    def build_warm_engine():
        eng = ContinuousBatcher(
            model, variables, max_rows=cfg.rows,
            default_max_new_tokens=cfg.new_tokens,
            paged_kv=pool, prefill_chunk=cfg.chunk)
        for _ in range(2):
            eng.submit(warm_prompt, max_new_tokens=2)
            eng.run_until_idle()
        return eng

    # ---- the batch leg + THE ledger (fair-share DRF over chips:
    # batch and serving tenants entitled half the cluster each, so the
    # second gang runs on BORROWED chips — the claim an under-share
    # serving peak is entitled to reclaim)
    workdir = tempfile.mkdtemp(prefix="kftpu-storm-")
    leg = _BatchGangLeg(cfg, tracer, workdir)
    ledger = leg.ledger
    ledger.set_shares({"default": 1.0, "serving": 1.0})
    leg.start()
    leg.wait_bound()

    standby = [build_warm_engine() for _ in range(cfg.max_replicas + 1)]
    in_day_builds = [0]

    def engine_factory():
        if standby:
            return standby.pop()
        in_day_builds[0] += 1
        return build_warm_engine()

    # ---- the fleet: one warm replica up, its chip claimed like every
    # scaled replica's will be (the scaler's claim-key convention, so
    # a later drain of the base releases the right claim)
    first = engine_factory()
    router = FleetRouter([("scaled-base", first)], max_requeues=5,
                         tracer=tracer)
    base_grant = ledger.claim_replica(
        "fleet/scaled-base", chips=cfg.serving_chips_per_replica,
        tenant="serving")
    assert base_grant.ok, base_grant

    # ---- in-run anchors (the prod-day trick): healthy decode tick
    # measured before monitoring attaches
    for p in make_prompts(cfg.rows, seed=cfg.seed + 91,
                          vocab=gpt_cfg.vocab_size,
                          prompt_len=cfg.prompt_body,
                          shared_prefix=cfg.shared_prefix):
        first.submit(p, max_new_tokens=cfg.new_tokens + 12)
    for _ in range(cfg.rows * (prompt_len // cfg.chunk + 2)):
        first.tick()
        if not first._pending and all(first._rows):
            break
    anchor_tsdb = TimeSeriesStore()
    saved_tsdb, first.tsdb = first.tsdb, anchor_tsdb
    for _ in range(12):
        first.tick()
    first.tsdb = saved_tsdb
    healthy_tick = sorted(
        v for _, v in anchor_tsdb.window("serving.decode_tick_s",
                                         3600.0))
    healthy_tick = healthy_tick[len(healthy_tick) // 2]
    first.run_until_idle()
    router.wire_monitoring(tsdb=tsdb)

    admission_slo_s = 500.0 * healthy_tick
    decode_threshold = DECODE_SLO_HEADROOM * healthy_tick
    router.ttft_slo_s = admission_slo_s
    router.retry_after_s = max(8.0 * healthy_tick, 1e-4)
    router.demand_tokens_per_replica = float(
        cfg.rows * (prompt_len + cfg.new_tokens))
    monitor = SLOMonitor(tsdb, calibrated_default_slos(
        TTFT_SLO_TICKS, decode_threshold))
    scaler = FleetScaler(
        router, engine_factory,
        ScalerConfig(
            min_replicas=1, max_replicas=cfg.max_replicas,
            scale_up_cooldown_evals=cfg.scale_up_cooldown_evals,
            scale_down_stable_evals=cfg.scale_down_stable_evals,
            idle_to_zero_evals=cfg.idle_to_zero_evals,
            drain_grace_evals=cfg.drain_grace_evals,
            hang_detect_evals=cfg.hang_detect_evals),
        monitor=monitor, tracer=tracer,
        on_release=standby.append,
        # the tentpole wiring: every scaled replica claims its chip
        # from the SAME ledger the batch gangs occupy
        chipsched=ledger,
        chips_per_replica=cfg.serving_chips_per_replica,
        tenant="serving")
    if frozen:
        ledger.freeze()  # the sched_freeze chaos: granting stops

    prompts = make_prompts(
        int(cfg.day_ticks * max(cfg.peak1_rate, cfg.peak2_rate)) + 64,
        seed=cfg.seed, vocab=gpt_cfg.vocab_size,
        prompt_len=cfg.prompt_body, shared_prefix=cfg.shared_prefix)

    handles: dict[int, object] = {}
    retries: list[tuple[int, int]] = []
    shed_retries = 0
    ttft_ticks: list[int] = []
    arrival_tick: dict[int, int] = {}
    first_tok_tick: dict[int, int] = {}
    cur_tick = [0]
    collected: set[int] = set()
    n_submitted = 0
    replicas_peak = 1

    def _note_first_token(idx: int):
        def cb(_freq, _tok):
            first_tok_tick.setdefault(idx, cur_tick[0])
        return cb

    def submit(idx: int, tick: int) -> None:
        nonlocal shed_retries
        try:
            handles[idx] = router.submit(
                prompts[idx], max_new_tokens=cfg.new_tokens,
                on_token=_note_first_token(idx))
            arrival_tick[idx] = tick
        except FleetOverloaded as exc:
            shed_retries += 1
            delay = min(max(1, round(exc.retry_after_s
                                     / max(healthy_tick, 1e-9))), 25)
            retries.append((tick + delay, idx))

    def one_tick(tick: int, arrivals: int) -> None:
        nonlocal n_submitted, replicas_peak
        cur_tick[0] = tick
        for _ in range(arrivals):
            if n_submitted < len(prompts):
                submit(n_submitted, tick)
                n_submitted += 1
        for due, idx in list(retries):
            if due <= tick:
                retries.remove((due, idx))
                submit(idx, tick)
        for rep in list(router.replicas):
            if rep.alive:
                rep.engine.tick()
        for idx, h in list(handles.items()):
            if idx not in collected and h.done.is_set() \
                    and h.error is None:
                collected.add(idx)
                if idx in first_tok_tick:
                    dt = first_tok_tick[idx] - arrival_tick[idx]
                    ttft_ticks.append(dt)
                    tsdb.record(
                        'kftpu_fleet_ttft_seconds{quantile="0.99"}',
                        float(dt))
        tsdb.record("kftpu_fleet_requests_failed_total",
                    router.metrics["requests_failed_total"])
        tsdb.record("kftpu_prof_goodput_ratio", leg.step(tick))
        if tick % cfg.slo_eval_every == 0:
            monitor.evaluate()
        scaler.evaluate()
        # rebind on the tick that freed chips: a drain completed in
        # THIS evaluate released its claim — give the evicted gang its
        # synchronous admission pass now, not at the 0.5s poll
        leg.nudge()
        replicas_peak = max(replicas_peak, len(router._admittable()))

    t0 = time.perf_counter()
    tick = 0
    try:
        for tick in range(cfg.day_ticks):
            one_tick(tick, _arrivals(arrival_rate(tick, cfg), rng))
        # night: serve out the backlog, then keep the loop alive until
        # the evicted gang is back (the scale-down that frees its
        # chips is itself ticks away) — both bounded
        while tick < cfg.day_ticks + cfg.max_drain_ticks:
            tick += 1
            if (not retries
                    and all(h.done.is_set() for h in handles.values())
                    and len(handles) + len(retries) >= n_submitted):
                break
            one_tick(tick, 0)
        resume_deadline = tick + cfg.resume_wait_ticks
        while not frozen and not leg.all_bound() \
                and tick < resume_deadline:
            tick += 1
            one_tick(tick, 0)
    finally:
        wall_s = time.perf_counter() - t0
        batch = leg.finish()
        for rep in router.replicas:
            rep.engine.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    dropped = sum(
        1 for h in handles.values()
        if h.error is not None or not h.done.is_set()
    ) + len(retries)

    report = build_slo_report_from_spans(tracer.snapshot(),
                                         monitor=monitor)
    states = {s["name"]: s for s in report["slos"]}
    serving_alerts = [a["slo"] for a in report["alerts"]
                      if a["slo"].startswith("serving_")]
    worst_burn = 0.0
    for name in ("serving_ttft_p99", "serving_decode_tick",
                 "serving_zero_drop"):
        rates = states.get(name, {}).get("burn_rates", {})
        if rates:
            worst_burn = max(worst_burn, max(rates.values()))

    def _p99(values):
        s = sorted(values)
        return s[min(len(s) - 1, int(len(s) * 0.99))] if s else 0.0

    resume_mean = (sum(batch["resume_ticks"])
                   / len(batch["resume_ticks"])
                   if batch["resume_ticks"] else 0.0)
    m = scaler.metrics
    # conservation audit: a full day of preempt/grant/release churn
    # must leave the ledger internally consistent (asserts inside)
    ledger_audit = ledger.audit()

    return {
        "seed": cfg.seed,
        "frozen": frozen,
        "ticks": tick + 1,
        "day_ticks": cfg.day_ticks,
        "wall_s": round(wall_s, 3),
        "capacity_chips": cfg.capacity_chips,
        "chips_per_slice": cfg.chips_per_slice,
        "n_requests": n_submitted,
        "completed": len(collected),
        "dropped": dropped,
        "shed_retries": shed_retries,
        "requeued": router.metrics["requests_requeued_total"],
        "replicas_peak": replicas_peak,
        "in_day_engine_builds": in_day_builds[0],
        "scaler": dict(m),
        "chip_denies": m["chip_denies_total"],
        "sched": dict(ledger.metrics),
        "sched_snapshot": ledger.snapshot(),
        "ledger_audit": ledger_audit,
        "batch": batch,
        "preempt_to_resume_ticks_mean": round(resume_mean, 2),
        "preempt_to_resume_ticks_max": float(
            max(batch["resume_ticks"], default=0)),
        "preempt_to_resume_s": list(ledger.preempt_to_resume_s),
        "ttft_p99_ticks": float(_p99(ttft_ticks)),
        "ttft_bad_frac": round(
            sum(1 for t in ttft_ticks if t > TTFT_SLO_TICKS)
            / max(len(ttft_ticks), 1), 4),
        "ttft_threshold_ticks": TTFT_SLO_TICKS,
        "healthy_tick_s": round(healthy_tick, 6),
        "slo": {
            "alerts": [a["slo"] for a in report["alerts"]],
            "serving_alerts": serving_alerts,
            "worst_serving_burn": round(worst_burn, 4),
            "states": {
                name: {"fired": st["fired"],
                       "burn_rates": st["burn_rates"],
                       "samples": st["samples"]}
                for name, st in states.items()
            },
        },
        "report": {
            "requests": report["requests"],
            "tsdb": report["tsdb"],
        },
    }


# ------------------------------------------------ the day, on real pods


@dataclass(frozen=True)
class PodSoakConfig:
    """One compact production day on REAL pod subprocesses over the TCP
    transport: every replica is a podworker behind the length-prefixed
    wire, so the day's faults cross actual sockets. The in-process day
    (SoakConfig / run_prod_day) owns the scale-to-zero and SLO-burn
    story; this one owns the NETWORK failure matrix — a SIGKILL
    discovered through the wire, a SIGSTOP indicted by heartbeat age,
    and a mid-peak partition that heals only AFTER the scaler has
    replaced the victim, with the fenced claim's late deliveries
    refused (docs/serving.md "Pod-backed replicas": epoch fencing).
    Sized to run in seconds on CPU behind a shared XLA compile cache."""

    seed: int = 23
    day_ticks: int = 90
    #: diurnal peaks in arrivals/tick (arrival_rate reads these through
    #: the same two-peak profile as the in-process day)
    peak1_rate: float = 0.5
    peak2_rate: float = 0.6
    prompt_body: int = 4
    shared_prefix: int = 2
    new_tokens: int = 4
    block: int = 4
    #: fleet bounds; the floor is 2 so every fault's victim is REPLACED
    #: (the partition-heal gate is "heal after replacement") — the
    #: scale-down/scale-to-zero transitions belong to run_prod_day
    min_replicas: int = 2
    max_replicas: int = 3
    #: pre-spawned warm workers: initial replica + min-floor top-up +
    #: peak headroom + one replacement per injected fault
    standby: int = 6
    #: fault schedule, day fractions: SIGKILL in peak 1, SIGSTOP early
    #: peak 2, partition late peak 2
    kill_at: float = 0.30
    hang_at: float = 0.62
    partition_at: float = 0.74
    #: hang indictment is heartbeat-only here (beats ride the tick
    #: verb, so a SIGSTOPped worker's age grows while its mirrored
    #: step_count freezes — the wall-clock path the in-process day
    #: cannot exercise)
    heartbeat_max_age_s: float = 0.4
    scale_up_cooldown_evals: int = 2
    #: per-op wire timeout: ALSO a failure detector here — a submit the
    #: router routes to the SIGSTOPped pod wedges a round-trip, and
    #: this bound (not the 30s production default) is what converts it
    #: to a pod death when traffic reaches the wedge before the
    #: heartbeat watch does
    op_timeout_s: float = 2.0
    #: drain ticks are wire round-trips (~ms); the bound must cover the
    #: heartbeat ceiling's wall-clock wait
    max_drain_ticks: int = 20000
    transport: str = "tcp"
    #: persistent XLA cache shared across the workers (None = the
    #: stable per-machine temp path; tests pass their repo-local cache)
    compile_cache_dir: str | None = None


def run_prod_day_pods(cfg: PodSoakConfig | None = None) -> dict:
    """The production day re-composed on a spawn_pod fleet (class
    docstring above): diurnal traffic + autoscaler + torn checkpoint,
    with every replica a live subprocess dialed over `cfg.transport`.

    The three faults and what each must prove:

      - **SIGKILL** (peak 1): the client discovers the corpse through
        the wire (reset, redial refused, retries exhausted), the router
        requeues, the scaler replaces — zero drops.
      - **SIGSTOP** (peak 2): sockets stay open, mirrored counters
        freeze. TWO independent detectors race: the heartbeat age
        (ScalerConfig.heartbeat_max_age_s) indicts the wedge if no
        traffic touches it first; a submit the router routes to it
        wedges a round-trip until the op timeout converts it to a pod
        death. Either way the wedged pod ends dead, replaced, with its
        work requeued — the drill gates the outcome, not the winner.
      - **partition** (late peak 2): the victim's host becomes
        unreachable (set_partitioned — nothing crosses, the WORKER
        KEEPS RUNNING). The connection supervisor burns its retry
        budget, the death fences the claim, the router requeues, the
        scaler replaces. Only after the replacement lands does the
        partition HEAL; a fenced_poll then reads the stale worker's
        late deliveries and refuses every one — the zero-duplicate
        proof the drill returns.

    Gates (pinned by tests/test_soak.py): dropped == 0 EXACT,
    token_overruns == 0 (every completed stream is single-copy), and
    partition.healed_after_replacement with the fenced claim refusing
    all late events."""
    import os
    import signal

    from kubeflow_tpu.serving.fleet import (
        PagedKVPool as _Pool,
        spawn_pod,
        wire_pod_deaths,
    )
    from kubeflow_tpu.serving.fleet.podclient import (
        attach_router_death,
        pod_metrics_snapshot,
    )
    from kubeflow_tpu.serving.fleet.wire import PodWireError

    cfg = cfg or PodSoakConfig()
    rng = random.Random(f"kftpu-pods-soak-{cfg.seed}")
    vocab = 64
    prompt_len = cfg.shared_prefix + cfg.prompt_body
    warm = make_prompts(1, seed=cfg.seed + 99, vocab=vocab,
                        prompt_len=cfg.prompt_body,
                        shared_prefix=cfg.shared_prefix)
    spec = {
        "model": {"vocab_size": vocab, "hidden_size": 32, "num_layers": 1,
                  "num_heads": 2, "mlp_dim": 64, "dropout_rate": 0.0,
                  "max_len": prompt_len + cfg.new_tokens + 24},
        "seed": 0, "init_seed": 7, "max_rows": 2,
        "default_max_new_tokens": cfg.new_tokens, "eos_token_id": None,
        "prefill_chunk": 0,
        "pool": {"block_size": cfg.block, "capacity_blocks": 256},
        "warmup_prompts": [[int(t) for t in p] for p in warm],
        "warmup_new_tokens": cfg.new_tokens, "warmup_repeats": 1,
        "warmup_resume": True,
        "max_queue": 64,
        "compile_cache_dir": cfg.compile_cache_dir or os.path.join(
            tempfile.gettempdir(), "kftpu-prof-pods-xla-cache"),
    }
    state_dir = tempfile.mkdtemp(prefix="kftpu-pods-soak-")
    home = _Pool(block_size=cfg.block, capacity_blocks=1024)
    all_pods: list = []

    def _spawn(name: str, connect: bool):
        c = spawn_pod(name, spec, state_dir, home_pool=home,
                      connect=connect, transport=cfg.transport,
                      op_timeout_s=cfg.op_timeout_s)
        all_pods.append(c)
        return c

    t0 = time.perf_counter()
    try:
        # warm the whole pool CONCURRENTLY (the serve_pods trick): total
        # cold start is one worker's warmup, not standby's
        standby = [_spawn(f"pods-{i}", connect=False)
                   for i in range(cfg.standby + 1)]
        for c in standby:
            c.connect()
        in_day_spawns = [0]
        first = standby.pop()
        router = FleetRouter([("pods-base", first)], max_requeues=5)
        wire_pod_deaths(router)

        def engine_factory():
            if standby:
                c = standby.pop()
            else:
                in_day_spawns[0] += 1
                c = _spawn(f"pods-cold-{in_day_spawns[0]}", connect=True)
            attach_router_death(c, router)
            return c

        # admission shedding is last-resort (the run_prod_day
        # reasoning); the demand signal runs on queue math — two seated
        # rows per pod is the working set
        router.ttft_slo_s = 60.0
        router.retry_after_s = 0.01
        router.demand_tokens_per_replica = float(
            2 * (prompt_len + cfg.new_tokens))
        scaler = FleetScaler(
            router, engine_factory,
            ScalerConfig(
                min_replicas=cfg.min_replicas,
                max_replicas=cfg.max_replicas,
                scale_up_cooldown_evals=cfg.scale_up_cooldown_evals,
                scale_down_stable_evals=10 ** 6,  # no drains: this
                # day's transitions are the fault replacements
                hang_detect_evals=10 ** 6,        # heartbeat-only
                heartbeat_max_age_s=cfg.heartbeat_max_age_s))

        prompts = make_prompts(
            int(cfg.day_ticks * max(cfg.peak1_rate, cfg.peak2_rate)) + 16,
            seed=cfg.seed, vocab=vocab, prompt_len=cfg.prompt_body,
            shared_prefix=cfg.shared_prefix)
        kill_tick = int(cfg.kill_at * cfg.day_ticks)
        hang_tick = int(cfg.hang_at * cfg.day_ticks)
        part_tick = int(cfg.partition_at * cfg.day_ticks)

        handles: dict[int, object] = {}
        retries: list[tuple[int, int]] = []
        collected: set[int] = set()
        hung: set[str] = set()
        pod_base = pod_metrics_snapshot()
        n_submitted = 0
        shed_retries = 0
        kills_done = 0
        hang_done = False
        replicas_peak = 1
        ckpt: dict = {}
        hang_victim = None  # the SIGSTOPped pod's PodClient
        pvictim = None      # the partition victim's PodClient
        part = {"injected_tick": None, "victim": None, "death_tick": None,
                "adds_before": 0, "healed_tick": None,
                "healed_after_replacement": False,
                "worker_survived_partition": False,
                "late_events": 0, "late_tokens": 0, "refused": 0}

        def submit(idx: int, tick: int) -> None:
            nonlocal shed_retries
            try:
                handles[idx] = router.submit(
                    prompts[idx], max_new_tokens=cfg.new_tokens)
            except FleetOverloaded:
                shed_retries += 1
                retries.append((tick + 2, idx))

        def one_tick(tick: int, arrivals: int) -> None:
            nonlocal n_submitted, kills_done, hang_done, replicas_peak
            nonlocal pvictim, hang_victim
            # faults first (the drill order: the world breaks, then
            # serves). Victims must hold seated work — an idle victim
            # proves nothing — and enough healthy peers must remain.
            candidates = [r for r in router._admittable()
                          if r.name not in hung]
            if not kills_done and tick >= kill_tick \
                    and len(candidates) >= 2:
                busy = [r for r in candidates if r.depth() > 0]
                if busy:
                    victim = busy[rng.randrange(len(busy))]
                    kills_done += 1
                    os.kill(victim.engine.worker_pid, signal.SIGKILL)
            if not hang_done and tick >= hang_tick \
                    and len(candidates) >= 2:
                busy = [r for r in candidates if r.depth() > 0]
                if busy:
                    hung.add(busy[0].name)
                    hang_victim = busy[0].engine
                    hang_done = True
                    os.kill(hang_victim.worker_pid, signal.SIGSTOP)
            if pvictim is None and tick >= part_tick \
                    and len(candidates) >= 2:
                busy = [r for r in candidates if r.depth() > 0] \
                    or candidates
                rep = busy[rng.randrange(len(busy))]
                pvictim = rep.engine
                part["injected_tick"] = tick
                part["victim"] = rep.name
                part["adds_before"] = \
                    scaler.metrics["replicas_added_total"]
                pvictim.set_partitioned(True)
            # the partition HEALS only after the scaler has landed the
            # replacement — the split-brain window the fence closes
            if pvictim is not None and part["healed_tick"] is None \
                    and pvictim.dead \
                    and scaler.metrics["replicas_added_total"] \
                    > part["adds_before"]:
                if part["death_tick"] is None:
                    part["death_tick"] = tick
                part["worker_survived_partition"] = (
                    pvictim.proc is not None
                    and pvictim.proc.poll() is None)
                pvictim.set_partitioned(False)
                part["healed_tick"] = tick
                part["healed_after_replacement"] = True
            # a dead pod can't stay "hung" — whichever detector won
            # (heartbeat indictment or the submit timeout), the kill
            # ends the SIGSTOP episode. Checked on the CLIENT, not the
            # replica list: the scaler REMOVES indicted replicas.
            if hang_victim is not None and hang_victim.dead:
                hung.clear()
            for _ in range(arrivals):
                if n_submitted < len(prompts):
                    submit(n_submitted, tick)
                    n_submitted += 1
            for due, idx in list(retries):
                if due <= tick:
                    retries.remove((due, idx))
                    submit(idx, tick)
            for rep in list(router.replicas):
                if rep.alive and rep.name not in hung:
                    rep.engine.tick()
            for idx, h in list(handles.items()):
                if idx not in collected and h.done.is_set() \
                        and h.error is None:
                    collected.add(idx)
            scaler.evaluate()
            replicas_peak = max(replicas_peak,
                                len(router._admittable()))

        tick = 0
        for tick in range(cfg.day_ticks):
            if not ckpt and tick >= cfg.day_ticks // 2:
                ckpt = _torn_checkpoint()  # the mid-day torn save
            one_tick(tick, _arrivals(arrival_rate(tick, cfg), rng))
        # night drain: no arrivals; the backlog AND the in-flight fault
        # episodes (a pending heartbeat indictment, the partition heal)
        # must all settle — drain ticks are real wire round-trips, so
        # the heartbeat ceiling's wall-clock wait passes through here
        while tick < cfg.day_ticks + cfg.max_drain_ticks:
            tick += 1
            served = (not retries
                      and all(h.done.is_set() for h in handles.values())
                      and len(handles) + len(retries) >= n_submitted)
            settled = (not hung
                       and (pvictim is None
                            or part["healed_tick"] is not None))
            if served and settled:
                break
            one_tick(tick, 0)

        # ---- the heal probe: the fenced claim's worker is reachable
        # again — whatever its outbox still holds (events delivered but
        # never acked, plus one tick of fresh decode on rows the fleet
        # already re-served elsewhere) must be REFUSED, not applied
        if pvictim is not None and pvictim.fenced \
                and not pvictim.partitioned \
                and pvictim.proc is not None \
                and pvictim.proc.poll() is None:
            try:
                probe = pvictim.fenced_poll(timeout_s=5.0)
                part["late_events"] = probe["late_events"]
                part["late_tokens"] = probe["late_tokens"]
                part["refused"] = probe["refused"]
            except (PodWireError, RuntimeError, OSError) as e:
                part["probe_error"] = str(e)

        dropped = sum(
            1 for h in handles.values()
            if h.error is not None or not h.done.is_set()
        ) + len(retries)
        # single-copy proof: every completed stream carries EXACTLY the
        # requested tokens — a duplicate delivery that slipped the
        # ack/fence filters would overrun
        token_overruns = 0
        for idx in collected:
            if len(handles[idx].result(timeout=5.0)) != cfg.new_tokens:
                token_overruns += 1
        pod_now = pod_metrics_snapshot()
        m = scaler.metrics
        return {
            "seed": cfg.seed,
            "transport": cfg.transport,
            "ticks": tick + 1,
            "day_ticks": cfg.day_ticks,
            "wall_s": round(time.perf_counter() - t0, 3),
            "n_requests": n_submitted,
            "completed": len(collected),
            "dropped": dropped,
            "shed_retries": shed_retries,
            "token_overruns": token_overruns,
            "requeued": router.metrics["requests_requeued_total"],
            "resumed": router.metrics["requeues_resumed_total"],
            "kills_injected": kills_done,
            "hang_injected": hang_done,
            "hang_victim_dead": (hang_victim is not None
                                 and hang_victim.dead),
            "hangs_indicted": m["hangs_detected_total"],
            "partition": dict(part),
            "replicas_peak": replicas_peak,
            "in_day_spawns": in_day_spawns[0],
            "standby_left": len(standby),
            "ckpt": ckpt,
            "scaler": dict(m),
            "pod_metrics": {
                k: pod_now[k] - pod_base[k]
                for k in ("net_reconnects_total",
                          "net_partitions_injected_total",
                          "net_fenced_frames_total",
                          "net_duplicate_acks_refused_total",
                          "wire_retries_total",
                          "wire_retries_exhausted_total",
                          "kills_total")
            },
        }
    finally:
        # drill teardown, not the production path: partitioned and
        # disowned deaths deliberately leave their workers running
        # (that IS the split-brain hazard) — reap every survivor here
        for c in all_pods:
            try:
                c.stop()
            except RuntimeError:  # teardown best-effort
                pass
            c.partitioned = False
            c._disowned = False
            c._kill_process()
        shutil.rmtree(state_dir, ignore_errors=True)
