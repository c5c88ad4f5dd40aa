"""Observability — Prometheus-text /metrics endpoint for the platform.

Reference parity (unverified cites, SURVEY.md §5.5): every operator exposes
a controller-runtime Prometheus endpoint (workqueue depth, reconcile
totals, custom counters). Here one endpoint aggregates all in-process
controllers, the object store, and the pod runtime.

Format is the Prometheus text exposition format, served by stdlib
http.server — scrape `GET /metrics`.
"""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from kubeflow_tpu.utils.prom import Exposition, observe

#: preempt-to-resume histogram buckets (seconds): a resume rides a
#: diurnal trough, so the range runs sub-second (unit drills) to
#: minutes (a gang parked across a whole serving peak)
SCHED_RESUME_BUCKETS = (0.1, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0,
                        120.0, 300.0)


def render_metrics(platform) -> str:
    """Aggregate platform state into Prometheus text format."""
    # one builder, one HELP/TYPE declaration path (utils/prom.Exposition):
    # repeated TYPE lines for a family are exposition-format violations,
    # and multi-sample families below (per-kind gauges, per-controller
    # quantiles) would hand-roll that bug without the de-dup
    exp = Exposition()
    counter, gauge = exp.counter, exp.gauge

    worker_depths: list[tuple[str, list[int]]] = []
    for cname, ctrl in platform.controllers.items():
        for mname, v in sorted(ctrl.metrics.items()):
            counter(f"kftpu_{cname}_{mname}", v)
        gauge(
            f"kftpu_{cname}_workqueue_depth", len(ctrl.wq),
            help_="pending reconcile keys",
        )
        worker_depths.append((cname, ctrl.wq.depths()))
        # reconcile-duration histogram (controller-runtime parity):
        # cumulative le buckets + _sum/_count in exposition format
        counts, total = ctrl.latency_snapshot()
        exp.histogram(
            f"kftpu_{cname}_reconcile_duration_seconds",
            ctrl.latency_buckets, counts, total,
        )

    # keyed-pool shape (docs/architecture.md "Control-plane scaling"): one
    # depth sample per worker queue — a skewed profile means hot keys are
    # hashing onto one worker. Emitted AFTER the per-controller loop so
    # the family's samples form one contiguous exposition group.
    for cname, depths in worker_depths:
        for i, depth in enumerate(depths):
            gauge(
                "kftpu_cplane_worker_queue_depth", depth,
                help_="pending keys per keyed-pool worker queue",
                labels=f'{{controller="{cname}",worker="{i}"}}',
            )

    # control-plane scale-out signals (docs/architecture.md): shard-lock
    # contention on the sharded store, and the status-write coalescing
    # effectiveness of the kubelet layer's group commit
    counter(
        "kftpu_cplane_shard_lock_waits_total",
        sum(platform.cluster.lock_wait_counts().values()),
    )
    runtime_sb = getattr(getattr(platform, "pod_runtime", None),
                         "status_writes", None)
    if runtime_sb is not None:
        for mname, v in sorted(runtime_sb.metrics.items()):
            counter(f"kftpu_cplane_status_{mname}", v)

    # serving fleet (kubeflow_tpu/serving/fleet, docs/serving.md):
    # admission/shed/requeue accounting, queue+latency autoscaler signals,
    # and the prefix-reuse ledger, aggregated over every registered
    # router. Families render ZERO-valued on a fleetless platform so the
    # golden exposition pins a stable surface (KFTPU-METRIC contract).
    routers = list(getattr(platform, "fleet_routers", {}).values())
    snaps = [r.snapshot() for r in routers]

    def fleet_sum(field_):
        return sum(s.get(field_, 0) for s in snaps)

    for fam, field_, help_ in (
        ("kftpu_fleet_requests_admitted_total", "requests_admitted_total",
         "requests past the SLO admission gate"),
        ("kftpu_fleet_requests_shed_total", "requests_shed_total",
         "requests shed with 503 + Retry-After by admission control"),
        ("kftpu_fleet_requests_requeued_total", "requests_requeued_total",
         "in-flight requests requeued to a surviving replica"),
        ("kftpu_fleet_requeues_resumed_total", "requeues_resumed_total",
         "requeues that resumed from the surviving paged-KV chain"),
        ("kftpu_fleet_requeue_resumed_tokens_total",
         "requeue_resumed_tokens_total",
         "tokens salvaged from surviving KV chains instead of re-decoded"),
        ("kftpu_fleet_prefill_handoffs_total", "prefill_handoffs_total",
         "chains handed from the prefill tier to a decode replica"),
        ("kftpu_fleet_requests_completed_total", "requests_completed_total",
         None),
        ("kftpu_fleet_requests_failed_total", "requests_failed_total",
         None),
        ("kftpu_fleet_replica_kills_total", "replica_kills_total", None),
    ):
        counter(fam, fleet_sum(field_), help_=help_)
    prefill = reused = 0
    for r in routers:
        for rep in r.replicas:
            prefill += rep.engine.prefill_tokens_total
            reused += rep.engine.prefill_tokens_reused
    counter("kftpu_fleet_prefill_tokens_total", prefill,
            help_="prompt tokens the engines actually computed")
    counter("kftpu_fleet_prefill_tokens_reused_total", reused,
            help_="prompt tokens seeded from the paged-KV prefix pool")
    # paged-KV pool health (fleet/pagedkv.py): the pinned working set and
    # the eviction/COW churn, deduped across routers sharing one pool —
    # previously only the prefill reuse ledger was surfaced
    pools: dict[int, object] = {}
    for r in routers:
        for rep in r.replicas:
            p = getattr(rep.engine, "paged_kv", None)
            if p is not None:
                pools[id(p)] = p
    gauge("kftpu_fleet_kv_blocks_in_use",
          sum(p.blocks_in_use() for p in pools.values()),
          help_="paged-KV blocks pinned by live sequences (the "
                "block-budgeted admission working set)")
    counter("kftpu_fleet_kv_evictions_total",
            sum(p.metrics["blocks_evicted_total"] for p in pools.values()),
            help_="unreferenced paged-KV blocks evicted (LRU, leaf-first)")
    counter("kftpu_fleet_kv_cow_copies_total",
            sum(p.metrics["cow_copies_total"] for p in pools.values()),
            help_="copy-on-write block copies on shared-chain divergence")
    for fam, field_, help_ in (
        ("kftpu_fleet_queue_depth", "queue_depth",
         "queued + in-flight requests across live replicas"),
        ("kftpu_fleet_pending_tokens", "pending_tokens",
         "token backlog (queued prompts + in-flight budgets)"),
        ("kftpu_fleet_replicas_alive", "replicas_alive", None),
        ("kftpu_fleet_demand_replicas", "demand_replicas",
         "autoscaler demand signal from the queue/latency view"),
    ):
        gauge(fam, fleet_sum(field_), help_=help_)
    for q, field_ in (("0.5", "ttft_p50_s"), ("0.99", "ttft_p99_s")):
        gauge("kftpu_fleet_ttft_seconds",
              max((s.get(field_, 0.0) for s in snaps), default=0.0),
              help_="time-to-first-token quantiles over the fleet's "
                    "sample window",
              labels=f'{{quantile="{q}"}}')

    # fleet autoscaler (serving/fleet/scaler.py, docs/autoscaling.md):
    # the closed loop's decision ledger — scale events, graceful-drain
    # vs polite-kill outcomes, scale-to-zero/wake cycles, hang
    # detections — aggregated over every registered fleet's scaler and
    # ZERO-valued on a scalerless platform (KFTPU-METRIC contract)
    scalers = [s for s in (getattr(r, "scaler", None) for r in routers)
               if s is not None]

    def scaler_sum(field_):
        return sum(s.metrics.get(field_, 0) for s in scalers)

    for fam, field_, help_ in (
        ("kftpu_scaler_evaluations_total", "evaluations_total",
         "scaling-loop passes over the demand signal"),
        ("kftpu_scaler_frozen_evaluations_total",
         "frozen_evaluations_total",
         "passes that evaluated but acted on nothing (the "
         "scaler_freeze chaos mode)"),
        ("kftpu_scaler_scale_ups_total", "scale_ups_total", None),
        ("kftpu_scaler_scale_downs_total", "scale_downs_total", None),
        ("kftpu_scaler_replicas_added_total", "replicas_added_total",
         None),
        ("kftpu_scaler_replicas_removed_total",
         "replicas_removed_total", None),
        ("kftpu_scaler_drains_completed_total", "drains_completed_total",
         "scale-down drains that emptied gracefully"),
        ("kftpu_scaler_drain_kills_total", "drain_kills_total",
         "drains finished as a polite kill after the grace window "
         "(requests chain-resumed onto survivors)"),
        ("kftpu_scaler_hangs_detected_total", "hangs_detected_total",
         "replicas declared hung (work held, engine not advancing)"),
        ("kftpu_scaler_scale_to_zero_total", "scale_to_zero_total",
         None),
        ("kftpu_scaler_scale_from_zero_total", "scale_from_zero_total",
         "wake-on-arrival cold starts out of the scaled-to-zero state"),
    ):
        counter(fam, scaler_sum(field_), help_=help_)
    gauge("kftpu_scaler_target_replicas",
          sum(s.target_replicas for s in scalers),
          help_="the demand signal's last clamped target")
    gauge("kftpu_scaler_frozen",
          sum(1 for s in scalers if s.frozen),
          help_="scalers currently frozen (chaos mode)")
    gauge("kftpu_scaler_cold_start_seconds",
          max((s.cold_start_ewma_s for s in scalers), default=0.0),
          help_="EWMA of observed replica cold-start durations")

    # chip scheduler (kubeflow_tpu/scheduler, docs/scheduler.md): the
    # shared inventory BOTH workload classes claim through — the grant/
    # deny/preemption/quota decision counters, the free-chip view, the
    # per-tenant fair-share accounting, and the preempt-to-resume
    # latency histogram. One consistent snapshot (ChipScheduler holds
    # its mutex once), ZERO-valued on a schedulerless platform and with
    # the per-tenant families DECLARED even before any tenant has
    # claimed (KFTPU-METRIC contract: the golden pins a stable
    # surface).
    sched = getattr(platform, "chip_scheduler", None)
    sched_snap = sched.snapshot() if sched is not None else {}
    sched_counts = sched_snap.get("metrics", {})
    for fam, field_, help_ in (
        ("kftpu_sched_grants_total", "grants_total",
         "chip claims admitted (gangs and serving replicas alike)"),
        ("kftpu_sched_denies_total", "denies_total",
         "chip claims refused (frozen / quota / capacity) with a "
         "Retry-After hint and a traced sched.deny"),
        ("kftpu_sched_preemptions_total", "preemptions_total",
         "lower-priority gang claims evicted for a claim that could "
         "not otherwise fit (each emits a sched.preempt span)"),
        ("kftpu_sched_quota_borrows_total", "quota_borrows_total",
         "grants that ran a tenant past its fair-share entitlement "
         "on idle (reclaimable) chips"),
        ("kftpu_sched_quota_reclaims_total", "quota_reclaims_total",
         "preemptions that reclaimed borrowed chips for an "
         "under-entitlement tenant"),
        ("kftpu_sched_resumes_total", "resumes_total",
         "preempted gangs that re-claimed their chips (closes a "
         "preempt-to-resume latency sample)"),
        ("kftpu_sched_reclaimed_chips_total", "reclaimed_chips_total",
         "chips returned to the pool by releases and evictions"),
        ("kftpu_sched_double_count_avoided_chips_total",
         "double_count_avoided_chips_total",
         "pending-gang chips the combined demand_and_free snapshot "
         "kept out of demand because the ledger already holds them "
         "(the autoscaler paired-read race, counted)"),
    ):
        counter(fam, sched_counts.get(field_, 0), help_=help_)
    gauge("kftpu_sched_free_chips", sched_snap.get("free_chips", 0),
          help_="unclaimed chips in the shared ledger")
    gauge("kftpu_sched_used_chips", sched_snap.get("used_chips", 0))
    gauge("kftpu_sched_frozen",
          1 if sched_snap.get("frozen") else 0,
          help_="1 while the ledger refuses all claims (the "
                "sched_freeze chaos mode)")
    gauge("kftpu_sched_quota_enforced",
          1 if sched_snap.get("quota_enforced") else 0,
          help_="1 once set_shares armed fair-share tenant quotas")
    tenant_fams = (
        ("kftpu_sched_tenant_share", "share",
         "armed fair-share weight per tenant"),
        ("kftpu_sched_tenant_entitled_chips", "entitled_chips",
         "weighted max-min chip entitlement under the armed shares"),
        ("kftpu_sched_tenant_used_chips", "used_chips",
         "chips each tenant's claims currently hold"),
        ("kftpu_sched_tenant_borrowed_chips", "borrowed_chips",
         "held chips past the entitlement (reclaim-eligible)"),
    )
    for fam, _, help_ in tenant_fams:
        exp.declare(fam, "gauge", help_)
    # zero-valued-stable (the kftpu_slo_* pattern): an idle ledger still
    # exposes the two default claim tenants, so the families are pinned
    # in the golden exposition with samples, not just HELP/TYPE
    tenants = sched_snap.get("tenants", {}) or {
        t: {"share": 0.0, "entitled_chips": 0, "used_chips": 0,
            "borrowed_chips": 0}
        for t in ("default", "serving")
    }
    for t, info in sorted(tenants.items()):
        for fam, field_, _ in tenant_fams:
            gauge(fam, info[field_], labels=f'{{tenant="{t}"}}')
    # preempt-to-resume: eviction to re-grant wall time — the latency a
    # batch gang actually waited for serving to hand the chips back
    resume_counts = [0] * (len(SCHED_RESUME_BUCKETS) + 1)
    resume_total = 0.0
    for s in sched_snap.get("preempt_to_resume_s", ()):
        observe(SCHED_RESUME_BUCKETS, resume_counts, s)
        resume_total += s
    exp.histogram(
        "kftpu_sched_preempt_to_resume_seconds", SCHED_RESUME_BUCKETS,
        resume_counts, resume_total,
        help_="preempted-gang eviction-to-resume wall time")

    # pod-backed serving replicas (serving/fleet/podclient.py): the
    # cross-process tier's lifecycle and wire-health ledger — spawns,
    # kills (graceful and SIGKILL alike), retried/reset wire ops,
    # deadline rejections, and the KV-handoff volume crossing the
    # process boundary. Module-global like the ckpt-verify counters
    # (pods outlive any one router) and ZERO-valued with no pod tier
    # (KFTPU-METRIC contract).
    from kubeflow_tpu.serving.fleet.podclient import (
        pod_heartbeat_age_max_s,
        pod_metrics_snapshot,
    )

    pod_help = {
        "spawns_total": "pod worker processes launched (spawn_pod)",
        "kills_total": "pod workers terminated — graceful kills, wire "
                       "deaths, and real SIGKILLs alike",
        "wire_retries_total": "pod wire ops retried under the backoff "
                              "policy (resets, torn frames, 503 "
                              "backpressure)",
        "wire_retries_exhausted_total": "pod wire calls that exhausted "
                                        "the retry policy — the give-up "
                                        "that escalates to pod death, "
                                        "visible here instead of only "
                                        "as an unexplained kill",
        "wire_resets_total": "pod wire connections torn down by fault "
                             "injection (chaos WireFault)",
        "net_reconnects_total": "pod wire redials AFTER an established "
                                "connection — each one exercised the "
                                "rid-dedup + cumulative-ack replay "
                                "contract",
        "net_fenced_frames_total": "frames refused by the epoch fence, "
                                   "both directions: worker 410s to "
                                   "stale clients and client refusals "
                                   "of a fenced pod's late acks/tokens",
        "net_duplicate_acks_refused_total": "redelivered outbox events "
                                            "dropped by the cumulative-"
                                            "ack id filter (lost acks, "
                                            "replayed ticks) — never "
                                            "double-pushed",
        "net_partitions_injected_total": "network partitions opened "
                                         "against pod hosts (chaos "
                                         "NetFault windows and drill-"
                                         "driven set_partitioned)",
        "deadline_rejects_total": "pod calls refused 504 — the "
                                  "propagated deadline was spent on "
                                  "arrival",
        "handoff_bytes_total": "serialized paged-KV chain bytes that "
                               "crossed a pod process boundary",
    }
    for mname, v in sorted(pod_metrics_snapshot().items()):
        counter(f"kftpu_pod_{mname}", v, help_=pod_help.get(mname))
    gauge("kftpu_pod_heartbeat_age_seconds", pod_heartbeat_age_max_s(),
          help_="oldest live pod worker heartbeat age (the hang "
                "watch's SIGSTOP signal); 0 with no live pods")

    # protocol model checker (kubeflow_tpu/analysis/protocheck,
    # docs/analysis.md "Protocol model checking"): sweep accounting —
    # nonzero only after `make modelcheck` / run_modelcheck() ran in
    # this process
    from kubeflow_tpu.analysis.protocheck import protocheck_metrics_snapshot
    protocheck_help = {
        "models_checked_total": "protocol models swept by the "
                                "bounded-exhaustive explorer "
                                "(wire/kv/ledger x runs)",
        "states_explored_total": "distinct protocol states visited "
                                 "across all modelcheck sweeps",
        "violations_total": "invariant violations found (0 at HEAD; "
                            "nonzero means a counterexample schedule "
                            "was rendered)",
    }
    for mname, v in sorted(protocheck_metrics_snapshot().items()):
        counter(f"kftpu_protocheck_{mname}", v,
                help_=protocheck_help.get(mname))

    # SLO burn-rate monitor (kubeflow_tpu/monitoring, docs/slo.md):
    # evaluation/alert counters, per-objective burn-rate and alert
    # gauges, and the TSDB's volume/loss accounting. A platform without
    # start_slo() renders the DEFAULT objective set zero-valued so the
    # golden exposition pins a stable surface (KFTPU-METRIC contract).
    from kubeflow_tpu.monitoring import SLOMonitor, default_slos

    monitor = getattr(platform, "slo_monitor", None)
    if monitor is not None:
        slo_states = monitor.describe()
        slo_counts = monitor.metrics
        tsdb_stats = monitor.tsdb.stats()
    else:
        slo_states = [
            {"name": c.name, "fired": False,
             "burn_rates": {SLOMonitor._wkey(w): 0.0
                            for w, _ in c.windows}}
            for c in default_slos()
        ]
        slo_counts = {"evaluations_total": 0, "alerts_fired_total": 0}
        tsdb_stats = {"series": 0, "samples_total": 0,
                      "samples_dropped_total": 0,
                      "series_rejected_total": 0}
    counter("kftpu_slo_evaluations_total",
            slo_counts["evaluations_total"],
            help_="SLO monitor evaluation passes")
    counter("kftpu_slo_alerts_fired_total",
            slo_counts["alerts_fired_total"],
            help_="alerts fired across evaluations (docs/slo.md)")
    counter("kftpu_slo_samples_total", tsdb_stats["samples_total"],
            help_="samples recorded into the monitoring TSDB")
    counter("kftpu_slo_samples_dropped_total",
            tsdb_stats["samples_dropped_total"],
            help_="samples evicted from full series rings (raise "
                  "KFTPU_SLO_CAPACITY)")
    counter("kftpu_slo_series_rejected_total",
            tsdb_stats["series_rejected_total"],
            help_="new series refused past the bounded series set")
    gauge("kftpu_slo_series", tsdb_stats["series"],
          help_="live series in the monitoring TSDB")
    for st in slo_states:
        gauge("kftpu_slo_alert_active", 1 if st["fired"] else 0,
              help_="1 while the objective's multi-window burn alert "
                    "fires",
              labels=f'{{slo="{st["name"]}"}}')
    for st in slo_states:
        for wkey in sorted(st["burn_rates"], key=float, reverse=True):
            gauge("kftpu_slo_burn_rate", st["burn_rates"][wkey],
                  help_="error-budget burn rate per objective window "
                        "(1.0 = burning exactly the budget)",
                  labels=f'{{slo="{st["name"]}",window_s="{wkey}"}}')

    # training hot path (utils/compile_cache.py + train/data.AsyncLoader,
    # docs/perf.md "MFU hunt"): restart-warm compile reuse and the async
    # host-loader ledger. Both registries are process-global — trainers
    # are constructed ad hoc by jobs, drills, and benches — and families
    # render ZERO-valued on an idle platform so the golden exposition
    # pins a stable surface (KFTPU-METRIC contract).
    from kubeflow_tpu.train.data import loader_metrics_snapshot
    from kubeflow_tpu.utils.compile_cache import compile_counts

    for mname, v in sorted(compile_counts().items()):
        counter(f"kftpu_train_compile_{mname}",
                v if isinstance(v, int) else f"{v:.6f}")
    loader_snap = loader_metrics_snapshot()
    live_loaders = loader_snap.pop("live_loaders")
    for mname, v in sorted(loader_snap.items()):
        counter(f"kftpu_train_loader_{mname}",
                v if isinstance(v, int) else f"{v:.6f}")
    gauge(
        "kftpu_train_loader_live", live_loaders,
        help_="AsyncLoader producer threads still running "
              "(a wedged loader thread shows here)",
    )
    # liveness layer (kubeflow_tpu/health.py): lease expiries and straggler
    # declarations counted apart from crash deaths, plus per-incarnation
    # heartbeat age straight from the kubelet layer's side table
    liveness = getattr(getattr(platform, "controller", None), "liveness", None)
    if liveness is not None:
        for mname, v in sorted(liveness.metrics.items()):
            counter(f"kftpu_health_{mname}", v)
    runtime = getattr(platform, "pod_runtime", None)
    if runtime is not None:
        ages = runtime.heartbeat_ages()
        for (key, uid), age in sorted(ages.items()):
            gauge("kftpu_health_heartbeat_age_seconds", f"{age:.3f}",
                  labels=f'{{pod="{key}",uid="{uid}"}}')

    # checkpoint integrity verification (train/checkpoint.py): the registry
    # is process-global — checkpointers are constructed ad hoc by trainers,
    # drills, and pipelines, and all of them report here
    from kubeflow_tpu.health import ckpt_verify_snapshot

    for mname, v in sorted(ckpt_verify_snapshot().items()):
        counter(f"kftpu_ckpt_verify_{mname}", v)

    # chaos-drill injection counters (kubeflow_tpu/chaos.py): exported so
    # recovery behavior is measurable against what was actually injected
    chaos = getattr(platform, "chaos", None)
    if chaos is not None:
        for mname, v in sorted(chaos.metrics.items()):
            counter(f"kftpu_chaos_{mname}", v)
        gauge(
            "kftpu_chaos_plan_seed", chaos.plan.seed,
            help_="seed of the armed fault plan (reproduce with this)",
        )

    # span tracing (kubeflow_tpu/tracing): volume + loss accounting for the
    # flight recorder, so a ring sized too small for the span rate is
    # visible as kftpu_trace_spans_dropped_total
    tracer = getattr(platform, "tracer", None)
    if tracer is not None and tracer.recorder is not None:
        for mname, v in sorted(tracer.metrics.items()):
            counter(f"kftpu_trace_{mname}", v)
        gauge(
            "kftpu_trace_recorder_spans", len(tracer.recorder),
            help_="completed spans currently held in the flight recorder",
        )
        gauge(
            "kftpu_trace_recorder_capacity", tracer.recorder.capacity,
            help_="flight recorder ring size",
        )

        # profiling analytics (kubeflow_tpu/profiling, docs/profiling.md):
        # the same breakdown /debug/profile and `kftpu profile` serve,
        # derived from the recorder snapshot (+ worker flushes in
        # trace_dir) at scrape time — scrapers get step-time histograms
        # and goodput without a second instrumentation path
        from kubeflow_tpu.profiling import (
            PROF_BUCKETS,
            REQUEST_PHASES,
            control_plane_stats,
            goodput as prof_goodput,
            platform_spans,
            request_breakdown,
            step_breakdown,
        )

        spans, _dropped = platform_spans(platform)
        steps = step_breakdown(spans)
        # serving request breakdown (the step-breakdown analogue over
        # `request` root spans — profiling/analytics.request_breakdown):
        # per-request wall histogram + sum-exact phase totals, the same
        # numbers /debug/slo and the `slo` CLI serve (docs/slo.md)
        reqs = request_breakdown(spans)
        req_counts = [0] * (len(PROF_BUCKETS) + 1)
        req_total = 0.0
        for rq in reqs:
            observe(PROF_BUCKETS, req_counts, rq["wall"])
            req_total += rq["wall"]
        exp.histogram(
            "kftpu_request_wall_seconds", PROF_BUCKETS, req_counts,
            req_total,
            help_="serving request wall time (submit to done, requeues "
                  "included) from request root spans")
        for phase in REQUEST_PHASES:
            counter(
                "kftpu_request_phase_seconds_total",
                f"{sum(rq[phase] for rq in reqs):.6f}",
                help_="per-phase serving request time; phases sum "
                      "exactly to request wall (docs/slo.md)",
                labels=f'{{phase="{phase}"}}')
        counter("kftpu_request_requeues_total",
                sum(max(rq["attempts"] - 1, 0) for rq in reqs),
                help_="extra dispatch attempts across traced requests "
                      "(the replica-kill requeue chain)")
        for fam, phase, help_ in (
            ("kftpu_prof_step_time_seconds", "wall",
             "per-step cycle wall time (end of previous step to end of "
             "this one)"),
            ("kftpu_prof_data_load_seconds", "data_load",
             "host-side input fetch time charged to each step cycle"),
            ("kftpu_prof_stall_seconds", "stall",
             "per-step unattributed remainder (wall - accounted phases)"),
        ):
            counts = [0] * (len(PROF_BUCKETS) + 1)
            total = 0.0
            for st in steps:
                observe(PROF_BUCKETS, counts, st[phase])
                total += st[phase]
            exp.histogram(fam, PROF_BUCKETS, counts, total, help_=help_)
        gauge(
            "kftpu_prof_goodput_ratio",
            prof_goodput(spans, steps)["goodput"],
            help_="productive step time over the trace window "
                  "(docs/profiling.md)",
        )
        # stable label set: every registered controller gets its quantile
        # samples (0 until reconcile spans exist), so dashboards and the
        # golden pin see the same series on a fresh and a busy platform
        rec_stats = control_plane_stats(spans)["reconcile"]
        for ctrl in sorted(set(platform.controllers) | set(rec_stats)):
            st = rec_stats.get(ctrl)
            for q, key in (("0.5", "p50_s"), ("0.99", "p99_s")):
                gauge(
                    "kftpu_prof_reconcile_latency_seconds",
                    st[key] if st else 0.0,
                    help_="reconcile-duration quantiles per controller, "
                          "derived from reconcile spans",
                    labels=f'{{controller="{ctrl}",quantile="{q}"}}',
                )

    cluster = platform.cluster
    for kind in cluster.KINDS:
        gauge("kftpu_objects", len(cluster.list(kind)),
              labels=f'{{kind="{kind}"}}')
    gauge("kftpu_events_total", len(cluster.events))
    gauge(
        "kftpu_capacity_chips", cluster.capacity_chips,
        help_="schedulable chips in the gang scheduler",
    )
    return exp.text()


class MetricsServer:
    """GET /metrics and GET /healthz on a local port."""

    def __init__(self, platform, port: int = 0, host: str = "127.0.0.1"):
        self.platform = platform
        self.host = host
        self.port = port
        self._httpd: ThreadingHTTPServer | None = None

    def start(self) -> "MetricsServer":
        plat = self.platform

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass  # metrics scrapes are not worth log noise

            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path == "/metrics":
                    body = render_metrics(plat).encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path == "/healthz":
                    body, ctype = b"ok\n", "text/plain"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()
        return self

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
