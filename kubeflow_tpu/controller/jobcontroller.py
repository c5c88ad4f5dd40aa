"""JobController — the generic gang reconciler for every job kind.

Reference parity (unverified cites, SURVEY.md §2.1): the common JobController
(pkg/controller.v1/common/{job_controller.go, job.go#ReconcileJobs,
pod.go#ReconcilePods, expectation.go}) that TFJob/PyTorchJob/... reconcilers
share. Level-triggered: watch events only enqueue keys; reconcile() computes
desired state from scratch each pass. The hot bookkeeping (work queue with
per-key backoff, expectations) is the native C++ core.

TPU gang semantics: a non-elastic SPMD gang cannot lose a process — any
worker failure triggers a whole-gang restart from checkpoint (bounded by
runPolicy.backoffLimit), not a single-pod restart (SURVEY.md §5.3).
"""

from __future__ import annotations

import os
import time

from kubeflow_tpu.api.common import (
    CleanPodPolicy,
    JobConditionType,
    ReplicaStatus,
    RestartPolicy,
    is_retryable_exit_code,
    utcnow as _now_ts,
)
from kubeflow_tpu.api.jobs import SUCCESS_REPLICA, JobKind, TrainJob, REPLICA_WORKER
from kubeflow_tpu.api.common import ObjectMeta
from kubeflow_tpu.controller.base import ControllerBase
from kubeflow_tpu.controller.envcontract import synthesize_env
from kubeflow_tpu.controller.fakecluster import (
    ConflictError,
    EventType,
    FakeCluster,
    Pod,
    PodGroup,
    PodPhase,
)
from kubeflow_tpu.controller.poddefault import apply_pod_defaults
from kubeflow_tpu.health import (
    ENV_HEARTBEAT_FILE,
    HUNG_POD_EXIT_CODE,
    DeadVerdict,
    LivenessConfig,
    LivenessDetector,
    heartbeat_path,
    job_heartbeat_dir,
)
from kubeflow_tpu.native import Expectations
from kubeflow_tpu.runtime.rendezvous import LocalResolver
from kubeflow_tpu.tracing import ENV_TRACE_DIR, ENV_TRACEPARENT, current_context
from kubeflow_tpu.utils.compile_cache import (
    ENV_JAX_CACHE_DIR,
    resolve_cache_dir,
)
from kubeflow_tpu.utils.envvars import ENV_COMPILE_CACHE_DIR, ENV_STATE_DIR
from kubeflow_tpu.utils.retry import BackoffPolicy, with_conflict_retry

JOB_NAME_LABEL = "kubeflow-tpu.org/job-name"
REPLICA_TYPE_LABEL = "kubeflow-tpu.org/replica-type"
REPLICA_INDEX_LABEL = "kubeflow-tpu.org/replica-index"
# World size the pod's env contract was synthesized for. SPMD cannot change
# world size live: any mismatch with the current spec forces a whole-gang
# re-mesh (elastic scale event), never an in-place patch.
WORLD_SIZE_LABEL = "kubeflow-tpu.org/world-size"

#: gang-restart requeue schedule (crashloop-backoff analogue): the Nth
#: restart of a job waits ~2x longer before its recreate pass, so a crash
#: storm cannot hot-loop pod churn. Jittered so simultaneous gang restarts
#: (e.g. after a node loss) don't stampede the scheduler in lockstep.
RESTART_BACKOFF = BackoffPolicy(base_s=0.05, max_s=2.0, jitter=0.5)


class JobController(ControllerBase):
    """Reconciles every job in the cluster. Start one per process."""

    # every job, but only pods this controller owns: unlabeled pod
    # storms (serving, notebooks, bare runs) cost it nothing. The keys
    # are also the kind filter (WATCH_SELECTORS subsumes WATCH_KINDS).
    WATCH_SELECTORS = {"jobs": None, "pods": {JOB_NAME_LABEL: None}}

    def __init__(
        self,
        cluster: FakeCluster,
        workers: int = 1,
        resync_period_s: float = 5.0,
        local_rewrite: bool = True,
        liveness: LivenessConfig | None = None,
        heartbeat_dir: str = "",
        compile_cache_dir: str = "",
    ):
        super().__init__(
            cluster, name="job", workers=workers, resync_period_s=resync_period_s
        )
        self.exp = Expectations(ttl_s=30.0)
        self.local_rewrite = local_rewrite
        # liveness layer (docs/health.md): lease/straggler failure detector
        # + where worker heartbeat files live; pods get the per-incarnation
        # path via the env contract (ENV_HEARTBEAT_FILE)
        self.liveness = LivenessDetector(liveness)
        self.heartbeat_dir = heartbeat_dir or os.path.join(
            os.environ.get(ENV_STATE_DIR, ".kubeflow_tpu"), "heartbeats"
        )
        # persistent XLA compile cache shared by EVERY incarnation of every
        # job (entries are content-keyed, so sharing one dir is safe): a
        # gang-restarted worker replays its train-step executables instead
        # of re-tracing+recompiling (utils/compile_cache.py, docs/perf.md)
        self.compile_cache_dir = resolve_cache_dir(
            compile_cache_dir, default=True)
        self._resolvers: dict[str, LocalResolver] = {}
        # prometheus-style counters (SURVEY.md §5.5)
        self.metrics.update({
            "jobs_created_total": 0,
            "jobs_succeeded_total": 0,
            "jobs_failed_total": 0,
            "jobs_restarted_total": 0,
            "jobs_remeshed_total": 0,
            "pods_created_total": 0,
            "pods_deleted_total": 0,
            # recovery observability (chaos drills assert on these): how many
            # jobs came back from >=1 restart, how many reconcile passes and
            # restarts that recovery consumed — the measurable shape of the
            # gang-restart-from-checkpoint contract
            "jobs_recovered_total": 0,
            "recovery_reconcile_passes_total": 0,
            "recovery_restarts_consumed_total": 0,
        })
        #: per-job reconcile passes spent since its first restart; folded
        #: into recovery_* counters when the job reaches Succeeded
        self._recovery_passes: dict[str, int] = {}

    # -------------------------------------------------------------- informer

    def observe_event(self, etype, kind: str, obj) -> None:
        if kind != "pods":
            return
        job_name = obj.metadata.labels.get(JOB_NAME_LABEL)
        if not job_name:
            return
        key = f"{obj.metadata.namespace}/{job_name}"
        if etype == EventType.ADDED:
            self.exp.creation_observed(key)
        elif etype == EventType.DELETED:
            self.exp.deletion_observed(key)

    def kind_filter(self, etype, kind: str, obj) -> str | None:
        if kind == "jobs":
            return self.cluster._key(obj)
        if kind == "pods":
            job_name = obj.metadata.labels.get(JOB_NAME_LABEL)
            if job_name:
                return f"{obj.metadata.namespace}/{job_name}"
        return None

    def resync_keys(self):
        return [self.cluster._key(j) for j in self.cluster.list("jobs")]

    # ------------------------------------------------------------- reconcile

    def reconcile(self, key: str) -> float | None:
        """One level-triggered pass. Returns optional requeue delay.

        Works on a deep snapshot of the job (read-copy-update): every
        status write goes through cluster.update, which rejects the write
        with ConflictError if a client mutated the spec mid-pass — the pass
        is then simply retried against fresh state. This is the same
        optimistic-concurrency discipline the reference controllers get from
        the k8s apiserver's resourceVersion.
        """
        job: TrainJob | None = self.cluster.get("jobs", key, copy_obj=True)
        if job is None:
            # GC analogue: reap anything that outlived (or raced) a deleted
            # job — a reconcile pass holding a pre-delete snapshot may create
            # pods after delete_job_cascade ran; their create events re-queue
            # this key and land here
            ns, name = key.split("/", 1)
            for p in self.cluster.list(
                "pods",
                lambda p: p.metadata.labels.get(JOB_NAME_LABEL) == name
                and p.metadata.namespace == ns,
            ):
                self.cluster.delete("pods", p.key)
            self.cluster.delete("podgroups", key)
            self.exp.delete(key)
            self.wq.forget(key)
            self._resolvers.pop(key, None)
            self._recovery_passes.pop(key, None)
            self._reap_heartbeats(ns, name)
            return None

        st = job.status
        if st.restart_count and not st.is_finished:
            # recovery in progress: every pass until the terminal condition
            # counts toward the job's convergence cost
            self._recovery_passes[key] = self._recovery_passes.get(key, 0) + 1
        entry_fp = _status_fingerprint(st)
        if not st.conditions:
            # persist-then-emit: a ConflictError before the persist must not
            # have incremented counters or recorded events (replay hazard)
            st.set_condition(JobConditionType.CREATED, "JobCreated")
            job = self.cluster.update("jobs", job)
            st = job.status
            entry_fp = _status_fingerprint(st)
            self.metrics["jobs_created_total"] += 1
            self.cluster.record_event("jobs", key, "JobCreated", "created")

        pods = self._owned_pods(job)

        # -- terminal state: cleanup, TTL
        if st.is_finished:
            return self._cleanup_finished(job, key, pods)

        # -- suspension (runPolicy.suspend)
        if job.spec.run_policy.suspend:
            if pods:
                self._delete_pods(key, pods)
            self._delete_podgroup(job)
            self._resolvers.pop(key, None)
            if not st.has_condition(JobConditionType.SUSPENDED):
                st.set_condition(JobConditionType.SUSPENDED, "JobSuspended")
                self.cluster.update("jobs", job)
            return None
        if st.has_condition(JobConditionType.SUSPENDED):
            st.set_condition(JobConditionType.RESTARTING, "JobResumed")
            self.cluster.update("jobs", job)

        # -- active deadline
        rp = job.spec.run_policy
        if rp.active_deadline_seconds and st.start_time:
            age = time.time() - _parse_ts(st.start_time)
            if age > rp.active_deadline_seconds:
                self._fail(job, key, pods, "DeadlineExceeded",
                           f"active for {age:.0f}s > {rp.active_deadline_seconds}s")
                return None

        # -- stale-cache guard: wait out pending create/deletes
        if not self.exp.satisfied(key):
            return 0.05

        # -- elastic re-mesh: pods built for a different world size must all
        # go; the gang restarts at the new size from checkpoint (slice-
        # granular scaling, SURVEY.md §2.2/§5.3)
        if pods and self._needs_remesh(job, pods):
            st.set_condition(
                JobConditionType.RESTARTING,
                "ElasticRemesh",
                f"re-meshing gang to {job.total_replicas()} replicas",
            )
            self.cluster.update("jobs", job)
            tracer = self.cluster.tracer  # single read: races stop_tracing
            if tracer is not None:
                tracer.event(
                    "job.elastic_remesh", key=key,
                    world_size=job.total_replicas(),
                )
            self._delete_pods(key, pods)
            self._delete_podgroup(job)
            self._resolvers.pop(key, None)
            self.metrics["jobs_remeshed_total"] += 1
            self.cluster.record_event(
                "jobs", key, "ElasticRemesh",
                f"scale -> {job.total_replicas()} replicas (gang re-mesh)",
            )
            return 0.05

        # -- liveness: a hung worker never reaches FAILED on its own — the
        # lease/straggler detector marks it, then the normal gang-restart
        # path below takes over on the requeued pass
        if self.liveness.config.enabled and self._check_liveness(job, key, pods):
            return 0.0

        # -- failure handling (gang semantics)
        failed = [p for p in pods if p.status.phase == PodPhase.FAILED]
        if failed:
            return self._handle_failures(job, key, pods, failed)

        # -- success detection
        if self._is_succeeded(job, pods):
            st.set_condition(JobConditionType.SUCCEEDED, "JobSucceeded")
            st.completion_time = _now_ts()
            self._update_replica_statuses(job, pods)
            self.cluster.update("jobs", job)
            self.metrics["jobs_succeeded_total"] += 1
            if st.restart_count:
                # the job survived faults: record what the recovery cost
                self.metrics["jobs_recovered_total"] += 1
                self.metrics["recovery_restarts_consumed_total"] += st.restart_count
                self.metrics["recovery_reconcile_passes_total"] += (
                    self._recovery_passes.pop(key, 0)
                )
            self.cluster.record_event("jobs", key, "JobSucceeded", "completed")
            return 0.0  # immediate cleanup pass

    # -- pod/podgroup creation
        created = self._reconcile_pods(job, key, pods)

        if st.start_time is None:
            st.start_time = _now_ts()
        running = [p for p in pods if p.status.phase == PodPhase.RUNNING]
        if running and len(running) == job.total_replicas():
            if not st.has_condition(JobConditionType.RUNNING):
                st.set_condition(JobConditionType.RUNNING, "JobRunning")
                self.cluster.record_event("jobs", key, "JobRunning", "all replicas running")
        self._update_replica_statuses(job, pods)
        # only publish a MODIFIED event on real change — an unconditional
        # update would re-enqueue this key via the informer and turn every
        # live job into a self-triggering hot reconcile loop
        if _status_fingerprint(st) != entry_fp:
            st.last_reconcile_time = _now_ts()
            self.cluster.update("jobs", job)
        if created:
            return 0.2
        # lease cadence: while MONITORED workers run (heartbeat file exists
        # — the same opt-in-by-behavior rule the detector applies), re-check
        # liveness a few times per timeout window instead of waiting out the
        # 5s resync, which would make small timeouts undetectable within
        # their own window. Never-beating legacy jobs stay on resync cadence.
        if self.liveness.config.enabled and any(
            p.status.phase == PodPhase.RUNNING
            and (hb := p.env.get(ENV_HEARTBEAT_FILE))
            and os.path.exists(hb)
            for p in pods
        ):
            return self.liveness.config.requeue_delay()
        return None

    # ---------------------------------------------------------- sub-steps

    def _needs_remesh(self, job: TrainJob, pods: list[Pod]) -> bool:
        """True when any live pod's env contract was synthesized for a world
        size other than the spec's current one. Pods predating the label are
        grandfathered; a fully-succeeded gang is left to success detection."""
        if all(p.status.phase == PodPhase.SUCCEEDED for p in pods):
            return False
        want = str(job.total_replicas())
        return any(
            p.metadata.labels.get(WORLD_SIZE_LABEL, want) != want for p in pods
        )

    def _owned_pods(self, job: TrainJob) -> list[Pod]:
        return self.cluster.list(
            "pods",
            lambda p: p.metadata.labels.get(JOB_NAME_LABEL) == job.metadata.name
            and p.metadata.namespace == job.metadata.namespace,
        )

    def _reconcile_pods(self, job: TrainJob, key: str, pods: list[Pod]) -> int:
        existing = {
            (
                p.metadata.labels.get(REPLICA_TYPE_LABEL),
                int(p.metadata.labels.get(REPLICA_INDEX_LABEL, -1)),
            )
            for p in pods
        }
        to_create: list[tuple[str, int]] = []
        for rtype, rs in job.spec.replica_specs.items():
            for i in range(rs.replicas):
                if (rtype, i) not in existing:
                    to_create.append((rtype, i))
        if not to_create:
            return 0

        tracer = self.cluster.tracer
        if tracer is None:
            return self._create_pods(job, key, to_create, None)
        with tracer.span("job.create_pods", key=key, count=len(to_create),
                         world_size=job.total_replicas(),
                         restart=job.status.restart_count):
            return self._create_pods(job, key, to_create, tracer)

    def _create_pods(self, job: TrainJob, key: str,
                     to_create: list[tuple[str, int]], tracer) -> int:
        self._ensure_podgroup(job)
        # The resolver must persist across passes within one gang incarnation
        # (pods created in different passes need identical port maps), but a
        # stale one — built for a different replica set, e.g. after a
        # suspend -> scale -> resume — would leave new hostnames unrewritten.
        resolver = self._resolvers.get(key)
        if resolver is None or _replica_signature(resolver.job) != _replica_signature(job):
            resolver = LocalResolver(job)
            self._resolvers[key] = resolver
            if tracer is not None:
                # the port-map build IS local rendezvous setup: every pod of
                # this incarnation connects through the endpoints fixed here
                tracer.event("job.rendezvous", key=key,
                             world_size=job.total_replicas())
        if job.kind == JobKind.MPI:
            self._materialize_hostfile(job, resolver)
        # trace context rides the env contract into the pods: workers join
        # the creating pass's trace and flush spans to the shared trace_dir
        trace_env: dict[str, str] = {}
        if tracer is not None and tracer.trace_dir:
            trace_env[ENV_TRACE_DIR] = tracer.trace_dir
            ctx = current_context()
            if ctx is not None:
                trace_env[ENV_TRACEPARENT] = ctx.to_header()
        self.exp.expect_creations(key, len(to_create))
        for rtype, i in to_create:
            env = synthesize_env(job, rtype, i)
            if self.local_rewrite:
                env = resolver.rewrite_env(env)
            env.update(trace_env)
            # liveness contract: a per-INCARNATION heartbeat path (the
            # restart count is baked into the name, so a restarted gang is
            # never judged by its predecessor's stale file). setdefault: a
            # user-supplied path wins, like the rest of the env contract.
            env.setdefault(ENV_HEARTBEAT_FILE, heartbeat_path(
                self.heartbeat_dir, job.metadata.namespace,
                job.metadata.name, job.replica_name(rtype, i),
                job.status.restart_count,
            ))
            # restart-warm compile contract: unlike the heartbeat path the
            # cache dir is NOT per-incarnation — surviving the restart is
            # the whole point (the restarted worker's warm_start hits it).
            # With JAX_COMPILATION_CACHE_DIR set the pod inherits the
            # directory with the rest of the environment.
            if not os.environ.get(ENV_JAX_CACHE_DIR):
                env.setdefault(ENV_COMPILE_CACHE_DIR, self.compile_cache_dir)
            c = job.spec.replica_specs[rtype].template.container
            # job-level labels (e.g. the experiment label) propagate to pods,
            # mirroring k8s template-label propagation
            labels = {**job.metadata.labels, **job.labels(rtype, i)}
            labels[WORLD_SIZE_LABEL] = str(job.total_replicas())
            pod = Pod(
                metadata=ObjectMeta(
                    name=job.replica_name(rtype, i),
                    namespace=job.metadata.namespace,
                    labels=labels,
                ),
                command=list(c.command) + list(c.args),
                env=env,
                working_dir=c.working_dir,
                scheduler_name=job.spec.replica_specs[rtype].template.scheduler_name,
                group_name=job.metadata.name,
            )
            apply_pod_defaults(self.cluster, pod)  # admission mutation
            self.cluster.create("pods", pod)
            self.metrics["pods_created_total"] += 1
        return len(to_create)

    def _materialize_hostfile(self, job: TrainJob, resolver) -> None:
        """Write the MPI hostfile to its per-job path before any pod starts —
        the ConfigMap-mount analogue (SURVEY.md §2.1 MPIJob row). Pods find
        it via OMPI_MCA_orte_default_hostfile (envcontract.mpi_env)."""
        from pathlib import Path

        from kubeflow_tpu.controller.envcontract import (
            mpi_hostfile,
            mpi_hostfile_path,
        )

        content = mpi_hostfile(job)
        if self.local_rewrite:
            content = resolver.rewrite_text(content)
        path = Path(mpi_hostfile_path(job))
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(content)

    def _ensure_podgroup(self, job: TrainJob) -> None:
        pg_key = f"{job.metadata.namespace}/{job.metadata.name}"
        if self.cluster.get("podgroups", pg_key) is not None:
            return
        sp = job.spec.run_policy.scheduling_policy
        # Clamp to the current total: a stale min_available above the post-
        # scale-down replica count would make the gang unsatisfiable forever.
        total = job.total_replicas()
        from kubeflow_tpu.controller.gang import resolve_priority, topology_chips

        topo = sp.slice_topology if sp else ""
        pg = PodGroup(
            metadata=ObjectMeta(
                name=job.metadata.name, namespace=job.metadata.namespace
            ),
            min_member=(min(sp.min_available, total) if sp and sp.min_available else total),
            queue=sp.queue if sp else "default",
            slice_topology=topo,
            # a multislice job reserves num_slices whole slices
            chips=topology_chips(topo) * max(job.spec.num_slices, 1),
            priority=resolve_priority(sp.priority_class if sp else ""),
        )
        self.cluster.create("podgroups", pg)

    def _check_liveness(self, job: TrainJob, key: str, pods: list[Pod]) -> int:
        """Run the lease/straggler detector over this gang and mark every
        verdict's pod FAILED. Returns how many pods were declared dead —
        the caller requeues immediately so the SAME gang-restart machinery
        that handles crashes handles hangs."""
        declared = 0
        for v in self.liveness.check(pods):
            if self._declare_pod_dead(key, v):
                declared += 1
        return declared

    def _declare_pod_dead(self, key: str, v: DeadVerdict) -> bool:
        """Conflict-retried, incarnation-guarded FAILED write for one
        liveness verdict, inside a health.* span whose context rides the
        pod object (CARRIER_ANNOTATION) — the gang restart parent-links to
        the detection, exactly like it links to a crash's exit span."""
        tracer = self.cluster.tracer
        span_name = (
            "health.lease_expired" if v.reason == "LivenessLeaseExpired"
            else "health.straggler"
        )

        def declare(carrier: str) -> bool:
            def attempt():
                cur = self.cluster.get("pods", v.key, copy_obj=True)
                if cur is None or cur.metadata.uid != v.uid:
                    return None
                if cur.status.phase in (PodPhase.SUCCEEDED, PodPhase.FAILED):
                    return None  # raced a real exit: its verdict wins
                cur.status.phase = PodPhase.FAILED
                cur.status.exit_code = HUNG_POD_EXIT_CODE
                cur.status.finish_time = time.time()
                cur.status.message = f"{v.reason}: {v.message}"
                if carrier:
                    from kubeflow_tpu.tracing import CARRIER_ANNOTATION

                    cur.metadata.annotations[CARRIER_ANNOTATION] = carrier
                return self.cluster.update("pods", cur)

            try:
                return with_conflict_retry(attempt) is not None
            except (ConflictError, KeyError):
                return False  # churned away mid-declaration; next pass re-checks

        if tracer is None:
            ok = declare("")
        else:
            with tracer.span(span_name, pod=v.key, uid=v.uid,
                             heartbeat_age_s=round(v.heartbeat_age_s, 3),
                             step=v.step) as sp:
                ctx = sp.context
                ok = declare(ctx.to_header() if ctx is not None else "")
                sp.set_attribute("declared", ok)
        if ok:
            self.liveness.bump("pods_declared_dead_total")
            self.liveness.bump(
                "leases_expired_total"
                if v.reason == "LivenessLeaseExpired"
                else "stragglers_declared_total")
            self.cluster.record_event(
                "pods", v.key, v.reason, v.message, type="Warning")
            self.cluster.record_event(
                "jobs", key, v.reason,
                f"{v.key}: {v.message}", type="Warning")
        return ok

    def _handle_failures(
        self, job: TrainJob, key: str, pods: list[Pod], failed: list[Pod]
    ) -> float | None:
        st = job.status
        rp = job.spec.run_policy
        # Elastic jobs budget restarts via ElasticPolicy.max_restarts
        # (torchelastic PET_MAX_RESTARTS parity); others via backoff_limit.
        limit = (
            rp.elastic_policy.max_restarts
            if rp.elastic_policy is not None
            else rp.backoff_limit
        )
        # Decide retryability from each failed pod's replica restart policy.
        retryable = True
        for p in failed:
            rtype = p.metadata.labels.get(REPLICA_TYPE_LABEL, REPLICA_WORKER)
            rs = job.spec.replica_specs.get(rtype)
            policy = rs.restart_policy if rs else RestartPolicy.NEVER
            if policy == RestartPolicy.NEVER:
                retryable = False
            elif policy == RestartPolicy.EXIT_CODE:
                if not is_retryable_exit_code(p.status.exit_code or 1):
                    retryable = False
        if not retryable or st.restart_count >= limit:
            reason = (
                "BackoffLimitExceeded"
                if retryable
                else "NonRetryableExit"
            )
            self._fail(job, key, pods,
                       reason,
                       f"{len(failed)} replica(s) failed "
                       f"(restarts={st.restart_count}/{limit})")
            return None
        # gang restart: tear down ALL pods, restart from checkpoint.
        # Persist the incremented count BEFORE deleting pods: a conflict here
        # retries cleanly, whereas deleting first and conflicting after would
        # lose the increment and grant a free restart.
        st.restart_count += 1
        st.set_condition(
            JobConditionType.RESTARTING,
            "GangRestart",
            f"restart {st.restart_count}/{limit}",
        )
        self.cluster.update("jobs", job)
        tracer = self.cluster.tracer  # single read: races stop_tracing,
        # and an exception here would retry a pass that ALREADY committed
        # the restart_count increment (double-charging backoff_limit)
        if tracer is not None:
            from kubeflow_tpu.tracing import CARRIER_ANNOTATION, SpanContext

            # parent = the failed pod's exit span (carried on the object),
            # NOT this pass's trigger: multiple watch events coalesce into
            # one pass, but the restart is causally the failure's child
            cause = next(
                (SpanContext.from_header(
                    p.metadata.annotations.get(CARRIER_ANNOTATION, ""))
                 for p in failed
                 if p.metadata.annotations.get(CARRIER_ANNOTATION)),
                None,
            )
            attrs = dict(key=key, restart=st.restart_count, limit=limit,
                         failed=len(failed))
            if cause is not None:
                tracer.event("job.gang_restart", parent=cause, **attrs)
            else:
                tracer.event("job.gang_restart", **attrs)
        self._delete_pods(key, pods)
        self._delete_podgroup(job)
        self.metrics["jobs_restarted_total"] += 1
        self.cluster.record_event(
            "jobs", key, "GangRestart",
            f"worker failure -> gang restart {st.restart_count}",
            type="Warning",
        )
        # Nth restart waits exponentially longer before the recreate pass
        # (shared jittered-backoff policy — no more fixed 50ms hot requeue)
        return RESTART_BACKOFF.delay_for(st.restart_count - 1)

    def _is_succeeded(self, job: TrainJob, pods: list[Pod]) -> bool:
        by = {
            (
                p.metadata.labels.get(REPLICA_TYPE_LABEL),
                int(p.metadata.labels.get(REPLICA_INDEX_LABEL, -1)),
            ): p
            for p in pods
        }
        def all_workers_succeeded() -> bool:
            workers = job.spec.replica_specs.get(REPLICA_WORKER)
            n = workers.replicas if workers else 0
            if n == 0:
                return False
            return all(
                (w := by.get((REPLICA_WORKER, i))) is not None
                and w.status.phase == PodPhase.SUCCEEDED
                for i in range(n)
            )

        if job.kind == JobKind.JAX:
            return all_workers_succeeded()
        success_rtype = SUCCESS_REPLICA[job.kind]
        rs = job.spec.replica_specs.get(success_rtype)
        if rs is None or rs.replicas == 0:
            # present-but-empty decider spec falls back exactly like
            # LocalRunner (runtime/local.py): worker-0 decides — a
            # 0-replica chief never gets a pod, so waiting on it would
            # leave the job unfinishable
            success_rtype = REPLICA_WORKER
        p = by.get((success_rtype, 0))
        decider_done = p is not None and p.status.phase == PodPhase.SUCCEEDED
        if job.spec.success_policy != "AllWorkers":
            return decider_done
        # TFJob successPolicy=AllWorkers: the decider AND every worker
        # replica must complete (passive PS-style replicas excluded)
        return decider_done and all_workers_succeeded()

    def _cleanup_finished(
        self, job: TrainJob, key: str, pods: list[Pod]
    ) -> float | None:
        policy = job.spec.run_policy.clean_pod_policy
        if policy == CleanPodPolicy.ALL:
            doomed = pods
        elif policy == CleanPodPolicy.RUNNING:
            doomed = [
                p for p in pods
                if p.status.phase in (PodPhase.RUNNING, PodPhase.PENDING)
            ]
        else:
            doomed = []
        if doomed:
            self._delete_pods(key, doomed)
        self._delete_podgroup(job)
        ttl = job.spec.run_policy.ttl_seconds_after_finished
        if ttl is not None and job.status.completion_time:
            age = time.time() - _parse_ts(job.status.completion_time)
            if age >= ttl:
                self.cluster.delete("jobs", key)
                self._reap_heartbeats(
                    job.metadata.namespace, job.metadata.name)
                return None
            return ttl - age
        return None

    def _reap_heartbeats(self, namespace: str, name: str) -> None:
        """Remove a deleted job's heartbeat subtree — incarnation files are
        small but unbounded over crashloops, and a stale file must never
        greet a later same-named job (the pid gate would filter it, but the
        disk growth would not filter itself)."""
        import shutil

        shutil.rmtree(
            job_heartbeat_dir(self.heartbeat_dir, namespace, name),
            ignore_errors=True,
        )

    def _fail(
        self, job: TrainJob, key: str, pods: list[Pod], reason: str, msg: str
    ) -> None:
        job.status.set_condition(JobConditionType.FAILED, reason, msg)
        job.status.completion_time = _now_ts()
        self._update_replica_statuses(job, pods)
        self.cluster.update("jobs", job)
        self.metrics["jobs_failed_total"] += 1
        self._recovery_passes.pop(key, None)  # recovery lost, not converged
        self.cluster.record_event("jobs", key, reason, msg, type="Warning")

    def _delete_pods(self, key: str, pods: list[Pod]) -> None:
        if not pods:
            return
        self.exp.expect_deletions(key, len(pods))
        for p in pods:
            self.cluster.delete("pods", p.key)
            self.metrics["pods_deleted_total"] += 1

    def _delete_podgroup(self, job: TrainJob) -> None:
        self.cluster.delete(
            "podgroups", f"{job.metadata.namespace}/{job.metadata.name}"
        )

    def _update_replica_statuses(self, job: TrainJob, pods: list[Pod]) -> None:
        stats: dict[str, ReplicaStatus] = {}
        for rtype in job.spec.replica_specs:
            stats[rtype] = ReplicaStatus(
                selector=f"{JOB_NAME_LABEL}={job.metadata.name},"
                f"{REPLICA_TYPE_LABEL}={rtype}"
            )
        for p in pods:
            rtype = p.metadata.labels.get(REPLICA_TYPE_LABEL)
            if rtype not in stats:
                continue
            ph = p.status.phase
            if ph in (PodPhase.RUNNING, PodPhase.PENDING):
                stats[rtype].active += 1
            elif ph == PodPhase.SUCCEEDED:
                stats[rtype].succeeded += 1
            elif ph == PodPhase.FAILED:
                stats[rtype].failed += 1
        job.status.replica_statuses = stats


def delete_job_cascade(cluster: FakeCluster, name: str, namespace: str = "default") -> None:
    """Tear down a job and everything it owns (pods, podgroup) — the shared
    delete path for the SDK client, sweep engine, and anything else that
    removes jobs out-of-band."""
    key = f"{namespace}/{name}"
    for p in cluster.list(
        "pods",
        lambda p: p.metadata.labels.get(JOB_NAME_LABEL) == name
        and p.metadata.namespace == namespace,
    ):
        cluster.delete("pods", p.key)
    cluster.delete("podgroups", key)
    cluster.delete("jobs", key)


def _replica_signature(job: TrainJob) -> tuple:
    """Identity of a job's rendezvous-relevant shape: if this changes, the
    old incarnation's resolver/port map no longer covers the replica set."""
    return (
        tuple(sorted((rt, rs.replicas) for rt, rs in job.spec.replica_specs.items())),
        job.spec.coordinator_port,
    )


def _status_fingerprint(st) -> tuple:
    """Hashable snapshot of the reconcile-relevant status (excludes
    last_reconcile_time, which must never itself trigger an update)."""
    return (
        tuple((c.type, c.status, c.reason, c.message) for c in st.conditions),
        tuple(
            (rt, rs.active, rs.succeeded, rs.failed)
            for rt, rs in sorted(st.replica_statuses.items())
        ),
        st.start_time,
        st.completion_time,
        st.restart_count,
    )


def _parse_ts(ts: str) -> float:
    import datetime

    return datetime.datetime.strptime(ts, "%Y-%m-%dT%H:%M:%SZ").replace(
        tzinfo=datetime.timezone.utc
    ).timestamp()
