"""TrainingClient + Platform — the Python SDK surface (layer L5).

Reference parity: training-operator sdk/python/kubeflow/training
TrainingClient.{create_job, get_job, get_job_logs, wait_for_job_conditions,
delete_job} (unverified, SURVEY.md §2.1). Here the 'cluster' is in-process:
Platform wires the fake-cluster store, gang scheduler, pod runtime, and the
job controller into one unit with real subprocess workloads.
"""

from __future__ import annotations

from pathlib import Path

from kubeflow_tpu.api.common import JobConditionType
from kubeflow_tpu.api.jobs import REPLICA_WORKER, TrainJob, apply_elastic_scale
from kubeflow_tpu.api.validation import validate_job
from kubeflow_tpu.controller.fakecluster import FakeCluster
from kubeflow_tpu.controller.gang import GangScheduler
from kubeflow_tpu.controller.jobcontroller import JobController, delete_job_cascade
from kubeflow_tpu.controller.profile import check_job_admission
from kubeflow_tpu.controller.podruntime import PodRuntime
from kubeflow_tpu.utils.retry import BackoffPolicy, poll_until


class Platform:
    """One in-process 'cluster': apiserver + scheduler + kubelet + operators
    (job controller + experiment controller)."""

    def __init__(
        self,
        log_dir: str = ".kubeflow_tpu/pod-logs",
        capacity_chips: int = 8,
        controller_workers: int = 2,
        liveness=None,
    ):
        """liveness: optional health.LivenessConfig tuning the hang/straggler
        failure detector (docs/health.md); None = defaults."""
        from kubeflow_tpu.controller.devservers import (
            NotebookController,
            PVCViewerController,
        )
        from kubeflow_tpu.controller.autoscaler import TrainingAutoscaler
        from kubeflow_tpu.controller.profile import ProfileController
        from kubeflow_tpu.controller.tensorboard import TensorboardController
        from kubeflow_tpu.pipelines.crd import PipelineRunController
        from kubeflow_tpu.serving.controller import InferenceServiceController
        from kubeflow_tpu.sweep.controller import ExperimentController

        self.cluster = FakeCluster()
        self.cluster.capacity_chips = capacity_chips
        self.pod_runtime = PodRuntime(self.cluster, log_dir=log_dir)
        # ONE chip inventory for both workload classes (docs/scheduler.md):
        # the gang scheduler routes admission through it, registered
        # fleets claim replica chips from it, and /debug/sched +
        # kftpu_sched_* read it
        import os as _os

        from kubeflow_tpu.scheduler.chipsched import (
            DEFAULT_RETRY_AFTER_S,
            ChipScheduler,
        )
        from kubeflow_tpu.utils.envvars import (
            ENV_SCHED_CHIPS_PER_SLICE,
            ENV_SCHED_RETRY_AFTER_S,
        )

        self.chip_scheduler = ChipScheduler(
            capacity_fn=lambda: self.cluster.capacity_chips,
            tracer_fn=lambda: self.cluster.tracer,
            chips_per_slice=int(
                _os.environ.get(ENV_SCHED_CHIPS_PER_SLICE, "8")),
            retry_after_s=float(
                _os.environ.get(ENV_SCHED_RETRY_AFTER_S,
                                str(DEFAULT_RETRY_AFTER_S))))
        self.gang_scheduler = GangScheduler(
            self.cluster, chipsched=self.chip_scheduler)
        self.controller = JobController(
            self.cluster, workers=controller_workers, liveness=liveness,
            # heartbeats live next to the pod logs, so test platforms rooted
            # in a tmp dir keep their liveness state there too
            heartbeat_dir=str(Path(log_dir).parent / "heartbeats"),
        )
        self.experiment_controller = ExperimentController(
            self.cluster, log_reader=self._read_pod_log,
            observation_db=str(Path(log_dir).parent / "sweep-observations.db"),
        )
        self.isvc_controller = InferenceServiceController(
            self.cluster,
            model_cache_dir=str(Path(log_dir).parent / "model-cache"),
            platform=self,
        )
        self.profile_controller = ProfileController(self.cluster)
        self.tensorboard_controller = TensorboardController(self.cluster)
        self.notebook_controller = NotebookController(self.cluster)
        self.pvcviewer_controller = PVCViewerController(self.cluster)
        self.pipelinerun_controller = PipelineRunController(
            self.cluster,
            work_dir=str(Path(log_dir).parent / "pipelines"),
            platform=self,
        )
        self.autoscaler = TrainingAutoscaler(self.cluster, self.gang_scheduler)
        self.metrics_server = None  # started on demand
        self.activator = None  # started on demand (serverless front door)
        self.tracer = None  # enabled on demand (start_tracing)
        #: SLO burn-rate monitor over a bounded TSDB (start_slo):
        #: /debug/slo, the `slo` CLI, and kftpu_slo_* read these
        self.slo_monitor = None
        self.slo_tsdb = None
        self._slo_sampler = None
        #: serving fleets (serving/fleet): "ns/name" -> FleetRouter.
        #: register_fleet() adds one; /metrics aggregates kftpu_fleet_*
        #: over this registry and the activator's queue-depth-aware pick
        #: reads fleet_load_view (callable -> {endpoint url: load})
        self.fleet_routers: dict[str, object] = {}
        self.fleet_load_view = None
        # single registry: observability iterates THIS, so a new controller
        # can never silently fall out of /metrics
        self.controllers = {
            "job": self.controller,
            "experiment": self.experiment_controller,
            "isvc": self.isvc_controller,
            "pipelinerun": self.pipelinerun_controller,
            "profile": self.profile_controller,
            "tensorboard": self.tensorboard_controller,
            "notebook": self.notebook_controller,
            "pvcviewer": self.pvcviewer_controller,
            "autoscaler": self.autoscaler,
        }
        self._started = False

    def start_metrics_server(self, port: int = 0) -> str:
        """Expose GET /metrics (Prometheus text) + /healthz; returns the URL."""
        from kubeflow_tpu.observability import MetricsServer

        if self.metrics_server is None:
            self.metrics_server = MetricsServer(self, port=port).start()
        return self.metrics_server.url

    def start_tracing(self, capacity: int = 4096, trace_dir: str = ""):
        """Arm span tracing + the flight recorder (docs/observability.md).

        Every layer (apiserver, controllers, gang scheduler, pod runtime,
        activator, chaos engine) starts emitting spans into one bounded
        in-memory ring; span counters join /metrics as kftpu_trace_*.
        `trace_dir`, when set, also rides the pod env contract so worker
        processes flush their own spans there for merged export
        (tracing.export_merged_trace). Returns the Tracer."""
        from kubeflow_tpu.tracing import Tracer

        if self.tracer is None:
            self.tracer = Tracer(capacity=capacity, trace_dir=trace_dir,
                                 service="platform")
        self.tracer.armed = True
        self.cluster.tracer = self.tracer  # (re-)arm every layer
        # fleets registered BEFORE tracing was enabled join now —
        # register_fleet/start_tracing must compose in either order
        for router in self.fleet_routers.values():
            self._wire_fleet(router)
        return self.tracer

    def stop_tracing(self) -> None:
        """Freeze span EMISSION everywhere — detach from the cluster AND
        disarm the tracer itself (the apiserver/activator reach it via
        `platform.tracer`, so detaching alone would let HTTP spans keep
        evicting the captured ring). The recorded ring stays on
        `self.tracer`: /debug/trace, /metrics kftpu_trace_*, and snapshot
        exports keep serving exactly what was captured; reading a trace
        never mutates it. start_tracing() re-arms the same recorder."""
        self.cluster.tracer = None
        if self.tracer is not None:
            self.tracer.armed = False

    def register_fleet(self, key: str, router, load_view=None):
        """Attach a serving fleet (serving/fleet.FleetRouter) under
        "namespace/name": its kftpu_fleet_* counters join /metrics, its
        demand signal becomes autoscaler input, and `load_view` (callable
        -> {endpoint url: load}) makes the activator's ready-endpoint
        pick queue-depth-aware (docs/serving.md). When tracing / the SLO
        monitor are live, the router and its engines inherit the
        platform tracer (per-request spans, docs/slo.md) and TSDB
        (decode-tick/TTFT series) unless they brought their own."""
        self.fleet_routers[key] = router
        if load_view is not None:
            self.fleet_load_view = load_view
        self._wire_fleet(router)
        return router

    def _wire_fleet(self, router) -> None:
        # the router owns engine wiring (FleetRouter.wire_monitoring →
        # _wire_engine, the same path add_replica uses), so the platform
        # cannot drift from the fleet's own attach rules
        wire = getattr(router, "wire_monitoring", None)
        if wire is not None:
            wire(tracer=self.tracer, tsdb=self.slo_tsdb)

    def start_slo(self, configs=None, sample_interval_s: float | None = None,
                  capacity: int | None = None):
        """Arm the SLO burn-rate monitor (docs/slo.md): a bounded
        ring-buffer TSDB, a background sampling tick over the existing
        kftpu_* families, and declarative objectives evaluated as
        multi-window burn rates. Registered fleets' engines start
        feeding decode-tick/TTFT series. Surfaces: GET /debug/slo,
        `python -m kubeflow_tpu slo`, kftpu_slo_* in /metrics, and
        FleetRouter.demand_replicas_burn. Returns the SLOMonitor."""
        import os as _os

        from kubeflow_tpu.monitoring import (
            MetricSampler,
            SLOMonitor,
            TimeSeriesStore,
        )
        from kubeflow_tpu.utils.envvars import (
            ENV_SLO_CAPACITY,
            ENV_SLO_TICK_S,
        )

        if self.slo_monitor is not None:
            # a second start_slo re-arms the sampler (the stop_slo
            # freeze contract) — it must not silently DROP overrides
            # the caller believes took effect
            if configs is not None or sample_interval_s is not None \
                    or capacity is not None:
                raise ValueError(
                    "start_slo: the SLO monitor is already running — "
                    "configs/interval/capacity cannot be changed in "
                    "place (series and burn state would be torn); "
                    "build a new Platform to reconfigure")
        else:
            if capacity is None:
                capacity = int(_os.environ.get(ENV_SLO_CAPACITY, "512"))
            if sample_interval_s is None:
                sample_interval_s = float(
                    _os.environ.get(ENV_SLO_TICK_S, "1.0"))
            self.slo_tsdb = TimeSeriesStore(capacity_per_series=capacity)
            self.slo_monitor = SLOMonitor(self.slo_tsdb, configs)
            for router in self.fleet_routers.values():
                self._wire_fleet(router)
            self._slo_sampler = MetricSampler(
                self, self.slo_tsdb, interval_s=sample_interval_s,
                monitor=self.slo_monitor)
        self.slo_tsdb.armed = True
        self._slo_sampler.start()  # re-arms after stop_slo too
        return self.slo_monitor

    def stop_slo(self) -> None:
        """Freeze the monitoring plane: stop the sampling tick AND
        disarm the TSDB, so hot-path producers (the engines' decode-
        tick/TTFT hooks, which keep their reference) degrade to no-ops
        — reading a captured incident window can never evict it (the
        stop_tracing freeze contract applied to samples). The monitor
        and its recorded series stay readable; start_slo() re-arms the
        same store."""
        if self._slo_sampler is not None:
            self._slo_sampler.stop()
        if self.slo_tsdb is not None:
            self.slo_tsdb.armed = False

    def start_activator(self, port: int = 0,
                        host: str = "127.0.0.1") -> str:
        """Serverless front door for InferenceServices (Knative activator
        analogue): stable per-service URLs, canary traffic split, and
        request-holding scale-from-zero. Returns the URL."""
        from kubeflow_tpu.serving.activator import Activator

        if self.activator is None:
            self.activator = Activator(self, port=port, host=host).start()
        return self.activator.url

    def _read_pod_log(self, pod_name: str, namespace: str = "default") -> str:
        path = self.pod_runtime.log_path(pod_name, namespace)
        try:
            return path.read_text()
        except OSError:
            return ""

    def start(self) -> "Platform":
        if not self._started:
            # runtime first, then every registered controller — the registry
            # is the single list (observability iterates the same one)
            self.pod_runtime.start()
            self.gang_scheduler.start()
            for ctrl in self.controllers.values():
                ctrl.start()
            self._started = True
        return self

    def stop(self) -> None:
        self.stop_slo()
        if self.activator is not None:
            self.activator.stop()
            self.activator = None
        for router in self.fleet_routers.values():
            router.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None
        for ctrl in reversed(list(self.controllers.values())):
            ctrl.stop()
        self.gang_scheduler.stop()
        self.pod_runtime.stop()
        self._started = False

    def __enter__(self) -> "Platform":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


class TrainingClient:
    """SDK client; drives jobs through the platform's object store."""

    def __init__(self, platform: Platform):
        self.platform = platform
        self.cluster = platform.cluster

    # ------------------------------------------------------------------ CRUD

    def create_job(self, job: TrainJob) -> TrainJob:
        validate_job(job)
        check_job_admission(self.cluster, job)  # namespace quota (Profile)
        return self.cluster.create("jobs", job)

    def get_job(self, name: str, namespace: str = "default") -> TrainJob | None:
        return self.cluster.get("jobs", f"{namespace}/{name}")

    def list_jobs(self, namespace: str | None = None) -> list[TrainJob]:
        return self.cluster.list(
            "jobs",
            None if namespace is None else (lambda j: j.metadata.namespace == namespace),
        )

    def delete_job(self, name: str, namespace: str = "default") -> None:
        delete_job_cascade(self.cluster, name, namespace)

    def scale_job(
        self, name: str, replicas: int, namespace: str = "default"
    ) -> TrainJob:
        """Elastic scale: set the worker count of a running JAXJob.

        TPU elasticity is slice-granular (SURVEY.md §2.2): the new size must
        keep whole slices, and the change lands as a whole-gang re-mesh
        (coordinator restart + resume from checkpoint), never a live resize.
        Requires an ElasticPolicy and min_replicas <= replicas <= max_replicas.
        """
        return self._read_modify_write(
            name, namespace, lambda job: apply_elastic_scale(job, replicas)
        )

    def _read_modify_write(
        self, name: str, namespace: str, mutate, retries: int = 10
    ) -> TrainJob:
        return self.cluster.read_modify_write(
            "jobs", f"{namespace}/{name}", mutate, retries=retries,
            backoff_s=0.01,
        )

    def suspend_job(self, name: str, namespace: str = "default") -> None:
        def mutate(job: TrainJob) -> None:
            job.spec.run_policy.suspend = True

        self._read_modify_write(name, namespace, mutate)

    def resume_job(self, name: str, namespace: str = "default") -> None:
        def mutate(job: TrainJob) -> None:
            job.spec.run_policy.suspend = False

        self._read_modify_write(name, namespace, mutate)

    # ---------------------------------------------------------------- status

    def train(
        self,
        name: str,
        *,
        family: str = "mnist",
        num_workers: int = 1,
        namespace: str = "default",
        device: str = "auto",
        args: list[str] | None = None,
        elastic: tuple[int, int] | None = None,
        wait: bool = True,
        timeout_s: float = 3600.0,
    ) -> dict[str, float]:
        """High-level train() convenience (the reference SDK's
        TrainingClient.train HF-fine-tune helper, SURVEY.md §2.1 — here over
        the in-tree model families instead of HF images): build a JAXJob
        around `python -m examples.<family>`, submit it, wait, and return
        the final metrics parsed from worker-0's log.

        family: mnist | resnet | bert | bert_pretrain | gpt | afmoe
        args:   extra example flags (e.g. ["--steps=200", "--bf16"])
        elastic: (min_replicas, max_replicas) to attach an ElasticPolicy
        """
        import sys as _sys

        from kubeflow_tpu.api.jobs import build_example_train_job

        job = build_example_train_job(
            name, family=family, num_workers=num_workers, namespace=namespace,
            device=device, args=args, elastic=elastic,
            # in-process: same environment, so the concrete interpreter and
            # the repo root are correct here
            interpreter=_sys.executable,
            working_dir=str(Path(__file__).resolve().parents[1]),
        )
        self.create_job(job)
        if not wait:
            return {}
        done = self.wait_for_job_conditions(
            name, namespace, timeout_s=timeout_s
        )
        if not done.status.is_succeeded:
            failed = next(
                (c for c in done.status.conditions
                 if c.type == JobConditionType.FAILED), None
            )
            detail = f": {failed.message}" if failed and failed.message else ""
            raise RuntimeError(f"train job {name} failed{detail}")
        # the sweep collector's parser: importing the train package would
        # pull jax into the control-plane process
        from kubeflow_tpu.sweep.collector import final_metrics_from_log

        return final_metrics_from_log(self.get_job_logs(name, namespace))

    def wait_for_job_conditions(
        self,
        name: str,
        namespace: str = "default",
        expected: tuple[JobConditionType, ...] = (
            JobConditionType.SUCCEEDED,
            JobConditionType.FAILED,
        ),
        timeout_s: float = 120.0,
        poll_s: float = 0.1,
    ) -> TrainJob:
        def reached() -> TrainJob | None:
            job = self.get_job(name, namespace)
            if job is not None:
                for cond in expected:
                    if job.status.has_condition(cond):
                        return job
            return None

        try:
            return poll_until(
                reached,
                timeout_s=timeout_s,
                policy=BackoffPolicy(base_s=0.02, max_s=poll_s, jitter=0.5),
            )
        except TimeoutError:
            raise TimeoutError(
                f"job {namespace}/{name} did not reach {expected} "
                f"in {timeout_s}s"
            ) from None

    def get_job_logs(
        self, name: str, namespace: str = "default", rtype: str = "worker", index: int = 0
    ) -> str:
        path = self.platform.pod_runtime.log_path(f"{name}-{rtype}-{index}", namespace)
        return Path(path).read_text() if Path(path).exists() else ""

    def get_events(self, name: str, namespace: str = "default") -> list:
        return self.cluster.events_for(f"{namespace}/{name}")
