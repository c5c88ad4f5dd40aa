"""Job kinds — the TrainJob family.

Reference parity: training-operator pkg/apis/kubeflow.org/v1/{tfjob_types.go,
pytorchjob_types.go, mpijob_types.go} (unverified, SURVEY.md §2.1).

The flagship kind is JAXJob: a gang of identical SPMD worker processes that
rendezvous through `jax.distributed.initialize`. TFJob/PyTorchJob/MPIJob specs
are kept for migration parity — their env contracts are synthesized exactly
(controller/envcontract.py), so a user moving off the reference finds the same
knobs, but the recommended path is JAXJob.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from kubeflow_tpu.api.common import (
    JobStatus,
    ObjectMeta,
    ReplicaSpec,
    RunPolicy,
)

# Canonical replica type names (label values under
# training.kubeflow.org/replica-type in the reference).
REPLICA_WORKER = "worker"
REPLICA_CHIEF = "chief"
REPLICA_PS = "ps"
REPLICA_MASTER = "master"
REPLICA_LAUNCHER = "launcher"
REPLICA_EVALUATOR = "evaluator"
REPLICA_SCHEDULER = "scheduler"  # MXNet
REPLICA_SERVER = "server"        # MXNet


class JobKind(str, enum.Enum):
    JAX = "JAXJob"
    TF = "TFJob"
    PYTORCH = "PyTorchJob"
    MPI = "MPIJob"
    XGBOOST = "XGBoostJob"
    PADDLE = "PaddleJob"
    MXNET = "MXJob"


# Default rendezvous ports, matching the reference's per-framework defaults.
DEFAULT_PORTS = {
    JobKind.JAX: 1234,       # jax.distributed coordinator
    JobKind.TF: 2222,        # tfjob default port
    JobKind.PYTORCH: 23456,  # MASTER_PORT default in pytorch envvar.go
    JobKind.MPI: 22,
    JobKind.XGBOOST: 9991,
    JobKind.PADDLE: 36543,
    JobKind.MXNET: 9091,     # mxnet scheduler (DMLC_PS_ROOT_PORT)
}

# Which replica type's completion decides job success, per kind
# (tfjob: chief, else worker-0 / master / launcher).
SUCCESS_REPLICA = {
    JobKind.JAX: REPLICA_WORKER,
    JobKind.TF: REPLICA_CHIEF,      # falls back to worker if no chief
    JobKind.PYTORCH: REPLICA_MASTER,
    JobKind.MPI: REPLICA_LAUNCHER,
    JobKind.XGBOOST: REPLICA_MASTER,
    JobKind.PADDLE: REPLICA_MASTER,
    JobKind.MXNET: REPLICA_WORKER,  # scheduler/server idle; workers decide
}


@dataclass
class JAXJobSpec:
    replica_specs: dict[str, ReplicaSpec] = field(default_factory=dict)
    run_policy: RunPolicy = field(default_factory=RunPolicy)
    # Port the worker-0 coordination service listens on.
    coordinator_port: int = DEFAULT_PORTS[JobKind.JAX]
    # Number of slices for multislice (DCN/megascale) jobs; 1 = single slice.
    num_slices: int = 1
    # First-class profiling toggle (SURVEY.md §5.1): when set, workers get
    # KFTPU_PROFILE_DIR and the in-tree trainer writes a jax.profiler
    # (perfetto-compatible) trace per process under it.
    profile_dir: str = ""
    # TFJob successPolicy parity: "" = the kind's success replica decides
    # (chief/master/launcher/worker-0; JAX jobs always need all workers);
    # "AllWorkers" = every worker AND the success replica must complete
    # (passive replicas — PS/scheduler/server — stay excluded: they never
    # exit and are reaped on success)
    success_policy: str = ""


@dataclass
class TrainJob:
    """Base class for every training job kind."""

    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    spec: JAXJobSpec = field(default_factory=JAXJobSpec)
    status: JobStatus = field(default_factory=JobStatus)

    kind: JobKind = JobKind.JAX
    api_version: str = "kubeflow-tpu.org/v1"

    # -- naming conventions (pkg/core/pod.go GenGeneralName analogues) --

    @property
    def name(self) -> str:
        return self.metadata.name

    @property
    def namespace(self) -> str:
        return self.metadata.namespace

    def replica_name(self, rtype: str, index: int) -> str:
        return f"{self.metadata.name}-{rtype}-{index}"

    def replica_hostname(self, rtype: str, index: int) -> str:
        """Stable DNS-style name for a replica — the headless-Service contract.
        In the fake cluster this resolves via the rendezvous registry."""
        return f"{self.replica_name(rtype, index)}.{self.metadata.name}.{self.metadata.namespace}"

    def total_replicas(self) -> int:
        return sum(rs.replicas for rs in self.spec.replica_specs.values())

    def labels(self, rtype: str | None = None, index: int | None = None) -> dict[str, str]:
        """Label conventions, mirroring training.kubeflow.org/* labels."""
        out = {
            "kubeflow-tpu.org/job-name": self.metadata.name,
            "kubeflow-tpu.org/job-kind": self.kind.value,
        }
        if rtype is not None:
            out["kubeflow-tpu.org/replica-type"] = rtype
        if index is not None:
            out["kubeflow-tpu.org/replica-index"] = str(index)
        return out


@dataclass
class JAXJob(TrainJob):
    kind: JobKind = JobKind.JAX


@dataclass
class TFJob(TrainJob):
    kind: JobKind = JobKind.TF


@dataclass
class PyTorchJob(TrainJob):
    kind: JobKind = JobKind.PYTORCH


@dataclass
class MPIJob(TrainJob):
    kind: JobKind = JobKind.MPI


@dataclass
class XGBoostJob(TrainJob):
    kind: JobKind = JobKind.XGBOOST


@dataclass
class PaddleJob(TrainJob):
    kind: JobKind = JobKind.PADDLE


@dataclass
class MXJob(TrainJob):
    kind: JobKind = JobKind.MXNET


# stamped by apply_elastic_scale on every scale; read by the capacity
# autoscaler as its stabilization-window anchor
LAST_SCALE_ANNOTATION = "kubeflow-tpu.org/autoscale-last-scale"


def apply_elastic_scale(job: TrainJob, replicas: int) -> None:
    """Mutate `job` in place to `replicas` workers (elastic scale).

    TPU elasticity is slice-granular (SURVEY.md §2.2): the new size must keep
    whole slices, and the change lands as a whole-gang re-mesh (coordinator
    restart + resume from checkpoint), never a live resize. Requires an
    ElasticPolicy and min_replicas <= replicas <= max_replicas. Shared by
    TrainingClient.scale_job and the capacity autoscaler (the reference's
    pytorch HPA analogue) so both enforce identical invariants.
    """
    if job.status.is_finished:
        raise ValueError(f"job {job.name} already finished; cannot scale")
    ep = job.spec.run_policy.elastic_policy
    if ep is None:
        raise ValueError(f"job {job.name} has no elasticPolicy; cannot scale")
    if not (ep.min_replicas <= replicas <= ep.max_replicas):
        raise ValueError(
            f"replicas {replicas} outside elastic range "
            f"[{ep.min_replicas}, {ep.max_replicas}]"
        )
    workers = job.spec.replica_specs.get(REPLICA_WORKER)
    if workers is None:
        raise ValueError(f"job {job.name} has no worker replicas; cannot scale")
    old_total = job.total_replicas()
    if job.spec.num_slices > 1:
        per_slice = workers.replicas // job.spec.num_slices
        if replicas % per_slice:
            raise ValueError(
                f"replicas {replicas} not a multiple of per-slice worker "
                f"count {per_slice} (scale by whole slices)"
            )
        job.spec.num_slices = replicas // per_slice
    workers.replicas = replicas
    # every scale (user or autoscaler) opens a stabilization window: the
    # capacity autoscaler (controller/autoscaler.py) must not revert a manual
    # scale inside its cooldown, so the stamp lives in this shared path
    import time as _time

    job.metadata.annotations[LAST_SCALE_ANNOTATION] = str(_time.time())
    sp = job.spec.run_policy.scheduling_policy
    if sp is not None and sp.min_available is not None:
        # full-gang intent follows the new size; an explicit partial
        # min stays, clamped to remain satisfiable
        if sp.min_available >= old_total:
            sp.min_available = job.total_replicas()
        else:
            sp.min_available = min(sp.min_available, job.total_replicas())


TRAIN_FAMILIES = ("mnist", "resnet", "bert", "bert_pretrain", "gpt", "afmoe")


def build_example_train_job(
    name: str,
    *,
    family: str,
    num_workers: int = 1,
    namespace: str = "default",
    device: str = "auto",
    args: list | None = None,
    interpreter: str = "python",
    working_dir: str = "",
    elastic: tuple | None = None,
) -> "JAXJob":
    """The ONE builder behind TrainingClient.train() and RemoteClient.train():
    a JAXJob running `<interpreter> -m examples.<family>`. In-process clients
    pass sys.executable + the repo root; remote clients pass the symbolic
    "python" and no working_dir — the SERVER's pod runtime resolves both."""
    from kubeflow_tpu.api.common import (
        ContainerSpec,
        ElasticPolicy,
        ObjectMeta,
        PodTemplateSpec,
        ReplicaSpec,
        RunPolicy,
    )

    if family not in TRAIN_FAMILIES:
        raise ValueError(f"unknown family {family!r} (one of {TRAIN_FAMILIES})")
    rp = RunPolicy()
    if elastic is not None:
        lo, hi = elastic
        if not (lo <= num_workers <= hi):
            raise ValueError(
                f"num_workers {num_workers} outside elastic range [{lo}, {hi}]"
            )
        rp.elastic_policy = ElasticPolicy(min_replicas=lo, max_replicas=hi)
    return JAXJob(
        metadata=ObjectMeta(name=name, namespace=namespace),
        spec=JAXJobSpec(
            replica_specs={REPLICA_WORKER: ReplicaSpec(
                replicas=num_workers,
                template=PodTemplateSpec(container=ContainerSpec(
                    command=[interpreter, "-m", f"examples.{family}",
                             f"--device={device}", *(args or [])],
                    working_dir=working_dir,
                )),
            )},
            run_policy=rp,
        ),
    )


_KIND_TO_CLS = {
    JobKind.JAX: JAXJob,
    JobKind.TF: TFJob,
    JobKind.PYTORCH: PyTorchJob,
    JobKind.MPI: MPIJob,
    JobKind.XGBOOST: XGBoostJob,
    JobKind.PADDLE: PaddleJob,
    JobKind.MXNET: MXJob,
}


def job_class_for_kind(kind: JobKind | str) -> type[TrainJob]:
    return _KIND_TO_CLS[JobKind(kind)]
