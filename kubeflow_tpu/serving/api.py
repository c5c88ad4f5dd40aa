"""Serving CR-equivalents: InferenceService.

Reference parity (unverified cites, SURVEY.md §2.5): kserve
pkg/apis/serving/v1beta1 InferenceService{predictor,transformer,explainer}.
Deployment mode is the RawDeployment analogue — the Knative/Istio serverless
stack is intentionally out of scope (SURVEY.md §7 'what NOT to build');
replica processes are managed directly by the ISVC controller.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from kubeflow_tpu.api.common import ObjectMeta


class PredictorRuntime(str, enum.Enum):
    # In-tree JAX runtime: model dir holds config.json + params.msgpack for
    # an in-tree family; server builds the module and jit-compiles predict.
    JAX = "jax"
    # Custom runtime: user supplies "pkg.module:ModelClass" (the kserve
    # custom-predictor container analogue, minus the container).
    CUSTOM = "custom"
    # Framework wrapper runtimes (kserve sklearnserver/torchserve zoo
    # analogue, serving/runtimes.py): artifact pulled by the storage
    # initializer, loaded by the matching wrapper.
    SKLEARN = "sklearn"
    TORCH = "torch"
    XGBOOST = "xgboost"
    LIGHTGBM = "lightgbm"
    PADDLE = "paddle"
    PMML = "pmml"
    # Triton-repository-shaped runtime (config.pbtxt + <version>/model.<ext>
    # layout; triton is the OIP reference server, so it rides the v2 paths).
    TRITON = "triton"


@dataclass
class PredictorSpec:
    runtime: PredictorRuntime = PredictorRuntime.JAX
    # gs:// s3:// pvc:// file:// or bare path; pulled by the storage
    # initializer into the pod's model dir (/mnt/models contract)
    storage_uri: str = ""
    # CUSTOM runtime: import path "package.module:ClassName"
    model_class: str = ""
    replicas: int = 1
    # >0 enables server-side adaptive micro-batching: concurrent requests
    # coalesce into one forward pass of up to this many rows
    max_batch_size: int = 0
    # serve the v2 Open Inference Protocol over gRPC too (kserve serves v2
    # on REST and gRPC); each replica binds an ephemeral gRPC port,
    # surfaced in the pod's grpc-address annotation
    grpc: bool = False
    env: dict[str, str] = field(default_factory=dict)
    # device flag forwarded to the server process (tpu|cpu)
    device: str = ""
    # JAX runtime only: export + serialize the compiled predictor at deploy
    # (serving/aot.py) — replicas load the artifact without retracing, and
    # against the compile cache the deploy warmed (utils/compile_cache.py
    # resolve_cache_dir) the restart path compiles nothing
    aot: bool = False


@dataclass
class TransformerSpec:
    """Pre/post-processing hop (kserve transformer analogue): a CUSTOM model
    class whose preprocess/postprocess wrap the predictor call."""

    model_class: str = ""
    env: dict[str, str] = field(default_factory=dict)


@dataclass
class ExplainerSpec:
    """:explain hop (kserve explainer analogue): a CUSTOM model class whose
    explain() answers /v1/models/{m}:explain; it receives the predictor
    chain as predict_fn for black-box perturbation."""

    model_class: str = ""
    env: dict[str, str] = field(default_factory=dict)


@dataclass
class AutoscalingSpec:
    """HPA analogue for predictors: the controller samples each replica's
    request counters and sizes the replica set to target_qps_per_replica."""

    min_replicas: int = 1  # 0 enables serverless scale-to-zero
    max_replicas: int = 4
    target_qps_per_replica: float = 10.0
    # seconds between scaling decisions (cooldown)
    scale_interval_s: float = 15.0
    # with min_replicas=0: how long the service must be idle (zero
    # observed qps) before the last replica is reaped (Knative
    # scale-to-zero grace analogue)
    scale_to_zero_grace_s: float = 30.0


@dataclass
class InferenceServiceSpec:
    predictor: PredictorSpec = field(default_factory=PredictorSpec)
    transformer: TransformerSpec | None = None
    explainer: ExplainerSpec | None = None
    # canary rollout (kserve canaryTrafficPercent): a second predictor spec
    # served canary_traffic_percent of requests until promoted/rolled back
    canary: PredictorSpec | None = None
    canary_traffic_percent: int = 0
    autoscaling: AutoscalingSpec | None = None


@dataclass
class ReplicaEndpoint:
    url: str = ""
    ready: bool = False


@dataclass
class InferenceServiceStatus:
    ready: bool = False
    url: str = ""  # primary endpoint (replica 0)
    replicas_ready: int = 0
    endpoints: list[ReplicaEndpoint] = field(default_factory=lambda: [])
    canary_ready: int = 0
    canary_endpoints: list[ReplicaEndpoint] = field(default_factory=lambda: [])
    message: str = ""


@dataclass
class InferenceService:
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    spec: InferenceServiceSpec = field(default_factory=InferenceServiceSpec)
    status: InferenceServiceStatus = field(default_factory=InferenceServiceStatus)
    kind: str = "InferenceService"
    api_version: str = "kubeflow-tpu.org/v1beta1"


def validate_isvc(isvc: InferenceService) -> InferenceService:
    if not isvc.metadata.name:
        raise ValueError("inferenceservice: metadata.name required")
    p = isvc.spec.predictor
    if p.replicas < 1:
        raise ValueError("inferenceservice: predictor.replicas must be >= 1")
    if p.runtime != PredictorRuntime.CUSTOM and not p.storage_uri:
        raise ValueError(
            f"inferenceservice: {p.runtime.value} runtime requires storageUri"
        )
    if p.runtime == PredictorRuntime.CUSTOM and not p.model_class:
        raise ValueError(
            "inferenceservice: custom runtime requires modelClass 'module:Class'"
        )
    if isvc.spec.transformer is not None and not isvc.spec.transformer.model_class:
        raise ValueError("inferenceservice: transformer requires modelClass")
    if isvc.spec.explainer is not None and not isvc.spec.explainer.model_class:
        raise ValueError("inferenceservice: explainer requires modelClass")
    if not (0 <= isvc.spec.canary_traffic_percent <= 100):
        raise ValueError(
            "inferenceservice: canaryTrafficPercent must be in [0, 100]"
        )
    if isvc.spec.canary_traffic_percent > 0 and isvc.spec.canary is None:
        raise ValueError(
            "inferenceservice: canaryTrafficPercent requires a canary predictor"
        )
    a = isvc.spec.autoscaling
    if a is not None:
        if not (0 <= a.min_replicas <= a.max_replicas) or a.max_replicas < 1:
            raise ValueError(
                "inferenceservice: autoscaling needs "
                "0 <= minReplicas <= maxReplicas, maxReplicas >= 1 "
                "(minReplicas=0 enables scale-to-zero)"
            )
        if a.target_qps_per_replica <= 0:
            raise ValueError(
                "inferenceservice: autoscaling.targetQpsPerReplica must be > 0"
            )
        if a.scale_to_zero_grace_s <= 0:
            raise ValueError(
                "inferenceservice: autoscaling.scaleToZeroGraceS must be > 0"
            )
    return isvc
