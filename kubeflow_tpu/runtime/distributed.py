"""Worker-side distributed bootstrap — the consumer of the L3 env contract.

The controller synthesizes JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID (controller/envcontract.py#jax_env); this module is the other
half: a worker process calls `initialize_from_env()` first thing, which wires
`jax.distributed.initialize` (the gRPC coordination service built into
jaxlib — the TPU-native replacement for the reference's c10d/NCCL rendezvous,
SURVEY.md §2.3) and returns the process topology.

Works identically on: real multi-host TPU slices (env comes from GKE), local
multi-process CPU gangs (env comes from the fake cluster's LocalResolver),
and single-process runs (no env -> no-op).
"""

from __future__ import annotations

import os
from dataclasses import dataclass


@dataclass(frozen=True)
class DistContext:
    process_id: int
    num_processes: int
    coordinator: str | None
    # multislice topology (MEGASCALE_* contract, SURVEY.md §2.3): slices are
    # the DCN-connected units; processes within a slice share ICI
    num_slices: int = 1
    slice_id: int = 0

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0

    @property
    def is_multislice(self) -> bool:
        return self.num_slices > 1

    @property
    def processes_per_slice(self) -> int:
        return self.num_processes // max(self.num_slices, 1)


def initialize_from_env(
    platform: str | None = None, local_device_count: int | None = None
) -> DistContext:
    """Initialize jax.distributed from the JAXJob env contract.

    platform: force a jax platform ("cpu" for local gangs — a chip belongs
    to one process at a time). local_device_count: virtual CPU
    devices this process contributes (overrides any inherited XLA_FLAGS —
    pod processes inherit the parent env, which may carry a test harness's
    device-count flag). Must run before any other jax use.
    """
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    n = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    pid = int(os.environ.get("JAX_PROCESS_ID", "0"))
    # liveness: announce this incarnation BEFORE anything that can wedge
    # (jax import, distributed rendezvous) — a worker stuck right here is
    # exactly the hang the lease detector exists for (docs/health.md)
    from kubeflow_tpu.health import HeartbeatWriter

    hb = HeartbeatWriter.from_env()
    if hb is not None:
        hb.beat(step=-1, phase="rendezvous")
    # multislice contract: on real Cloud TPU these are consumed by libtpu's
    # megascale transport; here they carry the slice topology into the mesh
    # builder (slice-major device order => data-like axes ride DCN)
    num_slices = int(os.environ.get("MEGASCALE_NUM_SLICES", "1"))
    slice_id = int(os.environ.get("MEGASCALE_SLICE_ID", "0"))
    if num_slices > 1:
        if n % num_slices:
            raise ValueError(
                f"JAX_NUM_PROCESSES {n} not divisible by "
                f"MEGASCALE_NUM_SLICES {num_slices}"
            )
        expect = pid // (n // num_slices)
        if slice_id != expect:
            raise ValueError(
                f"MEGASCALE_SLICE_ID {slice_id} inconsistent with process "
                f"{pid}/{n} over {num_slices} slices (expected {expect})"
            )

    if local_device_count is not None:
        import re

        flags = os.environ.get("XLA_FLAGS", "")
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={local_device_count}"
        ).strip()

    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if coord and n > 1:
        # the gang's rendezvous is the canonical recovery-path span: a
        # restarted gang's wall-clock between rebind and first step is
        # mostly spent right here. No-op unless the pod env carries
        # KFTPU_TRACE_DIR (tracing.init_worker_from_env).
        from kubeflow_tpu.tracing import init_worker_from_env

        tracer = init_worker_from_env(service="worker")
        with tracer.span("rendezvous", coordinator=coord, world=n, rank=pid):
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=n, process_id=pid
            )
    if hb is not None:
        # the gang is formed: subsequent beats come from the training loop
        hb.beat(step=-1, phase="rendezvous-done")
    return DistContext(
        process_id=pid, num_processes=n, coordinator=coord,
        num_slices=num_slices, slice_id=slice_id,
    )


def shutdown() -> None:
    import jax

    if jax.process_count() > 1:
        jax.distributed.shutdown()
