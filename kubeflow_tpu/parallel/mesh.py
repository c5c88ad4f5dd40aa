"""Device mesh construction — the one mechanism under every strategy.

The canonical axis vocabulary (SURVEY.md §2.2 table):
  data     pure data parallel (gradient allreduce)
  fsdp     data parallel with sharded params/optimizer (ZeRO-3 analogue)
  model    tensor parallel (matmul sharding over ICI)
  context  sequence/context parallel (ring attention KV rotation)
  pipeline pipeline stages (microbatch loop over ppermute)
  expert   MoE expert parallel (all-to-all dispatch)

Mesh axes are ordered fastest-varying-last onto the physical topology; ICI
bandwidth favors putting `model`/`context` on the innermost (intra-slice)
dimension and `data` on the outermost (inter-slice DCN) dimension — the
scaling-book recipe.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_MODEL = "model"
AXIS_CONTEXT = "context"
AXIS_PIPELINE = "pipeline"
AXIS_EXPERT = "expert"

# Outer-to-inner canonical order: data-like axes ride DCN, model-like ride ICI.
CANONICAL_ORDER = [AXIS_DATA, AXIS_FSDP, AXIS_PIPELINE, AXIS_EXPERT, AXIS_CONTEXT, AXIS_MODEL]


@dataclass
class MeshConfig:
    """Sizes per axis; -1 on at most one axis means 'all remaining devices'."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    context: int = 1
    pipeline: int = 1
    expert: int = 1

    def sizes(self) -> dict[str, int]:
        return {
            AXIS_DATA: self.data,
            AXIS_FSDP: self.fsdp,
            AXIS_PIPELINE: self.pipeline,
            AXIS_EXPERT: self.expert,
            AXIS_CONTEXT: self.context,
            AXIS_MODEL: self.model,
        }


def build_mesh(
    config: MeshConfig | None = None, devices: list | None = None
) -> Mesh:
    """Build a Mesh over `devices` (default: all local devices).

    Axes of size 1 are kept in the mesh so sharding specs can always name
    them — XLA erases trivial axes at compile time, so this costs nothing.
    """
    config = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    n = len(devices)

    sizes = config.sizes()
    wild = [a for a, s in sizes.items() if s == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one axis may be -1, got {wild}")
    fixed = math.prod(s for s in sizes.values() if s != -1)
    if wild:
        if n % fixed != 0:
            raise ValueError(
                f"{n} devices not divisible by fixed axes product {fixed}"
            )
        sizes[wild[0]] = n // fixed
    elif fixed != n:
        raise ValueError(f"axis sizes {sizes} product {fixed} != {n} devices")

    axis_names = tuple(CANONICAL_ORDER)
    shape = tuple(sizes[a] for a in axis_names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def single_device_mesh() -> Mesh:
    """1-device mesh with the full axis vocabulary (all sizes 1 except data)."""
    return build_mesh(MeshConfig(), jax.devices()[:1])


def build_multislice_mesh(
    num_slices: int, config: MeshConfig | None = None, devices: list | None = None
) -> Mesh:
    """Mesh for a multislice (DCN/megascale) job.

    Devices arrive slice-major from jax.devices() (processes are ordered by
    id and slices are contiguous process ranges — envcontract.jax_env), so
    with the canonical outer-to-inner axis order the data-like axes span
    slices (DCN) while model-like axes stay inside a slice (ICI) — the
    scaling-book placement. Validates that the outermost non-trivial axis is
    a multiple of num_slices so no ICI-class axis straddles a DCN boundary.
    """
    config = config or MeshConfig()
    mesh = build_mesh(config, devices)
    # only the data-like outer axes may straddle the DCN boundary; model/
    # context/expert/pipeline collectives must stay inside one slice's ICI
    dcn = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
    if dcn % num_slices != 0:
        raise ValueError(
            f"mesh {dict(mesh.shape)}: data×fsdp = {dcn} is not a multiple "
            f"of num_slices {num_slices}; an ICI-class axis would straddle "
            f"the DCN slice boundary"
        )
    return mesh


# ---------------------------------------------------------------- manual region

# Trace-time marker: set while a stage body is being traced INSIDE an
# already-manual shard_map region (gpipe's pipeline ring). Collective
# constructs that normally open their OWN shard_map (ring/ulysses
# attention, MoE dispatch) consult it and fall back to their
# auto-partitioned formulation instead of nesting — reverse-mode AD
# through a nested shard_map inside a manual region produces WRONG
# cotangents in current JAX (forward exact, gradients corrupted; found
# by the r5 real-dim composed execution test: finite loss, NaN/exploding
# grad-norm growing geometrically with layers-per-stage). The
# auto-partitioned bodies compute identical math and let the XLA
# partitioner insert the context/expert collectives.
import contextvars as _contextvars

_IN_MANUAL_REGION = _contextvars.ContextVar("kft_in_manual_region",
                                            default=False)


class manual_region:
    """Context manager marking 'tracing inside a manual shard_map body'.

    Explicit marker (gpipe sets it around stage bodies); in_manual_region
    ALSO auto-detects via the abstract mesh's axis types, so a future
    manual construct that forgets the marker still routes its inner
    collectives safely."""

    def __enter__(self):
        self._tok = _IN_MANUAL_REGION.set(True)
        return self

    def __exit__(self, *exc):
        _IN_MANUAL_REGION.reset(self._tok)
        return False


def in_manual_region() -> bool:
    """True while tracing inside any manual shard_map region — via the
    explicit marker OR the ambient abstract mesh's axis types (inside a
    shard_map body the bound axes report Manual), so detection does not
    depend on every manual-region author remembering the marker."""
    if _IN_MANUAL_REGION.get():
        return True
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    manual = jax.sharding.AxisType.Manual
    return any(t == manual for t in mesh.axis_types)
