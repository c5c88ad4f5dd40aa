"""T5X-style partitioner — logical axis rules OWN the sharding.

Before this module, sharding lived in two ad-hoc places: a largest-dim
FSDP heuristic (`sharding.fsdp_param_pspec`) and per-model regex→
PartitionSpec tables (`PARTITION_RULES`). Both keep working — they are
now the top and bottom tiers of ONE derivation the partitioner owns:

  1. explicit path rules   (regex → PartitionSpec; the model tables)
  2. logical axis rules    (path → logical dim names → mesh axes)
  3. FSDP heuristic        (largest divisible dim over `fsdp`)

The logical tier is the T5X shape: a param path maps to per-dimension
LOGICAL names (``("embed", "heads")`` for an attention projection), and a
separate rule list maps logical names to MESH axes (``("embed", "fsdp")``,
``("heads", "tensor")``). Changing how a model family shards is then one
rule edit, not N regex rows — and the same logical names place per-stage
gangs in the MPMD pipeline work (ROADMAP item 1).

A named dim that does not divide its mesh-axis product is REPLICATED
(that dim drops to None) instead of discarding the whole rule — the
spec-fits-mesh fallback the tiny-mesh tests pin. The legacy
`sharding.state_pspec` wrapper keeps its historical all-or-nothing rule
matching for existing callers.

The partitioner also owns two step-level contracts the Trainer consumes:

  - ``constrain_grads``: per-rule ``with_sharding_constraint`` on the
    gradient tree, so XLA's scheduler can start each gradient's
    reduce-scatter/all-reduce the moment the layer's backward produces
    it — overlapping collectives with the remaining backward instead of
    serializing one big all-reduce after it (1909.09756's first MFU
    front; gated by the `grad_overlap` cpu-proxy workload).
  - ``deterministic_rng``: partitionable threefry scoped around state
    init and step tracing. The legacy (jax<=0.4.x default) threefry
    path produces DIFFERENT random bits when XLA partitions the
    generator — an FSDP-sharded init diverged from the single-device
    init by ~0.26 abs on a lecun_normal kernel, the root cause of the
    long-standing fsdp-vs-single numerics failures. Under the
    partitioner every layout draws identical bits.
"""

from __future__ import annotations

import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_MODEL,
    AXIS_PIPELINE,
    MeshConfig,
    build_mesh,
    build_multislice_mesh,
)

#: params with fewer elements than this replicate under the heuristic
#: (sharding a 128-float bias wastes a collective)
DEFAULT_MIN_SIZE = 2**12

#: accepted spellings for mesh axes in logical rules — "tensor" is the
#: T5X/Megatron name for what our mesh calls `model`
AXIS_ALIASES = {"tensor": AXIS_MODEL}

#: logical name -> mesh axis (str | tuple | None). First match wins,
#: T5X semantics; None pins the dim replicated.
LogicalAxisRules = Sequence[tuple[str, Any]]

#: path regex -> per-dimension logical names. First match wins; a name of
#: None replicates that dim regardless of the axis rules.
PathLogicalRules = Sequence[tuple[str, tuple]]

#: path regex -> PartitionSpec (the legacy model PARTITION_RULES shape)
PathSpecRules = Sequence[tuple[str, P]]

#: The default logical vocabulary. `embed` rides fsdp (ZeRO-3 weight
#: sharding), the matmul-wide dims (`heads`/`mlp`/`vocab`) ride tensor
#: parallelism, `expert` rides expert parallelism, `length` context
#: parallelism; bookkeeping dims (`kv`, `stack`, `norm`, `pos`) replicate.
DEFAULT_LOGICAL_AXIS_RULES: tuple[tuple[str, Any], ...] = (
    ("batch", (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)),
    ("embed", AXIS_FSDP),
    ("heads", "tensor"),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", AXIS_EXPERT),
    ("length", AXIS_CONTEXT),
    ("stage", AXIS_PIPELINE),
    ("kv", None),
    ("stack", None),
    ("norm", None),
    ("pos", None),
)

#: Param-path → logical names for the in-tree transformer families
#: (models/gpt.py, models/bert.py, parallel/moe.py naming). Derives the
#: SAME PartitionSpecs the hand-written PARTITION_RULES tables pin —
#: tests/test_partitioner.py proves the round trip on real param trees.
DEFAULT_PATH_LOGICAL_RULES: tuple[tuple[str, tuple], ...] = (
    # attention projections exist in both shapes: DenseGeneral's
    # (embed, heads, head_dim) rank-3 form (the in-tree models) and the
    # fused rank-2 form — rule lookup is RANK-AWARE (first pattern match
    # whose arity equals the param's rank wins)
    (r"(query|key|value)/kernel$", ("embed", "heads", "kv")),
    (r"(query|key|value)/kernel$", ("embed", "heads")),
    (r"attn_out/kernel$", ("heads", "kv", "embed")),
    (r"attn_out/kernel$", ("heads", "embed")),
    (r"(mlp_up|mlp_gate)/kernel$", ("embed", "mlp")),
    (r"mlp_down/kernel$", ("mlp", "embed")),
    (r"token_embed/embedding$", ("vocab", "embed")),
    (r"(position_embed|type_embed)/embedding$", ("pos", "embed")),
    (r"lm_head/kernel$", ("embed", "vocab")),
    (r"(pooler|mlm_dense)/kernel$", ("embed", "mlp")),
    (r"moe/(w_up|w_gate)$", ("expert", "embed", "mlp")),
    (r"moe/(b_up|b_gate)$", ("expert", "mlp")),
    (r"moe/w_down$", ("expert", "mlp", "embed")),
    (r"moe/b_down$", ("expert", "embed")),
)


def heuristic_pspec(shape: tuple[int, ...], fsdp_size: int,
                    min_size: int = DEFAULT_MIN_SIZE) -> P:
    """The FSDP fallback: shard the largest dim divisible by fsdp_size;
    tiny params replicate. (Moved here from parallel/sharding.py, which
    now delegates — the heuristic is the partitioner's bottom tier.)"""
    if fsdp_size <= 1 or int(np.prod(shape)) < min_size:
        return P()
    candidates = [i for i, d in enumerate(shape) if d % fsdp_size == 0]
    if not candidates:
        return P()
    dim = max(candidates, key=lambda i: shape[i])
    spec: list[Any] = [None] * len(shape)
    spec[dim] = AXIS_FSDP
    return P(*spec)


def spec_fits(spec: P, shape: tuple[int, ...], mesh: Mesh) -> bool:
    """All-or-nothing divisibility check (the legacy state_pspec rule
    contract): rank must not exceed the shape's and every named dim must
    divide its mesh-axis product."""
    if len(spec) > len(shape):
        return False
    for dim, axes in enumerate(spec):
        if axes is None:
            continue
        axes = axes if isinstance(axes, tuple) else (axes,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if shape[dim] % size != 0:
            return False
    return True


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Per-dimension spec-fits-mesh fallback: a named dim whose size does
    not divide its mesh-axis product REPLICATES (drops to None) instead of
    invalidating the whole rule — a 2-head model on a model=4 mesh keeps
    its embed sharding and merely replicates the heads dim. A spec longer
    than the shape's rank replicates entirely (rule/shape mismatch)."""
    if len(spec) > len(shape):
        return P()
    out: list[Any] = []
    for dim, axes in enumerate(spec):
        if axes is None:
            out.append(None)
            continue
        taxes = axes if isinstance(axes, tuple) else (axes,)
        size = int(np.prod([mesh.shape[a] for a in taxes]))
        out.append(axes if size and shape[dim] % size == 0 else None)
    return P(*out)


def resolve_pspec(path_str: str, shape: tuple[int, ...], mesh: Mesh,
                  rules: PathSpecRules | None,
                  min_size: int = DEFAULT_MIN_SIZE) -> P:
    """The legacy derivation (`sharding.state_pspec` delegates here):
    explicit path rules with all-or-nothing fit, then the heuristic."""
    if len(shape) == 0:
        return P()
    if rules:
        for pattern, spec in rules:
            if re.search(pattern, path_str) and spec_fits(spec, shape, mesh):
                return spec
    return heuristic_pspec(shape, mesh.shape[AXIS_FSDP], min_size)


def path_str_of(path) -> str:
    """'/'-joined tree path (DictKey/GetAttr/SequenceKey tolerant)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


@dataclass
class Partitioner:
    """Derives every PartitionSpec the trainer needs from one rule set.

    mesh construction is folded in: pass a ready `mesh`, or a
    `mesh_config` (+ `num_slices` > 1 for the hybrid DCN×ICI multislice
    mesh, with `build_multislice_mesh`'s no-ICI-axis-across-DCN guard).

    Derivation order for a param/state leaf (first hit wins):
      1. `path_specs`  — explicit regex → PartitionSpec (model
         PARTITION_RULES); per-dim fitted to the mesh (non-dividing dims
         replicate).
      2. `path_logical` + `logical_rules` — path → logical dim names →
         mesh axes; unknown logical names replicate loudly only under
         `strict`, silently otherwise (the T5X default).
      3. FSDP heuristic (largest divisible dim over `fsdp`).
    """

    mesh: Mesh | None = None
    mesh_config: MeshConfig | None = None
    num_slices: int = 1
    path_specs: PathSpecRules | None = None
    path_logical: PathLogicalRules = DEFAULT_PATH_LOGICAL_RULES
    logical_rules: LogicalAxisRules = DEFAULT_LOGICAL_AXIS_RULES
    min_size: int = DEFAULT_MIN_SIZE
    strict: bool = False
    _logical_map: dict = field(init=False, repr=False, default_factory=dict)

    def __post_init__(self):
        if self.mesh is None:
            cfg = self.mesh_config or MeshConfig()
            self.mesh = (build_multislice_mesh(self.num_slices, cfg)
                         if self.num_slices > 1 else build_mesh(cfg))
        # first-match-wins: build the lookup once, earlier rules shadow
        for name, axes in self.logical_rules:
            if name not in self._logical_map:
                self._logical_map[name] = self._canon(axes)

    @staticmethod
    def _canon(axes):
        if axes is None:
            return None
        if isinstance(axes, (tuple, list)):
            return tuple(AXIS_ALIASES.get(a, a) for a in axes)
        return AXIS_ALIASES.get(axes, axes)

    # ------------------------------------------------------------ derivation

    def mesh_axes_for(self, logical: str):
        """Mesh axis (or tuple, or None) for one logical dim name."""
        if logical in self._logical_map:
            return self._logical_map[logical]
        if self.strict:
            raise ValueError(
                f"no logical axis rule for {logical!r} "
                f"(rules: {[n for n, _ in self.logical_rules]})")
        return None

    def logical_to_spec(self, logical_axes: Sequence[str | None],
                        shape: tuple[int, ...]) -> P:
        """Logical dim names → fitted PartitionSpec over this mesh."""
        spec = P(*(None if name is None else self.mesh_axes_for(name)
                   for name in logical_axes))
        return fit_spec(spec, shape, self.mesh)

    def logical_axes_for_path(self, path_str: str,
                              rank: int | None = None) -> tuple | None:
        """First matching rule; with `rank`, the first match whose arity
        equals it (the same param name can carry different logical shapes
        — fused vs per-head attention projections)."""
        for pattern, names in self.path_logical:
            if re.search(pattern, path_str) and (
                    rank is None or len(names) == rank):
                return tuple(names)
        return None

    def spec_for(self, path_str: str, shape: tuple[int, ...]) -> P:
        """The full three-tier derivation for one state leaf."""
        if len(shape) == 0:
            return P()
        if self.path_specs:
            for pattern, spec in self.path_specs:
                if re.search(pattern, path_str):
                    return fit_spec(spec, shape, self.mesh)
        logical = self.logical_axes_for_path(path_str, rank=len(shape))
        if logical is not None:
            return self.logical_to_spec(logical, shape)
        return heuristic_pspec(shape, self.mesh.shape[AXIS_FSDP],
                               self.min_size)

    # -------------------------------------------------------- trainer hooks

    def state_shardings(self, state: Any) -> Any:
        """NamedSharding pytree matching `state` (jit in/out_shardings,
        checkpoint restore targets). Rules written against param paths
        also hit the mirrored adam mu/nu trees — the param path is a
        suffix of the optimizer-state path."""

        def one(path, leaf):
            spec = self.spec_for(path_str_of(path), np.shape(leaf))
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(one, state)

    def grad_specs(self, params: Any) -> Any:
        """PartitionSpec tree for a gradient pytree: gradients share the
        parameter layout (that is what makes the per-rule constraint a
        reduce-scatter XLA can start early)."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.spec_for(path_str_of(path),
                                             np.shape(leaf)),
            params,
        )

    def constrain_grads(self, grads: Any) -> Any:
        """Per-rule `with_sharding_constraint` over the gradient tree —
        the comm/compute-overlap hook. Pinning each gradient to its
        param's layout right where backward produces it lets XLA's
        latency-hiding scheduler overlap every gradient's collective with
        the REST of the backward pass, instead of fusing one serialized
        all-reduce after it (docs/partitioner.md "Overlap mechanics")."""

        def one(path, g):
            spec = self.spec_for(path_str_of(path), np.shape(g))
            if not any(a is not None for a in tuple(spec)):
                # fully-replicated grad: a constraint would only add a
                # no-op custom-call per leaf to every compiled step (the
                # common pure-data-parallel case) — nothing to overlap
                return g
            return jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(one, grads)

    # ------------------------------------------------------------- numerics

    @contextmanager
    def deterministic_rng(self):
        """Partitionable threefry for everything traced inside: random
        draws become layout-invariant — an FSDP/TP-sharded init produces
        bit-identical params to the single-device init (the fsdp-vs-
        single numerics fix; see module docstring). Scoped, not global:
        the legacy generator's values are pinned by seeded tests
        elsewhere in the repo."""
        with jax.threefry_partitionable(True):
            yield

    # ------------------------------------------------------------ cache key

    def key_fields(self) -> dict:
        """Everything about this partitioner that changes the compiled
        step program, in stable string form — folded into the trainer's
        executable content key so the restart-warm compile cache can
        never serve a binary built under different sharding rules."""
        def spec_s(spec):
            return repr(tuple(spec))

        return {
            "mesh": tuple(sorted(self.mesh.shape.items())),
            "num_slices": self.num_slices,
            "path_specs": tuple(
                (p, spec_s(s)) for p, s in (self.path_specs or ())),
            "path_logical": tuple(
                (p, tuple(n)) for p, n in self.path_logical),
            # key the EFFECTIVE first-match-wins map, None entries
            # included: a rule pinning a logical dim replicated must move
            # the key exactly like one sharding it (dropping Nones — or
            # keying the raw ordered list — would let two partitioners
            # with different effective sharding share a cached binary)
            "logical_rules": tuple(sorted(
                (k, "+".join(v) if isinstance(v, tuple) else str(v))
                for k, v in self._logical_map.items())),
            "min_size": self.min_size,
        }
