"""Pipeline parallelism — GPipe-style microbatch loop over the `pipeline` axis.

The reference has no in-platform PP (DeepSpeed/Megatron user images supply it
— SURVEY.md §2.2); here it is a first-class, single-program SPMD construct:

  - per-stage params are stacked on a leading stage axis sharded over the
    mesh's `pipeline` axis (one stage's weights per device group),
  - a lax.scan runs n_micro + n_stages - 1 ticks; each tick every stage
    applies itself to its current microbatch and the activation ring rotates
    one hop via ppermute (single-program — no MPMD runtime needed, cf. the
    MPMD PP paper in PAPERS.md for the road not taken),
  - the shard_map is *partial-manual* over ONLY `pipeline`: the ppermute is
    explicit, while data/fsdp/model/context shardings inside each stage stay
    automatic — XLA still inserts the FSDP all-gathers and TP collectives
    for the stage body. This is what lets a real (TP+FSDP-sharded) model
    ride the pipeline, where the round-1 full-manual version could not.
  - reverse-mode autodiff through scan+ppermute yields the backward pipeline
    automatically — no hand-written 1F1B schedule. Stages are rematerialized
    (jax.checkpoint) so live activation memory is O(microbatch), the GPipe
    memory contract.

Activations may be arbitrary pytrees (e.g. (hidden, mask)); every leaf must
keep the same shape/dtype at every stage boundary — the circulating-ring
shape contract. Heterogeneous per-stage *behavior* is supported by branching
on the `stage` index passed to stage_fn (lax.switch over bodies); boundary
layers with different shapes (embeddings, heads) run outside the ring.

Bubble fraction is (S-1)/(T+S-1) as in GPipe; raise n_micro to amortize.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_PIPELINE, manual_region


def _pin(tree: Any, batch_dim: int) -> Any:
    """Pin each leaf's batch dim to the data-like axes (auto axes inside the
    partial-manual region); keeps the ring body's select/update ops on ONE
    layout so the partitioner never falls back to full rematerialization."""
    from kubeflow_tpu.parallel.sharding import BATCH_AXES

    if jax.sharding.get_abstract_mesh().empty:
        return tree

    def one(a):
        spec = [None] * jnp.ndim(a)
        spec[batch_dim] = BATCH_AXES
        return jax.lax.with_sharding_constraint(a, P(*spec))

    return jax.tree.map(one, tree)


def lift_pipeline_rules(rules: list) -> list:
    """Lift a model family's dense PARTITION_RULES onto pipeline-stacked
    stage params: each rule re-anchored under 'stages/' with the leading
    stage dim sharded over `pipeline`, plus a catch-all so every stage
    param is at least stage-sharded, plus the dense rules for boundary
    params (embeddings, heads). One definition for every pipelined family
    (bert_pp, gpt_pp, ...)."""
    return [
        *[(r"stages/.*" + pat, P(AXIS_PIPELINE, *spec)) for pat, spec in rules],
        (r"stages/", P(AXIS_PIPELINE)),
        *rules,
    ]


def stack_stage_params(per_stage: list[Any]) -> Any:
    """Stack a list of per-stage param pytrees on a new leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def stage_pspec(params_stacked: Any, axis_name: str = AXIS_PIPELINE) -> Any:
    """PartitionSpec tree sharding the leading stage axis over `pipeline`."""
    return jax.tree.map(
        lambda x: P(axis_name, *([None] * (jnp.ndim(x) - 1))), params_stacked
    )


def _n_stages(params_stacked: Any) -> int:
    return jax.tree.leaves(params_stacked)[0].shape[0]


def gpipe(
    stage_fn: Callable,
    params_stacked: Any,
    x: Any,
    n_micro: int,
    *,
    rng: jax.Array | None = None,
    axis_name: str = AXIS_PIPELINE,
    remat: bool = True,
) -> Any:
    """Apply a pipeline of stages to a global batch.

    stage_fn(stage_params, activation, *, stage, rng) -> activation, where
    `activation` is a pytree whose every leaf is (B, ...) with identical
    shapes at all stage boundaries, `stage` is the stage index (traced
    scalar — branch with lax.switch for heterogeneous stages) and `rng` is a
    per-(stage, tick) PRNG key (None when `rng` is not given).
    params_stacked has leading dim n_stages; with an ambient mesh whose
    `pipeline` axis matches n_stages the stages run as a ppermute ring; with
    pipeline=1 they run as a sequential scan (identical numerics). Batch
    leaves may be sharded over the data-like mesh axes — those shardings
    stay automatic inside the ring.
    """
    mesh = jax.sharding.get_abstract_mesh()
    n_stages = _n_stages(params_stacked)
    pp = 1 if mesh.empty else mesh.shape.get(axis_name, 1)
    leaves = jax.tree.leaves(x)
    batch = leaves[0].shape[0]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")

    body = jax.checkpoint(stage_fn, static_argnums=()) if remat else stage_fn

    if pp == 1:
        # no pipeline axis: sequential scan over stages, same numerics —
        # including the SAME collective-construct routing as the pp>1
        # ring (manual_region), so e.g. MoE dispatch picks the identical
        # capacity-pool semantics in both modes
        def seq_tick(carry, sp):
            act, s = carry
            r = None if rng is None else jax.random.fold_in(rng, s)
            with manual_region():
                out = body(sp, act, stage=s, rng=r)
            return (out, s + 1), None

        (out, _), _ = jax.lax.scan(
            seq_tick, (x, jnp.int32(0)), params_stacked
        )
        return out
    if n_stages != pp:
        raise ValueError(
            f"{n_stages} stages need pipeline axis {n_stages}, mesh has {pp}"
        )

    mb = batch // n_micro
    from kubeflow_tpu.parallel.sharding import BATCH_AXES

    data_ways = 1
    for a in BATCH_AXES:
        data_ways *= mesh.shape.get(a, 1)
    if mb % data_ways:
        raise ValueError(
            f"microbatch size {mb} (batch {batch} / n_micro {n_micro}) must "
            f"be divisible by the data-like mesh extent {data_ways}; lower "
            f"n_micro or raise the batch size (a non-divisible microbatch "
            f"forces the partitioner into padded reshards at the ring "
            f"boundary)"
        )
    # Microbatch layout is (mb, n_micro, ...): microbatch t is the STRIDED
    # slice x[t::n_micro], so the batch-sharded dim 0 keeps its sharding
    # through the reshape (a (n_micro, mb, ...) split would move the sharded
    # dim and force the partitioner into a full-remat reshard). Per-example
    # numerics are unchanged; only which examples share a microbatch differs,
    # which matters to no per-example stage (layernorm etc.).
    x_mb = _pin(
        jax.tree.map(lambda a: a.reshape(mb, n_micro, *a.shape[1:]), x),
        batch_dim=0,
    )

    def per_stage(params_local, x_mb):
        # params_local leading dim is 1 (this device group's stage)
        params = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis_name)
        ring = pp  # == n_stages, checked above
        perm = [(i, (i + 1) % ring) for i in range(ring)]
        ticks = n_micro + n_stages - 1

        def tick(carry, t):
            circ, outbuf = carry
            # stage 0 ingests microbatch t (zeros after the last one, whose
            # outputs are discarded); other stages consume what rotated in.
            # Microbatch t lives at index t of dim 1 (strided layout — the
            # batch-sharded dim 0 never moves).
            feed_idx = jnp.clip(t, 0, n_micro - 1)
            inp = _pin(
                jax.tree.map(
                    lambda buf, c: jnp.where(
                        stage == 0,
                        jnp.take(buf, feed_idx, axis=1)
                        * (t < n_micro).astype(buf.dtype),
                        c,
                    ),
                    x_mb, circ,
                ),
                batch_dim=0,
            )
            r = None if rng is None else jax.random.fold_in(
                jax.random.fold_in(rng, stage), t
            )
            # stage bodies trace inside THIS shard_map's manual region:
            # collective constructs (ring/ulysses attention, MoE dispatch)
            # must not nest their own shard_map here — nested-manual
            # reverse AD corrupts cotangents (see mesh.manual_region) —
            # so the marker routes them to their auto-partitioned forms
            with manual_region():
                out = _pin(body(params, inp, stage=stage, rng=r),
                           batch_dim=0)
            # last stage emits microbatch t-(S-1) once the pipe is full
            emit_idx = t - (n_stages - 1)
            is_emit = jnp.logical_and(stage == ring - 1, emit_idx >= 0)
            outbuf = jax.lax.cond(
                is_emit,
                lambda ob: jax.tree.map(
                    lambda o, b: jax.lax.dynamic_update_index_in_dim(
                        b, o, jnp.maximum(emit_idx, 0), 1
                    ),
                    out, ob,
                ),
                lambda ob: ob,
                outbuf,
            )
            circ = _pin(
                jax.tree.map(
                    lambda o: jax.lax.ppermute(o, axis_name, perm), out
                ),
                batch_dim=0,
            )
            return (circ, _pin(outbuf, batch_dim=0)), None

        init = (
            jax.tree.map(lambda a: jnp.zeros_like(a[:, 0]), x_mb),
            jax.tree.map(lambda a: jnp.zeros_like(a), x_mb),
        )
        (circ, outbuf), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
        # only the last stage holds real outputs; psum broadcasts them so
        # the result is replicated over the pipeline axis. The psum runs in
        # f32: low-precision all-reduce here trips XLA's AllReducePromotion
        # pass (CHECK failure cloning the remat boundary copy) and f32 is
        # numerically safer anyway.
        outbuf = jax.tree.map(
            lambda b: jax.lax.psum(
                jnp.where(stage == ring - 1, b, jnp.zeros_like(b)).astype(
                    jnp.float32
                ),
                axis_name,
            ).astype(b.dtype),
            outbuf,
        )
        return outbuf

    out_mb = jax.shard_map(
        per_stage,
        mesh=mesh,
        axis_names={axis_name},
        in_specs=(stage_pspec(params_stacked, axis_name),
                  jax.tree.map(lambda _: P(), x_mb)),
        out_specs=jax.tree.map(lambda _: P(), x_mb),
        check_vma=False,
    )(params_stacked, x_mb)
    return jax.tree.map(
        lambda a: a.reshape(n_micro * mb, *a.shape[2:]), out_mb
    )
