"""The trainer: one jit-compiled SPMD step over a device mesh.

TPU-first design notes:
  - ONE traced/compiled train step (static shapes, donated state buffers);
    the Python loop only feeds numpy batches and reads scalars.
  - Mesh-aware from day one: the same trainer runs 1-device or N-device;
    parallelism is data placement (parallel/sharding.py), not code.
  - bfloat16 compute path via `compute_dtype` (params stay f32; matmuls run
    on the MXU in bf16).
  - Metrics print in the sweep-collector `name=value` contract.

Reference parity: replaces the user-image training loops the platform
launches (kubeflow/examples mnist et al. — SURVEY.md L6) with an in-tree,
device-flag-selectable equivalent (north-star configs #1-#3).
"""

from __future__ import annotations

import inspect
import math
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh

from kubeflow_tpu.parallel import build_mesh, MeshConfig
from kubeflow_tpu.parallel.partitioner import Partitioner
from kubeflow_tpu.parallel.sharding import (
    put_global,
    put_process_local,
    shard_batch,
    stacked_batch_sharding,
)
from kubeflow_tpu.tracing import get_tracer, init_worker_from_env
from kubeflow_tpu.utils import compile_cache as cc
from kubeflow_tpu.utils.device import device_summary
from kubeflow_tpu.utils.envvars import ENV_EVENT_DIR, ENV_PROFILE_DIR
from kubeflow_tpu.train import metrics as metrics_lib
from kubeflow_tpu.train.checkpoint import Checkpointer
from kubeflow_tpu.train.data import (
    AsyncLoader,
    Dataset,
    batches,
    prefetch_to_device,
)


def _traced_data_iter(tracer, it, stats_from=None):
    """Wrap a batch iterator so each HOST-side fetch (shuffle/stack/device
    put — everything before the step dispatch) is a train.data_load span.
    Only installed when tracing is enabled; the plain loop is untouched.
    Each span carries its fetch sequence number so the profiler
    (kubeflow_tpu/profiling) can pair fetches with step cycles
    deterministically instead of by wall-clock alone.

    `stats_from` (an AsyncLoader) stamps the queue-wait vs host-assemble
    split on each span: wait_s is what the step critical path actually
    paid, assemble_s the producer-thread work that overlapped compute —
    profiling.step_breakdown splits data_load into data_wait/data_assemble
    from these, sum-exactly."""
    it = iter(it)
    seq = 0
    while True:
        sp = tracer.start_span("train.data_load", seq=seq)
        seq += 1
        try:
            batch = next(it)
            if stats_from is not None:
                st = stats_from.pop_stats()
                sp.set_attribute("wait_s", round(st["wait_s"], 9))
                sp.set_attribute("assemble_s", round(st["assemble_s"], 9))
        except StopIteration:
            return
        finally:
            # close BEFORE yielding (the span times the fetch, not the
            # consumer) and on EVERY exit — a data-loader exception used
            # to leak the span and truncate the causal chain
            sp.end()
        yield batch


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    # non-param variable collections (e.g. {"batch_stats": ...}); empty dict
    # for purely functional models
    extra: Any = struct.field(default_factory=dict)


@dataclass
class TrainerConfig:
    batch_size: int = 128
    epochs: int = 1
    steps: int | None = None          # overrides epochs when set
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    warmup_steps: int = 0
    # cosine decay to lr_final_fraction·lr, reaching the floor at `steps`
    # total (decay spans steps - warmup_steps); requires `steps`.
    # "constant" keeps the warmup->flat behavior
    lr_schedule: str = "constant"     # constant | cosine
    lr_final_fraction: float = 0.0
    grad_clip_norm: float = 0.0       # 0 = off (global-norm clipping)
    # accumulate this many microbatch grads per optimizer step — big
    # effective batches without PP; runs as a lax.scan inside ONE jit step
    grad_accum_steps: int = 1
    # run this many optimizer steps per jit dispatch in fit() (lax.scan over
    # a stacked batch chunk) — amortizes host dispatch overhead, the
    # TPU-idiomatic steady-state loop. 1 = per-step dispatch (prefetch
    # overlaps transfers). Log/checkpoint/preemption cadence becomes
    # chunk-granular.
    fused_steps: int = 1
    seed: int = 0
    # None = AUTO: MXU-heavy model families (GPT/BERT/ViT/ResNet publish
    # PREFERRED_COMPUTE_DTYPE = bfloat16) train in bf16 on accelerator
    # backends — the module's compute dtype is flipped so the matmuls
    # actually run on the MXU, params stay f32 — while CPU (no MXU;
    # emulated bf16 is strictly slower) and preference-less models keep
    # f32. An explicit value is always honored verbatim: compute_dtype=
    # jnp.float32 is the documented bf16 opt-out, and an explicit
    # bfloat16 keeps today's input-cast behavior on any backend.
    compute_dtype: Any = None
    eval_every_epochs: int = 1
    checkpoint_dir: str | None = None
    checkpoint_every_steps: int = 200
    log_every_steps: int = 50
    mesh: MeshConfig | None = None    # None => single-device mesh semantics
    # jax.profiler trace output dir; "" defers to the platform's
    # KFTPU_PROFILE_DIR env (the JAXJob profile toggle, SURVEY.md §5.1)
    profile_dir: str = ""
    # tfevents scalar output for TensorBoard; "" defers to KFTPU_EVENT_DIR
    event_dir: str = ""
    # keep the max_to_keep BEST checkpoints by this eval-metric key (e.g.
    # "accuracy") instead of the newest — model selection; restore via
    # Checkpointer.restore_best. Best mode saves at eval cadence (metrics
    # exist only there) plus preemption; plain mode keeps step-cadence saves.
    keep_best_metric: str | None = None
    best_mode: str = "max"            # max | min (e.g. "loss")
    checkpoint_max_to_keep: int = 3
    # stop after this many consecutive evals without improvement on
    # early_stop_metric (best_mode direction); 0 = off. Epoch-granular
    # (metrics exist at eval cadence). Pairs with keep_best_metric so the
    # served model is the pre-plateau best.
    early_stop_patience: int = 0
    early_stop_metric: str = "accuracy"
    early_stop_mode: str = "max"      # max | min — independent of best_mode
    early_stop_min_delta: float = 0.0
    # "replicated": every process feeds the identical full batch (the
    # seed-deterministic pipeline convention); "process_local": each
    # process feeds ONLY its own rows (disjoint per-host loading via
    # train/data.py load_dataset_shards) and jax assembles the global
    # batch across hosts
    data_placement: str = "replicated"  # replicated | process_local
    # persistent XLA compile-cache dir (utils/compile_cache.py); "" defers
    # to the pod env contract (the jobcontroller injects a platform-wide
    # dir that SURVIVES gang restarts). When a dir resolves either way,
    # fit() warm-starts the train-step executables under a train.compile
    # span — a restarted incarnation performs zero backend compilations
    # of the train step (docs/perf.md "MFU hunt").
    compile_cache_dir: str = ""
    # background-thread host input pipeline (train/data.AsyncLoader):
    # batch assembly + host sharding run off the step critical path,
    # composing with the async device_put transfer. Batch order and
    # content are identical either way; False restores the inline
    # double-buffered prefetch.
    async_loader: bool = True


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def classification_eval_metrics(logits: jax.Array, labels: jax.Array):
    """Default eval contract: per-example (loss, accuracy), each (B,)."""
    per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    # token-level label tensors reduce their trailing dims to per-example
    while per_ex.ndim > 1:
        per_ex = per_ex.mean(-1)
    while acc.ndim > 1:
        acc = acc.mean(-1)
    return per_ex, acc


class Trainer:
    """Classification trainer for a flax module `model(x) -> logits`.

    Handles models with mutable collections (BatchNorm batch_stats) and a
    `train: bool` kwarg automatically. apply_fn can be overridden for exotic
    models; it receives (params, extra, x, rng, train) and returns
    (logits, new_extra) where extra is the dict of non-param collections.
    """

    def __init__(
        self,
        model,
        config: TrainerConfig,
        tx: optax.GradientTransformation | None = None,
        apply_fn: Callable | None = None,
        loss_fn: Callable[[jax.Array, jax.Array], jax.Array] = cross_entropy_loss,
        eval_metrics_fn: Callable | None = None,
        mesh: Mesh | None = None,
        partition_rules: Any = None,
        partitioner: Partitioner | None = None,
    ):
        self.config = config
        if partitioner is not None and mesh is not None \
                and mesh is not partitioner.mesh:
            raise ValueError(
                "mesh and partitioner disagree: pass one or the other "
                "(the partitioner's mesh is the one every sharding is "
                "derived over)")
        self.mesh = (
            partitioner.mesh if partitioner is not None and mesh is None
            else mesh if mesh is not None
            else build_mesh(config.mesh or MeshConfig())
        )
        # models may publish TP rules as a PARTITION_RULES attribute
        self.partition_rules = (
            partition_rules
            if partition_rules is not None
            else getattr(model, "PARTITION_RULES", None)
        )
        # the partitioner OWNS the sharding (parallel/partitioner.py):
        # model rules become its explicit top tier, the logical-axis
        # rules and FSDP heuristic sit beneath, and the trainer consumes
        # its hooks (state_shardings, constrain_grads, deterministic_rng)
        self.partitioner = partitioner or Partitioner(
            mesh=self.mesh, path_specs=self.partition_rules)
        # bf16-by-default resolution (docs/partitioner.md): may rebuild
        # the module with its family's preferred compute dtype
        self.model, self.compute_dtype = self.resolve_compute_dtype(
            model, config)
        self.loss_fn = loss_fn
        # per-example (loss, accuracy) for eval AND the train-step accuracy
        # metric; tasks whose loss shifts/masks (causal LM) supply a matching
        # metric fn so eval numbers measure what training optimizes
        self.eval_metrics_fn = eval_metrics_fn or classification_eval_metrics
        self._accepts_train = model is not None and (
            "train" in inspect.signature(model.__call__).parameters
        )
        self.apply_fn = apply_fn or self._default_apply
        # tx may be a GradientTransformation, or a FACTORY taking the
        # config-built default (warmup/cosine schedule + clipping) — so
        # wrappers like lora_tx compose with the schedule instead of
        # silently replacing it with a bare optimizer
        if tx is None:
            self.tx = self._default_tx()
        elif isinstance(tx, optax.GradientTransformation):
            self.tx = tx
        else:
            self.tx = tx(self._default_tx())
        self._jit_train_step = jax.jit(self._train_step, donate_argnums=0)
        self._fused_cache: dict[int, Callable] = {}  # n -> jitted n-step scan
        self._fused_compiled: dict[int, Any] = {}  # n -> AOT executable
        self._fused_data_cache: dict[int, Callable] = {}  # k -> data-scan
        self._fused_data_compiled: dict[int, Any] = {}  # k -> AOT executable
        self._step_compiled: Any = None  # warm_start's AOT single-step
        self._jit_eval_step = jax.jit(self._eval_step)
        self.checkpointer = (
            Checkpointer(
                config.checkpoint_dir,
                max_to_keep=config.checkpoint_max_to_keep,
                keep_best_metric=config.keep_best_metric,
                best_mode=config.best_mode,
            )
            if config.checkpoint_dir else None
        )

    def _default_apply(self, params, extra, x, rng, train):
        variables = {"params": params, **extra}
        kwargs = {"train": train} if self._accepts_train else {}
        rngs = {"dropout": rng}
        if train:
            # 'losses' is a write-only output collection (MoE aux etc.);
            # it is popped before state update (sow would otherwise
            # accumulate across steps if fed back in via variables)
            mutable = list(extra) + ["losses"]
            logits, updates = self.model.apply(
                variables, x, rngs=rngs, mutable=mutable, **kwargs
            )
            return logits, dict(updates)
        return self.model.apply(variables, x, rngs=rngs, **kwargs), extra

    def _default_tx(self) -> optax.GradientTransformation:
        c = self.config
        lr: Any = c.learning_rate
        if c.lr_schedule == "cosine":
            if c.steps is None:
                raise ValueError("lr_schedule=cosine requires TrainerConfig.steps")
            lr = optax.warmup_cosine_decay_schedule(
                init_value=0.0 if c.warmup_steps else c.learning_rate,
                peak_value=c.learning_rate,
                warmup_steps=c.warmup_steps,
                decay_steps=c.steps,
                end_value=c.learning_rate * c.lr_final_fraction,
            )
        elif c.warmup_steps:
            lr = optax.linear_schedule(0.0, c.learning_rate, c.warmup_steps)
        opt = (
            optax.adamw(lr, weight_decay=c.weight_decay)
            if c.weight_decay
            else optax.adam(lr)
        )
        if c.grad_clip_norm > 0:
            opt = optax.chain(optax.clip_by_global_norm(c.grad_clip_norm), opt)
        return opt

    # -------------------------------------------------------------- dtype

    @staticmethod
    def resolve_compute_dtype(model, config: TrainerConfig,
                              backend: str | None = None):
        """bf16-by-default policy (ROADMAP item 5): returns the (possibly
        rebuilt) module and the resolved compute dtype.

        An explicit config.compute_dtype always wins verbatim — passing
        jnp.float32 is the bf16 opt-out. Under AUTO (None), a model
        publishing PREFERRED_COMPUTE_DTYPE (the MXU-heavy families) gets
        that dtype on accelerator backends, and the module is REBUILT
        (flax clone) with its internal compute dtype flipped so the
        matmuls genuinely run in bf16 — a trainer-side input cast alone
        would be promoted straight back to f32 by dtype-pinned modules.
        Params stay f32 (flax param_dtype is separate). CPU resolves
        AUTO to f32: there is no MXU to feed, and emulated bf16 is
        strictly slower. `backend` is injectable so the bf16 numerics
        gate can exercise the accelerator policy on the CPU suite."""
        if config.compute_dtype is not None:
            return model, config.compute_dtype
        pref = getattr(model, "PREFERRED_COMPUTE_DTYPE", None)
        backend = backend or jax.default_backend()
        if pref is None or backend == "cpu":
            return model, jnp.float32
        return Trainer._module_with_dtype(model, pref), pref

    @staticmethod
    def _module_with_dtype(model, dt):
        """Rebuild a flax module with its compute dtype flipped: cfg-style
        models (GPT/BERT/ViT carry a frozen config dataclass with a
        `dtype` field) get a replaced cfg, attr-style models (ResNet) a
        cloned attr; anything else is returned unchanged (the input cast
        still applies)."""
        import dataclasses

        cfg = getattr(model, "cfg", None)
        if dataclasses.is_dataclass(cfg) and hasattr(cfg, "dtype"):
            return model.clone(cfg=dataclasses.replace(cfg, dtype=dt))
        if hasattr(model, "dtype"):
            try:
                return model.clone(dtype=dt)
            except TypeError:
                return model
        return model

    # ------------------------------------------------------------------ init

    def _state_builder(self, sample_x: np.ndarray):
        """The state-construction closure shared by init_state (concrete)
        and abstract_state (shape-only)."""
        rng = jax.random.PRNGKey(self.config.seed)
        p_rng, s_rng = jax.random.split(rng)
        x = self._cast(jnp.asarray(sample_x))
        kwargs = {"train": False} if self._accepts_train else {}

        def build(x):
            variables = dict(self.model.init(p_rng, x, **kwargs))
            params = variables.pop("params")
            variables.pop("losses", None)  # output collection, not state
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.tx.init(params),
                rng=s_rng,
                extra=variables,
            )

        return build, x

    def init_state(self, sample_x: np.ndarray) -> TrainState:
        """The seeded state, built on the device by one jitted program
        (`jit_init_state` in the start-up log). The region `train.init_state`
        is the host's time until that program is built and enqueued, not
        until the device has run it."""
        # Build INSIDE jit with the shardings constrained in-graph: params
        # materialize directly sharded (never replicated on one device first
        # — required for models bigger than a single chip's HBM), and the
        # outputs carry the same concrete compiled layouts the train step
        # emits, so the step's jit cache sees ONE input specialization. A
        # host-side build + device_put leaves layout=None, and the second
        # train_step call then pays a full re-specialization — on TPU a
        # second multi-second remote compile inside what should be
        # steady-state stepping. (with_sharding_constraint rather than jit
        # out_shardings: the latter's outputs also keep layout=None and the
        # re-specialization returns.) deterministic_rng: partitionable
        # threefry, so the constrained build draws the SAME bits the
        # single-device build would — the layout-invariant-init contract
        # the fsdp-vs-single numerics tests pin (parallel/partitioner.py).
        with cc.region("train.init_state"):
            build, x = self._state_builder(sample_x)
            with jax.set_mesh(self.mesh), \
                    self.partitioner.deterministic_rng():
                abstract = jax.eval_shape(build, x)
                shardings = self.partitioner.state_shardings(abstract)

                def init_state(x):
                    return jax.tree.map(
                        jax.lax.with_sharding_constraint, build(x), shardings)

                return jax.jit(init_state)(x)

    @staticmethod
    def param_placement(state: TrainState) -> dict[str, int]:
        """Where the parameters live, read off the arrays themselves: how
        many distinct devices their shardings name, their total bytes, and
        the bytes one device holds (equal to the total when nothing is
        partitioned). Printed once by fit() beside the device line."""
        leaves = jax.tree.leaves(state.params)
        devices = set().union(*(a.sharding.device_set for a in leaves))
        return {
            "param_devices": len(devices),
            "param_bytes": sum(a.nbytes for a in leaves),
            "param_bytes_per_device": sum(
                a.addressable_shards[0].data.nbytes for a in leaves),
        }

    def abstract_state(self, sample_x: np.ndarray):
        """Sharded ShapeDtypeStructs of the train state — no parameter
        materialization. Feeds compile-only validation at production dims
        (VERDICT r3 weak #5: tiny-shape dryruns can't catch real-dim
        divisibility/partitioning bugs; lowering+compiling the step over
        abstract args can, at any model size, in seconds)."""
        build, x = self._state_builder(sample_x)
        with jax.set_mesh(self.mesh):
            abstract = jax.eval_shape(build, x)
            shardings = self.partitioner.state_shardings(abstract)
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                abstract, shardings,
            )

    def compile_check(self, sample_x: np.ndarray, sample_y=None):
        """AOT-lower and XLA-compile ONE train step over abstract sharded
        args (production dims, zero parameter memory). Returns the compiled
        executable; raises on any trace-time divisibility error or
        compile-time partitioning failure."""
        abstract = self.abstract_state(sample_x)
        x_sds = jax.ShapeDtypeStruct(
            np.shape(sample_x), np.asarray(sample_x).dtype)
        y_sds = (jax.ShapeDtypeStruct(np.shape(sample_y),
                                      np.asarray(sample_y).dtype)
                 if sample_y is not None
                 else jax.ShapeDtypeStruct((np.shape(sample_x)[0],), np.int32))
        with jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            return jax.jit(self._train_step, donate_argnums=0).lower(
                abstract, (x_sds, y_sds)).compile()

    #: donation gate threshold: leaves at or above this many BYTES must
    #: alias (the params/opt-state weights whose double-buffering is the
    #: HBM cost donation exists to erase). Sub-threshold leaves (biases,
    #: norm scales — a few hundred bytes) are reported, not gated: XLA's
    #: allocator may pack/skip aliasing tiny buffers at its discretion,
    #: and their copies are noise at real model sizes.
    DONATION_MIN_BYTES = 4096

    def donation_stats(self, sample_x, sample_y,
                       fused_k: int | None = None) -> dict:
        """Buffer-donation accounting straight off the compiled step.

        The optimizer update runs INSIDE the one jitted step with the
        state donated (donate_argnums=0 on the single step, the n-scan
        and the k-data-scan alike), so params/opt-state update in place
        — at real model sizes an un-donated step doubles peak HBM. This
        parses the input_output_alias table of the lowered executable
        and maps aliased entry parameters back to state leaves:
        `unexpected_copies` counts leaves >= DONATION_MIN_BYTES that
        FAILED to alias an output buffer (budget 0 — gated by
        tests/test_partitioner.py); `unaliased_small` the sub-threshold
        remainder (reported only — tiny-buffer packing is backend
        discretion). Everything comes from the compiled HLO, so a
        regression in donation coverage (a dtype mismatch breaking the
        alias, a new un-donated state leaf) is caught at lower time with
        no device run."""
        import re as _re

        def stats_of(compiled, leaves):
            alias_lines = [l for l in compiled.as_text().splitlines()
                           if "input_output_alias" in l]
            # entry form: `{out_idx...}: (param_number, {...}, may-alias)`
            # — state leaves flatten to entry params 0..N-1 (donated args
            # come first), so the param number IS the leaf index
            aliased = set()
            for line in alias_lines:
                aliased.update(int(p) for p in _re.findall(
                    r"\((\d+), \{[^)]*?\}, (?:may|must)-alias\)", line))
            big_missing, small_missing = [], []
            for i, (path, leaf) in enumerate(leaves):
                if i in aliased:
                    continue
                size = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                (big_missing if size >= self.DONATION_MIN_BYTES
                 else small_missing).append(
                    f"{'/'.join(str(getattr(k, 'key', k)) for k in path)}"
                    f":{size}B")
            return {"aliased": len(aliased & set(range(len(leaves)))),
                    "state_leaves": len(leaves),
                    "unexpected_copies": len(big_missing),
                    "unaliased_big": big_missing,
                    "unaliased_small": len(small_missing)}

        sample_y = np.asarray(sample_y)
        abstract = self.abstract_state(sample_x)
        leaves = jax.tree_util.tree_leaves_with_path(abstract)
        x_sds = jax.ShapeDtypeStruct(
            np.shape(sample_x), np.asarray(sample_x).dtype)
        y_sds = jax.ShapeDtypeStruct(sample_y.shape, sample_y.dtype)
        with jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            step = jax.jit(self._train_step, donate_argnums=0).lower(
                abstract, (x_sds, y_sds)).compile()
            out = {"train_step": stats_of(step, leaves)}
            if fused_k:
                xs = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        (fused_k, *s.shape), s.dtype), (x_sds, y_sds))
                comp = self._fused_data_fn(fused_k).lower(
                    abstract, xs).compile()
                out[f"train_chunk_{fused_k}"] = stats_of(comp, leaves)
        return out

    # ------------------------------------------------------------------ steps

    def _cast(self, x):
        """Cast float leaves to the RESOLVED compute dtype; ints (token
        ids) untouched."""
        dt = self.compute_dtype
        return jax.tree.map(
            lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating) else a, x
        )

    def _loss_of(self, params, extra, x, y, rng):
        # the device scopes of the step (train.cast, train.loss,
        # train.grad_norm, train.optimizer) are names in the operator's
        # profile (docs/observability.md); they change no instruction
        with jax.named_scope("train.loss"):
            logits, new_extra = self.apply_fn(params, extra, x, rng, True)
            loss = self.loss_fn(logits.astype(jnp.float32), y)
            # auxiliary objectives sown into the 'losses' collection (e.g.
            # MoE load-balance, parallel/moe.py) join the objective here;
            # popped so they never persist into TrainState.extra
            aux = new_extra.pop("losses", None) if isinstance(new_extra, dict) else None
            if aux:
                loss = loss + sum(
                    jnp.asarray(a, jnp.float32) for a in jax.tree.leaves(aux)
                )
        return loss, (logits, new_extra)

    def _train_step(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        x, y = batch
        step_rng = jax.random.fold_in(state.rng, state.step)
        with jax.named_scope("train.cast"):
            x = self._cast(x)
        n_acc = max(self.config.grad_accum_steps, 1)
        x, y = _corrupted(self.model, step_rng, x, y)  # on this line for ROADMAP D17: see there
        if n_acc == 1:
            (loss, (logits, new_extra)), grads = jax.value_and_grad(
                self._loss_of, has_aux=True
            )(state.params, state.extra, x, y, step_rng)
            # comm/compute overlap (docs/partitioner.md): pin every
            # gradient to its param's rule-derived layout HERE, where
            # backward produces it — XLA's scheduler can then start each
            # gradient's reduce-scatter/all-reduce while the rest of the
            # backward is still running, instead of one serialized
            # all-reduce after it (1909.09756's first MFU front)
            grads = self.partitioner.constrain_grads(grads)
            acc = self.eval_metrics_fn(logits.astype(jnp.float32), y)[1].mean()
        else:
            # microbatch scan: grads averaged across n_acc slices before ONE
            # optimizer update — big effective batches without extra memory
            mb = x.shape[0] // n_acc
            if mb * n_acc != x.shape[0]:
                raise ValueError(
                    f"batch {x.shape[0]} not divisible by "
                    f"grad_accum_steps {n_acc}"
                )
            xs = jax.tree.map(
                lambda a: a.reshape(n_acc, mb, *a.shape[1:]), (x, y)
            )

            def micro(carry, mb_xy):
                grads_acc, loss_acc, acc_acc, extra, i = carry
                mx, my = mb_xy
                rng_i = jax.random.fold_in(step_rng, i)
                (l, (lg, new_extra)), g = jax.value_and_grad(
                    self._loss_of, has_aux=True
                )(state.params, extra, mx, my, rng_i)
                # per-microbatch constraint: under accumulation the
                # overlap window is each microbatch's backward, so the
                # collective is pinned where that backward emits it
                g = self.partitioner.constrain_grads(g)
                a = self.eval_metrics_fn(lg.astype(jnp.float32), my)[1].mean()
                grads_acc = jax.tree.map(jnp.add, grads_acc, g)
                return (grads_acc, loss_acc + l, acc_acc + a, new_extra,
                        i + 1), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (grads, loss, acc, new_extra, _), _ = jax.lax.scan(
                micro,
                (zeros, jnp.float32(0), jnp.float32(0), state.extra,
                 jnp.int32(0)),
                xs,
            )
            # back to the param dtype so both accumulation modes feed the
            # optimizer identically-typed grads
            grads = jax.tree.map(
                lambda g, p: (g / n_acc).astype(p.dtype), grads, state.params
            )
            loss, acc = loss / n_acc, acc / n_acc

        # clipping, where configured, is the first link of tx's chain
        with jax.named_scope("train.optimizer"):
            updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state, extra=new_extra
        )
        # global grad-norm as a first-class metric: the standard training
        # health signal (divergence shows here before the loss moves), and
        # the finiteness witness the real-dim composed execution test pins.
        # Traced last, as it was before it had a scope: the scopes are
        # metadata and must leave the step's instructions, and so its key in
        # the compile cache, as they were
        with jax.named_scope("train.grad_norm"):
            grad_norm = optax.global_norm(grads)
        return new_state, _step_metrics(self.model, loss, acc, grad_norm, new_extra, y)

    def _eval_step(self, state: TrainState, batch) -> dict:
        x, y, w = batch  # w: validity mask for padded tail batches
        # a model that corrupts its batch is evaluated under one fixed draw
        x, y = _corrupted(self.model, state.rng, self._cast(x), y)
        logits, _ = self.apply_fn(state.params, state.extra, x, state.rng, False)
        logits = logits.astype(jnp.float32)
        per_ex, acc = self.eval_metrics_fn(logits, y)
        return {
            "loss_sum": (per_ex * w).sum(),
            "correct": (acc * w).sum(),
            "count": w.sum(),
        }

    @property
    def _process_local(self) -> bool:
        return self.config.data_placement == "process_local"

    def _place(self, batch):
        return shard_batch(batch, self.mesh, process_local=self._process_local)

    def train_step(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        """Enqueue one optimizer step; returns before the device has run
        it. `train.enqueue` is everything the host does in here to start
        the step (with `train.place_batch`, the batch's placement, inside
        it): on the profiler's clock while a jax.profiler session records,
        in the flight recorder while a Tracer is armed. A step that had to
        build its program first (the first of a process, a new batch
        shape) says so: `built`, the programs the backend compiled or
        loaded under it, which the start-up log names."""
        tracer = get_tracer()
        built = cc.programs_built()
        # ambient mesh enables P-form with_sharding_constraint pins inside
        # models (bert.constrain) without threading the mesh through
        # modules; deterministic_rng keeps traced random draws (dropout,
        # fold_in) layout-invariant — see init_state
        with tracer.span(
                "train.enqueue",
                path="executable" if self._step_compiled is not None else "jit",
        ) as sp, jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            try:
                with tracer.span("train.place_batch"):
                    placed = self._place(batch)
                if self._step_compiled is not None:
                    try:
                        # warm_start's executable (reloaded from the
                        # compile cache on a restarted incarnation, or
                        # AOT-compiled at setup) — same program as the jit
                        # path; a signature mismatch falls through to jit
                        # dispatch ONCE and drops the executable (retrying
                        # every step would put a raise/catch on the hot
                        # path this PR exists to thin)
                        return self._step_compiled(state, placed)
                    except (TypeError, ValueError):
                        self._step_compiled = None
                        sp.set_attribute("path", "jit")
                return self._jit_train_step(state, placed)
            finally:
                if cc.programs_built() != built:
                    sp.set_attribute(
                        "built", cc.programs_built() - built)

    def train_steps_fused(
        self, state: TrainState, batch, n: int
    ) -> tuple[TrainState, dict]:
        """Run n optimizer steps in ONE jit dispatch — a lax.scan over the
        step with a constant (device-resident) batch.

        The TPU-idiomatic loop shape for on-device data: host dispatch
        overhead is paid once per n steps instead of per step, and XLA can
        pipeline across iterations.
        The per-step rng still varies (the step counter folds into the key
        inside _train_step). Returns the final state and the LAST step's
        metrics. Real `fit` keeps per-step dispatch — host data arrives per
        step and prefetch overlaps the transfer — but benches and synthetic-
        data loops should use this."""
        with jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            batch = self._place(batch)
            compiled = self._fused_compiled.get(n)
            if compiled is not None:
                try:
                    # reuse the AOT executable compile_fused built — same n,
                    # same shapes is the common case; a signature mismatch
                    # falls through to the jit dispatch path (which traces
                    # and compiles for the new avals)
                    return compiled(state, batch)
                except (TypeError, ValueError):
                    pass
            return self._fused_fn(n)(state, batch)

    def _fused_builder(self, n: int, scanned_data: bool):
        """jit'd n-step scan over _train_step, returning the LAST step's
        metrics. scanned_data=False: the batch is a scan-invariant constant
        (benches); True: the batch is the scanned xs, one (B, ...) slice per
        step from a stacked (n, B, ...) chunk (fit's steady state)."""
        cache = self._fused_data_cache if scanned_data else self._fused_cache
        fn = cache.get(n)
        if fn is None:

            def many(state, batch):
                def body(s, b):
                    return self._train_step(s, batch if not scanned_data else b)

                state, ms = jax.lax.scan(
                    body, state,
                    batch if scanned_data else None,
                    length=None if scanned_data else n,
                )
                return state, jax.tree.map(lambda v: v[-1], ms)

            fn = jax.jit(many, donate_argnums=0)
            cache[n] = fn
        return fn

    def _fused_fn(self, n: int):
        return self._fused_builder(n, scanned_data=False)

    def _fused_data_fn(self, k: int):
        return self._fused_builder(k, scanned_data=True)

    def train_chunk(self, state: TrainState, stacked, k: int):
        """Run k steps over a host-stacked chunk (k, B, ...) in one dispatch."""
        with jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            s = stacked_batch_sharding(self.mesh)
            place = put_process_local if self._process_local else put_global
            xs = jax.tree.map(lambda a: place(a, s), stacked)
            compiled = self._fused_data_compiled.get(k)
            if compiled is not None:
                try:
                    # warm_start's k-scan executable — same
                    # drop-on-mismatch contract as train_step
                    return compiled(state, xs)
                except (TypeError, ValueError):
                    self._fused_data_compiled.pop(k, None)
            return self._fused_data_fn(k)(state, xs)

    def compile_fused(self, state: TrainState, batch, n: int):
        """AOT-compile the n-step fused program WITHOUT executing it.

        Returns (compiled, placed_batch): the executable is cached so a
        later train_steps_fused(n) with the same shapes reuses it instead of
        paying a second trace+compile, and placed_batch is DEVICE-BORN (a
        jit output) — the single placement site benches rely on.
        `compiled(state, placed_batch)` runs with the jit-declared state
        donation."""
        with jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            batch = self._place(batch)
            batch = jax.jit(lambda t: jax.tree.map(lambda a: a + 0, t))(batch)
            compiled = self._fused_fn(n).lower(state, batch).compile()
            self._fused_compiled[n] = compiled
        return compiled, batch

    # ----------------------------------------------------------- warm start

    def _executable_key(self, placed_batch, kind: str) -> str:
        """Everything that changes the compiled step program, folded into
        one content key (utils/compile_cache.executable_key adds jax
        version + backend). Functions are keyed by qualname + a hash of
        their BYTECODE (co_code/co_consts), so editing a custom loss_fn's
        body invalidates the cached binary; closure VALUES and code the
        function merely calls are not captured — a cache dir shared
        across such changes should be cleared (the entries are otherwise
        content-addressed and safe to share)."""
        import functools
        import hashlib

        c = self.config

        def _code_blob(code) -> bytes:
            # recursive bytecode fingerprint: nested functions/lambdas are
            # code objects inside co_consts whose repr carries a memory
            # address — descend into them instead of repr'ing (the same
            # key-poison the model repr is scrubbed of below)
            parts = [code.co_code]
            for const in code.co_consts:
                if hasattr(const, "co_code"):
                    parts.append(_code_blob(const))
                else:
                    parts.append(repr(const).encode())
            return b"|".join(parts)

        def _fn_id(fn) -> str:
            if isinstance(fn, functools.partial):
                kw = sorted((fn.keywords or {}).items())
                return (f"partial({_fn_id(fn.func)},"
                        f"args={fn.args!r},kw={kw!r})")
            code = getattr(fn, "__code__", None) or getattr(
                getattr(type(fn), "__call__", None), "__code__", None)
            name = getattr(fn, "__qualname__", None) or type(fn).__name__
            if code is not None:
                digest = hashlib.sha256(_code_blob(code)).hexdigest()[:12]
                return f"{name}#{digest}"
            # no bytecode to fingerprint (C callable): name-only — stable
            # across processes, unlike a repr carrying a memory address
            return name

        import re

        batch_avals = jax.tree.map(
            lambda a: (tuple(a.shape), str(a.dtype)), placed_batch)
        # default object reprs carry a memory address — key-poison that
        # would make every process miss; strip it so such models key by
        # class (weaker, but stable) while flax reprs keep their fields
        model_repr = re.sub(r" at 0x[0-9a-fA-F]+", "", repr(self.model))
        return cc.executable_key(
            kind=kind,
            model=model_repr,
            apply_fn=_fn_id(self.apply_fn),
            loss_fn=_fn_id(self.loss_fn),
            eval_metrics_fn=_fn_id(self.eval_metrics_fn),
            mesh=tuple(sorted(self.mesh.shape.items())),
            batch=batch_avals,
            # the RESOLVED dtype (bf16-by-default may differ from the
            # config literal) and the partitioner's whole rule surface:
            # a cached binary compiled under different sharding rules or
            # compute dtype must never be replayed (PR-10's restart-warm
            # zero-compile guarantee survives because the key moves with
            # these knobs instead of silently matching)
            compute_dtype=str(jnp.dtype(self.compute_dtype)),
            partition=tuple(sorted(
                (k, repr(v))
                for k, v in self.partitioner.key_fields().items())),
            # total steps enter the program only as the cosine decay span:
            # a restarted job asked for more steps keeps its executable
            opt=(c.learning_rate, c.weight_decay, c.grad_clip_norm,
                 c.lr_schedule, c.lr_final_fraction, c.warmup_steps,
                 c.steps if c.lr_schedule == "cosine" else None,
                 c.grad_accum_steps),
        )

    def warm_start(self, sample_x, sample_y, cache_dir: str = "",
                   fused_k: int = 1) -> dict:
        """Make the train-step executables exist WITHOUT paying a backend
        compile on a restarted incarnation (ROADMAP item 5; the restart-
        recompile cost of 2011.03641).

        Enables the persistent XLA cache at `cache_dir` (or the resolved
        config/env dir), then per program (single step; plus the k-step
        data-scan when fused_k > 1): reload the serialized executable by
        content key — trace AND compile skipped — else AOT-compile it
        (backend compile served from the persistent cache when warm) and
        serialize it for the next incarnation. Returns the attribution
        dict fit() stamps on its train.compile span; no-op ({"enabled":
        False}) when no cache dir resolves anywhere."""
        cache_dir = cc.resolve_cache_dir(
            cache_dir or self.config.compile_cache_dir)
        if not cache_dir:
            return {"enabled": False}
        cc.enable_persistent_cache(cache_dir)
        before = cc.compile_counts()
        reloaded: list[str] = []
        compiled_now: list[str] = []
        sample_x = np.asarray(sample_x)
        sample_y = np.asarray(sample_y)
        # the per-step loop feeds each process ONLY its slice of the
        # global batch (fit's per-step path divides batch_size by the
        # process count under process_local); the fused k-scan stacks
        # FULL batches — warm each program at the exact shape it will see
        local = max(len(sample_x) // (jax.process_count()
                                      if self._process_local else 1), 1)
        with jax.set_mesh(self.mesh), self.partitioner.deterministic_rng():
            # the content key needs only the batch avals (+ config/mesh);
            # the abstract state — an eval_shape trace of the whole model
            # build — is built LAZILY, only when something must actually
            # compile: on the warm path the reload skips tracing entirely
            abstract = None

            def _abstract():
                nonlocal abstract
                if abstract is None:
                    abstract = self.abstract_state(sample_x[:local])
                return abstract

            placed = self._place((sample_x[:local], sample_y[:local]))
            key = self._executable_key(placed, kind="train_step")
            devices = self.mesh.devices.flat
            loaded = cc.load_executable(cache_dir, key, devices)
            if loaded is None:
                loaded = self._jit_train_step.lower(
                    _abstract(), placed).compile()
                cc.save_executable(cache_dir, key, loaded)
                compiled_now.append("train_step")
            else:
                reloaded.append("train_step")
            self._step_compiled = loaded
            if fused_k > 1:
                s = stacked_batch_sharding(self.mesh)
                place = (put_process_local if self._process_local
                         else put_global)
                stacked = tuple(
                    np.stack([a] * fused_k) for a in (sample_x, sample_y))
                xs = jax.tree.map(lambda a: place(a, s), stacked)
                kkey = self._executable_key(
                    xs, kind=f"train_chunk_{fused_k}")
                kc = cc.load_executable(cache_dir, kkey, devices)
                if kc is None:
                    kc = self._fused_data_fn(fused_k).lower(
                        _abstract(), xs).compile()
                    cc.save_executable(cache_dir, kkey, kc)
                    compiled_now.append(f"train_chunk_{fused_k}")
                else:
                    reloaded.append(f"train_chunk_{fused_k}")
                self._fused_data_compiled[fused_k] = kc
        after = cc.compile_counts()

        def since(counter):
            return after[counter] - before[counter]

        return {
            "enabled": True,
            "cache_dir": cache_dir,
            "key": key,
            "reloaded": ",".join(reloaded),
            "compiled": ",".join(compiled_now),
            "backend_misses": since("backend_misses_total"),
            "backend_requests": since("requests_total"),
            "cache_hits": since("cache_hits_total"),
            "trace_s": since("trace_seconds_total"),
            "lower_s": since("lower_seconds_total"),
            "backend_s": since("backend_seconds_total"),
        }

    # ------------------------------------------------------------------- fit

    def fit(
        self,
        dataset: Dataset,
        *,
        resume: bool = True,
        on_epoch_end: Callable[[int, dict], None] | None = None,
    ) -> tuple[TrainState, dict]:
        import os

        profile_dir = self.config.profile_dir or os.environ.get(
            ENV_PROFILE_DIR, ""
        )
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
            try:
                return self._fit(dataset, resume=resume, on_epoch_end=on_epoch_end)
            finally:
                jax.profiler.stop_trace()
                metrics_lib.emit(profile_trace_written=1)
        return self._fit(dataset, resume=resume, on_epoch_end=on_epoch_end)

    def _fit(
        self,
        dataset: Dataset,
        *,
        resume: bool = True,
        on_epoch_end: Callable[[int, dict], None] | None = None,
    ) -> tuple[TrainState, dict]:
        import os

        c = self.config
        # Enable the persistent compile cache BEFORE the first compile:
        # jax latches the cache state at first use, so enabling it after
        # init_state would leave this process's cache writes silently
        # skipped (see utils/compile_cache.enable_persistent_cache).
        cache_dir = cc.resolve_cache_dir(c.compile_cache_dir)
        if cache_dir:
            cc.enable_persistent_cache(cache_dir)
        # the device this fit actually runs on, as jax reports it — what
        # the smoke test and every benchmark row read instead of guessing
        metrics_lib.emit(**device_summary())
        # Tracing: the installed tracer, else one from the pod env contract
        # (KFTPU_TRACE_DIR — the controller injects it when the platform
        # traces with a trace_dir; init_worker_from_env keeps an already-
        # installed tracer and is a no-op without the env). Untraced runs
        # get the NOOP tracer: every span call below is then a shared
        # inert object, off the hot path. Before init_state, whose region
        # and builds are the first spans of a traced job.
        tracer = init_worker_from_env(service="trainer")
        state = self.init_state(dataset.x_train[: c.batch_size])
        metrics_lib.emit(**self.param_placement(state))

        event_dir = c.event_dir or os.environ.get(ENV_EVENT_DIR, "")
        events = metrics_lib.TfEventsWriter(event_dir) if event_dir else None

        start_step = 0
        if resume and self.checkpointer is not None:
            with tracer.span("checkpoint.restore") as sp:
                restored = self.checkpointer.restore_latest(state)
                sp.set_attribute(
                    "step", restored[0] if restored is not None else -1)
            if restored is not None:
                start_step, state = restored
                metrics_lib.emit(step=start_step, resumed=1)

        # Restart-warm compile (docs/perf.md "MFU hunt"): with a compile
        # cache configured (config or the pod env the jobcontroller
        # injects), pin the step executables NOW under a train.compile
        # span — so a restarted incarnation's recompile cost is zero
        # backend compiles, and the profiler can split restart overhead
        # into compile vs restore vs schedule. Without a cache dir this
        # is a no-op and the first step compiles inline, as before.
        if cache_dir:
            per_epoch = len(dataset.x_train) // c.batch_size
            with cc.region("train.compile") as sp:
                info = self.warm_start(
                    dataset.x_train[:c.batch_size],
                    dataset.y_train[:c.batch_size],
                    fused_k=min(c.fused_steps, max(per_epoch, 1)),
                )
                for k, v in info.items():
                    sp.set_attribute(k, v)

        # TPU preemption contract: on SIGTERM save a checkpoint and exit
        # cleanly so the gang restart resumes instead of losing the epoch
        # (signals only bind on the main thread; elsewhere skip silently).
        # The previous handler is restored when fit() returns.
        preempted = {"flag": False}
        prev_handler = None
        if self.checkpointer is not None:
            import signal as _signal

            def _on_term(signum, frame):
                preempted["flag"] = True

            try:
                prev_handler = _signal.signal(_signal.SIGTERM, _on_term)
            except ValueError:
                pass
        try:
            return self._fit_loop(
                dataset, c, state, start_step, events, preempted,
                on_epoch_end, tracer,
            )
        finally:
            if prev_handler is not None:
                import signal as _signal

                try:
                    _signal.signal(_signal.SIGTERM, prev_handler)
                except ValueError:
                    pass

    def _fit_loop(self, dataset, c, state, start_step, events, preempted,
                  on_epoch_end, tracer=None):
        """The step loop of fit(). Its spans: `train.step` around each
        `train_step` call — on an asynchronous backend that is the time to
        ENQUEUE the step (`train.enqueue` and `train.place_batch` are its
        children), never the device's step time, which only the device
        trace has (`step_ms.train`); `train.wait_for_metrics` where the
        host does wait for the device, to read the log line's numbers;
        `train.data_load`, `train.eval`, `checkpoint.save`."""
        import os

        if tracer is None:
            tracer = get_tracer()

        # liveness contract (docs/health.md): one heartbeat per optimizer
        # step through the pod env's KFTPU_HEARTBEAT_FILE — the lease the
        # platform's hang detector judges this worker by. None (no env) for
        # standalone runs; beat() throttles itself, so this is off the hot
        # path either way.
        from kubeflow_tpu.health import HeartbeatWriter

        hb = HeartbeatWriter.from_env()
        if hb is not None:
            hb.beat(step=start_step, phase="fit-start")

        def save_ckpt(step, st, metrics=None):
            with tracer.span("checkpoint.save", step=step):
                self.checkpointer.save(step, st, metrics=metrics)

        per_epoch = len(dataset.x_train) // c.batch_size
        if per_epoch == 0:
            raise ValueError(
                f"batch_size {c.batch_size} exceeds train set size "
                f"{len(dataset.x_train)}: no full batch can be formed"
            )
        total_steps = c.steps if c.steps is not None else c.epochs * per_epoch
        timer = metrics_lib.Timer()
        global_step = start_step
        last = {}

        epoch = global_step // max(per_epoch, 1)

        # Per-batch-of-steps bookkeeping, shared by both stepping modes.
        # Returns True when fit must stop (preemption). `took` is how many
        # optimizer steps the dispatch covered; log/checkpoint fire when
        # their cadence boundary falls inside the chunk.
        stop = {"flag": False}
        last_eval: list = [None]  # newest eval metrics (best-mode saves)
        es_best, es_bad = None, 0  # early-stopping plateau tracking

        def after(took: int, m) -> bool:
            nonlocal global_step, last
            global_step += took
            if hb is not None:
                hb.beat(step=global_step)
            timer.tick(items=took * c.batch_size, steps=took)
            if (global_step % c.log_every_steps) < took or global_step == total_steps:
                # reading a metric is where the host waits for the device
                with tracer.span("train.wait_for_metrics", step=global_step):
                    last = {k: float(v) for k, v in m.items()}
                metrics_lib.emit(
                    step=global_step,
                    **last,
                    images_per_sec=timer.items_per_sec,
                    steps_per_sec=timer.steps_per_sec,
                )
                if events is not None:
                    events.scalars(
                        global_step, **last,
                        images_per_sec=timer.items_per_sec,
                    )
            if preempted["flag"]:
                # rescue saves carry NO metrics: orbax preserves metric-less
                # checkpoints outside the BestN ranking
                # (keep_checkpoints_without_metrics), so the rescue is never
                # GC'd as "not best", never mislabeled with stale metrics,
                # and never returned by best_step — while restore_latest
                # still resumes from it
                save_ckpt(global_step, state)
                self.checkpointer.wait()
                metrics_lib.emit(step=global_step, preempted=1)
                stop["flag"] = True
                return True
            if (
                self.checkpointer is not None
                and not c.keep_best_metric
                and (global_step % c.checkpoint_every_steps) < took
            ):
                save_ckpt(global_step, state)
            return False

        while global_step < total_steps:
            # Steady-state stepping: per-step dispatch with double-buffered
            # host->device prefetch (transfer off the critical path), or —
            # fused_steps > 1 — full chunks of exactly fused_steps run as ONE
            # k-step lax.scan dispatch (host dispatch amortized, one stacked
            # upload). Epoch tails and the total_steps boundary fall back to
            # per-step dispatch so numerics never depend on the chunking and
            # compile count stays at two programs (k-scan + single step).
            # a chunk can never exceed an epoch: without the clamp, a
            # too-large fused_steps would silently run everything per-step
            # AND without prefetch — worse than fused_steps=1
            fused_k = min(c.fused_steps, per_epoch)
            if fused_k > 1:
                k = fused_k
                pending: list = []
                batch_src = batches(
                    dataset.x_train, dataset.y_train, c.batch_size,
                    seed=c.seed + epoch,
                )
                if tracer.enabled:
                    batch_src = _traced_data_iter(tracer, batch_src)
                for b in batch_src:
                    if global_step >= total_steps or stop["flag"]:
                        break
                    if total_steps - global_step >= k:
                        pending.append(b)
                        if len(pending) == k:
                            stacked = tuple(
                                np.stack(z) for z in zip(*pending)
                            )
                            pending = []
                            with tracer.span("train.chunk",
                                             step=global_step, steps=k):
                                state, m = self.train_chunk(state, stacked, k)
                            if after(k, m):
                                break
                    else:
                        with tracer.span("train.step", step=global_step):
                            state, m = self.train_step(state, b)
                        if after(1, m):
                            break
                # epoch tail smaller than a chunk: per-step
                for b in pending:
                    if global_step >= total_steps or stop["flag"]:
                        break
                    with tracer.span("train.step", step=global_step):
                        state, m = self.train_step(state, b)
                    if after(1, m):
                        break
            else:
                raw = batches(
                    dataset.x_train, dataset.y_train,
                    # process_local: each host feeds its 1/P slice of
                    # the GLOBAL batch (equal counts guaranteed by
                    # load_dataset_shards), keeping step counts in
                    # lockstep across the gang
                    c.batch_size // (jax.process_count()
                                     if self._process_local else 1),
                    seed=c.seed + epoch,
                )
                loader = None
                if c.async_loader:
                    # batch assembly + host sharding on a background
                    # thread (train/data.AsyncLoader): shard_batch's
                    # device_put is asynchronous, so the transfer also
                    # starts ahead of consumption — the double-buffered
                    # prefetch's overlap, plus the host work itself off
                    # the step critical path
                    loader = AsyncLoader(
                        raw,
                        transform=lambda b: shard_batch(
                            b, self.mesh,
                            process_local=self._process_local),
                        size=2,
                        mesh=self.mesh,
                    )
                    batch_src = loader
                else:
                    batch_src = prefetch_to_device(
                        raw, self.mesh,
                        process_local=self._process_local,
                    )
                if tracer.enabled:
                    batch_src = _traced_data_iter(
                        tracer, batch_src, stats_from=loader)
                try:
                    for bx, by in batch_src:
                        if global_step >= total_steps or stop["flag"]:
                            break
                        with tracer.span("train.step", step=global_step):
                            state, m = self.train_step(state, (bx, by))
                        if after(1, m):
                            break
                finally:
                    # every exit path (preemption, early stop, the steps
                    # boundary, an exception) joins the loader thread —
                    # an abandoned epoch must not leak its producer
                    if loader is not None:
                        loader.close()
            if stop["flag"]:
                return state, {**last, "preempted": 1.0}
            epoch += 1
            if epoch % c.eval_every_epochs == 0:
                with tracer.span("train.eval", step=global_step):
                    ev = self.evaluate(state, dataset)
                if hb is not None:
                    # evals can outlast a step-sized lease window: refresh
                    # the lease the moment the eval pass finishes
                    hb.beat(step=global_step, phase="eval")
                last_eval[0] = dict(ev)
                if self.checkpointer is not None and c.keep_best_metric:
                    # best-mode cadence: metrics only exist at evals
                    save_ckpt(global_step, state, metrics=ev)
                metrics_lib.emit(step=global_step, **{f"eval_{k}": v for k, v in ev.items()})
                last.update({f"eval_{k}": v for k, v in ev.items()})
                if events is not None:
                    events.scalars(
                        global_step, **{f"eval_{k}": v for k, v in ev.items()}
                    )
                if on_epoch_end is not None:
                    on_epoch_end(epoch, ev)
                if c.early_stop_patience > 0:
                    if c.early_stop_metric not in ev:
                        raise ValueError(
                            f"early_stop_metric {c.early_stop_metric!r} "
                            f"not in eval metrics {sorted(ev)}"
                        )
                    cur = float(ev[c.early_stop_metric])
                    # direction is early_stop_mode's, NOT best_mode's: the
                    # two knobs may track different metrics (stop on loss,
                    # keep best by accuracy)
                    sign = 1.0 if c.early_stop_mode == "max" else -1.0
                    if (es_best is None
                            or sign * cur
                            > sign * es_best + c.early_stop_min_delta):
                        es_best, es_bad = cur, 0
                    else:
                        es_bad += 1
                        if es_bad >= c.early_stop_patience:
                            metrics_lib.emit(step=global_step,
                                             early_stopped=1)
                            break

        with tracer.span("train.eval", step=global_step, final=True):
            final_eval = self.evaluate(state, dataset)
        if self.checkpointer is not None:
            save_ckpt(global_step, state, metrics=dict(final_eval))
            self.checkpointer.wait()
        metrics_lib.emit(step=global_step, **{f"final_{k}": v for k, v in final_eval.items()})
        if events is not None:
            events.scalars(
                global_step, **{f"final_{k}": v for k, v in final_eval.items()}
            )
            events.close()
        return state, {**last, **{f"final_{k}": v for k, v in final_eval.items()}}

    # ------------------------------------------------------------------ eval

    def evaluate(self, state: TrainState, dataset: Dataset) -> dict[str, float]:
        c = self.config
        bs = min(c.batch_size, len(dataset.x_test))
        # round bs down to a multiple of the batch-sharding divisor
        from kubeflow_tpu.parallel.sharding import BATCH_AXES

        div = math.prod(self.mesh.shape[a] for a in BATCH_AXES)
        bs = max(div, (bs // div) * div)
        tot_loss, correct, count = 0.0, 0, 0
        # tail batch is zero-padded to the static shape and masked, keeping
        # one compiled shape while covering every test example
        for bx, by in batches(
            dataset.x_test, dataset.y_test, bs, drop_remainder=False
        ):
            n = len(bx)
            if n < bs:
                pad = bs - n
                bx = np.concatenate([bx, np.zeros((pad, *bx.shape[1:]), bx.dtype)])
                # labels may be token-level (B, L) — pad with the full shape
                by = np.concatenate([by, np.zeros((pad, *by.shape[1:]), by.dtype)])
            w = (np.arange(bs) < n).astype(np.float32)
            with jax.set_mesh(self.mesh), \
                    self.partitioner.deterministic_rng():
                m = self._jit_eval_step(state, shard_batch((bx, by, w), self.mesh))
            tot_loss += float(m["loss_sum"])
            correct += float(m["correct"])
            count += int(m["count"])
        return {
            "loss": tot_loss / max(count, 1),
            "accuracy": correct / max(count, 1),
        }


def _step_metrics(model, loss, accuracy, grad_norm, extra, y) -> dict:
    """The step's metrics, and what the model counts for itself a step: a
    model may define `step_counters(extra, y) -> dict` of scalars out of the
    collections the Trainer carries in `TrainState.extra` (AfmoeLM: its
    routers' counters) and out of the labels the loss saw (SdarMoeLM: the
    share of positions its corruption masked). Whether it does is seen while
    tracing, so a model without it lowers to the step it always had. (The
    function sits after the class because a line added above would move the
    callers of the flash kernel, whose line numbers its Mosaic payload
    carries into the compile cache's key: ROADMAP D17.)"""
    metrics = {"loss": loss, "accuracy": accuracy, "grad_norm": grad_norm}
    counters = getattr(model, "step_counters", None)
    if counters is not None:
        metrics.update(counters(extra, y))
    return metrics


def _corrupted(model, rng, x, y):
    """The batch as the model's objective wants it. A model may publish, as
    it publishes `PARTITION_RULES` and `step_counters`, a corruption
    `corrupt(rng, x, y) -> (x', y')`: noise drawn from the step's rng
    (`fold_in(state.rng, state.step)`: a resumed job draws the same noise at
    the same step) before the forward pass, under the device scope
    `train.corrupt`. `x'` goes to `apply_fn`; `y'`, any tree whose leaves lead
    with the batch (labels, which positions were masked, their weights), to
    `loss_fn`, `eval_metrics_fn` and `step_counters`. A model that publishes
    none lowers to the step it always had."""
    corrupt = getattr(model, "corrupt", None)
    if corrupt is None:
        return x, y
    with jax.named_scope("train.corrupt"):
        return corrupt(rng, x, y)
