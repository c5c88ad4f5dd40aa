"""Runner kind `train_job_update`: `train_job`, and the first step's change of the state
compared with the reference's.

For a cell whose first loss cannot tell a lower precision from the stated one (at the
start a language model's logits are near uniform and 8k labels average rounding away:
`traffic/lm-packed-8k.json` has the readings). The family's reference takes the first step
of training itself, in float32 at `highest` (`reference_update_fn`: gradient, one step of
Adam, what else the configuration moves at a step), from the state `init_state` made; the
program takes its normal `train_step`; and `update_gap` reads how far the two states
differ, as a share of how far the reference moved: 0 is the same step, 1 is a state left
as it was, 1.41 a step of the same length in an unrelated direction. That sees what the
loss cannot: the backward pass of every layer, the optimizer, the sign and size of the
step, a state without gradient that the step moves.

Set-up, the window, the other checks and the result are `kinds/train_job.py`'s, restated
here because that file is the accepted benchmark's and a PR that adds a cell may edit
none of it: a `benchmark` issue folds the two into one (PERF.md section 7). The mix's keys
are `train_job`'s and `update_tolerance`; the family gives `reference_state(state)` (the
program's state as its reference takes it, the same buffers) and
`reference_update_fn(config, mix)` beside what `train_job` asks of it, and its
`train_flop_per_token` takes a third argument: the means over the window of the counters
the model's step reports beside loss, accuracy and gradient norm (the fact
`step_counters`), so that work the routing decides is counted as it ran. The reference
takes the first batch in one call, so `reference_rows_per_call` has to cover `batch`."""

from __future__ import annotations

import importlib
import itertools
import math
import time

from benchmarks.kinds.train_job import IN_FLIGHT_STEPS

#: what every model's step reports; what else a step reports is the model's own counters
#: (an expert layer's routing), whose means over the window go to the readers as a fact
STEP_METRICS = ("loss", "accuracy", "grad_norm")


def update_gap(before, expected, after) -> dict:
    """How far `after` is from `expected`, over how far `expected` is from `before`
    (norms over all the leaves of a group), by group: the leaves of one name pooled over
    the layers (`layers/3/wq` is of `layers/wq`), and `all`. Three trees of host arrays of
    one structure. A group the reference did not move reads 0 if the program left it too,
    else infinity."""
    import jax
    import numpy as np

    sums: dict[str, list[float]] = {}
    for (path, b), e, a in zip(jax.tree_util.tree_leaves_with_path(before),
                               jax.tree.leaves(expected), jax.tree.leaves(after)):
        name = "/".join(k.key for k in path if isinstance(k, jax.tree_util.DictKey))
        b, e, a = (np.asarray(v, np.float32) for v in (b, e, a))
        off, moved = np.square(a - e, dtype=np.float64).sum(), np.square(e - b, dtype=np.float64).sum()
        for group in (name, "all"):
            pair = sums.setdefault(group, [0.0, 0.0])
            pair[0] += float(off)
            pair[1] += float(moved)
    return {group: (math.sqrt(off / moved) if moved else (0.0 if not off else math.inf))
            for group, (off, moved) in sums.items()}


def run(config: dict, mix: dict, seed: int, seconds: float, trace, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import generator, runtime
    from kubeflow_tpu.parallel.sharding import shard_batch
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import AsyncLoader, batches

    log, builds = env["log"], env["builds"]
    seed31 = seed % (2**31 - 1)  # what a PRNGKey and numpy's RandomState both take
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    batch, seq_len = int(mix["batch"]), int(mix["seq_len"])
    if batch > int(mix["reference_rows_per_call"]):
        raise ValueError("the reference takes its first step on the whole batch in one call")
    log("program imported")
    x_pool, y_pool = generator.train_pool(mix, config["vocab_size"], seed)
    model = family.train_model(config, mix)
    trainer = Trainer(
        model["module"],
        TrainerConfig(batch_size=batch, learning_rate=float(mix["learning_rate"]),
                      warmup_steps=int(mix["warmup_steps"]), seed=seed31),
        loss_fn=model["loss_fn"], eval_metrics_fn=model["eval_metrics_fn"])
    log(f"compute_dtype={jnp.dtype(trainer.compute_dtype).name} mesh={dict(trainer.mesh.shape)}")

    def epochs():
        for epoch in itertools.count():
            yield from batches(x_pool, y_pool, batch, seed=seed31 + epoch)

    state = trainer.init_state(x_pool[:batch])
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params))
    log(f"parameters={n_params}")

    # the reference first: the step donates the state it is given. Both copies wait on the
    # host, where the step's temporaries do not need the room
    first = next(epochs())
    before = family.reference_state(state)
    total, weight, expected = family.reference_update_fn(config, mix)(before, first[0], first[1])
    ref_loss = float(total) / float(weight)
    before, expected = jax.device_get(before), jax.device_get(expected)
    log(f"reference_loss={ref_loss:.6f}")

    with AsyncLoader(epochs(), transform=lambda b: shard_batch(b, trainer.mesh), size=2,
                     mesh=trainer.mesh, name="bench.loader") as loader:
        state, m = trainer.train_step(state, first)
        first_loss = float(m["loss"])
        gaps = update_gap(before, expected, jax.device_get(family.reference_state(state)))
        del before, expected
        worst = max((g for g in gaps if g != "all"), key=gaps.get)
        same_batch = [first_loss]
        for _ in range(int(mix["descent_steps"]) - 1):
            state, m = trainer.train_step(state, first)
            same_batch.append(float(m["loss"]))
        log(f"first_step_loss={first_loss:.6f} relative_gap={abs(first_loss - ref_loss) / abs(ref_loss):.2e}; "
            f"on the same batch again: {' '.join(f'{v:.6f}' for v in same_batch[1:])}")
        log(f"update_gap={gaps[worst]:.4f} in {worst}, over all leaves {gaps['all']:.4f}; by group: "
            + " ".join(f"{g}={v:.4f}" for g, v in sorted(gaps.items())))
        setup_builds = builds.snapshot()
        log("the window opens")

        losses, norms, counters = [], [], []
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, m = trainer.train_step(state, next(loader))
            losses.append(m["loss"])
            norms.append(m["grad_norm"])
            counters.append({k: v for k, v in m.items() if k not in STEP_METRICS})
            if len(losses) > IN_FLIGHT_STEPS:
                with jax.profiler.TraceAnnotation("bench.wait_for_step"):
                    jax.block_until_ready(losses[-1 - IN_FLIGHT_STEPS])
            elapsed = time.perf_counter() - t0
            trace.poll(elapsed)
            if elapsed >= seconds:
                break
        jax.block_until_ready(losses[-1])
        t1 = time.perf_counter()
        trace.stop()
    window_builds = runtime.since(builds.snapshot(), setup_builds)

    losses = [float(v) for v in jax.device_get(losses)]
    norms = [float(v) for v in jax.device_get(norms)]
    steps, window_s = len(losses), t1 - t0
    counters = jax.device_get(counters)
    step_counters = {k: float(np.mean([c[k] for c in counters])) for k in counters[0]}
    checks = {
        "first_loss_matches_reference":
            abs(first_loss - ref_loss) <= float(mix["loss_tolerance"]) * max(abs(ref_loss), 1.0),
        "first_update_matches_reference": gaps[worst] <= float(mix["update_tolerance"]),
        "all_finite": all(map(math.isfinite, losses + norms)),
        "loss_fell_on_the_same_batch": same_batch[-1] < first_loss,
        "nothing_built_in_window": window_builds["built"] == 0,
    }
    log(f"steps={steps} window_s={window_s:.4f} loss {losses[0]:.4f} -> {losses[-1]:.4f} "
        f"grad_norm last={norms[-1]:.4f} step_counters={step_counters} checks={checks}")
    return {
        "correct": all(checks.values()), "attempted": steps,
        "failed": sum(not (math.isfinite(a) and math.isfinite(b)) for a, b in zip(losses, norms)),
        "t_window_start": t0, "setup_builds": setup_builds, "window_builds": window_builds,
        "end_to_end": {"train_tokens_per_s": steps * batch * seq_len / window_s},
        "facts": {"tokens_per_step": batch * seq_len,
                  "flop_per_token": family.train_flop_per_token(config, mix, step_counters),
                  "step_counters": step_counters,
                  "step_program": r"^jit__train_step\b", "dispatch_span": r"^PjitFunction\(_train_step\)$"},
    }
