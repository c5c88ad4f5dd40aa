"""Runner kind `train_job`: an in-process `train.trainer.Trainer` stepped for the window.

Set-up: the pool of batches from the seed (host, numpy), `init_state` (the weights are made
on the device by one jitted call from the seed), the plain reference's loss on the first
batch, and `descent_steps` steps on that one batch (the first compiles or loads and its
loss is the one compared with the reference; the rest show that nothing is specialised
again and that the update descends: the same rows again, so the loss falls whatever the
seed, which a loss over the window's fresh batches need not do on a plateau; the mix
files say how many steps and why). Window: `train_step` in a loop over the program's own
input iterator (`train.data.batches` under an `AsyncLoader` that places each batch with
`shard_batch`, as `_fit_loop` does), at most `IN_FLIGHT_STEPS` steps enqueued beyond the
one the host last waited for, no value read from the device. The window closes on
`block_until_ready` of the last step's loss. Loss and gradient norm of every step stay on
the device until then."""

from __future__ import annotations

import importlib
import itertools
import math
import time


#: steps enqueued beyond the one the host last waited for: the device always has the next
#: step queued, and the host never runs further ahead than the window can close on
IN_FLIGHT_STEPS = 2


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

    # the reference first: the step donates the state it is given
    first = next(epochs())
    ref_params = family.reference_params(state.params)
    ref_fn = family.reference_loss_fn(config, mix)
    rows = int(mix["reference_rows_per_call"])
    total = weight = 0.0
    for i in range(0, batch, rows):
        s, w = ref_fn(ref_params, first[0][i:i + rows], first[1][i:i + rows])
        total, weight = total + float(s), weight + float(w)
    ref_loss = total / weight
    del ref_params
    log(f"reference_loss={ref_loss:.6f}")

    with AsyncLoader(epochs(), transform=lambda b: shard_batch(b, trainer.mesh), size=2,
                     mesh=trainer.mesh, name="bench.loader") as loader:
        same_batch = []
        for _ in range(int(mix["descent_steps"])):
            state, m = trainer.train_step(state, first)
            same_batch.append(float(m["loss"]))
        first_loss = same_batch[0]
        log(f"first_step_loss={first_loss:.6f} relative_gap={abs(first_loss - ref_loss) / abs(ref_loss):.2e}; "
            f"on the same batch again: {' '.join(f'{v:.6f}' for v in same_batch[1:])}")
        setup_builds = builds.snapshot()
        log("the window opens")

        losses, norms = [], []
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, m = trainer.train_step(state, next(loader))
            losses.append(m["loss"])
            norms.append(m["grad_norm"])
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
    checks = {
        "first_loss_matches_reference":
            abs(first_loss - ref_loss) <= float(mix["loss_tolerance"]) * max(abs(ref_loss), 1.0),
        "all_finite": all(map(math.isfinite, losses + norms)),
        "loss_fell_on_the_same_batch": same_batch[-1] < first_loss,
        "nothing_built_in_window": window_builds["built"] == 0,
    }
    log(f"steps={steps} window_s={window_s:.4f} loss {losses[0]:.4f} -> {losses[-1]:.4f} "
        f"grad_norm last={norms[-1]:.4f} checks={checks}")
    return {
        "correct": all(checks.values()), "attempted": steps,
        "failed": sum(not (math.isfinite(a) and math.isfinite(b)) for a, b in zip(losses, norms)),
        "t_window_start": t0, "setup_builds": setup_builds, "window_builds": window_builds,
        "end_to_end": {"train_tokens_per_s": steps * batch * seq_len / window_s},
        "facts": {"tokens_per_step": batch * seq_len,
                  "flop_per_token": family.train_flop_per_token(config, mix),
                  "step_program": r"^jit__train_step\b", "dispatch_span": r"^PjitFunction\(_train_step\)$"},
    }
