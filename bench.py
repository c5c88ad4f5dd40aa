"""Benchmark functions — ONE JSON line per metric, measured on a TPU.

Every row names the device it ran on (`platform`, `device_kind`,
`device_count`, as jax reports them) and carries `mfu`: analytic model
FLOPs over the chip's bf16 peak from PEAK_FLOPS_BY_KIND. `main` refuses to
start unless jax's platform is `tpu` and the `device_kind` is in that table
— a measurement path that finds no chip fails, it does not fall back — runs
every bench in this one process (one process owns the chip), and exits
non-zero if any row failed.

  python bench.py                 # resnet50 images/sec/chip
  python bench.py --suite         # every bench, one JSON line each
  python bench.py --only NEEDLE   # the benches whose metric matches

The bench functions themselves stay callable at tiny sizes on the CPU (the
tests do); a number from such a run is not a device metric.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys
import time

#: what every row says about where it ran (utils.device.device_summary)
DEVICE_FIELDS = ("platform", "device_kind", "device_count")

# bf16 peak FLOP/s per chip, by PJRT device_kind (public spec sheets).
PEAK_FLOPS_BY_KIND = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # trillium
}


def _timed_steps(trainer, state, batch, steps: int):
    # Protocol: ALL `steps` run inside ONE jit dispatch (lax.scan over the
    # step, the TPU-idiomatic loop for on-device data) so per-dispatch host
    # overhead is out of the measurement. compile_fused is the single
    # placement site: it device-births the batch and AOT-compiles without
    # executing. Then ONE warm execution before the timed one: a fresh
    # executable's first run carries one-time overheads (output allocation,
    # runtime first-touch). The timed region ends in a host read of the
    # scalar loss, which depends on the whole chained step sequence.
    compiled, batch = trainer.compile_fused(state, batch, steps)
    state, m = compiled(state, batch)
    float(m["loss"])
    t0 = time.perf_counter()
    state, m = compiled(state, batch)
    loss = float(m["loss"])
    dt = time.perf_counter() - t0
    # Numerics honesty: a throughput line for a training step whose loss
    # went non-finite is not a valid training benchmark — surface it as a
    # failed row instead.
    if not math.isfinite(loss):
        raise RuntimeError(
            f"non-finite loss ({loss}) after timed steps — throughput would "
            "be timing-valid but numerically meaningless")
    return dt


def _finish(result: dict, dt: float, steps: int, flops_per_step: float) -> dict:
    """Attach the device the row ran on, steps/sec and mfu (analytic model
    FLOPs / chip peak; None off the peaks table — `main` never gets there)."""
    from kubeflow_tpu.utils.device import device_summary

    dev = device_summary()
    result.update({k: dev[k] for k in DEVICE_FIELDS})
    steps_per_sec = steps / dt
    peak = PEAK_FLOPS_BY_KIND.get(dev["device_kind"])
    result["steps_per_sec"] = round(steps_per_sec, 3)
    result["model_flops_per_step"] = flops_per_step
    result["mfu"] = (
        round(flops_per_step * steps_per_sec / peak, 4) if peak else None
    )
    return result


def bench_resnet50(steps: int = 30, batch_size: int = 128, image_size: int = 224) -> dict:
    import jax.numpy as jnp

    from kubeflow_tpu.models import ResNet50
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_image_dataset

    ds = synthetic_image_dataset(
        n_train=batch_size, n_test=batch_size,
        shape=(image_size, image_size, 3), num_classes=1000,
    )
    # probe-verdict adoption knobs (VERDICT r4 #3: the fixes are SHIPPED
    # config, so a positive probe_resnet verdict flips the flagship bench
    # with env flags, zero code change): stem "7x7"|"s2d" (exact-equivalent
    # under stem_weights_7x7_to_s2d), conv_impl "auto"|"xla"|"im2col" or a
    # comma-list of 5 per-stage impls (stem,stage1..4). With no env flags
    # set, the verdict is adopted AUTOMATICALLY from probe_resnet.txt's
    # fastest full-model row at this batch size — so the driver's plain
    # `python bench.py` benefits from a probe that landed the same round.
    env_set = (os.environ.get("KFT_RESNET_STEM")
               or os.environ.get("KFT_RESNET_CONV_IMPL"))
    if env_set:
        # operator pinned the config: env wins WHOLESALE (a probe value
        # must not silently fill the other half of a pinned pair)
        auto = None
        stem = os.environ.get("KFT_RESNET_STEM", "7x7")
        conv_impl: str | tuple = os.environ.get("KFT_RESNET_CONV_IMPL",
                                                "auto")
    else:
        auto = _resnet_probe_flags(batch_size)
        stem = (auto or ("7x7",))[0]
        conv_impl = (auto or (None, "auto"))[1]
    if "," in conv_impl:
        conv_impl = tuple(conv_impl.split(","))
        if len(conv_impl) != 5:
            raise ValueError(
                "KFT_RESNET_CONV_IMPL as a list needs exactly 5 entries "
                f"(stem,stage1..stage4), got {len(conv_impl)}")
    trainer = Trainer(
        ResNet50(num_classes=1000, dtype=jnp.bfloat16, stem=stem,
                 conv_impl=conv_impl),
        TrainerConfig(batch_size=batch_size, compute_dtype=jnp.bfloat16,
                      log_every_steps=10**9),
    )
    state = trainer.init_state(ds.x_train[:batch_size])
    batch = (ds.x_train[:batch_size], ds.y_train[:batch_size])
    dt = _timed_steps(trainer, state, batch, steps)
    # analytic fallback: ResNet-50 forward ≈ 4.09 GFLOP/image at 224²;
    # fwd+bwd ≈ 3× forward
    r = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(steps * batch_size / dt, 1),
        "unit": "images/sec/chip",
        # capture self-description, like flash_bwd_impl on the flash rows
        "stem": stem,
        "conv_impl": (",".join(conv_impl)
                      if isinstance(conv_impl, tuple) else conv_impl),
        "flags_from": ("env" if env_set
                       else ("probe_resnet" if auto else "default")),
    }
    return _finish(r, dt, steps, 3 * 4.09e9 * batch_size)


def _resnet_probe_flags(batch_size: int,
                        path: str | None = None) -> tuple[str, str] | None:
    """(stem, conv_impl) of the fastest probe_resnet full-model row at this
    batch size, or None if the probe has not banked any.

    probe_resnet section C rows are configs a bench can adopt verbatim
    (`resnet50_{impl}_{stem}_fwdbwd_b{bs}_ms=<ms> tflops=<tf>`); the
    artifact is append-accumulated across windows, so the LAST line per
    key wins (the window-capture watcher contract)."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "probe_resnet.txt")
    best: tuple[float, str, str] | None = None
    try:
        # last line per key wins — INCLUDING a later =ERROR re-measurement,
        # which invalidates the key (adopting a config whose most recent
        # probe run failed would crash the flagship bench)
        rows: dict[str, float | None] = {}
        with open(path) as fh:
            for ln in fh:
                ln = ln.strip()
                m = re.match(
                    rf"RESULT resnet50_(\w+)_(\w+)_fwdbwd_b{batch_size}"
                    r"_ms=([0-9.]+)", ln)
                if m:
                    rows[f"{m.group(1)}|{m.group(2)}"] = float(m.group(3))
                    continue
                m = re.match(
                    rf"RESULT resnet50_(\w+)_(\w+)_fwdbwd_b{batch_size}"
                    r"=ERROR", ln)
                if m:
                    rows[f"{m.group(1)}|{m.group(2)}"] = None
        for key, ms in rows.items():
            if ms is None:
                continue
            impl, stem = key.split("|")
            if best is None or ms < best[0]:
                best = (ms, stem, impl)
    except OSError:
        return None
    return (best[1], best[2]) if best else None


def bench_bert_base(steps: int = 20, batch_size: int = 16, seq_len: int = 128) -> dict:
    import jax.numpy as jnp

    from kubeflow_tpu.models import BertConfig, BertForSequenceClassification
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_text_dataset

    cfg = BertConfig.base(dtype=jnp.bfloat16, dropout_rate=0.0)
    ds = synthetic_text_dataset(n_train=batch_size, n_test=batch_size,
                                seq_len=seq_len, vocab_size=cfg.vocab_size)
    trainer = Trainer(
        BertForSequenceClassification(cfg, num_classes=2),
        TrainerConfig(batch_size=batch_size, compute_dtype=jnp.bfloat16,
                      log_every_steps=10**9),
    )
    state = trainer.init_state(ds.x_train[:batch_size])
    batch = (ds.x_train[:batch_size], ds.y_train[:batch_size])
    dt = _timed_steps(trainer, state, batch, steps)
    # analytic fallback: 6·N·tokens (N ≈ 110M params) + attention score/value
    # matmuls 12·layers·seq²·hidden per example, ×3 for fwd+bwd on the latter
    tokens = batch_size * seq_len
    attn = 12 * cfg.num_layers * seq_len * seq_len * cfg.hidden_size * batch_size
    r = {
        "metric": "bert_base_steps_per_sec",
        "value": round(steps / dt, 3),
        "unit": "steps/sec",
    }
    return _finish(r, dt, steps, 6 * 110e6 * tokens + attn)


def _flash_bwd_impl() -> str:
    """The flash backward impl in effect (env override or code default)."""
    from kubeflow_tpu.parallel import ring_attention

    return ring_attention.FLASH_BWD_IMPL


def bench_gpt2s_flash_2k(steps: int = 10, batch_size: int = 4,
                         seq_len: int = 2048, window: int = 0,
                         metric: str = "gpt2s_flash_2k_tokens_per_sec_per_chip",
                         ) -> dict:
    """GPT-2-small causal LM at 2k context through the pallas flash kernel —
    the long-context path (SURVEY.md §5.7). On TPU this is the Mosaic-
    compiled (non-interpret) kernel, so the metric doubles as the kernel's
    production validation. window > 0 runs the sliding-window variant
    (the kernel skips KV blocks outside the window: O(L·W) attention)."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import GPTConfig, GPTLM, causal_lm_loss
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_lm_dataset

    cfg = GPTConfig.small(dtype=jnp.bfloat16, dropout_rate=0.0,
                          attention="flash", max_len=seq_len,
                          attention_window=window)
    ds = synthetic_lm_dataset(n_train=batch_size, n_test=batch_size,
                              seq_len=seq_len, vocab_size=cfg.vocab_size)
    trainer = Trainer(
        GPTLM(cfg),
        TrainerConfig(batch_size=batch_size, compute_dtype=jnp.bfloat16,
                      log_every_steps=10**9),
        loss_fn=causal_lm_loss,
    )
    state = trainer.init_state(ds.x_train[:batch_size])
    batch = (ds.x_train[:batch_size], ds.y_train[:batch_size])
    dt = _timed_steps(trainer, state, batch, steps)
    tokens = batch_size * seq_len
    # 6·N per token fwd+bwd (N ≈ 124M) + attention score/value matmuls:
    # 12·L·s·min(s/2, window)·h·bs (causal half discount, or the window)
    per_q = min(seq_len // 2, window) if window else seq_len // 2
    attn = 12 * cfg.num_layers * seq_len * per_q * cfg.hidden_size * batch_size
    r = {
        "metric": metric,
        "value": round(steps * tokens / dt, 1),
        "unit": "tokens/sec/chip",
        # capture self-description: which flash backward produced this row
        # (the watcher may flip KFT_FLASH_BWD_IMPL between windows, and
        # resume-skip freezes whichever impl first banked the row)
        "flash_bwd_impl": _flash_bwd_impl(),
    }
    if window:
        r["window"] = window
    return _finish(r, dt, steps, 6 * 124e6 * tokens + attn)


def bench_gpt2s_swa_2k(**kw) -> dict:
    """Sliding-window (Mistral) flash at 2k context, window 256: the
    block-skipping kernel's O(L·W) win over full causal — compare
    tokens/sec against gpt2s_flash_2k."""
    return bench_gpt2s_flash_2k(
        window=256, metric="gpt2s_swa_2k_tokens_per_sec_per_chip", **kw)


def bench_vitb16(steps: int = 30, batch_size: int = 128, image_size: int = 224) -> dict:
    """ViT-B/16 images/sec/chip — the MXU-native image-training path. On
    this backend convs run at 0.3-0.6 TFLOP/s while matmuls hit 117
    (docs/perf.md), so ViT is the performance-first counterpoint to the
    conv-bound ResNet flagship: same task shape, all-matmul compute."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import ViTClassifier, ViTConfig
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_image_dataset

    cfg = ViTConfig.base(dtype=jnp.bfloat16, dropout_rate=0.0,
                         image_size=image_size)
    ds = synthetic_image_dataset(
        n_train=batch_size, n_test=batch_size,
        shape=(image_size, image_size, 3), num_classes=1000,
    )
    trainer = Trainer(
        ViTClassifier(cfg),
        TrainerConfig(batch_size=batch_size, compute_dtype=jnp.bfloat16,
                      log_every_steps=10**9),
    )
    state = trainer.init_state(ds.x_train[:batch_size])
    batch = (ds.x_train[:batch_size], ds.y_train[:batch_size])
    dt = _timed_steps(trainer, state, batch, steps)
    # ViT-B/16 fwd ~= 17.6 GFLOP/image at 224^2 (attention + MLP matmuls);
    # fwd+bwd ~= 3x
    r = {
        "metric": "vitb16_images_per_sec_per_chip",
        "value": round(steps * batch_size / dt, 1),
        "unit": "images/sec/chip",
    }
    return _finish(r, dt, steps, 3 * 17.6e9 * batch_size)


def bench_gpt2s_decode(batch_size: int = 8, prompt_len: int = 128,
                       new_tokens: int = 128, num_kv_heads: int = 0,
                       metric: str = "gpt2s_decode_tokens_per_sec_per_chip",
                       ) -> dict:
    """Autoregressive decode throughput (generated tokens/sec/chip) through
    the KV-cache path — the LLM serving metric. Decode is HBM-bandwidth
    bound (the whole model streams per token), so MFU here is expected to
    be small; the number of record is tokens/sec."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM, generate

    cfg = GPTConfig.small(dtype=jnp.bfloat16, dropout_rate=0.0,
                          max_len=prompt_len + new_tokens,
                          num_kv_heads=num_kv_heads)
    model = GPTLM(cfg)
    prompt_host = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, prompt_len), 1, cfg.vocab_size,
        jnp.int32,
    )
    prompt = jax.jit(lambda x: x + 0)(prompt_host)  # device-born
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
    gen = jax.jit(lambda v, p: generate(model, v, p, new_tokens))
    out = gen(variables, prompt)
    int(out.sum())  # true sync (host read)
    t0 = time.perf_counter()
    out = gen(variables, prompt)
    int(out.sum())
    dt = time.perf_counter() - t0
    toks = batch_size * new_tokens
    r = {
        "metric": metric,
        "value": round(toks / dt, 1),
        "unit": "tokens/sec/chip",
    }
    # fwd-only FLOPs per generated token: 2N with N the REAL parameter
    # count (GQA shrinks K/V kernels, so a hardcoded 124M would overstate
    # the GQA record's MFU — the exact comparison this bench exists for)
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(variables["params"]))
    return _finish(r, dt, new_tokens, 2 * n_params * batch_size)


def bench_gpt2s_rolling_decode(batch_size: int = 8, prompt_len: int = 128,
                               new_tokens: int = 128, window: int = 256,
                               capacity: int = 384,
                               budget_len: int = 4096) -> dict:
    """Rolling KV cache at a 4k context budget: decode attends over
    `capacity` ring slots instead of a 4k-deep buffer (~10x less cache
    traffic per token at GPT-2s dims). The record carries BOTH numbers —
    value = rolling tokens/sec, full_cache_tokens_per_sec = the max_len-
    deep twin under the identical window — so the win is self-contained."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM, generate

    prompt_host = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, prompt_len), 1, 50257, jnp.int32)
    prompt = jax.jit(lambda x: x + 0)(prompt_host)

    def run(capacity_):
        cfg = GPTConfig.small(dtype=jnp.bfloat16, dropout_rate=0.0,
                              max_len=budget_len, attention_window=window,
                              kv_cache_capacity=capacity_)
        model = GPTLM(cfg)
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
        gen = jax.jit(lambda v, p: generate(model, v, p, new_tokens))
        out = gen(variables, prompt)
        int(out.sum())  # true sync
        t0 = time.perf_counter()
        out = gen(variables, prompt)
        int(out.sum())
        return batch_size * new_tokens / (time.perf_counter() - t0)

    rolling = run(capacity)
    full = run(0)
    r = {
        "metric": "gpt2s_rolling_decode_tokens_per_sec_per_chip",
        "value": round(rolling, 1),
        "unit": "tokens/sec/chip",
        "full_cache_tokens_per_sec": round(full, 1),
        "window": window, "capacity": capacity, "budget_len": budget_len,
    }
    # decode FLOPs ~2N/token; dt re-derived from the rolling value
    return _finish(r, batch_size * new_tokens / rolling, new_tokens,
                   2 * 124e6 * batch_size)


def bench_gpt2s_gqa_decode(**kw) -> dict:
    """GQA decode (3 KV heads for 12 query heads, the Llama grouping): the
    KV cache shrinks 4x, the direct lever on bandwidth-bound decode —
    measured against gpt2s_decode's MHA number."""
    return bench_gpt2s_decode(
        num_kv_heads=3,
        metric="gpt2s_gqa_decode_tokens_per_sec_per_chip", **kw)


def bench_gpt2s_continuous_serve(rows: int = 8, n_requests: int = 24,
                                 prompt_len: int = 128,
                                 new_tokens: int = 64) -> dict:
    """Continuous-batching serving throughput: n_requests concurrent
    GPT-2s decodes interleaved on a fixed `rows`-row engine (iteration-
    level scheduling, serving/continuous.py). The number of record is
    aggregate generated tokens/sec/chip — the comparison against
    gpt2s_decode (one blocking batch) is the serving win: admissions
    refill retiring rows, so the decode executable never runs below
    capacity while requests queue."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = GPTConfig.small(dtype=jnp.bfloat16, dropout_rate=0.0,
                          max_len=prompt_len + new_tokens)
    model = GPTLM(cfg)
    prompt_host = jax.random.randint(
        jax.random.PRNGKey(1), (n_requests, prompt_len), 1, cfg.vocab_size,
        jnp.int32)
    prompts = np.asarray(prompt_host)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.asarray(prompts[:1]))
    # steps_per_tick amortizes one host round-trip over 8 tokens/row
    # (scheduling granularity stays iteration-level; see
    # serving/continuous.py)
    steps_per_tick = 8
    eng = ContinuousBatcher(model, variables, max_rows=rows,
                            default_max_new_tokens=new_tokens,
                            steps_per_tick=steps_per_tick)
    # warmup: compile prefill + decode-step + splice once
    eng.submit(prompts[0], max_new_tokens=2)
    eng.run_until_idle()
    step0 = eng.step_count  # exclude warmup dispatches from the timed count
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    eng.run_until_idle()
    toks = sum(len(r.result(timeout=0) if r.done.is_set() else ())
               for r in reqs)
    dt = time.perf_counter() - t0
    assert toks == n_requests * new_tokens, toks
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(variables["params"]))
    r = {
        "metric": "gpt2s_continuous_serve_tokens_per_sec_per_chip",
        "value": round(toks / dt, 1),
        "unit": "tokens/sec/chip",
        "rows": rows, "n_requests": n_requests,
        "decode_dispatches": eng.step_count - step0,
    }
    # step_count counts DISPATCHES; each dispatch chains steps_per_tick
    # decode steps, so per-dispatch model FLOPs carry that factor (ADVICE
    # r4: without it mfu/model_flops_per_step under-report ~8x)
    return _finish(r, dt, eng.step_count - step0,
                   2 * n_params * rows * steps_per_tick)


def bench_gpt2s_spec_serve(rows: int = 8, n_requests: int = 24,
                           prompt_len: int = 128, new_tokens: int = 64,
                           gamma: int = 4) -> dict:
    """Speculative decoding INSIDE the continuous engine: per-row
    draft/verify, row-local rewind (serving/continuous.py). Self-draft
    (draft == target) pins the mechanics' ceiling — every round accepts
    gamma tokens, so tokens/dispatch is (gamma+1)x the plain engine's
    steps_per_tick=1 rate. The record carries dispatch counts so the drop
    vs gpt2s_continuous_serve is self-contained."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.gpt import GPTConfig, GPTLM
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = GPTConfig.small(dtype=jnp.bfloat16, dropout_rate=0.0,
                          max_len=prompt_len + new_tokens + gamma + 2)
    model = GPTLM(cfg)
    prompt_host = jax.random.randint(
        jax.random.PRNGKey(1), (n_requests, prompt_len), 1, cfg.vocab_size,
        jnp.int32)
    prompts = np.asarray(prompt_host)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.asarray(prompts[:1]))
    eng = ContinuousBatcher(model, variables, max_rows=rows,
                            default_max_new_tokens=new_tokens,
                            draft_module=model, draft_variables=variables,
                            gamma=gamma)
    eng.submit(prompts[0], max_new_tokens=2)
    eng.run_until_idle()
    step0 = eng.step_count
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    eng.run_until_idle()
    toks = sum(len(r.result(timeout=0) if r.done.is_set() else ())
               for r in reqs)
    dt = time.perf_counter() - t0
    assert toks == n_requests * new_tokens, toks
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(variables["params"]))
    r = {
        "metric": "gpt2s_spec_serve_tokens_per_sec_per_chip",
        "value": round(toks / dt, 1),
        "unit": "tokens/sec/chip",
        "rows": rows, "n_requests": n_requests, "gamma": gamma,
        "decode_dispatches": eng.step_count - step0,
        "draft": "self",
    }
    # per dispatch: gamma+1 draft steps (the engine always runs the extra
    # cache-write step) + one (gamma+1)-token verify, all full model
    # passes under self-draft => 2N*rows*(2*gamma+2) FLOPs
    return _finish(r, dt, eng.step_count - step0,
                   2 * n_params * rows * (2 * gamma + 2))


def bench_mnist_mlp(steps: int = 60, batch_size: int = 512) -> dict:
    from kubeflow_tpu.models import MnistMLP
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_image_dataset

    ds = synthetic_image_dataset(n_train=batch_size * 2, n_test=batch_size,
                                 shape=(28, 28, 1))
    trainer = Trainer(
        MnistMLP(hidden=(512, 256)),
        TrainerConfig(batch_size=batch_size, log_every_steps=10**9),
    )
    state = trainer.init_state(ds.x_train[:batch_size])
    batch = (ds.x_train[:batch_size], ds.y_train[:batch_size])
    dt = _timed_steps(trainer, state, batch, steps)
    # MLP 784→512→256→10: ~0.54 MFLOP fwd/image, ×3 fwd+bwd
    mlp_flops = 2 * (784 * 512 + 512 * 256 + 256 * 10)
    r = {
        "metric": "mnist_mlp_images_per_sec_per_chip",
        "value": round(steps * batch_size / dt, 1),
        "unit": "images/sec/chip",
    }
    return _finish(r, dt, steps, 3 * mlp_flops * batch_size)


# The ONE registry every consumer derives from (suite order, failed-row
# records, metric/unit naming).
FLAGSHIP = (bench_resnet50, "resnet50_images_per_sec_per_chip", "images/sec/chip")
SUITE_BENCHES = [
    (bench_mnist_mlp, "mnist_mlp_images_per_sec_per_chip", "images/sec/chip"),
    (bench_bert_base, "bert_base_steps_per_sec", "steps/sec"),
    FLAGSHIP,
    (bench_vitb16, "vitb16_images_per_sec_per_chip", "images/sec/chip"),
    (bench_gpt2s_flash_2k, "gpt2s_flash_2k_tokens_per_sec_per_chip", "tokens/sec/chip"),
    (bench_gpt2s_swa_2k, "gpt2s_swa_2k_tokens_per_sec_per_chip",
     "tokens/sec/chip"),
    (bench_gpt2s_decode, "gpt2s_decode_tokens_per_sec_per_chip", "tokens/sec/chip"),
    (bench_gpt2s_gqa_decode, "gpt2s_gqa_decode_tokens_per_sec_per_chip",
     "tokens/sec/chip"),
    (bench_gpt2s_continuous_serve,
     "gpt2s_continuous_serve_tokens_per_sec_per_chip", "tokens/sec/chip"),
    (bench_gpt2s_rolling_decode,
     "gpt2s_rolling_decode_tokens_per_sec_per_chip", "tokens/sec/chip"),
    (bench_gpt2s_spec_serve,
     "gpt2s_spec_serve_tokens_per_sec_per_chip", "tokens/sec/chip"),
]


def _benches(argv: list[str]) -> list:
    """The bench list an invocation owes, from its arguments."""
    if "--only" in argv:
        needle = argv[argv.index("--only") + 1]
        return [b for b in SUITE_BENCHES if needle in b[1]]
    return list(SUITE_BENCHES) if "--suite" in argv else [FLAGSHIP]


def require_tpu() -> dict:
    """The device `main` measures on, or SystemExit: the platform must be
    `tpu` and the `device_kind` in PEAK_FLOPS_BY_KIND. A device off the
    table is an error, not a default."""
    from kubeflow_tpu.utils.device import device_summary

    try:
        dev = device_summary()
    except RuntimeError as exc:
        raise SystemExit(f"bench: no TPU: {exc}") from exc
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"bench: jax runs on platform {dev['platform']!r}, not 'tpu' — "
            "a device metric comes only from a chip")
    if dev["device_kind"] not in PEAK_FLOPS_BY_KIND:
        raise SystemExit(
            f"bench: device_kind {dev['device_kind']!r} is not in "
            "PEAK_FLOPS_BY_KIND — add its peak with its source")
    return dev


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    dev = require_tpu()
    failed = 0
    for bench, metric, unit in _benches(argv):
        try:
            row = bench()
        except Exception as exc:  # noqa: BLE001 — one bench must not cost
            # the rest their rows; the failure is a row and the exit code
            row = {"metric": metric, "value": 0.0, "unit": unit, "mfu": None,
                   **{k: dev[k] for k in DEVICE_FIELDS},
                   "error": f"{type(exc).__name__}: {exc}"[:500]}
            failed += 1
        print(json.dumps(row), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
