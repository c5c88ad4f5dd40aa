"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # from the repo root, on a machine with a TPU

Drives the two main paths once each, through the entry points a user calls,
at the full width of a model the repo supports (depth as published too; the
weights are random, made from a seed), and checks what comes out:

  trainer  Platform + TrainingClient.train(family="bert", device="tpu") ->
           one-worker JAXJob -> PodRuntime subprocess -> examples.bert
           (BERT-base, L=128) with a checkpoint directory, to SUCCEEDED;
           then a second job on the same checkpoint directory and compile
           cache that resumes. Read from the worker's log and trace: the
           device, finite falling loss, finite gradient norm, resumed=1,
           zero restarts, and zero backend compiles in the second
           incarnation's train.compile span.
  server   a child writes a GPT-2-small predictor from a seed; the parent
           creates an InferenceService (device: tpu, continuous batching,
           chunked prefill, paged KV), sends concurrent :predict requests
           of different prompt lengths and checks the BODIES: full token
           counts, identical tokens for identical greedy prompts, decode
           dispatches on /metrics. A second child then scores every served
           token against a dense full-sequence forward of the same weights
           (no KV cache, no chunking): each must be the argmax within a
           bf16 tolerance.
  kernel   examples.gpt --size=small --attention=flash --seq-len=2048 for a
           few steps under an XLA dump: the compiled train step must hold
           the Mosaic custom call (neither interpret mode nor the blockwise
           substitute), loss and gradient norm finite.
  mesh     only where the child sees four or more devices: examples.bert
           BERT-base for one step on the default mesh and on fsdp=2 x
           model=2, one process each — four devices in the parameter
           shardings, parameters actually partitioned, no involuntary
           rematerialization, equal first-step loss.

The legs run one after another, each in its own child, so the chip has one
owner at a time; this process never initialises a JAX backend (it checks
that it did not even import jax). There is no CPU mode: every child is told
`tpu`, and without a chip the first one fails. Times are printed as set-up
and wall seconds, never as a rate.

The last line of standard output is the result,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`,
printed only when every leg passed; any failure exits non-zero without it.
Worker logs land in chiprun_out/chip_smoke/, heavy state (checkpoints,
model directories) in .kubeflow_tpu/chip-smoke/; both are wiped at start.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".kubeflow_tpu" / "chip-smoke"
OUT = ROOT / "chiprun_out" / "chip_smoke"

#: the only platform this script accepts; children get it as --device
DEVICE = "tpu"
#: whole-script budget (the contract allows 1200 s, compilation included)
DEADLINE_S = 1150.0

BERT_ARGS = ["--size=base", "--seq-len=128", "--batch-size=32"]
#: total steps of the first job and of the resumed one. examples.bert warms
#: up over steps // 10, a constant of the compiled step: both totals share
#: it, so the second job can replay the first one's executable
BERT_STEPS = (30, 38)
GPT_ARGS = ["--size=small", "--attention=flash", "--seq-len=2048",
            "--batch-size=4", "--steps=3"]

PREDICTOR_SIZE = "small"
PREDICTOR_SEED = 0
NEW_TOKENS = 16
PREFILL_CHUNK = 32
PAGED_KV_BLOCK = 16
#: every prompt is a whole number of prefill chunks plus the same 8-token
#: remainder: different lengths, two chunk executables to compile
PROMPT_LENS = (40, 72, 104, 136)
#: a served token may trail the dense forward's best logit by this much
#: (bf16 matmuls under different batch shapes round differently)
LOGIT_TOL = 0.25
#: first-step loss, partitioned against unpartitioned (bf16 compute)
MESH_LOSS_TOL = 2e-2

_T0 = time.monotonic()


class SmokeFailure(Exception):
    """One assertion of a leg failed; carries the message to print."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError as exc:
        return f"<{path}: {exc}>"


# ------------------------------------------------------------------ children


def run_child(name: str, argv: list[str], env: dict | None = None,
              timeout_s: float = 900.0) -> tuple[str, float]:
    """Run one chip-owning child to its end; its output goes to
    OUT/<name>.log. Returns (log text, wall seconds); a non-zero exit or a
    timeout fails the leg. `env` replaces the inherited environment. The
    child gets its own session so a timeout kills everything it started."""
    log_path = OUT / f"{name}.log"
    t0 = time.monotonic()
    with open(log_path, "wb") as logf:
        proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=logf, stderr=subprocess.STDOUT,
            start_new_session=True)
        try:
            rc = proc.wait(timeout=max(min(timeout_s, remaining()), 1.0))
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise SmokeFailure(
                f"{name}: no exit after {time.monotonic() - t0:.0f}s\n"
                + tail(log_path)) from None
    wall = time.monotonic() - t0
    check(rc == 0, f"{name}: exit code {rc}\n{tail(log_path)}")
    return log_path.read_text(errors="replace"), wall


def self_child(mode: str, *args: str) -> list[str]:
    return [sys.executable, str(Path(__file__).resolve()), f"--child={mode}",
            *args]


def example(module: str, *args: str) -> list[str]:
    return [sys.executable, "-m", f"examples.{module}", f"--device={DEVICE}",
            *args]


def child_preflight() -> int:
    """What JAX finds, strictly on DEVICE — the first owner of the chip."""
    from kubeflow_tpu.utils.device import device_summary, select_device

    select_device(DEVICE)
    from kubeflow_tpu.train import metrics

    metrics.emit(**device_summary())  # the line every worker prints first
    return 0


def child_write_predictor(model_dir: str) -> int:
    """GPT-2-small from a seed, saved in the jax-runtime model-dir layout
    with the continuous engine, chunked prefill and the paged pool on."""
    from kubeflow_tpu.utils.device import select_device

    select_device(DEVICE)
    import jax
    import numpy as np

    from kubeflow_tpu.models.gpt import GPTLM, GPTConfig
    from kubeflow_tpu.serving.model import save_predictor

    mk = GPTConfig.tiny if PREDICTOR_SIZE == "tiny" else GPTConfig.small
    example_ids = np.ones((1, PROMPT_LENS[0]), np.int32)
    variables = jax.jit(GPTLM(mk()).init)(
        jax.random.PRNGKey(PREDICTOR_SEED), example_ids)
    save_predictor(
        model_dir, "gpt-lm", jax.device_get(variables), example_ids,
        size=PREDICTOR_SIZE,
        generate={"continuous": True, "continuous_rows": 8,
                  "max_new_tokens": NEW_TOKENS,
                  "prefill_chunk": PREFILL_CHUNK,
                  "paged_kv_block": PAGED_KV_BLOCK})
    return 0


def child_verify_served(model_dir: str, served_path: str) -> int:
    """Score every served token against a dense forward of the same
    weights over prompt + served tokens: the reference shares nothing with
    the engine's KV cache, chunked prefill or paged pool."""
    from kubeflow_tpu.utils.device import select_device

    select_device(DEVICE)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.serving.model import load_generative_model

    module, variables, _ = load_generative_model(Path(model_dir))
    served = json.loads(Path(served_path).read_text())
    seqs = [p + t for p, t in served]
    width = max(len(s) for s in seqs)
    # right padding: causal attention keeps it away from earlier positions
    ids = np.ones((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    logits = np.asarray(jax.jit(
        lambda v, x: module.apply(v, x).astype(jnp.float32))(variables, ids))
    if not np.isfinite(logits).all():
        print("VERIFY non-finite reference logits", flush=True)
        return 1
    worst = 0.0
    for i, (prompt, toks) in enumerate(served):
        for j, tok in enumerate(toks):
            row = logits[i, len(prompt) + j - 1]
            worst = max(worst, float(row.max() - row[tok]))
    print(f"VERIFY tokens={sum(len(t) for _, t in served)} "
          f"worst_logit_gap={worst:.4f} tol={LOGIT_TOL}", flush=True)
    return 0 if worst <= LOGIT_TOL else 1


# ----------------------------------------------------------------- log reads


def device_of(log: str, who: str) -> dict:
    from kubeflow_tpu.utils.device import parse_device_line

    dev = parse_device_line(log)
    check(dev is not None, f"{who}: no start-up device line in its log")
    return dev


def check_device(log: str, expect: dict, who: str) -> None:
    """The device a worker's log names is the chip the preflight found."""
    dev = device_of(log, who)
    check(dev["platform"] == DEVICE,
          f"{who} ran on platform {dev['platform']!r}, not {DEVICE!r}")
    for k in ("device_kind", "device_count"):
        check(dev[k] == expect[k],
              f"{who}: {k} {dev[k]!r} != preflight {expect[k]!r}")


def timelines(log: str, *names: str) -> dict[str, list[float]]:
    from kubeflow_tpu.sweep.collector import parse_metrics

    got = parse_metrics(log, set(names))
    for n in names:
        check(n in got, f"no {n}= value in the log")
    return got


def check_finite(series: dict[str, list[float]], who: str) -> None:
    for name, vals in series.items():
        check(all(math.isfinite(v) for v in vals),
              f"{who}: non-finite {name} {vals}")


def span_seconds(spans: list[dict], name: str) -> float:
    return sum(s["dur"] for s in spans if s["name"] == name)


def wait_gone(pid: int, timeout_s: float = 60.0) -> None:
    """Block until a process the pod runtime was told to kill has been
    reaped: the next leg's child needs the chip it held."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return
        time.sleep(0.1)
    raise SmokeFailure(f"process {pid} still alive {timeout_s:.0f}s after "
                       "its pod was deleted")


# ---------------------------------------------------------------------- legs


def leg_preflight() -> dict:
    log, wall = run_child("preflight", self_child("preflight"),
                          timeout_s=180.0)
    dev = device_of(log, "preflight")
    check(dev["platform"] == DEVICE,
          f"JAX found platform {dev['platform']!r}, not {DEVICE!r}")
    say(f"preflight PASS platform={dev['platform']} "
        f"device_kind={dev['device_kind']!r} "
        f"device_count={dev['device_count']} wall_s={wall:.1f}")
    return dev


def leg_trainer(platform, dev: dict) -> None:
    """Two JAXJobs through the platform, the second resuming the first."""
    from kubeflow_tpu.client import TrainingClient
    from kubeflow_tpu.tracing import load_chrome_trace

    client = TrainingClient(platform)
    ckpt = WORK / "bert-ckpt"
    trace_dir = Path(platform.tracer.trace_dir)
    seen_traces: set[Path] = set()
    for n, steps in enumerate(BERT_STEPS, 1):
        name = f"smoke-bert-{n}"
        t0 = time.monotonic()
        try:
            client.train(
                name, family="bert", device=DEVICE,
                args=[*BERT_ARGS, f"--steps={steps}",
                      f"--checkpoint-dir={ckpt}"],
                timeout_s=max(remaining(), 1.0))
        except (RuntimeError, TimeoutError) as exc:
            raise SmokeFailure(
                f"{name}: {exc}\n"
                + "\n".join(client.get_job_logs(name).splitlines()[-40:])
            ) from exc
        finally:
            log = client.get_job_logs(name)
            (OUT / f"{name}.log").write_text(log)
        wall = time.monotonic() - t0
        job = client.get_job(name)
        check(job.status.restart_count == 0,
              f"{name}: restart_count {job.status.restart_count} — a first "
              f"attempt crashed and the retry hid it\n{log[-3000:]}")
        check_device(log, dev, name)
        series = timelines(log, "loss", "grad_norm", "final_loss")
        check_finite(series, name)
        traces = set(trace_dir.glob("trace-*.json")) - seen_traces
        seen_traces |= traces
        # this job's worker flushes are the files that appeared with it
        spans = [s for p in traces for s in load_chrome_trace(str(p))]
        compile_spans = [s for s in spans if s["name"] == "train.compile"]
        check(len(compile_spans) == 1,
              f"{name}: {len(compile_spans)} train.compile spans flushed")
        info = compile_spans[0]["attrs"]
        if n == 1:
            check(series["final_loss"][-1] < series["loss"][0],
                  f"{name}: loss did not fall: first logged "
                  f"{series['loss'][0]}, final {series['final_loss'][-1]}")
            check("resumed=1" not in log,
                  f"{name}: resumed from a checkpoint that should not exist")
        else:
            check(f"step={BERT_STEPS[0]} resumed=1" in log,
                  f"{name}: no 'step={BERT_STEPS[0]} resumed=1' line")
            check(int(info["backend_misses"]) == 0,
                  f"{name}: resumed incarnation's train.compile ran "
                  f"{info['backend_misses']} backend compiles, cache "
                  f"{info.get('cache_dir')}")
        say(f"trainer {name} PASS steps={steps} loss={series['loss']} "
            f"grad_norm={series['grad_norm']} "
            f"final_loss={series['final_loss'][-1]} "
            f"compile: reloaded={info.get('reloaded')!r} "
            f"compiled={info.get('compiled')!r} "
            f"backend_misses={info['backend_misses']} "
            f"cache_dir={info.get('cache_dir')} "
            f"setup: compile_s={compile_spans[0]['dur']:.1f} "
            f"restore_s={span_seconds(spans, 'checkpoint.restore'):.1f} "
            f"save_s={span_seconds(spans, 'checkpoint.save'):.1f} "
            f"eval_s={span_seconds(spans, 'train.eval'):.1f} "
            f"steps_s={span_seconds(spans, 'train.step'):.1f} "
            f"wall_s={wall:.1f}")


def leg_server(platform, dev: dict) -> None:
    import urllib.request

    from kubeflow_tpu.api.common import ObjectMeta
    from kubeflow_tpu.serving.api import (
        InferenceService,
        InferenceServiceSpec,
        PredictorRuntime,
        PredictorSpec,
    )
    from kubeflow_tpu.serving.client import ServingClient

    model_dir = WORK / "gpt-predictor"
    _, write_wall = run_child(
        "write-predictor", self_child("write-predictor", str(model_dir)),
        timeout_s=300.0)

    name = "smoke-gpt"
    serving = ServingClient(platform)
    pod_log = platform.pod_runtime.log_path(f"{name}-predictor-0")
    t0 = time.monotonic()
    serving.create(InferenceService(
        metadata=ObjectMeta(name=name),
        spec=InferenceServiceSpec(predictor=PredictorSpec(
            runtime=PredictorRuntime.JAX,
            storage_uri=f"file://{model_dir}", device=DEVICE))))
    try:
        try:
            isvc = serving.wait_ready(
                name, timeout_s=max(min(600.0, remaining()), 1.0))
        except TimeoutError:
            raise SmokeFailure(
                f"InferenceService {name} not ready\n{tail(pod_log)}"
            ) from None
        ready_wall = time.monotonic() - t0

        # seeded prompts of different lengths; the last repeats the second
        # so two identical greedy prompts are in flight together
        import random

        rng = random.Random(PREDICTOR_SEED)
        vocab = 512 if PREDICTOR_SIZE == "tiny" else 50257
        prompts = [[rng.randrange(1, vocab) for _ in range(n)]
                   for n in PROMPT_LENS]
        prompts.append(list(prompts[1]))
        results: list = [None] * len(prompts)

        def ask(i: int) -> None:
            try:
                results[i] = serving.predict_timed(
                    name, [prompts[i]],
                    timeout_s=max(min(600.0, remaining()), 1.0))
            except Exception as exc:  # noqa: BLE001 — reported below, per request
                results[i] = exc

        t1 = time.monotonic()
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        predict_wall = time.monotonic() - t1

        served = []
        for i, res in enumerate(results):
            check(not isinstance(res, BaseException),
                  f"request {i} (prompt {len(prompts[i])}): {res!r}\n"
                  + tail(pod_log))
            body, timing = res
            rows = body.get("predictions")
            check(isinstance(rows, list) and len(rows) == 1
                  and len(rows[0]) == NEW_TOKENS
                  and all(isinstance(t, int) and 0 <= t < vocab
                          for t in rows[0]),
                  f"request {i}: body is not {NEW_TOKENS} token ids: {body}")
            check(timing.ttft_s is not None and timing.ttft_s > 0,
                  f"request {i}: no engine first-token time: {timing}")
            served.append([prompts[i], rows[0]])
        check(served[1][1] == served[-1][1],
              "identical greedy prompts returned different tokens: "
              f"{served[1][1]} vs {served[-1][1]}")

        with urllib.request.urlopen(f"{isvc.status.url}/metrics",
                                    timeout=30) as r:
            metrics = r.read().decode()
        dispatches = next(
            (float(ln.rsplit(" ", 1)[1]) for ln in metrics.splitlines()
             if ln.startswith("kfserving_engine_decode_dispatches_total{")),
            None)
        check(dispatches is not None and dispatches >= NEW_TOKENS - 1,
              f"/metrics shows no engine decode steps: {dispatches}")
        log = pod_log.read_text(errors="replace")
        check_device(log, dev, "model server")
    finally:
        if pod_log.exists():
            shutil.copyfile(pod_log, OUT / f"{name}-predictor-0.log")
        pod = platform.cluster.get("pods", f"default/{name}-predictor-0")
        serving.delete(name)
        if pod is not None and pod.status.pid:
            wait_gone(pod.status.pid)  # the chip is free for the next child

    served_path = WORK / "served.json"
    served_path.write_text(json.dumps(served))
    verify_log, verify_wall = run_child(
        "verify-served",
        self_child("verify-served", str(model_dir), str(served_path)),
        timeout_s=300.0)
    verdict = next((ln for ln in verify_log.splitlines()
                    if ln.startswith("VERIFY ")), "VERIFY ?")
    say(f"server PASS requests={len(prompts)} prompt_lens="
        f"{[len(p) for p in prompts]} tokens_each={NEW_TOKENS} "
        f"decode_dispatches={dispatches:.0f} {verdict} "
        f"setup: write_predictor_s={write_wall:.1f} ready_s={ready_wall:.1f} "
        f"verify_s={verify_wall:.1f} requests_wall_s={predict_wall:.1f}")


def leg_kernel(dev: dict) -> None:
    dump = WORK / "xla-dump"
    # never cached: the compiled text is what this leg reads, and a cache
    # hit compiles (and dumps) nothing
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_dump_to={dump}"
        " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*train_step.*"
    ).strip()
    log, wall = run_child("kernel-gpt-flash", example("gpt", *GPT_ARGS),
                          env=env)
    check_device(log, dev, "kernel leg")
    series = timelines(log, "loss", "grad_norm")
    check_finite(series, "kernel leg")
    texts = [p for p in dump.rglob("*train_step*")
             if p.is_file() and p.suffix == ".txt"]
    check(bool(texts), f"no train-step HLO text under {dump}")
    mosaic = sum(p.read_text(errors="replace").count("tpu_custom_call")
                 for p in texts)
    check(mosaic > 0,
          "the compiled train step holds no Mosaic custom call: flash ran "
          "in interpret mode or gave way to blockwise_attention")
    check("flash fell back to blockwise" not in log,
          "flash attention reported a blockwise fallback")
    say(f"kernel PASS {' '.join(GPT_ARGS)} mosaic_custom_calls={mosaic} "
        f"loss={series['loss']} grad_norm={series['grad_norm']} "
        f"wall_s={wall:.1f}")


def leg_mesh(dev: dict) -> None:
    """BERT-base, one step, unpartitioned against fsdp=2 x model=2."""
    runs = {}
    for name, extra in (("mesh-bert-default", []),
                        ("mesh-bert-fsdp2-model2",
                         ["--fsdp=2", "--model-parallel=2"])):
        log, wall = run_child(
            name, example("bert", *BERT_ARGS, "--steps=1", *extra))
        check_device(log, dev, name)
        series = timelines(log, "loss", "grad_norm", "param_devices",
                           "param_bytes", "param_bytes_per_device")
        check_finite(series, name)
        check("Involuntary full rematerialization" not in log,
              f"{name}: the partitioner rematerialized a tensor in full")
        runs[name] = (series, wall)
    base, part = (runs[n][0] for n in runs)
    check(part["param_devices"][0] == 4,
          f"parameters on {part['param_devices'][0]} devices, not 4")
    share = part["param_bytes_per_device"][0] / part["param_bytes"][0]
    check(share <= 0.5,
          f"parameters not partitioned: one device holds {share:.2f} of them")
    gap = abs(part["loss"][0] - base["loss"][0])
    check(gap <= MESH_LOSS_TOL,
          f"first-step loss {part['loss'][0]} (fsdp=2 x model=2) vs "
          f"{base['loss'][0]} (default mesh): gap {gap} > {MESH_LOSS_TOL}")
    say(f"mesh PASS param_devices={part['param_devices'][0]:.0f} "
        f"param_share_per_device={share:.3f} "
        f"first_step_loss default={base['loss'][0]} "
        f"fsdp2xmodel2={part['loss'][0]} gap={gap:.2e} "
        f"wall_s={[round(runs[n][1], 1) for n in runs]}")


# ---------------------------------------------------------------------- main


def main() -> int:
    for d in (WORK, OUT):
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True)
    dev = leg_preflight()

    from kubeflow_tpu.client import Platform

    # the chip count is passed, not discovered: this process stays off jax
    platform = Platform(log_dir=str(WORK / "platform" / "pod-logs"),
                        capacity_chips=dev["device_count"])
    platform.start_tracing(trace_dir=str(WORK / "trace"))
    platform.start()
    try:
        leg_trainer(platform, dev)
        leg_server(platform, dev)
    finally:
        platform.stop()  # reaps every pod it still holds
    leg_kernel(dev)
    if dev["device_count"] >= 4:
        leg_mesh(dev)
    else:
        say(f"mesh SKIPPED device_count={dev['device_count']} < 4")
    check("jax" not in sys.modules,
          "the parent imported jax: it must stay off the chip")
    say(f"all legs PASS total_wall_s={time.monotonic() - _T0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


CHILDREN = {
    "preflight": child_preflight,
    "write-predictor": child_write_predictor,
    "verify-served": child_verify_served,
}

if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1].startswith("--child="):
        sys.exit(CHILDREN[sys.argv[1][len("--child="):]](*sys.argv[2:]))
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        print(f"[chip_smoke] FAIL: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
