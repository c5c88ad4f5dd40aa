"""bench.py and chip_smoke.py tests (CPU): the row contract, the refusal to
measure without a TPU, failed rows, and the smoke's failure without a chip."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest


def test_bench_mnist_row_contract():
    """The smallest bench, called directly at a tiny size on the CPU: the
    row names its metric, its device and its FLOPs; mfu is None off the
    peaks table (only `main`, which requires a TPU, prints device metrics)."""
    import bench

    rec = bench.bench_mnist_mlp(steps=5, batch_size=64)
    assert rec["metric"] == "mnist_mlp_images_per_sec_per_chip"
    assert rec["value"] > 0
    assert rec["unit"] == "images/sec/chip"
    assert rec["model_flops_per_step"] > 0
    assert rec["mfu"] is None
    assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
    assert rec["device_count"] == 8
    json.dumps(rec)  # one JSON line per metric


def _fake_device(monkeypatch, **over):
    from kubeflow_tpu.utils import device

    dev = {"platform": "tpu", "device_kind": "TPU v5 lite",
           "device_count": 1, "process_count": 1, **over}
    monkeypatch.setattr(device, "device_summary", lambda: dev)
    return dev


def test_main_refuses_to_start_without_a_tpu(capsys):
    """No chip, no benchmark: the CPU the tests run on is not measured."""
    import bench

    with pytest.raises(SystemExit) as exc:
        bench.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code) and "tpu" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_main_refuses_a_device_kind_off_the_peaks_table(monkeypatch, capsys):
    import bench

    _fake_device(monkeypatch, device_kind="TPU v9 imaginary")
    with pytest.raises(SystemExit) as exc:
        bench.main(["--suite"])
    assert "PEAK_FLOPS_BY_KIND" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_main_exits_nonzero_when_any_row_failed(monkeypatch, capsys):
    """Every bench still gets its row, the failed one carries the error and
    the device, and the exit code says a row failed — flagship or not."""
    import bench

    dev = _fake_device(monkeypatch)

    def ok():
        return {"metric": "ok_metric", "value": 1.0, "unit": "u"}

    def boom():
        raise RuntimeError("non-finite loss")

    monkeypatch.setattr(bench, "SUITE_BENCHES", [
        (ok, "ok_metric", "u"), (boom, "boom_metric", "u"),
        (ok, "ok_metric", "u")])
    assert bench.main(["--suite"]) == 1
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [r["metric"] for r in rows] == [
        "ok_metric", "boom_metric", "ok_metric"]
    failed = rows[1]
    assert failed["value"] == 0.0 and "non-finite" in failed["error"]
    assert failed["platform"] == "tpu"
    assert failed["device_kind"] == dev["device_kind"]
    assert failed["device_count"] == 1
    # the same suite with nothing failing exits 0
    assert bench.main(["--only", "ok_metric"]) == 0


def test_bench_selection_from_arguments():
    import bench

    assert bench._benches([]) == [bench.FLAGSHIP]
    assert bench._benches(["--suite"]) == bench.SUITE_BENCHES
    assert [b[1] for b in bench._benches(["--only", "bert"])] == [
        "bert_base_steps_per_sec"]


def _smoke_tree(tmp_path, with_package: bool):
    """chip_smoke.py in a directory of its own (it wipes its work and
    output directories beside itself), with or without the repo."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(root, "chip_smoke.py"), tmp_path)
    if with_package:
        for name in ("kubeflow_tpu", "examples"):
            os.symlink(os.path.join(root, name), tmp_path / name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)


def test_chip_smoke_fails_fast_without_a_chip(tmp_path):
    """On this CPU-only box the smoke exits non-zero within seconds, names
    the missing chip, and prints no result line."""
    t0 = time.monotonic()
    out = _smoke_tree(tmp_path, with_package=True)
    assert time.monotonic() - t0 < 60
    assert out.returncode != 0
    assert "Unable to initialize backend 'tpu'" in out.stderr \
        or "not 'tpu'" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    """Without the program beside it the script fails too."""
    out = _smoke_tree(tmp_path, with_package=False)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_bench_continuous_serve_smoke(monkeypatch):
    """Continuous-serving bench runs end-to-end (tiny dims on CPU) and
    emits the metric contract with the scheduling fields."""
    import bench
    from kubeflow_tpu import models

    monkeypatch.setattr(
        models.GPTConfig, "small",
        staticmethod(lambda **kw: models.GPTConfig.tiny(**kw)),
    )
    r = bench.bench_gpt2s_continuous_serve(
        rows=2, n_requests=4, prompt_len=8, new_tokens=4)
    assert r["metric"] == "gpt2s_continuous_serve_tokens_per_sec_per_chip"
    assert r["value"] > 0
    # 4 requests through 2 rows at 8-step ticks = 2 timed dispatches
    # (warmup excluded); sequential serving would need 4
    assert 2 <= r["decode_dispatches"] < 4
    assert r["rows"] == 2 and r["n_requests"] == 4
    # ADVICE r4: per-dispatch FLOPs must carry the steps_per_tick factor
    # (each dispatch chains 8 decode steps) — 2*N*rows*8, not 2*N*rows
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu import models as m2

    cfg = m2.GPTConfig.small(dtype=jnp.bfloat16, dropout_rate=0.0, max_len=12)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(m2.GPTLM(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 8), jnp.int32))["params"]))
    assert r["model_flops_per_step"] == 2 * n_params * 2 * 8


def test_bench_spec_serve_smoke(monkeypatch):
    """Speculative-continuous bench runs end-to-end (tiny dims on CPU):
    self-draft means every round accepts gamma tokens, so the dispatch
    count sits near requests*new_tokens/(rows*(gamma+1))."""
    import bench
    from kubeflow_tpu import models

    monkeypatch.setattr(
        models.GPTConfig, "small",
        staticmethod(lambda **kw: models.GPTConfig.tiny(**kw)),
    )
    r = bench.bench_gpt2s_spec_serve(
        rows=2, n_requests=4, prompt_len=8, new_tokens=8, gamma=3)
    assert r["metric"] == "gpt2s_spec_serve_tokens_per_sec_per_chip"
    assert r["value"] > 0 and r["gamma"] == 3
    # 4 requests x 8 tokens through 2 rows at 4 tokens/round = 4 dispatches
    assert r["decode_dispatches"] <= 5


def test_bench_rolling_decode_smoke(monkeypatch):
    import bench
    from kubeflow_tpu import models

    monkeypatch.setattr(
        models.GPTConfig, "small",
        staticmethod(lambda **kw: models.GPTConfig.tiny(**kw)),
    )
    r = bench.bench_gpt2s_rolling_decode(
        batch_size=2, prompt_len=6, new_tokens=4, window=8, capacity=16,
        budget_len=64)
    assert r["metric"] == "gpt2s_rolling_decode_tokens_per_sec_per_chip"
    assert r["value"] > 0 and r["full_cache_tokens_per_sec"] > 0
    assert r["capacity"] == 16


def test_bench_gpt_flash_smoke(monkeypatch):
    """Long-context GPT bench runs end-to-end (tiny dims, interpret-mode
    pallas on CPU) and emits the metric contract."""
    import bench
    from kubeflow_tpu import models

    monkeypatch.setattr(
        models.GPTConfig, "small",
        staticmethod(lambda **kw: models.GPTConfig.tiny(**kw)),
    )
    # batch divisible by the 8-device data axis of the test mesh
    r = bench.bench_gpt2s_flash_2k(steps=1, batch_size=8, seq_len=256)
    assert r["metric"] == "gpt2s_flash_2k_tokens_per_sec_per_chip"
    assert r["value"] > 0
    assert r["model_flops_per_step"] > 0


def test_resnet_probe_flag_adoption(tmp_path):
    """bench_resnet50 adopts the fastest probe_resnet full-model row at its
    batch size (last line per key wins, append-accumulated artifact); env
    flags override; absent/empty artifact -> None (defaults)."""
    import bench

    art = tmp_path / "probe_resnet.txt"
    assert bench._resnet_probe_flags(128, str(art)) is None
    art.write_text(
        "RESULT resnet50_xla_7x7_fwdbwd_b128_ms=20.000 tflops=40.00\n"
        "RESULT resnet50_xla_s2d_fwdbwd_b128_ms=15.500 tflops=52.00\n"
        "RESULT resnet50_im2col_7x7_fwdbwd_b128_ms=30.000 tflops=26.00\n"
        "RESULT resnet50_xla_s2d_fwdbwd_b256_ms=1.000 tflops=99.00\n"
    )
    assert bench._resnet_probe_flags(128, str(art)) == ("s2d", "xla")
    assert bench._resnet_probe_flags(256, str(art)) == ("s2d", "xla")
    assert bench._resnet_probe_flags(64, str(art)) is None
    # append semantics: a later re-measurement of the same key wins
    with art.open("a") as fh:
        fh.write("RESULT resnet50_xla_7x7_fwdbwd_b128_ms=10.000 tflops=80.00\n")
    assert bench._resnet_probe_flags(128, str(art)) == ("7x7", "xla")
