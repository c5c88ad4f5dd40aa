"""Device selection (utils/device.py): no quiet fallback, one start-up line.

`tpu` is `tpu` whatever JAX_PLATFORMS holds; `auto` is what the variable
says, else `tpu`. The config is restored after each test — the suite itself
runs pinned to the CPU.
"""

import jax
import pytest

from kubeflow_tpu.utils import device


@pytest.fixture
def platforms():
    """jax_platforms as select_device leaves it; restored afterwards."""
    saved = jax.config.jax_platforms
    yield lambda: jax.config.jax_platforms
    jax.config.update("jax_platforms", saved)


@pytest.mark.parametrize("env", ["", "cpu", "cuda", "cuda,cpu", "tpu,cpu"])
def test_tpu_is_tpu_whatever_the_environment_holds(monkeypatch, platforms, env):
    if env:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    else:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert device.select_device("tpu") == "tpu"
    assert platforms() == "tpu"


def test_auto_without_the_variable_is_tpu(monkeypatch, platforms):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert device.select_device("auto") == "tpu"
    assert platforms() == "tpu"


def test_auto_follows_the_variable(monkeypatch, platforms):
    before = platforms()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.select_device("auto") == "cpu"
    assert device.select_device() == "cpu"  # auto is the default
    assert platforms() == before  # jax reads the variable itself


def test_cpu_and_unknown(platforms):
    assert device.select_device("cpu") == "cpu"
    assert platforms() == "cpu"
    with pytest.raises(ValueError, match="unknown device"):
        device.select_device("gpu")


def test_device_line_round_trip(capsys):
    """What Trainer.fit and the model server print once at start-up parses
    back to what jax reports, kind with spaces included."""
    from kubeflow_tpu.train import metrics

    summary = device.device_summary()
    assert summary == {"platform": "cpu", "device_kind": "cpu",
                       "device_count": 8, "process_count": 1}
    line = metrics.emit(**summary)
    assert device.parse_device_line(f"noise\n{line}\nstep=1 loss=0.5\n") \
        == summary
    chip = {"platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 4, "process_count": 1}
    assert device.parse_device_line(metrics.emit(**chip)) == chip
    assert device.parse_device_line("step=1 loss=0.5") is None
    # the numeric collector still reads the counts and skips the strings
    assert metrics.parse_line(line) == {"device_count": 8.0,
                                        "process_count": 1.0}
