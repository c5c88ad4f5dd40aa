"""Device selection — the north star's "select device via a single flag".

The reference platform selects hardware by pod resource requests
(`nvidia.com/gpu`, `google.com/tpu`); here a single `--device=tpu|cpu` flag
picks the JAX platform. Must be called before any jax import touches a
backend, hence the env-var approach.

There is no quiet fallback: `tpu` means the `tpu` platform whatever the
environment holds, and `auto` means "what JAX_PLATFORMS says, else tpu" —
a process started without a chip and without an explicit CPU request
fails at backend start-up instead of training on the host.
"""

from __future__ import annotations

import json
import os
import re


def select_device(device: str = "auto") -> str:
    """Pin the JAX platform. Call before the first jax array op.

    device: "tpu" | "cpu" | "auto". Returns the platform string chosen.
    """
    if device == "auto":
        platform = os.environ.get("JAX_PLATFORMS", "")
        if platform:
            return platform  # jax reads the variable itself
        platform = "tpu"
    elif device in ("tpu", "cpu"):
        platform = device
    else:
        raise ValueError(f"unknown device {device!r}; expected tpu|cpu|auto")

    import jax  # local import: reading jax.config is safe pre-backend

    if jax.config.jax_platforms != platform:
        try:
            jax.config.update("jax_platforms", platform)
        except RuntimeError:
            # backend already initialized; env var is the only lever left
            os.environ["JAX_PLATFORMS"] = platform
    return platform


def device_summary() -> dict:
    """What JAX actually runs on, as JAX reports it: the fields of the
    start-up line `Trainer.fit` and `serving.server` each print once, and
    that the smoke test and every benchmark row read instead of guessing.
    Initialises the backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "process_count": jax.process_count(),
    }


_DEVICE_LINE_RE = re.compile(
    r'platform=("[^"]*") device_kind=("[^"]*") '
    r"device_count=(\d+) process_count=(\d+)")


def parse_device_line(log_text: str) -> dict | None:
    """The first start-up device line in a worker or server log (printed
    through `train.metrics.emit(**device_summary())`), as a dict with the
    keys of `device_summary`; None when the log holds no such line."""
    m = _DEVICE_LINE_RE.search(log_text)
    if m is None:
        return None
    return {
        "platform": json.loads(m.group(1)),
        "device_kind": json.loads(m.group(2)),
        "device_count": int(m.group(3)),
        "process_count": int(m.group(4)),
    }
