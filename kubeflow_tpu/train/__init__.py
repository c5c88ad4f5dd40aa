"""Training loop layer: trainer, data, metrics, checkpointing.

This is in-tree "user workload" territory in the reference (kubeflow/examples
images — SURVEY.md L6) plus the checkpoint/resume contract the platform
guarantees (SURVEY.md §5.4). TPU-native: one jit-compiled train step, static
shapes, donated buffers, orbax async checkpoints.

Importing this package installs the compile listener (utils/compile_cache.py:
every program jax traces, lowers, compiles or loads from then on is in the
start-up log) and notes the package's own import as that log's first entry,
`train.import`: from this file's first line to its last, so the interpreter's
start, `import jax` where the caller did that first, and `kubeflow_tpu/__init__`
lie outside it.
"""

import time as _time

_IMPORT_STARTED = (_time.time(), _time.perf_counter())

from kubeflow_tpu.train.lora import (  # noqa: E402
    LoraModel,
    lora_init,
    lora_merge,
    lora_tx,
)
from kubeflow_tpu.train.trainer import Trainer, TrainerConfig, TrainState  # noqa: E402
from kubeflow_tpu.utils import compile_cache as _compile_cache  # noqa: E402

__all__ = ["Trainer", "TrainerConfig", "TrainState", "LoraModel",
           "lora_init", "lora_merge", "lora_tx"]

_compile_cache.install_compile_listener()
_compile_cache.note_import(
    "train.import", _IMPORT_STARTED[0],
    _time.perf_counter() - _IMPORT_STARTED[1])
