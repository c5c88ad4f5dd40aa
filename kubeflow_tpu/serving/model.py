"""Model API + the in-tree JAX predictor runtime.

Reference parity (unverified cites, SURVEY.md §2.5): kserve
python/kserve/kserve/model.py Model{load, preprocess, predict, postprocess}
— the lifecycle a custom predictor implements — plus the framework-runtime
wrappers (python/sklearnserver etc.), whose TPU-relevant analogue is a
JAX/flax predictor that jit-compiles (XLA) at load and serves from the
device (SURVEY.md §2.5 'XLA-AOT-compiled model on a TPU nodepool').
"""

from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path
from typing import Any

import numpy as np


class Model:
    """Base predictor. Subclass and override load/predict (and optionally
    preprocess/postprocess); the server drives the full chain per request."""

    def __init__(self, name: str):
        self.name = name
        self.ready = False

    def load(self) -> None:
        self.ready = True

    def preprocess(self, inputs: Any) -> Any:
        return inputs

    def predict(self, inputs: Any) -> Any:
        raise NotImplementedError

    def postprocess(self, outputs: Any) -> Any:
        return outputs

    def explain(self, inputs: Any) -> Any:
        """kserve :explain contract: override in an explainer model.
        `self.predict_fn` (bound by the server when an explainer wraps a
        predictor) calls the underlying predictor."""
        raise NotImplementedError(f"model {self.name!r} has no explainer")

    def __call__(self, inputs: Any) -> Any:
        return self.postprocess(self.predict(self.preprocess(inputs)))


class ExplainedModel(Model):
    """Explainer hop (kserve explainer analogue, in-process): predict flows
    through the predictor; :explain calls the explainer with a handle on the
    predictor chain (black-box explainers perturb inputs through it)."""

    def __init__(self, name: str, predictor: Model, explainer: Model):
        super().__init__(name)
        self.predictor = predictor
        self.explainer = explainer
        self.explainer.predict_fn = predictor  # callable chain handle

    def load(self) -> None:
        if not self.predictor.ready:
            self.predictor.load()
        if not self.explainer.ready:
            self.explainer.load()
        self.ready = True

    def predict(self, inputs: Any) -> Any:
        return self.predictor(inputs)

    def explain(self, inputs: Any) -> Any:
        return self.explainer.explain(inputs)


def load_model_class(path: str) -> type[Model]:
    """Import 'package.module:ClassName' (custom-runtime contract)."""
    mod_name, _, cls_name = path.partition(":")
    if not cls_name:
        raise ValueError(f"modelClass {path!r} must look like 'module:Class'")
    cls = getattr(importlib.import_module(mod_name), cls_name)
    if not issubclass(cls, Model):
        raise TypeError(f"{path} is not a kubeflow_tpu.serving.Model subclass")
    return cls


class TransformedModel(Model):
    """Transformer hop (kserve transformer analogue, in-process): the
    transformer's preprocess/postprocess wrap the predictor's full chain."""

    def __init__(self, name: str, predictor: Model, transformer: Model):
        super().__init__(name)
        self.predictor = predictor
        self.transformer = transformer

    def load(self) -> None:
        if not self.predictor.ready:
            self.predictor.load()
        if not self.transformer.ready:
            self.transformer.load()
        self.ready = True

    def preprocess(self, inputs: Any) -> Any:
        return self.transformer.preprocess(inputs)

    def predict(self, inputs: Any) -> Any:
        return self.predictor(inputs)

    def postprocess(self, outputs: Any) -> Any:
        return self.transformer.postprocess(outputs)


# ------------------------------------------------------------ JAX runtime

CONFIG_FILE = "config.json"
PARAMS_FILE = "params.msgpack"


def _build_family(family: str, kwargs: dict):
    """In-tree model registry for the jax runtime (models/ package)."""
    from kubeflow_tpu import models as M

    if family == "mnist-mlp":
        return M.MnistMLP(**kwargs)
    if family == "mnist-cnn":
        return M.MnistCNN(**kwargs)
    if family.startswith("resnet"):
        ctor = {
            "resnet18": M.ResNet18, "resnet34": M.ResNet34,
            "resnet50": M.ResNet50, "resnet101": M.ResNet101,
            "resnet152": M.ResNet152,
        }[family]
        return ctor(**kwargs)
    if family == "bert-classifier":
        cfg_kw = kwargs.pop("config", {})
        cfg = M.BertConfig.tiny(**cfg_kw) if kwargs.pop("size", "tiny") == "tiny" \
            else M.BertConfig.base(**cfg_kw)
        return M.BertForSequenceClassification(cfg=cfg, **kwargs)
    if family == "gpt-lm":
        from kubeflow_tpu.models.gpt import GPTConfig, GPTLM

        cfg_kw = kwargs.pop("config", {})
        cfg = GPTConfig.tiny(**cfg_kw) if kwargs.pop("size", "tiny") == "tiny" \
            else GPTConfig.small(**cfg_kw)
        return GPTLM(cfg, **kwargs)
    if family == "vit-classifier":
        cfg_kw = kwargs.pop("config", {})
        cfg = M.ViTConfig.tiny(**cfg_kw) if kwargs.pop("size", "tiny") == "tiny" \
            else M.ViTConfig.base(**cfg_kw)
        return M.ViTClassifier(cfg, **kwargs)
    raise ValueError(f"unknown model family {family!r}")


def save_predictor(
    model_dir: str | Path,
    family: str,
    variables: dict,
    example_input: np.ndarray,
    generate: dict | None = None,
    quantize: bool = False,
    **family_kwargs,
) -> Path:
    """Write the jax-runtime model-dir contract: config.json (family +
    kwargs + example input signature) and params.msgpack (all variable
    collections). `variables` is {'params': ..., maybe 'batch_stats': ...}.

    generate: for causal-LM families, decode parameters (max_new_tokens,
    temperature, top_k, eos_token_id — rows clamp to EOS after emitting
    it, incompatible with num_beams > 1) — the predictor then serves
    token GENERATION (ids in -> generated ids out, KV-cache decode loop)
    instead of logits.

    quantize: int8 weight-only artifact (~4x smaller params.msgpack;
    per-output-channel scales, dequantized once at load — serving/quant.py)."""
    from flax import serialization

    d = Path(model_dir)
    d.mkdir(parents=True, exist_ok=True)
    example = np.asarray(example_input)
    cfg = {
        "family": family,
        "kwargs": family_kwargs,
        "input_shape": list(example.shape),
        "input_dtype": str(example.dtype),
    }
    if generate is not None:
        cfg["generate"] = generate
    if quantize:
        from kubeflow_tpu.serving.quant import quantize_variables

        cfg["quantized"] = True
        variables = quantize_variables(dict(variables))
    (d / CONFIG_FILE).write_text(json.dumps(cfg, indent=2))
    (d / PARAMS_FILE).write_bytes(serialization.to_bytes(variables))
    return d


def load_generative_model(model_dir: Path):
    """(module, variables, config) rebuilt from a model-dir — the raw
    pieces compositional decode paths consume (e.g. speculative decoding:
    `kubeflow_tpu generate --draft-model-dir`)."""
    import inspect

    import jax
    import jax.numpy as jnp
    from flax import serialization

    model_dir = Path(model_dir)
    config = json.loads((model_dir / CONFIG_FILE).read_text())
    module = _build_family(config["family"], dict(config["kwargs"]))
    example = np.zeros(config["input_shape"], dtype=config["input_dtype"])
    kwargs = {}
    if "train" in inspect.signature(module.__call__).parameters:
        kwargs["train"] = False
    target = module.init(jax.random.PRNGKey(0), jnp.asarray(example), **kwargs)
    raw = (model_dir / PARAMS_FILE).read_bytes()
    if config.get("quantized"):
        # int8 artifact: its tree shape differs from the module's, so
        # restore target-free, dequantize, then cast to the target's leaf
        # dtypes (serving/quant.py)
        from kubeflow_tpu.serving.quant import dequantize_variables

        deq = dequantize_variables(serialization.msgpack_restore(raw))
        variables = jax.tree.map(
            lambda t, x: jnp.asarray(x, t.dtype), target, deq
        )
    else:
        variables = serialization.from_bytes(target, raw)
    return module, variables, config


def _load_predict_fn(model_dir: Path):
    """Rebuild the flax predictor from the model-dir contract. Returns
    (predict_fn, config, example) — the one definition both the jit-at-load
    path and the AOT exporter (serving/aot.py) compile from."""
    import inspect

    module, variables, config = load_generative_model(model_dir)
    example = np.zeros(config["input_shape"], dtype=config["input_dtype"])
    kwargs = {}
    if "train" in inspect.signature(module.__call__).parameters:
        kwargs["train"] = False

    gen = config.get("generate")
    if gen is not None:
        from kubeflow_tpu.models.gpt import beam_search as _beam_search
        from kubeflow_tpu.models.gpt import generate as _generate

        temperature = float(gen.get("temperature", 0.0))
        num_beams = int(gen.get("num_beams", 1))
        if num_beams > 1 and temperature > 0.0:
            raise ValueError(
                "generate config: num_beams > 1 and temperature > 0 are "
                "mutually exclusive (beam search is deterministic)"
            )
        eos_raw = gen.get("eos_token_id")
        # int or a stop-id list (Llama-3 imports) — generate() takes both
        eos_id = (None if eos_raw is None
                  else [int(x) for x in eos_raw]
                  if isinstance(eos_raw, (list, tuple)) else int(eos_raw))
        if num_beams > 1 and eos_id is not None:
            raise ValueError(
                "generate config: eos_token_id is not supported with "
                "num_beams > 1 (beam search scores full-length beams)"
            )
        if num_beams > 1:
            def predict_fn(x):
                ids, _ = _beam_search(
                    module, variables, x,
                    max_new_tokens=int(gen.get("max_new_tokens", 32)),
                    num_beams=num_beams,
                )
                return ids
        elif temperature > 0.0:
            # per-REQUEST key (passed as a traced argument, derived by the
            # caller from seed + a call counter): a key baked into the jit
            # closure would replay the identical "sample" on every request
            def predict_fn(x, key):
                return _generate(
                    module, variables, x,
                    max_new_tokens=int(gen.get("max_new_tokens", 32)),
                    temperature=temperature,
                    top_k=int(gen.get("top_k", 0)),
                    rng=key,
                    eos_token_id=eos_id,
                )
        else:
            def predict_fn(x):
                return _generate(
                    module, variables, x,
                    max_new_tokens=int(gen.get("max_new_tokens", 32)),
                    eos_token_id=eos_id,
                )
    else:
        def predict_fn(x):
            return module.apply(variables, x, **kwargs)

    return predict_fn, config, example


def _log_load(name: str, how: str) -> None:
    """Say which path loaded the predictor — on stderr: the server's log
    takes both streams, and library callers (the CLI) own their stdout."""
    print(f"predictor {name}: {how}", file=sys.stderr, flush=True)


class JaxModel(Model):
    """In-tree-family predictor.

    Load prefers a deploy-time AOT artifact (serving/aot.py: serialized
    jax.export with params baked in — no module rebuild, no params restore,
    no Python retrace; with a warmed persistent compile cache the process
    performs zero backend compilations). Without an artifact it falls back
    to rebuilding the module and jit-compiling at load (warmup on the
    recorded example shape, so the first request pays no compile)."""

    def __init__(self, name: str, model_dir: str | Path):
        super().__init__(name)
        self.model_dir = Path(model_dir)
        self._predict_fn = None
        self._aot_batch: int | None = None
        self._engine = None  # continuous-batching decode engine
        self._fleet = None   # multi-replica fleet router (serving/fleet)
        self.config: dict = {}

    def load(self) -> None:
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.serving import aot

        cfg_path = self.model_dir / CONFIG_FILE
        cfg = json.loads(cfg_path.read_text()) if cfg_path.exists() else {}
        gen = cfg.get("generate") or {}
        if gen.get("continuous"):
            # continuous batching (serving/continuous.py): concurrent
            # requests interleave decode steps on one fixed-row engine
            # instead of serializing whole decodes. Greedy or sampling
            # (per-request keys, engine-static top_k); jit path (the
            # engine's executables splice rows — not exportable as one
            # fixed computation).
            if int(gen.get("num_beams", 1)) > 1:
                raise ValueError(
                    "generate config: continuous batching does not "
                    "compose with beam search (num_beams == 1)")
            from kubeflow_tpu.serving.continuous import ContinuousBatcher

            _log_load(self.name, "continuous engine, jit at load")
            module, variables, self.config = load_generative_model(
                self.model_dir)
            eos = gen.get("eos_token_id")
            # speculative continuous serving: a second model dir provides
            # the draft (same pattern as the CLI's --draft-model-dir);
            # relative paths resolve against the target's model dir
            draft_module = draft_variables = None
            if gen.get("continuous_draft_dir"):
                ddir = Path(gen["continuous_draft_dir"])
                if not ddir.is_absolute():
                    ddir = self.model_dir / ddir
                draft_module, draft_variables, _ = load_generative_model(
                    ddir)
            # fleet extensions (docs/serving.md): chunked prefill, a
            # per-model paged-KV pool for prefix reuse, and with
            # fleet_replicas > 1 a FleetRouter over N engines sharing the
            # pool — SLO admission sheds surface as 503 + Retry-After
            paged_kv = None
            if int(gen.get("paged_kv_block", 0)) > 0:
                from kubeflow_tpu.serving.fleet import PagedKVPool

                paged_kv = PagedKVPool(
                    block_size=int(gen["paged_kv_block"]),
                    capacity_blocks=int(
                        gen.get("paged_kv_capacity_blocks", 1024)))

            def build_engine():
                return ContinuousBatcher(
                    module, variables,
                    max_rows=int(gen.get("continuous_rows", 8)),
                    default_max_new_tokens=int(
                        gen.get("max_new_tokens", 32)),
                    # int or stop-id list — the engine normalizes either
                    eos_token_id=eos,
                    top_k=int(gen.get("top_k", 0)),
                    seed=int(gen.get("seed", 0)),
                    steps_per_tick=int(
                        gen.get("continuous_steps_per_tick", 1)),
                    prefill_buckets=(
                        tuple(gen["continuous_prefill_buckets"])
                        if gen.get("continuous_prefill_buckets") else None),
                    draft_module=draft_module,
                    draft_variables=draft_variables,
                    gamma=int(gen.get("speculative_gamma", 4)),
                    prefill_chunk=int(gen.get("prefill_chunk", 0)),
                    paged_kv=paged_kv,
                )

            n_replicas = int(gen.get("fleet_replicas", 1))
            if n_replicas > 1:
                from kubeflow_tpu.serving.fleet import FleetRouter

                self._fleet = FleetRouter(
                    [build_engine() for _ in range(n_replicas)],
                    ttft_slo_s=float(gen.get("fleet_ttft_slo_s", 0.0)),
                    retry_after_s=float(
                        gen.get("fleet_retry_after_s", 1.0)),
                ).start()
            else:
                self._engine = build_engine().start()
            self.ready = True
            return

        if aot.aot_available(self.model_dir):
            _log_load(self.name, "loading the AOT artifact")
            self.config = json.loads((self.model_dir / CONFIG_FILE).read_text())
            meta = json.loads((self.model_dir / aot.AOT_META).read_text())
            call = aot.load_exported(self.model_dir)
            self._aot_batch = int(meta["batch_size"])
            example = np.zeros(
                self.config["input_shape"], dtype=self.config["input_dtype"]
            )
            # warmup executes the serialized computation once (backend
            # compile — a cache hit when the deploy step warmed the cache)
            np.asarray(call(jnp.asarray(example)))
            self._predict_fn = call
            self.ready = True
            return

        exported_for = aot.artifact_platforms(self.model_dir)
        _log_load(self.name, "jit at load" + (
            f" — the AOT artifact was exported for {exported_for}, this "
            f"backend is {jax.default_backend()!r}"
            if exported_for is not None else ""))
        predict_fn, self.config, example = _load_predict_fn(self.model_dir)
        predict_fn = jax.jit(predict_fn)
        # warmup: trace+compile on the recorded signature
        if self._sampling:
            jax.block_until_ready(
                predict_fn(jnp.asarray(example), jax.random.PRNGKey(0)))
        else:
            predict_fn(jnp.asarray(example)).block_until_ready()
        self._predict_fn = predict_fn
        self.ready = True

    @property
    def _sampling(self) -> bool:
        gen = self.config.get("generate")
        return gen is not None and float(gen.get("temperature", 0.0)) > 0.0

    def predict(self, inputs: np.ndarray) -> np.ndarray:
        x = np.asarray(inputs, dtype=self.config["input_dtype"])
        gen = self.config.get("generate")
        if gen is not None:
            pad = int(gen.get("pad_token_id", 0))
            if (x == pad).any():
                # the decode path has no pad masking (positions are cache-
                # indexed); a padded prompt would write pads into the KV
                # cache and sample from a pad position — reject loudly
                raise ValueError(
                    f"generation prompts must not contain the pad token id "
                    f"{pad}: send equal-length unpadded prompts"
                )
        if getattr(self, "_engine", None) is not None \
                or getattr(self, "_fleet", None) is not None:
            out, _ = self._engine_predict_timed(x, gen)
            return out
        if self._sampling:
            import jax

            seed = int(gen.get("seed", 0))
            # per-request key: seed folds with a monotonically advancing
            # call counter so repeated requests sample fresh completions
            self._calls = getattr(self, "_calls", 0) + 1
            key = jax.random.fold_in(jax.random.PRNGKey(seed), self._calls)
            return np.asarray(self._predict_fn(x, key))
        if self._aot_batch is not None:
            from kubeflow_tpu.serving import aot

            want = tuple(self.config["input_shape"][1:])
            if gen is not None and tuple(x.shape[1:]) != want:
                # generation prompts cannot pad (decode masks by position,
                # not pad id), so the exported fixed shape is a hard
                # contract along every non-batch dim
                raise ValueError(
                    f"AOT generative artifact is fixed to prompt shape "
                    f"{want}; got {tuple(x.shape[1:])} — send "
                    f"{want[0]}-token prompts or serve via the jit path "
                    f"(delete {aot.AOT_FILE})"
                )
            return aot.padded_chunk_predict(self._predict_fn, x, self._aot_batch)
        return np.asarray(self._predict_fn(x))

    def _engine_predict_timed(self, x: np.ndarray, gen: dict):
        """Engine/fleet decode for a prompt batch, with the streaming
        timing the load-test client reads: ({rows}, {"ttft_s",
        "tokens_per_s"}). Fleet admission sheds (FleetOverloaded)
        propagate — the server maps them to 503 + Retry-After."""
        budget = int(gen.get("max_new_tokens", 32))
        eos = gen.get("eos_token_id")
        temp = float(gen.get("temperature", 0.0))
        if self._fleet is not None:
            # gate ONCE with the whole batch's prompt work, then submit
            # ungated: a shed on row k would otherwise orphan the k rows
            # already admitted — decode capacity burned on answers
            # nobody reads, exactly what admission control exists to
            # prevent. A shed here is traced like a submit()-path shed
            # (record_shed), so the 503 body carries the decision's
            # span ctx + request id.
            from kubeflow_tpu.serving.fleet import FleetOverloaded

            batch_tokens = int(sum(len(row) for row in x))
            try:
                self._fleet.admit_or_raise(batch_tokens)
            except FleetOverloaded as exc:
                raise self._fleet.record_shed(exc, batch_tokens)
            submit = lambda row, **kw: self._fleet.submit(  # noqa: E731
                row, gate=False, **kw)
        else:
            submit = self._engine.submit
        reqs = [submit(row, max_new_tokens=budget, temperature=temp)
                for row in x]
        # eos may be a stop-id LIST (Llama-3 imports); the clamp
        # token past a retired row is the FIRST id — generate()'s
        # contract
        clamp = (int(eos[0]) if isinstance(eos, (list, tuple))
                 else None if eos is None else int(eos))
        outs = []
        for r in reqs:
            ids = r.result(timeout=300.0)
            if ids.size < budget:  # pad past the stop with the clamp
                ids = np.concatenate([
                    ids, np.full((budget - ids.size,), clamp,
                                 np.int32)])
            outs.append(ids)
        ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
        rates = [r.tokens_per_s for r in reqs
                 if r.tokens_per_s not in (None, float("inf"))]
        timing = {
            "ttft_s": round(min(ttfts), 6) if ttfts else None,
            "tokens_per_s": (round(sum(rates), 3) if rates else None),
        }
        return np.stack(outs), timing

    def close(self) -> None:
        """Stop the engine/fleet ticker threads (server shutdown path)."""
        if self._engine is not None:
            self._engine.stop()
        if self._fleet is not None:
            self._fleet.stop()

    def predict_timed(self, inputs: np.ndarray):
        """predict() plus per-request streaming timing when an engine or
        fleet serves the model — (output, timing|None). The v1 server
        surfaces the timing so clients (ServingClient.predict_timed)
        measure TTFT from the engine's own token timestamps instead of
        guessing from HTTP wall time."""
        x = np.asarray(inputs, dtype=self.config["input_dtype"])
        gen = self.config.get("generate")
        if gen is not None and (self._engine is not None
                                or self._fleet is not None):
            pad = int(gen.get("pad_token_id", 0))
            if (x == pad).any():
                raise ValueError(
                    f"generation prompts must not contain the pad token id "
                    f"{pad}: send equal-length unpadded prompts"
                )
            return self._engine_predict_timed(x, gen)
        return self.predict(inputs), None

    def postprocess(self, outputs: np.ndarray) -> dict:
        """Classification contract: logits -> class + per-class scores.
        Generative configs return the generated token ids directly."""
        if self.config.get("generate") is not None:
            ids = np.asarray(outputs, dtype=np.int64)
            return {"predictions": ids.tolist()}
        logits = np.asarray(outputs, dtype=np.float32)
        return {
            "predictions": np.argmax(logits, axis=-1).tolist(),
            "logits": logits.tolist(),
        }
