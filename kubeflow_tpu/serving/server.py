"""ModelServer — REST surface speaking the v1 and v2 inference protocols.

Reference parity (unverified cites, SURVEY.md §2.5, §3.5): kserve
python/kserve/kserve/model_server.py + protocol/ — v1 (`:predict`) and v2
Open Inference Protocol endpoints. Implemented on http.server (stdlib) so
the serving path has zero web-framework dependencies; JSON tensors in/out.

Routes:
  GET  /v2                         server metadata
  GET  /v2/health/live             liveness
  GET  /v2/health/ready            readiness (all models loaded)
  GET  /v2/models/{m}              model metadata
  GET  /v2/models/{m}/ready        per-model readiness
  POST /v2/models/{m}/infer        OIP inference
  GET  /v1/models/{m}              v1 status
  POST /v1/models/{m}:predict      v1 inference ({"instances": [...]})

Run as a pod: python -m kubeflow_tpu.serving.server --model-name m ...
"""

from __future__ import annotations

import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from kubeflow_tpu.serving.model import Model
from kubeflow_tpu.serving.requestid import (
    get_request_id,
    new_request_id,
    set_request_id,
)

SERVER_NAME = "kubeflow-tpu-modelserver"
SERVER_VERSION = "0.1"

_V2_TO_NP = {
    "FP16": np.float16, "FP32": np.float32, "FP64": np.float64,
    "INT8": np.int8, "INT16": np.int16, "INT32": np.int32, "INT64": np.int64,
    "UINT8": np.uint8, "BOOL": np.bool_,
}
_NP_TO_V2 = {np.dtype(v): k for k, v in _V2_TO_NP.items()}


def _np_to_datatype(arr: np.ndarray) -> str:
    return _NP_TO_V2.get(arr.dtype, "FP32")


class _RawJSON:
    """Pre-serialized JSON response body (single-serialization hot path);
    optionally carries extra response headers (503 Retry-After)."""

    __slots__ = ("data", "headers")

    def __init__(self, data: bytes, headers: dict | None = None):
        self.data = data
        self.headers = headers or {}


class ModelServer:
    """Hosts a repository of models behind one HTTP port.

    Agent capabilities (SURVEY.md §2.5 Agent row — serving/agent.py):
    request/response logging (`request_log_path` + GET /metrics counters),
    adaptive micro-batching (`max_batch_size` > 0 enables; concurrent
    requests coalesce into one forward pass), and the v2 repository API
    (POST /v2/repository/{index,models/{m}/load,models/{m}/unload}) for
    multi-model load/unload against `repository_dir`.
    """

    def __init__(self, models: list[Model] | None = None, port: int = 8080,
                 host: str = "127.0.0.1", request_log_path: str | None = None,
                 max_batch_size: int = 0, batch_max_latency_ms: float = 5.0,
                 repository_dir: str = ""):
        from kubeflow_tpu.serving.agent import MicroBatcher, RequestLogger

        self.models: dict[str, Model] = {}
        self.host = host
        self.port = port
        self.logger = RequestLogger(request_log_path)
        self.max_batch_size = max_batch_size
        self.batch_max_latency_ms = batch_max_latency_ms
        self.repository_dir = repository_dir
        self._batchers: dict[str, MicroBatcher] = {}
        self._httpd: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        for m in models or []:
            self.register(m)

    def register(self, model: Model) -> None:
        from kubeflow_tpu.serving.agent import MicroBatcher

        self.models[model.name] = model
        if self.max_batch_size > 0:
            old = self._batchers.pop(model.name, None)
            if old is not None:
                old.stop()
            self._batchers[model.name] = MicroBatcher(
                model, self.max_batch_size, self.batch_max_latency_ms
            )

    def unregister(self, name: str) -> bool:
        b = self._batchers.pop(name, None)
        if b is not None:
            b.stop()
        m = self.models.pop(name, None)
        close = getattr(m, "close", None)
        if close is not None:
            close()  # engine/fleet ticker threads die with the model
        return m is not None

    def _call_model(self, m: Model, arr):
        # dict inputs (multi-input models) cannot coalesce on a shared batch
        # axis — they bypass the adaptive batcher
        batcher = self._batchers.get(m.name)
        if batcher is not None and not isinstance(arr, dict):
            return batcher(arr)
        return m(arr)

    # ----------------------------------------------------------- lifecycle

    def start(self, block: bool = False) -> "ModelServer":
        for m in self.models.values():
            if not m.ready:
                m.load()
        handler = _make_handler(self)
        self._httpd = ThreadingHTTPServer((self.host, self.port), handler)
        self.port = self._httpd.server_address[1]  # resolve port 0
        if block:
            self._httpd.serve_forever()
        else:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever, daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        for b in self._batchers.values():
            b.stop()
        for m in self.models.values():
            close = getattr(m, "close", None)
            if close is not None:
                close()
        self.logger.close()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # ------------------------------------------------------------ handlers

    def handle_get(self, path: str) -> tuple[int, object]:
        if path == "/metrics":
            text = self.logger.render_metrics()  # raw prometheus text
            # continuous-batching engines publish scheduler gauges
            eng_lines = []
            fleet_lines = []
            for name, m in sorted(self.models.items()):
                fleet = getattr(m, "_fleet", None)
                engines = ([(name, getattr(m, "_engine", None))]
                           if fleet is None else
                           [(f"{name}:{r.name}", r.engine)
                            for r in fleet.replicas])
                if fleet is not None:
                    snap = fleet.snapshot()
                    fleet_lines += [
                        f'kfserving_fleet_{k}{{model="{name}"}} {v}'
                        for k, v in sorted(snap.items())
                        if isinstance(v, (int, float))
                    ]
                for label, eng in engines:
                    if eng is None:
                        continue
                    # gauges are instantaneous best-effort reads: the
                    # ticker mutates _rows/step_count OUTSIDE the engine
                    # lock by design (the lock guards only the submit
                    # queue — see tick()'s locking note), so only _queue
                    # needs the lock; a mid-tick read can be off by one
                    # row/dispatch, which a scrape-interval consumer
                    # cannot observe
                    busy = sum(1 for r in eng._rows if r is not None)
                    dispatches = eng.step_count
                    with eng._lock:
                        queued = len(eng._queue)
                    eng_lines += [
                        f'kfserving_engine_decode_dispatches_total'
                        f'{{model="{label}"}} {dispatches}',
                        f'kfserving_engine_rows_busy{{model="{label}"}} '
                        f'{busy}',
                        f'kfserving_engine_rows_total{{model="{label}"}} '
                        f'{eng.max_rows}',
                        f'kfserving_engine_queue_depth{{model="{label}"}} '
                        f'{queued}',
                    ]
            if fleet_lines:
                text += "# TYPE kfserving_fleet gauge\n" \
                    + "\n".join(fleet_lines) + "\n"
            if eng_lines:
                text += "\n".join(
                    ["# TYPE kfserving_engine_decode_dispatches_total "
                     "counter",
                     "# TYPE kfserving_engine_rows_busy gauge",
                     "# TYPE kfserving_engine_rows_total gauge",
                     "# TYPE kfserving_engine_queue_depth gauge"]
                    + eng_lines) + "\n"
            return 200, text
        if path == "/v2":
            return 200, {
                "name": SERVER_NAME,
                "version": SERVER_VERSION,
                "extensions": [],
            }
        if path == "/v2/health/live":
            return 200, {"live": True}
        if path == "/v2/health/ready":
            ready = all(m.ready for m in self.models.values()) and bool(self.models)
            return (200 if ready else 503), {"ready": ready}
        if path.startswith("/v2/models/") and path.endswith("/ready"):
            name = path[len("/v2/models/"):-len("/ready")]
            m = self.models.get(name)
            if m is None:
                return 404, {"error": f"model {name!r} not found"}
            return (200 if m.ready else 503), {"name": name, "ready": m.ready}
        if path.startswith("/v2/models/"):
            name = path[len("/v2/models/"):]
            m = self.models.get(name)
            if m is None:
                return 404, {"error": f"model {name!r} not found"}
            meta = {"name": name, "platform": "jax-xla", "versions": ["1"]}
            im = self.input_metadata(m)
            if im is not None:
                meta["inputs"] = [im]
            return 200, meta
        if path.startswith("/v1/models/"):
            name = path[len("/v1/models/"):]
            m = self.models.get(name)
            if m is None:
                return 404, {"error": f"model {name!r} not found"}
            return 200, {"name": name, "ready": m.ready}
        return 404, {"error": f"no route {path!r}"}

    def handle_post(self, path: str, body: dict, req_bytes: int = 0) -> tuple[int, dict]:
        if path.startswith("/v1/models/") and path.endswith(":predict"):
            name = path[len("/v1/models/"):-len(":predict")]
            return self._logged(name, "v1", req_bytes, self._predict_v1, body)
        if path.startswith("/v1/models/") and path.endswith(":explain"):
            name = path[len("/v1/models/"):-len(":explain")]
            return self._logged(name, "v1-explain", req_bytes,
                                self._explain_v1, body)
        if path.startswith("/v2/models/") and path.endswith("/infer"):
            name = path[len("/v2/models/"):-len("/infer")]
            return self._logged(name, "v2", req_bytes, self._infer_v2, body)
        # ---- v2 repository API (multi-model load/unload)
        if path == "/v2/repository/index":
            return 200, [
                {"name": n, "state": "READY" if m.ready else "UNAVAILABLE",
                 "version": "1"}
                for n, m in sorted(self.models.items())
            ]
        if path.startswith("/v2/repository/models/") and path.endswith("/load"):
            name = path[len("/v2/repository/models/"):-len("/load")]
            return self._repo_load(name, body)
        if path.startswith("/v2/repository/models/") and path.endswith("/unload"):
            name = path[len("/v2/repository/models/"):-len("/unload")]
            if not self.unregister(name):
                return 404, {"error": f"model {name!r} not found"}
            return 200, {"name": name, "state": "UNAVAILABLE"}
        return 404, {"error": f"no route {path!r}"}

    def _logged(self, name: str, protocol: str, req_bytes: int, fn, body):
        import time as _time

        t0 = _time.perf_counter()
        out = fn(name, body)
        # handlers return (code, payload) or (code, payload, headers) —
        # the fleet's 503 shed carries its Retry-After hint through here
        code, payload = out[0], out[1]
        headers = out[2] if len(out) > 2 else None
        # error bodies carry the request id (the apiserver's existing
        # contract, extended to the model server): a logged 4xx/5xx —
        # including the fleet's 503 shed — is greppable back to its
        # X-Request-Id without the client having kept the header
        rid = get_request_id()
        if code >= 400 and isinstance(payload, dict) and rid:
            payload.setdefault("request_id", rid)
        # serialize exactly once: the handler sends these bytes verbatim
        data = json.dumps(payload).encode()
        self.logger.log(
            name, protocol, code, _time.perf_counter() - t0, req_bytes, len(data)
        )
        return code, _RawJSON(data, headers)

    def _repo_load(self, name: str, body: dict) -> tuple[int, dict]:
        """Load (or reload) a model from the repository dir or a storage URI
        — the kserve agent multi-model-puller analogue."""
        import re

        from kubeflow_tpu.serving.model import JaxModel
        from kubeflow_tpu.serving.storage import pull_model

        # the name becomes a filesystem path component: allowlist it so a
        # crafted '../..' name can never escape the repository dir (pull_model
        # rmtree's its destination)
        if not re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9._-]*", name):
            return 422, {"error": f"invalid model name {name!r}"}
        body = body or {}
        uri = body.get("storage_uri", "")
        try:
            if uri:
                model_dir = pull_model(
                    uri, f"{self.repository_dir or '.kubeflow_tpu/models'}/{name}"
                )
            elif self.repository_dir:
                model_dir = f"{self.repository_dir}/{name}"
            else:
                return 400, {"error": "no storage_uri and no repository_dir"}
            model = JaxModel(name, model_dir)
            model.load()
        except Exception as exc:  # noqa: BLE001 — load failure is a client-visible error
            return 500, {"error": f"{type(exc).__name__}: {exc}"}
        self.register(model)
        return 200, {"name": name, "state": "READY"}

    @staticmethod
    def postprocess_arrays(out) -> list[tuple[str, np.ndarray]]:
        """Normalize a model's output into named v2 tensors — the ONE place
        both the HTTP and gRPC v2 surfaces get their output contract from."""
        if isinstance(out, dict):
            # the classification postprocess contract is exactly
            # {predictions[, logits]}; any other key set is a generic
            # named-output model (e.g. triton multi-output) and every
            # tensor must survive
            if "predictions" in out and set(out) <= {"predictions", "logits"}:
                return [
                    ("predictions", np.asarray(out["predictions"])),
                    ("logits",
                     np.asarray(out.get("logits", []), dtype=np.float32)),
                ]
            return [(str(k), np.asarray(v)) for k, v in out.items()]
        return [("output-0", np.asarray(out))]

    @staticmethod
    def input_metadata(m: Model) -> dict | None:
        """v2 metadata for a model's input tensor (shared HTTP/gRPC)."""
        cfg = getattr(m, "config", None)
        if not cfg:
            return None
        return {
            "name": "input-0",
            "datatype": _NP_TO_V2.get(np.dtype(cfg["input_dtype"]), "FP32"),
            "shape": [-1, *cfg["input_shape"][1:]],
        }

    @staticmethod
    def _shed_body(exc) -> dict:
        """The 503 shed response body: error + the shed decision's span
        context and request id when tracing stamped them
        (serving/fleet/router.FleetOverloaded)."""
        body = {"error": str(exc)}
        ctx = getattr(exc, "trace_ctx", None)
        if ctx is not None:
            body["trace"] = ctx.to_header()
        rid = getattr(exc, "request_id", "") or get_request_id()
        if rid:
            body["request_id"] = rid
        return body

    def _get_ready_model(self, name: str) -> Model | tuple[int, dict]:
        m = self.models.get(name)
        if m is None:
            return 404, {"error": f"model {name!r} not found"}
        if not m.ready:
            return 503, {"error": f"model {name!r} not ready"}
        return m

    def _predict_v1(self, name: str, body: dict) -> tuple:
        from kubeflow_tpu.serving.fleet import FleetOverloaded

        m = self._get_ready_model(name)
        if isinstance(m, tuple):
            return m
        instances = body.get("instances")
        if instances is None:
            return 400, {"error": "v1 request must carry 'instances'"}
        timing = None
        try:
            if getattr(m, "_engine", None) is not None \
                    or getattr(m, "_fleet", None) is not None:
                # engine/fleet decode: thread the streaming timing
                # (TTFT, tokens/sec) into the response so clients see
                # engine truth, not HTTP wall-time guesses
                raw, timing = m.predict_timed(
                    m.preprocess(np.asarray(instances)))
                out = m.postprocess(raw)
            else:
                out = self._call_model(m, np.asarray(instances))
        except FleetOverloaded as exc:
            # the activator's existing shed contract: the client re-dials
            # after the hint (serving/client.py _post). The body carries
            # the shed decision's span context, so a shed request is
            # attributable in the trace, not just gone
            return 503, self._shed_body(exc), {
                "Retry-After": str(max(1, int(round(exc.retry_after_s))))}
        except Exception as exc:  # noqa: BLE001 — surface as 500, keep serving
            return 500, {"error": f"{type(exc).__name__}: {exc}"}
        if isinstance(out, dict):
            # ndarray values (multi-output runtimes) must be JSON-ready
            body = {k: v.tolist() if isinstance(v, np.ndarray) else v
                    for k, v in out.items()}
            if "predictions" not in body:
                body = {"predictions": body}
        else:
            body = {"predictions": np.asarray(out).tolist()}
        if timing is not None:
            body["timing"] = timing
        return 200, body

    def _explain_v1(self, name: str, body: dict) -> tuple[int, dict]:
        m = self._get_ready_model(name)
        if isinstance(m, tuple):
            return m
        # no-explainer is a routing fact, decided by type — a crashing
        # explainer (incl. a NotImplementedError from user code) is a 500
        if type(m).explain is Model.explain:
            return 404, {"error": f"model {name!r} has no explainer"}
        instances = body.get("instances")
        if instances is None:
            return 400, {"error": "v1 request must carry 'instances'"}
        try:
            out = m.explain(np.asarray(instances))
        except Exception as exc:  # noqa: BLE001 — surface as 500, keep serving
            return 500, {"error": f"{type(exc).__name__}: {exc}"}
        if isinstance(out, dict):
            return 200, out
        return 200, {"explanations": np.asarray(out).tolist()}

    def _infer_v2(self, name: str, body: dict) -> tuple:
        from kubeflow_tpu.serving.fleet import FleetOverloaded

        m = self._get_ready_model(name)
        if isinstance(m, tuple):
            return m
        inputs = body.get("inputs") or []
        if not inputs:
            return 400, {"error": "v2 request must carry 'inputs'"}

        def decode(t: dict) -> np.ndarray:
            return np.asarray(
                t["data"],
                dtype=_V2_TO_NP.get(t.get("datatype", "FP32"), np.float32),
            ).reshape(t["shape"])

        try:
            if len(inputs) == 1:
                arr = decode(inputs[0])
            else:  # multi-input model: route by declared tensor names
                arr = {t.get("name", f"input-{i}"): decode(t)
                       for i, t in enumerate(inputs)}
            out = self._call_model(m, arr)
        except FleetOverloaded as exc:
            # same shed contract as v1: clients back off on the server's
            # schedule instead of hard-failing or piling on immediately
            return 503, self._shed_body(exc), {
                "Retry-After": str(max(1, int(round(exc.retry_after_s))))}
        except Exception as exc:  # noqa: BLE001
            return 500, {"error": f"{type(exc).__name__}: {exc}"}
        arrays = self.postprocess_arrays(out)
        return 200, {
            "model_name": name,
            "model_version": "1",
            "outputs": [
                {
                    "name": k,
                    "shape": list(v.shape),
                    "datatype": _np_to_datatype(v),
                    "data": v.ravel().tolist(),
                }
                for k, v in arrays
            ],
        }


def _make_handler(server: ModelServer):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # route to stdout for pod logs
            print(f"[http] {fmt % args}", flush=True)

        def _assign_request_id(self) -> None:
            # assign-or-echo (the apiserver's control-plane contract,
            # extended end-to-end through the serving path): the id
            # rides a contextvar on this request thread so the fleet's
            # `request` root span and every error body can stamp it
            set_request_id(self.headers.get("X-Request-Id")
                           or new_request_id())

        def _reply(self, code: int, payload) -> None:
            extra = {}
            if isinstance(payload, _RawJSON):
                data, ctype = payload.data, "application/json"
                extra = payload.headers
            elif isinstance(payload, str):
                data, ctype = payload.encode(), "text/plain; version=0.0.4"
            else:
                if code >= 400 and isinstance(payload, dict) \
                        and get_request_id():
                    payload.setdefault("request_id", get_request_id())
                data, ctype = json.dumps(payload).encode(), "application/json"
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            if get_request_id():
                self.send_header("X-Request-Id", get_request_id())
            for name, value in extra.items():
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):  # noqa: N802 (http.server API)
            self._assign_request_id()
            code, payload = server.handle_get(self.path)
            self._reply(code, payload)

        def do_POST(self):  # noqa: N802
            self._assign_request_id()
            length = int(self.headers.get("Content-Length", 0))
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as exc:
                self._reply(400, {"error": f"bad json: {exc}"})
                return
            code, payload = server.handle_post(self.path, body, req_bytes=length)
            self._reply(code, payload)

    return Handler


# -------------------------------------------------------------------- main

def main(argv: list[str] | None = None) -> None:
    import argparse

    from kubeflow_tpu.serving.model import JaxModel, load_model_class
    from kubeflow_tpu.serving.storage import pull_model

    ap = argparse.ArgumentParser(description="kubeflow-tpu model server")
    ap.add_argument("--model-name", required=True)
    ap.add_argument("--storage-uri", default="")
    ap.add_argument("--model-dir", default=".kubeflow_tpu/models")
    ap.add_argument(
        "--runtime", default="jax",
        choices=["jax", "custom", "sklearn", "torch", "xgboost", "lightgbm",
                 "paddle", "pmml", "triton"],
    )
    ap.add_argument("--model-class", default="")
    ap.add_argument("--transformer-class", default="")
    ap.add_argument("--explainer-class", default="")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--device", default="auto",
                    choices=["tpu", "cpu", "auto"],
                    help="auto = what JAX_PLATFORMS says, else tpu")
    ap.add_argument("--aot", action="store_true",
                    help="jax runtime: export+serialize the compiled "
                         "predictor at load if no artifact exists; replicas "
                         "then serve the AOT artifact (serving/aot.py)")
    # agent features (SURVEY.md §2.5 Agent row)
    ap.add_argument("--request-log", default="",
                    help="JSONL request/response log path")
    ap.add_argument("--max-batch-size", type=int, default=0,
                    help=">0 enables adaptive micro-batching")
    ap.add_argument("--batch-max-latency-ms", type=float, default=5.0)
    ap.add_argument("--repository-dir", default="",
                    help="multi-model repository root for /v2/repository API")
    ap.add_argument("--grpc-port", type=int, default=-1,
                    help=">=0 also serves the v2 OIP over gRPC (0 = ephemeral)")
    args = ap.parse_args(argv)

    from kubeflow_tpu.utils.device import select_device

    # like every other entry point: never a quiet run on the host
    select_device(args.device)

    if args.runtime == "custom":
        cls = load_model_class(args.model_class)
        model: Model = cls(args.model_name)
    else:
        model_dir = args.model_dir
        if args.storage_uri:
            model_dir = pull_model(args.storage_uri, f"{args.model_dir}/{args.model_name}")
        if args.runtime == "jax":
            from kubeflow_tpu.train import metrics as metrics_lib
            from kubeflow_tpu.utils.compile_cache import (
                enable_persistent_cache,
                resolve_cache_dir,
            )
            from kubeflow_tpu.utils.device import device_summary

            # persistent XLA compile cache (utils/compile_cache.py): a
            # replica restarted against the directory its predecessor
            # warmed compiles nothing; inference programs are safe under it
            cache_dir = resolve_cache_dir(default=True)
            enable_persistent_cache(cache_dir)
            # the device this server answers from, as jax reports it
            metrics_lib.emit(**device_summary())
            if args.aot:
                from kubeflow_tpu.serving.aot import aot_available, export_predictor

                if not aot_available(model_dir):
                    export_predictor(model_dir, compile_cache=cache_dir)
            model = JaxModel(args.model_name, model_dir)
        else:
            from kubeflow_tpu.serving.runtimes import build_runtime

            model = build_runtime(args.runtime, args.model_name, model_dir)
    if args.transformer_class:
        from kubeflow_tpu.serving.model import TransformedModel

        t_cls = load_model_class(args.transformer_class)
        model = TransformedModel(
            args.model_name, model, t_cls(f"{args.model_name}-transformer")
        )
    if args.explainer_class:
        from kubeflow_tpu.serving.model import ExplainedModel

        e_cls = load_model_class(args.explainer_class)
        model = ExplainedModel(
            args.model_name, model, e_cls(f"{args.model_name}-explainer")
        )

    srv = ModelServer(
        [model], port=args.port, host=args.host,
        request_log_path=args.request_log or None,
        max_batch_size=args.max_batch_size,
        batch_max_latency_ms=args.batch_max_latency_ms,
        repository_dir=args.repository_dir,
    )
    # gRPC binds BEFORE the HTTP server goes live: the controller's
    # readiness probe is HTTP, and an annotated gRPC port must never refuse
    # connections after readiness reports true
    grpc_note = ""
    if args.grpc_port >= 0:
        from kubeflow_tpu.serving.grpc_server import serve_grpc

        _, grpc_addr = serve_grpc(srv, port=args.grpc_port, host=args.host)
        grpc_note = f" grpc={grpc_addr}"
    srv.start(block=False)
    print(f"server ready url={srv.url} model={args.model_name}{grpc_note}",
          flush=True)
    threading.Event().wait()  # serve until killed


if __name__ == "__main__":
    main()
