"""P6: serving (KServe parity) tests.

Layered like the reference's (SURVEY.md §2.5): protocol handlers against an
in-process ModelServer, storage initializer as pure file ops, the jax
runtime's save/load round-trip, and ISVC e2e over the platform with real
predictor subprocesses (readiness, self-healing, round-robin, transformer).
"""

import json
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from kubeflow_tpu.client import Platform
from kubeflow_tpu.serving import (
    InferenceService,
    InferenceServiceSpec,
    ModelServer,
    PredictorRuntime,
    PredictorSpec,
    ServingClient,
    TransformerSpec,
    pull_model,
    resolve_uri,
    save_predictor,
    validate_isvc,
)
from kubeflow_tpu.api.common import ObjectMeta
from kubeflow_tpu.serving.model import JaxModel

from serving_fixtures import DoubleModel

FIXTURES_DIR = str(Path(__file__).resolve().parent)


class TestStorage:
    def test_file_uri(self, tmp_path):
        src = tmp_path / "model"
        src.mkdir()
        (src / "weights.bin").write_bytes(b"w")
        dest = pull_model(f"file://{src}", tmp_path / "dest")
        assert (dest / "weights.bin").read_bytes() == b"w"

    def test_pvc_uri(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KFTPU_PVC_ROOT", str(tmp_path / "volumes"))
        vol = tmp_path / "volumes" / "models-vol" / "bert"
        vol.mkdir(parents=True)
        (vol / "config.json").write_text("{}")
        dest = pull_model("pvc://models-vol/bert", tmp_path / "dest")
        assert (dest / "config.json").exists()

    def test_remote_schemes_have_no_local_path(self):
        # remote schemes resolve through providers in pull_model; the egress
        # gate (and the emulator) are covered in test_storage_schemes.py
        for uri in ("gs://bucket/m", "s3://bucket/m", "hf://org/m"):
            with pytest.raises(RuntimeError, match="pull_model"):
                resolve_uri(uri)

    def test_missing_source(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            pull_model(str(tmp_path / "nope"), tmp_path / "dest")


@pytest.fixture()
def server():
    s = ModelServer([DoubleModel("dbl")], port=0)
    s.start()
    yield s
    s.stop()


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestProtocol:
    def test_server_metadata(self, server):
        code, body = _get(f"{server.url}/v2")
        assert code == 200 and body["name"] == "kubeflow-tpu-modelserver"

    def test_health(self, server):
        assert _get(f"{server.url}/v2/health/live")[0] == 200
        code, body = _get(f"{server.url}/v2/health/ready")
        assert code == 200 and body["ready"] is True

    def test_model_metadata_and_ready(self, server):
        code, body = _get(f"{server.url}/v2/models/dbl")
        assert code == 200 and body["platform"] == "jax-xla"
        assert _get(f"{server.url}/v2/models/dbl/ready")[0] == 200
        assert _get(f"{server.url}/v2/models/nope")[0] == 404

    def test_v1_predict(self, server):
        code, body = _post(
            f"{server.url}/v1/models/dbl:predict", {"instances": [[1.0, 2.0]]}
        )
        assert code == 200
        assert body["predictions"] == [[2.0, 4.0]]

    def test_v1_status(self, server):
        code, body = _get(f"{server.url}/v1/models/dbl")
        assert code == 200 and body["ready"] is True

    def test_v2_infer(self, server):
        code, body = _post(
            f"{server.url}/v2/models/dbl/infer",
            {"inputs": [{"name": "input-0", "shape": [2, 2],
                         "datatype": "FP32", "data": [1, 2, 3, 4]}]},
        )
        assert code == 200
        out = body["outputs"][0]
        assert out["shape"] == [2, 2]
        assert out["data"] == [2.0, 4.0, 6.0, 8.0]

    def test_v2_bad_request(self, server):
        assert _post(f"{server.url}/v2/models/dbl/infer", {})[0] == 400

    def test_v1_unknown_model(self, server):
        assert _post(f"{server.url}/v1/models/nope:predict", {"instances": []})[0] == 404


class TestJaxRuntime:
    def test_save_load_predict_roundtrip(self, tmp_path):
        import jax

        from kubeflow_tpu.models import MnistMLP

        model = MnistMLP(hidden=(16,), num_classes=10)
        example = np.zeros((1, 64), np.float32)
        variables = model.init(jax.random.PRNGKey(0), example)
        d = save_predictor(
            tmp_path / "m", "mnist-mlp", dict(variables), example,
            hidden=[16], num_classes=10,
        )
        jm = JaxModel("mnist", d)
        jm.load()
        assert jm.ready
        x = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)
        out = jm(x)
        assert len(out["predictions"]) == 4
        assert np.asarray(out["logits"]).shape == (4, 10)
        # determinism: same params, same input, same logits
        expected = np.asarray(model.apply(variables, x), np.float32)
        np.testing.assert_allclose(np.asarray(out["logits"]), expected, rtol=1e-5)


class TestSerde:
    def test_sample_manifest_roundtrip(self):
        from kubeflow_tpu.serving.serde import isvc_from_yaml, isvc_to_yaml

        text = Path("samples/inferenceservice_mnist.yaml").read_text()
        isvc = isvc_from_yaml(text)
        validate_isvc(isvc)
        assert isvc.metadata.name == "mnist-server"
        assert isvc.spec.predictor.runtime == PredictorRuntime.JAX
        assert isvc.spec.predictor.replicas == 2
        assert isvc.spec.predictor.device == "tpu"
        again = isvc_from_yaml(isvc_to_yaml(isvc))
        assert isvc_to_yaml(again) == isvc_to_yaml(isvc)

    def test_gptlm_sample_roundtrip(self):
        from kubeflow_tpu.serving.serde import isvc_from_yaml, isvc_to_yaml

        text = Path("samples/inferenceservice_gptlm.yaml").read_text()
        isvc = isvc_from_yaml(text)
        validate_isvc(isvc)
        assert isvc.metadata.name == "gpt-lm-server"
        assert isvc.spec.autoscaling.min_replicas == 0  # scale-to-zero
        again = isvc_from_yaml(isvc_to_yaml(isvc))
        assert isvc_to_yaml(again) == isvc_to_yaml(isvc)


@pytest.fixture()
def platform(tmp_path):
    p = Platform(log_dir=str(tmp_path / "pod-logs"))
    with p:
        yield p


@pytest.fixture()
def serving(platform):
    return ServingClient(platform)


def custom_isvc(name, model_class="serving_fixtures:DoubleModel", replicas=1,
                transformer=None):
    return InferenceService(
        metadata=ObjectMeta(name=name),
        spec=InferenceServiceSpec(
            predictor=PredictorSpec(
                runtime=PredictorRuntime.CUSTOM,
                model_class=model_class,
                replicas=replicas,
                env={"PYTHONPATH": FIXTURES_DIR},
            ),
            transformer=transformer,
        ),
    )


class TestValidation:
    def test_jax_requires_storage(self):
        isvc = InferenceService(
            metadata=ObjectMeta(name="x"),
            spec=InferenceServiceSpec(predictor=PredictorSpec()),
        )
        with pytest.raises(ValueError, match="storageUri"):
            validate_isvc(isvc)

    def test_custom_requires_class(self):
        isvc = InferenceService(
            metadata=ObjectMeta(name="x"),
            spec=InferenceServiceSpec(
                predictor=PredictorSpec(runtime=PredictorRuntime.CUSTOM)
            ),
        )
        with pytest.raises(ValueError, match="modelClass"):
            validate_isvc(isvc)


class TestISVCE2E:
    def test_custom_predictor_lifecycle(self, serving):
        serving.create(custom_isvc("dbl"))
        isvc = serving.wait_ready("dbl", timeout_s=60)
        assert isvc.status.url.startswith("http://127.0.0.1:")
        out = serving.predict("dbl", [[1.5, 2.5]])
        assert out["predictions"] == [[3.0, 5.0]]
        out2 = serving.infer("dbl", [1, 2, 3, 4], shape=[2, 2])
        assert out2["outputs"][0]["data"] == [2.0, 4.0, 6.0, 8.0]
        serving.delete("dbl")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            pods = serving.cluster.list(
                "pods",
                lambda p: p.metadata.labels.get(
                    "kubeflow-tpu.org/inferenceservice") == "dbl",
            )
            if not pods:
                return
            time.sleep(0.2)
        pytest.fail("predictor pods not torn down")

    def test_self_healing_replica(self, serving, platform):
        serving.create(custom_isvc("heal"))
        serving.wait_ready("heal", timeout_s=60)
        assert platform.pod_runtime.inject_kill("default/heal-predictor-0")
        # must dip (pod replaced) and come back ready
        deadline = time.monotonic() + 60
        healed = False
        while time.monotonic() < deadline:
            isvc = serving.get("heal")
            if (
                platform.isvc_controller.metrics["predictor_pods_restarted_total"] > 0
                and isvc.status.ready
            ):
                healed = True
                break
            time.sleep(0.2)
        assert healed
        out = serving.predict("heal", [[2.0]])
        assert out["predictions"] == [[4.0]]

    def test_multi_replica_round_robin(self, serving):
        serving.create(custom_isvc("multi", replicas=2))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            isvc = serving.get("multi")
            if isvc.status.replicas_ready == 2:
                break
            time.sleep(0.2)
        assert isvc.status.replicas_ready == 2
        # both endpoints answer
        for _ in range(4):
            assert serving.predict("multi", [[1.0]])["predictions"] == [[2.0]]

    def test_transformer_chain(self, serving):
        serving.create(
            custom_isvc(
                "chained",
                transformer=TransformerSpec(
                    model_class="serving_fixtures:PlusOneTransformer"
                ),
            )
        )
        serving.wait_ready("chained", timeout_s=60)
        # output = -((x + 1) * 2)
        out = serving.predict("chained", [[1.0, 4.0]])
        assert out["predictions"] == [[-4.0, -10.0]]

    def test_jax_predictor_e2e(self, serving, tmp_path):
        import jax

        from kubeflow_tpu.models import MnistMLP

        model = MnistMLP(hidden=(16,), num_classes=10)
        example = np.zeros((1, 64), np.float32)
        variables = model.init(jax.random.PRNGKey(0), example)
        save_predictor(
            tmp_path / "mnist-model", "mnist-mlp", dict(variables), example,
            hidden=[16], num_classes=10,
        )
        isvc = InferenceService(
            metadata=ObjectMeta(name="mnist"),
            spec=InferenceServiceSpec(
                predictor=PredictorSpec(
                    runtime=PredictorRuntime.JAX,
                    storage_uri=f"file://{tmp_path / 'mnist-model'}",
                    # pin CPU: the logits are compared with a local CPU
                    # forward pass below
                    device="cpu",
                )
            ),
        )
        serving.create(isvc)
        serving.wait_ready("mnist", timeout_s=90)  # includes jax import+jit
        x = np.random.default_rng(1).normal(size=(2, 64)).astype(np.float32)
        out = serving.predict("mnist", x.tolist())
        assert len(out["predictions"]) == 2
        assert all(0 <= c <= 9 for c in out["predictions"])
        # logits must match a local forward pass bit-for-bit-ish
        expected = np.asarray(model.apply(variables, x), np.float32)
        np.testing.assert_allclose(
            np.asarray(out["logits"], np.float32), expected, rtol=1e-4
        )


class TestMultiTensorV2:
    """Multi-input requests and generic named multi-output responses over
    the v2 HTTP surface (the contract multi-tensor runtimes like triton
    serve through)."""

    @pytest.fixture()
    def mt_server(self):
        from tests.serving_fixtures import AffinePairModel, TwoOutModel

        s = ModelServer(
            [AffinePairModel("pair"), TwoOutModel("twoout")], port=0
        )
        s.start()
        yield s
        s.stop()

    def test_v2_multi_input_routed_by_name(self, mt_server):
        code, body = _post(
            f"{mt_server.url}/v2/models/pair/infer",
            {"inputs": [
                {"name": "a", "shape": [1, 2], "datatype": "FP32",
                 "data": [1.0, 2.0]},
                {"name": "b", "shape": [1, 2], "datatype": "FP32",
                 "data": [10.0, 20.0]},
            ]},
        )
        assert code == 200
        assert body["outputs"][0]["data"] == [12.0, 24.0]

    def test_v2_single_input_against_multi_model_is_500_not_crash(
            self, mt_server):
        code, body = _post(
            f"{mt_server.url}/v2/models/pair/infer",
            {"inputs": [{"name": "a", "shape": [1], "datatype": "FP32",
                         "data": [1.0]}]},
        )
        assert code == 500 and "dict" in body["error"]

    def test_v2_multi_output_one_tensor_per_name(self, mt_server):
        code, body = _post(
            f"{mt_server.url}/v2/models/twoout/infer",
            {"inputs": [{"name": "x", "shape": [2], "datatype": "FP32",
                         "data": [1.0, 2.0]}]},
        )
        assert code == 200
        by_name = {o["name"]: o["data"] for o in body["outputs"]}
        assert by_name == {"doubled": [2.0, 4.0], "plus1": [2.0, 3.0]}

    def test_v1_predict_multi_output_dict_serializes(self, mt_server):
        code, body = _post(
            f"{mt_server.url}/v1/models/twoout:predict",
            {"instances": [1.0, 2.0]},
        )
        assert code == 200
        assert body["predictions"] == {"doubled": [2.0, 4.0],
                                       "plus1": [2.0, 3.0]}

    def test_v2_output_named_predictions_keeps_siblings(self, mt_server):
        from kubeflow_tpu.serving.server import ModelServer
        import numpy as np

        arrays = ModelServer.postprocess_arrays(
            {"predictions": np.array([1.0]), "scores": np.array([0.5])}
        )
        assert [k for k, _ in arrays] == ["predictions", "scores"]


class TestRetryAfterHonored:
    """serving client x activator contract: a 503 carrying Retry-After means
    'the SERVER knows when capacity returns' — the client must sleep that
    advertised interval and re-dial, not apply its own backoff schedule."""

    class _Flaky:
        """Tiny HTTP server: N 503+Retry-After responses, then 200."""

        def __init__(self, fail_times: int, retry_after: str):
            import threading
            from http.server import BaseHTTPRequestHandler, HTTPServer

            state = {"left": fail_times, "times": []}
            self.state = state

            class H(BaseHTTPRequestHandler):
                def log_message(self, *a):
                    pass

                def do_POST(self):
                    self.rfile.read(
                        int(self.headers.get("Content-Length", 0)))
                    state["times"].append(time.monotonic())
                    if state["left"] > 0:
                        state["left"] -= 1
                        body = b'{"error": "cold start"}'
                        self.send_response(503)
                        self.send_header("Retry-After", retry_after)
                    else:
                        body = json.dumps({"predictions": [[2.0]]}).encode()
                        self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

            self.httpd = HTTPServer(("127.0.0.1", 0), H)
            self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
            import threading as _t

            _t.Thread(target=self.httpd.serve_forever, daemon=True).start()

        def stop(self):
            self.httpd.shutdown()
            self.httpd.server_close()

    def _client(self):
        # _post needs no platform state — a bare instance suffices
        return ServingClient.__new__(ServingClient)

    def test_sleeps_advertised_interval_then_redials(self):
        srv = self._Flaky(fail_times=1, retry_after="0.4")
        try:
            out = self._client()._post(srv.url, {"instances": [[1.0]]}, 5.0)
        finally:
            srv.stop()
        assert out == {"predictions": [[2.0]]}
        t = srv.state["times"]
        assert len(t) == 2
        # the gap between dials is the server's hint, not a client schedule
        assert 0.4 <= t[1] - t[0] < 2.0, t[1] - t[0]

    def test_gives_up_after_retry_budget(self):
        srv = self._Flaky(fail_times=10, retry_after="0.05")
        try:
            with pytest.raises(RuntimeError, match="HTTP 503"):
                self._client()._post(srv.url, {"instances": [[1.0]]}, 5.0)
        finally:
            srv.stop()
        # initial dial + RETRY_AFTER_MAX_RETRIES redials, then surface
        assert len(srv.state["times"]) == ServingClient.RETRY_AFTER_MAX_RETRIES + 1

    def test_hint_exceeding_caller_budget_is_not_honored(self):
        """timeout_s bounds the WHOLE call: a hint that would sleep past
        the caller's deadline surfaces the 503 instead of parking."""
        srv = self._Flaky(fail_times=10, retry_after="5")
        try:
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="HTTP 503"):
                self._client()._post(srv.url, {"instances": [[1.0]]}, 0.5)
            assert time.monotonic() - t0 < 2.0
            assert len(srv.state["times"]) == 1  # no redial past budget
        finally:
            srv.stop()

    def test_503_without_hint_raises_immediately(self):
        srv = self._Flaky(fail_times=10, retry_after="")
        # empty Retry-After parses as no hint -> no sleep, immediate raise
        try:
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="HTTP 503"):
                self._client()._post(srv.url, {"instances": [[1.0]]}, 5.0)
            assert time.monotonic() - t0 < 1.0
            assert len(srv.state["times"]) == 1
        finally:
            srv.stop()
