"""Custom predictor/transformer classes for serving tests, and the
zero-drop watch the seeded kill drills share.

Lives in an importable module (not the test file) because the custom-runtime
contract loads 'module:Class' inside the server subprocess.
"""

import numpy as np

from kubeflow_tpu.serving.model import Model


class DoubleModel(Model):
    """Predicts 2*x — trivially verifiable through the whole HTTP stack."""

    def load(self):
        self.ready = True

    def predict(self, inputs):
        return np.asarray(inputs) * 2.0


class PlusOneTransformer(Model):
    """preprocess adds 1, postprocess flips sign: output = -((x+1)*2)."""

    def load(self):
        self.ready = True

    def preprocess(self, inputs):
        return np.asarray(inputs) + 1.0

    def postprocess(self, outputs):
        return (-np.asarray(outputs)).tolist()


class TripleModel(Model):
    """Predicts 3*x — distinguishable from DoubleModel for canary tests."""

    def load(self):
        self.ready = True

    def predict(self, inputs):
        return np.asarray(inputs) * 3.0


class SignExplainer(Model):
    """Black-box explainer: attributes each feature its sign after the
    predictor chain (exercises the predict_fn handle)."""

    def load(self):
        self.ready = True

    def explain(self, inputs):
        preds = np.asarray(self.predict_fn(np.asarray(inputs)))
        return {"explanations": np.sign(preds).tolist(),
                "predictions": preds.tolist()}


class AffinePairModel(Model):
    """Two named inputs a,b -> a*2 + b — exercises the multi-input v2 path
    (HTTP and gRPC route >1 input tensors as a name->array dict)."""

    def load(self):
        self.ready = True

    def predict(self, inputs):
        if not isinstance(inputs, dict):
            raise ValueError("model declares 2 inputs; pass a dict (a, b)")
        return np.asarray(inputs["a"]) * 2.0 + np.asarray(inputs["b"])


class TwoOutModel(Model):
    """Generic named multi-output dict (no 'predictions' key) — exercises
    postprocess_arrays emitting one v2 output tensor per name."""

    def load(self):
        self.ready = True

    def predict(self, inputs):
        x = np.asarray(inputs)
        return {"doubled": x * 2.0, "plus1": x + 1.0}


class ZeroDropWatch:
    """The monitoring plane's half of a seeded kill drill: `on_tick`
    (the load harness's hook) samples the fleet's failure counter into
    a TSDB every tick; `verdict()` evaluates the zero-drop objective
    over it and returns its state plus the names of the alerts fired."""

    METRIC = "fleet.requests_failed_total"

    def __init__(self):
        from kubeflow_tpu.monitoring import TimeSeriesStore

        self.tsdb = TimeSeriesStore()

    def on_tick(self, _tick, router):
        self.tsdb.record(self.METRIC, router.metrics["requests_failed_total"])

    def verdict(self) -> dict:
        from kubeflow_tpu.monitoring import SLOConfig, SLOMonitor

        monitor = SLOMonitor(self.tsdb, (SLOConfig(
            "serving_zero_drop", metric=self.METRIC, kind="increase",
            budget=0.0, windows=((3600.0, 1.0),)),))
        alerts = [a.slo for a in monitor.evaluate()]
        (state,) = monitor.describe()
        return {**state, "alerts": alerts}
