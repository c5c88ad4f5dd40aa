"""RemoteClient — SDK over the platform REST API.

Reference parity: the training-operator/katib/kserve SDKs are all k8s API
clients over HTTPS (SURVEY.md §2.1 'Python SDK'); this is the same shape
against the PlatformServer, so a process that did NOT start the platform
can apply manifests, watch verdicts, read logs, and scale jobs.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.parse
import urllib.request

import yaml


class ApiError(RuntimeError):
    def __init__(self, code: int, message: str):
        self.code = code
        super().__init__(f"HTTP {code}: {message}")


class RemoteClient:
    def __init__(self, server: str, timeout_s: float = 10.0):
        self.server = server.rstrip("/")
        self.timeout_s = timeout_s

    # -------------------------------------------------------------- plumbing

    def _request(self, method: str, path: str, body: dict | None = None):
        req = urllib.request.Request(
            f"{self.server}{path}",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"},
            method=method,
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
                raw = r.read()
                ctype = r.headers.get("Content-Type", "")
        except urllib.error.HTTPError as exc:
            detail = exc.read().decode(errors="replace")
            try:
                detail = json.loads(detail).get("error", detail)
            except json.JSONDecodeError:
                pass
            raise ApiError(exc.code, detail) from exc
        if ctype.startswith("application/json"):
            return json.loads(raw)
        return raw.decode()

    # ------------------------------------------------------------------ CRUD

    def apply(self, manifest: str | dict) -> dict:
        """kubectl-apply analogue: create from a YAML manifest (text) or dict.
        The kind in the manifest picks the API group."""
        data = yaml.safe_load(manifest) if isinstance(manifest, str) else manifest
        from kubeflow_tpu.api.serde import MANIFEST_KINDS

        bucket = MANIFEST_KINDS.get(data.get("kind", ""))
        if bucket is None:
            raise ValueError(f"unknown kind {data.get('kind')!r}")
        return self._request("POST", f"/api/v1/{bucket}", data)

    def list(self, kind: str, namespace: str = "",
             label_selector: str = "") -> list[dict]:
        """List objects; optional server-side filters (kubectl parity):
        namespace, and equality selectors k=v | k==v | k!=v comma-ANDed."""
        params = {}
        if namespace:
            params["namespace"] = namespace
        if label_selector:
            params["labelSelector"] = label_selector
        qs = f"?{urllib.parse.urlencode(params)}" if params else ""
        return self._request("GET", f"/api/v1/{kind}{qs}")

    def get(self, kind: str, name: str, namespace: str = "default") -> dict:
        return self._request("GET", f"/api/v1/{kind}/{namespace}/{name}")

    def follow_job_logs(self, name: str, namespace: str = "default",
                        replica_type: str = "worker", index: int = 0,
                        timeout_s: float = 3600.0):
        """kubectl `logs -f` analogue: yields decoded chunks as the
        replica writes them, ending when the pod finishes."""
        qs = urllib.parse.urlencode({
            "replicaType": replica_type, "index": index,
            "follow": "true", "timeoutSeconds": timeout_s,
        })
        import codecs

        req = urllib.request.Request(
            f"{self.server}/api/v1/jobs/{namespace}/{name}/logs?{qs}")
        # incremental decoding: a multi-byte UTF-8 char split across
        # chunk boundaries must not decode to U+FFFD pairs
        dec = codecs.getincrementaldecoder("utf-8")(errors="replace")
        with urllib.request.urlopen(req, timeout=timeout_s + 5) as r:
            while True:
                chunk = r.read1(65536)
                if not chunk:
                    tail = dec.decode(b"", final=True)
                    if tail:
                        yield tail
                    return
                text = dec.decode(chunk)
                if text:
                    yield text

    def delete(self, kind: str, name: str, namespace: str = "default") -> dict:
        return self._request("DELETE", f"/api/v1/{kind}/{namespace}/{name}")

    def events(self, name: str, namespace: str = "default") -> list[dict]:
        return self._request("GET", f"/api/v1/events/{namespace}/{name}")

    # ------------------------------------------------------------------ jobs

    def job_logs(self, name: str, namespace: str = "default",
                 replica_type: str = "worker", index: int = 0) -> str:
        q = urllib.parse.urlencode({"replicaType": replica_type, "index": index})
        return self._request("GET", f"/api/v1/jobs/{namespace}/{name}/logs?{q}")

    def scale_job(self, name: str, replicas: int, namespace: str = "default") -> dict:
        return self._request(
            "POST", f"/api/v1/jobs/{namespace}/{name}/scale", {"replicas": replicas}
        )

    # ------------------------------------------------------------------ watch

    def watch(self, kind: str, namespace: str = "", name: str = "",
              timeout_s: float = 60.0, keepalive_s: float = 10.0,
              label_selector: str = ""):
        """NDJSON watch stream: yields {"type": ..., "object": ...} events
        (list+watch: current objects arrive first as ADDED). Terminates when
        the server-side timeout elapses.

        Deadness detection: the server guarantees at least one line per
        keepalive_s (KEEPALIVE lines, filtered out here). The socket read
        timeout is set to ~2x that budget, so a stream with NO bytes past it
        — a dropped connection, previously indistinguishable from a quiet
        one — raises TimeoutError/OSError: callers (see _wait_terminal)
        treat it as dead, close, and relist."""
        q = urllib.parse.urlencode({
            "watch": "true", "timeoutSeconds": f"{timeout_s:.0f}",
            "keepaliveSeconds": f"{keepalive_s:g}",
            **({"namespace": namespace} if namespace else {}),
            **({"name": name} if name else {}),
            # "k=v,k2" — filtered SERVER-side (the apiserver pushes it
            # into the watch hub), not client-side after transfer
            **({"labelSelector": label_selector} if label_selector else {}),
        })
        req = urllib.request.Request(f"{self.server}/api/v1/{kind}?{q}")
        quiet_budget = max(2.0 * keepalive_s + 2.0, 5.0)
        with urllib.request.urlopen(req, timeout=quiet_budget) as resp:
            for line in resp:
                if not line.strip():
                    continue
                ev = json.loads(line)
                if ev.get("type") == "KEEPALIVE":
                    continue  # liveness only — never an API event
                yield ev

    def wait_for_job(self, name: str, namespace: str = "default",
                     timeout_s: float = 600.0, poll_s: float = 0.5) -> dict:
        """Watch until the job reaches a terminal condition (falls back to
        polling if the stream drops — e.g. a server without watch support)."""

        def terminal(job: dict) -> bool:
            conds = {
                c["type"] for c in job.get("status", {}).get("conditions", [])
                if c.get("status", True)
            }
            return bool(conds & {"Succeeded", "Failed"})

        return self._wait_terminal(
            "jobs", name, namespace, timeout_s, poll_s, terminal
        )

    def train(
        self,
        name: str,
        *,
        family: str = "mnist",
        num_workers: int = 1,
        namespace: str = "default",
        device: str = "auto",
        args: list[str] | None = None,
        elastic: tuple | None = None,
        wait: bool = True,
        timeout_s: float = 3600.0,
    ) -> dict[str, float]:
        """Remote twin of TrainingClient.train(): build the examples.<family>
        JAXJob client-side, POST it over REST, ride the watch stream to a
        terminal condition, and parse final_* metrics from worker-0's log.
        The command uses the SYMBOLIC interpreter "python" and no working
        dir — the server's pod runtime resolves both server-side (this
        client's own paths may not exist there)."""
        from kubeflow_tpu.api.jobs import build_example_train_job
        from kubeflow_tpu.api.serde import job_to_dict

        job = build_example_train_job(
            name, family=family, num_workers=num_workers, namespace=namespace,
            device=device, args=args, elastic=elastic,
        )
        self.apply(job_to_dict(job))
        if not wait:
            return {}
        done = self.wait_for_job(name, namespace, timeout_s=timeout_s)
        conds = [
            c for c in done.get("status", {}).get("conditions", [])
            if c.get("status", True)
        ]
        if not any(c["type"] == "Succeeded" for c in conds):
            failed = next((c for c in conds if c["type"] == "Failed"), None)
            detail = (
                f": {failed.get('message')}" if failed and failed.get("message")
                else f": {sorted(c['type'] for c in conds)}"
            )
            raise RuntimeError(f"train job {name} failed{detail}")
        from kubeflow_tpu.sweep.collector import final_metrics_from_log

        return final_metrics_from_log(self.job_logs(name, namespace))

    # ------------------------------------------------------------- pipelines

    def submit_pipeline_run(
        self, name: str, pipeline_spec: dict, arguments: dict | None = None,
        namespace: str = "default", cache: bool = True,
    ) -> dict:
        """Submit compiled pipeline IR as a PipelineRun (KFP create_run
        analogue, SURVEY.md §2.6 API-server row)."""
        return self.apply({
            "apiVersion": "kubeflow-tpu.org/v1",
            "kind": "PipelineRun",
            "metadata": {"name": name, "namespace": namespace},
            "spec": {
                "pipelineSpec": pipeline_spec,
                "arguments": arguments or {},
                "cache": cache,
            },
        })

    def wait_for_pipeline_run(
        self, name: str, namespace: str = "default",
        timeout_s: float = 600.0, poll_s: float = 0.5,
    ) -> dict:
        return self._wait_terminal(
            "pipelineruns", name, namespace, timeout_s, poll_s,
            lambda o: o.get("status", {}).get("state") in ("Succeeded", "Failed"),
        )

    def _wait_terminal(self, kind: str, name: str, namespace: str,
                       timeout_s: float, poll_s: float, terminal) -> dict:
        """Watch until `terminal(obj)`; falls back to polling if the stream
        drops or the server lacks watch support."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                for ev in self.watch(
                    kind, namespace=namespace, name=name,
                    timeout_s=min(30.0, max(deadline - time.monotonic(), 1.0)),
                ):
                    if not isinstance(ev, dict) or "type" not in ev:
                        raise OSError("watch unsupported")
                    if ev["type"] == "DELETED":
                        raise KeyError(f"{kind} {namespace}/{name} deleted")
                    if terminal(ev["object"]):
                        return ev["object"]
            except (ApiError, OSError, json.JSONDecodeError):
                obj = self.get(kind, name, namespace)
                if terminal(obj):
                    return obj
                time.sleep(poll_s)
        raise TimeoutError(
            f"{kind} {namespace}/{name} not finished in {timeout_s}s"
        )

    def wait_for_experiment(
        self, name: str, namespace: str = "default",
        timeout_s: float = 600.0, poll_s: float = 0.5,
    ) -> dict:
        return self._wait_terminal(
            "experiments", name, namespace, timeout_s, poll_s,
            lambda o: o.get("status", {}).get("condition")
            in ("Succeeded", "Failed"),
        )

    def healthz(self) -> bool:
        try:
            return bool(self._request("GET", "/healthz").get("ok"))
        except (ApiError, OSError):
            return False
