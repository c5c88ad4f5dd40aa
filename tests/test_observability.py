"""P8: observability tests — /metrics endpoint, profiler toggle."""

import sys
import textwrap
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from kubeflow_tpu.api import (
    ContainerSpec,
    JAXJob,
    JAXJobSpec,
    ObjectMeta,
    PodTemplateSpec,
    ReplicaSpec,
    REPLICA_WORKER,
)
from kubeflow_tpu.client import Platform, TrainingClient
from kubeflow_tpu.controller.envcontract import synthesize_env


@pytest.fixture()
def platform(tmp_path):
    p = Platform(log_dir=str(tmp_path / "pod-logs"))
    with p:
        yield p


class TestMetricsEndpoint:
    def test_scrape_after_job(self, platform, tmp_path):
        url = platform.start_metrics_server()
        client = TrainingClient(platform)
        script = tmp_path / "ok.py"
        script.write_text("print('done')")
        client.create_job(
            JAXJob(
                metadata=ObjectMeta(name="obsjob"),
                spec=JAXJobSpec(
                    replica_specs={
                        REPLICA_WORKER: ReplicaSpec(
                            replicas=1,
                            template=PodTemplateSpec(
                                container=ContainerSpec(
                                    command=[sys.executable, str(script)]
                                )
                            ),
                        )
                    }
                ),
            )
        )
        client.wait_for_job_conditions("obsjob", timeout_s=30)
        with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "kftpu_job_jobs_succeeded_total 1" in body
        assert "kftpu_job_reconcile_total" in body
        assert 'kftpu_objects{kind="jobs"} 1' in body
        assert "kftpu_experiment_workqueue_depth" in body
        assert "kftpu_isvc_workqueue_depth" in body
        with urllib.request.urlopen(f"{url}/healthz", timeout=5) as r:
            assert r.read() == b"ok\n"


class TestGoldenExposition:
    """Golden-style pin of the FULL rendered exposition text for a fresh
    (unstarted) platform with tracing armed — every metric name, TYPE/HELP
    line, label, and ordering. A metric rename or removal (including the
    kftpu_trace_* series) fails here loudly instead of silently breaking
    scrapes and dashboards. Regenerate after an INTENTIONAL change with:

        KFTPU_UPDATE_GOLDEN=1 pytest tests/test_observability.py -k golden
    """

    GOLDEN = Path(__file__).resolve().parent / "golden" / \
        "metrics_exposition.txt"

    def test_full_exposition_matches_golden(self, tmp_path):
        import os

        from kubeflow_tpu.health import reset_ckpt_verify_metrics
        from kubeflow_tpu.observability import render_metrics
        from kubeflow_tpu.train.data import reset_loader_metrics
        from kubeflow_tpu.utils.compile_cache import reset_compile_metrics

        # kftpu_ckpt_verify_* / kftpu_train_* are process-global (the
        # reporters are constructed wherever trainers run); zero them so
        # this pins the same fresh-process surface regardless of which
        # tests ran first
        from kubeflow_tpu.analysis.protocheck import reset_protocheck_metrics
        from kubeflow_tpu.serving.fleet.podclient import reset_pod_metrics

        reset_ckpt_verify_metrics()
        reset_loader_metrics()
        reset_compile_metrics()
        reset_pod_metrics()
        reset_protocheck_metrics()
        p = Platform(log_dir=str(tmp_path / "logs"))
        p.start_tracing(capacity=4096)
        text = render_metrics(p)
        # the new series really are in the pinned surface
        for needle in (
            "kftpu_trace_spans_started_total",
            "kftpu_trace_spans_finished_total",
            "kftpu_trace_spans_dropped_total",
            "kftpu_trace_recorder_spans",
            "kftpu_trace_recorder_capacity 4096",
            "kftpu_health_leases_expired_total",
            "kftpu_health_stragglers_declared_total",
            "kftpu_ckpt_verify_steps_quarantined_total",
            "kftpu_ckpt_verify_fallback_restores_total",
            "kftpu_pod_spawns_total",
            "kftpu_pod_wire_retries_total",
            "kftpu_pod_handoff_bytes_total",
            "kftpu_pod_heartbeat_age_seconds",
            "kftpu_protocheck_models_checked_total",
            "kftpu_protocheck_states_explored_total",
            "kftpu_protocheck_violations_total",
            "kftpu_sched_grants_total",
            "kftpu_sched_denies_total",
            "kftpu_sched_preemptions_total",
            "kftpu_sched_quota_borrows_total",
            "kftpu_sched_free_chips",
            "kftpu_sched_tenant_share",
            "kftpu_sched_preempt_to_resume_seconds_bucket",
        ):
            assert needle in text, needle
        if os.environ.get("KFTPU_UPDATE_GOLDEN"):
            self.GOLDEN.write_text(text)
        golden = self.GOLDEN.read_text()
        assert text == golden, (
            "rendered /metrics exposition diverged from the golden file — "
            "if the change is intentional, regenerate with "
            "KFTPU_UPDATE_GOLDEN=1 (see class docstring)"
        )


class TestProfilerToggle:
    def test_env_contract_carries_profile_dir(self, tmp_path):
        job = JAXJob(
            metadata=ObjectMeta(name="profjob"),
            spec=JAXJobSpec(
                replica_specs={REPLICA_WORKER: ReplicaSpec(replicas=2)},
                profile_dir=str(tmp_path / "traces"),
            ),
        )
        env = synthesize_env(job, REPLICA_WORKER, 1)
        assert env["KFTPU_PROFILE_DIR"] == str(tmp_path / "traces") + "/process-1"
        # absent when not requested
        job.spec.profile_dir = ""
        assert "KFTPU_PROFILE_DIR" not in synthesize_env(job, REPLICA_WORKER, 0)

    def test_trainer_writes_trace(self, tmp_path):
        from kubeflow_tpu.models import MnistMLP
        from kubeflow_tpu.train import Trainer, TrainerConfig
        from kubeflow_tpu.train.data import synthetic_image_dataset

        ds = synthetic_image_dataset(n_train=64, n_test=32, shape=(8, 8, 1))
        trainer = Trainer(
            MnistMLP(hidden=(8,)),
            TrainerConfig(
                batch_size=32, steps=2, log_every_steps=1,
                profile_dir=str(tmp_path / "trace"),
            ),
        )
        trainer.fit(ds)
        # jax.profiler writes plugins/profile/<ts>/*.trace.json.gz (or .pb)
        produced = list((tmp_path / "trace").rglob("*"))
        assert any(p.is_file() for p in produced), "no trace files written"


class TestReconcileLatencyHistogram:
    def test_histogram_rendered_and_cumulative(self, tmp_path):
        from kubeflow_tpu.client import Platform, TrainingClient
        from kubeflow_tpu.observability import render_metrics

        with Platform(log_dir=str(tmp_path / "logs")) as p:
            import sys
            import time as _t

            from kubeflow_tpu.api import (
                ContainerSpec, JAXJob, JAXJobSpec, ObjectMeta,
                PodTemplateSpec, ReplicaSpec, REPLICA_WORKER,
            )

            script = tmp_path / "ok.py"
            script.write_text("print('ok')")
            TrainingClient(p).create_job(JAXJob(
                metadata=ObjectMeta(name="histo"),
                spec=JAXJobSpec(replica_specs={
                    REPLICA_WORKER: ReplicaSpec(
                        replicas=1,
                        template=PodTemplateSpec(container=ContainerSpec(
                            command=[sys.executable, str(script)]))),
                }),
            ))
            deadline = _t.monotonic() + 30
            while _t.monotonic() < deadline:
                j = p.cluster.get("jobs", "default/histo")
                if j is not None and j.status.is_finished:
                    break
                _t.sleep(0.1)
            text = render_metrics(p)
        assert "# TYPE kftpu_job_reconcile_duration_seconds histogram" in text
        import re

        buckets = re.findall(
            r'kftpu_job_reconcile_duration_seconds_bucket\{le="([^"]+)"\} '
            r"(\d+)", text)
        assert buckets and buckets[-1][0] == "+Inf"
        counts = [int(n) for _, n in buckets]
        assert counts == sorted(counts)          # cumulative
        assert counts[-1] > 0                    # reconciles observed
        m = re.search(
            r"kftpu_job_reconcile_duration_seconds_count (\d+)", text)
        assert int(m.group(1)) == counts[-1]
