"""Benchmark pipeline tests: local subprocess runner + cluster runner against
the fake API server + reporter output."""

import csv
import json
import os
import threading

import pytest

from kubeflow_tpu.bench import (
    BenchmarkResult,
    BenchmarkSpec,
    ClusterRunner,
    LocalRunner,
    report,
)
from kubeflow_tpu.k8s import FakeKubeClient
from kubeflow_tpu.manifests.components.tpujob_operator import (
    API_VERSION,
    TPUJOB_KIND,
)
from kubeflow_tpu.operators.tpujob import JOB_LABEL, TpuJobOperator

# subprocess workloads run on CPU in tests, on two virtual devices
CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


def test_local_runner_mnist_end_to_end():
    spec = BenchmarkSpec(
        name="mnist-smoke",
        workload="mnist",
        args=["--steps", "20", "--batch-size", "64", "--log-every", "5"],
        timeout_s=600,
    )
    result = LocalRunner(CPU_ENV).run(spec)
    assert result.status == "Succeeded", result
    assert result.metrics, "workload must emit JSON metric lines"
    assert "accuracy" in result.final_metrics
    assert result.final_metrics["step"] == 20


def test_local_runner_failure_status():
    spec = BenchmarkSpec(name="bad", workload="kubeflow_tpu.examples.mnist",
                         args=["--no-such-flag"], timeout_s=120)
    result = LocalRunner(CPU_ENV).run(spec)
    assert result.status == "Failed"


def test_reporter_writes_csv_and_json(tmp_path):
    result = BenchmarkResult(
        name="r", status="Succeeded", wall_time_s=1.5,
        metrics=[{"step": 1, "loss": 2.0}, {"step": 2, "loss": 1.0,
                                            "images_per_sec": 500.0}],
    )
    paths = report(result, str(tmp_path))
    summary = json.load(open(paths["json"]))
    assert summary["status"] == "Succeeded"
    assert summary["final_metrics"]["loss"] == 1.0
    rows = list(csv.DictReader(open(paths["csv"])))
    assert len(rows) == 2
    assert rows[1]["images_per_sec"] == "500.0"


def test_cluster_runner_monitors_job(tmp_path):
    client = FakeKubeClient()
    operator = TpuJobOperator(client)
    ctrl = operator.build_controller()
    ctrl.start(workers=2)

    # kubelet sim: run pods to completion as they appear
    stop = threading.Event()

    def kubelet():
        while not stop.is_set():
            for pod in client.list("v1", "Pod", "default"):
                if pod.get("status", {}).get("phase") not in ("Succeeded",):
                    pod.setdefault("status", {})["phase"] = "Succeeded"
                    client.update_status(pod)
            stop.wait(0.1)

    t = threading.Thread(target=kubelet, daemon=True)
    t.start()
    try:
        results_dir = str(tmp_path)
        with open(os.path.join(results_dir, "bench1.jsonl"), "w") as f:
            f.write('{"step": 10, "images_per_sec": 1234.5}\n')
        runner = ClusterRunner(client, results_dir=results_dir,
                               poll_interval_s=0.1)
        spec = BenchmarkSpec(name="bench1", workload="resnet", timeout_s=30)
        result = runner.run(spec)
        assert result.status == "Succeeded"
        assert result.final_metrics["images_per_sec"] == 1234.5
        job = client.get(API_VERSION, TPUJOB_KIND, "default", "bench1")
        assert job["status"]["phase"] == "Succeeded"
    finally:
        stop.set()
        ctrl.stop()


def test_cluster_runner_timeout_with_fake_clock():
    """The monitor loop runs off injectable clock/sleep (tpulint TPU003
    fix): a job that never completes times out without real waiting."""
    client = FakeKubeClient()
    now = {"t": 0.0}

    def clock():
        return now["t"]

    def sleep(s):
        now["t"] += s

    runner = ClusterRunner(client, poll_interval_s=5.0,
                           clock=clock, sleep=sleep)
    spec = BenchmarkSpec(name="stuck", workload="resnet", timeout_s=60)
    result = runner.run(spec)  # nobody reconciles: phase never set
    assert result.status == "Timeout"
    # the loop advanced fake time past the deadline via injected sleep
    assert now["t"] >= 60
    assert result.wall_time_s >= 60


def test_cluster_runner_collects_workload_results(tmp_path, monkeypatch):
    """log_metrics appends to KFTPU_RESULTS_DIR/<job>.jsonl (contract check)."""
    monkeypatch.setenv("KFTPU_RESULTS_DIR", str(tmp_path))
    monkeypatch.setenv("KFTPU_JOB_NAME", "myjob")
    from kubeflow_tpu.examples.common import log_metrics

    log_metrics(1, loss=2.5)
    log_metrics(2, loss=1.5)
    lines = open(tmp_path / "myjob.jsonl").read().strip().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[-1])["loss"] == 1.5
