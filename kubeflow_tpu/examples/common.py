"""Launcher scaffolding for in-cluster training workloads.

The reference's workloads bootstrap through ``launcher.py``: parse TF_CONFIG
into PS flags, exec the benchmark, emit JSON-ish logs
(``/root/reference/tf-controller-examples/tf-cnn/launcher.py:61-93``). Here
the scaffolding is: parse the operator's env contract, bring up
``jax.distributed``, build the mesh, and log structured JSON lines the
metrics collector can scrape.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time
from typing import Any, Dict, Optional

import jax

from kubeflow_tpu.parallel import MeshConfig, ProcessEnv, create_mesh
from kubeflow_tpu.parallel import distributed as dist


def setup_logging() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(levelname)s|%(asctime)s|%(pathname)s|%(lineno)d| %(message)s",
        datefmt="%Y-%m-%dT%H:%M:%S",
        stream=sys.stderr,
    )


def log_metrics(step: int, **metrics: Any) -> None:
    """One JSON line per step on stdout — the scrape contract for the
    benchmark reporter and the tuning metrics collector. When the operator
    injects ``KFTPU_RESULTS_DIR`` (the kubebench experiment-PVC equivalent),
    the same line is appended to ``<dir>/<job-name>.jsonl`` for the
    ClusterRunner's collect step."""
    rec: Dict[str, Any] = {"step": step, "ts": round(time.time(), 3)}
    for k, v in metrics.items():
        rec[k] = float(v) if hasattr(v, "__float__") else v
    line = json.dumps(rec)
    print(line, flush=True)
    results_dir = os.environ.get("KFTPU_RESULTS_DIR")
    if results_dir:
        job = os.environ.get("KFTPU_JOB_NAME", "job")
        try:
            os.makedirs(results_dir, exist_ok=True)
            with open(os.path.join(results_dir, f"{job}.jsonl"), "a") as f:
                f.write(line + "\n")
        except OSError:
            logging.exception("cannot write results to %s", results_dir)


def launcher_init(
    *, pp: int = 1, tp: Optional[int] = None
) -> tuple[ProcessEnv, "jax.sharding.Mesh"]:
    """Distributed bootstrap + mesh over all visible devices.

    Consumes the operator's full env contract: on a multi-slice job
    (``MEGASCALE_NUM_SLICES > 1``) the mesh gets a ``dcn`` outer-dp axis
    across slices; pp/tp always stay within one slice so their per-layer
    collectives never cross DCN."""
    setup_logging()
    penv = dist.initialize()
    from kubeflow_tpu.parallel.mesh import auto_mesh_config
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    if penv.is_multislice:
        per_slice = jax.device_count() // penv.num_slices
        slice_cfg = auto_mesh_config(per_slice, pp=pp, tp=tp)
        mesh = dist.multislice_mesh(penv, pp=slice_cfg.pp, tp=slice_cfg.tp)
        config = MeshConfig(dcn=penv.num_slices, dp=slice_cfg.dp,
                            pp=slice_cfg.pp, tp=slice_cfg.tp)
    else:
        config = auto_mesh_config(jax.device_count(), pp=pp, tp=tp)
        mesh = create_mesh(config)
    logging.info(
        "launcher up: rank %d/%d, %d %s devices (%s), mesh dcn=%d dp=%d "
        "pp=%d tp=%d, compile cache %s",
        penv.process_id, penv.num_processes, jax.device_count(),
        jax.default_backend(), jax.devices()[0].device_kind,
        config.dcn, config.dp, config.pp, config.tp, cache_dir,
    )
    return penv, mesh


def checkpoint_dir(default: str = "") -> str:
    return os.environ.get("KFTPU_CHECKPOINT_DIR", default)


def make_step_telemetry(*, tokens_per_step: int = 0,
                        examples_per_step: int = 0,
                        client=None, **kwargs):
    """A :class:`~kubeflow_tpu.obs.steps.StepTelemetry` wired from the
    operator's env contract: job/namespace/uid identity (so the step
    spans join the operator's trace), worker index, and — when running
    inside a TpuJob gang — a beacon sink publishing this host's health
    ConfigMap for the operator's straggler aggregation. Outside a gang
    (no ``KFTPU_JOB_NAME``) telemetry stays local: metrics + flight
    recorder, no cluster traffic."""
    from kubeflow_tpu.obs.steps import (
        ENV_JOB_UID,
        StepTelemetry,
        kube_beacon_sink,
    )

    penv = dist.from_env()
    job_uid = os.environ.get(ENV_JOB_UID, "")
    sink = None
    if penv.job_name and os.environ.get("KFTPU_BEACONS", "1") != "0":
        if client is None:
            try:
                from kubeflow_tpu.k8s.client import HttpKubeClient

                client = HttpKubeClient()
            except Exception:  # noqa: BLE001 — no cluster: local-only
                client = None
        if client is not None:
            # job_uid stamps the ownerReference: beacons GC with the CR
            sink = kube_beacon_sink(client, penv.namespace, penv.job_name,
                                    penv.process_id, job_uid=job_uid)
    kwargs.setdefault("beacon_every", 10)
    kwargs.setdefault("span_every", 10)
    kwargs.setdefault("n_chips", jax.device_count())
    if "hbm_sampler" not in kwargs:
        # live HBM watermarks on every beacon (docs/OBSERVABILITY.md
        # "Compile & memory"); CPU backends (memory_stats() is None)
        # degrade to no hbm block at zero cost
        from kubeflow_tpu.obs.xprof import HbmSampler

        kwargs["hbm_sampler"] = HbmSampler(
            namespace=penv.namespace, job=penv.job_name,
            worker=penv.process_id)
    return StepTelemetry(
        job=penv.job_name, namespace=penv.namespace,
        uid=job_uid, worker=penv.process_id,
        tokens_per_step=tokens_per_step,
        examples_per_step=examples_per_step,
        beacon_sink=sink, **kwargs)


def make_compile_ledger(*, install: bool = True):
    """A :class:`~kubeflow_tpu.obs.xprof.CompileLedger` wired from the
    operator's env contract (job/namespace/uid identity so compile
    spans join the job's trace tree) and, by default, subscribed to
    ``jax.monitoring`` — from here on every backend compile this
    worker pays becomes a ``kftpu_compile_seconds`` observation and a
    ground-truth ``startup_compile`` second in the goodput ledger.
    Call ``.uninstall()`` at shutdown (or use it as a context
    manager)."""
    from kubeflow_tpu.obs.steps import ENV_JOB_UID
    from kubeflow_tpu.obs.xprof import CompileLedger

    penv = dist.from_env()
    ledger = CompileLedger(
        namespace=penv.namespace, job=penv.job_name,
        uid=os.environ.get(ENV_JOB_UID, ""), worker=penv.process_id)
    if install:
        ledger.install()
    return ledger


def report_tuning_metrics(step: int, metrics: Dict[str, Any],
                          *, final: bool = False, client=None,
                          telemetry=None) -> None:
    """Publish trial metrics when running inside a study (no-op outside).

    The study controller injects ``KFTPU_TRIAL_NAME`` and
    ``KFTPU_OBJECTIVE_METRIC``; this appends the objective's step series
    (what median early stopping reads) and, on ``final``, the metrics the
    controller harvests on success. With ``telemetry`` (a
    :class:`~kubeflow_tpu.obs.steps.StepTelemetry`), the objective series
    comes from the telemetry's per-step records
    (:func:`kubeflow_tpu.tuning.study.append_history_from_telemetry`) —
    the same measurement stream the operator beacons and the flight
    recorder see — and the final report carries its p50/p99/recompile
    summary. Failures only log — a metrics hiccup must never kill a
    training step."""
    trial = os.environ.get("KFTPU_TRIAL_NAME")
    if not trial:
        return
    # exactly one reporter per gang: every worker shares the trial env,
    # and concurrent read-modify-writes of the one metrics ConfigMap
    # would drop or duplicate history points
    if dist.from_env().process_id != 0:
        return
    ns = os.environ.get("KFTPU_NAMESPACE", "default")
    objective = os.environ.get("KFTPU_OBJECTIVE_METRIC", "")
    try:
        from kubeflow_tpu.tuning.study import (
            append_history_points,
            append_trial_history,
            report_trial_metrics,
        )

        if client is None:
            from kubeflow_tpu.k8s.client import HttpKubeClient

            # one client for the trial's lifetime, not one per step
            client = getattr(report_tuning_metrics, "_client", None)
            if client is None:
                client = HttpKubeClient()
                report_tuning_metrics._client = client
        series = (telemetry.objective_series(objective)
                  if objective and telemetry is not None else [])
        if series:
            # the telemetry series IS the objective history; 0 appended
            # from a NON-EMPTY series means the points are already
            # persisted — never an ad-hoc append that would duplicate a
            # step. An empty series (metric unresolvable from step
            # records, e.g. "accuracy" under sync=False) falls through
            # to the explicit-value path below.
            append_history_points(client, ns, trial, series)
        elif objective and objective in metrics:
            append_trial_history(client, ns, trial, step,
                                 float(metrics[objective]))
        if final:
            harvest = {k: float(v) for k, v in metrics.items()
                       if hasattr(v, "__float__")}
            if telemetry is not None:
                harvest.update({k: float(v)
                                for k, v in telemetry.summary().items()
                                if isinstance(v, (int, float))})
            report_trial_metrics(client, ns, trial, harvest)
    except Exception:  # noqa: BLE001
        logging.exception("trial metrics report failed (continuing)")
