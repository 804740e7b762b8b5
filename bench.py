"""Headline benchmark: ResNet-50 training throughput + MFU, plus the four
other BASELINE.md configs.

Mirrors the reference's kubebench + tf_cnn_benchmarks ResNet-50 headline
workload (BASELINE.md config 2; reference harness
``/root/reference/kubeflow/kubebench/kubebench-job.libsonnet:250-396``).
Prints ONE JSON line: the headline metric stays
``resnet50_train_images_per_sec_per_chip`` with ``vs_baseline`` against the
reference era's GPU path (tf_cnn_benchmarks ResNet-50 on one V100, fp32,
batch 64, ~2019 ≈ 360 images/sec — the north-star per-chip target), and the
``extras`` key carries MFU plus the MNIST-smoke, BERT step-time, allreduce,
and serving-latency configs (BASELINE.md configs 1, 3, 4, 5) so every
baseline config emits numbers each round — plus the three TPU-first configs
the reference has no counterpart for: ``longcontext`` (seq-8192 flash
training), ``decode`` (KV-cache generation), and ``decode_engine``
(continuous-batching serving throughput at effective batch 32).

Rows that run a tuned Pallas kernel (longcontext, bert, and the
decode_engine paged-kernel A/B) carry ``tile_config`` — the resolved
tile blocks plus their resolution source (``table|fallback|override``,
kubeflow_tpu/ops/autotune.py) — so an A/B across rounds can attribute
a throughput move to a tile-table change (PERF.md "Tile autotune").

This is a chip artifact: it exits non-zero — after printing the line —
when any config raised or timed out, or when any row came from a
backend other than ``tpu``. CPU runs of the configs are tests
(tests/test_bench_suite.py), never rows here.
"""

from __future__ import annotations

import json
import sys

REFERENCE_GPU_IMAGES_PER_SEC = 360.0


def main() -> None:
    import argparse

    from kubeflow_tpu.bench.suite import run_all_isolated

    p = argparse.ArgumentParser()
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture XLA profiler traces into DIR")
    args = p.parse_args()

    # one process per chip: this parent never initializes a jax backend;
    # each config runs in its own child, one after another, under a
    # plain timeout
    results = run_all_isolated(profile_dir=args.profile)
    headline = results.get("resnet50", {})
    value = float(headline.get("images_per_sec_per_chip", 0.0))
    errored = sorted(n for n, r in results.items() if "error" in r)
    off_chip = sorted(n for n, r in results.items()
                      if "error" not in r and r.get("platform") != "tpu")
    device_kinds = sorted({r["device_kind"] for r in results.values()
                           if r.get("device_kind")})
    line = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(value / REFERENCE_GPU_IMAGES_PER_SEC, 3),
        "device_kind": device_kinds[0] if len(device_kinds) == 1
        else device_kinds,
        "errored": errored,
        "not_on_tpu": off_chip,
    }
    if "mfu" in headline:
        line["mfu"] = headline["mfu"]
        line["tflops_per_chip"] = headline["tflops_per_chip"]
    if "step_telemetry" in headline:
        # step-regularity evidence (p50/p99 step time, recompile count,
        # MFU from the instrumented pass) rides with the artifact so the
        # perf trajectory shows tails and recompiles, not just means
        # (kubeflow_tpu/obs/steps.py, docs/OBSERVABILITY.md)
        line["step_telemetry"] = headline["step_telemetry"]
    if "goodput" in headline:
        # productive-fraction next to img/s (the goodput ledger's bench
        # twin, docs/OBSERVABILITY.md "Goodput"): wall time the pass
        # spent stepping vs recompiling vs unattributed host gaps
        line["goodput"] = headline["goodput"]
    line["extras"] = results
    print(json.dumps(line))
    if errored or off_chip:
        # the artifact above records what happened; the exit code
        # records that this is not a clean chip round
        sys.exit(1)


if __name__ == "__main__":
    main()
