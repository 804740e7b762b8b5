#!/usr/bin/env bash
# Preflight gate: every check a PR must pass before review, one command.
#
#   scripts/preflight.sh            # tpulint + staged-blob check
#   scripts/preflight.sh --ref HEAD~1   # blob check over a commit range
#
# Checks:
#   1. tpulint (scripts/run_tpulint.py): rules TPU001-TPU018 over
#      kubeflow_tpu/ — the AST rules, the SPMD shardlint plane
#      (TPU006-TPU009), the lock-discipline dataflow plane
#      (TPU010-TPU012), TPU013 metric-contract, and the trace-taint
#      compile plane: TPU014 traced-control-flow, TPU015
#      recompile-hazard, TPU016 use-after-donate, TPU017
#      host-sync-in-hot-path, TPU018 unledgered-compile — gated on
#      tpulint_baseline.json (docs/ANALYSIS.md). Writes the SARIF
#      artifact to traces/tpulint.sarif on every run; --budget-check
#      ASSERTS the full 18-rule wall stays within +25% of the
#      TPU001-TPU013 reference pass and emits the measured delta into
#      the SARIF run properties (budget_delta_pct)
#   1b. compile audit (optional): when a ledger artifact exists at
#      traces/compile_events.json (CompileLedger.events_payload()
#      dump), join it against the static jit-site inventory and fail
#      on recompile storms; silently skipped when absent
#   2. binary-blob guard (scripts/check_binary_blobs.py): no large
#      binaries staged for commit (traces and caches are made at run
#      time and git-ignored; the tracked tree stays small)
#   3. obs smoke test (tests/test_obs.py): traceparent round-trip, span
#      propagation proxy->server->engine, /api/traces, histograms
#      (docs/OBSERVABILITY.md)
#   4. training-telemetry smoke test (tests/test_step_telemetry.py):
#      step clock + MFU/recompile accounting, flight-recorder dumps,
#      beacons -> operator straggler status -> dashboard
#      /api/jobs/<ns>/<name>/telemetry (docs/OBSERVABILITY.md
#      training-plane section)
#   5. paged-engine smoke (scripts/paged_smoke.py): admit -> chunked
#      prefill -> decode -> retire on CPU, prefix pages shared by
#      refcount and every refcount back to zero, in TWO passes — the
#      gather (bit-parity oracle) path, then the Pallas paged-attention
#      kernel path (interpret mode) with a copy-on-write boundary-page
#      split asserted to copy exactly once (docs/SERVING.md)
#   6. scheduler-plane smoke (scripts/scheduler_smoke.py): fake 4-slice
#      inventory, two gangs admit under tenant quota, a high-priority
#      gang preempts the minimum-cost victim (checkpointed exactly
#      once, Preempted condition, head-of-queue requeue, resume with
#      the step clock intact) and every chip stays accounted for
#      (docs/SCHEDULER.md)
#   7. monitoring/alerts smoke (scripts/alerts_smoke.py): fake-clock
#      end-to-end — scrape two fake targets into the tsdb, inject a
#      5xx burst, assert the burn-rate SLO rule walks
#      Pending -> Firing -> Resolved with exactly one Event per
#      transition and the firing gauge back at 0
#      (docs/OBSERVABILITY.md, Monitoring section)
#   8. elastic-training smoke (scripts/elastic_smoke.py): a fake
#      4-slice gang trains to step 50, shrinks to 2 slices through
#      snapshot-reshard-resume (exactly one save, spans in order),
#      trains to 100, and the loss stream matches a never-resized
#      oracle after the resync step (docs/ELASTIC.md)
#   9. fleet-edge smoke (scripts/edge_smoke.py): fake 3-replica fleet —
#      prefix-affinity routing concentrates a warmed prefix (warm
#      replica hit-rate > cold), an overload burst at 2x capacity
#      sheds lowest-SLO-class-first with the shed/served split in ONE
#      trace, and kftpu_edge_shed_total{class} reads back through the
#      tsdb + /api/metrics/query (docs/EDGE.md)
#  10. goodput-ledger smoke (scripts/goodput_smoke.py): a fake 2-slice
#      elastic job queues, trains, gets preempted, resumes, and
#      shrinks; status.goodput shows queue_wait/preempted/resizing/
#      checkpoint_save/restore, fractions sum to 1.0, intervals tile
#      the wall clock, the counter reads back through the tsdb, and
#      job-badput-burn walks Pending -> Firing -> Resolved on an
#      injected checkpoint stall (docs/OBSERVABILITY.md "Goodput")
#  11. tile-table validate (scripts/tile_sweep.py --validate): strict
#      legality over every committed kubeflow_tpu/ops/tile_table.json
#      entry (divisibility, analytic VMEM estimate, dtype-lane
#      legality) plus a CPU-tier parity smoke running the three flash
#      kernels and the paged kernel with every committed tile config
#      against the default-tile oracle — a bad table edit fails here
#      before a bench round burns chip time
#  12. compile/HBM profile smoke (scripts/profile_smoke.py): a live
#      jax.jit compile lands in the CompileLedger via jax.monitoring
#      exactly once, timed_compile fingerprints the HLO + records the
#      memory_analysis budget, the CPU HbmSampler degrades silently,
#      and on a fake clock injected compile events become the goodput
#      ledger's ground truth (startup_compile == event-sourced seconds
#      exactly), kftpu_compile_seconds reads back through the tsdb +
#      /api/metrics/query, /api/jobs/<ns>/<name>/profile serves the
#      summary, and an injected HBM climb walks hbm-headroom
#      Pending -> Firing -> Resolved with one Event per transition
#      (docs/OBSERVABILITY.md "Compile & memory")
#  13. request-lifecycle smoke (scripts/request_smoke.py): a mixed
#      burst rides edge->engine on CPU with traceparents; every
#      record's phases tile [submit, end] exactly, each request is ONE
#      trace tree (edge + engine spans under the inbound trace id),
#      kftpu_request_ttft_ms reads back through the tsdb +
#      /api/metrics/query, the worst-TTFT exemplar resolves through
#      /api/traces/<id>, and ttft-slo-burn walks
#      Pending -> Firing -> Resolved on an injected breach storm with
#      one Event per transition (docs/OBSERVABILITY.md
#      "Request lifecycle")
set -euo pipefail
cd "$(dirname "$0")/.."

rc=0

echo "== preflight: tpulint =="
python scripts/run_tpulint.py --budget-check \
    --sarif-out traces/tpulint.sarif || rc=1

if [ -f traces/compile_events.json ]; then
    echo "== preflight: compile audit =="
    python scripts/run_tpulint.py \
        --compile-audit traces/compile_events.json || rc=1
else
    echo "== preflight: compile audit (skipped: no traces/compile_events.json) =="
fi

echo "== preflight: binary blobs =="
python scripts/check_binary_blobs.py "$@" || rc=1

echo "== preflight: obs smoke test =="
JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py -q -m 'not slow' \
    -p no:cacheprovider || rc=1

echo "== preflight: training-telemetry smoke test =="
JAX_PLATFORMS=cpu python -m pytest tests/test_step_telemetry.py -q \
    -m 'not slow' -p no:cacheprovider || rc=1

echo "== preflight: paged decode engine smoke =="
JAX_PLATFORMS=cpu python scripts/paged_smoke.py || rc=1

echo "== preflight: scheduler plane smoke =="
JAX_PLATFORMS=cpu python scripts/scheduler_smoke.py || rc=1

echo "== preflight: monitoring/alerts smoke =="
JAX_PLATFORMS=cpu python scripts/alerts_smoke.py || rc=1

echo "== preflight: elastic training smoke =="
JAX_PLATFORMS=cpu XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python scripts/elastic_smoke.py || rc=1

echo "== preflight: fleet serving edge smoke =="
JAX_PLATFORMS=cpu python scripts/edge_smoke.py || rc=1

echo "== preflight: goodput ledger smoke =="
JAX_PLATFORMS=cpu python scripts/goodput_smoke.py || rc=1

echo "== preflight: tile table validate =="
JAX_PLATFORMS=cpu python scripts/tile_sweep.py --validate || rc=1

echo "== preflight: compile/HBM profile smoke =="
JAX_PLATFORMS=cpu python scripts/profile_smoke.py || rc=1

echo "== preflight: request lifecycle smoke =="
JAX_PLATFORMS=cpu python scripts/request_smoke.py || rc=1

if [ "$rc" -ne 0 ]; then
    echo "preflight: FAILED" >&2
else
    echo "preflight: ok"
fi
exit "$rc"
