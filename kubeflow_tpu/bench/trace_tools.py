"""Trace inspection: the auditable per-op breakdown behind PERF.md.

The profiler tier (``kubeflow_tpu/utils/profiler.py``) writes
TensorBoard-compatible trace dirs (``plugins/profile/<run>/*.trace.json.gz``);
this reads them back and aggregates device-lane op durations, so a perf
claim ("backward conv fusions dominate at N ms/step") is reproducible
from a captured trace dir with one command:

    ctl trace-top chiprun_out/traces/resnet50 [--top 20]

The reference's closest surface is "open TensorBoard and look"
(``/root/reference/kubeflow/tensorboard/tensorboard.libsonnet``); a CLI
table is what perf review actually needs.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from typing import Any, Dict, List, Optional

# the device lane the XLA profiler emits per-op events into
_OP_LANE = "XLA Ops"
_STEP_LANE = "Steps"


def find_trace_file(trace_dir: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under ``trace_dir`` (searched
    recursively — the profiler nests ``plugins/profile/<timestamp>/``)."""
    hits = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load_events(path: str) -> List[Dict[str, Any]]:
    with gzip.open(path, "rt") as f:
        return json.load(f).get("traceEvents", [])


def top_ops(trace_dir: str, top: int = 20) -> Dict[str, Any]:
    """Aggregate device-lane op durations from the newest trace.

    Returns ``{trace_file, device, steps, device_total_ms, ops: [{name,
    total_ms, pct, count, mean_us}, ...]}`` — ops sorted by total time.
    """
    path = find_trace_file(trace_dir)
    if path is None:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {trace_dir!r} — capture one with "
            "bench.py --profile or utils.profiler.trace()")
    events = load_events(path)
    proc = {e["pid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    lanes = {(e["pid"], e.get("tid")): e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    device_pids = {p for p, n in proc.items() if "/device:" in n}
    agg: Dict[str, float] = collections.defaultdict(float)
    cnt: collections.Counter = collections.Counter()
    steps_by_pid: collections.Counter = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = lanes.get((e["pid"], e.get("tid")))
        if lane == _OP_LANE:
            agg[e["name"]] += float(e.get("dur", 0.0))
            cnt[e["name"]] += 1
        elif lane == _STEP_LANE:
            steps_by_pid[e["pid"]] += 1
    # every core replays the same steps; op totals aggregate all cores
    steps = max(steps_by_pid.values()) if steps_by_pid else 0
    total = sum(agg.values())
    ops = [{
        "name": name,
        "total_ms": round(dur / 1e3, 3),
        "pct": round(100.0 * dur / total, 1) if total else 0.0,
        "count": cnt[name],
        "mean_us": round(dur / cnt[name], 1),
    } for name, dur in sorted(agg.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "trace_file": path,
        "devices": sorted(proc[p] for p in device_pids),
        "steps": steps,
        "device_total_ms": round(total / 1e3, 3),
        "ops": ops,
    }


def format_top_ops(report: Dict[str, Any]) -> str:
    lines = [
        f"trace:  {report['trace_file']}",
        f"devices: {', '.join(report['devices'])}   "
        f"steps: {report['steps']}   "
        f"device time: {report['device_total_ms']:.1f} ms",
        f"{'total ms':>10} {'%':>6} {'count':>6} {'mean us':>9}  op",
    ]
    for op in report["ops"]:
        lines.append(f"{op['total_ms']:>10.2f} {op['pct']:>6.1f} "
                     f"{op['count']:>6d} {op['mean_us']:>9.1f}  "
                     f"{op['name']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    """Shared CLI body (also behind ``ctl trace-top``)."""
    import argparse
    import sys

    p = argparse.ArgumentParser(
        description="per-op device-time table from a profiler trace dir")
    p.add_argument("trace_dir")
    p.add_argument("--top", type=int, default=20)
    p.add_argument("--json", action="store_true",
                   help="emit the report as JSON instead of a table")
    args = p.parse_args(argv)
    try:
        report = top_ops(args.trace_dir, top=args.top)
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 1
    print(json.dumps(report) if args.json else format_top_ops(report))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
