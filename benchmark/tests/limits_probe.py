"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 benchmark/tests/limits_probe.py --workload <cell> \
        --seeds 11,12,13,14 --control-seeds 11,12,13 --seconds 10

For each seed it runs the cell as a run does (the cell's own load, a
short window) and prints the numbers compared; on the control seeds it
also reads the control (the reference with every matmul operand rounded
to float8_e4m3, in the program's place) and, for a training cell, the
half-batch fault planted in the reference. PERF.md section 2 holds the
readings that the limits in benchmark/limits/ were set from. The
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import check  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                 "limits"))
    a = p.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    fp8 = check.load_reference().fp8_operands
    rows = []
    for seed in seeds:
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        code, result = bench_run.run_cell(
            args, control=fp8 if seed in controls else None)
        if result is None:
            return code or 1
        row = {"seed": seed, "correct": result["correct"],
               "attempted": result["attempted"], "failed": result["failed"],
               "program": {n: c["value"]
                           for n, c in result["checked"].items()},
               "control": result.get("control"),
               "halfbatch": result.get("halfbatch"),
               "metrics": {n: m["value"]
                           for n, m in result["metrics"].items()},
               "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        rows.append(row)
        print("PROBE " + json.dumps(row), flush=True)
    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, f"{a.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    names = list(rows[0]["program"])
    for n in names:
        lower = max(r["program"][n] for r in rows)
        ups = [r["control"][n] for r in rows
               if r["control"] and n in r["control"]]
        halves = [r["halfbatch"][n] for r in rows
                  if r["halfbatch"] and n in r["halfbatch"]]
        print(f"SUMMARY {n}: program max {lower:.6g} over {len(rows)} seeds; "
              f"control min {min(ups) if ups else None}; "
              f"half-batch min {min(halves) if halves else None}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
