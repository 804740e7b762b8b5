"""Readings for the limits of Nemotron-3-Nano's cell, on the chip, in one
process: ``limits_probe_dsa.py`` for this cell's three variants to hold
off (the fp8 control and the two rehearsed faults: the conv tail dropped
where prefill hands a row to decode, the gated norm taken over all 4096
channels).

    python3 benchmark/tests/limits_probe_ssm.py --seeds 11,12,13 \\
        --control-seeds 11,12,13 --seconds 40

For each seed it runs the cell as a run does and prints the numbers
compared; on the control seeds it also reads the three variants (the
reference computed through each, in the program's place). The
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
from benchmark.harness.drivers import serve_ssm  # noqa: E402

CELL = "nemotron-3-nano-30b-a3b.many-rows-reasoning"


def variants() -> dict:
    """The keywords of the reference's forward, by variant."""
    ref = check.load_reference("nemotron_h_f32")
    return {"control": {"cast": ref.fp8_operands},
            "notail": {"fault": "conv_tail_dropped"},
            "nogroup": {"fault": "norm_ungrouped"}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default=CELL)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--trace-seeds", default="")
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                 "limits"))
    a = p.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    traced = {int(s) for s in a.trace_seeds.split(",") if s}
    seen, run = {}, serve_ssm.run

    def spy(cell):
        seen["numbers"] = (out := run(cell))["numbers"]
        return out

    serve_ssm.run = spy
    os.makedirs(a.out, exist_ok=True)
    rows = []
    for seed in seeds:
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds,
                                  trace=int(seed in traced))
        code, result = bench_run.run_cell(
            args, control=variants() if seed in controls else None)
        if result is None:
            return code or 1
        row = {"seed": seed, "trace": int(seed in traced),
               "correct": result["correct"],
               "attempted": result["attempted"], "failed": result["failed"],
               "numbers": seen["numbers"],
               "metrics": {n: m["value"]
                           for n, m in result["metrics"].items()},
               "device": result["device"],
               "breakdown": result.get("breakdown")}
        rows.append(row)
        print("PROBE " + json.dumps(row), flush=True)
        with open(os.path.join(a.out, f"{a.workload}.json"), "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
