"""Helpers of the CPU rehearsals: a tiny benchmark spec over the files in
benchmark/tests/data, and one run of a tiny cell with no chip."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [("tiny-serve", "tiny-open", 1), ("tiny-serve", "tiny-closed", 1),
             ("tiny-train", "tiny-steps", 1),
             ("tiny-train-tp4", "tiny-steps", 4)]
    spec["configs"] = [
        {"name": c, "file": f"benchmark/tests/data/configs/{c}.json"}
        for c in sorted({c for c, _t, _n in cells})]
    spec["workloads"] = [
        {"name": f"{c}.{t}", "config": c, "traffic": t, "chips": n}
        for c, t, n in cells]
    kind = {"tiny-open": "chat-steady", "tiny-closed": "offline-batch",
            "tiny-steps": "pretrain-8k"}
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [
                    w["name"] for w in spec["workloads"]
                    if any(x.endswith(kind[w["traffic"]])
                           for x in m["workloads"])]
    return spec


def run(workload: str, seed: int = 3, seconds: float = 2.0, trace: int = 0,
        control=None):
    from benchmark import run as bench_run

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return bench_run.run_cell(args, require_chip=False, control=control,
                              spec=tiny_spec(), data_root=DATA)


if __name__ == "__main__":
    code, result = run(sys.argv[1], *(int(x) for x in sys.argv[2:3]))
    print(json.dumps(result))
    sys.exit(code)
