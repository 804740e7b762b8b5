"""The benchmark's entry: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip itself. It reads ``BENCHMARK.json`` for
the cell (configuration, traffic mix, chips) and for the metrics that
the cell reports, finds the configuration's driver by name, lets it set
up, measure and check, finds each per-layer metric's reader by the
metric's name (``benchmark/metrics/<name>.py``), and prints the
contract's one JSON object as the last line of standard output. The
numbers compared for ``correct`` go, each beside its limit, to the last
lines of standard error and under ``checked`` at the end of that object.

It exits with a code other than 0, and prints no result, where JAX finds
no TPU or fewer chips than the cell asks for, and where the repository's
program is not beside it.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import check, common  # noqa: E402


class Cell:
    """What a driver and the metric readers are handed."""

    def __init__(self, spec: dict, workload: dict, args,
                 data_root: str = common.BENCH) -> None:
        self.spec = spec
        self.workload = workload["name"]
        self.chips = int(workload["chips"])
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace = bool(int(args.trace))
        config = next(c for c in spec["configs"]
                      if c["name"] == workload["config"])
        with open(os.path.join(ROOT, config["file"])) as f:
            self.cfg = json.load(f)
        with open(os.path.join(data_root, "traffic",
                               f"{workload['traffic']}.json")) as f:
            self.mix = json.load(f)
        self.limits = check.load_limits(self.workload, data_root)
        self.control = None          # set by benchmark/tests/limits_probe.py
        self.devices = None
        self.compiles = None
        self.setup_s = None

    def mark_window_start(self) -> None:
        self.setup_s = time.monotonic() - _T_PROCESS

    def metrics_reported(self, group: str) -> list:
        """The metrics of ``group`` that this cell reports."""
        e2e = {m["name"] for m in self.spec["end_to_end"]
               if self.workload in m.get("workloads", [self.workload])}
        if group == "end_to_end":
            return [m for m in self.spec["end_to_end"]
                    if m["name"] in e2e]
        return [m for m in self.spec["per_layer"]
                if (self.workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def load_reader(name: str):
    return common.load_module(
        os.path.join(common.BENCH, "metrics", f"{name}.py")).read


def run_cell(args, require_chip: bool = True, control=None, spec=None,
             data_root: str = common.BENCH):
    """(exit code, result object or None). The keyword arguments are for
    benchmark/tests: a CPU rehearsal (``require_chip`` False) of a tiny
    cell (``spec`` in place of BENCHMARK.json, its mixes and limits under
    ``data_root``), and the probe that reads the control beside the
    program."""
    if spec is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            spec = json.load(f)
    workload = next((w for w in spec["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        common.log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2, None
    cell = Cell(spec, workload, args, data_root)
    cell.control = control
    try:
        importlib.import_module("kubeflow_tpu")
    except ImportError as e:
        common.log(f"the program is not beside the benchmark: {e}")
        return 2, None
    try:
        cell.devices = common.find_devices(cell.chips, require_chip)
    except (common.NoChip, RuntimeError) as e:
        common.log(f"no chip: {e}")
        return 2, None
    # the program's one helper: <checkout>/.jax_cache, a fixed place inside
    # the checkout, unless JAX_COMPILATION_CACHE_DIR places it
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    cell.compiles = common.CompileCounter()
    common.log(f"cell {cell.workload} seed {cell.seed} seconds "
               f"{cell.seconds} trace {int(cell.trace)} on "
               f"{cell.devices[0].device_kind} x{len(cell.devices)}; "
               f"compile cache {cache}")
    driver = importlib.import_module(
        f"benchmark.harness.drivers.{cell.cfg['driver']}")
    out = driver.run(cell)
    out["cell"] = cell

    values = dict(out["end_to_end"])
    values["setup_s"] = cell.setup_s
    metrics = {}
    if not cell.trace:
        for m in cell.metrics_reported("end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        out["values"] = values
        for m in cell.metrics_reported("per_layer"):
            value = load_reader(m["name"])(out)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    correct, rows = check.judge(out["numbers"], cell.limits)
    tracing = out.get("trace")
    device = dict(out["device"])
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if tracing is not None:
        device.update(tracing.device_extra())
        if tracing.breakdown():
            result["breakdown"] = tracing.breakdown()
    for fault in ("control", "halfbatch"):
        got = {k[len(fault) + 1:]: v for k, v in out["numbers"].items()
               if k.startswith(fault + "_") and not k.startswith(fault + "__")}
        if got:
            result[fault] = got
    result["checked"] = {n: {"value": v, "limit": lim}
                         for n, v, lim in rows}
    common.log(f"setup_s {cell.setup_s:.3f}; compiles in process "
               f"{cell.compiles.total}; other numbers: "
               + json.dumps({k: v for k, v in out["numbers"].items()
                             if k not in result["checked"]}))
    for n, v, lim in rows:
        print(f"checked {n} = {v} (limit {lim})", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    return 0, result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    code, result = run_cell(args)
    if result is not None:
        sys.stdout.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
