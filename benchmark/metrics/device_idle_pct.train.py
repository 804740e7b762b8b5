"""Share of the traced window in which no operation ran on the device."""

from benchmark.harness import readers


def read(out):
    return readers.idle_pct(out) if out.get("train") else None
