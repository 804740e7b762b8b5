"""What the per-layer metric readers share. A reader takes the run's
``out`` (the driver's record, the reduced trace, the cell) and returns a
number, or None where it finds nothing to read."""

from __future__ import annotations

import re
from typing import List, Optional

from benchmark.harness import costs, peaks


def chip_peaks(out) -> Optional[dict]:
    """The chip's published peaks; None off the chip (a CPU rehearsal
    reports no share of a peak)."""
    dev = out["cell"].devices[0]
    if dev.platform != "tpu":
        return None
    return peaks.peaks_for(dev.device_kind)


def reduced_trace(out) -> Optional[dict]:
    tracing = out.get("trace")
    return tracing.reduced if tracing is not None else None


def idle_pct(out) -> Optional[float]:
    red = reduced_trace(out)
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def module_durations(out, pattern: str) -> List[float]:
    """Seconds of each traced execution of the programs whose name
    matches ``pattern``."""
    red = reduced_trace(out)
    if not red:
        return []
    rx = re.compile(pattern)
    return [d / 1e9 for n, _s, d in red["modules"] if rx.search(n)]


def decode_step_s(out) -> Optional[float]:
    """Mean device time of one decode step: the engine's K-step scan
    programs (general and all-greedy) over K."""
    runs = module_durations(out, r"^jit__step(_greedy)?\b")
    if not runs:
        return None
    return sum(runs) / len(runs) / out["serve"]["steps_per_sync"]


def cost_fn(out, key: str):
    return getattr(costs, out["cell"].cfg[key])
