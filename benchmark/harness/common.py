"""What every driver shares: the look for a chip, the count of programs
built, the device block of the result line."""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(ROOT, ".bench_out")


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def load_module(path: str):
    """A module from a file found by name (a metric's reader, a
    configuration's reference): names with ``.`` or ``-`` import too."""
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def find_devices(chips: int, require_chip: bool):
    """The devices this cell runs on. Without ``require_chip`` (the CPU
    rehearsals under benchmark/tests) any backend will do."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX has "
                     f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts the programs JAX builds or loads (a hit in the persistent
    cache still stalls the caller, so it counts)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self.total = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.total += 1


def device_block(devices, extra: dict | None = None) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind,
             "count": len(devices), "memory_peak_bytes": peak}
    block.update(extra or {})
    return block
