"""From a profiler trace to numbers: the reduction every PR shares.

``Window`` starts and stops ``jax.profiler`` around a short part of the
measured window and reads the ``.xplane.pb`` it leaves (under
``<checkout>/.bench_out``, removed once read). ``reduce`` works on plain
data (planes -> lines -> events as ``(name, start_ns, duration_ns)``), so
it is checked on a small recorded trace without a chip:

- busy: the union of the intervals in which an operation ran on a device,
  averaged over the devices used;
- self time per operation: an event's duration less that of the events
  nested in it (a ``while`` encloses its body), summed by name;
- idle gaps: the longest stretches with no operation on the device, each
  named by the host event that overlaps it most;
- module events: the executions of whole compiled programs, by name.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Tuple

from benchmark.harness import common

Event = Tuple[str, int, int]           # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(path: str) -> List[dict]:
    """Planes of an ``.xplane.pb`` as plain data."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def _line(plane: dict, name: str) -> List[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(events: List[Event]) -> List[Tuple[int, int]]:
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    merged: List[List[int]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds of self time by name (children's time taken out)."""
    out: Dict[str, float] = {}
    stack: List[list] = []             # [name, end, self_ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + item[2] / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return out


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line: keep the result's
    name, its first shape and the opcode."""
    left, sep, right = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", right)
    op = _OPCODE.search(right)
    return " ".join(x for x in (left, shape.group(1) if shape else "",
                                op.group(1) if op else "") if x)


def _host_events(planes: List[dict]) -> List[Event]:
    events: List[Event] = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                events.extend(e for e in line["events"] if e[2] > 0)
    return events


def _name_gap(gap: Tuple[int, int], host: List[Event]) -> str:
    best, name = 0, "host: nothing recorded"
    for n, s, d in host:
        over = min(gap[1], s + d) - max(gap[0], s)
        if over > best:
            best, name = over, n
    return name


def reduce(planes: List[dict], window_s: Optional[float] = None,
           n_devices: Optional[int] = None) -> Optional[dict]:
    """The numbers of one traced window; None where no device plane
    holds an operation."""
    devs = [p for p in planes if is_device_plane(p["name"])
            and _line(p, OPS_LINE)]
    if n_devices:
        devs = devs[:n_devices]
    if not devs:
        return None
    busy, ops, modules = 0.0, {}, []
    lo = min(e[1] for p in devs for e in _line(p, OPS_LINE))
    hi = max(e[1] + e[2] for p in devs for e in _line(p, OPS_LINE))
    for p in devs:
        merged = _union(_line(p, OPS_LINE))
        busy += sum(b - a for a, b in merged) / 1e9
        for name, s in self_times(_line(p, OPS_LINE)).items():
            ops[name] = ops.get(name, 0.0) + s / len(devs)
        modules.extend(_line(p, MODULES_LINE))
    first = _union(_line(devs[0], OPS_LINE))
    gaps = sorted(((b0, a1) for (_a0, b0), (a1, _b1)
                   in zip(first, first[1:])),
                  key=lambda g: g[0] - g[1])[:10]
    host = _host_events(planes)
    span_s = (hi - lo) / 1e9
    return {
        "busy_s": busy / len(devs),
        "window_s": max(window_s or 0.0, span_s),
        "span_s": span_s,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1]),
        "idle_gaps": [[_name_gap(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps],
        "modules": modules,
        "op_events": [e for p in devs for e in _line(p, OPS_LINE)],
        "devices": len(devs),
    }


def describe(planes: List[dict], top: int = 12) -> str:
    """What a trace holds, for a first look by hand."""
    rows = []
    for p in planes:
        rows.append(f"plane {p['name']!r}")
        for line in p["lines"]:
            ev = line["events"]
            rows.append(f"  line {line['name']!r}: {len(ev)} events")
            tot: Dict[str, float] = {}
            for n, _s, d in ev:
                tot[n] = tot.get(n, 0.0) + d / 1e9
            for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                rows.append(f"    {s:10.6f} s  {n[:110]}")
    return "\n".join(rows)


def sample(planes: List[dict], span_ns: int, max_events: int = 600) -> dict:
    """A small cut of a trace (the first ``span_ns`` of device time, and
    the host events that overlap it), as the plain data that ``reduce``
    takes: what benchmark/tests/data/small_trace.json was recorded with."""
    devs = [p for p in planes if is_device_plane(p["name"])
            and _line(p, OPS_LINE)]
    if not devs:
        return {"planes": []}
    lo = min(e[1] for e in _line(devs[0], OPS_LINE))
    hi = lo + span_ns
    out = []
    for p in planes:
        if not (p is devs[0] or p["name"].startswith("/host:")):
            continue
        lines = []
        for line in p["lines"]:
            ev = [list(e) for e in line["events"]
                  if e[1] >= lo and e[1] + e[2] <= hi][:max_events]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        out.append({"name": p["name"], "lines": lines})
    return {"planes": out, "window_s": span_ns / 1e9}


class Window:
    """The traced part of a measured window."""

    def __init__(self, cell) -> None:
        self.dir = os.path.join(common.OUT_DIR, "trace", cell.workload)
        self.n_devices = len(cell.devices)
        self.keep = bool(os.environ.get("BENCH_KEEP_TRACE"))
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.reduced: Optional[dict] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host annotations, no call tracing
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.monotonic()

    def stop(self) -> None:
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    def tick(self, elapsed: float, at: float, length: float) -> None:
        if self.t_start is None and elapsed >= at:
            self.start()
        elif (self.t_start is not None and self.t_stop is None
              and elapsed >= at + length):
            self.stop()

    def close(self) -> None:
        """Stop if still open, read the trace and remove it."""
        if self.t_start is None:
            return
        if self.t_stop is None:
            self.stop()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if found:
            planes = load_xplane(found[0])
            self.reduced = reduce(planes, self.t_stop - self.t_start,
                                  self.n_devices)
            if self.keep:
                with open(os.path.join(common.OUT_DIR,
                                       "trace_look.txt"), "w") as f:
                    f.write(describe(planes))
                with open(os.path.join(common.OUT_DIR,
                                       "trace_sample.json"), "w") as f:
                    json.dump(sample(planes, 60_000_000), f)
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)

    def device_extra(self) -> dict:
        if not self.reduced:
            return {}
        return {"busy_s": self.reduced["busy_s"],
                "window_s": self.reduced["window_s"]}

    def breakdown(self) -> Optional[dict]:
        if not self.reduced:
            return None
        return {"device_ops": [[short_name(n), s] for n, s
                               in self.reduced["device_ops"][:10]],
                "idle_gaps": self.reduced["idle_gaps"][:10]}
