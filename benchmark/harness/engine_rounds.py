"""The engine's own account of its thread: the ``engine.round`` spans
that ``DecodeEngine.run_once`` records, one per cycle that did work, with
the seconds of each phase as attributes (``wait_s`` blocked on an empty
queue, ``admit_s`` admission and prefill on the host's clock, ``step_s``
launching the K steps, ``sync_s`` waiting for them and reading the tokens,
``emit_s`` handing them out; ``k`` steps in the round).

The spans land in the program's default collector, which outlives the
engine and stamps with ``time.monotonic``, the serve driver's own clock:
so the readers cut them to the measured window. A program that records
no such span (any commit before the rounds existed) reads as None.
"""

from __future__ import annotations

from typing import List, Optional

MIN_ROUNDS = 50        # fewer say little: the ring evicted them, or no load


def window_rounds(out) -> Optional[List]:
    """The round spans of this cell's engine that began inside the
    measured window; None where fewer than ``MIN_ROUNDS`` are held."""
    serve = out.get("serve")
    if not serve:
        return None
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR

    model = out["cell"].cfg["name"]
    rounds = [sp for sp in DEFAULT_COLLECTOR.spans()
              if sp.name == "engine.round"
              and sp.attrs.get("model") == model
              and serve["t0"] <= sp.start < serve["t_end"]]
    return rounds if len(rounds) >= MIN_ROUNDS else None


def ms_per_step(out, *phases: str) -> Optional[float]:
    """Seconds of ``phases`` summed over the window's rounds that
    stepped, over the steps those rounds made, in ms."""
    rounds = window_rounds(out)
    if rounds is None:
        return None
    stepped = [r for r in rounds if r.attrs["k"] > 0]
    steps = sum(r.attrs["k"] for r in stepped)
    if not steps:
        return None
    return 1e3 * sum(r.attrs[p] for r in stepped for p in phases) / steps
