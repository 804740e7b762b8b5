"""The engine's own account of its admissions: the ``engine.admission``
spans that the cache manager records, one per device admission (one
prefill the engine waits for: a row, a prefix continuation, a long
prompt's chunks read once, or a batch), with the four durations that
tile it as attributes (``launch_s`` host -> device puts and the dispatch
of the prefill, ``read_s`` blocked until the first tokens are on the
host, ``insert_s`` the dispatch of the insert, ``host_s`` the rest) and
what the program ran (``prompt_tokens`` of the requests' own among
``scanned_tokens`` = ``rows_padded`` x ``width``).

The spans land in the program's default collector, as the rounds do
(``engine_rounds.py``), and are cut to the measured window the same way.
A window may hold very few (DeepSeek-V3.2: about three, each seconds
long), so one is enough; none, or a program that records no such span
(any commit before PR 37), reads as None.
"""

from __future__ import annotations

from typing import List, Optional


def window_admissions(out) -> Optional[List]:
    """The admission spans of this cell's engine that began inside the
    measured window; None where there is none."""
    serve = out.get("serve")
    if not serve:
        return None
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR

    model = out["cell"].cfg["name"]
    found = [sp for sp in DEFAULT_COLLECTOR.spans()
             if sp.name == "engine.admission"
             and sp.attrs.get("model") == model
             and serve["t0"] <= sp.start < serve["t_end"]]
    return found or None


def total(admissions, *attrs: str) -> float:
    """Sum of the named attributes over the admissions."""
    return sum(a.attrs[name] for a in admissions for name in attrs)


def seconds(admissions) -> float:
    return sum(a.end - a.start for a in admissions)
