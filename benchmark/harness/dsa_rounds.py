"""What the sparse-attention layers counted over the window's decode
steps, from the ``index_scored`` and ``index_selected`` that the engine's
``engine.round`` spans carry (``benchmark/harness/engine_rounds.py`` finds
the spans). A program whose rounds carry no such counts, as any commit
before PR 33, reads as None."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.harness import engine_rounds


def per_layer_step(out) -> Optional[Tuple[float, float]]:
    """(cached positions scored, positions kept) a sparse-attention layer
    a decode step, summed over the step's rows, averaged over the
    window's rounds that stepped."""
    rounds = [r for r in (engine_rounds.window_rounds(out) or [])
              if r.attrs["k"] > 0]
    if not rounds or any(r.attrs.get("index_scored") is None
                         for r in rounds):
        return None
    layer_steps = (sum(r.attrs["k"] for r in rounds)
                   * out["cell"].cfg["num_hidden_layers"])
    return (sum(r.attrs["index_scored"] for r in rounds) / layer_steps,
            sum(r.attrs["index_selected"] for r in rounds) / layer_steps)
