"""What the routed expert layers counted over the window's decode steps,
from the ``experts_hit`` and ``routed_pairs`` that the engine's
``engine.round`` spans carry (``benchmark/harness/engine_rounds.py``
finds the spans). A program whose rounds carry no such counts, as any
commit before PR 29, reads as None."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.harness import engine_rounds


def routed_layers(cfg: dict) -> int:
    return len(cfg["layer_types"]) - cfg["first_k_dense_replace"]


def per_layer_step(out) -> Optional[Tuple[float, float]]:
    """(held routed pairs, distinct held experts hit) a routed layer a
    decode step, averaged over the window's rounds that stepped."""
    rounds = [r for r in (engine_rounds.window_rounds(out) or [])
              if r.attrs["k"] > 0]
    if not rounds or any(r.attrs.get("routed_pairs") is None
                         for r in rounds):
        return None
    layer_steps = (sum(r.attrs["k"] for r in rounds)
                   * routed_layers(out["cell"].cfg))
    return (sum(r.attrs["routed_pairs"] for r in rounds) / layer_steps,
            sum(r.attrs["experts_hit"] for r in rounds) / layer_steps)
