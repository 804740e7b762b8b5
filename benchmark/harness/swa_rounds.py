"""What MiMo-V2-Flash's layers counted over the window's decode steps,
from the attributes that the engine's ``engine.round`` spans carry
(``benchmark/harness/engine_rounds.py`` finds the spans): the routed
layers' ``experts_hit`` and ``routed_pairs`` (``moe_rounds.py`` reads the
same, counting routed layers from keys this configuration does not
have), the global layers' ``kv_blocks_read`` / ``kv_blocks_reserved`` and
the window layers' ``window_positions_read``. A program whose rounds
carry no such counts, as any commit before PR 40 for the window, reads
as None."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.harness import costs_swa, engine_rounds


def _stepped(out, *names):
    rounds = [r for r in (engine_rounds.window_rounds(out) or [])
              if r.attrs["k"] > 0]
    if not rounds or any(r.attrs.get(n) is None for r in rounds
                         for n in names):
        return None, 0
    return rounds, sum(r.attrs["k"] for r in rounds)


def per_layer_step(out) -> Optional[Tuple[float, float]]:
    """(held routed pairs, distinct held experts hit) a routed layer a
    decode step, averaged over the window's rounds that stepped."""
    rounds, steps = _stepped(out, "routed_pairs", "experts_hit")
    if not rounds:
        return None
    layer_steps = steps * costs_swa.layer_counts(out["cell"].cfg)[3]
    return (sum(r.attrs["routed_pairs"] for r in rounds) / layer_steps,
            sum(r.attrs["experts_hit"] for r in rounds) / layer_steps)


def attention_positions(out) -> Optional[Tuple[float, float]]:
    """(cached positions a global layer's step attention read, ring slots
    a window layer's read) a decode step, summed over the rows: the live
    blocks the step read times the positions a block holds (a row's
    blocks of ``max_position_embeddings`` over the blocks it holds), and
    the live rows' ``min(pos + 1, window)``."""
    rounds, steps = _stepped(out, "kv_blocks_read", "kv_blocks_reserved",
                             "window_positions_read")
    if not rounds:
        return None
    slots = out["serve"]["slots"]
    reserved = sum(r.attrs["kv_blocks_reserved"] for r in rounds) / steps
    block = out["cell"].cfg["max_position_embeddings"] * slots / reserved
    return (block * sum(r.attrs["kv_blocks_read"] for r in rounds) / steps,
            sum(r.attrs["window_positions_read"] for r in rounds) / steps)
