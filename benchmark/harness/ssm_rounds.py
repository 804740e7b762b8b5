"""What the engine's spans carry for a model of one-sublayer blocks, cut
to the measured window: the routed blocks' ``experts_hit`` and
``routed_pairs`` on ``engine.round`` (``benchmark/harness/moe_rounds.py``
reads the same attributes, and counts routed layers from keys this
configuration does not have: here they are the pattern's ``E`` blocks),
and the ``bucket`` and ``prompt_tokens`` of the admissions'
``engine.prefill`` spans. A program whose spans carry no such counts
reads as None."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.harness import costs_ssm, engine_rounds


def per_block_step(out) -> Optional[Tuple[float, float]]:
    """(held routed pairs, distinct held experts hit) a routed block a
    decode step, averaged over the window's rounds that stepped."""
    rounds = [r for r in (engine_rounds.window_rounds(out) or [])
              if r.attrs["k"] > 0]
    if not rounds or any(r.attrs.get("routed_pairs") is None
                         for r in rounds):
        return None
    block_steps = (sum(r.attrs["k"] for r in rounds)
                   * costs_ssm.routed_blocks(out["cell"].cfg))
    return (sum(r.attrs["routed_pairs"] for r in rounds) / block_steps,
            sum(r.attrs["experts_hit"] for r in rounds) / block_steps)


def scan_tokens(out) -> Optional[Tuple[int, int]]:
    """(tokens the chunk-wise scan ran, of them the buckets' padding)
    over the admissions that began inside the window: every admitted
    prompt is scanned at the width of its bucket (``bucket`` on its
    ``engine.prefill`` span, a child of its ``engine.admit``), of which
    ``prompt_tokens`` are its own. The pad ROWS of a batch admission (a
    burst of 3 runs as 4) have no span and are not counted."""
    serve = out.get("serve")
    if not serve:
        return None
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR

    model = out["cell"].cfg["name"]
    spans = DEFAULT_COLLECTOR.spans()
    admits = {sp.span_id for sp in spans
              if sp.name == "engine.admit"
              and sp.attrs.get("model") == model
              and serve["t0"] <= sp.start < serve["t_end"]}
    prefills = [sp.attrs for sp in spans
                if sp.name == "engine.prefill" and sp.parent_id in admits
                and "bucket" in sp.attrs]
    if not prefills:
        return None
    return (sum(a["bucket"] for a in prefills),
            sum(a["bucket"] - a["prompt_tokens"] for a in prefills))
