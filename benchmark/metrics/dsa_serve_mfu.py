"""The whole serving step's share of the chip's bf16 peak for
DeepSeek-V3.2's decoder: the operations of every prefill whose first
token fell in the window (every token at its own context: the indexer at
the positions it scores, attention at the positions it keeps) and of
every token decoded in it, the routed experts at the held (token, expert)
pairs a token-layer that the engine's rounds counted (or the even share
where they counted none), over window x peak
(benchmark/harness/costs_dsa.py)."""

import numpy as np

from benchmark.harness import costs_dsa, moe_rounds, readers


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    if not s or pk is None:
        return None
    t0, t_end = s["t0"], s["t_end"]
    cfg = out["cell"].cfg
    counted = moe_rounds.per_layer_step(out)
    pairs = counted[0] / s["slots"] if counted else None
    flops, contexts = 0.0, []
    for r in s["requests"]:
        if r.t_first is None:
            continue
        if t0 < r.t_first <= t_end:
            flops += costs_dsa.dsa_prefill_flops(cfg, r.prompt.size, pairs)
        for j, t in enumerate(r.stamps[1:], start=1):
            if t0 < t <= t_end:
                contexts.append(r.prompt.size + j)
    flops += float(np.sum(costs_dsa.dsa_forward_flops_per_token(
        cfg, np.asarray(contexts, np.float64), True, pairs)))
    return 100.0 * flops / ((t_end - t0) * pk["bf16_flops_per_s"])
