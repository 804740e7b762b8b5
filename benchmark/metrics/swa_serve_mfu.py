"""The whole serving step's share of the chip's bf16 peak for
MiMo-V2-Flash's decoder: the operations of every prefill whose first
token fell in the window (every token at its own context: a global layer
attends to all of it, a window layer to the last 128 positions) and of
every token decoded in it, the routed experts at the held (token, expert)
pairs a token-layer that the engine's rounds counted (or the even share
where they counted none), over window x peak
(benchmark/harness/costs_swa.py)."""

import numpy as np

from benchmark.harness import costs_swa, readers, swa_rounds


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    if not s or pk is None:
        return None
    t0, t_end = s["t0"], s["t_end"]
    cfg = out["cell"].cfg
    counted = swa_rounds.per_layer_step(out)
    pairs = counted[0] / s["slots"] if counted else None
    flops, contexts = 0.0, []
    for r in s["requests"]:
        if r.t_first is None:
            continue
        if t0 < r.t_first <= t_end:
            flops += costs_swa.swa_prefill_flops(cfg, r.prompt.size, pairs)
        for j, t in enumerate(r.stamps[1:], start=1):
            if t0 < t <= t_end:
                contexts.append(r.prompt.size + j)
    flops += float(np.sum(costs_swa.swa_forward_flops_per_token(
        cfg, np.asarray(contexts, np.float64), True, pairs)))
    return 100.0 * flops / ((t_end - t0) * pk["bf16_flops_per_s"])
