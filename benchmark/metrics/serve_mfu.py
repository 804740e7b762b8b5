"""The whole serving step's share of the chip's bf16 peak: the operations
of every prefill whose first token fell in the window and of every token
decoded in it (each at its own context), over window x peak."""

import numpy as np

from benchmark.harness import readers


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    if not s or pk is None:
        return None
    t0, t_end = s["t0"], s["t_end"]
    prompts, contexts = [], []
    for r in s["requests"]:
        if r.t_first is None:
            continue
        if t0 <= r.t_first <= t_end:
            prompts.append(r.prompt.size)
        for j, t in enumerate(r.stamps[1:], start=1):
            if t <= t_end:
                contexts.append(r.prompt.size + j)
    cfg, per_token = out["cell"].cfg, readers.cost_fn(out, "flops_per_token")
    p, ctx = np.asarray(prompts, np.float64), np.asarray(contexts, np.float64)
    head = per_token(cfg, 0.0) - per_token(cfg, 0.0, with_head=False)
    flops = (np.sum(p * per_token(cfg, (p + 1) / 2, with_head=False))
             + head * p.size + np.sum(per_token(cfg, ctx)))
    return 100.0 * float(flops) / ((t_end - t0) * pk["bf16_flops_per_s"])
