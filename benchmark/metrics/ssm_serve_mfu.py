"""The whole serving step's share of the chip's bf16 peak for
Nemotron-3-Nano's decoder: the operations of every prefill whose first
token fell in the window and of every token decoded in it (attention at
each token's own context; the routed experts at the held (token, expert)
pairs a token-block that the engine's rounds counted, or the even share
where they counted none), over window x peak
(benchmark/harness/costs_ssm.py)."""

import numpy as np

from benchmark.harness import costs_ssm, readers, ssm_rounds


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    if not s or pk is None:
        return None
    t0, t_end = s["t0"], s["t_end"]
    prompts, contexts = [], []
    for r in s["requests"]:
        if r.t_first is None:
            continue
        if t0 < r.t_first <= t_end:
            prompts.append(r.prompt.size)
        for j, t in enumerate(r.stamps[1:], start=1):
            if t0 < t <= t_end:
                contexts.append(r.prompt.size + j)
    cfg = out["cell"].cfg
    counted = ssm_rounds.per_block_step(out)
    pairs = counted[0] / s["slots"] if counted else None

    def per_token(context, with_head=True):
        return costs_ssm.forward_flops_per_token(cfg, context, with_head,
                                                 pairs)

    p, ctx = np.asarray(prompts, np.float64), np.asarray(contexts, np.float64)
    head = per_token(0.0) - per_token(0.0, with_head=False)
    flops = (np.sum(p * per_token((p + 1) / 2, with_head=False))
             + head * p.size + np.sum(per_token(ctx)))
    return 100.0 * float(flops) / ((t_end - t0) * pk["bf16_flops_per_s"])
