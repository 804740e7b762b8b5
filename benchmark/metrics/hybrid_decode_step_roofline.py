"""The hybrid decoder's decode step against the HBM roofline: the bytes
one step has to move (the non-expert weights once, the held experts that
the step's rows actually hit, from the engine rounds' ``experts_hit``,
every slot's KDA state read and written, the latent of the positions that
live rows hold, averaged over the window from the client's stamps) over
the chip's bandwidth, over the traced device time of a step."""

import numpy as np

from benchmark.harness import costs_hybrid, moe_rounds, readers


def live_context_tokens(s) -> float:
    """Positions that live rows hold, averaged over the window."""
    t0, t_end = s["t0"], s["t_end"]
    live_token_seconds = 0.0
    for r in s["requests"]:
        if r.t_first is None:
            continue
        stamps = np.clip(np.asarray(r.stamps + [r.t_last]), t0, t_end)
        ctx = r.prompt.size + 1 + np.arange(len(r.stamps))
        live_token_seconds += float(np.sum(np.diff(stamps) * ctx))
    return live_token_seconds / (t_end - t0)


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    step = readers.decode_step_s(out) if s else None
    counted = moe_rounds.per_layer_step(out) if s else None
    if not s or pk is None or step is None or counted is None:
        return None
    cfg = out["cell"].cfg
    need = costs_hybrid.hybrid_decode_step_bytes(
        cfg, live_context_tokens(s),
        counted[1] * moe_rounds.routed_layers(cfg), s["slots"])
    return 100.0 * need / pk["hbm_bytes_per_s"] / step
