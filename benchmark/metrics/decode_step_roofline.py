"""The decode step against the HBM roofline: the bytes one step has to
read (every weight, and the keys and values of the positions live rows
hold, averaged over the window from the client's stamps) over the chip's
bandwidth, over the traced device time of a step."""

import numpy as np

from benchmark.harness import readers


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    step = readers.decode_step_s(out) if s else None
    if not s or pk is None or step is None:
        return None
    t0, t_end = s["t0"], s["t_end"]
    live_token_seconds = 0.0
    for r in s["requests"]:
        if r.t_first is None:
            continue
        stamps = np.clip(np.asarray(r.stamps + [r.t_last]), t0, t_end)
        ctx = r.prompt.size + 1 + np.arange(len(r.stamps))
        live_token_seconds += float(np.sum(np.diff(stamps) * ctx))
    live = live_token_seconds / (t_end - t0)
    need = readers.cost_fn(out, "bytes_per_step")(out["cell"].cfg, live)
    return 100.0 * need / pk["hbm_bytes_per_s"] / step
