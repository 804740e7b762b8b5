"""The one-token step attention of MiMo-V2-Flash's global and window
layers (``ops/step_attention.py``, one call a layer a decode step)
against its roofline: the least time the chip could take for a step's
calls (their operations over the bf16 peak, or the cached K and V the
rows read, their live row blocks and ring slots, with the queries in and
the outputs out, over the HBM peak, whichever is larger;
benchmark/harness/costs_swa.py, the positions from the engine rounds'
counters) over the device time the traced calls took.

A Mosaic kernel is named by the scope it was traced in, so the calls are
the custom calls named ``step_attention*`` (the kernel's own scope, under
``attn.attend`` and ``swa.attend``): a decode step makes one a layer.
"""

import re

from benchmark.harness import costs_swa, readers, swa_rounds

CALL = re.compile(r"^%step_attention[\w.]* = .*? custom-call\(")


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    counted = swa_rounds.attention_positions(out) if s else None
    if not s or pk is None or not red or counted is None:
        return None
    cfg = out["cell"].cfg
    flops, nbytes = costs_swa.step_attend_cost(cfg, counted[0], counted[1],
                                               s["slots"])
    least_step = max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    took = [dur / 1e9 for name, _start, dur in red["op_events"]
            if CALL.match(name)]
    calls_a_step = len(cfg["hybrid_layer_pattern"])
    if not took:
        return None
    return 100.0 * least_step * len(took) / calls_a_step / sum(took)
