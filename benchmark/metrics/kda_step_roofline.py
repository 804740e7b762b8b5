"""The one-token KDA state update (the ``kda.step`` kernel, one call a
KDA layer a decode step) against its roofline: the least time the chip
could take for a call (the float32 state of every slot read once and
written once over the HBM peak, or its operations over the bf16 peak,
whichever is larger; benchmark/harness/costs_hybrid.py) over the device
time the traced calls took.

A Mosaic kernel is named by the scope it was traced in, so the calls are
the custom calls named ``kda.step*``. The chunk-wise prefill under
``kda.prefill`` is XLA fusions, whose event names carry no scope: it is
not read here (PERF.md, Open questions).
"""

import re

from benchmark.harness import costs_hybrid, readers

CALL = re.compile(r"^%kda\.step[\w.]* = .*? custom-call\(")


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    if not s or pk is None or not red:
        return None
    flops, nbytes = costs_hybrid.kda_step_cost(out["cell"].cfg, s["slots"])
    least_one = max(flops / pk["bf16_flops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
    took = [dur / 1e9 for name, _start, dur in red["op_events"]
            if CALL.match(name)]
    return 100.0 * len(took) * least_one / sum(took) if took else None
