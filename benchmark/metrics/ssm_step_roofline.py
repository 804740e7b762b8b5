"""The one-token state-space update (the ``ssm.step`` kernel, one call a
Mamba-2 block a decode step) against its roofline: the least time the
chip could take for a call (the float32 state of every slot read once and
written once over the HBM peak, or its operations over the bf16 peak,
whichever is larger; benchmark/harness/costs_ssm.py) over the device
time the traced calls took.

A Mosaic kernel is named by the scope it was traced in, so the calls are
the custom calls named ``ssm.step*``. The chunk-wise prefill under
``ssm.chunk`` is XLA fusions, whose event names carry no scope: it is
not read here.
"""

import re

from benchmark.harness import costs_ssm, readers

CALL = re.compile(r"^%ssm\.step[\w.]* = .*? custom-call\(")


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    if not s or pk is None or not red:
        return None
    flops, nbytes = costs_ssm.ssm_step_cost(out["cell"].cfg, s["slots"])
    least_one = max(flops / pk["bf16_flops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
    took = [dur / 1e9 for name, _start, dur in red["op_events"]
            if CALL.match(name)]
    return 100.0 * len(took) * least_one / sum(took) if took else None
