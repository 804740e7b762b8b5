"""The lightning indexer's scores in a decode step (the ``dsa.index``
kernel, one call a layer a step) against its roofline: the least time the
chip could take for a call (its operations over the bf16 peak, or the
index keys of the rows' live positions in and their float32 scores out
over the HBM peak, whichever is larger; benchmark/harness/costs_dsa.py,
the live positions from the engine rounds' ``index_scored``) over the
device time the traced calls took.

A Mosaic kernel is named by the scope it was traced in, so the calls are
the custom calls named ``dsa.index*``; a decode step's give one score row
a slot (``f32[slots,1,S]``). An admission chunk's calls
(``f32[1,chunk,S]``) depend on where in its prompt the chunk lies, which
the trace does not say: they are not read here.
"""

import re

from benchmark.harness import costs_dsa, dsa_rounds, readers

CALL = re.compile(r"^%dsa\.index[\w.]* = f32\[(\d+),1,\d+\]\S* custom-call\(")


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    counted = dsa_rounds.per_layer_step(out) if s else None
    if not s or pk is None or not red or counted is None:
        return None
    flops, nbytes = costs_dsa.index_scores_cost(
        out["cell"].cfg, s["slots"], counted[0], counted[0])
    least_one = max(flops / pk["bf16_flops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
    took = [dur / 1e9 for name, _start, dur in red["op_events"]
            if (m := CALL.match(name)) and int(m.group(1)) == s["slots"]]
    return 100.0 * len(took) * least_one / sum(took) if took else None
