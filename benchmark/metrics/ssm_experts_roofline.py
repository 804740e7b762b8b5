"""The routed blocks' grouped products (``moe.experts``: up and down,
each one ``ragged_dot`` over the (token, expert) pairs sorted by expert)
against their roofline, in the decode step: for each traced call the
least time the chip could take (the larger of the held pairs' operations
over the bf16 peak and the hit experts' bytes over the HBM peak, from
benchmark/harness/costs_ssm.py and the pairs and experts a block-step
that the engine's rounds counted over the pattern's ``E`` blocks) over
the device time the calls took.

The TPU compiler names the grouped product ``ragged-dot*`` whatever scope
it was traced in; a decode step's calls are told from a prefill's by their
rows: slots x experts a token.
"""

import re

from benchmark.harness import costs_ssm, readers, ssm_rounds

CALL = re.compile(r"^%ragged-dot[\w.\-]* = (?:bf16|f32)\[(\d+),\d+\]\S* "
                  r"custom-call\(")


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    counted = ssm_rounds.per_block_step(out) if s else None
    if not s or pk is None or not red or counted is None:
        return None
    cfg = out["cell"].cfg
    flops, nbytes = costs_ssm.grouped_product_cost(cfg, *counted)
    least_one = max(flops / pk["bf16_flops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
    rows = s["slots"] * cfg["num_experts_per_tok"]
    took = [dur / 1e9 for name, _start, dur in red["op_events"]
            if (found := CALL.match(name)) and int(found.group(1)) == rows]
    return 100.0 * len(took) * least_one / sum(took) if took else None
