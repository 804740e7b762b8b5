"""Device time a decode step spends choosing each row's ``index_topk``
positions out of its scores: the ``dsa.select`` kernel's calls (one a
layer a step, over ``f32[slots,S]`` score rows, found by that operand
shape; an admission chunk's calls have its queries for rows and are left
out), summed over the layers of a step."""

import re

from benchmark.harness import readers

CALL = re.compile(r"^%dsa\.select[\w.]* = f32\[(\d+),\d+\]\S* custom-call\(")


def read(out):
    s, red = out.get("serve"), readers.reduced_trace(out)
    if not s or not red:
        return None
    took = [dur / 1e6 for name, _start, dur in red["op_events"]
            if (m := CALL.match(name)) and int(m.group(1)) == s["slots"]]
    layers = out["cell"].cfg["num_hidden_layers"]
    return sum(took) / (len(took) / layers) if took else None
