"""The flash-attention kernels against their roofline: over the traced
forward, dQ and dK/dV kernel calls, the least time the chip could take for
each (the larger of operations over the bf16 peak and bytes over the HBM
peak, counted from shapes in benchmark/harness/costs.py) over the device
time the calls took.

The program gives its ``pallas_call``s no name, so a call is found as a
Mosaic custom call inside ``attn._attend`` and told apart by what it
returns: (out, row statistics) is the forward, one tensor is dQ, two
tensors are dK and dV (PERF.md, for the tracing issue).
"""

import re

from benchmark.harness import costs, readers

CALL = re.compile(r"^%attn\._attend[\w.]* = (.*?) custom-call\(")


def kind_of(name: str):
    hit = CALL.match(name)
    if not hit:
        return None
    outs = re.findall(r"(bf16|f32)\[", hit.group(1))
    if outs == ["bf16", "f32"]:
        return "fwd"
    return {1: "dq", 2: "dkv"}.get(len(outs)) if set(outs) == {"bf16"} \
        else None


def read(out):
    t, pk = out.get("train"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    if not t or pk is None or not red:
        return None
    cfg = out["cell"].cfg
    least = took = 0.0
    for name, _start, dur in red["op_events"]:
        kind = kind_of(name)
        if kind is None:
            continue
        flops, nbytes = costs.flash_kernel_cost(
            kind, t["batch"], cfg["num_attention_heads"], t["seq_len"],
            cfg["head_dim"])
        least += max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
        took += dur / 1e9
    return 100.0 * least / took if took > 0 else None
