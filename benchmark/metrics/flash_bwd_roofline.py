"""The fused flash backward against its roofline: over the traced calls of
the one kernel that returns dQ, dK and dV, the least time the chip could
take for the backward's mathematics (the larger of operations over the
bf16 peak and bytes over the HBM peak) over the device time the calls
took.

A call is found as ``flash_roofline.py`` finds its own: a Mosaic custom
call inside ``attn._attend``, told apart by what it returns. Three bf16
tensors are dQ, dK and dV; the forward (a tensor and row statistics) and
the separate dQ and dK/dV kernels (one and two tensors) are that file's
and are skipped here. A program without such a call reads nothing.

The cost is the five products the mathematics has (S = QK^T, dP = dO V^T,
dV = P^T dO, dK = dS^T Q, dQ = dS K), whatever a kernel runs, and each of
q, k, v, dO, dQ, dK, dV once (the row statistics are small).
"""

import re

from benchmark.harness import readers

CALL = re.compile(r"^%attn\._attend[\w.]* = (.*?) custom-call\(")
PRODUCTS, TENSORS = 5, 7


def is_fused_backward(name: str) -> bool:
    hit = CALL.match(name)
    return bool(hit) and re.findall(
        r"(bf16|f32)\[", hit.group(1)) == ["bf16"] * 3


def cost(batch: int, heads: int, seq: int, head_dim: int,
         bytes_per_value: int = 2):
    """(flops, bytes) of one causal call: each product is 2 B H S^2 Dh
    flops, halved by causality."""
    flops = PRODUCTS * 2.0 * batch * heads * seq * seq * head_dim / 2.0
    nbytes = TENSORS * batch * heads * seq * head_dim * bytes_per_value
    return flops, float(nbytes)


def read(out):
    t, pk = out.get("train"), readers.chip_peaks(out)
    red = readers.reduced_trace(out)
    if not t or pk is None or not red:
        return None
    cfg = out["cell"].cfg
    flops, nbytes = cost(t["batch"], cfg["num_attention_heads"],
                         t["seq_len"], cfg["head_dim"])
    least = max(flops / pk["bf16_flops_per_s"],
                nbytes / pk["hbm_bytes_per_s"])
    took = [dur / 1e9 for name, _start, dur in red["op_events"]
            if is_fused_backward(name)]
    return 100.0 * least * len(took) / sum(took) if took else None
