"""Nemotron-3-Nano's decode step against the HBM roofline: the bytes one
step has to move (the non-expert weights once, the held experts that the
step's rows actually hit, from the engine rounds' ``experts_hit``, every
slot's state-space state read and written, K and V of the positions that
live rows hold, averaged over the window from the client's stamps) over
the chip's bandwidth, over the traced device time of a step. The step's
attention reads a slot's K and V rows whole, whatever it holds, so this
reads low by that much."""

import os

from benchmark.harness import common, costs_ssm, readers, ssm_rounds

# positions that live rows hold, averaged over the window: the hybrid
# cell's reader has the count from the client's stamps
live_context_tokens = common.load_module(os.path.join(
    common.BENCH, "metrics",
    "hybrid_decode_step_roofline.py")).live_context_tokens


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    step = readers.decode_step_s(out) if s else None
    counted = ssm_rounds.per_block_step(out) if s else None
    if not s or pk is None or step is None or counted is None:
        return None
    cfg = out["cell"].cfg
    need = costs_ssm.decode_step_bytes(
        cfg, live_context_tokens(s),
        counted[1] * costs_ssm.routed_blocks(cfg), s["slots"])
    return 100.0 * need / pk["hbm_bytes_per_s"] / step
