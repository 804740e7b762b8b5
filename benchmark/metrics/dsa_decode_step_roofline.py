"""DeepSeek-V3.2's decode step against the HBM roofline: the bytes one
step has to move (the non-expert weights once, the held experts that the
step's rows actually hit, the index keys of every position that live rows
hold, the latent of the positions the rows SELECTED; the last three from
the engine rounds' counters) over the chip's bandwidth, over the traced
device time of a step. A step that reads the whole latent, kept positions
or not, moves more than this and scores lower."""

from benchmark.harness import costs_dsa, dsa_rounds, moe_rounds, readers


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    step = readers.decode_step_s(out) if s else None
    routed = moe_rounds.per_layer_step(out) if s else None
    sparse = dsa_rounds.per_layer_step(out) if s else None
    if not s or pk is None or step is None or not routed or not sparse:
        return None
    cfg = out["cell"].cfg
    need = costs_dsa.dsa_decode_step_bytes(
        cfg, sparse[0], sparse[1],
        routed[1] * moe_rounds.routed_layers(cfg), s["slots"])
    return 100.0 * need / pk["hbm_bytes_per_s"] / step
