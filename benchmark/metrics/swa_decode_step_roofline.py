"""MiMo-V2-Flash's decode step against the HBM roofline: the bytes one
step has to move (the non-expert weights once, the held experts that the
step's rows actually hit, the global layers' live row blocks and the
window layers' live ring slots; the last three from the engine rounds'
counters) over the chip's bandwidth, over the traced device time of a
step (benchmark/harness/costs_swa.py)."""

from benchmark.harness import costs_swa, readers, swa_rounds


def read(out):
    s, pk = out.get("serve"), readers.chip_peaks(out)
    step = readers.decode_step_s(out) if s else None
    routed = swa_rounds.per_layer_step(out) if s else None
    read_kv = swa_rounds.attention_positions(out) if s else None
    if not s or pk is None or step is None or not routed or not read_kv:
        return None
    cfg = out["cell"].cfg
    need = costs_swa.swa_decode_step_bytes(
        cfg, read_kv[0], read_kv[1],
        routed[1] * costs_swa.layer_counts(cfg)[3], s["slots"])
    return 100.0 * need / pk["hbm_bytes_per_s"] / step
