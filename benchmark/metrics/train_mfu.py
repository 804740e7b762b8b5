"""The whole training step's share of the chip's bf16 peak: tokens per
second x (6 N + 6 L S d) over the peak; recomputation is not counted."""

from benchmark.harness import readers


def read(out):
    t, pk = out.get("train"), readers.chip_peaks(out)
    if not t or pk is None:
        return None
    per_token = readers.cost_fn(out, "flops_per_token")(
        out["cell"].cfg, t["seq_len"])
    rate = out["values"]["train_tokens_per_s"]
    return 100.0 * rate * per_token / (pk["bf16_flops_per_s"]
                                       * len(out["cell"].devices))
