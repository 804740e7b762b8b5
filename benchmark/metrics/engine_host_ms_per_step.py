"""The emit loop and the finish bookkeeping (``emit_s`` of the window's
rounds that stepped), over the steps made: the part of a token's time in
which the chip has nothing queued."""

from benchmark.harness import engine_rounds


def read(out):
    return engine_rounds.ms_per_step(out, "emit_s")
