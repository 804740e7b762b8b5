"""How long each output token of the running rows waits for other
requests' admission on the host's clock: queue pop, prefill, first-token
readback, insert enqueued (``admit_s`` of the window's rounds that
stepped), over the steps made. The insert program is not waited for: its
device time shows in ``engine_step_wall_ms``."""

from benchmark.harness import engine_rounds


def read(out):
    return engine_rounds.ms_per_step(out, "admit_s")
