"""Device time of one decode step: the traced executions of the engine's
K-step scan programs, over K."""

from benchmark.harness import readers


def read(out):
    if not out.get("serve"):
        return None
    step = readers.decode_step_s(out)
    return None if step is None else 1e3 * step
