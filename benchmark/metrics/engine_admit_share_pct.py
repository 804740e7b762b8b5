"""Share of the engine thread's working time (its rounds, less what
they spent blocked on an empty queue) in which the slots stand still for
admission and prefill."""

from benchmark.harness import engine_rounds


def read(out):
    rounds = engine_rounds.window_rounds(out)
    if rounds is None:
        return None
    working = sum(r.end - r.start - r.attrs["wait_s"] for r in rounds)
    if working <= 0:
        return None
    return 100.0 * sum(r.attrs["admit_s"] for r in rounds) / working
