"""A decode step as the engine's thread sees it: launching the K steps
of a round and then waiting for them and reading their tokens
(``step_s`` + ``sync_s`` of the window's rounds), over the steps made.
Less ``decode_step_ms`` (device time) it is the launch and readback a
step costs, and the device work that admission enqueued before it."""

from benchmark.harness import engine_rounds


def read(out):
    return engine_rounds.ms_per_step(out, "step_s", "sync_s")
