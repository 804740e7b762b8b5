"""Share of the window's rounds that stepped whose K steps the engine
launched before it read the round before them (``ahead`` on each
``engine.round``; ``kftpu_engine_rounds_ahead_total`` counts the same):
how often the device had the next round queued while the host read,
emitted and admitted. A program whose rounds carry no ``ahead`` (any
commit before the engine ran a round ahead) reads as None."""

from benchmark.harness import engine_rounds


def read(out):
    rounds = engine_rounds.window_rounds(out)
    if rounds is None:
        return None
    stepped = [r for r in rounds if r.attrs["k"] > 0]
    if not stepped or "ahead" not in stepped[0].attrs:
        return None
    return 100.0 * sum(bool(r.attrs["ahead"]) for r in stepped) / len(stepped)
