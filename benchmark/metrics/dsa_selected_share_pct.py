"""How sparse the traffic made the attention: cached positions kept over
cached positions scored, summed over the rows, layers and decode steps of
the window's rounds (the engine rounds' ``index_selected`` and
``index_scored``). 100 while every row is shorter than ``index_topk``."""

from benchmark.harness import dsa_rounds


def read(out):
    counted = dsa_rounds.per_layer_step(out)
    if counted is None or counted[0] <= 0:
        return None
    return 100.0 * counted[1] / counted[0]
