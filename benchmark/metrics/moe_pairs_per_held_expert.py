"""The load on the held experts: routed (token, expert) pairs that fell
on experts held here, a decode step a routed layer, over the experts
held (from the ``routed_pairs`` of the window's ``engine.round`` spans).
32 rows x 8 experts a token over 512 experts is 0.5 if routing is even; a
deployment whose four chips each decode 32 rows of their own would send
2."""

from benchmark.harness import moe_rounds


def read(out):
    counted = moe_rounds.per_layer_step(out)
    if counted is None:
        return None
    return counted[0] / out["cell"].cfg["num_experts"]
