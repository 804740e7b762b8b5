"""The load on the held experts: routed (token, expert) pairs that fell
on experts held here, a decode step a routed block, over the experts held
(from the ``routed_pairs`` of the window's ``engine.round`` spans, over
the pattern's ``E`` blocks). 128 rows x 6 experts a token over 128
experts is 6 if routing is even; a deployment whose four chips each
decode 128 rows of their own would send 24."""

from benchmark.harness import ssm_rounds


def read(out):
    counted = ssm_rounds.per_block_step(out)
    if counted is None:
        return None
    return counted[0] / out["cell"].cfg["n_routed_experts"]
