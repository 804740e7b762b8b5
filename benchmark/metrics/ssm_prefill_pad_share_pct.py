"""What power-of-two prompt buckets cost a scan that runs every padded
chunk: of the tokens the chunk-wise state-space scan ran at the window's
admissions (``bucket`` on their ``engine.prefill`` spans: each prompt at
its bucket's width), the share that was the bucket's padding (``bucket``
less ``prompt_tokens``)."""

from benchmark.harness import ssm_rounds


def read(out):
    counted = ssm_rounds.scan_tokens(out)
    if counted is None or not counted[0]:
        return None
    return 100.0 * counted[1] / counted[0]
