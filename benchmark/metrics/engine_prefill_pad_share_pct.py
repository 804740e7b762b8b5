"""Of the tokens the window's prefill programs scanned (``rows_padded``
x ``width`` an admission), the share that was no request's own: a
batch's pad ROWS and every row's padding to its bucket or chunk, both
(``ssm_prefill_pad_share_pct`` counts the second alone, from the
requests' own spans)."""

from benchmark.harness import admissions


def read(out):
    found = admissions.window_admissions(out)
    if found is None:
        return None
    scanned = admissions.total(found, "scanned_tokens")
    if not scanned:
        return None
    return (100.0 * (scanned - admissions.total(found, "prompt_tokens"))
            / scanned)
