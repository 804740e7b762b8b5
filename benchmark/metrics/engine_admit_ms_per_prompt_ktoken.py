"""Admission's time over its work: ms of the window's admissions for a
thousand of the requests' own prompt tokens that the prefill programs
ran, comparable across buckets and batch sizes."""

from benchmark.harness import admissions


def read(out):
    found = admissions.window_admissions(out)
    if found is None:
        return None
    tokens = admissions.total(found, "prompt_tokens")
    if not tokens:
        return None
    return 1e3 * admissions.seconds(found) / (tokens / 1e3)
