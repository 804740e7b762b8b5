"""The one traffic generator: a mix is a data file of parameters.

A mix's sizes (prompt and output lengths, which requests are greedy) and
its inter-arrival gaps are drawn from the mix's own ``population_seed``,
so every run of a cell offers the same multiset of work; ``--seed``
draws the token ids and the sampler seeds and, unless the mix says
``"order": "fixed"``, orders the work (sizes and gaps are shuffled
apart). Runs with different seeds then differ by order at the most, not
by how much they were asked to do. A mix whose metric is a tail over some
tens of requests fixes the order too: which requests meet in the queue
decides such a tail, and no two orders of one Poisson schedule give the
same one (PERF.md section 6).

Mix keys (``benchmark/traffic/<mix>.json``):

    loop            "open" | "closed" | "steps"
    order           "seeded" (default) | "fixed": whether ``--seed`` or the
                    mix's own seed orders sizes and arrival gaps
    arrivals        {"process": "poisson" | "gamma", "rate_per_s", "cv"}
    clients, requests_per_client          (closed loop)
    prompt_tokens / output_tokens  {"dist": "lognormal" | "fixed" |
                    "uniform", "median", "sigma", "value", "min", "max"}
    max_total_tokens   prompt + output never pass it
    sampling        {"greedy_share", "temperature", "top_p", "top_k"}
    shared_prefix   {"tokens", "groups"}   optional: prompts open with one
                    of ``groups`` common prefixes (sent as ``prefix_len``)
    batch, seq_len  (steps: a training feed of uniform token ids)
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray
    max_new: int
    greedy: bool
    temperature: float
    top_p: float
    top_k: int
    sampler_seed: int
    prefix_len: int = 0
    due: float = 0.0           # seconds after the window opens (open loop)
    # stamped by the client while the window runs
    t_due: Optional[float] = None
    t_submit: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    handle: object = None


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        out = np.full(n, spec["value"], np.float64)
    elif dist == "uniform":
        out = rng.uniform(spec["min"], spec["max"], n)
    elif dist == "lognormal":
        out = np.exp(np.log(spec["median"])
                     + spec["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min", 1), spec.get("max", 2 ** 31)
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def population(mix: dict, n: int):
    """(prompt lengths, output lengths, greedy flags): the multiset every
    run of the mix offers, from the mix's own seed."""
    rng = np.random.default_rng(int(mix["population_seed"]))
    prompts = _lengths(mix["prompt_tokens"], n, rng)
    outs = _lengths(mix["output_tokens"], n, rng)
    total = int(mix.get("max_total_tokens", 2 ** 31))
    outs = np.maximum(1, np.minimum(outs, total - prompts))
    n_greedy = int(round(mix["sampling"]["greedy_share"] * n))
    greedy = np.zeros(n, bool)
    greedy[rng.permutation(n)[:n_greedy]] = True
    return prompts, outs, greedy


def arrival_gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` gaps of the arrival process, from the mix's own seed."""
    arr = mix["arrivals"]
    rng = np.random.default_rng(int(mix["population_seed"]) + 1)
    cv = 1.0 if arr["process"] == "poisson" else float(arr["cv"])
    if arr["process"] not in ("poisson", "gamma"):
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / (shape * arr["rate_per_s"]), n)


def n_requests(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    if mix["loop"] == "closed":
        return int(mix["clients"]) * int(mix["requests_per_client"])
    raise ValueError(f"mix loop {mix['loop']!r} has no requests")


def build_requests(mix: dict, seconds: float, vocab: int,
                   seed: int) -> List[Request]:
    n = n_requests(mix, seconds)
    prompts, outs, greedy = population(mix, n)
    rng = np.random.default_rng([int(seed) % 2 ** 32, int(seed) // 2 ** 32])
    how = mix.get("order", "seeded")
    if how not in ("seeded", "fixed"):
        raise ValueError(f"unknown order {how!r}")
    # the order's own generator: a fixed order leaves ``rng`` untouched
    order_rng = (rng if how == "seeded" else
                 np.random.default_rng(int(mix["population_seed"]) + 2))
    order = order_rng.permutation(n)
    prompts, outs, greedy = prompts[order], outs[order], greedy[order]
    smp = mix["sampling"]
    prefixes, pre = None, mix.get("shared_prefix")
    if pre:
        prefixes = rng.integers(0, vocab, (pre["groups"], pre["tokens"]))
    if mix["loop"] == "open":
        gaps = arrival_gaps(mix, n)[order_rng.permutation(n)]
        due = np.cumsum(gaps) * (seconds * n / (n + 1.0) / gaps.sum())
    else:
        due = np.zeros(n)
    reqs = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(prompts[i])).astype(np.int32)
        plen = 0
        if prefixes is not None and pre["tokens"] < ids.size:
            plen = pre["tokens"]
            ids[:plen] = prefixes[rng.integers(0, pre["groups"])]
        reqs.append(Request(
            index=i, prompt=ids, max_new=int(outs[i]),
            greedy=bool(greedy[i]),
            temperature=0.0 if greedy[i] else float(smp["temperature"]),
            top_p=1.0 if greedy[i] else float(smp["top_p"]),
            top_k=0 if greedy[i] else int(smp.get("top_k", 0)),
            sampler_seed=int(rng.integers(0, 2 ** 31 - 1)),
            prefix_len=plen, due=float(due[i])))
    return reqs


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """The token ids of one training step: rows that all differ."""
    rng = np.random.default_rng(
        [int(seed) % 2 ** 32, int(seed) // 2 ** 32, int(step)])
    return rng.integers(0, vocab, (int(mix["batch"]), int(mix["seq_len"])),
                        dtype=np.int32)


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation over all values."""
    return float(np.percentile(np.asarray(values, np.float64), q))
