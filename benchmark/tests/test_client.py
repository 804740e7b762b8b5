"""Traffic is a data file read by one generator: draws repeat from the
seed, and every seed offers the same multiset of work."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import client  # noqa: E402


def _mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _sizes(reqs):
    return sorted((r.prompt.size, r.max_new, r.greedy) for r in reqs)


def test_open_loop_repeats_and_permutes():
    mix = dict(_mix("chat-steady"), order="seeded")
    a = client.build_requests(mix, 30, 49152, 2 ** 31 + 9)
    b = client.build_requests(mix, 30, 49152, 2 ** 31 + 9)
    c = client.build_requests(mix, 30, 49152, 12345)
    assert len(a) == round(mix["arrivals"]["rate_per_s"] * 30)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               and x.sampler_seed == y.sampler_seed for x, y in zip(a, b))
    assert _sizes(a) == _sizes(c)                     # same work
    assert [r.prompt.size for r in a] != [r.prompt.size for r in c]
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due for r in rs]), 9))  # noqa: E731
    assert gaps(a) == gaps(c)                         # same arrivals
    assert 0.0 < a[0].due and a[-1].due < 30.0
    for r in a:
        assert 32 <= r.prompt.size <= 768 and 1 <= r.max_new <= 256
        assert r.prompt.size + r.max_new <= 1024
        assert (r.temperature == 0.0) == r.greedy
    share = sum(r.greedy for r in a) / len(a)
    assert abs(share - mix["sampling"]["greedy_share"]) < 1.0 / len(a)


def test_fixed_order_leaves_the_seed_the_tokens_only():
    mix = dict(_mix("chat-steady"), order="fixed")
    a = client.build_requests(mix, 30, 49152, 2 ** 31 + 9)
    c = client.build_requests(mix, 30, 49152, 12345)
    assert [(r.prompt.size, r.max_new, r.greedy, r.due) for r in a] == \
        [(r.prompt.size, r.max_new, r.greedy, r.due) for r in c]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    seeded = client.build_requests(dict(mix, order="seeded"), 30, 49152, 12345)
    assert _sizes(seeded) == _sizes(c)


def test_closed_loop_population():
    mix = _mix("offline-batch")
    a = client.build_requests(mix, 30, 49152, 1)
    assert len(a) == mix["clients"] * mix["requests_per_client"]
    assert all(r.due == 0.0 for r in a)
    assert _sizes(a) == _sizes(client.build_requests(mix, 5, 49152, 2))


def test_gamma_arrivals_and_shared_prefix_are_data():
    mix = dict(_mix("chat-steady"))
    mix["arrivals"] = {"process": "gamma", "rate_per_s": 10.0, "cv": 2.5}
    mix["shared_prefix"] = {"tokens": 16, "groups": 2}
    reqs = client.build_requests(mix, 30, 49152, 5)
    gaps = np.diff([r.due for r in reqs])
    assert gaps.std() / gaps.mean() > 1.5             # burstier than Poisson
    heads = {tuple(r.prompt[:16]) for r in reqs}
    assert len(heads) == 2 and all(r.prefix_len == 16 for r in reqs)


def test_training_rows_all_differ():
    mix = _mix("pretrain-8k")
    a = client.train_batch(mix, 49152, 2 ** 31 + 1, 0)
    assert a.shape == (2, 8192) and a.dtype == np.int32
    assert (a == client.train_batch(mix, 49152, 2 ** 31 + 1, 0)).all()
    assert (a[0] != a[1]).any()
    assert (a != client.train_batch(mix, 49152, 2 ** 31 + 1, 1)).any()
