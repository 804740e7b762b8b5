"""The reader of the rounds launched a round ahead, on a hand-made
collector."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import engine_rounds  # noqa: E402
from kubeflow_tpu.obs import trace as obs_trace  # noqa: E402

T0, T_END = 100.0, 140.0


def _round(start, *, k=8, **extra):
    return obs_trace.Span(
        trace_id="t" * 32, span_id="s" * 16, parent_id="p" * 16,
        name="engine.round", start=start, end=start + 0.25,
        attrs={"model": "m", "k": k, **extra})


def _out():
    return {"serve": {"t0": T0, "t_end": T_END},
            "cell": types.SimpleNamespace(cfg={"name": "m"})}


@pytest.fixture
def collector(monkeypatch):
    c = obs_trace.SpanCollector()
    monkeypatch.setattr(obs_trace, "DEFAULT_COLLECTOR", c)
    return c


def _read(out):
    return bench_run.load_reader("engine_rounds_ahead_pct")(out)


def test_share_of_the_rounds_that_stepped(collector):
    """Rounds that read no tokens (k 0: admission alone) are out of the
    base; the share is Σ ahead over the rest."""
    for i in range(60):
        collector.record(_round(T0 + 0.3 * i, ahead=i % 4 != 0))
    for i in range(10):
        collector.record(_round(T0 + 20.0 + i, k=0, ahead=False))
    collector.record(_round(T0 - 1.0, ahead=False))       # warm-up
    assert _read(_out()) == pytest.approx(100.0 * 45 / 60)


def test_a_program_without_the_attribute_reads_none(collector):
    """The parent's rounds carry no ``ahead``: nothing is read and
    nothing raises; too few rounds read nothing either."""
    assert _read(_out()) is None
    for i in range(engine_rounds.MIN_ROUNDS):
        collector.record(_round(T0 + 0.3 * i))
    assert _read(_out()) is None
    assert _read({"train": {}}) is None
