"""The readers of the engine's round spans, on a hand-made collector."""

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
PER_STEP = ("engine_step_wall_ms", "engine_admit_ms_per_step",
            "engine_host_ms_per_step")


def _round(start, *, model="m", k=8, wait=0.0, admit=0.004, step=0.002,
           sync=0.240, emit=0.001):
    if not k:
        step = sync = emit = 0.0
    attrs = {"model": model, "k": k, "wait_s": wait, "admit_s": admit,
             "step_s": step, "sync_s": sync, "emit_s": emit}
    return obs_trace.Span(
        trace_id="t" * 32, span_id="s" * 16, parent_id="p" * 16,
        name="engine.round", start=start,
        end=start + wait + admit + step + sync + emit, attrs=attrs)


def _out(model="m"):
    return {"serve": {"t0": T0, "t_end": T_END},
            "cell": types.SimpleNamespace(cfg={"name": model})}


@pytest.fixture
def collector(monkeypatch):
    c = obs_trace.SpanCollector()
    monkeypatch.setattr(obs_trace, "DEFAULT_COLLECTOR", c)
    return c


def _read(name, out):
    return bench_run.load_reader(name)(out)


def test_window_cut_and_model_filter(collector):
    inside = [_round(T0 + 0.3 * i) for i in range(60)]
    for sp in inside:
        collector.record(sp)
    collector.record(_round(T0 - 0.5, admit=9.0))         # warm-up
    collector.record(_round(T_END, admit=9.0))            # the drain
    collector.record(_round(T0 + 1.0, model="other", admit=9.0))
    collector.record(obs_trace.Span(                      # another span
        trace_id="t" * 32, span_id="x" * 16, parent_id=None,
        name="engine.admit", start=T0 + 1.0, end=T0 + 2.0,
        attrs={"model": "m"}))
    assert engine_rounds.window_rounds(_out()) == inside
    assert _read("engine_admit_ms_per_step", _out()) == \
        pytest.approx(1e3 * 0.004 / 8)
    assert _read("engine_step_wall_ms", _out()) == \
        pytest.approx(1e3 * 0.242 / 8)
    assert _read("engine_host_ms_per_step", _out()) == \
        pytest.approx(1e3 * 0.001 / 8)
    assert _read("engine_admit_share_pct", _out()) == \
        pytest.approx(100 * 0.004 / 0.247)


@pytest.mark.parametrize("name", PER_STEP + ("engine_admit_share_pct",))
def test_nothing_to_read(collector, name):
    assert _read(name, _out()) is None                    # the parent
    for i in range(engine_rounds.MIN_ROUNDS - 1):
        collector.record(_round(T0 + 0.3 * i))
    assert _read(name, _out()) is None                    # too few
    assert _read(name, {"train": {}}) is None             # not a serve cell
    collector.record(_round(T0 + 30.0))
    assert _read(name, _out()) is not None


def test_per_step_metrics_sum_to_the_rounds_working_time(collector):
    """Over the rounds that stepped, the three per-step metrics add up to
    the rounds' time less their waiting, over the steps; a round of
    admission alone (k 0) counts in the share and in none of the three."""
    rounds = [_round(T0 + 0.3 * i, k=8 if i % 3 else 4, wait=0.01 * (i % 2),
                     admit=0.001 * i, emit=0.0005 * (i % 5))
              for i in range(70)]
    idle = [_round(T0 + 25.0 + i, k=0, admit=0.05) for i in range(5)]
    for sp in rounds + idle:
        collector.record(sp)
    steps = sum(r.attrs["k"] for r in rounds)
    working = sum(r.end - r.start - r.attrs["wait_s"] for r in rounds)
    assert sum(_read(n, _out()) for n in PER_STEP) == \
        pytest.approx(1e3 * working / steps)
    admit = sum(r.attrs["admit_s"] for r in rounds + idle)
    assert _read("engine_admit_share_pct", _out()) == \
        pytest.approx(100 * admit / (working + 5 * 0.05))
