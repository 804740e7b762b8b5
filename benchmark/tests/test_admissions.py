"""The readers of the engine's admission spans: on a hand-made collector
(the window's cut, the model filter, None where nothing is held), and in
the four serve rehearsals, where the program itself records the spans:
the three metrics come out as numbers on the CPU (counts and host
seconds, no device number)."""

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
from benchmark.harness import admissions  # noqa: E402
from benchmark.tests import (  # noqa: E402
    rehearse,
    test_rehearsal_dsa,
    test_rehearsal_hybrid,
    test_rehearsal_ssm,
)
from kubeflow_tpu.obs import trace as obs_trace  # noqa: E402

T0, T_END = 100.0, 140.0
NAMES = ("engine_admit_host_share_pct", "engine_admit_ms_per_prompt_ktoken",
         "engine_prefill_pad_share_pct")


def _admission(start, *, model="m", host=0.002, launch=0.003, read=0.010,
               insert=0.001, rows=1, rows_padded=1, width=8,
               prompt_tokens=5):
    attrs = {"model": model, "kind": "batch" if rows > 1 else "row",
             "rows": rows, "rows_padded": rows_padded, "width": width,
             "prompt_tokens": prompt_tokens,
             "scanned_tokens": rows_padded * width, "host_s": host,
             "launch_s": launch, "read_s": read, "insert_s": insert}
    return obs_trace.Span(
        trace_id="t" * 32, span_id="s" * 16, parent_id="p" * 16,
        name="engine.admission", start=start,
        end=start + host + launch + read + insert, attrs=attrs)


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
    # a row of 5 tokens in the bucket of 8, and a burst of 3 (18 tokens)
    # that ran as 4 rows of 8
    inside = [_admission(T0 + 1.0),
              _admission(T0 + 2.0, rows=3, rows_padded=4, prompt_tokens=18,
                         host=0.004, launch=0.004, read=0.020, insert=0.002)]
    for sp in inside:
        collector.record(sp)
    collector.record(_admission(T0 - 0.5, read=9.0))          # warm-up
    collector.record(_admission(T_END, read=9.0))             # the drain
    collector.record(_admission(T0 + 1.0, model="other", read=9.0))
    collector.record(obs_trace.Span(                          # another span
        trace_id="t" * 32, span_id="x" * 16, parent_id=None,
        name="engine.admit", start=T0 + 1.0, end=T0 + 2.0,
        attrs={"model": "m"}))
    assert admissions.window_admissions(_out()) == inside
    busy = 0.016 + 0.030
    assert _read("engine_admit_host_share_pct", _out()) == \
        pytest.approx(100 * (0.006 + 0.010) / busy)
    assert _read("engine_admit_ms_per_prompt_ktoken", _out()) == \
        pytest.approx(1e3 * busy / (23 / 1e3))
    # 8 + 32 scanned, 5 + 18 the requests' own: the pad row counts
    assert _read("engine_prefill_pad_share_pct", _out()) == \
        pytest.approx(100 * (40 - 23) / 40)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(collector, name):
    assert _read(name, _out()) is None                    # the parent
    assert _read(name, {"train": {}}) is None             # not a serve cell
    collector.record(_admission(T0 - 1.0))                # before the window
    collector.record(_admission(T0 + 1.0, model="other"))
    assert _read(name, _out()) is None
    collector.record(_admission(T0 + 30.0))               # one is enough
    assert _read(name, _out()) is not None


def _numbers(result, ssm=False):
    """The three metrics of a traced rehearsal, each a number in its
    range."""
    assert result["failed"] == 0 and result["attempted"] > 0
    got = {n: result["metrics"][n]["value"] for n in NAMES}
    assert 0 < got["engine_admit_host_share_pct"] < 100
    assert got["engine_admit_ms_per_prompt_ktoken"] > 0
    assert 0 <= got["engine_prefill_pad_share_pct"] < 100
    return got


def test_dense_rehearsal_reports_the_three():
    code, result = rehearse.run("tiny-serve.tiny-closed", trace=1)
    assert code == 0
    _numbers(result)


def test_hybrid_rehearsal_reports_the_three():
    code, result = test_rehearsal_hybrid._run(trace=1)
    assert code == 0
    _numbers(result)


def test_dsa_rehearsal_reports_the_three():
    code, result = test_rehearsal_dsa._run(trace=1)
    assert code == 0
    _numbers(result)


def test_ssm_rehearsal_counts_pad_rows_above_the_buckets_padding():
    code, result = test_rehearsal_ssm._run(trace=1)
    assert code == 0
    got = _numbers(result)
    # the same admissions from the requests' own spans: the buckets'
    # padding alone, so never above the count that has the pad rows too
    assert got["engine_prefill_pad_share_pct"] >= \
        result["metrics"]["ssm_prefill_pad_share_pct"]["value"]
