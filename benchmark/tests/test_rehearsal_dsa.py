"""CPU rehearsal of DeepSeek-V3.2's driver at a tiny size (float32, the
three sparse-attention kernels interpreted): the warm start, the
comparison and its two rehearsed faults, the cost functions against hand
counts at the published widths, and the metric readers on event names as
the chip's trace has them.

No number printed here is a device number (``platform`` is ``cpu``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import check, costs_dsa  # noqa: E402
from benchmark.harness.drivers import serve_dsa  # noqa: E402
from benchmark.tests import rehearse  # noqa: E402

CELL = "deepseek-v3.2.long-context-reasoning"
TINY = "tiny-dsa.tiny-reasoning"


def tiny_spec() -> dict:
    """BENCHMARK.json with the cell replaced by its tiny twin."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny-dsa",
                        "file": "benchmark/tests/data/configs/tiny-dsa.json"}]
    spec["workloads"] = [{"name": TINY, "config": "tiny-dsa",
                          "traffic": "tiny-reasoning", "chips": 1}]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [TINY] if CELL in m["workloads"] else []
    return spec


def _run(trace=0, control=None, seed=3, seconds=2.0):
    args = argparse.Namespace(workload=TINY, seed=seed, seconds=seconds,
                              trace=trace)
    return bench_run.run_cell(args, require_chip=False, control=control,
                              spec=tiny_spec(), data_root=rehearse.DATA)


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3.2.json")) as f:
        return json.load(f)


def test_dsa_serve_end_to_end_with_warm_start(monkeypatch):
    seen = {}
    run = serve_dsa.run

    def spy(cell):
        seen["out"] = run(cell)
        return seen["out"]

    monkeypatch.setattr(serve_dsa, "run", spy)
    code, result = _run()
    assert code == 0
    assert result["correct"] is True, result["checked"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"serve_tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert result["device"]["platform"] == "cpu"
    s = seen["out"]["serve"]
    # the loop began before the window: every slot's first request was
    # submitted, and had delivered a token, before t0
    early = [r for r in s["requests"] if r.t_submit < s["t0"]]
    assert len(early) >= 8 and sum(
        r.t_first is not None and r.t_first <= s["t0"] for r in early) >= 4
    # tokens stamped before the window opened are not in the rate
    before = sum(1 for r in s["requests"] for t in r.stamps if t <= s["t0"])
    assert before > 0
    assert s["tokens_in_window"] == sum(
        1 for r in s["requests"] for t in r.stamps
        if s["t0"] < t <= s["t_end"])
    assert s["programs_built_in_window"] == 0


def test_dsa_traced_reports_counters_and_all_three_variants_fail(
        monkeypatch):
    """A traced run with the control and the two rehearsed faults beside
    the program: the fp8 control, dense attention (the selection ignored)
    and the YaRN scale left out each pass one of the cell's limits."""
    seen = {}
    run = serve_dsa.run

    def spy(cell):
        seen["out"] = run(cell)
        return seen["out"]

    monkeypatch.setattr(serve_dsa, "run", spy)
    ref = check.load_reference("deepseek_v32_f32")
    variants = {"control": {"cast": ref.fp8_operands},
                "dense": {"fault": "dense_attention"},
                "noyarn": {"fault": "no_yarn_scale"}}
    # a window long enough for the 50 rounds the round readers ask for
    code, result = _run(trace=1, control=variants, seed=2 ** 31 + 11,
                        seconds=6.0)
    assert code == 0 and result["correct"] is True, result["checked"]
    assert 0 < result["metrics"]["dsa_selected_share_pct"]["value"] < 100
    assert 0 < result["metrics"]["engine_slot_occupancy_pct"]["value"] <= 100
    # no chip: no share of a peak and no device time
    for name in ("dsa_serve_mfu", "dsa_decode_step_roofline",
                 "dsa_index_roofline", "dsa_select_ms_per_step",
                 "device_idle_pct.serve"):
        assert name not in result["metrics"]
    numbers, limits = seen["out"]["numbers"], result["checked"]
    assert 0 < numbers["selection_flip_share"] < 0.5
    for name in variants:
        over = [n for n in ("logit_gap_max", "logit_gap_mean")
                if numbers[f"{name}_{n}"] > limits[n]["limit"]]
        assert over, (name, numbers)
    assert result["control"]["logit_gap_max"] == \
        numbers["control_logit_gap_max"]


def test_parameter_count_is_the_issue_s(published):
    parts = costs_dsa.param_counts(published)
    assert round(sum(parts.values()) / 1e9, 2) == 4.64
    mla = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
           + 16384 * 7168)
    assert parts["mla"] == 5 * mla and round(mla / 1e6, 1) == 187.1
    assert parts["indexer"] == 5 * (1536 * 64 * 128 + 7168 * 128 + 7168 * 64)
    assert parts["experts"] == 4 * 16 * 3 * 7168 * 2048
    assert parts["dense_mlp"] == 3 * 7168 * 18432
    assert parts["embed_head"] == 2 * 16160 * 7168
    assert round(costs_dsa.non_expert_weight_bytes(published) / 1e9, 2) \
        == 3.42
    assert costs_dsa.cache_bytes_per_token(published) == 5 * (576 + 128) * 2


def test_step_bytes_and_flops_by_hand(published):
    """16 rows at 18000 positions, 2048 kept, 6 experts hit a layer: 3.42
    GB of weights outside the experts, 24 experts of 88 MB, 288000 live
    positions of 1280 B of index keys, 32768 kept rows of 5760 B."""
    need = costs_dsa.dsa_decode_step_bytes(published, 16 * 18000.0,
                                           16 * 2048.0, 4 * 6.0, 16)
    want = (costs_dsa.non_expert_weight_bytes(published) + 24 * 88080384
            + 288000 * 1280 + 32768 * 5760 + 16 * 7168 * 2)
    assert need == pytest.approx(want)
    assert 6.0 < 1e3 * need / 819e9 < 8.0
    base = costs_dsa.dsa_forward_flops_per_token(published, 0.0)
    more = costs_dsa.dsa_forward_flops_per_token(published, 10000.0)
    # a cached position: 64 index heads x 128 x 2 scored; of 10000
    # positions 2048 attended by 128 heads x (192 + 128) x 2; 5 layers
    assert more - base == pytest.approx(
        5 * (2 * 64 * 128 * 10000 + 2 * 128 * 320 * 2048))
    few = costs_dsa.dsa_forward_flops_per_token(published, 100.0)
    assert few - base == pytest.approx(
        5 * (2 * 64 * 128 * 100 + 2 * 128 * 320 * 100))
    assert costs_dsa.dsa_prefill_flops(published, 3) == pytest.approx(
        sum(costs_dsa.dsa_forward_flops_per_token(published, float(c), False)
            for c in (1, 2, 3)) + 2 * 7168 * 16160)
    flops, nbytes = costs_dsa.index_scores_cost(published, 16, 288000.0,
                                                288000.0)
    assert flops == 2 * 64 * 128 * 288000
    assert nbytes == 288000 * 256 + 288000 * 4 + 16 * 64 * 260


# -- the trace readers on event names as the chip's trace has them ---------------

class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


class _Span:
    def __init__(self, **attrs):
        self.attrs = attrs


def _traced_out(published, events, modules, rounds, monkeypatch):
    from benchmark.harness import engine_rounds

    class Cell:
        cfg, devices = published, [_Dev()]

    class Tracing:
        reduced = {"op_events": events, "modules": modules, "busy_s": 1.0,
                   "window_s": 1.0}

    monkeypatch.setattr(engine_rounds, "window_rounds", lambda out: rounds)
    return {"cell": Cell(), "trace": Tracing(),
            "serve": {"slots": 16, "steps_per_sync": 8, "t0": 0.0,
                      "t_end": 40.0, "requests": []}}


def test_the_five_readers_read_the_chip_s_event_names(published,
                                                      monkeypatch):
    index = ("%dsa.index.35 = f32[16,1,32768]{2,1,0:T(1,128)S(1)} "
             "custom-call(%get-tuple-element.4029, %pad_maximum_fusion.10)")
    select = ("%dsa.select.35 = f32[16,32768]{1,0:T(8,128)S(1)} "
              "custom-call(%copy_bitcast_fusion.21)")
    chunk_index = index.replace("f32[16,1,32768]", "f32[1,1024,32768]")
    chunk_select = select.replace("f32[16,32768]", "f32[1024,32768]")
    scored, kept = 16 * 18000, 16 * 2048
    flops, nbytes = costs_dsa.index_scores_cost(published, 16, scored,
                                                scored)
    least = max(flops / 197e12, nbytes / 819e9)
    # one traced round of 8 steps: 40 index calls at four times their
    # least time, 40 select calls of 0.3 ms; an admission's calls left out
    events = ([(index, 0, int(4e9 * least))] * 40
              + [(select, 0, 300_000)] * 40
              + [(chunk_index, 0, 10 ** 9), (chunk_select, 0, 10 ** 9)])
    need = costs_dsa.dsa_decode_step_bytes(published, scored, kept, 4 * 6.0,
                                           16)
    step_ns = int(2e9 * need / 819e9)           # half the HBM peak
    modules = [("jit__step(123)", 0, 8 * step_ns)]
    rounds = [_Span(k=8, experts_hit=8 * 4 * 6, routed_pairs=8 * 4 * 8,
                    index_scored=8 * 5 * scored, index_selected=8 * 5 * kept)]
    out = _traced_out(published, events, modules, rounds, monkeypatch)
    read = lambda name: bench_run.load_reader(name)(out)  # noqa: E731
    assert read("dsa_index_roofline") == pytest.approx(25.0, rel=1e-3)
    assert read("dsa_select_ms_per_step") == pytest.approx(1.5, rel=1e-6)
    assert read("dsa_decode_step_roofline") == pytest.approx(50.0, rel=1e-3)
    assert read("dsa_selected_share_pct") == pytest.approx(
        100 * 2048 / 18000)
    # no request decoded in this canned window: no operations
    assert read("dsa_serve_mfu") == 0.0


def test_readers_return_none_where_the_program_counts_nothing(published,
                                                              monkeypatch):
    """A program whose rounds carry no ``index_scored`` and whose trace
    has no ``dsa.*`` call: every reader that needs them returns None and
    raises nothing."""
    rounds = [_Span(k=8)] * 60
    out = _traced_out(published, [("%fusion.1 = f32[8] fusion()", 0, 5)],
                      [], rounds, monkeypatch)
    for name in ("dsa_index_roofline", "dsa_select_ms_per_step",
                 "dsa_decode_step_roofline", "dsa_selected_share_pct"):
        assert bench_run.load_reader(name)(out) is None
