"""CPU rehearsal of Nemotron-3-Nano's driver at a tiny size (float32, the
state-space step kernel interpreted): the warm start, the comparison and
its two rehearsed faults, the cost functions against hand counts at the
published widths, and the metric readers on event names as the chip's
trace has them.

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
from benchmark.harness import costs_ssm  # noqa: E402
from benchmark.harness.drivers import serve_ssm  # noqa: E402
from benchmark.tests import limits_probe_ssm, rehearse  # noqa: E402

CELL = "nemotron-3-nano-30b-a3b.many-rows-reasoning"
TINY = "tiny-ssm.tiny-many-rows"
NEW = ("ssm_serve_mfu", "ssm_decode_step_roofline", "ssm_step_roofline",
       "ssm_experts_roofline", "ssm_pairs_per_held_expert",
       "ssm_prefill_pad_share_pct")


def tiny_spec() -> dict:
    """BENCHMARK.json with the cell replaced by its tiny twin."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny-ssm",
                        "file": "benchmark/tests/data/configs/tiny-ssm.json"}]
    spec["workloads"] = [{"name": TINY, "config": "tiny-ssm",
                          "traffic": "tiny-many-rows", "chips": 1}]
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
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def _spied(monkeypatch) -> dict:
    seen = {}
    run = serve_ssm.run

    def spy(cell):
        seen["out"] = run(cell)
        return seen["out"]

    monkeypatch.setattr(serve_ssm, "run", spy)
    return seen


def test_ssm_serve_end_to_end_with_warm_start(monkeypatch):
    seen = _spied(monkeypatch)
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
    assert len(early) >= 12 and sum(
        r.t_first is not None and r.t_first <= s["t0"] for r in early) >= 4
    # tokens stamped before the window opened are not in the rate
    assert sum(1 for r in s["requests"] for t in r.stamps
               if t <= s["t0"]) > 0
    assert s["tokens_in_window"] == sum(
        1 for r in s["requests"] for t in r.stamps
        if s["t0"] < t <= s["t_end"])
    assert s["programs_built_in_window"] == 0


def test_ssm_traced_reports_counters_and_all_three_variants_fail(
        monkeypatch):
    """A traced run with the control and the two rehearsed faults beside
    the program: the fp8 control, the conv tail dropped at the hand-over
    and the gated norm ungrouped each pass one of the cell's limits."""
    seen = _spied(monkeypatch)
    variants = limits_probe_ssm.variants()
    # a window long enough for the 50 rounds the round readers ask for
    code, result = _run(trace=1, control=variants, seed=2 ** 31 + 11,
                        seconds=6.0)
    assert code == 0 and result["correct"] is True, result["checked"]
    # 4 rows x 3 experts a token, half of the 16 experts held
    assert 0 < result["metrics"]["ssm_pairs_per_held_expert"]["value"] < 1.5
    assert 0 < result["metrics"]["ssm_prefill_pad_share_pct"]["value"] < 50
    assert 0 < result["metrics"]["engine_slot_occupancy_pct"]["value"] <= 100
    # no chip: no share of a peak and no device time
    for name in ("ssm_serve_mfu", "ssm_decode_step_roofline",
                 "ssm_step_roofline", "ssm_experts_roofline",
                 "device_idle_pct.serve"):
        assert name not in result["metrics"]
    numbers, limits = seen["out"]["numbers"], result["checked"]
    assert 0 <= numbers["routing_flip_share"] < 0.5
    for name in variants:
        over = [n for n in ("logit_gap_max", "logit_gap_mean",
                            "handover_gap_mean")
                if numbers[f"{name}_{n}"] > limits[n]["limit"]]
        assert over, (name, numbers)
    # the conv tail's loss shows where it happens: right after the
    # hand-over, and an order above what it does to the mean of all gaps
    assert numbers["notail_handover_gap_mean"] > max(
        limits["handover_gap_mean"]["limit"],
        3 * numbers["notail_logit_gap_mean"])
    assert result["control"]["logit_gap_max"] == \
        numbers["control_logit_gap_max"]


def test_parameter_count_is_the_issue_s(published):
    parts = costs_ssm.param_counts(published)
    assert sum(parts.values()) == 3_249_672_576
    assert parts["ssm"] == 8 * 38_744_896
    assert parts["attn"] == 2 * 23_399_040
    assert parts["experts"] == 8 * 32 * 9_977_856
    assert (parts["router"] + parts["shared"] + parts["moe_norms"]
            == 8 * 20_302_592)
    assert parts["embed_head"] == 2 * 32768 * 2688
    whole = dict(published, **published["published"])
    del whole["n_routed_experts_total"]
    assert round(costs_ssm.params_total(whole) / 1e9, 2) == 31.58
    assert round(costs_ssm.non_expert_weight_bytes(published) / 1e9, 2) \
        == 1.22
    assert costs_ssm.state_bytes_per_slot(published) == 8 * (2_097_152
                                                             + 36_864)
    assert costs_ssm.kv_bytes_per_token(published) == 2 * 1024


def test_step_bytes_and_flops_by_hand(published):
    """128 rows at 1500 positions, 31 experts hit a block: 1.22 GB of
    weights outside the experts, 248 experts of 20 MB, 128 slots of 17 MB
    of state read and written, 192000 live positions of 2048 B."""
    need = costs_ssm.decode_step_bytes(published, 128 * 1500.0, 8 * 31.0,
                                       128)
    want = (costs_ssm.non_expert_weight_bytes(published)
            + 248 * 2 * 2688 * 1856 * 2 + 2 * 128 * 8 * 2_134_016
            + 192000 * 2048 + 128 * 2688 * 2)
    assert need == pytest.approx(want)
    assert 12.0 < 1e3 * need / 819e9 < 14.0
    base = costs_ssm.forward_flops_per_token(published, 0.0)
    more = costs_ssm.forward_flops_per_token(published, 1000.0)
    # a cached position: 32 heads x 128 x 2 products x 2, two blocks
    assert more - base == pytest.approx(2 * 4 * 32 * 128 * 1000)
    ssm = 2 * (2688 * 10304 + 4096 * 2688) + 2 * 4 * 6144 + 64 * 5 * 64 * 128
    attn = 2 * (2 * 2688 * 4096 + 2 * 2688 * 256)
    moe = 2 * (2688 * 128 + 2 * 2688 * 3712 + 1.5 * 2 * 2688 * 1856)
    assert base == pytest.approx(8 * ssm + 2 * attn + 8 * moe
                                 + 2 * 2688 * 32768)
    flops, nbytes = costs_ssm.ssm_step_cost(published, 128)
    assert nbytes == 2 * 128 * 2_097_152 and flops == 128 * 64 * 5 * 8192
    flops, nbytes = costs_ssm.grouped_product_cost(published, 192.0, 31.0)
    assert flops == 2 * 192 * 2688 * 1856
    assert nbytes == 31 * 2688 * 1856 * 2 + 192 * (2688 + 1856) * 2


# -- the trace readers on event names as the chip's trace has them ---------------

class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


class _Span:
    def __init__(self, **attrs):
        self.attrs = attrs


def _traced_out(published, events, modules, rounds, monkeypatch, scan=None):
    from benchmark.harness import engine_rounds, ssm_rounds

    class Cell:
        cfg, devices = published, [_Dev()]

    class Tracing:
        reduced = {"op_events": events, "modules": modules, "busy_s": 1.0,
                   "window_s": 1.0}

    monkeypatch.setattr(engine_rounds, "window_rounds", lambda out: rounds)
    monkeypatch.setattr(ssm_rounds, "scan_tokens", lambda out: scan)
    return {"cell": Cell(), "trace": Tracing(),
            "serve": {"slots": 128, "steps_per_sync": 8, "t0": 0.0,
                      "t_end": 40.0, "requests": []}}


def test_the_six_readers_read_the_chip_s_event_names(published, monkeypatch):
    step = ("%ssm.step.35 = (f32[8,128,32,128,128]{4,3,2,1,0:T(8,128)}, "
            "f32[128,32,128]{2,1,0:T(8,128)}) custom-call(%fusion.1, "
            "%fusion.2, %fusion.3, %get-tuple-element.40)")
    product = ("%ragged-dot-none.7 = f32[768,2048]{1,0:T(8,128)S(1)} "
               "custom-call(%get-tuple-element.1, %copy-done.3)")
    prefill_product = product.replace("f32[768,2048]", "f32[49152,2048]")
    flops, nbytes = costs_ssm.ssm_step_cost(published, 128)
    least_step = max(flops / 197e12, nbytes / 819e9)
    pairs, hit = 192.0, 31.0
    flops, nbytes = costs_ssm.grouped_product_cost(published, pairs, hit)
    least_product = max(flops / 197e12, nbytes / 819e9)
    # one traced round of 8 steps: 64 step calls at twice their least
    # time, 128 grouped products at four times; an admission's left out
    events = ([(step, 0, int(2e9 * least_step))] * 64
              + [(product, 0, int(4e9 * least_product))] * 128
              + [(prefill_product, 0, 10 ** 9)])
    need = costs_ssm.decode_step_bytes(published, 0.0, 8 * hit, 128)
    step_ns = int(2e9 * need / 819e9)           # half the HBM peak
    modules = [("jit__step(123)", 0, 8 * step_ns)]
    rounds = [_Span(k=8, experts_hit=int(8 * 8 * hit),
                    routed_pairs=int(8 * 8 * pairs))]
    out = _traced_out(published, events, modules, rounds, monkeypatch,
                      scan=(4096, 1024))
    read = lambda name: bench_run.load_reader(name)(out)  # noqa: E731
    assert read("ssm_step_roofline") == pytest.approx(50.0, rel=1e-3)
    assert read("ssm_experts_roofline") == pytest.approx(25.0, rel=1e-3)
    assert read("ssm_decode_step_roofline") == pytest.approx(50.0, rel=1e-3)
    assert read("ssm_pairs_per_held_expert") == pytest.approx(6.0)
    assert read("ssm_prefill_pad_share_pct") == pytest.approx(25.0)
    # no request decoded in this canned window: no operations
    assert read("ssm_serve_mfu") == 0.0


def test_readers_return_none_where_the_program_counts_nothing(published,
                                                              monkeypatch):
    """A program whose rounds carry no ``routed_pairs``, whose window
    holds no admission and whose trace has no ``ssm.step`` call:
    every reader that needs them returns None and raises nothing."""
    rounds = [_Span(k=8)] * 60
    out = _traced_out(published, [("%fusion.1 = f32[8] fusion()", 0, 5)],
                      [], rounds, monkeypatch)
    for name in NEW[1:]:
        assert bench_run.load_reader(name)(out) is None
