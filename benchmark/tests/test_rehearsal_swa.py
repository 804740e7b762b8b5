"""CPU rehearsal of MiMo-V2-Flash's driver at a tiny size (float32; the
step attention kernel interpreted where a test steers it there): the warm
start, the comparison and its control and rehearsed faults, the cost
functions against hand counts at the published widths, and the metric
readers on event names as the chip's trace has them.

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
from benchmark.harness import check, costs_swa  # noqa: E402
from benchmark.harness.drivers import serve_swa  # noqa: E402
from benchmark.tests import rehearse  # noqa: E402

CELL = "mimo-v2-flash.long-context-agents"
TINY = "tiny-swa.tiny-agents"
NEW = ("swa_serve_mfu", "swa_decode_step_roofline", "swa_attend_roofline")


def tiny_spec() -> dict:
    """BENCHMARK.json with the cell replaced by its tiny twin."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny-swa",
                        "file": "benchmark/tests/data/configs/tiny-swa.json"}]
    spec["workloads"] = [{"name": TINY, "config": "tiny-swa",
                          "traffic": "tiny-agents", "chips": 1}]
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
                           "mimo-v2-flash.json")) as f:
        return json.load(f)


def _spied(monkeypatch) -> dict:
    seen = {}
    run = serve_swa.run

    def spy(cell):
        seen["out"] = run(cell)
        return seen["out"]

    monkeypatch.setattr(serve_swa, "run", spy)
    return seen


def test_swa_serve_end_to_end_with_warm_start(monkeypatch):
    seen = _spied(monkeypatch)
    code, result = _run()
    assert code == 0
    assert result["correct"] is True, result["checked"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"serve_tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert result["device"]["platform"] == "cpu"
    s = seen["out"]["serve"]
    early = [r for r in s["requests"] if r.t_submit < s["t0"]]
    assert len(early) >= 8 and sum(
        r.t_first is not None and r.t_first <= s["t0"] for r in early) >= 4
    assert s["tokens_in_window"] == sum(
        1 for r in s["requests"] for t in r.stamps
        if s["t0"] < t <= s["t_end"])
    assert s["programs_built_in_window"] == 0


def test_swa_traced_counts_the_kernel_and_the_variants_fail(monkeypatch):
    """A traced run through the interpreted step kernel, with the fp8
    control and the sinks left out beside the program: the rounds carry
    the window and global counters, and each variant passes a limit."""
    from kubeflow_tpu.ops import step_attention

    monkeypatch.setattr(step_attention, "use_kernel", lambda: True)
    seen = _spied(monkeypatch)
    ref = check.load_reference("mimo_v2_flash_f32")
    variants = {"control": {"cast": ref.fp8_operands}}
    code, result = _run(trace=1, control=variants, seed=2 ** 31 + 11,
                        seconds=6.0)
    assert code == 0 and result["correct"] is True, result["checked"]
    numbers, limits = seen["out"]["numbers"], result["checked"]
    for name in ("control", "no_sinks"):
        over = [n for n in ("logit_gap_max", "logit_gap_mean")
                if numbers[f"{name}_{n}"] > limits[n]["limit"]]
        assert over, (name, numbers)
    out = dict(seen["out"], cell=seen["out"]["cell"])
    from benchmark.harness import swa_rounds

    global_read, window_read = swa_rounds.attention_positions(out)
    # 4 rows: a ring row reads at most the window of 8
    assert 0 < window_read <= 4 * 8 and global_read >= window_read
    # no chip: no share of a peak
    for name in NEW + ("device_idle_pct.serve",):
        assert name not in result["metrics"]


def test_parameter_count_is_the_issue_s(published):
    parts = costs_swa.param_counts(published)
    router_bias = 6 * 256
    assert sum(parts.values()) - router_bias == 3_429_953_856
    assert parts["attn_global"] == 2 * 89_128_960
    assert parts["attn_window"] == 5 * 94_371_840 and parts["sinks"] == 5 * 64
    assert parts["experts"] == 6 * 16 * 25_165_824
    assert parts["dense_mlp"] == 201_326_592
    assert parts["embed_head"] == 156_237_824
    whole = dict(published, **published["published"])
    whole["n_routed_experts_total"] = whole["n_routed_experts"]
    assert round(costs_swa.params_total(whole) / 1e9, 2) == 308.78


def test_step_bytes_and_flops_by_hand(published):
    """32 rows at 12288 positions, 10 experts hit a routed layer: the
    non-expert weights, 60 experts of 50 MB, two global layers' rows of
    2560 B a position, five rings of 4096 B a slot."""
    need = costs_swa.swa_decode_step_bytes(published, 32 * 12288.0,
                                           32 * 128.0, 60.0, 32)
    want = (costs_swa.non_expert_weight_bytes(published)
            + 60 * 3 * 4096 * 2048 * 2 + 2 * 32 * 12288 * 4 * 320 * 2
            + 5 * 32 * 128 * 8 * 320 * 2 + 32 * 4096 * 2)
    assert need == pytest.approx(want)
    assert 8.0 < 1e3 * need / 819e9 < 10.0
    base = costs_swa.swa_forward_flops_per_token(published, 1.0)
    more = costs_swa.swa_forward_flops_per_token(published, 1001.0)
    # 1000 more positions: the two global layers read them all, the
    # window layers 127 more (the window is full past 128)
    assert more - base == pytest.approx(2 * 64 * 320 * (2 * 1000 + 5 * 127))
    flops, nbytes = costs_swa.step_attend_cost(published, 1000.0, 100.0, 4)
    assert flops == 2 * 64 * 320 * (2 * 1000 + 5 * 100)
    assert nbytes == (2 * 1000 * 4 * 320 * 2 + 5 * 100 * 8 * 320 * 2
                      + 7 * 4 * 64 * 320 * 2)


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
            "serve": {"slots": 32, "steps_per_sync": 8, "t0": 0.0,
                      "t_end": 40.0, "requests": []}}


def test_the_readers_read_the_chip_s_event_names(published, monkeypatch):
    call = ("%step_attention.3 = bf16[32,64,128]{2,1,0:T(8,128)(2,1)} "
            "custom-call(%constant.13, %pos.1, %fusion.2, %reduce_max.7)")
    # a step: 32 rows at 12288 positions, blocks of 256; rings full
    blocks, reserved = 32 * 48, 32 * 128
    flops, nbytes = costs_swa.step_attend_cost(
        published, blocks * 256.0, 32 * 128.0, 32)
    least = max(flops / 197e12, nbytes / 819e9)
    # one traced round of 8 steps: 7 calls a step at four times their
    # share of the least time
    events = [(call, 0, int(4e9 * least / 7))] * 56
    need = costs_swa.swa_decode_step_bytes(published, blocks * 256.0,
                                           32 * 128.0, 6 * 10.0, 32)
    step_ns = int(2e9 * need / 819e9)           # half the HBM peak
    modules = [("jit__step(77)", 0, 8 * step_ns)]
    rounds = [_Span(k=8, experts_hit=8 * 6 * 10, routed_pairs=8 * 6 * 32,
                    kv_blocks_read=8 * blocks, kv_blocks_reserved=8 * reserved,
                    window_positions_read=8 * 32 * 128)]
    out = _traced_out(published, events, modules, rounds, monkeypatch)
    read = lambda name: bench_run.load_reader(name)(out)  # noqa: E731
    assert read("swa_attend_roofline") == pytest.approx(25.0, rel=1e-3)
    assert read("swa_decode_step_roofline") == pytest.approx(50.0, rel=1e-3)
    # no request decoded in this canned window: no operations
    assert read("swa_serve_mfu") == 0.0


def test_readers_return_none_where_the_program_counts_nothing(published,
                                                              monkeypatch):
    """The parent's program: rounds with no window counter and no kernel
    call of this cell's in the trace. The readers return None and raise
    nothing."""
    rounds = [_Span(k=8, experts_hit=1, routed_pairs=1)] * 60
    out = _traced_out(published, [("%fusion.1 = f32[8] fusion()", 0, 5)],
                      [("jit__step(1)", 0, 10)], rounds, monkeypatch)
    for name in NEW[1:]:
        assert bench_run.load_reader(name)(out) is None
