"""CPU rehearsal of the hybrid decoder's driver at a tiny size (float32,
the KDA step kernel interpreted), its cost functions against hand counts
at the published widths, and its metric readers off the chip.

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
from benchmark.harness import costs_hybrid  # noqa: E402
from benchmark.tests import rehearse  # noqa: E402

CELL = "ling-3.0-flash-vl.long-answers"
TINY = "tiny-hybrid.tiny-long"


def tiny_spec() -> dict:
    """BENCHMARK.json with the hybrid cell replaced by its tiny twin."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny-hybrid",
                        "file": "benchmark/tests/data/configs/"
                                "tiny-hybrid.json"}]
    spec["workloads"] = [{"name": TINY, "config": "tiny-hybrid",
                          "traffic": "tiny-long", "chips": 1}]
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
                           "ling-3.0-flash-vl.json")) as f:
        return json.load(f)


def test_hybrid_serve_end_to_end():
    code, result = _run()
    assert code == 0
    assert result["correct"] is True, result["checked"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"serve_tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert result["device"]["platform"] == "cpu"


def test_hybrid_traced_reports_counters_only_and_control_fails():
    from benchmark.harness import check

    fp8 = check.load_reference("ling_hybrid_f32").fp8_operands
    # a window long enough for the 50 rounds the round readers ask for,
    # at 4 steps a round on the CPU
    code, result = _run(trace=1, control=fp8, seed=2 ** 31 + 11,
                        seconds=6.0)
    assert code == 0 and result["correct"] is True
    # 4 slots x 3 experts a token x half of the 16 experts held, over 8
    assert 0.3 < result["metrics"]["moe_pairs_per_held_expert"]["value"] < 1.5
    # the engine's own counters, which the dense serve cell reports too
    assert 0 < result["metrics"]["engine_slot_occupancy_pct"]["value"] <= 100
    # no chip: no share of a peak and no device time
    for name in ("hybrid_serve_mfu", "hybrid_decode_step_roofline",
                 "moe_experts_roofline", "kda_step_roofline",
                 "device_idle_pct.serve"):
        assert name not in result["metrics"]
    assert result["control"]["logit_gap_max"] > \
        result["checked"]["logit_gap_max"]["limit"]


def test_fitted_router_bias_evens_the_load():
    """The aux-loss-free fit on the tiny decoder: under the drawn bias
    the 16 experts' loads over the calibration tokens are far from even,
    under the fitted one each lies near the mean; both sides of the
    comparison are given the fitted one (``init_weights(router_bias=)``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import check
    from benchmark.harness import weights_hybrid as W

    with open(os.path.join(rehearse.DATA, "configs",
                           "tiny-hybrid.json")) as f:
        cfg = json.load(f)
    ref = check.load_reference(cfg["reference"])
    key = W.seed_key(2 ** 31 + 5)
    fitted = W.balanced_router_bias(cfg, key, jnp.float32, ref)
    assert fitted.shape == (2, cfg["num_experts_total"])
    a = cfg["assumed"]["router_balance"]
    toks = jax.random.randint(jax.random.fold_in(key, 2 ** 31 - 1),
                              (a["rows"], a["tokens"]), 0, cfg["vocab_size"])

    def spread(bias):
        w = W.init_weights(cfg, key, jnp.float32, bias)
        chosen = np.asarray(ref.forward(w, toks, cfg)[1])
        load = np.stack([np.bincount(layer.ravel(),
                                     minlength=cfg["num_experts_total"])
                         for layer in chosen])
        return float(np.max(load.std(-1) / load.mean(-1)))

    assert spread(None) > 0.3
    assert spread(fitted) < 0.1
    given = W.init_weights(cfg, key, jnp.float32, fitted)["router_bias"]
    assert np.array_equal(np.asarray(given), np.asarray(fitted))


def test_parameter_count_is_the_issue_s(published):
    parts = costs_hybrid.param_counts(published)
    assert round(sum(parts.values()) / 1e9, 2) == 5.23
    # KDA: six 2560 x 4096 projections, beta, conv taps, A_log, dt_bias,
    # the output norm
    kda = 6 * 2560 * 4096 + 2560 * 32 + 4 * 3 * 4096 + 32 + 4096 + 128
    assert parts["kda"] == 6 * kda
    assert parts["experts"] == 6 * 128 * 3 * 2560 * 768
    assert parts["embed_head"] == 2 * 39296 * 2560
    assert parts["dense_mlp"] == 3 * 2560 * 6144


def test_step_bytes_at_51_experts_hit(published):
    """By hand: 1.22 GB of weights outside the experts, 6 x 51 experts
    of 11.8 MB, 2 x 32 slots x 13.0 MB of KDA state and conv tail, 19200
    live positions of 1216 B: 5.7 GB, 6.9 ms at 819 GB/s."""
    need = costs_hybrid.hybrid_decode_step_bytes(published, 32 * 600,
                                                 6 * 51, 32)
    assert costs_hybrid.expert_bytes(published) == 3 * 2560 * 768 * 2
    assert costs_hybrid.latent_bytes_per_token(published) == 608 * 2
    assert costs_hybrid.state_bytes_per_slot(published) == \
        6 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert 5.6e9 < need < 5.8e9
    every = costs_hybrid.hybrid_decode_step_bytes(published, 32 * 600,
                                                  6 * 128, 32)
    assert 13.0 < 1e3 * every / 819e9 < 14.0


def test_flops_per_token_by_hand(published):
    got = costs_hybrid.hybrid_forward_flops_per_token(published, 0.0)
    d, c = 2560, 4096
    kda = 2 * (6 * d * c + d * 32) + 2 * 4 * 3 * c + 32 * 6 * 128 * 128
    mla = 2 * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32
               + 32 * 128 * d)
    routed = 2 * (d * 512 + 3 * d * 768 + 2.0 * 3 * d * 768)
    want = (6 * kda + mla + 6 * d * 6144 + 6 * routed + 2 * d * 39296)
    assert got == pytest.approx(want)
    # MLA attention: 32 heads x (192 + 128) x 2 a cached position
    more = costs_hybrid.hybrid_forward_flops_per_token(published, 100.0)
    assert more - got == pytest.approx(2 * 32 * 320 * 100)


def test_grouped_product_and_kda_step_costs(published):
    flops, nbytes = costs_hybrid.grouped_product_cost(published, 64, 50)
    assert flops == 2 * 64 * 2560 * 768
    assert nbytes == 50 * 2560 * 768 * 2 + 64 * (2560 + 768) * 2
    flops, nbytes = costs_hybrid.kda_step_cost(published, 32)
    assert nbytes == 2 * 32 * 32 * 128 * 128 * 4
    assert flops == 32 * 32 * 6 * 128 * 128


# -- the trace readers on event names as the chip's trace has them (PR 29) ------

class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


class _Span:
    def __init__(self, **attrs):
        self.attrs = attrs


def _traced_out(published, events, rounds, monkeypatch):
    from benchmark.harness import engine_rounds

    class Cell:
        cfg, devices = published, [_Dev()]

    class Tracing:
        reduced = {"op_events": events, "modules": [], "busy_s": 1.0,
                   "window_s": 1.0}

    monkeypatch.setattr(engine_rounds, "window_rounds", lambda out: rounds)
    return {"cell": Cell(), "trace": Tracing(),
            "serve": {"slots": 32, "steps_per_sync": 8}}


def test_kernel_rooflines_read_the_chip_s_event_names(published, monkeypatch):
    step = ("%kda.step.3 = (f32[6,32,32,128,128]{4,3,2,1,0:T(8,128)}, "
            "f32[32,32,128]{2,1,0:T(8,128)}) custom-call(f32[32,4,128,32] "
            "%copy.1)")
    dot = ("%ragged-dot-none.2 = f32[256,2560]{1,0:T(8,128)S(1)} "
           "custom-call(s32[1]{0:T(128)} %get-tuple-element.5966)")
    prefill_dot = dot.replace("f32[256,2560]", "f32[8192,2560]")
    # a step of 6 KDA calls at twice their least time, 18 grouped
    # products at four times theirs; a prefill's product is left out
    least_kda = 2 * 32 * 32 * 128 * 128 * 4 / 819e9
    least_dot = 40 * 2560 * 768 * 2 / 819e9 + 64 * 3328 * 2 / 819e9
    events = ([(step, 0, int(2e9 * least_kda))] * 6
              + [(dot, 0, int(4e9 * least_dot))] * 18
              + [(prefill_dot, 0, 10 ** 9)])
    rounds = [_Span(k=8, experts_hit=8 * 6 * 40, routed_pairs=8 * 6 * 64)]
    out = _traced_out(published, events, rounds, monkeypatch)
    assert bench_run.load_reader("kda_step_roofline")(out) == \
        pytest.approx(50.0, rel=1e-3)
    assert bench_run.load_reader("moe_experts_roofline")(out) == \
        pytest.approx(25.0, rel=1e-3)
    assert bench_run.load_reader("moe_pairs_per_held_expert")(out) == \
        pytest.approx(0.5)


def test_readers_return_none_where_the_program_counts_nothing(published,
                                                              monkeypatch):
    """The parent commit's rounds carry no ``experts_hit`` and its trace
    no ``kda.step``: every reader returns None and raises nothing."""
    rounds = [_Span(k=8)] * 60
    out = _traced_out(published, [("%fusion.1 = f32[8] fusion()", 0, 5)],
                      rounds, monkeypatch)
    for name in ("kda_step_roofline", "moe_experts_roofline",
                 "moe_pairs_per_held_expert",
                 "hybrid_decode_step_roofline"):
        assert bench_run.load_reader(name)(out) is None
