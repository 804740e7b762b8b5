"""The fused flash backward's reader on a hand-made list of traced
operations whose answer is known."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import common, peaks  # noqa: E402

MS = 1_000_000
T = "bf16[30,8192,64]{2,1,0:T(8,128)(2,1)}"
FWD = f"%attn._attend.30 = ({T}, f32[30,8192,1]{{2,1,0}}) custom-call("
DQ = f"%attn._attend.31 = {T} custom-call(bf16[30,8192,64] %x)"
DKV = f"%attn._attend.32 = ({T}, {T}) custom-call("
FUSED = f"%attn._attend.33 = ({T}, {T}, {T}) custom-call("
ELSEWHERE = f"%mlp.down.3 = ({T}, {T}, {T}) custom-call("
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def reader():
    return common.load_module(os.path.join(
        ROOT, "benchmark", "metrics", "flash_bwd_roofline.py"))


def _out(events, platform="tpu"):
    cell = types.SimpleNamespace(
        cfg={"num_attention_heads": 15, "head_dim": 64},
        devices=[types.SimpleNamespace(platform=platform,
                                       device_kind=KIND)])
    return {"cell": cell, "train": {"batch": 2, "seq_len": 8192},
            "trace": types.SimpleNamespace(reduced={"op_events": events})}


def test_calls_are_told_apart_by_what_they_return(reader):
    assert reader.is_fused_backward(FUSED)
    for other in (FWD, DQ, DKV, ELSEWHERE,
                  "%fusion.3 = bf16[2,8192,960] fusion("):
        assert not reader.is_fused_backward(other)


def test_fused_calls_count_five_products_and_seven_tensors(reader):
    pk = peaks.peaks_for(KIND)
    flops, nbytes = reader.cost(2, 15, 8192, 64)
    assert flops == 5 * 2 * 30 * 8192 ** 2 * 64 / 2
    assert nbytes == 7 * 30 * 8192 * 64 * 2
    least = max(flops / pk["bf16_flops_per_s"],
                nbytes / pk["hbm_bytes_per_s"])
    assert least == flops / pk["bf16_flops_per_s"]      # compute-bound
    # two fused calls of 8 and 9 ms; the forward, dQ and dK/dV calls of
    # a program that also ran them are the other reader's
    events = [(FWD, 0, 4 * MS), (FUSED, 5 * MS, 8 * MS),
              (DQ, 14 * MS, 5 * MS), (DKV, 20 * MS, 7 * MS),
              (FUSED, 30 * MS, 9 * MS), (ELSEWHERE, 40 * MS, 3 * MS)]
    got = reader.read(_out(events))
    assert got == pytest.approx(100.0 * 2 * least / 0.017)
    assert 30.0 < got < 52.0


@pytest.mark.parametrize("events", [
    [], [(FWD, 0, 4 * MS), (DQ, 5 * MS, 5 * MS), (DKV, 11 * MS, 7 * MS)]],
    ids=["nothing-traced", "the-parents-three-kernels"])
def test_reads_nothing_without_such_a_call(reader, events):
    assert reader.read(_out(events)) is None


def test_reads_nothing_off_the_chip(reader):
    assert reader.read(_out([(FUSED, 0, 8 * MS)], platform="cpu")) is None
    out = _out([(FUSED, 0, 8 * MS)])
    out["trace"] = None
    assert reader.read(out) is None
