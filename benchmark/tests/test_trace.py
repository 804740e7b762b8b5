"""The trace reduction on plain data: a hand-made trace whose answers are
known, and a small trace recorded on the chip (PR 26)."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import trace  # noqa: E402

MS = 1_000_000


def _planes():
    ops = [("while.1", 0, 10 * MS), ("fusion.2", 1 * MS, 3 * MS),
           ("flash_fwd", 5 * MS, 4 * MS), ("copy.3", 20 * MS, 5 * MS)]
    mods = [("jit__step(123)", 0, 10 * MS), ("jit__insert(9)", 20 * MS, 5 * MS)]
    host = [("engine.prefill", 9 * MS, 12 * MS), ("noise", 11 * MS, 1 * MS)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "engine", "events": host}]},
    ]


def test_busy_self_time_and_gaps():
    red = trace.reduce(_planes(), window_s=0.030)
    assert abs(red["busy_s"] - 0.015) < 1e-12          # 0-10 and 20-25 ms
    assert red["window_s"] == 0.030
    ops = dict(red["device_ops"])
    assert abs(ops["while.1"] - 0.003) < 1e-12         # 10 - 3 - 4 ms
    assert abs(ops["flash_fwd"] - 0.004) < 1e-12
    assert red["idle_gaps"] == [["engine.prefill", 0.010]]
    assert [m[0] for m in red["modules"]] == ["jit__step(123)",
                                              "jit__insert(9)"]


def test_no_device_plane_reads_nothing():
    assert trace.reduce(_planes()[1:]) is None


def test_recorded_chip_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small_trace.json")
    with open(path) as f:
        rec = json.load(f)
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [tuple(e) for e in ln["events"]]}
        for ln in p["lines"]]} for p in rec["planes"]]
    red = trace.reduce(planes, rec["window_s"], 1)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-9
    assert red["device_ops"][0][0] == rec["expect"]["top_op"]
    assert len(red["modules"]) == rec["expect"]["modules"]


def test_flash_calls_are_told_apart_in_the_recorded_trace():
    from benchmark.harness import common

    mod = common.load_module(
        os.path.join(ROOT, "benchmark", "metrics", "flash_roofline.py"))
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "small_trace.json")) as f:
        rec = json.load(f)
    names = [e[0] for p in rec["planes"] for ln in p["lines"]
             if ln["name"] == "XLA Ops" for e in ln["events"]]
    kinds = {mod.kind_of(n) for n in names} - {None}
    assert "fwd" in kinds
    assert mod.kind_of("%attn._attend.44 = bf16[30,8192,64]{2,1,0} "
                       "custom-call(bf16[30,8192,64] %x)") == "dq"
    assert mod.kind_of("%attn._attend.45 = (bf16[30,8192,64]{2,1,0}, "
                       "bf16[30,8192,64]{2,1,0}) custom-call(") == "dkv"
    assert mod.kind_of("%fusion.3 = bf16[2,8192,960] fusion(") is None
    assert trace.short_name(names[0]).count(" ") <= 2
