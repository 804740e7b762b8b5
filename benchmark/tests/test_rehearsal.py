"""CPU rehearsals of whole runs at a tiny size (kernels interpreted): each
driver end to end, the faults that ``correct`` has to catch, the control,
and a mesh read from a configuration file on four virtual devices.

These skip the harness's look for a chip and drive the rest of a run; no
number they print is a device number (``platform`` is ``cpu``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests import rehearse  # noqa: E402


def _ok(result, names):
    assert result["correct"] is True, result["checked"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(names) <= set(result["metrics"])
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checked"


def test_serve_open_loop_end_to_end():
    code, result = rehearse.run("tiny-serve.tiny-open")
    assert code == 0
    _ok(result, ["tpot_p80_ms", "setup_s"])
    assert "serve_tokens_per_s" not in result["metrics"]


def test_serve_closed_loop_traced_reports_counters_only():
    code, result = rehearse.run("tiny-serve.tiny-closed", trace=1)
    assert code == 0
    _ok(result, ["engine_slot_occupancy_pct"])
    # no chip: no share of a peak, no device time, no idle share
    for name in ("serve_mfu", "decode_step_roofline", "device_idle_pct.serve"):
        assert name not in result["metrics"]
    assert "busy_s" not in result["device"]


def test_train_end_to_end():
    code, result = rehearse.run("tiny-train.tiny-steps")
    assert code == 0
    _ok(result, ["train_tokens_per_s", "setup_s"])


def test_altered_token_is_not_correct(monkeypatch):
    from kubeflow_tpu.serving.engine import DecodeEngine

    real = DecodeEngine._emit

    def emit(self, slot, token, t):
        return real(self, slot, (token + 1) % self.config.vocab_size, t)

    monkeypatch.setattr(DecodeEngine, "_emit", emit)
    _code, result = rehearse.run("tiny-serve.tiny-open")
    assert result["correct"] is False
    assert result["checked"]["logit_gap_max"]["value"] > 0.1


def _patched_step(monkeypatch, wrap):
    import kubeflow_tpu.train.trainer as trainer

    make = trainer.make_lm_train_step

    def faulty(mesh, *a, **kw):
        kw["donate"] = False
        return wrap(make(mesh, *a, **kw))

    monkeypatch.setattr(trainer, "make_lm_train_step", faulty)


def test_unchanged_state_is_not_correct(monkeypatch):
    _patched_step(monkeypatch,
                  lambda step: lambda s, t: (s, step(s, t)[1]))
    _code, result = rehearse.run("tiny-train.tiny-steps")
    assert result["correct"] is False
    assert result["checked"]["first_grad_leaf_gap"]["value"] > 0.9
    assert result["checked"]["change_leaf_gap"]["value"] > 0.9


def test_half_batch_is_not_correct(monkeypatch):
    _patched_step(monkeypatch,
                  lambda step: lambda s, t: step(s, t[: t.shape[0] // 2]))
    _code, result = rehearse.run("tiny-train.tiny-steps")
    assert result["correct"] is False


def test_controls_are_not_correct():
    from benchmark.harness import check

    fp8 = check.load_reference().fp8_operands
    _code, served = rehearse.run("tiny-serve.tiny-open", control=fp8)
    assert served["correct"] is True         # the program itself is sound
    assert served["control"]["logit_gap_max"] > \
        served["checked"]["logit_gap_max"]["limit"]
    _code, trained = rehearse.run("tiny-train.tiny-steps", control=fp8)
    assert trained["correct"] is True
    assert any(trained["control"][n] > spec["limit"]
               for n, spec in trained["checked"].items())
    assert any(trained["halfbatch"][n] > spec["limit"]
               for n, spec in trained["checked"].items())


def _child(args, env_extra, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_mesh_comes_from_the_configuration_file():
    """{"tp": 4} is data: the same driver on four virtual devices."""
    out = _child(["benchmark/tests/rehearse.py", "tiny-train-tp4.tiny-steps"],
                 {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["device"]["count"] == 4


def test_refuses_to_run_without_a_tpu():
    out = _child(["benchmark/run.py", "--workload",
                  "smollm2-1.7b.offline-batch", "--seed", "1", "--seconds",
                  "1", "--trace", "0"], {}, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("name", ["BENCHMARK.json"])
def test_spec_names_files_that_exist(name):
    with open(os.path.join(ROOT, name)) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        for sub, n in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", sub,
                                               f"{n}.json"))
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           f"{m['name']}.py"))
