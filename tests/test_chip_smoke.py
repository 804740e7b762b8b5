"""chip_smoke.py on CPU at toy width: the same parent code drives the
real launcher and the real server as children, the same checks read
their output — only the sizes differ, and ``main()`` (full width, TPU
only) must refuse this machine."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(
    d_model=64, n_layers=2, n_heads=4, d_ff=128, vocab=256, seq_len=64,
    train_steps=3, per_device_batch=2, prompt_lens=(3, 9, 20), max_new=4,
    flash_seq=256, flash_heads=2, masked_seq=128, masked_batch=2,
    masked_heads=2, page_size=16, slots=8, check_layers=2)


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """Children's logs and the export go under tmp_path, and the
    children run on one CPU device like a one-chip host."""
    monkeypatch.setattr(chip_smoke, "LOG_DIR", str(tmp_path / "logs"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    return tmp_path


def test_main_refuses_a_non_tpu_backend(scratch, capsys):
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert "FAILED phase device" in captured.err
    assert "'cpu', not 'tpu'" in captured.err
    # no result line of any kind
    assert '"ok"' not in captured.out


def test_train_export_serve_at_toy_width(scratch):
    train = chip_smoke.phase_train(TOY, str(scratch), dp=1)
    assert train["steps"] == TOY.train_steps
    assert train["tokens_per_step"] == 2 * 64
    assert os.path.isfile(scratch / "models" / "transformer" / "1"
                          / "params.npz")
    serve = chip_smoke.phase_serve(TOY, str(scratch))
    # the burst (greedy + sampled per length) plus the solo repeat pair
    assert serve["requests"] == 2 * len(TOY.prompt_lens) + 2
    assert serve["prefill_buckets"] == [4, 16, 32]
    # every returned token came out of the DecodeEngine
    assert serve["engine_tokens_total"] == serve["tokens_returned"] == 32
    assert serve["recoveries"] == 0 and serve["warmup_failures"] == 0


def test_serve_phase_fails_when_a_request_bypasses_the_engine(
        scratch, monkeypatch):
    """decode_slots=0 serves :generate on the unary bucketed path; the
    smoke must notice, not pass."""
    chip_smoke.phase_train(TOY, str(scratch))
    monkeypatch.setenv("KFTPU_DECODE_SLOTS", "0")
    with pytest.raises(chip_smoke.PhaseError, match="bypassed"):
        chip_smoke.phase_serve(TOY, str(scratch))


def test_a_failing_child_names_its_phase(scratch):
    with pytest.raises(chip_smoke.PhaseError, match="phase train: exit"):
        chip_smoke.run_child("train", ["-c", "raise SystemExit(7)"],
                             timeout=60)
    with pytest.raises(chip_smoke.PhaseError, match="timed out"):
        chip_smoke.run_child("serve", ["-c", "import time; time.sleep(30)"],
                             timeout=1)


def test_kernel_checks_against_their_references_at_toy_width():
    """The in-process checks of the kernels child, through the Pallas
    interpreter (the longcontext train step rides the slow tier,
    tests/test_bench_suite.py)."""
    causal = chip_smoke.check_flash(TOY, seq=TOY.flash_seq, batch=1,
                                    heads=TOY.flash_heads, causal=True,
                                    masked=False)
    assert causal["ok"], causal
    assert set(causal["tiles"]) == {"flash_fwd", "flash_bwd_fused"}
    masked = chip_smoke.check_flash(TOY, seq=TOY.masked_seq,
                                    batch=TOY.masked_batch,
                                    heads=TOY.masked_heads, causal=False,
                                    masked=True)
    assert masked["ok"], masked
    paged = chip_smoke.check_paged(TOY)
    assert paged["ok"], paged
    sampler = chip_smoke.check_sampler(TOY)
    assert sampler["ok"], sampler


def test_batch_and_row_prefill_programs_agree_at_toy_width():
    """The engine's two admission programs over the smoke's own prompts
    (tests/test_engine.py holds the token-identity of the two paths
    through the engine itself)."""
    res = chip_smoke.check_prefill_programs(TOY)
    assert res["ok"], res
    assert set(res["prompts"]) == {"3", "20"}  # smallest and largest
    first = res["prompts"]["3"]
    assert first["bucket"] == 4
    assert {"batch_vs_row_logits", "batch_vs_row_kv",
            "batch_vs_unpadded_logits", "decode_logits"} <= set(
                first["rel_err"])
    assert first["f32_rel_err"]["batch_vs_row_logits"] < 1e-4
    # a flip is only ever reported with the margin that explains it
    for p in res["prompts"].values():
        for flip in p["argmax_flips"]:
            assert flip["top2_gap"] <= 2 * flip["max_abs_logit_diff"]


def test_metrics_parser_and_request_mix():
    m = chip_smoke.parse_metrics(
        '# HELP x y\n# TYPE x counter\n'
        'kftpu_engine_tokens_total{model="transformer"} 176.0\n'
        'h_bucket{le="0.1"} 3 # {trace_id="ab"} 0.05\n'
        'plain 2\n')
    assert m == {'kftpu_engine_tokens_total{model="transformer"}': 176.0,
                 'h_bucket{le="0.1"}': 3.0, "plain": 2.0}
    reqs = chip_smoke.smoke_requests(chip_smoke.FULL)
    assert reqs == chip_smoke.smoke_requests(chip_smoke.FULL)  # seeded
    assert len(reqs) == 10 and "temperature" not in reqs[0]
    assert sum("temperature" in r for r in reqs) == 5
    assert {len(r["prompt_tokens"][0]) for r in reqs} == {5, 20, 70, 200,
                                                           600}
    # the full-width model is the bench LM, never cut
    f = chip_smoke.FULL
    assert (f.d_model, f.n_layers, f.n_heads, f.d_ff, f.vocab) == (
        1024, 8, 16, 4096, 32000)
