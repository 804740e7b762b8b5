"""BASELINE.md bench suite: structure, error isolation, and the light
configs end-to-end on the virtual CPU mesh (the heavy resnet/bert configs
run on the real chip via bench.py)."""

import jax
import pytest

from kubeflow_tpu.bench import suite


def test_mnist_config_learns():
    out = suite.bench_mnist(steps=8, batch=64)
    assert out["learned"], out
    assert out["images_per_sec"] > 0


def test_allreduce_config_on_virtual_mesh():
    out = suite.bench_allreduce(size_mb=0.5, iters=2)
    assert out["n_chips"] == jax.device_count()
    if jax.device_count() >= 2:
        assert out["bus_gb_per_sec"] > 0
    else:
        assert "skipped" in out


def test_virtual_mesh_allreduce_subprocess():
    out = suite._virtual_mesh_allreduce(size_mb=0.25, iters=2, n_devices=4)
    assert out is not None and "error" not in out, out
    assert out["bus_gb_per_sec"] > 0
    assert out["n_devices"] == 4


def test_serving_config_reports_latency():
    # 128² keeps the JSON payload multi-MB, so binary-beats-JSON is
    # structural (parse cost), not scheduler noise — a 64² batch-2 run
    # flaked under full-suite load. 3 requests make p50 a true median
    # (one scheduler hiccup cannot flip a 2-sample comparison), and a
    # single re-measure guards the comparative assertion against a
    # CPU-steal burst landing on one transport's window.
    kw = dict(requests=3, batch=2, image_size=128, rest_requests=3)
    out = suite.bench_serving(**kw)
    assert out["transport"] == "grpc"
    assert out["p50_ms"] > 0
    assert out["p99_ms"] >= out["p50_ms"]
    assert out["qps_per_chip"] > 0
    assert out["rest_p50_ms"] > 0
    assert out["uint8_p50_ms"] > 0
    if out["p50_ms"] > out["rest_p50_ms"]:
        out = suite.bench_serving(**kw)
    # binary tensors beat multi-MB JSON text round-trips
    assert out["p50_ms"] <= out["rest_p50_ms"]


def test_run_all_isolates_failures(monkeypatch):
    def boom():
        raise RuntimeError("kaput")

    monkeypatch.setitem(suite.CONFIGS, "resnet50", boom)
    monkeypatch.setitem(suite.CONFIGS, "bert", boom)
    monkeypatch.setitem(suite.CONFIGS, "serving", boom)
    out = suite.run_all(only=["mnist", "resnet50"])
    assert "error" in out["resnet50"]
    assert out["mnist"]["images_per_sec"] > 0
    assert "bert" not in out  # respected the subset


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


def test_peak_flops_detection(monkeypatch):
    # CPU devices → 0.0 (MFU meaningless), never a crash; and no run
    # may assert its own peak any more
    monkeypatch.setenv("KFTPU_PEAK_TFLOPS", "123.5")
    assert suite.peak_flops_per_chip() == 0.0
    # the string this repo's v5e reports (chip run, CHANGES.md PR 21)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("TPU v5 lite")])
    assert suite.peak_flops_per_chip() == 197.0e12
    assert suite._by_device_kind(suite._HBM_GBPS) == 819.0


def test_unknown_tpu_device_kind_is_an_error(monkeypatch):
    """A TPU the peaks table does not know must stop the row, not
    produce MFU-less (or zero-peak) numbers in silence."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("TPU v9 mystery")])
    with pytest.raises(LookupError, match="v9 mystery"):
        suite.peak_flops_per_chip()
    with pytest.raises(LookupError):
        suite._mfu(1e12, 1.0, 1)


def test_mfu_math():
    assert suite._mfu(None, 1.0, 1) == {}
    out = suite._mfu(12.33e9 * 256, 1.0, 1)
    assert out == {}  # CPU: no peak → no MFU claimed


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_decode_engine_config_tiny():
    # tiny model: the CPU tier checks the continuous-batching path end to
    # end (prefill/insert/chunked step/drain); the chip checks the speed
    out = suite.bench_decode_engine(concurrency=3, slots=2, prompt_len=8,
                                    new_tokens=8, steps_per_sync=4,
                                    d_model=32, n_layers=2, n_heads=2,
                                    d_ff=64)
    assert out["tokens_per_sec_per_chip"] > 0
    assert out["effective_batch"] == 2
    assert out["engine_steps"] > 0
    # ISSUE 6 comparisons ride the same suite: paged-vs-dense and
    # fused-vs-exact-sort both produce numbers on the CPU tier
    assert out["paged_tokens_per_sec_per_chip"] > 0
    assert out["sampled_exact_fused_tokens_per_sec_per_chip"] > 0
    assert out["sampled_exact_sort_tokens_per_sec_per_chip"] > 0
    # ISSUE 7: the gather-vs-kernel A/B and the prefix-trie/COW
    # counters land in the same artifact (CPU tier proves the paths;
    # the TPU round adjudicates the kernel)
    assert out["paged_attn_gather_tokens_per_sec_per_chip"] > 0
    assert out["paged_attn_kernel_tokens_per_sec_per_chip"] > 0
    assert out["paged_attn_kernel_vs_gather"] > 0
    assert (out["paged_prefix_hits"] + out["paged_prefix_misses"]
            == out["concurrency"])
    assert out["paged_prefix_hits"] >= 1
    assert out["paged_cow_splits"] >= 1
    assert out["paged_prefix_pages_shared"] >= out["paged_prefix_hits"]


# the configs no other test here executes, at the tiny shapes the
# deleted run_cpu_smoke tier used: that tier only ever ran beside a chip
# round (bench.py), so this is its CPU coverage kept as a test
_TINY_ARGS = {
    "resnet50": {"batch_per_chip": 2, "steps": 2, "warmup": 1},
    "bert": {"batch_per_chip": 1, "seq_len": 128, "steps": 2, "warmup": 1},
    "decode": {"batch": 2, "prompt_len": 16, "new_tokens": 8,
               "d_model": 128, "n_layers": 2, "n_heads": 4, "d_ff": 256},
    "edge_fleet": {"replicas": 3, "prefixes": 2, "repeats": 4,
                   "page_size": 4, "burst": 12},
}


@pytest.mark.slow  # whole-model XLA compiles on CPU
@pytest.mark.parametrize("name", sorted(_TINY_ARGS))
def test_config_runs_at_tiny_shapes_on_cpu(name):
    row = suite.CONFIGS[name](**_TINY_ARGS[name])
    assert "error" not in row
    rates = [v for k, v in row.items()
             if k.endswith(("_per_sec", "_per_sec_per_chip", "_hit_rate"))]
    assert rates and all(v > 0 for v in rates), row


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_longcontext_config_on_virtual_mesh():
    # tiny model: the CPU tier checks the path, the chip checks the speed
    out = suite.bench_longcontext(seq_len=512, batch_per_chip=1, steps=2,
                                  warmup=1, d_model=64, n_layers=2,
                                  n_heads=4, d_ff=128)
    assert out["tokens_per_sec_per_chip"] > 0
    assert out["attention"] == "flash(pallas)+remat"
    assert out["seq_len"] == 512


def _fake_suite_children(monkeypatch, tmp_path, body):
    """Stand-in for ``python -m kubeflow_tpu.bench.suite <config>``:
    every child run_all_isolated starts runs ``body`` instead."""
    import subprocess as _sp
    import sys

    fake = tmp_path / "fake_suite.py"
    fake.write_text(body)
    real_run = _sp.run

    def fake_run(cmd, **kw):
        name = cmd[cmd.index("kubeflow_tpu.bench.suite") + 1]
        return real_run([sys.executable, str(fake), name], **kw)

    monkeypatch.setattr(_sp, "run", fake_run)


def test_run_all_isolated_survives_hung_config(monkeypatch, tmp_path):
    """A config that never returns times out to an error row, and the
    configs after it still run (plain timeout; no probing, no skipping
    of the rest)."""
    _fake_suite_children(
        monkeypatch, tmp_path,
        "import sys, time, json\n"
        "name = sys.argv[1]\n"
        "if name == 'mnist':\n"
        "    time.sleep(60)\n"
        "print(json.dumps({name: {'images_per_sec': 1.0}}))\n")
    out = suite.run_all_isolated(only=["mnist", "resnet50", "bert"],
                                 timeout_s=3.0)
    assert out["mnist"] == {"error": "timeout after 3s"}
    assert out["resnet50"] == {"images_per_sec": 1.0}
    assert out["bert"] == {"images_per_sec": 1.0}


def test_run_all_isolated_keeps_rows_of_a_failed_child(monkeypatch,
                                                       tmp_path):
    """``suite.main`` exits non-zero when a config raised, but it
    printed its rows first — the parent keeps them, error and all."""
    _fake_suite_children(
        monkeypatch, tmp_path,
        "import sys, json\n"
        "print(json.dumps({sys.argv[1]: {'error': 'RuntimeError: kaput'}}))\n"
        "sys.exit('bench configs raised')\n")
    out = suite.run_all_isolated(only=["mnist"], timeout_s=30.0)
    assert out == {"mnist": {"error": "RuntimeError: kaput"}}


def test_suite_main_exits_nonzero_when_a_config_raised(monkeypatch,
                                                       capsys):
    import json as _json

    def boom():
        raise RuntimeError("kaput")

    monkeypatch.setitem(suite.CONFIGS, "resnet50", boom)
    monkeypatch.setattr("sys.argv", ["suite", "mnist", "resnet50"])
    monkeypatch.setitem(suite.CONFIGS, "mnist",
                        lambda: {"images_per_sec": 5.0})
    with pytest.raises(SystemExit) as e:
        suite.main()
    assert e.value.code not in (0, None)
    assert "resnet50" in str(e.value.code)
    rows = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "kaput" in rows["resnet50"]["error"]
    # every good row says what ran it
    assert rows["mnist"]["platform"] == "cpu"
    assert rows["mnist"]["device_kind"] == jax.devices()[0].device_kind
    # a clean run returns normally
    monkeypatch.setattr("sys.argv", ["suite", "mnist"])
    suite.main()


def test_suite_import_touches_no_device():
    """bench.py's parent imports the suite and then starts children
    that need the chip: the import must not initialize a backend."""
    import subprocess
    import sys

    prog = ("import kubeflow_tpu.bench.suite\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    proc = subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=120,
                          cwd=suite._REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-500:]


def _run_bench(monkeypatch, capsys, rows):
    import json as _json

    import bench

    monkeypatch.setattr(suite, "run_all_isolated", lambda **kw: dict(rows))
    monkeypatch.setattr("sys.argv", ["bench.py"])
    code = 0
    try:
        bench.main()
    except SystemExit as e:
        code = e.code
    line = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return code, line


TPU_ROW = {"platform": "tpu", "device_kind": "TPU v5 lite"}


def test_bench_exits_nonzero_on_a_dead_headline(monkeypatch, capsys):
    """A headline that died on the chip is a failed round — whatever
    else passed. No CPU tier stands in for it any more: CPU rows are a
    test, not part of a chip artifact."""
    code, line = _run_bench(monkeypatch, capsys, {
        "mnist": {"images_per_sec": 5.0, **TPU_ROW},
        "resnet50": {"error": "timeout after 900s"}})
    assert code == 1
    assert line["value"] == 0.0
    assert line["errored"] == ["resnet50"]
    assert "cpu_smoke" not in line and "tier" not in line
    assert not hasattr(suite, "run_cpu_smoke")


def test_bench_exits_nonzero_on_a_non_tpu_row(monkeypatch, capsys):
    code, line = _run_bench(monkeypatch, capsys, {
        "mnist": {"images_per_sec": 5.0, "platform": "cpu",
                  "device_kind": "cpu"},
        "resnet50": {"images_per_sec_per_chip": 100.0, **TPU_ROW}})
    assert code == 1
    assert line["not_on_tpu"] == ["mnist"] and line["errored"] == []


def test_bench_clean_chip_round_exits_zero(monkeypatch, capsys):
    code, line = _run_bench(monkeypatch, capsys, {
        "mnist": {"images_per_sec": 5.0, **TPU_ROW},
        "resnet50": {"images_per_sec_per_chip": 100.0, "mfu": 0.2,
                     "tflops_per_chip": 39.4, **TPU_ROW}})
    assert code == 0
    assert line["value"] == 100.0 and line["mfu"] == 0.2
    assert line["device_kind"] == "TPU v5 lite"
    assert line["errored"] == [] and line["not_on_tpu"] == []
