"""Serving tests: model store round-trip + live HTTP server (the reference's
serving test pattern: gRPC PredictRequest vs golden with tolerance,
``testing/test_tf_serving.py:40-57`` — here REST against a real socket)."""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import MnistCnn
from kubeflow_tpu.serving import ModelServer, export_model, load_latest


@pytest.fixture(scope="module")
def mnist_params():
    model = MnistCnn()
    return model, model.init(jax.random.key(0),
                             jnp.zeros((1, 28, 28, 1)))["params"]


@pytest.fixture
def repo(tmp_path, mnist_params):
    model, params = mnist_params
    export_model(str(tmp_path / "mnist"), "mnist", params, version=1)
    return tmp_path


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def test_store_roundtrip(tmp_path, mnist_params):
    model, params = mnist_params
    export_model(str(tmp_path / "m"), "mnist", params, version=3)
    loaded = load_latest(str(tmp_path / "m"))
    assert loaded.version == 3
    x = jnp.ones((2, 28, 28, 1))
    np.testing.assert_allclose(
        np.asarray(loaded.predict(x)),
        np.asarray(model.apply({"params": params}, x)),
        atol=1e-5,
    )


def test_server_predict_end_to_end(repo, mnist_params):
    model, params = mnist_params
    server = ModelServer(str(repo), port=0, poll_interval_s=0.2)
    port = server.start()
    try:
        # golden comparison with numeric tolerance
        x = np.random.RandomState(0).randn(2, 28, 28, 1).astype(np.float32)
        code, body = _post(
            f"http://127.0.0.1:{port}/v1/models/mnist:predict",
            {"instances": x.tolist()})
        assert code == 200
        expected = np.asarray(model.apply({"params": params}, jnp.asarray(x)))
        np.testing.assert_allclose(np.asarray(body["predictions"]), expected,
                                   atol=1e-4)
        assert body["model_version"] == "1"

        code, body = _get(f"http://127.0.0.1:{port}/v1/models")
        assert body["models"] == ["mnist"]
        code, body = _get(f"http://127.0.0.1:{port}/v1/models/mnist")
        assert body["model_version_status"][0]["state"] == "AVAILABLE"
    finally:
        server.stop()


def test_server_version_hot_reload(repo, mnist_params):
    model, params = mnist_params
    server = ModelServer(str(repo), port=0, poll_interval_s=0.1)
    port = server.start()
    try:
        zero_params = jax.tree_util.tree_map(jnp.zeros_like, params)
        export_model(str(repo / "mnist"), "mnist", zero_params, version=2)
        import time

        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            _, body = _post(
                f"http://127.0.0.1:{port}/v1/models/mnist:predict",
                {"instances": np.zeros((1, 28, 28, 1)).tolist()})
            if body.get("model_version") == "2":
                break
            time.sleep(0.1)
        assert body["model_version"] == "2"
    finally:
        server.stop()


def test_server_error_paths(repo):
    server = ModelServer(str(repo), port=0)
    port = server.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{port}/v1/models/nope:predict",
                  {"instances": [[0.0]]})
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{port}/v1/models/mnist:predict",
                  {"wrong": 1})
        assert ei.value.code == 400
        # oversized batch
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{port}/v1/models/mnist:predict",
                  {"instances": np.zeros((64, 28, 28, 1)).tolist()})
        assert ei.value.code == 400
        # version pin to a missing version
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{port}/v1/models/mnist/versions/9:predict",
                  {"instances": np.zeros((1, 28, 28, 1)).tolist()})
        assert ei.value.code == 404
    finally:
        server.stop()


def test_padding_keeps_one_compiled_shape(repo):
    """Odd batch sizes bucket up to fixed shapes (no per-request recompiles)."""
    server = ModelServer(str(repo), port=0, max_batch_size=8)
    port = server.start()
    try:
        for n in (1, 3, 5):
            code, body = _post(
                f"http://127.0.0.1:{port}/v1/models/mnist:predict",
                {"instances": np.zeros((n, 28, 28, 1)).tolist()})
            assert code == 200
            assert len(body["predictions"]) == n
    finally:
        server.stop()


def test_scalar_instances_clean_400(repo):
    server = ModelServer(str(repo), port=0)
    port = server.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{port}/v1/models/mnist:predict",
                  {"instances": 5})
        assert ei.value.code == 400
    finally:
        server.stop()


def test_pinned_version_served_and_cached(repo, mnist_params):
    model, params = mnist_params
    from kubeflow_tpu.serving import export_model
    import jax, jax.numpy as jnp

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    export_model(str(repo / "mnist"), "mnist", zero, version=2)
    server = ModelServer(str(repo), port=0, poll_interval_s=60)
    server.repo.refresh()
    port = server.start()
    try:
        x = np.zeros((1, 28, 28, 1)).tolist()
        # latest is 2; pin 1
        _, body = _post(f"http://127.0.0.1:{port}/v1/models/mnist/versions/1:predict",
                        {"instances": x})
        assert body["model_version"] == "1"
        assert ("mnist", 1) in server.repo._pinned  # cached for next time
    finally:
        server.stop()


def test_server_options_hand_the_engine_its_cache_sizing(tmp_path,
                                                         monkeypatch):
    """The five deployment names a pod sets are read where every other
    server setting is (``server_options``) and reach the engine the
    repository builds as keywords; no socket is opened."""
    import os

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.serving import transformer_export_config
    from kubeflow_tpu.serving.server import server_options

    for name, value in (("KFTPU_PAGED", "1"), ("KFTPU_KV_PAGE_SIZE", "4"),
                        ("KFTPU_KV_PAGES", "9"),
                        ("KFTPU_PREFILL_CHUNK", "6"),
                        ("KFTPU_PREFIX_CACHE_BYTES", "4096"),
                        ("KFTPU_DECODE_SLOTS", "2"), ("KFTPU_WARMUP", "0")):
        monkeypatch.setenv(name, value)
    opts = server_options(os.environ)
    assert opts["engine_options"] == {
        "paged": True, "kv_page_size": 4, "kv_pages": 9,
        "prefill_chunk_tokens": 6, "prefix_cache_bytes": 4096}
    assert opts["decode_slots"] == 2 and opts["warmup"] is False
    # unset or empty names leave the keyword out: the engine's default
    assert server_options({"KFTPU_PAGED": "0", "KFTPU_KV_PAGES": ""})[
        "engine_options"] == {}

    config = TransformerConfig(vocab_size=97, d_model=32, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=64,
                               max_seq_len=48, dtype=jnp.float32,
                               remat=False)
    params = Transformer(config).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), poll_interval_s=3600, **opts)
    try:
        eng = srv.repo.engine_for("lm", srv.repo.get("lm"))
        assert eng.paged and eng.slots == 2
        assert (eng.kv_page_size, eng.kv_pages) == (4, 9)
        assert eng._kv.prefill_chunk_tokens == 6
        assert eng._prefix_budget_bytes == 4096
        assert eng._pool.pages_total == 9
    finally:
        srv.stop()
