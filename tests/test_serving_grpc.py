"""gRPC predict surface: REST/gRPC answer parity, status, warmup."""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import MnistCnn
from kubeflow_tpu.serving import ModelServer, export_model
from kubeflow_tpu.serving.grpc_server import (
    PredictClient,
    array_to_tensor,
    serve_grpc,
    tensor_to_array,
)


@pytest.fixture(scope="module")
def mnist_params():
    model = MnistCnn()
    return model, model.init(jax.random.key(0),
                             jnp.zeros((1, 28, 28, 1)))["params"]


@pytest.fixture
def stack(tmp_path, mnist_params):
    """REST + gRPC servers over one repository."""
    model, params = mnist_params
    export_model(str(tmp_path / "mnist"), "mnist", params, version=1)
    server = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    rest_port = server.start()
    grpc_srv, grpc_port = serve_grpc(server.repo, 0)
    client = PredictClient(f"127.0.0.1:{grpc_port}")
    yield server, rest_port, client
    client.close()
    grpc_srv.stop(grace=None)
    server.stop()


def test_tensor_roundtrip():
    for arr in (np.arange(6, dtype=np.float32).reshape(2, 3),
                np.ones((1, 2, 2), np.int32)):
        out = tensor_to_array(array_to_tensor(arr))
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == arr.dtype


def test_tensor_bfloat16_wire():
    import ml_dtypes

    arr = np.asarray(jnp.ones((2, 2), jnp.bfloat16))
    assert arr.dtype == np.dtype(ml_dtypes.bfloat16)
    out = tensor_to_array(array_to_tensor(arr))
    assert out.dtype == arr.dtype


def test_grpc_and_rest_same_predict(stack):
    server, rest_port, client = stack
    x = np.random.RandomState(0).rand(3, 28, 28, 1).astype(np.float32)

    req = urllib.request.Request(
        f"http://127.0.0.1:{rest_port}/v1/models/mnist:predict",
        data=json.dumps({"instances": x.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        rest = json.loads(resp.read())

    out, version = client.predict("mnist", x)
    assert version == 1
    np.testing.assert_allclose(out, np.array(rest["predictions"]), atol=1e-5)


def test_grpc_model_status_and_list(stack):
    _, _, client = stack
    assert client.list_models() == ["mnist"]
    status = client.model_status("mnist")
    assert (1, "AVAILABLE") in status


def test_grpc_unknown_model(stack):
    import grpc

    _, _, client = stack
    with pytest.raises(grpc.RpcError) as err:
        client.predict("nope", np.zeros((1, 28, 28, 1), np.float32))
    assert err.value.code() == grpc.StatusCode.NOT_FOUND


def test_grpc_accepts_image_sized_messages(stack):
    """A batch-8 224×224×3 fp32 request is ~4.8 MB — past gRPC's 4 MB
    default cap. The serving bench sends exactly this; both directions
    must be raised (BENCH r03 regression: RESOURCE_EXHAUSTED)."""
    server, _, client = stack
    big = np.zeros((8, 224, 224, 3), np.float32)
    assert big.nbytes > 4 * 1024 * 1024
    # mnist can't consume it — but the transport must deliver it; a
    # model-shape error proves the message got through the size cap
    with pytest.raises(Exception) as ei:
        client.predict("mnist", big)
    assert "RESOURCE_EXHAUSTED" not in str(ei.value)


def test_grpc_uint8_input_cast_to_float(stack):
    """Integer tensors (image-client convention) are accepted and cast;
    predictions match sending the same values as f32."""
    server, _, client = stack
    u8 = (np.random.default_rng(0).random((2, 28, 28, 1)) * 255).astype(
        np.uint8)
    out_u8, _ = client.predict("mnist", u8)
    out_f32, _ = client.predict("mnist", u8.astype(np.float32))
    np.testing.assert_allclose(out_u8, out_f32, rtol=1e-5)


def test_grpc_oversized_batch(stack):
    import grpc

    _, _, client = stack
    with pytest.raises(grpc.RpcError) as err:
        client.predict("mnist", np.zeros((99, 28, 28, 1), np.float32))
    assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_warmup_precompiles_buckets(tmp_path, mnist_params):
    model, params = mnist_params
    export_model(str(tmp_path / "mnist"), "mnist", params, version=1)
    server = ModelServer(str(tmp_path), port=0, poll_interval_s=3600,
                         max_batch_size=4, warmup=True)
    loaded = server.repo.get("mnist")
    assert loaded.input_shape == (28, 28, 1)
    # every bucket is already compiled: cache hits, no new traces
    sizes = getattr(loaded.predict, "_cache_size", None)
    if callable(sizes):
        before = loaded.predict._cache_size()
        for b in (1, 2, 4):
            loaded.predict(jnp.zeros((b, 28, 28, 1)))
        assert loaded.predict._cache_size() == before
    server.stop()


def test_failed_warmup_still_serves_but_is_counted(tmp_path, mnist_params,
                                                    monkeypatch):
    """A warm-up that raises is logged and the version still serves —
    production behaviour — but /metrics says so, and says 0 when none
    failed (chip_smoke.py asserts on the series)."""
    from kubeflow_tpu.serving import server as server_mod
    from kubeflow_tpu.serving.model_store import LoadedModel

    _, params = mnist_params
    export_model(str(tmp_path / "ok" / "mnist"), "mnist", params, version=1)
    export_model(str(tmp_path / "bad" / "mnist2"), "mnist", params,
                 version=1)
    ok = ModelServer(str(tmp_path / "ok"), port=0, poll_interval_s=3600,
                     max_batch_size=2, warmup=True)
    assert server_mod._warmup_failures.get(model="mnist") == 0.0
    assert ('kftpu_serving_warmup_failures_total{model="mnist"} 0'
            in server_mod._warmup_failures.expose())
    ok.stop()

    def boom(self, batch_sizes):
        raise RuntimeError("injected warm-up failure")

    monkeypatch.setattr(LoadedModel, "warmup", boom)
    bad = ModelServer(str(tmp_path / "bad"), port=0, poll_interval_s=3600,
                      max_batch_size=2, warmup=True)
    assert server_mod._warmup_failures.get(model="mnist2") == 1.0
    assert bad.repo.get("mnist2") is not None   # still served
    bad.stop()


def test_export_records_input_shape(tmp_path, mnist_params):
    _, params = mnist_params
    export_model(str(tmp_path / "m"), "mnist", params, version=2,
                 input_shape=(28, 28, 1), input_dtype="float32")
    from kubeflow_tpu.serving.model_store import load_version

    loaded = load_version(str(tmp_path / "m"), 2)
    assert loaded.input_shape == (28, 28, 1)
    assert loaded.warmup([1, 2]) == 2
