"""KV-cache decoding tests: cached generation must reproduce the full
forward pass exactly (the cache is an optimization, never a semantics
change), padded prompts must not leak into attention, and the whole
loop must be jit-compilable with static shapes.

No reference counterpart: the reference serves opaque TF-Serving
containers and has no generation path — this is TPU-native capability
(SURVEY §7 design stance: the framework owns the model math).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import Transformer, TransformerConfig
from kubeflow_tpu.models.decode import (
    decode_step,
    generate,
    make_generate,
    prefill,
    prefill_continue,
)
from kubeflow_tpu.serving import transformer_export_config

from conftest import KV_GEOMETRIES, full_forward_greedy


def small_config(**kw):
    base = dict(vocab_size=97, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=64, max_seq_len=32,
                dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    model = Transformer(config)
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0,
                                config.vocab_size)
    params = model.init(jax.random.key(0), prompt)["params"]
    return config, model, params, prompt


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_greedy_matches_full_forward(setup):
    config, model, params, prompt = setup
    want = full_forward_greedy(model, params, prompt, 6)
    got = generate(config, params, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(got, want)


def test_prefill_logits_match_forward(setup):
    config, model, params, prompt = setup
    full = model.apply({"params": params}, prompt)
    last, _ = prefill(config, params, prompt)
    np.testing.assert_allclose(last, full[:, -1], atol=1e-5)


def test_padded_prompt_matches_unpadded(setup):
    """Right-padding to a bucket + true_len must change nothing: the
    padded tail is masked until overwritten."""
    config, model, params, prompt = setup
    pad = jnp.zeros((prompt.shape[0], 11 - prompt.shape[1]), jnp.int32)
    padded = jnp.concatenate([prompt, pad], axis=1)
    want = generate(config, params, prompt, max_new_tokens=5)
    got = generate(config, params, padded, max_new_tokens=5,
                   true_len=prompt.shape[1])
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_ragged_batch_matches_per_row_oracle(setup):
    """Per-row true lengths: each row of a ragged batch must generate
    exactly what it would generate alone (physical slot == logical
    position per row, so causality is exact)."""
    config, model, params, _ = setup
    rng = jax.random.key(9)
    lens = [3, 5, 7]
    rows = [jax.random.randint(jax.random.fold_in(rng, i), (1, n), 0,
                               config.vocab_size)
            for i, n in enumerate(lens)]
    width = max(lens)
    padded = jnp.zeros((len(rows), width), jnp.int32)
    for i, r in enumerate(rows):
        padded = padded.at[i, :lens[i]].set(r[0])

    got = generate(config, params, padded, max_new_tokens=5,
                   true_len=jnp.asarray(lens, jnp.int32))
    for i, r in enumerate(rows):
        want = full_forward_greedy(model, params, r, 5)
        np.testing.assert_array_equal(got[i:i + 1], want,
                                      err_msg=f"row {i} (len {lens[i]})")


def test_decode_step_advances_one_token(setup):
    config, model, params, prompt = setup
    last, cache = prefill(config, params, prompt)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    logits, cache = decode_step(config, params, cache, tok)
    # oracle: full forward over prompt+tok
    full = model.apply({"params": params},
                       jnp.concatenate([prompt, tok[:, None]], axis=1))
    np.testing.assert_allclose(logits, full[:, -1], atol=1e-5)


def test_generate_is_jittable(setup):
    config, model, params, prompt = setup
    fn = make_generate(config, max_new_tokens=4)
    got = fn(params, prompt, jnp.int32(prompt.shape[1]), jax.random.key(0))
    want = generate(config, params, prompt, max_new_tokens=4)
    np.testing.assert_array_equal(got, want)
    # second call with same shapes hits the jit cache (no retrace error)
    fn(params, prompt, jnp.int32(prompt.shape[1]), jax.random.key(1))


def test_sampling_is_reproducible_and_varies(setup):
    config, model, params, prompt = setup
    a = generate(config, params, prompt, max_new_tokens=8,
                 temperature=1.0, rng=jax.random.key(7))
    b = generate(config, params, prompt, max_new_tokens=8,
                 temperature=1.0, rng=jax.random.key(7))
    c = generate(config, params, prompt, max_new_tokens=8,
                 temperature=1.0, rng=jax.random.key(8))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)  # overwhelmingly likely to differ


def test_sampling_requires_rng(setup):
    config, _, params, prompt = setup
    with pytest.raises(ValueError, match="rng"):
        generate(config, params, prompt, max_new_tokens=2, temperature=0.7)


def test_unscanned_layers_decode(setup):
    """scan_layers=False keeps per-block caches; same numerics."""
    config = small_config(scan_layers=False)
    model = Transformer(config)
    prompt = jax.random.randint(jax.random.key(1), (1, 4), 0,
                                config.vocab_size)
    params = model.init(jax.random.key(0), prompt)["params"]
    want = full_forward_greedy(model, params, prompt, 4)
    got = generate(config, params, prompt, max_new_tokens=4)
    np.testing.assert_array_equal(got, want)


# (n_heads, n_kv_heads, head size): the merged K/V axis is KH·Dh lanes
GEOMETRIES = {"h4kv2d8": (4, 2, 8), **KV_GEOMETRIES}


def geometry_config(name, **kw):
    H, KH, Dh = GEOMETRIES[name]
    return small_config(n_heads=H, n_kv_heads=KH, d_model=H * Dh, **kw)


def layer0_kv(config, params, tokens):
    """Layer 0's K (rotated) and V of ``tokens`` ``(B, T)`` as
    ``(B, T, KH, Dh)``, computed from the parameters alone: what a cache
    laid out ``(…, KH, Dh)`` held at those positions."""
    from kubeflow_tpu.models.transformer import apply_rope, rope_tables

    block = (jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
             if config.scan_layers else params["block_0"])
    x = jnp.take(params["token_embed"], tokens, axis=0)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    x = x * block["attn_norm"]["scale"]
    k = jnp.einsum("bsd,dhk->bshk", x, block["attn"]["k_proj"])
    v = jnp.einsum("bsd,dhk->bshk", x, block["attn"]["v_proj"])
    sin, cos = rope_tables(tokens.shape[1], config.head_dim,
                           config.rope_theta)
    return apply_rope(k, sin, cos), v


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["carried", "per_layer"])
def test_prefix_then_suffix_then_steps_continue_one_cache(scan_layers,
                                                          geometry):
    """prefill → prefill_continue → decode steps hand ONE cache along:
    same layout at every stage, the tokens and live K/V of a prompt
    prefilled whole, and the logits of the full forward; at every
    geometry of the merged K/V axis (one head's lanes to 320, a width
    that is no multiple of 128), whose ``(…, KH, Dh)`` view holds what
    the parameters say."""
    config = geometry_config(geometry, scan_layers=scan_layers)
    model = Transformer(config)
    prompt = jax.random.randint(jax.random.key(1), (1, 9), 0,
                                config.vocab_size)
    params = model.init(jax.random.key(0), prompt)["params"]
    contract = config.cache_leaves(1)
    KH, Dh = config.n_kv_heads, config.head_dim
    assert contract["k"].shape[-1] == KH * Dh == contract["v"].shape[-1]
    assert contract["k"].head_width == Dh

    def steps(logits, cache):
        toks = []
        for _ in range(3):
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            toks.append(int(tok[0]))
            logits, cache = jax.jit(decode_step, static_argnums=0)(
                config, params, cache, tok)
        return toks, logits, cache

    def layout(c):
        return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), c)

    def written(path, leaf, n):
        """The leaf's first ``n`` positions: the axis after the rows."""
        return jax.lax.slice_in_dim(
            leaf, 0, n, axis=contract[path[-1].key].batch_axis + 1)

    whole = steps(*prefill(config, params, prompt))
    _, cache = prefill(config, params, prompt[:, :5])
    suffix = jnp.pad(prompt[:, 5:], ((0, 0), (0, 4)))   # 4 real + 4 pad
    logits, cont = prefill_continue(config, params, cache, suffix, 4, 9)
    assert layout(cont) == layout(cache)
    split = steps(logits, cont)
    assert split[0] == whole[0]
    np.testing.assert_allclose(split[1], whole[1], rtol=1e-4, atol=1e-5)
    assert layout(split[2]) == layout(cache)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(split[2]),
            jax.tree_util.tree_leaves(whole[2])):
        if path[-1].key == "positions":
            np.testing.assert_array_equal(got, np.full(got.shape, 12))
            np.testing.assert_array_equal(got, want)
        else:   # the 12 written positions, wherever the leaf keeps them
            np.testing.assert_allclose(written(path, got, 12),
                                       written(path, want, 12),
                                       rtol=1e-4, atol=1e-5)

    seen = jnp.concatenate([prompt, jnp.asarray([split[0]], jnp.int32)],
                           axis=1)                       # 12 tokens
    full = model.apply({"params": params}, seen)
    np.testing.assert_allclose(split[1], full[:, -1], rtol=1e-4, atol=1e-5)
    first = jax.tree_util.tree_map(
        lambda a: a[0], split[2]) if scan_layers else (
            split[2]["block_0"]["attn"])
    for name, want in zip("kv", layer0_kv(config, params, seen)):
        got = first[name][:, :12].reshape(1, 12, KH, Dh)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("geometry", sorted(KV_GEOMETRIES))
def test_speculative_rounds_at_every_kv_geometry(geometry):
    """The speculative verify writes several tokens a row from ragged
    starts into the merged K/V rows and attends against their
    ``(…, KH, Dh)`` view; a draft's steps take the one-token form. The
    stream is the target's full-forward greedy stream at every width."""
    from kubeflow_tpu.models.decode import speculative_generate

    config = geometry_config(geometry, max_seq_len=48)
    draft = geometry_config(geometry, max_seq_len=48, n_layers=1)
    model = Transformer(config)
    prompt = jnp.asarray([[5, 11, 17, 3], [9, 2, 0, 0]], jnp.int32)
    lens = jnp.asarray([4, 2], jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    draft_params = Transformer(draft).init(jax.random.key(1),
                                           prompt)["params"]
    got, stats = speculative_generate(
        config, params, draft, draft_params, prompt, max_new_tokens=7,
        draft_len=3, true_len=lens)
    assert stats["rounds"] >= 2
    for i, n in enumerate([4, 2]):
        want = full_forward_greedy(model, params, prompt[i:i + 1, :n], 7)
        np.testing.assert_array_equal(np.asarray(got)[i:i + 1], want)


def test_moe_decode(setup):
    config = small_config(n_experts=4, experts_per_token=2)
    model = Transformer(config)
    prompt = jax.random.randint(jax.random.key(1), (2, 4), 0,
                                config.vocab_size)
    params = model.init(jax.random.key(0), prompt)["params"]
    want = full_forward_greedy(model, params, prompt, 3)
    got = generate(config, params, prompt, max_new_tokens=3)
    np.testing.assert_array_equal(got, want)


def test_serving_generate_endpoint(tmp_path, setup):
    """:generate over live HTTP — export a transformer, generate through
    the model server, and match the in-process greedy oracle."""
    import json
    import urllib.request

    from kubeflow_tpu.serving import ModelServer, export_model

    config, model, params, prompt = setup
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    port = srv.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/lm:generate",
            data=json.dumps({
                "prompt_tokens": np.asarray(prompt).tolist(),
                "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.load(resp)
        want = full_forward_greedy(model, params, prompt, 4)
        np.testing.assert_array_equal(np.asarray(out["tokens"]), want)
        assert out["tokens_per_sec"] > 0

        # non-LM kinds refuse :generate with a clear 400
        import jax as _jax
        from kubeflow_tpu.models import MnistCnn

        m = MnistCnn()
        export_model(str(tmp_path / "mnist"), "mnist",
                     m.init(_jax.random.key(0),
                            jnp.zeros((1, 28, 28, 1)))["params"], version=1)
        srv.repo.refresh()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/mnist:generate",
            data=json.dumps({"prompt_tokens": [[1]]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
    finally:
        srv.stop()


def test_grpc_generate_matches_rest(tmp_path, setup):
    """The gRPC Generate RPC (binary prompt tensors) and the REST
    :generate endpoint share one core — same tokens out."""
    from kubeflow_tpu.serving import ModelServer, export_model
    from kubeflow_tpu.serving.grpc_server import PredictClient, serve_grpc

    config, model, params, prompt = setup
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    srv.start()
    grpc_srv, grpc_port = serve_grpc(srv.repo, 0)
    client = PredictClient(f"127.0.0.1:{grpc_port}")
    try:
        tokens, version = client.generate(
            "lm", np.asarray(prompt), max_new_tokens=4)
        want = full_forward_greedy(model, params, prompt, 4)
        np.testing.assert_array_equal(tokens, want)
        assert version == 1
        # right-padded prompt with an out-of-vocab PAD id: the pad
        # columns never reach the model, so this must succeed
        padded = np.full((prompt.shape[0], 8), -1, np.int32)
        padded[:, :prompt.shape[1]] = prompt
        tokens_p, _ = client.generate("lm", padded, max_new_tokens=4,
                                      true_len=prompt.shape[1])
        np.testing.assert_array_equal(tokens_p, want)
        # true_len whose pow2 bucket is below the padded width must
        # still serve (regression: bucket sized from true_len used to
        # crash the broadcast into the narrower bucket)
        wide = np.zeros((prompt.shape[0], 16), np.int32)
        wide[:, :prompt.shape[1]] = prompt
        tokens_w, _ = client.generate("lm", wide, max_new_tokens=4,
                                      true_len=prompt.shape[1])
        np.testing.assert_array_equal(tokens_w, want)

        # errors surface as INVALID_ARGUMENT with the core's message
        import grpc as _grpc

        with pytest.raises(_grpc.RpcError) as ei:
            client.generate("lm", np.asarray(prompt), max_new_tokens=999)
        assert ei.value.code() == _grpc.StatusCode.INVALID_ARGUMENT
        assert "context" in ei.value.details()
        # a scalar prompt tensor is a clean INVALID_ARGUMENT, not UNKNOWN
        with pytest.raises(_grpc.RpcError) as ei:
            client.generate("lm", np.int32(5))
        assert ei.value.code() == _grpc.StatusCode.INVALID_ARGUMENT
    finally:
        client.close()
        grpc_srv.stop(grace=None)
        srv.stop()


def test_serving_generate_validation(tmp_path, setup):
    from kubeflow_tpu.serving import export_model
    from kubeflow_tpu.serving.server import ModelServer

    config, model, params, _ = setup
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    srv.start()
    try:
        # ragged REST batches are first-class now: each row generates
        # from its own length
        code, out = srv.handle_generate("lm", None,
                                        {"prompt_tokens": [[1, 2], [3]],
                                         "max_new_tokens": 2})
        assert code == 200, out
        assert len(out["tokens"]) == 2
        code, out = srv.handle_generate("lm", None, {})
        assert code == 400
        # context overflow must be a 400, not silently-clamped garbage
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[1] * 8],
                         "max_new_tokens": 1000})
        assert code == 400 and "context" in out["error"]
        # negative temperature inverts the distribution — reject
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[1, 2]], "temperature": -0.7})
        assert code == 400 and "temperature" in out["error"]
        # oversized batch rejected like the predict path
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[1, 2]] * 99})
        assert code == 400 and "batch" in out["error"]
        # a prompt past half the context must still generate: the budget
        # is ctx - true_len, NOT ctx - pow2_bucket (ctx=32 here)
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[1] * 20],
                         "max_new_tokens": 4})
        assert code == 200, out
        assert len(out["tokens"][0]) == 4
        # misshaped (3-D) prompts are a 400, not a handler crash
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[[1, 2], [3, 4]]]})
        assert code == 400
        assert ("2-D" in out["error"]
                or "bad prompt_tokens" in out["error"])
        # out-of-vocab ids would silently clamp in the embedding
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[999999, 1]]})
        assert code == 400 and "token ids" in out["error"]
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[-5, 1]]})
        assert code == 400
    finally:
        srv.stop()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the flagship twin
def test_lm_example_generate_small_context(tmp_path, capsys):
    """--generate with a tiny --seq-len must sample (or skip cleanly),
    never crash in the scan."""
    from kubeflow_tpu.examples.lm import main

    main(["--steps", "2", "--per-device-batch", "1", "--seq-len", "8",
          "--vocab-size", "32", "--d-model", "8", "--n-layers", "1",
          "--n-heads", "2", "--d-ff", "16", "--log-every", "2",
          "--generate", "4"])
    out = capsys.readouterr().out
    assert "sample_tokens" in out


def test_serving_generate_near_context_end_buckets_pow2(tmp_path, setup):
    """A prompt near the context end must not mint per-length compiled
    programs: the clamped new-token bucket stays a power of two."""
    from kubeflow_tpu.serving import export_model
    from kubeflow_tpu.serving.server import ModelServer

    config, _, params, _ = setup  # max_seq_len = 32
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    srv.start()
    try:
        lm = srv.repo.get("lm")
        # budgets 7, 6, 5 all round down to the pow2 bucket 4
        for tl in (25, 26, 27):
            code, _ = srv.handle_generate(
                "lm", None, {"prompt_tokens": [[1] * tl],
                             "max_new_tokens": 3})
            assert code == 200
        assert lm.generate._cache_size() == 1
        # exact-fit tail: prompt 29 + max_new 3 = 32 fits even though
        # pow2(3)=4 does not — served exactly, not rejected
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[1] * 29],
                         "max_new_tokens": 3})
        assert code == 200, out
        assert len(out["tokens"][0]) == 3
        # but an unservable ask is an honest 400
        code, out = srv.handle_generate(
            "lm", None, {"prompt_tokens": [[1] * 30],
                         "max_new_tokens": 3})
        assert code == 400 and "context" in out["error"]
    finally:
        srv.stop()


def test_serving_ragged_rows_match_solo_requests(tmp_path, setup):
    """Each row of a ragged REST batch must generate exactly what a
    solo request for that prompt generates."""
    from kubeflow_tpu.serving import ModelServer, export_model

    config, model, params, _ = setup
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    srv.start()
    try:
        rows = [[5, 9, 2], [7, 1, 3, 8, 4]]
        code, batch = srv.handle_generate(
            "lm", None, {"prompt_tokens": rows, "max_new_tokens": 4})
        assert code == 200, batch
        for i, row in enumerate(rows):
            code, solo = srv.handle_generate(
                "lm", None, {"prompt_tokens": [row],
                             "max_new_tokens": 4})
            assert code == 200
            assert batch["tokens"][i] == solo["tokens"][0], f"row {i}"
        # a REST client that pads client-side and passes true_len gets
        # the unpadded behavior (the old documented contract)
        code, via_tl = srv.handle_generate(
            "lm", None, {"prompt_tokens": [rows[0] + [0, 0]],
                         "true_len": 3, "max_new_tokens": 4})
        assert code == 200, via_tl
        code, solo = srv.handle_generate(
            "lm", None, {"prompt_tokens": [rows[0]],
                         "max_new_tokens": 4})
        assert via_tl["tokens"][0] == solo["tokens"][0]
    finally:
        srv.stop()


def test_generate_rejects_context_overrun(setup):
    """The library API errors on overruns instead of silently clamping
    cache writes (max_seq_len=32 in the fixture)."""
    config, _, params, prompt = setup
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(config, params, prompt,
                 max_new_tokens=config.max_seq_len)


def test_serving_generate_temperatures_share_one_compile(tmp_path, setup):
    """Distinct temperatures must reuse one compiled sampling program —
    temperature is traced, only greedy-vs-sampling is static."""
    import jax as _jax

    from kubeflow_tpu.serving import export_model
    from kubeflow_tpu.serving.server import ModelServer

    config, model, params, prompt = setup
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    srv.start()
    try:
        lm = srv.repo.get("lm")
        body = {"prompt_tokens": np.asarray(prompt).tolist(),
                "max_new_tokens": 2, "seed": 1}
        for t in (0.5, 0.7, 0.9):
            code, _ = srv.handle_generate("lm", None,
                                          {**body, "temperature": t})
            assert code == 200
        # one sampling cache entry despite three temperatures
        assert lm.generate._cache_size() == 1
    finally:
        srv.stop()


def test_decode_on_sharded_mesh(setup):
    """Generation with tensor-parallel-sharded params on the virtual
    mesh: the multi-chip serving path. Results must match unsharded
    greedy decode exactly."""
    from jax.sharding import NamedSharding

    from conftest import shard_params
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import (
        logical_to_mesh_axes,
        mesh_context,
    )

    config, model, params, prompt = setup
    want = generate(config, params, prompt, max_new_tokens=5)

    mesh = create_mesh(MeshConfig(dp=2, tp=4))
    sharded = shard_params(params, mesh)
    tokens = jax.device_put(
        prompt, NamedSharding(mesh, logical_to_mesh_axes(("batch", None))))
    with mesh_context(mesh):
        got = jax.jit(lambda p, t: generate(
            config, p, t, max_new_tokens=5))(sharded, tokens)
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_lm_example_train_generate_export(tmp_path, capsys):
    """The flagship loop end to end: train → greedy sample → export →
    reload with a generate-capable LoadedModel."""
    from kubeflow_tpu.examples.lm import main
    from kubeflow_tpu.serving import load_latest

    loss = main(["--steps", "3", "--per-device-batch", "1",
                 "--seq-len", "16", "--vocab-size", "64",
                 "--d-model", "16", "--n-layers", "1", "--n-heads", "2",
                 "--d-ff", "32", "--log-every", "3",
                 "--export", str(tmp_path / "lm"), "--generate", "4"])
    assert loss == loss  # finite
    out = capsys.readouterr().out
    assert "sample_tokens" in out and "exported" in out
    m = load_latest(str(tmp_path / "lm"))
    assert m.kind == "transformer" and m.generate is not None
    assert m.max_seq_len == 16 and m.vocab_size == 64


def test_softcap_decode():
    config = small_config(logits_softcap=30.0)
    model = Transformer(config)
    prompt = jax.random.randint(jax.random.key(1), (1, 3), 0,
                                config.vocab_size)
    params = model.init(jax.random.key(0), prompt)["params"]
    want = full_forward_greedy(model, params, prompt, 3)
    got = generate(config, params, prompt, max_new_tokens=3)
    np.testing.assert_array_equal(got, want)
