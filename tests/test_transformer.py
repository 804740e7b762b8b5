"""Transformer model unit tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import (
    Transformer,
    param_logical_axes,
    param_partition_specs,
    tiny_config,
)
from kubeflow_tpu.parallel import MeshConfig, create_mesh


def _init(config, batch=2, seq=16):
    model = Transformer(config)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    return model, params, tokens


def test_forward_shapes():
    config = tiny_config()
    model, params, tokens = _init(config)
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, config.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_causality():
    """Changing a future token must not change past logits."""
    config = tiny_config()
    model, params, _ = _init(config)
    rng = jax.random.key(1)
    t1 = jax.random.randint(rng, (1, 16), 0, config.vocab_size)
    t2 = t1.at[0, 10].set((t1[0, 10] + 1) % config.vocab_size)
    l1 = model.apply({"params": params}, t1)
    l2 = model.apply({"params": params}, t2)
    np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
    assert not np.allclose(l1[0, 10:], l2[0, 10:], atol=1e-5)


def test_moe_forward():
    config = tiny_config(n_experts=4, experts_per_token=2)
    model, params, tokens = _init(config)
    logits, mut = model.apply({"params": params}, tokens, mutable=["losses"])
    assert logits.shape == (2, 16, config.vocab_size)
    aux = jax.tree_util.tree_leaves(mut)
    assert aux and np.isfinite(np.asarray(aux[0])).all()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_moe_matches_dense_dispatch_semantics():
    """With E experts and k=E, MoE output is a convex combination: finite + grad-safe."""
    config = tiny_config(n_experts=2, experts_per_token=2)
    model, params, tokens = _init(config)

    def loss(p):
        logits, _ = model.apply({"params": p}, tokens, mutable=["losses"])
        return jnp.mean(logits ** 2)

    g = jax.grad(loss)(params)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(g)]
    assert all(np.isfinite(norms))


def test_unscanned_matches_scanned_param_count():
    cfg_scan = tiny_config()
    cfg_loop = tiny_config(scan_layers=False)
    _, p_scan, _ = _init(cfg_scan)
    _, p_loop, _ = _init(cfg_loop)
    n_scan = sum(x.size for x in jax.tree_util.tree_leaves(p_scan))
    n_loop = sum(x.size for x in jax.tree_util.tree_leaves(p_loop))
    assert n_scan == n_loop


def test_param_specs_cover_all_leaves():
    config = tiny_config(n_experts=4)
    _, params, _ = _init(config)
    axes = param_logical_axes(params)
    specs = param_partition_specs(params)
    flat_p = jax.tree_util.tree_leaves(params)
    flat_a = jax.tree_util.tree_leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_p) == len(flat_a)
    for leaf, ax in zip(flat_p, flat_a):
        assert leaf.ndim == len(ax)
    # moe experts must shard over the expert axis
    flat_specs = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: not isinstance(x, dict)
    )[0]
    moe_specs = [s for path, s in flat_specs if "moe" in str(path)]
    assert any("dp" in str(s) for s in moe_specs)


def test_sharded_forward_on_mesh():
    config = tiny_config()
    model, params, _ = _init(config, batch=8, seq=16)
    mesh = create_mesh(MeshConfig(dp=2, pp=1, tp=4))
    from jax.sharding import NamedSharding

    from conftest import shard_params
    from kubeflow_tpu.parallel.mesh import logical_to_mesh_axes

    params = shard_params(params, mesh)
    tokens = jax.device_put(
        jnp.zeros((8, 16), jnp.int32),
        NamedSharding(mesh, logical_to_mesh_axes(("batch", None))),
    )
    from kubeflow_tpu.parallel.mesh import mesh_context
    with mesh_context(mesh):
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
    assert logits.shape == (8, 16, config.vocab_size)


def test_sequence_parallel_impls_match_dense():
    """ring and ulysses attention inside the full model produce the same
    logits as the dense core on a tp-sharded mesh."""
    import numpy as np

    from kubeflow_tpu.models import Transformer, TransformerConfig

    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import mesh_context

    mesh = create_mesh(MeshConfig(dp=2, tp=4))
    base = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=64, max_seq_len=64, dtype=jnp.float32,
                remat=False, scan_layers=False)
    tokens = jax.random.randint(jax.random.key(0), (2, 64), 0, 128)

    dense = Transformer(TransformerConfig(**base, attention_impl="dense"))
    params = dense.init(jax.random.key(1), tokens)["params"]
    with mesh_context(mesh):
        ref = jax.jit(lambda p, t: dense.apply({"params": p}, t))(
            params, tokens)
        for impl in ("ring", "ulysses"):
            model = Transformer(
                TransformerConfig(**base, attention_impl=impl))
            out = jax.jit(
                lambda p, t, m=model: m.apply({"params": p}, t))(
                params, tokens)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-4, err_msg=impl)


# ---------------------------------------------------------------------------
# Tile-knob plumbing: attention_block_q/attention_block_k split + the
# autotune resolution path (kubeflow_tpu/ops/autotune.py)
# ---------------------------------------------------------------------------


def test_attention_block_q_and_k_thread_as_independent_overrides():
    """The split knobs reach the flash kernels as an override (recorded
    with source="override"), fitted to divisors of the sequence."""
    from kubeflow_tpu.ops import autotune

    config = tiny_config(attention_impl="flash", attention_block_q=16,
                         attention_block_k=32)
    model, params, tokens = _init(config, seq=32)
    with autotune.record_resolutions() as rec:
        model.apply({"params": params}, tokens)
    summary = autotune.summarize_resolutions(rec)
    assert summary, "flash path must resolve tiles"
    for d in summary:
        assert d["source"] == "override"
        assert (d["block_q"], d["block_k"]) == (16, 32)


def test_default_none_blocks_resolve_per_kernel_key():
    """attention_block_k=None (the new default) resolves each flash
    kernel key independently instead of pinning one square edge."""
    from kubeflow_tpu.ops import autotune

    config = tiny_config(attention_impl="flash")
    assert config.attention_block_k is None
    model, params, tokens = _init(config, seq=32)
    with autotune.record_resolutions() as rec:
        jax.grad(lambda p: jnp.sum(
            model.apply({"params": p}, tokens)))(params)
    kernels = {d["kernel"] for d in autotune.summarize_resolutions(rec)}
    assert {"flash_fwd", "flash_bwd_fused"} <= kernels


def test_old_square_config_matches_new_default_numerically():
    """Parity pin for the knob split: an old-style config (explicit
    square attention_block_k=1024, the pre-PR default) and the new
    None default produce identical logits at CPU-tier shapes (both fit
    to the same full-sequence tile)."""
    old = tiny_config(attention_impl="flash", attention_block_k=1024)
    new = tiny_config(attention_impl="flash")
    model_old, params, tokens = _init(old, seq=32)
    model_new = Transformer(new)
    lo = model_old.apply({"params": params}, tokens)
    ln = model_new.apply({"params": params}, tokens)
    assert np.array_equal(np.asarray(lo), np.asarray(ln))


def test_auto_impl_selects_dense_oracle_off_tpu():
    config = tiny_config(attention_impl="auto")
    dense = tiny_config(attention_impl="dense")
    model, params, tokens = _init(config, seq=16)
    la = model.apply({"params": params}, tokens)
    ld = Transformer(dense).apply({"params": params}, tokens)
    assert np.array_equal(np.asarray(la), np.asarray(ld))


def test_bad_tile_knob_rejected():
    with pytest.raises(ValueError, match="attention_block_q"):
        tiny_config(attention_block_q=0).validate()
    with pytest.raises(ValueError, match="paged_head_block"):
        tiny_config(paged_head_block=-1).validate()


def test_flash_under_a_dp_tp_mesh_matches_one_device():
    """A Mosaic kernel cannot be partitioned by XLA, so under a mesh the
    flash kernels run inside a full-manual shard_map — batch rows over
    dp, heads over tp (parallel/mesh.py:shard_kernel). Loss and every
    gradient must match the mesh-free run; the masked (kv_len) variant
    rides the same wrapper through BERT."""
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import mesh_context

    config = tiny_config(attention_impl="flash", n_kv_heads=4)
    model, params, _ = _init(config, batch=4, seq=32)
    tokens = jax.random.randint(jax.random.key(3), (4, 32), 0,
                                config.vocab_size)

    def loss(p):
        return jnp.mean(model.apply({"params": p}, tokens) ** 2)

    want_l, want_g = jax.jit(jax.value_and_grad(loss))(params)
    mesh = create_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    with mesh_context(mesh):
        got_l, got_g = jax.jit(jax.value_and_grad(loss))(params)
    # the mesh reorders the loss reduction, nothing else
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5), got_g, want_g)


def test_shard_kernel_drops_an_axis_that_does_not_divide_every_dim(caplog):
    """q heads split without their kv heads would pair the wrong
    groups: a logical axis is applied only if it divides every dim
    that carries it."""
    from jax.sharding import PartitionSpec as P

    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import (
        mesh_context,
        record_kernel_placements,
        shard_kernel,
    )

    mesh = create_mesh(MeshConfig(tp=4), devices=jax.devices()[:4])
    seen = {}

    def fn(q, k):
        seen["shapes"] = (q.shape, k.shape)
        return q + k.sum(axis=1, keepdims=True)

    q = jnp.ones((2, 8, 4))
    axes = ((None, "heads", None),) * 2

    def run(q, k):
        return shard_kernel("test_kernel", fn, (q, k), axes, q.shape,
                            (None, "heads", None))

    with mesh_context(mesh), record_kernel_placements() as placed:
        out = jax.jit(run)(q, jnp.ones((2, 8, 4)))
        assert seen["shapes"] == ((2, 2, 4), (2, 2, 4))  # 8 heads / tp=4
        assert placed == [{"kernel": "test_kernel", "devices": 4,
                           "split": {"heads": 4}, "dropped": []}]
        with caplog.at_level("WARNING", logger="kubeflow_tpu.parallel.mesh"):
            jax.jit(run)(q, jnp.ones((2, 2, 4)))
        assert seen["shapes"] == ((2, 8, 4), (2, 2, 4))  # 2 kv heads: whole
        # never silently: recorded for the bench row, and logged
        assert placed[1:] == [{"kernel": "test_kernel", "devices": 4,
                               "split": {}, "dropped": ["heads"]}]
        assert "test_kernel" in caplog.text and "dropped" in caplog.text
    assert out.shape == (2, 8, 4)
    # no mesh: a plain call
    assert run(q, q).shape == (2, 8, 4)
