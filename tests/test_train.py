"""End-to-end sharded training-step tests: loss must go down on the mesh."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models import MnistCnn, Transformer, tiny_config
from kubeflow_tpu.models.resnet import resnet18_thin
from kubeflow_tpu.parallel import MeshConfig, create_mesh
from kubeflow_tpu.train import (
    TrainState,
    create_sharded_state,
    make_image_train_step,
    make_lm_train_step,
    make_optimizer,
)


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_lm_train_loss_decreases():
    config = tiny_config()
    model = Transformer(config)
    mesh = create_mesh(MeshConfig(dp=2, pp=1, tp=4))
    tx = make_optimizer(1e-2, warmup_steps=1, decay_steps=100)
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0, config.vocab_size)

    def init_fn(rng):
        params = model.init(rng, tokens)["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, jax.random.key(1), mesh)
    step = make_lm_train_step(mesh)
    losses = []
    for _ in range(5):
        state, metrics = step(state, tokens)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(state.step) == 5


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_lm_train_step_moe():
    config = tiny_config(n_experts=4, experts_per_token=2)
    model = Transformer(config)
    mesh = create_mesh(MeshConfig(dp=4, pp=1, tp=2))
    tx = make_optimizer(1e-2, warmup_steps=1, decay_steps=100)
    tokens = jax.random.randint(jax.random.key(0), (8, 16), 0, config.vocab_size)

    def init_fn(rng):
        params = model.init(rng, tokens)["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, jax.random.key(1), mesh)
    step = make_lm_train_step(mesh)
    state, m1 = step(state, tokens)
    state, m2 = step(state, tokens)
    assert np.isfinite(float(m2["loss"]))


def test_image_train_resnet_with_batchstats():
    model = resnet18_thin(num_classes=10)
    mesh = create_mesh(MeshConfig(dp=8))
    tx = make_optimizer(1e-2, warmup_steps=1, decay_steps=100)
    images = jax.random.normal(jax.random.key(0), (8, 32, 32, 3))
    labels = jnp.arange(8) % 10

    def init_fn(rng):
        variables = model.init(rng, images, train=True)
        return TrainState.create(
            apply_fn=model.apply,
            params=variables["params"],
            batch_stats=variables["batch_stats"],
            tx=tx,
        )

    state, _ = create_sharded_state(init_fn, jax.random.key(1), mesh)
    step = make_image_train_step(mesh)
    state, m = step(state, images, labels)
    assert np.isfinite(float(m["loss"]))
    # BN stats must actually update
    stats0 = jax.tree_util.tree_leaves(state.batch_stats)
    assert any(float(jnp.abs(s).sum()) > 0 for s in stats0)


def test_mnist_train_no_batchstats():
    model = MnistCnn()
    mesh = create_mesh(MeshConfig(dp=8))
    tx = make_optimizer(1e-3, warmup_steps=1, decay_steps=100)
    images = jax.random.normal(jax.random.key(0), (16, 28, 28, 1))
    labels = jnp.arange(16) % 10

    def init_fn(rng):
        params = model.init(rng, images)["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state, _ = create_sharded_state(init_fn, jax.random.key(1), mesh)

    def apply_no_train(variables, images, train=True):
        return model.apply(variables, images)

    state = state.replace(apply_fn=apply_no_train)
    step = make_image_train_step(mesh)
    losses = []
    for _ in range(5):
        state, m = step(state, images, labels)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_next_token_loss_is_log_softmax_at_the_targets(dtype):
    """``next_token_loss`` reads the target's logit from the logits and
    subtracts it from logsumexp, so that no float32 log-probability
    outlives the head: the value and the gradient are those of the
    gather from ``log_softmax``."""
    from kubeflow_tpu.train import next_token_loss

    logits = (4.0 * jax.random.normal(jax.random.key(0), (3, 17, 97))
              ).astype(dtype)
    tokens = jax.random.randint(jax.random.key(1), (3, 17), 0, 97)

    def plain(x):
        logp = jax.nn.log_softmax(x[:, :-1].astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(ll)

    want, want_g = jax.value_and_grad(plain)(logits)
    got, got_g = jax.value_and_grad(
        lambda x: next_token_loss(x, tokens))(logits)
    assert got.dtype == jnp.float32 and got_g.dtype == dtype
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    # one bfloat16 step at the largest gradient (1/48 a token)
    atol = 1e-8 if dtype == jnp.float32 else 2.0 ** -13
    np.testing.assert_allclose(np.asarray(got_g, np.float32),
                               np.asarray(want_g, np.float32), atol=atol)
    assert not np.any(np.asarray(got_g[:, -1], np.float32))


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_chunked_loss_matches_full_logits_path():
    """chunked_next_token_loss from hidden states must equal
    next_token_loss on the model's logits — value AND parameter
    gradients — including ragged S-1 vs chunk and a softcap."""
    import numpy as np

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.train import chunked_next_token_loss, next_token_loss

    config = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=24, dtype=jnp.float32,
        param_dtype=jnp.float32, logits_softcap=20.0, remat=False)
    full = Transformer(config)
    hid = Transformer(config, return_hidden=True)
    tokens = jax.random.randint(jax.random.key(0), (2, 24), 0, 64)
    params = full.init(jax.random.key(1), tokens)["params"]

    def loss_full(p):
        return next_token_loss(full.apply({"params": p}, tokens), tokens)

    def loss_chunked(p):
        h = hid.apply({"params": p}, tokens)
        # chunk 8 does not divide S-1=23: exercises the pad+mask path
        return chunked_next_token_loss(h, p["token_embed"], tokens,
                                       chunk=8, softcap=20.0)

    lf, gf = jax.value_and_grad(loss_full)(params)
    lc, gc = jax.value_and_grad(loss_chunked)(params)
    np.testing.assert_allclose(float(lc), float(lf), rtol=1e-6)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(gf)[0],
            jax.tree_util.tree_flatten_with_path(gc)[0]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-5, err_msg=str(pa))


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_lm_train_step_loss_chunk_mode():
    """make_lm_train_step(loss_chunk=): same loss trajectory as the
    full-logits step on the virtual mesh."""
    import numpy as np

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.parallel import MeshConfig, create_mesh

    config = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=16, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    tokens = jax.random.randint(jax.random.key(0), (4, 16), 0, 64)
    mesh = create_mesh(MeshConfig(dp=2, tp=4))
    tx = make_optimizer(1e-3, warmup_steps=2, decay_steps=10)

    def mk(model, **kw):
        params = Transformer(config).init(jax.random.key(1),
                                          tokens[:2])["params"]
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=tx)
        return state, make_lm_train_step(mesh, **kw)

    s1, step1 = mk(Transformer(config))
    s2, step2 = mk(Transformer(config, return_hidden=True), loss_chunk=8)
    for _ in range(3):
        s1, m1 = step1(s1, tokens)
        s2, m2 = step2(s2, tokens)
        np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]),
                                   rtol=1e-5)
