"""The plain reference against the program's own model at a toy size (both
float32): logits, and three optimizer steps against optax."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import adapter, check  # noqa: E402
from benchmark.harness import weights as W  # noqa: E402

CFG = dict(hidden_size=64, intermediate_size=160, num_hidden_layers=3,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           vocab_size=128, max_position_embeddings=32, rope_theta=100000,
           rms_norm_eps=1e-6, tie_word_embeddings=True,
           assumed=dict(initializer_range=0.2, norm_weight_jitter=0.05))
OPT = dict(learning_rate=3e-4, warmup_steps=5, decay_steps=10000,
           weight_decay=0.1, b1=0.9, b2=0.95, eps=1e-8, grad_clip=1.0)


def _setup():
    from kubeflow_tpu.models.transformer import Transformer

    ref = check.load_reference()
    w = W.init_weights(CFG, W.seed_key(2 ** 31 + 5), jnp.float32)
    pc = adapter.program_config(CFG, dtype=jnp.float32, remat=False)
    return ref, w, Transformer(pc)


def test_layout_round_trip():
    _ref, w, _model = _setup()
    back = adapter.from_program_params(adapter.to_program_params(w, CFG), CFG)
    assert all(bool(jnp.all(back[k] == w[k])) for k in w)


def test_logits_match_the_program():
    ref, w, model = _setup()
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, 128)
    with jax.default_matmul_precision("highest"):
        prog = model.apply({"params": adapter.to_program_params(w, CFG)},
                           toks)
    mine = ref.logits_at(w, toks, jnp.arange(32), CFG)
    assert float(jnp.std(mine)) > 0.5          # logits are not flat
    assert float(jnp.max(jnp.abs(prog - mine))) < 1e-4


def test_three_steps_match_optax():
    import optax

    from kubeflow_tpu.train.trainer import make_optimizer, next_token_loss

    ref, w, model = _setup()
    tx = make_optimizer(OPT["learning_rate"],
                        warmup_steps=OPT["warmup_steps"],
                        decay_steps=OPT["decay_steps"])
    params = adapter.to_program_params(w, CFG)
    st = tx.init(params)
    rw, rs = w, ref.adamw_init(w)
    for i in range(3):
        t = jax.random.randint(jax.random.key(10 + i), (2, 32), 0, 128)
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(lambda p: next_token_loss(
                model.apply({"params": p}, t), t))(params)
        up, st = tx.update(g, st, params)
        params = optax.apply_updates(params, up)
        rw, rs, rloss, rnorm, _g = ref.train_step(rw, rs, t, CFG, OPT)
        assert abs(float(loss) - float(rloss)) < 1e-5
        assert abs(float(optax.global_norm(g)) - float(rnorm)) < 1e-4
    back = adapter.from_program_params(params, CFG)
    moved = max(float(jnp.max(jnp.abs(back[k] - w[k]))) for k in w)
    apart = max(float(jnp.max(jnp.abs(back[k] - rw[k]))) for k in w)
    assert moved > 1e-5 and apart < 1e-6


def test_blocked_paths_match_the_unblocked():
    """Query blocks and loss blocks change nothing but memory."""
    ref, w, _model = _setup()
    toks = jax.random.randint(jax.random.key(2), (2, 32), 0, 128)
    whole = ref.next_token_loss(w, toks, CFG)
    ref.Q_BLOCK, ref.LOSS_BLOCK, keep = 8, 8, (ref.Q_BLOCK, ref.LOSS_BLOCK)
    try:
        blocked, grads = ref.loss_and_grads(w, toks, CFG)
    finally:
        ref.Q_BLOCK, ref.LOSS_BLOCK = keep
    assert abs(float(whole) - float(blocked)) < 1e-5
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref_n = {"embed": np.float32(10.0), "final_norm": np.float32(1e-9),
             "wq": np.asarray([1.0, 2.0, 3.0], np.float32)}
    prog = {"embed": np.float32(10.0), "final_norm": np.float32(0.5),
            "wq": np.asarray([1.0, 2.2, 3.0], np.float32)}
    gap, leaf = check.worst_leaf_gap(prog, ref_n)
    # final_norm is all but zero in the reference: held against the median
    assert leaf == "final_norm" and abs(gap - 0.5 / 2.0) < 1e-6
    assert check.moving_leaves(ref_n) == {"embed", "wq.0", "wq.1", "wq.2"}
