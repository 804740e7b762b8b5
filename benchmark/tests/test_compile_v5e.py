"""Compile-only checks at published widths for a described TPU v5e.

Nothing runs and no chip is needed: the TPU compiler is installed here
and compiles for a chip that is described (``v5e:2x2``), which shows what
the interpreter cannot (Mosaic refusals, programs that do not fit 16 GB).
The topology is described inside a fixture and every test lives in this
one file, so that under several test workers only the worker given this
file loads the TPU library.

    python -m pytest benchmark/tests/test_compile_v5e.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HBM = 16e9


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _total_bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return float(m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _serve_shapes(one_chip):
    import jax
    import jax.numpy as jnp

    from benchmark.harness import adapter
    from benchmark.harness import weights as W

    cfg = _cfg("smollm2-1.7b")
    pc = adapter.program_config(cfg, dtype=jnp.bfloat16,
                                param_dtype=jnp.bfloat16)
    params = _abstract(jax.eval_shape(
        lambda k: adapter.to_program_params(
            W.init_weights(cfg, k, jnp.bfloat16), cfg),
        jax.random.key(0)), one_chip)
    return cfg, pc, params


def test_decode_step_fits(one_chip, no_cache):
    """The engine's K-step greedy scan over the configured slots x 1024
    positions (a bf16 cache of head size 64 pads 2x in HBM)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import decode_step, prefill

    cfg, pc, params = _serve_shapes(one_chip)
    eng = cfg["assumed"]["engine"]
    slots, k = eng["slots"], eng["steps_per_sync"]
    cache = _abstract(jax.eval_shape(
        lambda p: prefill(pc, p, jnp.zeros((slots, 1), jnp.int32))[1],
        params), one_chip)
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)

    def step(params, cache, tokens):
        def body(carry, _):
            cache, tokens = carry
            logits, cache = decode_step(pc, params, cache, tokens)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt
        (cache, _), out = jax.lax.scan(body, (cache, tokens), None, length=k)
        return cache, out

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, toks).compile()
    assert _total_bytes(compiled) < HBM


def test_batch_prefill_8x1024_fits_beside_the_cache(one_chip, no_cache):
    """The widest admission: 8 prompts of the 1024 bucket, while weights
    (3.4 GB, an argument) and the engine cache (6.4 GB) are resident."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import prefill

    cfg, pc, params = _serve_shapes(one_chip)
    toks = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t, n: prefill(pc, p, t, n)).lower(
        params, toks, lens).compile()
    slots = cfg["assumed"]["engine"]["slots"]
    lanes = 128                      # head size 64 pads to the 128-lane tile
    engine_cache = slots * 1024 * 2 * 24 * 32 * lanes * 2
    assert _total_bytes(compiled) + engine_cache < HBM


def test_train_step_2x8192_fits(one_chip, no_cache):
    """The 360M train step as the driver builds it (flash kernels, remat,
    f32 AdamW), at 2 x 8192 tokens."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import adapter
    from benchmark.harness import weights as W
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.train.trainer import (
        TrainState, make_optimizer, next_token_loss,
    )

    cfg = _cfg("smollm2-360m")
    tr = cfg["assumed"]["train"]
    opt = tr["optimizer"]
    pc = adapter.program_config(
        cfg, dtype=jnp.bfloat16, param_dtype=jnp.float32,
        attention_impl=tr["attention_impl"], remat=tr["remat"],
        max_seq_len=8192)
    model = Transformer(pc)
    tx = make_optimizer(opt["learning_rate"],
                        warmup_steps=opt["warmup_steps"],
                        decay_steps=opt["decay_steps"])

    def init(key):
        params = adapter.to_program_params(
            W.init_weights(cfg, key, jnp.float32), cfg)
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state = _abstract(jax.eval_shape(init, jax.random.key(0)), one_chip)
    toks = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def step(state, tokens):
        def loss_fn(p):
            return next_token_loss(state.apply_fn({"params": p}, tokens),
                                   tokens)
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    # the flash kernels pick the interpreter off the TPU backend; compile
    # the kernels themselves for the described chip
    import kubeflow_tpu.ops.attention as att
    real = att.resolve_interpret
    att.resolve_interpret = lambda interpret: False
    try:
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, toks).compile()
    finally:
        att.resolve_interpret = real
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM
