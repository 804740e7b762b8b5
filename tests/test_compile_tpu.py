"""Compile-only checks of the serving step for a described TPU v5e.

Nothing runs and no chip is needed: the TPU compiler is installed here
and compiles for a chip that is described (``v5e:2x2``), layouts
included, which the CPU backend cannot show. The topology is described
inside a fixture and every such test of ``tests/`` lives in this one
file, so that under several test workers only the worker given this file
loads the TPU library (``benchmark/tests/test_compile_v5e.py`` is the
benchmark's own, outside tier-1).
"""

import os
import re

import pytest

# SmolLM2-1.7B's attention geometry (head size 64: under one 128-lane
# tile, which is what makes the chip's default layout differ from the
# one the step wants) at 4 layers
GEOMETRY = dict(vocab_size=49152, d_model=2048, n_layers=4, n_heads=32,
                n_kv_heads=32, d_ff=8192, max_seq_len=1024, remat=False)
SLOTS, K = 8, 8


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

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _stack_copies(one_chip, row_major: bool):
    """How often the engine's K-step greedy program, compiled for the
    described chip, copies a buffer of the stacked cache's shape: (inside
    the loops, in the entry computation). The cache arrives in the chip's
    default layout or pinned row-major."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.models.decode import decode_step, prefill

    cfg = TransformerConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                            **GEOMETRY)

    def where(s, fmt):
        return (Format(Layout(tuple(range(len(s.shape)))), one_chip)
                if fmt else one_chip)

    def place(s, fmt=False):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=where(s, fmt))

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: Transformer(cfg).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"])
    cache = jax.tree_util.tree_map(
        lambda s: place(s, row_major),
        jax.eval_shape(lambda p: prefill(
            cfg, p, jnp.zeros((SLOTS, 1), jnp.int32))[1], params))
    tokens = place(jax.ShapeDtypeStruct((SLOTS,), jnp.int32))

    def step(params, cache, tokens):
        def body(carry, _):
            cache, tokens = carry
            logits, cache = decode_step(cfg, params, cache, tokens)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt
        (cache, _), out = jax.lax.scan(body, (cache, tokens), None,
                                       length=K)
        return cache, out

    out = (jax.tree_util.tree_map(lambda s: where(s, row_major), cache),
           None)
    text = jax.jit(step, donate_argnums=(1,), out_shardings=out).lower(
        params, cache, tokens).compile().as_text()
    stack = re.escape("bf16[%d,%d,%d,%d,%d]" % (
        cfg.n_layers, SLOTS, cfg.max_seq_len, cfg.n_kv_heads, cfg.head_dim))
    loops, _, entry = text.partition("\nENTRY ")
    copy = rf"= {stack}\S* copy\("
    return len(re.findall(copy, loops)), len(re.findall(copy, entry))


@pytest.mark.parametrize("row_major, at_the_boundary",
                         [(False, 4), (True, 0)],
                         ids=["chip_default", "row_major"])
def test_k_step_program_copies_no_stack_inside_its_loops(
        one_chip, no_cache, row_major, at_the_boundary):
    """On the TPU's own compiler: the K-step program copies no buffer of
    the stacked cache's shape inside its loops (PR 28; before it, K and V
    were copied on every step). What is left at head size 64 is outside
    them: the chip's default layout of such a leaf puts the positions
    minor-most, the loop wants the head minor-most, and the program
    re-lays K and V out on entry and on exit, four whole-cache copies a
    round (PERF.md section 5). The control pins the cache row-major, and
    those four go too: a layout that ``jax.jit`` cannot be given here,
    because an executable read back from the persistent compile cache
    forgets its result layout (jax 0.9.0), so the cure is a leaf whose
    last axis fills the lanes."""
    assert _stack_copies(one_chip, row_major) == (0, at_the_boundary)
