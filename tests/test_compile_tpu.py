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


# The hybrid decoder (kubeflow_tpu/models/hybrid.py) at Ling-3.0-flash's
# published widths, one layer of each kind: a dense-MLP KDA layer, a
# routed MLA layer, a routed KDA layer; 128 of 512 experts held
HYBRID = dict(vocab_size=39296, d_model=2560, n_heads=32, head_dim=128,
              layer_types=("kda", "mla", "kda"), first_k_dense=1, d_ff=6144,
              max_seq_len=4096, kv_lora_rank=512, qk_nope_dim=128,
              qk_rope_dim=64, v_head_dim=128, rope_theta=6e6, n_experts=512,
              experts_per_token=8, n_group=8, topk_group=4, d_expert=768,
              d_shared=768, experts_held=(0, 128))
HYBRID_SLOTS = 32


def test_hybrid_k_step_program_rewrites_no_leaf_whole(one_chip, no_cache,
                                                      monkeypatch):
    """The K-step program of the hybrid decoder, on the TPU's own
    compiler: no stacked cache leaf (``kda_state``, ``kda_conv``,
    ``latent``) is copied, inside the loops or at the program's boundary,
    and the float32 state is written by nothing but the ``kda.step``
    kernel, in place (one call a KDA layer). The latent leaf's last axis
    is padded to whole lanes for this: at 576 the chip's default layout
    differs from the step's and the leaf was re-laid twice a round."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import decode_step_stats, prefill
    from kubeflow_tpu.models.hybrid import HybridConfig, HybridDecoder
    from kubeflow_tpu.ops import kda

    # this process's backend is the CPU: compile the kernel itself, not
    # its interpreter
    monkeypatch.setattr(kda, "resolve_interpret", lambda interpret: False)
    cfg = HybridConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                       **HYBRID)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: HybridDecoder(cfg).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"])
    cache = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda p: prefill(cfg, p, jnp.zeros((HYBRID_SLOTS, 1), jnp.int32))[1],
        params))
    tokens = place(jax.ShapeDtypeStruct((HYBRID_SLOTS,), jnp.int32))

    def step(params, cache, tokens):
        def body(carry, _):
            cache, tokens = carry
            logits, cache, stats = decode_step_stats(cfg, params, cache,
                                                     tokens)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt), (nxt, stats)
        (cache, _), out = jax.lax.scan(body, (cache, tokens), None,
                                       length=K)
        return cache, out

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile().as_text()
    shapes = {name: "%s[%s]" % ({"float32": "f32", "bfloat16": "bf16"}[
        str(leaf.dtype)], ",".join(map(str, leaf.shape)))
        for name, leaf in cache.items() if name != "positions"}
    assert shapes["latent"] == "bf16[1,32,4096,640]"
    for name, shape in shapes.items():
        assert not re.findall(rf"= {re.escape(shape)}\S* copy\(", text), name
    state = re.escape(shapes["kda_state"])
    writers = {re.search(r"\s([a-z][a-z0-9\-]*)\(", line).group(1)
               for line in text.splitlines()
               if re.search(rf" = \(?{state}", line)}
    assert writers - {"parameter", "get-tuple-element"} == {"custom-call"}
    assert len(re.findall(r"%kda\.step[\w.]* = ", text)) == cfg.n_kda
