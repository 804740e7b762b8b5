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

import numpy as np
import pytest

# SmolLM2-1.7B's attention width (32 heads of 64: a head is under one
# 128-lane tile, which is what made the chip's default layout of a
# ``(…, KH, Dh)`` leaf differ from the one the step wants) at 4 layers,
# and the same width as 16 heads of 128
GEOMETRY = dict(vocab_size=49152, d_model=2048, n_layers=4, d_ff=8192,
                max_seq_len=1024, remat=False)
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


def _k_step_text(one_chip, n_heads: int):
    """The engine's K-step greedy program compiled for the described
    chip, as text, and the cache's ``k`` leaf. Every argument is a bare
    shape: the cache arrives and leaves in the chip's default layout and
    nothing is pinned."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.models.decode import decode_step, prefill

    cfg = TransformerConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                            n_heads=n_heads, n_kv_heads=n_heads, **GEOMETRY)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: Transformer(cfg).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"])
    cache = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda p: prefill(cfg, p, jnp.zeros((SLOTS, 1), jnp.int32))[1],
        params))
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

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile().as_text()
    return text, cache["k"]


_RESULT = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]*)\]\S* "
                     r"([a-z][a-z\-]*)\(")


def _cache_sized_results(text: str, leaf):
    """What the program makes of the stacked K/V leaf's size or of one
    layer's slice of it: ``(relayouts inside the loops, relayouts in the
    entry computation, slices a loop body materializes)``. A relayout is
    a ``copy`` or ``transpose`` with such a result, wherever it stands (a
    fusion's body too); a materialized slice is any instruction of a
    computation that is no fusion's body and not the entry, so a loop's
    body or condition, whose bfloat16 result has one layer's elements.
    Only results that keep the leaf's rows and positions as dimensions
    count. The stack's in-place writers give results of the stack's size
    and are no relayout."""
    stack = int(np.prod(leaf.shape))
    one_layer = stack // leaf.shape[0]
    relayouts = {False: [], True: []}
    slices, entry, fused = [], False, False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            entry = line.startswith("ENTRY ")
            fused = "fused_computation" in line.split(" ", 1)[0]
            continue
        m = _RESULT.match(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        size = int(np.prod(dims))
        if not set(leaf.shape[1:3]) <= set(dims):
            continue    # no rows x positions: a weight of the same size
        if size in (stack, one_layer) and m.group(2) in ("copy",
                                                          "transpose"):
            relayouts[entry].append(line.strip())
        elif (size == one_layer and not entry and not fused
              and m.group(2) not in ("parameter", "get-tuple-element")):
            slices.append(line.strip())
    return relayouts[False], relayouts[True], slices


def _step_attention_calls(text):
    """The result shapes of ``ops/step_attention.py``'s calls."""
    return re.findall(
        r"%step_attention[\w.]* = (\w+\[[\d,]*\])\S* custom-call\(", text)


@pytest.mark.parametrize("n_heads", [32, 16], ids=["head64", "head128"])
def test_k_step_program_relays_no_stack_and_no_layer_slice(
        one_chip, no_cache, monkeypatch, n_heads):
    """On the TPU's own compiler, the cache in the chip's default layout
    and nothing pinned: the K-step program copies or transposes neither
    the stacked K/V nor one layer's slice of it, inside its loops or at
    its boundary, and no loop body materializes a slice (the attention's
    two products read K and V where they lie). At head size 64 the
    ``(…, KH, Dh)`` leaf failed this twice: its default layout put the
    positions minor-most, so the program re-laid K and V out on entry and
    on exit (four whole-stack copies a round, PR 28), and inside a
    program a head of 64 is padded to the 128-lane tile. The leaf's
    declared shape is the lever (``TransformerConfig.cache_leaves``: KV
    heads merged on the last axis), since a layout pinned through
    ``jax.jit`` does not survive the persistent compile cache (jax
    0.9.0). Head size 128 at the same width is the same leaf. The step
    attention kernel reads the stack where it lies (one call in the
    layer loop's body, the layer by scalar prefetch): no copy of one
    layer's K or V slice stands anywhere in the program."""
    _compile_kernels(monkeypatch)
    text, leaf = _k_step_text(one_chip, n_heads)
    assert leaf.shape == (GEOMETRY["n_layers"], SLOTS,
                          GEOMETRY["max_seq_len"], GEOMETRY["d_model"])
    in_loops, at_the_boundary, slices = _cache_sized_results(text, leaf)
    assert (in_loops, at_the_boundary, slices) == ([], [], [])
    one_layer = "bf16[%d,%d,%d]" % leaf.shape[1:]
    assert not re.findall(rf"= {re.escape(one_layer)}\S* copy\(", text)
    assert _step_attention_calls(text) == [
        "bf16[%d,%d,%d]" % (SLOTS, n_heads, GEOMETRY["d_model"] // n_heads)]


def _grouped_products(text, cfg):
    """The result shapes of the grouped matmul's calls in a compiled
    text (``ops/gmm.py``; a Mosaic call is named by its scope, and the
    benchmark's readers find the routed experts' products by
    ``%ragged-dot*``). No tensor of the held experts may be copied on
    its way there: the kernel reads it as the chip lays it out."""
    assert "ragged_dot_tiling" not in text      # the compiler's own is gone
    (_, n), d, f = cfg.held, cfg.d_model, cfg.d_expert
    assert not re.findall(
        rf"= bf16\[{n},(?:{d},{f}|{f},{d})\]\S* copy\(", text)
    return [tuple(map(int, dims.split(","))) for dims in re.findall(
        r"%ragged-dot\.gmm[\w.]* = f32\[([\d,]+)\]\S* custom-call\(", text)]


def _compile_kernels(monkeypatch, *modules):
    """This process's backend is the CPU: compile the kernels themselves,
    not their interpreter, and take the paths a chip takes."""
    from kubeflow_tpu.ops import gmm, step_attention

    for module in modules + (gmm, step_attention):
        monkeypatch.setattr(module, "resolve_interpret",
                            lambda interpret: False)
    monkeypatch.setattr(step_attention, "use_kernel", lambda: True)


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


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_hybrid_k_step_program_rewrites_no_leaf_whole(one_chip, no_cache,
                                                      monkeypatch, program):
    """The K-step program of the hybrid decoder, on the TPU's own
    compiler: no stacked cache leaf (``kda_state``, ``kda_conv``,
    ``latent``) is copied, inside the loops or at the program's boundary,
    and the float32 state is written by nothing but the ``kda.step``
    kernel, in place (one call a KDA layer). The latent leaf's last axis
    is padded to whole lanes for this: at 576 the chip's default layout
    differs from the step's and the leaf was re-laid twice a round. The
    routed layers' products are the grouped matmul's calls, three a
    layer, of slots x 8 rows in the step and of 2 x 1024 x 8 in a batch
    prefill (also compiled here)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import decode_step_stats, prefill
    from kubeflow_tpu.models.hybrid import HybridConfig, HybridDecoder
    from kubeflow_tpu.ops import kda

    _compile_kernels(monkeypatch, kda)
    cfg = HybridConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                       **HYBRID)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: HybridDecoder(cfg).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"])
    if program == "prefill":
        text = jax.jit(lambda p, t, n: prefill(cfg, p, t, n)).lower(
            params, place(jax.ShapeDtypeStruct((2, 1024), jnp.int32)),
            place(jax.ShapeDtypeStruct((2,), jnp.int32))).compile().as_text()
        assert _grouped_products(text, cfg) == [
            (2 * 1024 * 8, 768), (2 * 1024 * 8, 768), (2 * 1024 * 8, 2560)] * 2
        return
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
    rows = HYBRID_SLOTS * cfg.experts_per_token
    assert _grouped_products(text, cfg) == [(rows, 768), (rows, 768),
                                       (rows, 2560)] * 2


# DeepSeek-V3.2's sparse latent attention (kubeflow_tpu/models/hybrid.py
# "dsa" layers, kubeflow_tpu/ops/dsa.py) at the published widths, one
# dense-MLP layer and one routed: 16 of 256 experts held, 16 slots x 32768
DSA = dict(vocab_size=16160, d_model=7168, n_heads=128,
           layer_types=("dsa", "dsa"), first_k_dense=1, d_ff=18432,
           max_seq_len=32768, q_lora_rank=1536, kv_lora_rank=512,
           qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
           use_qk_norm=False, head_gate=False, rope_theta=10000.0,
           rope_factor=40.0, rope_original_len=4096,
           rope_mscale_all_dim=1.0, index_n_heads=64, index_head_dim=128,
           index_topk=2048, prefill_chunk=1024, n_experts=256,
           experts_per_token=8, n_group=8, topk_group=4, d_expert=2048,
           d_shared=2048, experts_held=(0, 16))
DSA_SLOTS = 16


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_sparse_attention_programs_compile_with_three_kernels_a_layer(
        one_chip, no_cache, monkeypatch, program):
    """The K-step program and the admission chunk of 1024 tokens, on the
    TPU's own compiler: Mosaic takes the index, select and attend kernels
    at the published shapes (one call each a layer), there is no sort of
    a row's 32768 scores (``lax.top_k`` lowered to one, 3 ms a layer), and
    the step copies no stacked cache leaf whole (the chunk program, whose
    row is not donated because a stored prefix row must outlive it, copies
    each leaf once on the way in: 0.25 GB of a 1-row cache)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import (
        decode_step_stats,
        prefill,
        prefill_continue,
    )
    from kubeflow_tpu.models.hybrid import HybridConfig, HybridDecoder
    from kubeflow_tpu.ops import dsa

    _compile_kernels(monkeypatch, dsa)
    cfg = HybridConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **DSA)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: HybridDecoder(cfg).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"])

    def cache_of(rows):
        return jax.tree_util.tree_map(place, jax.eval_shape(
            lambda p: prefill(cfg, p, jnp.zeros((rows, 1), jnp.int32))[1],
            params))

    if program == "step":
        cache = cache_of(DSA_SLOTS)

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
            params, cache,
            place(jax.ShapeDtypeStruct((DSA_SLOTS,), jnp.int32))
        ).compile().as_text()
        rows = DSA_SLOTS
    else:
        cache = cache_of(1)
        one = place(jax.ShapeDtypeStruct((1,), jnp.int32))
        text = jax.jit(
            lambda p, c, t, n, total: prefill_continue(cfg, p, c, t, n, total)
        ).lower(params, cache,
                place(jax.ShapeDtypeStruct((1, cfg.prefill_chunk),
                                           jnp.int32)), one, one
                ).compile().as_text()
        rows = 1
    assert set(cache) == {"positions", "latent", "index_k"}
    for kernel in ("index", "select", "attend"):
        calls = re.findall(rf"%dsa\.{kernel}[\w.]* = \S+ custom-call\(", text)
        assert len(calls) == cfg.n_dsa, (kernel, calls)
    assert not re.findall(r"= \(f32\[\d+,\d+,32768\]\S*, s32\S*\) sort\(",
                          text)
    for leaf in (f"bf16[2,{rows},32768,640]", f"bf16[2,{rows},32768,128]"):
        copies = re.findall(rf"= {re.escape(leaf)}\S* copy\(", text)
        assert len(copies) <= (program == "chunk"), (leaf, copies)
    # the one routed layer's three products, over the pairs of 16 slots'
    # tokens or of the chunk's 1024
    pairs = (DSA_SLOTS if program == "step" else cfg.prefill_chunk) * 8
    assert _grouped_products(text, cfg) == [(pairs, 2048), (pairs, 2048),
                                       (pairs, 7168)]


# Nemotron-3-Nano's one-sublayer blocks (kubeflow_tpu/models/hybrid.py
# "ssm", "gqa" and "moe" blocks, kubeflow_tpu/ops/ssm.py) at the published
# widths, one block of each kind: 32 of 128 experts held, 128 slots x 8192
SSM = dict(vocab_size=32768, d_model=2688, n_heads=32, n_kv_heads=2,
           head_dim=128, layer_types=("ssm", "moe", "gqa"), first_k_dense=0,
           norm_eps=1e-5, max_seq_len=8192, ssm_heads=64, ssm_head_dim=64,
           ssm_state=128, ssm_groups=8, ssm_chunk=128, n_experts=128,
           experts_per_token=6, n_group=1, topk_group=1, d_expert=1856,
           d_shared=3712, expert_act="relu2", experts_held=(0, 32))
SSM_SLOTS = 128


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_state_space_programs_compile_with_the_step_kernel_in_place(
        one_chip, no_cache, monkeypatch, program):
    """The K-step program at 128 slots and a batch prefill of 4 rows x
    2048 tokens, on the TPU's own compiler: Mosaic takes the ``ssm.step``
    kernel at the published shapes (a row's 2 MB of state in, 2 MB out),
    the float32 state is written by nothing else, the step copies no
    stacked cache leaf whole (state, conv tail, K, V) nor one block's K or
    V slice (the step attention kernel reads the stack where it lies),
    and the prefill's temporaries fit beside the resident weights and
    cache."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import decode_step_stats, prefill
    from kubeflow_tpu.models.hybrid import HybridConfig, HybridDecoder
    from kubeflow_tpu.ops import ssm

    _compile_kernels(monkeypatch, ssm)
    cfg = HybridConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **SSM)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: HybridDecoder(cfg).init(k, jnp.zeros((1, 8), jnp.int32),
                                          jnp.asarray([8])),
        jax.random.key(0))["params"])
    if program == "prefill":
        compiled = jax.jit(lambda p, t, n: prefill(cfg, p, t, n)).lower(
            params, place(jax.ShapeDtypeStruct((4, 2048), jnp.int32)),
            place(jax.ShapeDtypeStruct((4,), jnp.int32))).compile()
        assert "ssm.step" not in compiled.as_text()
        assert _grouped_products(compiled.as_text(), cfg) == [
            (4 * 2048 * 6, 1856), (4 * 2048 * 6, 2688)]
        # 16 of the full model's 18 blocks' weights and the 128-slot cache
        # leave 5 GB of the chip
        assert compiled.memory_analysis().temp_size_in_bytes < 4e9
        return
    cache = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda p: prefill(cfg, p, jnp.zeros((SSM_SLOTS, 1), jnp.int32),
                          jnp.ones((SSM_SLOTS,), jnp.int32))[1], params))

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
        params, cache, place(jax.ShapeDtypeStruct((SSM_SLOTS,), jnp.int32))
    ).compile().as_text()
    shapes = {name: "%s[%s]" % ({"float32": "f32", "bfloat16": "bf16"}[
        str(leaf.dtype)], ",".join(map(str, leaf.shape)))
        for name, leaf in cache.items() if name != "positions"}
    assert shapes == {"ssm_state": "f32[1,128,32,128,128]",
                      "ssm_conv": "bf16[1,128,3,6144]",
                      "k": "bf16[1,128,8192,256]",
                      "v": "bf16[1,128,8192,256]"}
    # the conv tail of ONE block (4.7 MB) the compiler keeps in fast
    # memory through the K steps: a copy in and one out, at the boundary
    for name in ("ssm_state", "k", "v"):
        assert not re.findall(rf"= {re.escape(shapes[name])}\S* copy\(",
                              text), name
    assert not re.findall(r"= bf16\[128,8192,256\]\S* copy\(", text)
    assert _step_attention_calls(text) == ["bf16[128,32,128]"] * cfg.n_gqa
    # an expert is stored at the published 1856 columns and no expert
    # tensor is re-laid: the chip lays ``up_proj`` out column-major
    # (2688 along the lanes), under ``ragged_dot`` that was a copy of
    # 319 MB a round, and the grouped matmul reads it as it lies
    assert cfg.expert_width == 1856
    assert params["layer_1"]["mlp"]["up_proj"].shape == (32, 2688, 1856)
    assert re.search(r"bf16\[32,2688,1856\]\{1,2,0[:}]", text)
    assert _grouped_products(text, cfg) == [(SSM_SLOTS * 6, 1856),
                                       (SSM_SLOTS * 6, 2688)]
    state = re.escape(shapes["ssm_state"])
    writers = {re.search(r"\s([a-z][a-z0-9\-]*)\(", line).group(1)
               for line in text.splitlines()
               if re.search(rf" = \(?{state}", line)}
    assert writers - {"parameter", "get-tuple-element"} == {"custom-call"}
    assert len(re.findall(r"%ssm\.step[\w.]* = ", text)) == cfg.n_ssm


@pytest.mark.parametrize("seq, heads", [(8192, 30), (32768, 2)],
                         ids=["pretrain-8k", "longest-fused-row"])
def test_fused_flash_backward_compiles_at_its_table_tile(one_chip, no_cache,
                                                         seq, heads):
    """The gradient of ``flash_attention`` at head size 64, on the TPU's
    own compiler: wherever the dQ row fits (``autotune.flash_bwd_fuses``)
    Mosaic takes the one kernel that returns dQ, dK and dV at the tile
    the table gives it, under the scope the kernel asks for (the row of
    8192 takes 24.1 MiB with 1024-edge tiles, past the default 16), and
    neither the dQ nor the dK/dV kernel is in the program."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import autotune
    from kubeflow_tpu.ops.attention import flash_attention

    x = jax.ShapeDtypeStruct((1, seq, heads, 64), jnp.bfloat16,
                             sharding=one_chip)

    def grads(q, k, v):
        # interpret=False: this process's backend is the CPU
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, None, None, None, False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    with autotune.record_resolutions() as rec:
        text = jax.jit(grads).lower(x, x, x).compile().as_text()
    assert {d["kernel"]: d["source"] for d in rec} == {
        "flash_fwd": "table", "flash_bwd_fused": "table"}
    t = rf"bf16\[{heads},{seq},64\]\S*"
    returns = [len(re.findall(t, outs)) for outs in re.findall(
        r"^\s*%[\w.\-]+ = (.*?) custom-call\(.*tpu_custom_call", text,
        flags=re.M)]
    # the forward (a tensor and its row statistics) and the fused call
    assert sorted(returns) == [1, 3]


# MiMo-V2-Flash's global and window layers (kubeflow_tpu/models/hybrid.py
# "attn" / "swa") at the published widths, one of each: the global layer
# with the dense MLP, the window layer routed (16 of 256 experts held);
# 32 slots x 32768, rings of 128
SWA = dict(vocab_size=19072, d_model=4096, n_heads=64, head_dim=192,
           v_head_dim=128, layer_types=("attn", "swa"), first_k_dense=1,
           norm_eps=1e-5, d_ff=16384, max_seq_len=32768, n_kv_heads=4,
           swa_kv_heads=8, rotary_dim=64, rope_theta=5e6,
           swa_rope_theta=1e4, window=128, attn_sinks=True,
           value_scale=0.707, prefill_chunk=2048, n_experts=256,
           experts_per_token=8, n_group=1, topk_group=1, routed_scaling=1.0,
           d_expert=2048, d_shared=0, experts_held=(0, 16))
SWA_SLOTS = 32


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_window_and_global_programs_compile_with_the_step_kernel(
        one_chip, no_cache, monkeypatch, program):
    """The K-step program at 32 slots and the admission chunk of 2048
    tokens, on the TPU's own compiler: the step attention kernel takes
    both kinds (a global layer's rows, qk 192 / v 128; a window layer's
    ring of 128 with its sinks), one call a layer; the step copies no
    stacked cache leaf whole; the chunk's temporaries (a global layer
    attends in key blocks of 512, never to a 2048 x 32768 score) stay
    under 1.5 GB beside the 1-row cache, which it copies once on the way
    in as DeepSeek-V3.2's chunk does."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.decode import (
        decode_step_stats,
        prefill,
        prefill_continue,
    )
    from kubeflow_tpu.models.hybrid import HybridConfig, HybridDecoder

    _compile_kernels(monkeypatch)
    cfg = HybridConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **SWA)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: HybridDecoder(cfg).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"])

    def cache_of(rows):
        return jax.tree_util.tree_map(place, jax.eval_shape(
            lambda p: prefill(cfg, p, jnp.zeros((rows, 1), jnp.int32))[1],
            params))

    if program == "step":
        rows = SWA_SLOTS

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

        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache_of(rows),
            place(jax.ShapeDtypeStruct((rows,), jnp.int32))).compile()
    else:
        rows = 1
        one = place(jax.ShapeDtypeStruct((1,), jnp.int32))
        compiled = jax.jit(
            lambda p, c, t, n, total: prefill_continue(cfg, p, c, t, n, total)
        ).lower(params, cache_of(rows),
                place(jax.ShapeDtypeStruct((1, cfg.prefill_chunk),
                                           jnp.int32)), one, one).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    assert _step_attention_calls(text) == (
        [f"bf16[{rows},64,128]"] * 2 if program == "step" else [])
    for leaf in (f"bf16[1,{rows},32768,768]", f"bf16[1,{rows},32768,512]",
                 f"bf16[1,{rows},128,1536]", f"bf16[1,{rows},128,1024]"):
        copies = re.findall(rf"= {re.escape(leaf)}\S* copy\(", text)
        assert len(copies) <= (program == "chunk" and "32768" in leaf), (
            leaf, copies)
