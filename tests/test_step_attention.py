"""The one-token step attention kernel (``ops/step_attention.py``, run
in the Pallas interpreter here) against the XLA merged-lane products it
replaces (``transformer._merged_step_attention``), and the decoders and
engine around it: greedy parity with the XLA path and the round's
``kv_blocks_read`` / ``kv_blocks_reserved``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import Transformer, TransformerConfig
from kubeflow_tpu.models.decode import decode_step_stats, prefill
from kubeflow_tpu.models.hybrid import HybridConfig, HybridDecoder
from kubeflow_tpu.models.transformer import _merged_step_attention
from kubeflow_tpu.ops import autotune
from kubeflow_tpu.ops import step_attention as sa
from kubeflow_tpu.serving.engine import DecodeEngine

# (KH, G, Dh): SmolLM2-1.7B's 32 heads of 64 over 32 KV heads (2048
# lanes) and Nemotron-3-Nano's 32 heads of 128 over 2 (256 lanes)
LAYOUTS = {"mha-32x64": (32, 1, 64), "gqa-32of2x128": (2, 16, 128)}
SMAX, BLOCK = 256, 64
# a row at 0, at a block's last and first position, at Smax - 1, and
# three idle rows (at Smax and past it)
POSITIONS = [0, BLOCK - 1, BLOCK, SMAX - 1, 130, SMAX, SMAX + 9, 2 * SMAX]


def _operands(KH, G, Dh, dtype, layers=3, seed=0):
    B = len(POSITIONS)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (B, KH, G, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (layers, B, SMAX, KH * Dh),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (layers, B, SMAX, KH * Dh),
                          jnp.float32).astype(dtype)
    return q, k, v, jnp.asarray(POSITIONS, jnp.int32)


def _reference(q, k, v, layer, pos):
    mask = jnp.arange(SMAX)[None, :] <= pos[:, None]
    return _merged_step_attention(q, k[layer], v[layer], mask)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_matches_the_merged_products_at_ragged_positions(layout,
                                                                dtype):
    """Every live row as the XLA form gives it, whatever its position
    against the blocks; an idle row (position >= Smax) reads nothing and
    gives zeros, not 0 / 0. Two programs of four rows: a program mixes
    live and idle rows."""
    q, k, v, pos = _operands(*LAYOUTS[layout], dtype)
    out = sa.step_attention(q, k, v, 1, pos, block_rows=4, block_len=BLOCK)
    ref = _reference(q, k, v, 1, pos)
    assert out.shape == ref.shape and out.dtype == q.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    live = np.asarray(POSITIONS) < SMAX
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert np.all(out[~live] == 0)
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_traced_layer_of_a_scan_and_a_static_layer(layout):
    """The dense decoder's layer is a traced scan index, the hybrid
    decoder's a Python int: both read the same layer of the stack."""
    q, k, v, pos = _operands(*LAYOUTS[layout], jnp.float32)

    def call(layer):
        return sa.step_attention(q, k, v, layer, pos, block_rows=8,
                                 block_len=BLOCK)

    _, scanned = jax.lax.scan(lambda c, i: (c, call(i)), 0,
                              jnp.arange(k.shape[0]))
    live = np.asarray(POSITIONS) < SMAX
    for layer in range(k.shape[0]):
        ref = np.asarray(_reference(q, k, v, layer, pos))[live]
        np.testing.assert_allclose(np.asarray(scanned[layer])[live], ref,
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(call(layer))[live], ref,
                                   atol=2e-5, rtol=2e-5)


def test_blocks_read_are_the_rows_own_and_the_rule_tiles_the_served_shapes():
    read = sa.live_blocks(jnp.asarray(POSITIONS), SMAX, BLOCK)
    assert read.tolist() == [1, 1, 2, 4, 3, 0, 0, 0]
    n_read, reserved = sa.block_counts(jnp.asarray(POSITIONS), SMAX, BLOCK)
    assert (int(n_read), int(reserved)) == (11, len(POSITIONS) * 4)
    # the two served shapes: SmolLM2-1.7B's 8 rows x 1024 x 2048 lanes,
    # Nemotron-3-Nano's 128 x 8192 x 256
    for rows, smax, kh, dh in ((8, 1024, 32, 64), (128, 8192, 2, 128)):
        cfg = autotune.resolve_step_attention(
            rows=rows, max_seq_len=smax, lanes=kh * dh, n_heads=32,
            head_dim=dh, dtype=jnp.bfloat16)
        block_rows, block_len = cfg.tiling
        assert rows % block_rows == 0 and smax % block_len == 0
        assert block_len % autotune.SUBLANE_FLOOR["bfloat16"] == 0
        assert autotune.step_vmem_bytes(block_rows, block_len, kh * dh, 32,
                                        dh, 2) <= autotune.STEP_VMEM_BYTES
        assert cfg.source == "fallback"
        assert block_len >= autotune.LANE_MULTIPLE
        assert block_rows <= autotune.STEP_MAX_ROWS
    # narrow rows fit VMEM many to a program; the semaphores cap them
    narrow = autotune.resolve_step_attention(
        rows=256, max_seq_len=128, lanes=128, n_heads=8, head_dim=128,
        dtype=jnp.bfloat16)
    assert narrow.tiling == (autotune.STEP_MAX_ROWS, 128)
    pinned = autotune.resolve_step_attention(
        rows=8, max_seq_len=1024, lanes=2048, n_heads=32, head_dim=64,
        dtype=jnp.bfloat16, tiling=(2, 128))
    assert (pinned.tiling, pinned.source) == ((2, 128), "override")


# -- the decoders and the engine ------------------------------------------------

@pytest.fixture
def small_blocks(monkeypatch):
    """Programs of two rows and blocks of 8 positions at the toy widths
    (the rule would give one program and one block), so that a row's
    step reads several blocks and a short row fewer than a long one."""
    monkeypatch.setattr(sa, "tiling", lambda rows, *_: (min(rows, 2), 8))


def _steer(monkeypatch, kernel: bool):
    """The decoders' step takes the kernel (in the interpreter here) or
    the XLA products: what the backend decides on a chip."""
    monkeypatch.setattr(sa, "use_kernel", lambda: kernel)


def _dense():
    cfg = TransformerConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=64, max_seq_len=48,
                            dtype=jnp.float32, remat=False)
    params = Transformer(cfg).init(jax.random.key(0),
                                   np.zeros((1, 8), np.int32))["params"]
    return cfg, params


def _hybrid():
    # two attention blocks (layers 0 and 1 of the stacked leaves) and a
    # routed block between them
    cfg = HybridConfig(vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, layer_types=("gqa", "moe", "gqa"),
                       first_k_dense=0, max_seq_len=64, n_experts=8,
                       experts_per_token=2, n_group=1, topk_group=1,
                       d_expert=32, d_shared=32, expert_act="relu2",
                       dtype=jnp.float32, param_dtype=jnp.float32)
    params = HybridDecoder(cfg).init(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32),
        jnp.asarray([8]))["params"]
    return cfg, params


MODELS = {"dense": _dense, "hybrid-gqa": _hybrid}


@pytest.mark.parametrize("model", list(MODELS))
def test_step_counts_the_rows_live_blocks(model, small_blocks, monkeypatch):
    """One step from a cache whose rows stand at known positions: the
    model counts each row's live blocks (an idle row none) for one
    attention layer, and the step's logits are the XLA path's."""
    cfg, params = MODELS[model]()
    _steer(monkeypatch, True)
    B = 4
    lens = jnp.asarray([3, 8, 17, 1], jnp.int32)
    tokens = jnp.ones((B, 24), jnp.int32)
    _, cache = prefill(cfg, params, tokens, lens)
    # row 3 made idle: its position past max_seq_len
    cache = dict(cache, positions=cache["positions"].at[..., 3].set(
        cfg.max_seq_len + 5))
    logits, _, stats = decode_step_stats(cfg, params, cache,
                                         jnp.ones((B,), jnp.int32))
    assert set(stats) == {"kv_blocks_read", "kv_blocks_reserved"} | (
        {"experts_hit", "routed_pairs"} if model == "hybrid-gqa" else set())
    # blocks of 8: positions 3, 8, 17 read 1, 2, 3 blocks
    assert int(stats["kv_blocks_read"]) == 1 + 2 + 3
    assert int(stats["kv_blocks_reserved"]) == B * cfg.max_seq_len // 8
    _steer(monkeypatch, False)
    want, _, xstats = decode_step_stats(cfg, params, cache,
                                        jnp.ones((B,), jnp.int32))
    assert "kv_blocks_read" not in xstats
    np.testing.assert_allclose(np.asarray(logits)[:3],
                               np.asarray(want)[:3], atol=2e-4, rtol=2e-4)


def _serve(cfg, params, prompts, max_new, name):
    eng = DecodeEngine(cfg, params, slots=4, steps_per_sync=4,
                       autostart=False, name=name)
    try:
        handles = [eng.submit(p, max_new=n) for p, n in zip(prompts,
                                                            max_new)]
        for _ in range(200):
            eng.run_once(timeout=0.01)
            if not (eng.active_count or eng.pending_count):
                break
        return [h.result() for h in handles]
    finally:
        eng.close()


@pytest.mark.parametrize("model", list(MODELS))
def test_engine_greedy_streams_match_the_xla_path(model, small_blocks,
                                                  monkeypatch):
    """Six greedy requests of unequal length on four slots (slots used
    again, rows idle at the end): the same tokens through the kernel as
    through the XLA products. Each round of the kernel's engine carries
    ``kv_blocks_read`` <= ``kv_blocks_reserved`` = k x slots x blocks a
    row, and the counter holds their sums."""
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR
    from kubeflow_tpu.serving import engine as engine_mod

    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 90, n).tolist() for n in (5, 12, 3, 20, 9, 7)]
    max_new = [9, 4, 14, 6, 11, 5]
    streams = {}
    counter = engine_mod._step_attn_blocks_c
    name = f"step-attn-{model}"
    before = {w: counter.get(model=name, what=w)
              for w in ("read", "reserved")}
    cfg, params = MODELS[model]()
    for impl in ("xla", "kernel"):
        _steer(monkeypatch, impl == "kernel")
        streams[impl] = _serve(cfg, params, prompts, max_new,
                               name if impl == "kernel" else name + "-xla")
    assert streams["kernel"] == streams["xla"]
    assert [len(s) for s in streams["kernel"]] == max_new
    rounds = [sp.attrs for sp in DEFAULT_COLLECTOR.spans()
              if sp.name == "engine.round" and sp.attrs.get("model") == name
              and sp.attrs["k"]]
    assert rounds
    blocks = cfg.max_seq_len // 8
    for r in rounds:
        assert r["kv_blocks_reserved"] == r["k"] * 4 * blocks
        assert 0 < r["kv_blocks_read"] <= r["kv_blocks_reserved"]
    assert counter.get(model=name, what="read") - before["read"] == sum(
        r["kv_blocks_read"] for r in rounds)
    assert (counter.get(model=name, what="reserved") - before["reserved"]
            == sum(r["kv_blocks_reserved"] for r in rounds))
    xla_rounds = [sp.attrs for sp in DEFAULT_COLLECTOR.spans()
                  if sp.name == "engine.round"
                  and sp.attrs.get("model") == name + "-xla"]
    assert xla_rounds and not any("kv_blocks_read" in r for r in xla_rounds)


def test_kernel_on_a_tp_mesh_splits_whole_kv_heads(small_blocks, monkeypatch):
    """On a serving mesh the kernel runs in ``shard_kernel``'s
    shard_map, each tp rank over its own KV heads' lanes of the stack
    and their query heads (tp=2 over 2 KV heads): greedy tokens equal
    the unsharded XLA path's."""
    from conftest import shard_params
    from kubeflow_tpu.models.decode import generate
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import record_kernel_placements

    cfg, params = _dense()
    prompt = [5, 11, 17, 3, 2]
    _steer(monkeypatch, False)
    want = np.asarray(generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                               max_new_tokens=8))[0].tolist()
    _steer(monkeypatch, True)
    mesh = create_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    eng = DecodeEngine(cfg, shard_params(params, mesh), slots=2,
                       steps_per_sync=4, autostart=False, mesh=mesh)
    try:
        with record_kernel_placements() as placed:
            r = eng.submit(prompt, max_new=8)
            for _ in range(50):
                eng.run_once(timeout=0.01)
                if not (eng.active_count or eng.pending_count):
                    break
        assert r.result() == want
        assert eng.recoveries == 0
    finally:
        eng.close()
    assert {"kernel": "step_attention", "devices": 2,
            "split": {"heads": 2}, "dropped": []} in placed
