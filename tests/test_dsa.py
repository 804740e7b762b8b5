"""Sparse latent attention (DeepSeek-V3.2's MLA with a low-rank query and
the lightning indexer) against its plain float32 reference, at a tiny size
on the CPU with ``index_topk`` below the context so that selection bites:
each piece alone, the kernel against the formula, prefill -> chunked
continuation -> decode through ``latent`` and ``index_k``, the same through
DecodeEngine with chunked admission, and what the engine refuses.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import adapter_dsa, weights_dsa
from kubeflow_tpu.models import decode, hybrid
from kubeflow_tpu.ops import dsa
from kubeflow_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "deepseek_v32_f32.py")
    spec = importlib.util.spec_from_file_location("deepseek_v32_f32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
WIDTH = 64          # the tiny context, and the reference's padded width


def tiny_cfg(**over) -> dict:
    """The published shape at toy widths, in the configuration file's own
    keys: 3 layers (one dense MLP, two routed), 8 of 64 positions kept."""
    cfg = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "layer_types": ["dsa", "dsa", "dsa"],
        "intermediate_size": 96, "vocab_size": 128,
        "max_position_embeddings": WIDTH,
        "q_lora_rank": 24, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 16,
                         "type": "yarn"},
        "n_routed_experts": 16, "n_routed_experts_total": 16,
        "experts_held": [0, 16], "n_shared_experts": 1,
        "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "moe_intermediate_size": 32,
        "assumed": {"initializer_range": 0.1, "norm_weight_jitter": 0.1,
                    "router_bias_std": 0.1,
                    "engine": {"prefill_chunk": 16}},
    }
    cfg.update(over)
    return cfg


def _setup(seed=0, **over):
    cfg = tiny_cfg(**over)
    w = weights_dsa.init_weights(cfg, weights_dsa.seed_key(seed),
                                 jnp.float32)
    pc = adapter_dsa.program_config(cfg, dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    return cfg, w, pc, adapter_dsa.to_program_params(w, cfg)


def _x(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _empty_cache(pc, batch):
    return {n: jnp.full(leaf.shape, leaf.fill, leaf.dtype)
            for n, leaf in pc.cache_leaves(batch).items()}


# -- (a) each piece against the reference ----------------------------------------

def test_yarn_table_matches_reference():
    cfg, _w, pc, _p = _setup()
    sin, cos = hybrid.mla_rope_tables(pc, cfg["qk_rope_head_dim"])
    inv = ref.yarn_inv_freq(cfg)
    ang = np.arange(WIDTH)[:, None] * np.asarray(inv)[None, :]
    np.testing.assert_allclose(sin, np.sin(ang), atol=1e-5)
    np.testing.assert_allclose(cos, np.cos(ang), atol=1e-5)
    # the blend is neither plain rope nor every frequency over the factor
    plain = 1.0 / 10000.0 ** (np.arange(0, 8, 2) / 8)
    assert np.allclose(inv[0], plain[0]) and np.allclose(inv[-1],
                                                         plain[-1] / 4)
    assert pc.rope_mscale == pytest.approx(ref.yarn_mscale(cfg))
    assert ref.yarn_mscale(cfg) == pytest.approx(0.1 * np.log(4) + 1)
    assert pc.softmax_scale == pytest.approx(
        24 ** -0.5 * ref.yarn_mscale(cfg) ** 2)


def test_published_yarn_scale_is_the_issue_s():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3.2.json")) as f:
        cfg = json.load(f)
    assert ref.yarn_mscale(cfg) == pytest.approx(1.3689, abs=1e-4)
    pc = adapter_dsa.program_config(cfg)
    assert pc.softmax_scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2,
                                             rel=1e-4)
    assert pc.latent_width == 640 and pc.prefill_chunk == 1024
    leaves = pc.cache_leaves(16)
    assert leaves["latent"].shape == (5, 16, 32768, 640)
    assert leaves["index_k"].shape == (5, 16, 32768, 128)
    assert set(leaves) == {"positions", "latent", "index_k"}


@pytest.mark.parametrize("t,block_t", [(1, 8), (5, 8), (16, 8), (12, 4)])
def test_index_kernel_matches_the_formula(t, block_t):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, t, 4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, t, 4)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((3, 2, 64, 16)), jnp.float32)
    pos = jnp.asarray([7, 40], jnp.int32)
    got = dsa.index_scores(q, w, keys, 1, pos, block_t=block_t, block_s=16,
                           interpret=True)
    want = dsa.index_scores_reference(q, w, keys, 1, pos)
    assert got.shape == (2, t, 64)
    live = np.isfinite(np.asarray(want))
    assert np.array_equal(np.isfinite(np.asarray(got)), live)
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], atol=1e-4)
    # a row's query t sees positions 0 .. pos + t and no other
    assert live[0, 0].sum() == 8 and live[1, t - 1].sum() == min(40 + t, 64)


@pytest.mark.parametrize("k", [1, 8, 64])
def test_select_kernel_keeps_what_top_k_keeps(k):
    """No sort in the kernel: the k-th largest score by its bits, ties at
    it to the lower position; queries that see fewer than k keep all."""
    rng = np.random.default_rng(4)
    sc = rng.standard_normal((2, 5, 64)).astype(np.float32)
    sc[0, :, 40:] = -np.inf          # a row 40 positions long
    sc[1, 2, 3:] = -np.inf           # a query that sees 3 positions
    sc[1, 3, :20] = 0.5              # 20 positions tied
    sc[1, 4, 10:30] = np.float32(0.0) * np.float32(-1.0)
    got = dsa.select_bias(jnp.asarray(sc), k, interpret=True)
    want = dsa.select_bias_reference(jnp.asarray(sc), k)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    kept = (np.asarray(got) == 0).sum(-1)
    assert kept[0, 0] == min(k, 40) and kept[1, 2] == min(k, 3)


@pytest.mark.parametrize("t,block_t", [(1, 8), (5, 4), (16, 8)])
def test_attend_kernel_matches_softmax_over_the_kept(t, block_t):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, t, 4, 32)), jnp.float32)
    lat = jnp.asarray(rng.standard_normal((3, 2, 64, 32)), jnp.float32)
    pos = jnp.asarray([7, 40], jnp.int32)
    q_pos = pos[:, None] + jnp.arange(t)[None]
    scores = jnp.where(
        jnp.arange(64)[None, None, :] <= q_pos[..., None],
        jnp.asarray(rng.standard_normal((2, t, 64)), jnp.float32), -jnp.inf)
    kept = dsa.select_bias(scores, 8, interpret=True)
    got = dsa.sparse_attend(q, kept, lat, 1, pos, scale=0.3, values=24,
                            block_t=block_t, block_s=16, interpret=True)
    s = jnp.einsum("bthw,bsw->bhts", q, lat[1]) * 0.3 + kept[:, None]
    want = jnp.einsum("bhts,bsw->bthw", jax.nn.softmax(s, -1),
                      lat[1][..., :24])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_indexer_scores_and_selection_match_reference():
    """The mixer's own scores (through the cache leaf and the kernel) and
    its picks against the reference's, for a whole fresh sequence."""
    cfg, w, pc, params = _setup()
    x = _x((2, 24, 64))
    lw = ref.layer_weights(w, "attn", 1)
    c_q = ref.rms_norm(ref.matmul(x, lw["mla_wqa"], None),
                       lw["mla_q_a_norm"], 1e-6)
    q, k, wt = ref.index_inputs(x, c_q, lw, cfg)
    want = ref.index_scores(q, k, wt, jnp.arange(24))
    want_sets, _mask = ref.select(want, jnp.arange(24), 8)

    (_out, cache, (scored, n_kept)), seen = hybrid.MlaAttention(
        pc, sparse=True).apply(
            {"params": params["layer_1"]["mixer"]}, x, _empty_cache(pc, 2),
            1, True, 1, mutable=["intermediates"])
    index_k = cache["index_k"]
    kept = np.asarray(seen["intermediates"]["kept"][0])[:, :, :24] == 0
    np.testing.assert_allclose(
        seen["intermediates"]["index_scores"][0][:, :, :24], want,
        atol=1e-4)
    np.testing.assert_allclose(index_k[1, :, :24], k, atol=1e-5)
    want_kept = np.zeros((2, 24, 25), bool)
    np.put_along_axis(want_kept, np.asarray(want_sets), True, axis=-1)
    assert np.array_equal(kept, want_kept[..., :24])
    assert np.array_equal(kept, np.asarray(_mask))
    assert int(scored) == 2 * sum(range(1, 25))
    assert int(n_kept) == 2 * sum(min(8, t + 1) for t in range(24))


@pytest.mark.parametrize("t", [1, 6, 24])
def test_sparse_mla_matches_reference(t):
    """Low-rank-query MLA, absorbed over the gathered rows, against the
    reference's expanded attention under the selection's mask."""
    cfg, w, pc, params = _setup()
    x = _x((2, t, 64))
    want, _ = ref.mla_mixer(x, ref.layer_weights(w, "attn", 2), cfg)
    got, cache, _ = hybrid.MlaAttention(pc, sparse=True).apply(
        {"params": params["layer_2"]["mixer"]}, x, _empty_cache(pc, 2), 2,
        True, 2)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert cache["latent"].shape[-1] == 128      # 24 + 8, no key scales


def test_expanded_equals_absorbed_without_the_indexer():
    """The same weights as a plain "mla" layer (low-rank query, YaRN, no
    qk-norm, no gate): the fresh expanded form, the absorbed form and the
    reference with the indexer ignored agree."""
    cfg, w, pc, params = _setup()
    dense = dataclasses.replace(pc, layer_types=("mla",) * 3)
    x = _x((2, 11, 64))
    want, _ = ref.mla_mixer(x, ref.layer_weights(w, "attn", 0), cfg,
                            dense=True)
    mixer = {k: v for k, v in params["layer_0"]["mixer"].items()
             if not k.startswith("index_")}
    for fresh in (True, False):
        got, _c, _n = hybrid.MlaAttention(dense).apply(
            {"params": mixer}, x, _empty_cache(dense, 2), 0, fresh)
        np.testing.assert_allclose(got, want, atol=2e-5)


# -- (b) prefill -> chunks -> decode against one full forward --------------------

_REF_FNS = {}


def _reference_logits(w, cfg, seq, **kw):
    key = json.dumps([cfg, sorted(kw.items())], sort_keys=True)
    if key not in _REF_FNS:
        _REF_FNS[key] = jax.jit(
            lambda w, toks: ref.logits(w, ref.hidden(w, toks, cfg, **kw))[0])
    toks = np.zeros((1, WIDTH), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(_REF_FNS[key](w, jnp.asarray(toks)))[:len(seq)]


def test_prefill_chunks_then_decode_match_full_forward():
    """A 9-token prefill, three chunks of 16 with a ragged tail (16, 16,
    7), then 8 decode steps: 56 positions against index_topk 8."""
    cfg, w, pc, params = _setup()
    seq = np.random.default_rng(5).integers(0, 128, 56)
    want = _reference_logits(w, cfg, seq)
    logits, cache = decode.prefill(pc, params, jnp.asarray(seq[None, :9]))
    np.testing.assert_allclose(logits[0], want[8], atol=1e-4)
    at = 9
    for n in (16, 16, 7):
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :n] = seq[at:at + n]
        logits, cache = decode.prefill_continue(
            pc, params, cache, jnp.asarray(chunk), n, at + n)
        at += n
        np.testing.assert_allclose(logits[0], want[at - 1], atol=1e-4)
    assert int(cache["positions"][0]) == 48
    for step in range(8):
        logits, cache, stats = decode.decode_step_stats(
            pc, params, cache, jnp.asarray(seq[48 + step:49 + step]))
        np.testing.assert_allclose(logits[0], want[48 + step], atol=1e-4)
    # one row at positions 55: 56 scored and 8 kept in each of 3 layers
    assert np.array_equal(stats["index_scored"], [56] * 3)
    assert np.array_equal(stats["index_selected"], [8] * 3)
    # the selection matters at this size: dense attention reads otherwise
    dense = _reference_logits(w, cfg, seq, dense=True)
    assert np.abs(dense[40:] - want[40:]).max() > 1e-2


def test_context_within_topk_equals_the_indexer_free_model():
    """``index_topk`` 64 keeps every position of a 64-token row: the
    model equals the same weights served as plain "mla" layers."""
    cfg, w, pc, params = _setup(index_topk=64)
    seq = np.random.default_rng(6).integers(0, 128, 30)
    want = _reference_logits(w, cfg, seq, dense=True)
    plain = dataclasses.replace(pc, layer_types=("mla",) * 3)
    stripped = jax.tree_util.tree_map(lambda a: a, params)
    for i in range(3):
        stripped[f"layer_{i}"]["mixer"] = {
            k: v for k, v in params[f"layer_{i}"]["mixer"].items()
            if not k.startswith("index_")}
    for cfg_i, p_i in ((pc, params), (plain, stripped)):
        logits, cache = decode.prefill(cfg_i, p_i, jnp.asarray(seq[None, :20]))
        np.testing.assert_allclose(logits[0], want[19], atol=1e-4)
        for step in range(10):
            logits, cache = decode.decode_step(
                cfg_i, p_i, cache, jnp.asarray(seq[20 + step:21 + step]))
            np.testing.assert_allclose(logits[0], want[20 + step], atol=1e-4)


def test_fault_variants_differ_from_the_reference():
    cfg, w, _pc, _params = _setup()
    seq = np.random.default_rng(8).integers(0, 128, 48)
    want = _reference_logits(w, cfg, seq)
    for fault in ref.FAULTS:
        got = _reference_logits(w, cfg, seq, fault=fault)
        assert np.abs(got - want).max() > 1e-2, fault
    with pytest.raises(ValueError, match="unknown fault"):
        ref.forward(w, jnp.zeros((1, 4), jnp.int32), cfg, fault="other")


# -- (c) the same through DecodeEngine -------------------------------------------

def _greedy_reference(w, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_reference_logits(w, cfg, seq)[-1])))
    return seq[len(prompt):]


def _engine(pc, params, **kw):
    return DecodeEngine(pc, params, slots=2, steps_per_sync=2,
                        autostart=False, name="tiny-dsa", **kw)


def _drive(engine, handles):
    for _ in range(300):
        engine.run_once(timeout=0.01)
        if not (engine.active_count or engine.pending_count):
            break
    return [h.result() for h in handles]


def test_engine_serves_the_reference_with_chunked_admission():
    """Five greedy requests on two slots, rows of unequal length: two
    prompts longer than the chunk (16) are admitted by the chunk program
    beside decoding rows, three take the bucket path, and every slot is
    used again. Tokens equal the reference's greedy continuation, which
    is what unbatched ``generate`` gives too."""
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR

    cfg, w, pc, params = _setup()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 128, n) for n in (9, 37, 5, 50, 16)]
    engine = _engine(pc, params)
    try:
        handles = [engine.submit(p, max_new=6) for p in prompts]
        got = _drive(engine, handles)
        assert engine.prefill_chunks == 3 + 4       # ceil(37/16), ceil(50/16)
    finally:
        engine.close()
    for p, tokens in zip(prompts, got):
        assert tokens == _greedy_reference(w, cfg, p, 6)
    alone = decode.generate(pc, params, jnp.asarray(prompts[3][None]),
                            max_new_tokens=6)
    assert got[3] == [int(t) for t in alone[0]]
    admits = [sp for sp in DEFAULT_COLLECTOR.spans()
              if sp.name == "engine.admit"
              and sp.attrs.get("model") == "tiny-dsa"]
    assert sorted(sp.attrs.get("chunks", 0) for sp in admits)[-2:] == [3, 4]


def test_engine_round_spans_carry_the_index_counts():
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR
    from kubeflow_tpu.utils import DEFAULT_REGISTRY
    from kubeflow_tpu.utils.metrics import exposition

    _cfg, _w, pc, params = _setup()
    engine = DecodeEngine(pc, params, slots=2, steps_per_sync=2,
                          autostart=False, name="tiny-dsa-counts")
    try:
        _drive(engine, [engine.submit(np.arange(1, 21), max_new=5),
                        engine.submit(np.arange(1, 4), max_new=5)])
    finally:
        engine.close()
    rounds = [sp for sp in DEFAULT_COLLECTOR.spans()
              if sp.name == "engine.round"
              and sp.attrs.get("model") == "tiny-dsa-counts"
              and sp.attrs["k"]]
    first = rounds[0].attrs
    # rows at 20 and 3 tokens, two steps, three layers: positions scored
    # are (21 + 4) + (22 + 5); kept min(8, .) of each
    assert first["index_scored"] == 3 * (21 + 4 + 22 + 5)
    assert first["index_selected"] == 3 * (8 + 4 + 8 + 5)
    assert first["routed_pairs"] == 2 * 3 * 2 * first["k"]
    text = exposition(DEFAULT_REGISTRY)[0].decode()
    assert 'kftpu_dsa_scored_total{model="tiny-dsa-counts"}' in text
    assert 'kftpu_dsa_selected_total{model="tiny-dsa-counts"}' in text


def test_prefix_reuse_works_through_the_prefix_row():
    """Every leaf of this model is positional, so a stored prefix row
    continues: a long prefix (chunked), then a short and a long suffix."""
    cfg, w, pc, params = _setup()
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 128, 20)
    prompts = [np.concatenate([shared, rng.integers(0, 128, n)])
               for n in (5, 23)]
    engine = _engine(pc, params)
    try:
        got = _drive(engine, [engine.submit(p, max_new=4, prefix_len=20)
                              for p in prompts])
        assert (engine.prefix_hits, engine.prefix_misses) == (1, 1)
    finally:
        engine.close()
    for p, tokens in zip(prompts, got):
        assert tokens == _greedy_reference(w, cfg, p, 4)


def test_engine_refuses_paging_and_speculation_by_the_leaves():
    _cfg, _w, pc, params = _setup()
    with pytest.raises(ValueError, match="also holds index_k, latent"):
        _engine(pc, params, paged=True)
    with pytest.raises(ValueError, match="also holds index_k, latent"):
        decode.speculative_generate(
            pc, params, pc, params, jnp.ones((1, 4), jnp.int32),
            max_new_tokens=2)
    assert not pc.has_recurrent_state
    assert {n: leaf.batch_axis for n, leaf in pc.cache_leaves(1).items()} \
        == {"positions": 0, "latent": 1, "index_k": 1}


def test_short_prompts_and_other_models_keep_their_admission():
    """A model that declares no chunk never builds the chunk program, and
    one that does takes today's path up to the chunk's width."""
    from kubeflow_tpu.models import Transformer, tiny_config

    tc = tiny_config()
    tp = Transformer(tc).init(jax.random.key(0),
                              jnp.zeros((1, 8), jnp.int32))["params"]
    dense = DecodeEngine(tc, tp, slots=2, autostart=False, name="tiny-dense")
    try:
        assert tc.prefill_chunk == 0
        assert not dense._kv._is_long(tc.max_seq_len)
        _drive(dense, [dense.submit(np.arange(1, 40), max_new=2)])
        assert dense.prefill_chunks == 0
    finally:
        dense.close()
    _cfg, _w, pc, params = _setup()
    engine = _engine(pc, params)
    try:
        _drive(engine, [engine.submit(np.arange(1, 17), max_new=2)])
        assert engine.prefill_chunks == 0
        assert engine._kv._is_long(17) and not engine._kv._is_long(16)
    finally:
        engine.close()
