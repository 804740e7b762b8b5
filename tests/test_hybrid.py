"""The hybrid decoder (KDA + MLA mixers, routed MLP with a shared expert,
an expert share) against its plain float32 reference, at a tiny size on
the CPU: each layer alone, chunk-wise KDA against the token recurrence,
prefill + decode through the cache, the same through DecodeEngine, the
four expert shares against the uncut layer, and what the engine refuses.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import adapter_hybrid, weights_hybrid
from kubeflow_tpu.models import decode, hybrid
from kubeflow_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references", "ling_hybrid_f32.py")
    spec = importlib.util.spec_from_file_location("ling_hybrid_f32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def tiny_cfg(**over) -> dict:
    """The published pattern at toy widths, in the configuration file's
    own keys: 7 layers (MLA at kept index 4), 16 experts in 4 groups."""
    cfg = {
        "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
        "num_hidden_layers": 7, "first_k_dense_replace": 1,
        "layer_types": ["kda", "kda", "kda", "kda", "mla", "kda", "kda"],
        "intermediate_size": 96, "vocab_size": 128,
        "max_position_embeddings": 64,
        "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "num_experts": 16, "num_experts_total": 16, "experts_held": [0, 16],
        "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 32,
        "assumed": {"initializer_range": 0.1, "norm_weight_jitter": 0.1,
                    "router_bias_std": 0.1, "conv_std": 0.4,
                    "a_log_std": 0.3, "dt_bias_mean": -3.0,
                    "dt_bias_std": 1.0},
    }
    cfg.update(over)
    return cfg


def _setup(seed=0, **over):
    cfg = tiny_cfg(**over)
    w = weights_hybrid.init_weights(cfg, weights_hybrid.seed_key(seed),
                                    jnp.float32)
    pc = adapter_hybrid.program_config(cfg, dtype=jnp.float32,
                                       param_dtype=jnp.float32, kda_chunk=8)
    return cfg, w, pc, adapter_hybrid.to_program_params(w, cfg)


def _x(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _empty_cache(pc, batch):
    return {n: jnp.full(leaf.shape, leaf.fill, leaf.dtype)
            for n, leaf in pc.cache_leaves(batch).items()}


# -- (a) each layer against the reference ----------------------------------------

@pytest.mark.parametrize("t", [1, 13, 24])
def test_kda_mixer_matches_reference(t):
    cfg, w, pc, params = _setup()
    x = _x((2, t, 64))
    want = ref.kda_mixer(x, ref.layer_weights(w, "kda", 2), cfg)
    got, _ = hybrid.KdaMixer(pc).apply(
        {"params": params["layer_2"]["mixer"]}, x, _empty_cache(pc, 2), 2)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("fresh", [True, False])
def test_mla_matches_reference_expanded_and_absorbed(fresh):
    cfg, w, pc, params = _setup()
    x = _x((2, 11, 64))
    want = ref.mla_mixer(x, ref.layer_weights(w, "mla", 0), cfg)
    got, _cache, _counts = hybrid.MlaAttention(pc).apply(
        {"params": params["layer_4"]["mixer"]}, x, _empty_cache(pc, 2), 0,
        fresh)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_routed_mlp_matches_reference_and_counts():
    cfg, w, pc, params = _setup()
    x = _x((2, 9, 64))
    want, chosen = ref.routed_mlp(x, ref.layer_weights(w, "moe", 1), cfg)
    got, hit, pairs = hybrid.RoutedMlp(pc).apply(
        {"params": params["layer_2"]["mlp"]}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(pairs) == 2 * 9 * 3
    assert int(hit) == len(np.unique(np.asarray(chosen)))


def test_router_is_group_limited_and_biased():
    cfg, w, pc, _ = _setup()
    lw = ref.layer_weights(w, "moe", 0)
    idx, wts = ref.route(_x((50, 64)), lw, cfg)
    groups = np.asarray(idx) // 4
    assert all(len(set(g)) <= cfg["topk_group"] for g in groups)
    np.testing.assert_allclose(np.sum(wts, -1), 2.5, rtol=1e-5)
    unbiased, _ = ref.route(_x((50, 64)),
                            dict(lw, router_bias=0 * lw["router_bias"]), cfg)
    assert np.any(np.sort(idx, -1) != np.sort(unbiased, -1))


# -- (b) chunk-wise KDA = the token recurrence -----------------------------------

def _kda_inputs(b, t, h=4, dk=16, seed=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q = hybrid._l2_norm(f(b, t, h, dk)) * dk ** -0.5
    k = hybrid._l2_norm(f(b, t, h, dk))
    a = -5.0 * jax.nn.sigmoid(f(b, t, h, dk) - 1.0)   # down to exp(-5) a token
    return q, k, f(b, t, h, dk), a, jax.nn.sigmoid(f(b, t, h)), f(b, h, dk, dk)


def _by_token(state, q, k, v, a, beta):
    outs = []
    for t in range(q.shape[1]):
        state, o = hybrid.kda_recurrent_step(
            state, q[:, t], k[:, t], v[:, t], a[:, t], beta[:, t])
        outs.append(o)
    return state, jnp.stack(outs, 1)


@pytest.mark.parametrize("t,chunk,fastest", [
    (16, 8, False), (21, 8, False), (5, 8, False), (64, 64, False),
    (100, 64, True)])
def test_chunked_kda_equals_recurrence(t, chunk, fastest):
    """``fastest``: every channel decays by exp(-4.99) a token, the
    published lower bound, where exp(-G) alone would overflow."""
    q, k, v, a, beta, s0 = _kda_inputs(2, t)
    if fastest:
        a = jnp.full_like(a, -4.99)
    s_want, o_want = _by_token(s0, q, k, v, a, beta)
    s_got, o_got = hybrid.kda_chunked(s0, q, k, v, a, beta, chunk)
    np.testing.assert_allclose(o_got, o_want, atol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5)


def test_ragged_rows_freeze_state_and_conv_tail():
    cfg, w, pc, params = _setup()
    x = _x((2, 19, 64))
    lens = jnp.asarray([19, 7])
    mixer = hybrid.KdaMixer(pc)
    p = {"params": params["layer_1"]["mixer"]}
    _, both = mixer.apply(p, x, _empty_cache(pc, 2), 1, lens)
    _, short = mixer.apply(p, x[1:, :7], _empty_cache(pc, 1), 1)
    for leaf in ("kda_state", "kda_conv"):
        np.testing.assert_allclose(both[leaf][1, 1], short[leaf][1, 0],
                                   atol=1e-5)
        assert np.all(np.asarray(both[leaf][0]) == 0)   # other layers' slices


# -- (c) prefill + decode through the cache = the full forward -------------------

_REF_WIDTH = 32
_REF_FNS = {}


def _reference_logits(w, cfg, seq):
    """The reference's logits at every position of ``seq``: one compiled
    program a configuration, at a fixed padded width (everything in the
    model is causal, so the pad tail changes nothing before it)."""
    key = json.dumps(cfg, sort_keys=True)
    if key not in _REF_FNS:
        _REF_FNS[key] = jax.jit(
            lambda w, toks: ref.logits(w, ref.hidden(w, toks, cfg))[0])
    toks = np.zeros((1, _REF_WIDTH), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(_REF_FNS[key](w, jnp.asarray(toks)))[:len(seq)]


def test_prefill_then_decode_matches_full_forward():
    cfg, w, pc, params = _setup()
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 128, n) for n in (29, 18)]
    lens = np.asarray([21, 10])           # prompts; the rest is teacher-forced
    prompts = np.zeros((2, 32), np.int32)
    for i, s in enumerate(seqs):
        prompts[i, :lens[i]] = s[:lens[i]]
    logits, cache = decode.prefill(pc, params, jnp.asarray(prompts),
                                   jnp.asarray(lens))
    want = [_reference_logits(w, cfg, s) for s in seqs]
    for i in range(2):
        np.testing.assert_allclose(logits[i], want[i][lens[i] - 1],
                                   atol=1e-4)
    for step in range(8):
        tok = jnp.asarray([s[lens[i] + step] for i, s in enumerate(seqs)])
        logits, cache, stats = decode.decode_step_stats(pc, params, cache,
                                                        tok)
        for i in range(2):
            np.testing.assert_allclose(logits[i], want[i][lens[i] + step],
                                       atol=1e-4)
    assert stats["experts_hit"].shape == (6,)
    assert np.all(np.asarray(stats["routed_pairs"]) == 2 * 3)


def test_prefill_continue_matches_full_forward():
    cfg, w, pc, params = _setup()
    seq = np.random.default_rng(6).integers(0, 128, 27)
    _, cache = decode.prefill(pc, params, jnp.asarray(seq[None, :12]))
    suffix = np.zeros((1, 16), np.int32)
    suffix[0, :15] = seq[12:]
    logits, cache = decode.prefill_continue(
        pc, params, cache, jnp.asarray(suffix), 15, 27)
    np.testing.assert_allclose(
        logits[0], _reference_logits(w, cfg, seq)[-1], atol=1e-4)
    assert int(cache["positions"][0]) == 27


# -- (d) the same through DecodeEngine -------------------------------------------

def _greedy_reference(w, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_reference_logits(w, cfg, seq)[-1])))
    return seq[len(prompt):]


def _engine(pc, params, **kw):
    return DecodeEngine(pc, params, slots=2, steps_per_sync=2,
                        autostart=False, name="tiny-hybrid", **kw)


def _drive(engine, handles):
    for _ in range(200):
        engine.run_once(timeout=0.01)
        if not (engine.active_count or engine.pending_count):
            break
    return [h.result() for h in handles]


# the engine builds a dozen programs a test: three layers (every kind of
# layer and of MLP once) keep their compilation short
_SHORT = dict(num_hidden_layers=3, layer_types=["kda", "mla", "kda"])


@pytest.mark.parametrize("admit_batch_max", [1, 8], ids=["row", "batch"])
def test_engine_serves_the_reference_and_reuses_slots(admit_batch_max):
    """Five greedy requests on two slots: every slot is used again after
    its first request ends, so a stale state would show in the later
    answers."""
    cfg, w, pc, params = _setup(**_SHORT)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 128, n) for n in (9, 13, 5, 17, 11)]
    engine = _engine(pc, params, admit_batch_max=admit_batch_max)
    try:
        handles = [engine.submit(p, max_new=6) for p in prompts]
        got = _drive(engine, handles)
        if admit_batch_max > 1:
            assert engine.batch_prefills >= 1
    finally:
        engine.close()
    for p, tokens in zip(prompts, got):
        assert tokens == _greedy_reference(w, cfg, p, 6)


def test_engine_round_spans_carry_the_routing_counts():
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR
    from kubeflow_tpu.utils import DEFAULT_REGISTRY
    from kubeflow_tpu.utils.metrics import exposition

    _cfg, _w, pc, params = _setup(**_SHORT)
    engine = _engine(pc, params)
    try:
        _drive(engine, [engine.submit(np.arange(1, 8), max_new=5)])
    finally:
        engine.close()
    rounds = [sp for sp in DEFAULT_COLLECTOR.spans()
              if sp.name == "engine.round"
              and sp.attrs.get("model") == "tiny-hybrid" and sp.attrs["k"]]
    last = rounds[-1].attrs
    # 2 slots x 3 experts a token x 2 routed layers x k steps, all held
    assert last["routed_pairs"] == 2 * 3 * 2 * last["k"]
    assert 0 < last["experts_hit"] <= last["routed_pairs"]
    text = exposition(DEFAULT_REGISTRY)[0].decode()
    assert 'kftpu_moe_routed_pairs_total{model="tiny-hybrid"}' in text
    assert 'kftpu_moe_experts_hit_total{model="tiny-hybrid"}' in text


# -- (e) the share test ----------------------------------------------------------

def _dsa_setup():
    """DeepSeek-V3.2's routed layer at toy widths: the same ``RoutedMlp``
    and ``route()`` under its own reference and weight layout."""
    import test_dsa

    cfg, w, pc, params = test_dsa._setup()
    return test_dsa.ref, cfg, w, pc, params["layer_2"]["mlp"], 1


def _ling_setup():
    cfg, w, pc, params = _setup()
    return ref, cfg, w, pc, params["layer_4"]["mlp"], 3


def _nemotron_setup():
    """Nemotron-3-Nano's routed block at toy widths: relu² experts of two
    matrices, one group, under its own reference and weight layout."""
    import test_ssm

    cfg, w, pc, params = test_ssm._setup()
    return test_ssm.ref, cfg, w, pc, params["layer_3"]["mlp"], 1


@pytest.mark.parametrize("family,shares", [(_ling_setup, 4),
                                           (_dsa_setup, 16),
                                           (_nemotron_setup, 4)],
                         ids=["ling-4", "deepseek-v3.2-16", "nemotron-4"])
def test_four_shares_and_one_shared_expert_make_the_uncut_layer(family,
                                                                shares):
    """The shares of an expert-parallel layer (4 chips of Ling's
    deployment, 16 of DeepSeek-V3.2's, 4 of Nemotron-3-Nano's), with the
    shared expert that every chip computes counted once, add up to the
    uncut reference layer."""
    rf, cfg, w, pc, mlp, layer = family()
    x = _x((2, 10, 64), seed=9)
    lw = rf.layer_weights(w, "moe", layer)
    gated = "exp_gate" in lw
    layer_of = rf.routed_mlp if gated else rf.moe_block
    whole, _ = layer_of(x, lw, cfg)
    n = 16 // shares
    total = 0.0
    for lo in range(0, 16, n):
        cut = {k: (v[lo:lo + n] if k in ("gate_proj", "up_proj", "down_proj")
                   else v) for k, v in mlp.items()}
        share = hybrid.RoutedMlp(
            hybrid.dataclasses.replace(pc, experts_held=(lo, n)))
        y, _hit, _pairs = share.apply({"params": cut}, x)
        total = total + y
        # the reference's share is the same part
        lw_cut = dict(lw, **{k: lw[k][lo:lo + n]
                             for k in ("exp_gate", "exp_up", "exp_down")
                             if k in lw})
        np.testing.assert_allclose(
            y, layer_of(x, lw_cut, cfg, held=(lo, n))[0], atol=2e-5)
    flat = x.reshape(20, 64)
    if gated:
        shared = rf.swiglu(flat, lw["sh_gate"], lw["sh_up"], lw["sh_down"],
                           None)
    else:
        shared = rf.relu2_mlp(flat, lw["sh_up"], lw["sh_down"], None)
    np.testing.assert_allclose(
        total - (shares - 1) * shared.reshape(x.shape), whole, atol=1e-4)


# -- (f) what the engine refuses for this model ----------------------------------

def test_engine_refuses_paged_prefix_reuse_and_speculation():
    _cfg, _w, pc, params = _setup(**_SHORT)
    with pytest.raises(ValueError, match="also holds kda_conv, kda_state, "
                                         "latent"):
        _engine(pc, params, paged=True)
    engine = _engine(pc, params)
    try:
        with pytest.raises(ValueError, match="recurrent state"):
            engine.submit(np.arange(1, 9), max_new=2, prefix_len=4)
    finally:
        engine.close()
    with pytest.raises(ValueError, match="also holds kda_conv"):
        decode.speculative_generate(
            pc, params, pc, params, jnp.ones((1, 4), jnp.int32),
            max_new_tokens=2)


def test_cache_contract_is_declared_by_name():
    _cfg, _w, pc, _params = _setup()
    contract = pc.cache_leaves(1)
    assert {n: leaf.batch_axis for n, leaf in contract.items()} == {
        "positions": 0, "latent": 1, "kda_state": 1, "kda_conv": 1}
    from kubeflow_tpu.models import tiny_config

    dense = tiny_config().cache_leaves(1)
    assert (dense["k"].batch_axis, dense["positions"].batch_axis) == (1, 1)
    assert dense["k"].heads_axis == 3
    flat = tiny_config(scan_layers=False).cache_leaves(1)
    assert (flat["k"].batch_axis, flat["k"].heads_axis) == (0, 2)
    paged = tiny_config(kv_page_size=8, kv_pages=4).cache_leaves(1)
    assert paged["k"].batch_axis is None
    assert paged["positions"].idle_value == tiny_config().max_seq_len
    assert paged["pages"].idle_value == 4
