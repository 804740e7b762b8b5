"""Global and sliding-window rotary attention layers of the hybrid decoder
(``"attn"`` / ``"swa"``: qk heads wider than v heads, partial rope, a
learned sink a head on the window kind, a ring of ``window`` positions a
row) and the routed MLP with no shared expert, against the plain float32
reference ``benchmark/references/mimo_v2_flash_f32.py``, at a tiny size on
the CPU: prefill then decode past several wraps of the ring, padded batch
prefill, chunked admission across the window, the faults each of those
comparisons catches, the step kernel's new forms, the expert shares, the
same through DecodeEngine, and what the engine refuses.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import adapter_swa, weights_swa
from kubeflow_tpu.models import decode, hybrid
from kubeflow_tpu.models.transformer import _merged_step_attention
from kubeflow_tpu.ops import step_attention
from kubeflow_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "mimo_v2_flash_f32.py")
    spec = importlib.util.spec_from_file_location("mimo_v2_flash_f32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
W = 8          # the window


def tiny_cfg(**over) -> dict:
    """The published pattern's shape at toy widths, in the configuration
    file's own keys: a global layer with the dense MLP, three window
    layers and a global one, all routed; window 8, 2 + 2 KV heads of qk 24
    / v 16, 8 of them rotated, 16 experts of which 4 are held."""
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 5,
        "hybrid_layer_pattern": [0, 1, 1, 1, 0],
        "moe_layer_freq": [0, 1, 1, 1, 1], "num_attention_heads": 4,
        "num_key_value_heads": 2, "swa_num_key_value_heads": 2,
        "head_dim": 24, "v_head_dim": 16, "partial_rotary_factor": 0.334,
        "rope_theta": 5e6, "swa_rope_theta": 1e4, "sliding_window": W,
        "add_swa_attention_sink_bias": True, "attention_value_scale": 0.707,
        "layernorm_epsilon": 1e-5, "intermediate_size": 96,
        "vocab_size": 128, "max_position_embeddings": 64,
        "n_routed_experts": 4, "n_routed_experts_total": 16,
        "experts_held": [4, 4], "num_experts_per_tok": 3, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": None,
        "norm_topk_prob": True, "moe_intermediate_size": 32,
        "n_shared_experts": None,
        "assumed": {"initializer_range": 0.1, "norm_weight_jitter": 0.1,
                    "router_bias_std": 0.1, "sink_mean": 1.0,
                    "sink_std": 1.0, "engine": {"prefill_chunk": 16}},
    }
    cfg.update(over)
    return cfg


def _setup(seed=0, **over):
    cfg = tiny_cfg(**over)
    w = weights_swa.init_weights(cfg, weights_swa.seed_key(seed),
                                 jnp.float32)
    pc = adapter_swa.program_config(cfg, dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    return cfg, w, pc, adapter_swa.to_program_params(w, cfg)


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 128, shape),
                       jnp.int32)


def _reference_logits(w, cfg, tokens, fault=None):
    h, _ = ref.forward(w, tokens, cfg, fault=fault)
    return ref.logits(w, h)


def _served(pc, params, tokens, start: int, chunk: int = 0, lens=None):
    """Logits of every position from ``start`` on: the first ``start``
    tokens prefilled (in chunks of ``chunk`` on a row's cache, or at once;
    ``lens`` a padded batch's real lengths), the rest decoded one by
    one through the cache."""
    B, T = tokens.shape
    if chunk:
        lg, cache = decode.prefill(pc, params, tokens[:, :chunk])
        more = jax.jit(functools.partial(decode.prefill_continue, pc))
        for at in range(chunk, start, chunk):
            n = min(chunk, start - at)
            piece = jnp.pad(tokens[:, at:at + n], ((0, 0), (0, chunk - n)))
            lg, cache = more(params, cache, piece, n, at + n)
    else:
        lg, cache = decode.prefill(pc, params, tokens[:, :start], lens)

    def step(cache, token):
        lg, cache = decode.decode_step(pc, params, cache, token)
        return cache, lg

    cache, rest = jax.lax.scan(step, cache, tokens[:, start:T - 1].T)
    return jnp.concatenate([lg[:, None], jnp.moveaxis(rest, 0, 1)], 1), cache


# -- (a) prefill and decode against the reference --------------------------------

@pytest.mark.parametrize("start, chunk", [(5, 0), (13, 0), (21, 6), (30, 16)],
                         ids=["prefill5", "prefill13", "chunks6", "chunks16"])
def test_prefill_then_decode_past_window_wraps_matches_reference(start,
                                                                 chunk):
    """40 positions through a window of 8 (four wraps of the ring after
    the prefill), prefilled whole or in chunks that straddle the window
    (6 < 8 < 16)."""
    cfg, w, pc, params = _setup()
    tokens = _tokens((2, 41))
    want = _reference_logits(w, cfg, tokens)[:, start - 1:-1]
    got, cache = _served(pc, params, tokens, start, chunk)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert cache["swa_k"].shape == (3, 2, W, 2 * 24)
    assert cache["swa_v"].shape == (3, 2, W, 2 * 16)
    assert cache["k"].shape == (2, 2, 64, 2 * 24)
    assert cache["v"].shape == (2, 2, 64, 2 * 16)


def test_padded_batch_prefill_keeps_pad_tokens_out_of_the_ring():
    """Rows of 5, 13 and 21 real tokens padded to 24, then decoded: each
    row's ring holds its own last 8 real keys, whatever its padding."""
    cfg, w, pc, params = _setup()
    tokens = _tokens((3, 34), seed=4)
    lens = jnp.asarray([5, 13, 21], jnp.int32)
    padded = jnp.where(jnp.arange(24)[None] < lens[:, None], tokens[:, :24],
                       99)
    lg, cache = decode.prefill(pc, params, padded, lens)
    want = _reference_logits(w, cfg, tokens)
    rows = jnp.arange(3)
    np.testing.assert_allclose(lg, want[rows, lens - 1], atol=3e-5)

    def step(cache, t):
        lg, cache = decode.decode_step(pc, params, cache,
                                       tokens[rows, lens + t])
        return cache, lg

    _, got = jax.lax.scan(step, cache, jnp.arange(10))
    for t in range(10):
        np.testing.assert_allclose(got[t], want[rows, lens + t], atol=3e-5)


def test_a_pad_token_written_into_the_ring_is_caught(monkeypatch):
    """The fault: a padded prefill writes its pad tokens' keys into the
    ring (the ring's end taken at the padded width, not the row's
    length). The next decoded tokens then differ from the reference."""
    cfg, w, pc, params = _setup()
    tokens = _tokens((1, 24), seed=4)
    update = hybrid.ring_update
    monkeypatch.setattr(
        hybrid, "ring_update",
        lambda ring, new, start, end, window: update(
            ring, new, start, start + new.shape[1], window))
    padded = jnp.where(jnp.arange(16)[None] < 11, tokens[:, :16], 99)
    _, cache = decode.prefill(pc, params, padded, jnp.asarray([11]))
    lg, _ = decode.decode_step(pc, params, cache, tokens[:, 11])
    want = _reference_logits(w, cfg, tokens)[:, 11]
    assert float(jnp.max(jnp.abs(lg - want))) > 1e-2


@pytest.mark.parametrize("fault", ["window_short", "window_long",
                                   "no_sinks", "rope_all_dims",
                                   "no_value_scale"])
def test_each_fault_of_the_equations_is_caught(fault):
    """The program agrees with the reference to 3e-5; the reference with
    one of the equations broken (the window one position short or long,
    the sinks left out, rope over all 24 dims, the value scale missing)
    lies far outside that on the same tokens, so a program with that
    fault would fail the comparisons above."""
    cfg, w, pc, params = _setup()
    tokens = _tokens((2, 30))
    got, _ = _served(pc, params, tokens, 13)
    wrong = _reference_logits(w, cfg, tokens, fault=fault)[:, 12:-1]
    assert float(jnp.max(jnp.abs(got - wrong))) > 1e-2


def test_the_kernel_step_serves_what_the_xla_step_does(monkeypatch):
    """Both kinds' one-token step through the interpreted kernel (ring
    read, sinks, v narrower than k; the global rows' live blocks) give
    the XLA form's logits, past a wrap of the ring, and sow the
    counters."""
    cfg, w, pc, params = _setup()
    tokens = _tokens((2, 12), seed=3)
    _, cache = decode.prefill(pc, params, tokens[:, :6])
    step = jax.jit(functools.partial(decode.decode_step_stats, pc))
    want, c = [], cache
    for t in range(6, 11):
        lg, c, _ = step(params, c, tokens[:, t])
        want.append(lg)
    monkeypatch.setattr(step_attention, "use_kernel", lambda: True)
    step = jax.jit(functools.partial(decode.decode_step_stats, pc))
    c = cache
    for i, t in enumerate(range(6, 11)):
        lg, c, stats = step(params, c, tokens[:, t])
        np.testing.assert_allclose(lg, want[i], atol=3e-5)
    # one window layer's reads: min(pos + 1, 8) a row, the rows at 10
    assert int(stats["window_positions_read"]) == 2 * W
    assert int(stats["kv_blocks_read"]) == 2
    assert int(stats["kv_blocks_reserved"]) == 2


def test_fresh_and_chunked_window_prefill_attend_in_query_blocks(
        monkeypatch):
    """Query blocks of 8 over a prompt of 24: a block reads the ring and
    the keys its band reaches, and gives what one block of all does."""
    cfg, w, pc, params = _setup()
    monkeypatch.setattr(hybrid, "GQA_Q_BLOCK", 8)
    tokens = _tokens((2, 33), seed=6)
    want = _reference_logits(w, cfg, tokens)[:, 23:-1]
    got, _ = _served(pc, params, tokens, 24)
    np.testing.assert_allclose(got, want, atol=3e-5)
    monkeypatch.setattr(hybrid, "ATTN_KEY_BLOCK", 16)
    got, _ = _served(pc, params, tokens, 24, chunk=8)
    np.testing.assert_allclose(got, want, atol=3e-5)


# -- (b) the step kernel's new forms ----------------------------------------------

@pytest.mark.parametrize("ring", [False, True], ids=["rows", "ring"])
@pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sinks"])
@pytest.mark.parametrize("dv", [24, 16], ids=["v=k", "v<k"])
def test_step_kernel_matches_merged_step_attention(ring, sinks, dv):
    """Interpret mode, 4 rows (one at 0, one idle), 2 KV heads of 2 query
    heads: with a value width apart from the key's, with sinks, and on a
    ring of 128 slots in a context of 1000."""
    B, KH, G, Dh, L = 4, 2, 2, 24, 2
    S, limit = (128, 1000) if ring else (256, None)
    keys = jax.random.split(jax.random.key(0), 4)
    k = jax.random.normal(keys[0], (L, B, S, KH * Dh))
    v = jax.random.normal(keys[1], (L, B, S, KH * dv))
    q = jax.random.normal(keys[2], (B, KH, G, Dh))
    b = jax.random.normal(keys[3], (KH, G)) + 2.0 if sinks else None
    pos = jnp.asarray([0, 5, 700 if ring else 200, limit or S], jnp.int32)
    got = step_attention.step_attention(
        q, k, v, 1, pos, block_rows=2, block_len=128, sinks=b,
        max_seq_len=limit, interpret=True)
    assert got.shape == (B, KH, G, dv)
    mask = jnp.arange(S)[None] <= pos[:, None]
    want = _merged_step_attention(q, k[1], v[1], mask, b)
    np.testing.assert_allclose(got[:3], want[:3], atol=1e-5)
    np.testing.assert_array_equal(got[3], 0.0)       # the idle row


def test_live_blocks_of_a_ring_stop_at_the_ring():
    pos = jnp.asarray([0, 127, 128, 5000, 9999], jnp.int32)
    assert step_attention.live_blocks(pos, 9999, 128, ring=128).tolist() \
        == [1, 1, 1, 1, 0]
    assert step_attention.live_blocks(pos, 9999, 128).tolist() \
        == [1, 1, 2, 40, 0]
    assert int(step_attention.window_positions(pos, 9999, 128)) \
        == 1 + 128 + 128 + 128


# -- (c) the expert share with no shared expert ------------------------------------

def test_four_shares_without_a_shared_expert_make_the_uncut_layer():
    cfg, w, pc, params = _setup(n_routed_experts=16, experts_held=[0, 16])
    mlp = params["layer_2"]["mlp"]
    assert not any(name.startswith("shared_") for name in mlp)
    x = jnp.asarray(np.random.default_rng(9).standard_normal((2, 10, 64)),
                    jnp.float32)
    lw = ref.layer_weights(w, "moe", 1)
    whole, _ = ref.routed_mlp(x, lw, cfg)
    total = 0.0
    for lo in range(0, 16, 4):
        cut = {k: (v[lo:lo + 4] if k in ("gate_proj", "up_proj", "down_proj")
                   else v) for k, v in mlp.items()}
        share = hybrid.RoutedMlp(dataclasses.replace(pc, experts_held=(lo, 4)))
        y, _hit, _pairs = share.apply({"params": cut}, x)
        lw_cut = dict(lw, **{k: lw[k][lo:lo + 4]
                             for k in ("exp_gate", "exp_up", "exp_down")})
        np.testing.assert_allclose(
            y, ref.routed_mlp(x, lw_cut, dict(cfg, experts_held=[lo, 4]))[0],
            atol=2e-5)
        total = total + y
    np.testing.assert_allclose(total, whole, atol=5e-5)


# -- (d) the engine ------------------------------------------------------------------

def _engine(pc, params, **kw):
    return DecodeEngine(pc, params, slots=2, steps_per_sync=2,
                        autostart=False, name="tiny-swa", **kw)


def _drive(engine, handles):
    for _ in range(300):
        engine.run_once(timeout=0.01)
        if not (engine.active_count or engine.pending_count):
            break
    return [h.result() for h in handles]


def _is_reference_greedy(w, cfg, prompts, answers, width=64):
    """Each answer is the reference's own greedy continuation of its
    prompt: teacher forced in one padded forward (causal: the padding
    after a sequence changes nothing before it), the reference's argmax
    at every position is the next served token."""
    seqs = np.zeros((len(prompts), width), np.int32)
    for i, (p, a) in enumerate(zip(prompts, answers)):
        seqs[i, :p.size + len(a)] = np.concatenate([p, a])
    best = np.asarray(jnp.argmax(jax.jit(
        lambda t: _reference_logits(w, cfg, t))(jnp.asarray(seqs)), -1))
    return all(list(best[i, p.size - 1:p.size - 1 + len(a)]) == list(a)
               for i, (p, a) in enumerate(zip(prompts, answers)))


@pytest.mark.parametrize("admit_batch_max", [1, 8], ids=["row", "batch"])
def test_engine_serves_the_reference_and_reuses_slots(admit_batch_max):
    """Five greedy requests on two slots, two of them longer than the
    chunk of 16 (chunked admission): every slot is used again, so a stale
    ring slot would show in the later answers."""
    cfg, w, pc, params = _setup()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 128, n) for n in (9, 21, 5, 17, 11)]
    engine = _engine(pc, params, admit_batch_max=admit_batch_max)
    try:
        got = _drive(engine, [engine.submit(p, max_new=12)
                              for p in prompts])
    finally:
        engine.close()
    assert all(len(a) == 12 for a in got)
    assert _is_reference_greedy(w, cfg, prompts, got)


def test_engine_refuses_paged_prefix_reuse_and_speculation():
    _cfg, _w, pc, params = _setup()
    with pytest.raises(ValueError, match="also holds swa_k, swa_v"):
        _engine(pc, params, paged=True)
    engine = _engine(pc, params)
    try:
        with pytest.raises(ValueError, match="window ring"):
            engine.submit(np.arange(1, 9), max_new=2, prefix_len=4)
    finally:
        engine.close()
    with pytest.raises(ValueError, match="also holds swa_k"):
        decode.speculative_generate(
            pc, params, pc, params, jnp.ones((1, 4), jnp.int32),
            max_new_tokens=2)


def test_cache_contract_and_config_checks():
    _cfg, _w, pc, _params = _setup()
    contract = pc.cache_leaves(3)
    assert {n: leaf.shape for n, leaf in contract.items()} == {
        "positions": (3,), "k": (2, 3, 64, 48), "v": (2, 3, 64, 32),
        "swa_k": (3, 3, W, 48), "swa_v": (3, 3, W, 32)}
    assert (contract["swa_v"].heads_axis, contract["swa_v"].head_width) \
        == (3, 16)
    assert pc.has_recurrent_state and pc.n_moe == 4
    assert pc.rotary_dim == 8 and pc.first_k_dense == 1
    for bad in (dict(window=0), dict(window=65), dict(rotary_dim=7),
                dict(rotary_dim=26), dict(swa_kv_heads=3),
                dict(layer_types=("attn", "gqa"), window=0,
                     attn_sinks=False)):
        with pytest.raises(ValueError):
            dataclasses.replace(pc, **bad).validate()
    with pytest.raises(ValueError, match="swa layers"):
        dataclasses.replace(pc, layer_types=("attn",)).validate()
