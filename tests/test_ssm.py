"""The one-sublayer blocks of the hybrid decoder (Mamba-2 state-space
mixer, rope-free GQA attention, relu² routed MLP) against their plain
float32 reference, at a tiny size on the CPU: each piece alone, the
one-token step and the chunk-wise form against the scan, the kernel
against the ``jnp`` step, ragged prefill then decode through all four
leaves, the same through DecodeEngine, and what the engine refuses.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_hybrid import _drive, _empty_cache, _x

from benchmark.harness import adapter_ssm, ssm_rounds, weights_ssm
from kubeflow_tpu.models import decode, hybrid
from kubeflow_tpu.ops import ssm
from kubeflow_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references", "nemotron_h_f32.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_f32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def tiny_cfg(**over) -> dict:
    """The published pattern's first period at toy widths, in the
    configuration file's own keys: 7 blocks MEMEM*E, 4 state-space heads
    of 8 in 2 groups, 4 / 2 attention heads, 16 experts, 3 a token."""
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 7,
        "hybrid_override_pattern": "MEMEM*E", "layer_norm_epsilon": 1e-5,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 128, "max_position_embeddings": 64,
        "n_routed_experts": 16, "n_routed_experts_total": 16,
        "experts_held": [0, 16], "num_experts_per_tok": 3, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "mlp_hidden_act": "relu2",
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48,
        "assumed": {"initializer_range": 0.1, "norm_weight_jitter": 0.1,
                    "router_bias_std": 0.1, "conv_std": 0.4,
                    "conv_bias_std": 0.1, "A_init_range": [1, 16]},
    }
    cfg.update(over)
    return cfg


def _setup(seed=0, **over):
    cfg = tiny_cfg(**over)
    w = weights_ssm.init_weights(cfg, weights_ssm.seed_key(seed),
                                 jnp.float32)
    pc = adapter_ssm.program_config(cfg, dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    return cfg, w, pc, adapter_ssm.to_program_params(w, cfg)


# -- (a) each piece against the reference ----------------------------------------

@pytest.mark.parametrize("t", [1, 13, 24])
def test_ssm_mixer_matches_reference(t):
    """One token (the kernel), and T not a multiple of the chunk (the
    chunk-wise form), against the reference's scan over time."""
    cfg, w, pc, params = _setup()
    x = _x((2, t, 64))
    want = ref.ssm_mixer(x, ref.layer_weights(w, "ssm", 1), cfg)
    got, _ = hybrid.SsmMixer(pc).apply(
        {"params": params["layer_2"]["mixer"]}, x, _empty_cache(pc, 2), 1)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_gated_norm_is_taken_within_each_group():
    cfg, w, pc, params = _setup()
    x = _x((1, 9, 64))
    lw = ref.layer_weights(w, "ssm", 0)
    got, _ = hybrid.SsmMixer(pc).apply(
        {"params": params["layer_0"]["mixer"]}, x, _empty_cache(pc, 1), 0)
    np.testing.assert_allclose(got, ref.ssm_mixer(x, lw, cfg), atol=2e-5)
    ungrouped = ref.ssm_mixer(x, lw, cfg, fault="norm_ungrouped")
    assert float(jnp.max(jnp.abs(ungrouped - got))) > 1e-2


@pytest.mark.parametrize("fresh", [True, False])
def test_rope_free_gqa_matches_reference(fresh):
    cfg, w, pc, params = _setup()
    x = _x((2, 11, 64))
    want = ref.attn_mixer(x, ref.layer_weights(w, "attn", 0), cfg)
    got, cache = hybrid.GqaAttention(pc).apply(
        {"params": params["layer_5"]["mixer"]}, x, _empty_cache(pc, 2), 0,
        fresh)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert cache["k"].shape == (1, 2, 64, 2 * 16)


def test_fresh_gqa_attends_in_query_blocks(monkeypatch):
    cfg, w, pc, params = _setup()
    monkeypatch.setattr(hybrid, "GQA_Q_BLOCK", 8)
    x = _x((2, 24, 64))
    got, _ = hybrid.GqaAttention(pc).apply(
        {"params": params["layer_5"]["mixer"]}, x, _empty_cache(pc, 2), 0,
        True)
    np.testing.assert_allclose(
        got, ref.attn_mixer(x, ref.layer_weights(w, "attn", 0), cfg),
        atol=2e-5)


@pytest.mark.parametrize("width, stored", [(32, 32), (300, 300)])
def test_relu2_routed_mlp_matches_reference_with_one_group(width, stored):
    """At a width of whole lanes' worth of nothing (32) and at one that
    is no multiple of 128 (300, the shape class of 1856): the program
    stores an expert at the published width, as the reference does."""
    cfg, w, pc, params = _setup(moe_intermediate_size=width)
    x = _x((2, 9, 64))
    want, chosen = ref.moe_block(x, ref.layer_weights(w, "moe", 1), cfg)
    mlp = params["layer_3"]["mlp"]
    assert "gate_proj" not in mlp and "shared_gate" not in mlp
    assert pc.d_expert == width
    assert mlp["up_proj"].shape == (16, 64, stored)
    assert mlp["down_proj"].shape == (16, stored, 64)
    got, hit, pairs = hybrid.RoutedMlp(pc).apply({"params": mlp}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(pairs) == 2 * 9 * 3
    assert int(hit) == len(np.unique(np.asarray(chosen)))
    # one group: the 3 largest of all 16, wherever they lie
    idx, wts = hybrid.route(_x((50, 16)), 0.0, pc)
    assert len({tuple(i // 4) for i in np.asarray(idx)}) > 4
    np.testing.assert_allclose(np.sum(wts, -1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_an_expert_is_stored_at_its_published_width(act):
    """Every width is stored as it is published (under ``ragged_dot``
    1856 was stored at 2048, PR 35; the grouped matmul of ``ops/gmm.py``
    needs no fill, PR 36): the module's own tensors have ``d_expert``
    columns, a loader's tensors pass ``stored_expert`` untouched, and
    one of another width is refused there."""
    _cfg, _w, pc, _params = _setup()
    for f in (32, 256, 300, 768, 1856, 1920, 2048):
        assert hybrid.dataclasses.replace(pc, d_expert=f
                                          ).expert_width == f
    c = hybrid.dataclasses.replace(pc, d_expert=300, expert_act=act)
    x = _x((2, 5, 64))
    mlp = hybrid.RoutedMlp(c)
    params = mlp.init(jax.random.key(3), x)["params"]
    names = ("up_proj", "gate_proj") if act == "swiglu" else ("up_proj",)
    for name in names:
        assert params[name].shape == (16, 64, 300)
        assert np.all(np.std(params[name], axis=(1, 2)) > 0)
        assert hybrid.stored_expert(params[name], c, 2) is params[name]
    assert params["down_proj"].shape == (16, 300, 64)
    assert hybrid.stored_expert(params["down_proj"], c, 1) is params[
        "down_proj"]
    with pytest.raises(ValueError):
        hybrid.stored_expert(jnp.zeros((16, 64, 512)), c, 2)
    with pytest.raises(ValueError):
        hybrid.stored_expert(params["down_proj"], c, 2)     # the wrong axis
    # every column counts: what lies in down_proj's last rows reaches y
    want = mlp.apply({"params": params}, x)[0]
    moved = dict(params, down_proj=params["down_proj"].at[:, 299:].add(1.0))
    assert np.any(np.asarray(mlp.apply({"params": moved}, x)[0]) != want)


# -- (b) step = scan, chunk-wise = scan, kernel = jnp step ------------------------

def _ssm_inputs(b, t, h=4, p=8, n=16, g=2, seed=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(b, t, h) - 1.0)
    A = -jnp.exp(f(h))
    return (f(b, h, p, n), f(b, t, h, p), dt, A, f(b, t, g, n), f(b, t, g, n),
            1.0 + 0.1 * f(h))


def _by_token(state, x, dt, A, B, C, D, lens=None):
    ys = []
    for t in range(x.shape[1]):
        new, y = ssm.ssm_recurrent_step(state, x[:, t], dt[:, t], A, B[:, t],
                                        C[:, t], D)
        if lens is not None:
            new = jnp.where((t < lens)[:, None, None, None], new, state)
        state = new
        ys.append(y)
    return state, jnp.stack(ys, axis=1)


@pytest.mark.parametrize("t,chunk", [(21, 8), (16, 8), (5, 8), (37, 16)])
def test_chunked_ssm_equals_recurrence(t, chunk):
    state, x, dt, A, B, C, D = _ssm_inputs(2, t)
    s_want, y_want = _by_token(state, x, dt, A, B, C, D)
    s_got, y_got = ssm.ssm_chunked(state, x, dt, A, B, C, D, chunk)
    np.testing.assert_allclose(y_got, y_want, atol=2e-5)
    np.testing.assert_allclose(s_got, s_want, atol=2e-5)


def test_chunked_ssm_freezes_a_ragged_row_exactly():
    state, x, dt, A, B, C, D = _ssm_inputs(3, 21)
    lens = jnp.asarray([21, 6, 13])
    s_want, y_want = _by_token(state, x, dt, A, B, C, D, lens)
    s_got, y_got = ssm.ssm_chunked(state, x, dt, A, B, C, D, 8, lens)
    np.testing.assert_allclose(s_got, s_want, atol=2e-5)
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(y_got[row, :n], y_want[row, :n],
                                   atol=2e-5)
    # a pad token is dt = 0: decay 1 and input 0, so the state is the
    # same float32 numbers as without the pad tail
    s_short, _ = ssm.ssm_chunked(state[1:2], x[1:2, :6], dt[1:2, :6], A,
                                 B[1:2, :6], C[1:2, :6], D, 8)
    assert np.array_equal(np.asarray(s_got[1]), np.asarray(s_short[0]))


def test_step_kernel_equals_jnp_step_in_place_at_its_layer():
    state, x, dt, A, B, C, D = _ssm_inputs(3, 1, seed=4)
    packed = ssm.pack_state(state, 2)        # two heads of 8 a lane tile
    assert packed.shape == (3, 2, 16, 16)
    assert np.array_equal(np.asarray(ssm.unpack_state(packed, 2)),
                          np.asarray(state))
    stack = jnp.stack([packed + 1.0, packed, packed - 1.0])
    s_want, y_want = ssm.ssm_recurrent_step(state, x[:, 0], dt[:, 0], A,
                                            B[:, 0], C[:, 0], D)
    got, y_got = ssm.ssm_step(stack, 1, x[:, 0], dt[:, 0], A, B[:, 0],
                              C[:, 0], D, interpret=True)
    np.testing.assert_allclose(y_got, y_want, atol=1e-5)
    np.testing.assert_allclose(ssm.unpack_state(got[1], 2), s_want,
                               atol=1e-5)
    assert np.array_equal(np.asarray(got[0]), np.asarray(stack[0]))
    assert np.array_equal(np.asarray(got[2]), np.asarray(stack[2]))


def test_ragged_rows_hand_state_and_conv_tail_to_decode():
    """A ragged batch prefill leaves each row's state and conv tail as a
    prefill of the row alone does, and the next token's step continues
    from them as the scan over the whole row does."""
    cfg, w, pc, params = _setup()
    x = _x((2, 20, 64))
    lens = jnp.asarray([19, 7])
    mixer = hybrid.SsmMixer(pc)
    p = {"params": params["layer_2"]["mixer"]}
    _, both = mixer.apply(p, x[:, :19], _empty_cache(pc, 2), 1, lens)
    _, short = mixer.apply(p, x[1:, :7], _empty_cache(pc, 1), 1)
    for leaf in ("ssm_state", "ssm_conv"):
        np.testing.assert_allclose(both[leaf][1, 1], short[leaf][1, 0],
                                   atol=1e-5)
        assert np.all(np.asarray(both[leaf][0]) == 0)   # other blocks' slices
    lw = ref.layer_weights(w, "ssm", 1)
    step, _ = mixer.apply(p, jnp.stack([x[0, 19:20], x[1, 7:8]]), both, 1)
    np.testing.assert_allclose(step[0, 0], ref.ssm_mixer(x[:1], lw, cfg)[0, 19],
                               atol=2e-5)
    np.testing.assert_allclose(step[1, 0],
                               ref.ssm_mixer(x[1:, :8], lw, cfg)[0, 7],
                               atol=2e-5)
    # the rehearsed fault: the conv tail dropped at the hand-over
    lost = ref.ssm_mixer(x[1:, :8], lw, cfg, fault="conv_tail_dropped",
                         handover=7)[0, 7]
    assert float(jnp.max(jnp.abs(lost - step[1, 0]))) > 1e-2


# -- (c) prefill + decode through the four leaves = the full forward --------------

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


def test_ragged_prefill_then_decode_matches_full_forward():
    cfg, w, pc, params = _setup()
    assert set(pc.cache_leaves(1)) == {"positions", "ssm_state", "ssm_conv",
                                       "k", "v"}
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
    assert stats["experts_hit"].shape == (3,)       # the three E blocks
    assert np.all(np.asarray(stats["routed_pairs"]) == 2 * 3)


# -- (d) the same through DecodeEngine -------------------------------------------

def _greedy_reference(w, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_reference_logits(w, cfg, seq)[-1])))
    return seq[len(prompt):]


def _engine(pc, params, **kw):
    return DecodeEngine(pc, params, slots=2, steps_per_sync=2,
                        autostart=False, name="tiny-ssm", **kw)


# every kind of block once keeps the engine's dozen programs short
_SHORT = dict(num_hidden_layers=3, hybrid_override_pattern="M*E")


@pytest.mark.parametrize("admit_batch_max", [1, 8], ids=["row", "batch"])
def test_engine_serves_the_reference_and_reuses_slots(admit_batch_max):
    """Five greedy requests of unequal length on two slots: every slot is
    used again after its first request ends, so a stale state, conv tail
    or K / V row would show in the later answers; the tokens are those of
    an unbatched ``generate`` and of the reference."""
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
        alone = decode.generate(pc, params, jnp.asarray(p[None]),
                                max_new_tokens=6)
        assert tokens == [int(t) for t in alone[0]]


def test_engine_spans_carry_the_routing_counts_and_the_scanned_bucket():
    from kubeflow_tpu.obs.trace import DEFAULT_COLLECTOR

    _cfg, _w, pc, params = _setup(**_SHORT)
    engine = _engine(pc, params)
    try:
        _drive(engine, [engine.submit(np.arange(1, 8), max_new=5)])
    finally:
        engine.close()
    spans = [sp for sp in DEFAULT_COLLECTOR.spans()
             if sp.attrs.get("model") == "tiny-ssm"]
    last = [sp for sp in spans if sp.name == "engine.round"
            and sp.attrs["k"]][-1].attrs
    # 2 slots x 3 experts a token x 1 routed block x k steps, all held
    assert last["routed_pairs"] == 2 * 3 * last["k"]
    assert 0 < last["experts_hit"] <= last["routed_pairs"]
    # 7 prompt tokens scanned in a bucket of 8: what the benchmark's
    # ``ssm_prefill_pad_share_pct`` reads, from the window's admissions
    admit = [sp for sp in spans if sp.name == "engine.admit"][-1]
    out = {"serve": {"t0": admit.start, "t_end": admit.start + 1.0},
           "cell": SimpleNamespace(cfg={"name": "tiny-ssm"})}
    assert ssm_rounds.scan_tokens(out) == (8, 1)


# -- (e) what the engine refuses for this model, and its contract -----------------

def test_engine_refuses_paged_prefix_reuse_and_speculation():
    _cfg, _w, pc, params = _setup(**_SHORT)
    with pytest.raises(ValueError, match="also holds ssm_conv, ssm_state"):
        _engine(pc, params, paged=True)
    engine = _engine(pc, params)
    try:
        with pytest.raises(ValueError, match="recurrent state"):
            engine.submit(np.arange(1, 9), max_new=2, prefix_len=4)
    finally:
        engine.close()
    with pytest.raises(ValueError, match="also holds ssm_conv"):
        decode.speculative_generate(
            pc, params, pc, params, jnp.ones((1, 4), jnp.int32),
            max_new_tokens=2)


def test_cache_contract_and_pattern_are_declared_on_the_config():
    _cfg, _w, pc, _params = _setup()
    contract = pc.cache_leaves(3)
    assert {n: leaf.shape for n, leaf in contract.items()} == {
        "positions": (3,), "ssm_state": (3, 3, 2, 16, 16),
        "ssm_conv": (3, 3, 3, 32 + 2 * 2 * 16), "k": (1, 3, 64, 32),
        "v": (1, 3, 64, 32)}
    assert contract["ssm_state"].dtype == jnp.float32
    assert (contract["k"].heads_axis, contract["k"].head_width) == (3, 16)
    assert pc.has_recurrent_state and pc.n_moe == 3
    # attention alone keeps positional leaves only
    gqa = hybrid.dataclasses.replace(pc, layer_types=("gqa", "moe"))
    assert not gqa.has_recurrent_state
    assert set(gqa.cache_leaves(1)) == {"positions", "k", "v"}
    for bad in (dict(layer_types=("ssm", "mamba")), dict(n_kv_heads=3),
                dict(ssm_groups=3), dict(expert_act="gelu")):
        with pytest.raises(ValueError):
            hybrid.dataclasses.replace(pc, **bad).validate()


# -- (f) the configurations that share hybrid.py lower as at the parent -----------

def _digest(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


# sha256 (first 16 hex) of the engine's programs for Ling's and
# DeepSeek-V3.2's toy twins. PR 35 read them with this test's code on ITS
# parent commit (351dbc9, before the one-sublayer blocks); PR 36 changed
# all eight programs on purpose (the routed layers' products are
# ``ops/gmm.py``'s kernel where they were ``jax.lax.ragged_dot``) and
# re-pinned them from its own tree. A PR that changes such a program on
# purpose replaces them.
SERVED = {
    "ling": {"_step": "ba1b20864ee58630", "_step_greedy": "16733965848aaa0e",
             "_prefill": "497fba429d7e72a1",
             "_prefill_batch": "5aee8d327cdd867c"},
    "deepseek-v3.2": {"_step": "292bb21c326ca689",
                      "_step_greedy": "49700db9151180ac",
                      "_prefill": "951b33553d7bc6e7",
                      "_prefill_batch": "d50a35b77009e7d8"},
}


@pytest.mark.parametrize("family", sorted(SERVED))
def test_mixer_layer_programs_lower_to_the_parents_text(family):
    """A model of mixer + MLP layers builds the programs pinned above,
    letter for letter: ``hybrid.py``'s one-sublayer blocks (PR 35) left
    them as they were, the grouped matmul (PR 36) replaced them, and a PR
    that means to touch neither kind of layer has to leave them so. (The
    dense decoder's served programs and the training step are pinned in
    ``tests/test_remat_policy.py``.)"""
    import test_dsa
    import test_hybrid

    _cfg, _w, pc, params = {"ling": test_hybrid,
                            "deepseek-v3.2": test_dsa}[family]._setup()
    eng = DecodeEngine(pc, params, slots=2, steps_per_sync=2,
                       autostart=False)
    try:
        kv = eng._kv
        vec_i = jnp.zeros((2,), jnp.int32)
        ones_f = jnp.ones((2,), jnp.float32)
        one_i, one_f = jnp.int32(0), jnp.float32(1.0)
        got = {
            "_step": _digest(eng._step.lower(
                params, kv.cache, vec_i, vec_i, vec_i, ones_f, vec_i,
                ones_f)),
            "_step_greedy": _digest(eng._step_greedy.lower(
                params, kv.cache, vec_i)),
            "_prefill": _digest(kv._prefill.lower(
                params, jnp.zeros((1, 32), jnp.int32),
                jnp.asarray([20], jnp.int32), one_f, one_i, one_f, one_i,
                one_i)),
            "_prefill_batch": _digest(kv._prefill_batch.lower(
                params, jnp.zeros((2, 32), jnp.int32),
                jnp.array([20, 32], jnp.int32), ones_f, vec_i, ones_f,
                vec_i)),
        }
    finally:
        eng.close()
    assert got == SERVED[family]
