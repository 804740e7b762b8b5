"""Plain reference: DeepSeek-V3.2's decoder in float32.

Written from the equations of ISSUE 33 (the config's keys and the model's
published inference code; ``benchmark/configs/deepseek-v3.2.json`` names
every reading under ``assumed``), importing nothing from the program.
Weights arrive in the layout of ``benchmark/harness/weights_dsa.py``.

    x = embed[tokens]
    for each kept layer i:
        x = x + Attn_i(rmsnorm(x, attn_norm_i))
        x = x + Ffn_i(rmsnorm(x, mlp_norm_i))          dense or routed
    logits = rmsnorm(x, final_norm) @ lm_head^T        (untied head)

Attention (MLA with a low-rank query, expanded form):

    c_q = rmsnorm(x Wqa);  q = c_q Wqb -> H x (nope | rope)
    [c_kv | k_r] = x Wkva;  c = rmsnorm(c_kv);  [k_nope | v] = c Wkvb
    rope on q_rope and on the one shared k_r (YaRN frequencies)
    s = (q_nope . k_nope + q_rope . k_r) * (nope + rope)^-0.5 * m^2,
        m = 0.1 * mscale_all_dim * ln(factor) + 1
    softmax over the SELECTED causal positions;  out = concat_h(p v) Wo

The lightning indexer selects, per query t at position t:

    q^I = c_q W^I_qb -> J x d;   k^I = layernorm(x W^I_k)   (with bias)
    rope on the first ``rope`` dims of both (rotate-half)
    w = x W^I_w * J^-0.5 * d^-0.5
    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]),   s <= t
    the min(index_topk, t + 1) positions of largest I[t, .], ties to the
    lower position

and attention is dense expanded attention under that selection as a
mask. Heads are attended in groups and queries in blocks, so that (block
x S x heads of a group) fits at 32768 positions.

Routed MLP: as ``ling_hybrid_f32.py`` (the same noaux_tc router): s =
sigmoid(x Wr); selection on s + b, a group's score the sum of its top 2,
``topk_group`` groups stay, the top 8 experts among them; weights scale *
s_e / sum of the chosen s; the part that the experts HELD here give
(``experts_held``), plus the shared expert.

Everything is float32 and every matrix product runs at ``highest``.
``cast`` is the control's hook, applied to both operands of every matrix
product (``fp8_operands``, ``bf16_operands``). ``fault`` plants one of
the rehearsed faults: ``"dense_attention"`` ignores the selection,
``"no_yarn_scale"`` leaves m^2 out of the softmax scale.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128        # query rows selected and attended at once
HEAD_GROUP = 16      # heads attended at once
FAULTS = ("dense_attention", "no_yarn_scale")


def fp8_operands(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def bf16_operands(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def einsum(spec: str, a, b, cast: Optional[Callable]):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def matmul(a, b, cast: Optional[Callable]):
    return einsum("...k,kn->...n", a, b, cast)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


ROW_BLOCK = 4096     # rows of an MLP computed at once


def swiglu(x, w_gate, w_up, w_down, cast):
    """x (..., D). Many rows go block by block: the two (rows, F) float32
    products of 32768 rows at F = 18432 would be 4.8 GB."""
    def one(rows):
        g = jax.nn.silu(matmul(rows, w_gate, cast)) * matmul(rows, w_up, cast)
        return matmul(g, w_down, cast)

    flat = x.reshape(-1, x.shape[-1])
    n = flat.shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return one(x)
    out = jax.lax.map(one, flat.reshape(n // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(x.shape[:-1] + (out.shape[-1],))


# -- rope ------------------------------------------------------------------------

def yarn_inv_freq(cfg):
    """The ``rope`` / 2 inverse frequencies. With ``rope_scaling`` of type
    yarn: a dim that turns more than ``beta_fast`` times over the
    original context keeps its frequency, one that turns fewer than
    ``beta_slow`` times has it divided by ``factor``, and the dims between
    blend linearly."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    rs = cfg.get("rope_scaling")
    if not rs:
        return inv
    orig = rs["original_max_position_embeddings"]

    def dim_of(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / rs["factor"] * ramp + inv * (1.0 - ramp)


def yarn_mscale(cfg) -> float:
    rs = cfg.get("rope_scaling")
    if not rs or rs["factor"] <= 1:
        return 1.0
    return 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0


def rope(x, inv_freq):
    """x (B, S, N, Dr), position s at index s: rotate-half over all of
    the last axis."""
    s, dr = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the indexer -----------------------------------------------------------------

def index_inputs(x, c_q, lw, cfg, cast=None):
    """(q^I (B, T, J, d), k^I (B, T, d), w (B, T, J))."""
    b, t, _ = x.shape
    j, d, rp = (cfg["index_n_heads"], cfg["index_head_dim"],
                cfg["qk_rope_head_dim"])
    inv = yarn_inv_freq(cfg)
    q = matmul(c_q, lw["idx_wq"], cast).reshape(b, t, j, d)
    k = layer_norm(matmul(x, lw["idx_wk"], cast), lw["idx_k_norm_w"],
                   lw["idx_k_norm_b"], cfg["rms_norm_eps"])
    q = jnp.concatenate([rope(q[..., :rp], inv), q[..., rp:]], -1)
    k = jnp.concatenate([rope(k[:, :, None, :rp], inv)[:, :, 0],
                         k[..., rp:]], -1)
    w = matmul(x, lw["idx_ww"], cast) * (j ** -0.5 * d ** -0.5)
    return q, k, w


def index_scores(q, k, w, q_pos, cast=None):
    """I[t, s] for the queries given: q (B, Q, J, d), w (B, Q, J), at
    positions ``q_pos`` (Q,), against k (B, S, d); ``-inf`` where s > t."""
    s = einsum("bqjd,bsd->bqjs", q, k, cast)
    s = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)
    live = jnp.arange(k.shape[1])[None, :] <= q_pos[:, None]
    return jnp.where(live[None], s, -jnp.inf)


def select(scores, q_pos, topk: int):
    """(each query's selected positions, ascending, (B, Q, K) int32 with
    the row length S where the query has fewer than K positions; the same
    sets as a mask (B, Q, S)). ``top_k`` breaks ties to the lower
    position, so the set is every position that scores above the K-th
    value, and of those that tie with it the lowest."""
    s = scores.shape[-1]
    k = min(topk, s)
    vals, idx = jax.lax.top_k(scores, k)
    valid = idx <= q_pos[None, :, None]
    kth = jnp.min(jnp.where(valid, vals, jnp.inf), axis=-1, keepdims=True)
    last = jnp.max(jnp.where(valid & (vals == kth), idx, -1), axis=-1,
                   keepdims=True)
    at = jnp.arange(s)[None, None, :]
    mask = (at <= q_pos[None, :, None]) & (
        (scores > kth) | ((scores == kth) & (at <= last)))
    picked = jnp.sort(jnp.where(valid, idx, s), axis=-1).astype(jnp.int32)
    return picked, mask


def _blocks(t: int):
    return (t // Q_BLOCK, Q_BLOCK) if t > Q_BLOCK and t % Q_BLOCK == 0 \
        else (1, t)


def selection(x, c_q, lw, cfg, cast=None):
    """(picked (B, T, K), mask (B, T, T)): the selected set of every
    query, block by block."""
    b, t, _ = x.shape
    q, k, w = index_inputs(x, c_q, lw, cfg, cast)
    nb, qb = _blocks(t)

    def one(xs):
        q_b, w_b, pos = xs
        return select(index_scores(q_b, k, w_b, pos, cast), pos,
                      cfg["index_topk"])

    split = lambda y: jnp.moveaxis(  # noqa: E731
        y.reshape((b, nb, qb) + y.shape[2:]), 1, 0)
    picked, mask = jax.lax.map(one, (split(q), split(w),
                                     jnp.arange(t).reshape(nb, qb)))
    join = lambda y: jnp.moveaxis(y, 0, 1).reshape(b, t, -1)  # noqa: E731
    return join(picked), join(mask)


# -- attention -------------------------------------------------------------------

def _attend_block(q, k, v, q_pos, chosen, scale, cast):
    """q (B, Q, G, dq) against all of k, v (B, S, G, .): causal, and where
    the mask ``chosen`` (B, Q, S) is given, over those positions alone."""
    s = einsum("bqhd,bkhd->bhqk", q, k, cast) * scale
    mask = jnp.broadcast_to(jnp.arange(k.shape[1])[None, None, :]
                            <= q_pos[None, :, None], s.shape[:1] + s.shape[2:])
    if chosen is not None:
        mask = mask & chosen
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return einsum("bhqk,bkhd->bqhd", p, v, cast)


def mla_mixer(x, lw, cfg, cast=None, fault=None, dense=False):
    """(out (B, T, D), picked (B, T, K)). ``dense`` attends to every
    causal position (the model with the indexer ignored)."""
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    r, nope, rp, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    inv = yarn_inv_freq(cfg)
    scale = (nope + rp) ** -0.5
    if fault != "no_yarn_scale":
        scale = scale * yarn_mscale(cfg) ** 2
    c_q = rms_norm(matmul(x, lw["mla_wqa"], cast), lw["mla_q_a_norm"], eps)
    kva = matmul(x, lw["mla_wkva"], cast)
    lat = rms_norm(kva[..., :r], lw["mla_kv_norm"], eps)
    k_r = rope(kva[:, :, None, r:], inv)                     # (B, T, 1, rp)
    picked, chosen = selection(x, c_q, lw, cfg, cast)
    use = None if (dense or fault == "dense_attention") else chosen
    g = min(HEAD_GROUP, h)
    ng = h // g
    nb, qb = _blocks(t)

    def group(out, ws):
        w_qb, w_kvb, w_o = ws          # this group's columns and rows
        q = matmul(c_q, w_qb, cast).reshape(b, t, g, nope + rp)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv)], -1)
        kv = matmul(lat, w_kvb, cast).reshape(b, t, g, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, g, rp))], -1)
        v = kv[..., nope:]
        if nb == 1:
            o = _attend_block(q, k, v, jnp.arange(t), use, scale, cast)
        else:
            split = lambda y: jnp.moveaxis(  # noqa: E731
                y.reshape((b, nb, qb) + y.shape[2:]), 1, 0)
            pos = jnp.arange(t).reshape(nb, qb)
            if use is None:
                o = jax.lax.map(lambda xs: _attend_block(
                    xs[0], k, v, xs[1], None, scale, cast), (split(q), pos))
            else:
                o = jax.lax.map(lambda xs: _attend_block(
                    xs[0], k, v, xs[1], xs[2], scale, cast),
                    (split(q), pos, split(use)))
            o = jnp.moveaxis(o, 0, 1).reshape(b, t, g, vd)
        return out + matmul(o.reshape(b, t, g * vd), w_o, cast), None

    qr = lw["mla_wqb"].shape[0]
    by_group = (
        jnp.moveaxis(lw["mla_wqb"].reshape(qr, ng, g * (nope + rp)), 1, 0),
        jnp.moveaxis(lw["mla_wkvb"].reshape(r, ng, g * (nope + vd)), 1, 0),
        lw["mla_wo"].reshape(ng, g * vd, -1))
    out, _ = jax.lax.scan(group, jnp.zeros_like(x), by_group)
    return out, picked


# -- routed MLP ------------------------------------------------------------------

def select_experts(sel, cfg):
    """The chosen expert ids (N, K) from the selection scores (N, E) =
    sigmoid score + bias: a group's score is the sum of its top 2, the
    top ``topk_group`` groups stay, the top K experts among them win."""
    n_group, et = cfg["n_group"], sel.shape[-1]
    grouped = sel.reshape(-1, n_group, et // n_group)
    g_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, g_idx = jax.lax.top_k(g_score, cfg["topk_group"])
    g_keep = jnp.sum(jax.nn.one_hot(g_idx, n_group), axis=1) > 0
    keep = jnp.repeat(g_keep, et // n_group, axis=-1)
    return jax.lax.top_k(jnp.where(keep, sel, -jnp.inf),
                         cfg["num_experts_per_tok"])[1]


def route(x, lw, cfg, cast=None):
    """(chosen expert ids (N, K), their weights (N, K)) over ALL the
    router's outputs; x (N, D)."""
    s = jax.nn.sigmoid(matmul(x, lw["router"], cast))
    idx = select_experts(s + lw["router_bias"], cfg)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, cfg["routed_scaling_factor"] * w


def routed_part(x, lw, cfg, cast=None, held=None):
    """What the experts held here add: x (N, D) -> (N, D), and the
    chosen ids. ``held`` = (lo, n) of the router's outputs; lw's expert
    tensors hold exactly those n. Every held expert is computed for every
    token and masked by its weight: no token is dropped."""
    idx, w = route(x, lw, cfg, cast)
    lo, n = held if held is not None else (0, lw["exp_gate"].shape[0])
    combine = jnp.sum(jax.nn.one_hot(idx - lo, n) * w[..., None], axis=1)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def one(y, xs):
        w_gate, w_up, w_down, c_e = xs
        return y + c_e[:, None] * swiglu(x, f32(w_gate), f32(w_up),
                                         f32(w_down), cast), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["exp_gate"], lw["exp_up"], lw["exp_down"], combine.T))
    return y, idx


def routed_mlp(x, lw, cfg, cast=None, held=None):
    """The layer as one chip computes it: its experts' part plus the
    shared expert. x (B, T, D)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    y, idx = routed_part(flat, lw, cfg, cast, held)
    y = y + swiglu(flat, lw["sh_gate"], lw["sh_up"], lw["sh_down"], cast)
    return y.reshape(b, t, d), idx.reshape(b, t, -1)


# -- the decoder -----------------------------------------------------------------

_KIND_KEYS = {
    "attn": ("mla_wqa", "mla_q_a_norm", "mla_wqb", "mla_wkva", "mla_kv_norm",
             "mla_wkvb", "mla_wo", "idx_wq", "idx_wk", "idx_k_norm_w",
             "idx_k_norm_b", "idx_ww"),
    "dense": ("dense_gate", "dense_up", "dense_down"),
    "moe": ("router", "router_bias", "sh_gate", "sh_up", "sh_down"),
}
_EXPERT_KEYS = ("exp_gate", "exp_up", "exp_down")   # widened expert by expert


def layer_weights(weights, kind: str, index: int):
    """Layer ``index`` of its kind, widened to float32 (the experts stay
    as they are until each is used)."""
    lw = {n: weights[n][index].astype(jnp.float32) for n in _KIND_KEYS[kind]}
    if kind == "moe":
        lw.update({n: weights[n][index] for n in _EXPERT_KEYS})
    return lw


def set_digest(picked):
    """(B, T, 2) uint32 that differs where two selected sets differ (the
    wrapping sums of the positions and of their squares): what a
    comparison of two forwards at 32768 positions keeps of each layer's
    1.3 GB of sets."""
    p = picked.astype(jnp.uint32)
    return jnp.stack([jnp.sum(p, -1), jnp.sum(p * p, -1)], -1)


def forward(weights, tokens, cfg, cast=None, fit_bias=None, fault=None,
            dense=False, digest=False):
    """(post-final-norm hidden states (B, T, D), the routed layers' chosen
    expert ids (Le, B, T, K), every layer's selected sets (L, B, T, Kt),
    or with ``digest`` their :func:`set_digest`) in float32.
    ``fit_bias`` is the hook of
    ``weights_dsa.balanced_router_bias``: called with a routed layer's
    inputs (N, D) and its weights, it returns the selection bias the
    layer then runs with."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    eps = cfg["rms_norm_eps"]
    held = tuple(cfg["experts_held"])
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    seen = {"dense": 0, "moe": 0}
    chosen, picked = [], []
    for i in range(cfg["num_hidden_layers"]):
        y = rms_norm(x, weights["attn_norm"][i].astype(jnp.float32), eps)
        out, sel = mla_mixer(y, layer_weights(weights, "attn", i), cfg, cast,
                             fault, dense)
        x = x + out
        picked.append(set_digest(sel) if digest else sel)
        y = rms_norm(x, weights["mlp_norm"][i].astype(jnp.float32), eps)
        mlp = "dense" if i < cfg["first_k_dense_replace"] else "moe"
        lw = layer_weights(weights, mlp, seen[mlp])
        seen[mlp] += 1
        if mlp == "dense":
            x = x + swiglu(y, lw["dense_gate"], lw["dense_up"],
                           lw["dense_down"], cast)
        else:
            if fit_bias is not None:
                lw["router_bias"] = fit_bias(y.reshape(-1, y.shape[-1]), lw)
            out, idx = routed_mlp(y, lw, cfg, cast, held)
            x = x + out
            chosen.append(idx)
    h = rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
    return h, jnp.stack(chosen), jnp.stack(picked)


def hidden(weights, tokens, cfg, cast=None, fault=None, dense=False):
    return forward(weights, tokens, cfg, cast, fault=fault, dense=dense)[0]


def logits(weights, h, cast=None):
    """Over the rows of the vocabulary held here."""
    return matmul(h, weights["lm_head"].astype(jnp.float32).T, cast)
