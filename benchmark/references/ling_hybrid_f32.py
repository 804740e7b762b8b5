"""Plain reference: the text decoder of Ling-3.0-flash-VL in float32.

Written from the equations of the configuration's source (ISSUE 29 lists
them; ``benchmark/configs/ling-3.0-flash-vl.json`` names every reading of
a flag under ``assumed``), importing nothing from the program. The vision
tower is not part of it. Weights arrive in the layout of
``benchmark/harness/weights_hybrid.py``.

    x = embed[tokens]
    for each kept layer i:
        x = x + Mixer_i(rmsnorm(x, attn_norm_i))       KDA or MLA
        x = x + Mlp_i(rmsnorm(x, mlp_norm_i))          dense or routed
    logits = rmsnorm(x, final_norm) @ lm_head^T        (untied head)

KDA (Kimi Delta Attention), per head, state S (dk x dv), token by token:

    q, k, v = silu(conv4(x Wq)), silu(conv4(x Wk)), silu(conv4(x Wv))
    q = l2norm(q) / sqrt(dk);  k = l2norm(k)
    a = lower_bound * sigmoid(exp(a_log_h) * (x Wg + dt_bias))   per channel
    beta = sigmoid(x Wbeta)                                      per head
    S <- diag(exp(a)) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q
    out = (sigmoid(x Wog) * rmsnorm_head(o, o_norm)) Wo

MLA (no query compression), expanded form, full causal softmax:

    q = x Wq -> H x (nope + rope);  [c, k_r] = x Wkva;  c = rmsnorm(c)
    [k_nope, v] = c Wkvb per head;  k = [k_nope, k_r]   (k_r shared)
    q, k = rmsnorm(q, q_norm), rmsnorm(k, k_norm)   over each head's 192
    rope(theta) on the rope parts;  p = softmax(q k^T / sqrt(192))
    out = (sigmoid(x Wgate)_h * (p v)_h) Wo

Routed MLP: s = sigmoid(x Wr); selection on s + b: a group's score is the
sum of its top 2, the top ``topk_group`` groups stay, the top 8 experts
among them are chosen; weights scale * s_e / sum of the chosen s; the
layer gives the part of sum_e w_e swiglu_e(x) that the experts HELD here
give (``experts_held`` = [lo, lo + n) of the router's outputs; the whole
range gives the uncut layer), plus the shared expert. Every held expert is
computed for every token and masked by its weight: no token is dropped.

Everything is float32 and every matrix product runs at ``highest``.
``cast`` is the control's hook: a function applied to both operands of
every matrix product (``fp8_operands`` rounds them to float8_e4m3;
``bf16_operands`` to bfloat16, which is how the share of routing
decisions that the served precision flips is read).
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256       # query rows attended at once
L2_EPS = 1e-6


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


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def rope(x, theta):
    """x (B, S, N, Dr): rotate-half over all of the last axis."""
    s, dr = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w_gate, w_up, w_down, cast):
    g = jax.nn.silu(matmul(x, w_gate, cast)) * matmul(x, w_up, cast)
    return matmul(g, w_down, cast)


# -- KDA -------------------------------------------------------------------------

def short_conv(x, taps):
    """Causal depthwise conv: y_t = sum_j taps[j] * x_{t - (K-1) + j}."""
    k = taps.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * xp[:, j:j + t] for j in range(k))


def kda_mixer(x, lw, cfg, cast=None):
    b, t, _ = x.shape
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    c = h * dk
    taps = lw["kda_conv"]
    heads = lambda y: y.reshape(b, t, h, dk)  # noqa: E731
    q, k, v = (heads(jax.nn.silu(short_conv(
        matmul(x, lw[name], cast), taps[:, i * c:(i + 1) * c])))
        for i, name in enumerate(("kda_wq", "kda_wk", "kda_wv")))
    q = l2_norm(q) * dk ** -0.5
    k = l2_norm(k)
    gate = heads(matmul(x, lw["kda_wg"], cast) + lw["kda_dt_bias"])
    a = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lw["kda_a_log"])[None, None, :, None] * gate)
    beta = jax.nn.sigmoid(matmul(x, lw["kda_wbeta"], cast))    # (B, T, H)

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * jnp.exp(a_t)[..., None]
        u = b_t[..., None] * (v_t - einsum("bhkv,bhk->bhv", state, k_t, cast))
        state = state + k_t[..., None] * u[..., None, :]
        return state, einsum("bhkv,bhk->bhv", state, q_t, cast)

    seq = lambda y: jnp.moveaxis(y, 1, 0)  # noqa: E731
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dk), jnp.float32),
                        (seq(q), seq(k), seq(v), seq(a), seq(beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), lw["kda_o_norm"], cfg["rms_norm_eps"])
    o = jax.nn.sigmoid(heads(matmul(x, lw["kda_wog"], cast))) * o
    return matmul(o.reshape(b, t, c), lw["kda_wo"], cast)


# -- MLA -------------------------------------------------------------------------

def _attend_block(q, k, v, q_pos, cast):
    s = einsum("bqhd,bkhd->bhqk", q, k, cast) / math.sqrt(q.shape[-1])
    mask = jnp.arange(k.shape[1])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return einsum("bhqk,bkhd->bqhd", p, v, cast)


def mla_mixer(x, lw, cfg, cast=None):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    r, nope, rp, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = matmul(x, lw["mla_wq"], cast).reshape(b, t, h, nope + rp)
    kva = matmul(x, lw["mla_wkva"], cast)
    lat = rms_norm(kva[..., :r], lw["mla_kv_norm"], eps)
    kv = matmul(lat, lw["mla_wkvb"], cast).reshape(b, t, h, nope + vd)
    k_rope = jnp.broadcast_to(kva[:, :, None, r:], (b, t, h, rp))
    k = jnp.concatenate([kv[..., :nope], k_rope], -1)
    v = kv[..., nope:]
    q = rms_norm(q, lw["mla_q_norm"], eps)
    k = rms_norm(k, lw["mla_k_norm"], eps)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([k[..., :nope], rope(k[..., nope:], theta)], -1)
    if t <= Q_BLOCK or t % Q_BLOCK:
        o = _attend_block(q, k, v, jnp.arange(t), cast)
    else:
        nb = t // Q_BLOCK
        qb = jnp.moveaxis(q.reshape(b, nb, Q_BLOCK, h, nope + rp), 1, 0)
        pos = jnp.arange(t).reshape(nb, Q_BLOCK)
        o = jax.lax.map(lambda xs: _attend_block(xs[0], k, v, xs[1], cast),
                        (qb, pos))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t, h, vd)
    o = jax.nn.sigmoid(matmul(x, lw["mla_wgate"], cast))[..., None] * o
    return matmul(o.reshape(b, t, h * vd), lw["mla_wo"], cast)


# -- routed MLP ------------------------------------------------------------------

def select(sel, cfg):
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
    idx = select(s + lw["router_bias"], cfg)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, cfg["routed_scaling_factor"] * w


def routed_part(x, lw, cfg, cast=None, held=None):
    """What the experts held here add: x (N, D) -> (N, D), and the
    chosen ids. ``held`` = (lo, n) of the router's outputs; lw's expert
    tensors hold exactly those n."""
    idx, w = route(x, lw, cfg, cast)
    lo, n = held if held is not None else (0, lw["exp_gate"].shape[0])
    combine = jnp.sum(jax.nn.one_hot(idx - lo, n) * w[..., None], axis=1)

    def one(y, xs):
        w_gate, w_up, w_down, c_e = xs
        return y + c_e[:, None] * swiglu(x, w_gate, w_up, w_down, cast), None

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    y, _ = jax.lax.scan(
        lambda y, xs: one(y, (f32(xs[0]), f32(xs[1]), f32(xs[2]), xs[3])),
        jnp.zeros_like(x),
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
    "kda": ("kda_wq", "kda_wk", "kda_wv", "kda_wg", "kda_wog", "kda_wo",
            "kda_wbeta", "kda_conv", "kda_a_log", "kda_dt_bias",
            "kda_o_norm"),
    "mla": ("mla_wq", "mla_wkva", "mla_kv_norm", "mla_wkvb", "mla_q_norm",
            "mla_k_norm", "mla_wgate", "mla_wo"),
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


def forward(weights, tokens, cfg, cast=None, fit_bias=None):
    """(post-final-norm hidden states (B, T, D), the routed layers' chosen
    expert ids (Le, B, T, K)) in float32. ``fit_bias`` is the hook of
    ``weights_hybrid.balanced_router_bias``: called with a routed layer's
    inputs (N, D) and its weights, it returns the selection bias the
    layer then runs with."""
    eps = cfg["rms_norm_eps"]
    held = tuple(cfg["experts_held"])
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    seen = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
    chosen = []
    for i, mixer in enumerate(cfg["layer_types"]):
        y = rms_norm(x, weights["attn_norm"][i].astype(jnp.float32), eps)
        lw = layer_weights(weights, mixer, seen[mixer])
        seen[mixer] += 1
        x = x + (kda_mixer if mixer == "kda" else mla_mixer)(y, lw, cfg, cast)
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
    return h, jnp.stack(chosen)


def hidden(weights, tokens, cfg, cast=None):
    return forward(weights, tokens, cfg, cast)[0]


def logits(weights, h, cast=None):
    """Over the rows of the vocabulary held here."""
    return matmul(h, weights["lm_head"].astype(jnp.float32).T, cast)
