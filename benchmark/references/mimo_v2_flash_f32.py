"""Plain reference: MiMo-V2-Flash's decoder in float32.

Written from the equations of ISSUE 40 (the config's keys;
``benchmark/configs/mimo-v2-flash.json`` names every reading under
``assumed``), importing nothing from the program. Weights arrive in the
layout of ``benchmark/harness/weights_swa.py``.

    x = embed[tokens]
    for each kept layer i:
        x = x + Attn_i(rmsnorm(x, attn_norm_i))        global or window
        x = x + Ffn_i(rmsnorm(x, mlp_norm_i))          dense or routed
    logits = rmsnorm(x, final_norm) @ lm_head^T        (untied head)

Attention, H query heads of dk over KH KV heads (KH by the layer's kind),
values of dv; query head h reads KV head h // (H / KH):

    q = x Wq, k = x Wk, v = x Wv;  rope (rotate-half) on the first
        ``rotary`` dims of each q and k head, at the kind's theta
    s_ij = q_i . k_j / sqrt(dk)
    global:  p_ij = softmax_j(s_ij),               j <= i
    window:  p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij')),
             i - window < j <= i                   (b_h: the head's sink)
    o_i = value_scale * sum_j p_ij v_j;   out = concat_h(o) Wo

Queries are attended in blocks and heads in groups, so that (block x keys
x heads of a group) fits at 32768 positions; a window block reads only
the keys its band reaches.

Routed MLP: s = sigmoid(x Wr) over all the router's outputs; the 8
experts of largest s + b (one group); weights s_e / sum of the chosen s,
times the scaling factor (1.0 where the config's is null); the part that
the experts HELD here give (``experts_held``). No shared expert.

Everything is float32 and every matrix product runs at ``highest``.
``cast`` is the control's hook, applied to both operands of every matrix
product (``fp8_operands``, ``bf16_operands``). ``fault`` plants one of the
rehearsed faults (``FAULTS``).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128        # query rows attended at once
HEAD_GROUP = 16      # query heads attended at once
ROW_BLOCK = 4096     # rows of an MLP computed at once
FAULTS = ("window_short", "window_long", "no_sinks", "rope_all_dims",
          "no_value_scale")


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


def swiglu(x, w_gate, w_up, w_down, cast):
    """x (..., D); many rows go block by block."""
    def one(rows):
        g = jax.nn.silu(matmul(rows, w_gate, cast)) * matmul(rows, w_up, cast)
        return matmul(g, w_down, cast)

    flat = x.reshape(-1, x.shape[-1])
    n = flat.shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return one(x)
    out = jax.lax.map(one, flat.reshape(n // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(x.shape[:-1] + (out.shape[-1],))


def rotary_dims(cfg) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def rope(x, rot: int, theta: float):
    """x (B, S, N, dk), position s at index s: rotate-half over the first
    ``rot`` dims of the last axis, the rest as they are."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


# -- attention -------------------------------------------------------------------

def _attend(q, k, v, q_pos, k_pos, window, sinks, scale, cast):
    """q (B, Q, G, dk) at ``q_pos`` (Q,) against k (B, K, G, dk), v (B, K,
    G, dv) at ``k_pos`` (K,); ``window`` None (causal) or the band's
    width; ``sinks`` (G,) or None."""
    s = einsum("bqhd,bkhd->bhqk", q, k, cast) * scale
    live = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
    if window is not None:
        live = live & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(live[None, None], s, -jnp.inf)
    if sinks is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        b = sinks[None, :, None, None]
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), b)
        e = jnp.exp(s - m)
        p = e / (jnp.exp(b - m) + jnp.sum(e, axis=-1, keepdims=True))
    return einsum("bhqk,bkhd->bqhd", p, v, cast)


def attention(x, lw, cfg, window: bool, cast=None, fault=None):
    """One attention sublayer: x (B, T, D) -> (B, T, D)."""
    b, t, _ = x.shape
    h, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    kh = (cfg["swa_num_key_value_heads"] if window
          else cfg["num_key_value_heads"])
    theta = float(cfg["swa_rope_theta"] if window else cfg["rope_theta"])
    rot = dk if fault == "rope_all_dims" else rotary_dims(cfg)
    w = None
    if window:
        w = cfg["sliding_window"] + {"window_short": -1,
                                     "window_long": 1}.get(fault, 0)
    scale = dk ** -0.5
    vs = 1.0 if fault == "no_value_scale" else cfg["attention_value_scale"]
    k = rope(matmul(x, lw["wk"], cast).reshape(b, t, kh, dk), rot, theta)
    v = matmul(x, lw["wv"], cast).reshape(b, t, kh, dv)
    sinks = lw["sinks"] if window and fault != "no_sinks" else None
    g = min(HEAD_GROUP, h)
    rep = h // kh
    qb = Q_BLOCK if t > Q_BLOCK and t % Q_BLOCK == 0 else t
    nb = t // qb
    span = qb if w is None else min(t, qb + w)   # keys a block reads

    def group(out, ws):
        w_q, w_o, heads = ws         # this group's columns and rows
        q = rope(matmul(x, w_q, cast).reshape(b, t, g, dk), rot, theta)
        kv_head = heads // rep
        kg, vg = k[:, :, kv_head], v[:, :, kv_head]
        sg = None if sinks is None else sinks[heads]

        def block(i):
            at = i * qb
            qp = at + jnp.arange(qb)
            q_i = jax.lax.dynamic_slice_in_dim(q, at, qb, 1)
            if w is None:                # every key up to the block's end
                return _attend(q_i, kg, vg, qp, jnp.arange(t), None, sg,
                               scale, cast)
            lo = jnp.clip(at + qb - span, 0, t - span)
            kp = lo + jnp.arange(span)
            return _attend(q_i, jax.lax.dynamic_slice_in_dim(kg, lo, span, 1),
                           jax.lax.dynamic_slice_in_dim(vg, lo, span, 1), qp,
                           kp, w, sg, scale, cast)

        o = jax.lax.map(block, jnp.arange(nb))          # (nb, B, qb, g, dv)
        o = jnp.moveaxis(o, 0, 1).reshape(b, t, g * dv)
        return out + vs * matmul(o, w_o, cast), None

    ng = h // g
    by_group = (jnp.moveaxis(lw["wq"].reshape(-1, ng, g * dk), 1, 0),
                lw["wo"].reshape(ng, g * dv, -1),
                jnp.arange(h).reshape(ng, g))
    out, _ = jax.lax.scan(group, jnp.zeros_like(x), by_group)
    return out


# -- routed MLP ------------------------------------------------------------------

def select_experts(sel, cfg):
    """The chosen expert ids (N, K) from the selection scores (N, E) =
    sigmoid score + bias, one group."""
    return jax.lax.top_k(sel, cfg["num_experts_per_tok"])[1]


def route(x, lw, cfg, cast=None):
    """(chosen expert ids (N, K), their weights (N, K)) over ALL the
    router's outputs; x (N, D)."""
    s = jax.nn.sigmoid(matmul(x, lw["router"], cast))
    idx = select_experts(s + lw["router_bias"], cfg)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, (cfg["routed_scaling_factor"] or 1.0) * w


def routed_mlp(x, lw, cfg, cast=None):
    """What the experts held here add, x (B, T, D) -> (B, T, D), and the
    chosen ids (B, T, K). Every held expert is computed for every token
    and masked by its weight: no token is dropped."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    idx, w = route(flat, lw, cfg, cast)
    lo, n = cfg["experts_held"]
    combine = jnp.sum(jax.nn.one_hot(idx - lo, n) * w[..., None], axis=1)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def one(y, xs):
        w_gate, w_up, w_down, c_e = xs
        return y + c_e[:, None] * swiglu(flat, f32(w_gate), f32(w_up),
                                         f32(w_down), cast), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(flat),
        (lw["exp_gate"], lw["exp_up"], lw["exp_down"], combine.T))
    return y.reshape(b, t, d), idx.reshape(b, t, -1)


# -- the decoder -----------------------------------------------------------------

_KIND_KEYS = {
    "global": ("g_wq", "g_wk", "g_wv", "g_wo"),
    "window": ("s_wq", "s_wk", "s_wv", "s_wo", "s_sinks"),
    "dense": ("dense_gate", "dense_up", "dense_down"),
    "moe": ("router", "router_bias"),
}
_EXPERT_KEYS = ("exp_gate", "exp_up", "exp_down")   # widened expert by expert


def layer_weights(weights, kind: str, index: int):
    """Layer ``index`` of its kind, widened to float32, under the names
    the sublayer reads (the experts stay as they are until each is
    used)."""
    lw = {n.split("_", 1)[1] if kind in ("global", "window") else n:
          weights[n][index].astype(jnp.float32) for n in _KIND_KEYS[kind]}
    if kind == "moe":
        lw.update({n: weights[n][index] for n in _EXPERT_KEYS})
    return lw


def forward(weights, tokens, cfg, cast=None, fit_bias=None, fault=None):
    """(post-final-norm hidden states (B, T, D), the routed layers' chosen
    expert ids (Le, B, T, K)) in float32. ``fit_bias`` is the hook of
    ``weights_swa.balanced_router_bias``: called with a routed layer's
    inputs (N, D) and its weights, it returns the selection bias the
    layer then runs with."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    eps = cfg["layernorm_epsilon"]
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    seen = dict.fromkeys(_KIND_KEYS, 0)
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        kind = "window" if cfg["hybrid_layer_pattern"][i] else "global"
        lw = layer_weights(weights, kind, seen[kind])
        seen[kind] += 1
        y = rms_norm(x, weights["attn_norm"][i].astype(jnp.float32), eps)
        x = x + attention(y, lw, cfg, kind == "window", cast, fault)
        y = rms_norm(x, weights["mlp_norm"][i].astype(jnp.float32), eps)
        mlp = "moe" if cfg["moe_layer_freq"][i] else "dense"
        lw = layer_weights(weights, mlp, seen[mlp])
        seen[mlp] += 1
        if mlp == "dense":
            x = x + swiglu(y, lw["dense_gate"], lw["dense_up"],
                           lw["dense_down"], cast)
        else:
            if fit_bias is not None:
                lw["router_bias"] = fit_bias(y.reshape(-1, y.shape[-1]), lw)
            out, idx = routed_mlp(y, lw, cfg, cast)
            x = x + out
            chosen.append(idx)
    h = rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
    return h, jnp.stack(chosen)


def hidden(weights, tokens, cfg, cast=None, fault=None):
    return forward(weights, tokens, cfg, cast, fault=fault)[0]


def logits(weights, h, cast=None):
    """Over the rows of the vocabulary held here."""
    return matmul(h, weights["lm_head"].astype(jnp.float32).T, cast)
