"""Plain reference: NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type``
``nemotron_h``) in float32.

Written from the equations of the configuration's source (ISSUE 35 lists
them; ``benchmark/configs/nemotron-3-nano-30b-a3b.json`` names every
reading under ``assumed``), importing nothing from the program. Weights
arrive in the layout of ``benchmark/harness/weights_ssm.py``.

    x = embed[tokens]
    for each kept block i, by the pattern's letter:
        x = x + Sub_i(rmsnorm(x, block_norm_i))     ONE sublayer a block
    logits = rmsnorm(x, final_norm) @ lm_head^T     (untied head)

``M`` (Mamba-2), H heads of P, state N a head, G groups, token by token:

    [z | xBC | dt] = h W_in                    (I | I + 2 G N | H), I = H P
    xBC_t = silu(b + sum_j w[j] xBC_{t-3+j})   depth-wise, causal, zeros
                                               before the row's start
    [x | B | C] = xBC                          (H, P) | (G, N) | (G, N)
    D_t = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h],   g(h) = h // (H / G)
    y = y * silu(z), RMS-normalised within each of the G groups of I / G
    channels, times a weight (I);  out = y W_out

The recurrence is a ``lax.scan`` over time (not the chunk-wise form the
program's prefill takes), the convolution explicit shifts.

``*`` (attention): q = h W_q (Hq x Dh), k = h W_k, v = h W_v (KH x Dh
each), NO rotary embedding, causal softmax(q k / sqrt(Dh)) v with Hq / KH
query heads a KV head, out = W_o; in query blocks of ``Q_BLOCK``.

``E`` (MoE): s = sigmoid(h W_r); selection on s + bias, one group, so the
K largest of all; weights s of the chosen over their sum, times the
scaling; y = the part of sum_e w_e W_down,e relu(W_up,e h)^2 that the
experts HELD here give (``experts_held`` = [lo, lo + n) of the router's
outputs; the whole range gives the uncut block) + the shared expert
W_down,s relu(W_up,s h)^2. The held experts are a loop; each is computed
for every token and masked by its weight: no token is dropped.

Everything is float32 and every matrix product runs at ``highest``.
``cast`` is the control's hook: a function applied to both operands of
every matrix product (``fp8_operands``, ``bf16_operands``). ``fault``
plants one of the rehearsed faults: ``"conv_tail_dropped"`` makes the
convolution of every token from ``handover`` on read zeros for the
inputs before ``handover`` (the conv tail lost where prefill hands a row
to decode); ``"norm_ungrouped"`` takes the gated norm over all I
channels at once.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256       # query rows attended at once
FAULTS = ("conv_tail_dropped", "norm_ungrouped")
KINDS = {"M": "ssm", "*": "attn", "E": "moe"}


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


def relu2_mlp(x, w_up, w_down, cast):
    return matmul(jnp.square(jax.nn.relu(matmul(x, w_up, cast))), w_down,
                  cast)


# -- Mamba-2 ---------------------------------------------------------------------

def short_conv(x, taps, bias, handover=None):
    """Causal depthwise conv: y_t = b + sum_j taps[j] x_{t-(K-1)+j}.
    ``handover`` (the fault): tokens from there on see zeros before it."""
    k, t = taps.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = bias
    for j in range(k):
        shifted = xp[:, j:j + t]               # input t - (k - 1) + j
        if handover is not None:
            at = jnp.arange(t)
            lost = (at >= handover) & (at - (k - 1) + j < handover)
            shifted = jnp.where(lost[None, :, None], 0.0, shifted)
        y = y + taps[j] * shifted
    return y


def gated_norm(y, z, weight, groups: int, eps):
    """y * silu(z), RMS-normalised within each group's channels."""
    b, t, i = y.shape
    y = (y * jax.nn.silu(z)).reshape(b, t, groups, i // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y.reshape(b, t, i) * weight


def ssm_mixer(x, lw, cfg, cast=None, fault=None, handover=None):
    b, t, _ = x.shape
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    i = h * p
    zxd = matmul(x, lw["ssm_in"], cast)
    z, xbc, dt = zxd[..., :i], zxd[..., i:2 * i + 2 * g * n], zxd[..., -h:]
    xbc = jax.nn.silu(short_conv(
        xbc, lw["ssm_conv_w"], lw["ssm_conv_b"],
        handover if fault == "conv_tail_dropped" else None))
    xs = xbc[..., :i].reshape(b, t, h, p)
    bm = xbc[..., i:i + g * n].reshape(b, t, g, n)
    cm = xbc[..., i + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + lw["ssm_dt_bias"])                # (B, T, H)
    a = -jnp.exp(lw["ssm_a_log"])
    heads = lambda y: jnp.repeat(y, h // g, axis=1)  # noqa: E731

    def step(state, xs_t):
        x_t, dt_t, b_t, c_t = xs_t       # (B, H, P) (B, H) (B, G, N) x2
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None]
                 * heads(b_t)[:, :, None, :])
        return state, einsum("bhpn,bhn->bhp", state, heads(c_t), cast)

    seq = lambda y: jnp.moveaxis(y, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32),
                        (seq(xs), seq(dt), seq(bm), seq(cm)))
    y = jnp.moveaxis(y, 0, 1) + lw["ssm_d"][:, None] * xs
    y = gated_norm(y.reshape(b, t, i), z, lw["ssm_norm"],
                   1 if fault == "norm_ungrouped" else g,
                   cfg["layer_norm_epsilon"])
    return matmul(y, lw["ssm_out"], cast)


# -- attention -------------------------------------------------------------------

def _attend_block(q, k, v, q_pos, cast):
    """q (B, Q, KH, R, Dh) against k, v (B, T, KH, Dh)."""
    s = einsum("bqhrd,bkhd->bhrqk", q, k, cast) / math.sqrt(q.shape[-1])
    mask = jnp.arange(k.shape[1])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf),
                       axis=-1)
    return einsum("bhrqk,bkhd->bqhrd", p, v, cast)


def attn_mixer(x, lw, cfg, cast=None):
    b, t, _ = x.shape
    hq, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = matmul(x, lw["attn_wq"], cast).reshape(b, t, kh, hq // kh, dh)
    k = matmul(x, lw["attn_wk"], cast).reshape(b, t, kh, dh)
    v = matmul(x, lw["attn_wv"], cast).reshape(b, t, kh, dh)
    if t <= Q_BLOCK or t % Q_BLOCK:
        o = _attend_block(q, k, v, jnp.arange(t), cast)
    else:
        nb = t // Q_BLOCK
        qb = jnp.moveaxis(q.reshape(b, nb, Q_BLOCK, kh, hq // kh, dh), 1, 0)
        pos = jnp.arange(t).reshape(nb, Q_BLOCK)
        o = jax.lax.map(lambda xs: _attend_block(xs[0], k, v, xs[1], cast),
                        (qb, pos))
        o = jnp.moveaxis(o, 0, 1)
    return matmul(o.reshape(b, t, hq * dh), lw["attn_wo"], cast)


# -- MoE -------------------------------------------------------------------------

def select(sel, cfg):
    """The chosen expert ids (N, K) from the selection scores (N, E) =
    sigmoid score + bias: one group, so the K largest of all."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this router has one group")
    return jax.lax.top_k(sel, cfg["num_experts_per_tok"])[1]


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
    lo, n = held if held is not None else (0, lw["exp_up"].shape[0])
    combine = jnp.sum(jax.nn.one_hot(idx - lo, n) * w[..., None], axis=1)

    def one(y, xs):
        w_up, w_down, c_e = xs
        return y + c_e[:, None] * relu2_mlp(
            x, w_up.astype(jnp.float32), w_down.astype(jnp.float32),
            cast), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lw["exp_up"], lw["exp_down"], combine.T))
    return y, idx


def moe_block(x, lw, cfg, cast=None, held=None):
    """The block as one chip computes it: its experts' part plus the
    shared expert. x (B, T, D)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    y, idx = routed_part(flat, lw, cfg, cast, held)
    y = y + relu2_mlp(flat, lw["sh_up"], lw["sh_down"], cast)
    return y.reshape(b, t, d), idx.reshape(b, t, -1)


# -- the decoder -----------------------------------------------------------------

_KIND_KEYS = {
    "ssm": ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_a_log", "ssm_dt_bias",
            "ssm_d", "ssm_norm", "ssm_out"),
    "attn": ("attn_wq", "attn_wk", "attn_wv", "attn_wo"),
    "moe": ("router", "router_bias", "sh_up", "sh_down"),
}
_EXPERT_KEYS = ("exp_up", "exp_down")   # widened expert by expert


def layer_weights(weights, kind: str, index: int):
    """Block ``index`` of its kind, widened to float32 (the experts stay
    as they are until each is used)."""
    lw = {n: weights[n][index].astype(jnp.float32) for n in _KIND_KEYS[kind]}
    if kind == "moe":
        lw.update({n: weights[n][index] for n in _EXPERT_KEYS})
    return lw


def forward(weights, tokens, cfg, cast=None, fit_bias=None, fault=None,
            handover=None):
    """(post-final-norm hidden states (B, T, D), the routed blocks' chosen
    expert ids (Le, B, T, K)) in float32. ``fit_bias`` is the hook of
    ``weights_ssm.balanced_router_bias``: called with a routed block's
    inputs (N, D) and its weights, it returns the selection bias the
    block then runs with. ``fault`` / ``handover``: the module's text."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    eps = cfg["layer_norm_epsilon"]
    held = tuple(cfg["experts_held"])
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    seen = dict.fromkeys(_KIND_KEYS, 0)
    chosen = []
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        kind = KINDS[letter]
        y = rms_norm(x, weights["block_norm"][i].astype(jnp.float32), eps)
        lw = layer_weights(weights, kind, seen[kind])
        seen[kind] += 1
        if kind == "ssm":
            x = x + ssm_mixer(y, lw, cfg, cast, fault, handover)
        elif kind == "attn":
            x = x + attn_mixer(y, lw, cfg, cast)
        else:
            if fit_bias is not None:
                lw["router_bias"] = fit_bias(y.reshape(-1, y.shape[-1]), lw)
            out, idx = moe_block(y, lw, cfg, cast, held)
            x = x + out
            chosen.append(idx)
    h = rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
    return h, jnp.stack(chosen)


def hidden(weights, tokens, cfg, cast=None, fault=None, handover=None):
    return forward(weights, tokens, cfg, cast, fault=fault,
                   handover=handover)[0]


def logits(weights, h, cast=None):
    """Over the rows of the vocabulary held here."""
    return matmul(h, weights["lm_head"].astype(jnp.float32).T, cast)
