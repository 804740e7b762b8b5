"""Plain reference: the published SmolLM2 (Llama-style) decoder in float32.

Written from the model's description, importing nothing from the program:

    x   = embed[tokens]
    for each layer:
        h = rmsnorm(x, attn_norm, eps)
        q, k, v = h @ wq, h @ wk, h @ wv          (no biases)
        q, k = rope(q), rope(k)                    (rotate-half, theta)
        a = softmax(q k^T / sqrt(Dh) + causal) v   (GQA: each KV head serves
                                                    H/KH query heads)
        x = x + a @ wo
        h = rmsnorm(x, mlp_norm, eps)
        x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = rmsnorm(x, final_norm, eps) @ embed^T  (tied head)

Everything is float32 and every matrix product runs at ``highest``
precision (on a TPU a float32 product is otherwise done in bfloat16
passes). Weights arrive in the layout of ``benchmark/harness/weights.py``
and are widened to float32 layer by layer, so a bf16 model's reference
never holds a float32 copy of all of it. Long sequences are handled in
blocks of query rows and of loss positions, recomputed in the backward
pass, so 2 x 8192 tokens fit one chip beside the optimizer state.

``operand_cast`` is the control's hook: a function applied to both
operands of every matrix product (``fp8_operands`` rounds them to
float8_e4m3). The reference proper passes none.

For training the file also holds the loss, its gradients and a plain
AdamW with global-norm clipping and a linear-warm-up / cosine schedule,
as the configuration's ``optimizer`` section states them.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
              "w_gate", "w_up", "w_down")
Q_BLOCK = 1024      # query rows attended at once
LOSS_BLOCK = 2048   # positions whose logits exist at once


def fp8_operands(x):
    """The control: round a matmul operand to float8_e4m3 and back."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def matmul(a, b, cast: Optional[Callable]):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta):
    """x (B, S, N, Dh); rotate-half convention of the published model."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend_block(q, k, v, q_pos, cast):
    """q (B, Sq, H, Dh) against all of k, v (B, S, H, Dh), causal."""
    dh = q.shape[-1]
    qt = jnp.swapaxes(q, 1, 2)                      # (B, H, Sq, Dh)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = matmul(qt, jnp.swapaxes(kt, -1, -2), cast) / math.sqrt(dh)
    kv_pos = jnp.arange(k.shape[1])
    mask = kv_pos[None, :] <= q_pos[:, None]        # (Sq, S)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(matmul(p, vt, cast), 1, 2)     # (B, Sq, H, Dh)


def attention(q, k, v, cast):
    """Causal attention, in blocks of query rows where S is long."""
    b, s, h, dh = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    if s <= Q_BLOCK or s % Q_BLOCK:
        return _attend_block(q, k, v, jnp.arange(s), cast)
    nb = s // Q_BLOCK
    qb = jnp.moveaxis(q.reshape(b, nb, Q_BLOCK, h, dh), 1, 0)
    pos = jnp.arange(s).reshape(nb, Q_BLOCK)
    block = jax.checkpoint(
        lambda qq, pp: _attend_block(qq, k, v, pp, cast))
    out = jax.lax.map(lambda xs: block(*xs), (qb, pos))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)


def layer(x, lw, cfg, cast):
    b, s, d = x.shape
    h, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    lw = {n: w.astype(jnp.float32) for n, w in lw.items()}
    pos = jnp.arange(s)
    y = rms_norm(x, lw["attn_norm"], eps)
    q = matmul(y, lw["wq"], cast).reshape(b, s, h, dh)
    k = matmul(y, lw["wk"], cast).reshape(b, s, kh, dh)
    v = matmul(y, lw["wv"], cast).reshape(b, s, kh, dh)
    a = attention(rope(q, pos, theta), rope(k, pos, theta), v, cast)
    x = x + matmul(a.reshape(b, s, h * dh), lw["wo"], cast)
    y = rms_norm(x, lw["mlp_norm"], eps)
    g = jax.nn.silu(matmul(y, lw["w_gate"], cast)) * matmul(y, lw["w_up"], cast)
    return x + matmul(g, lw["w_down"], cast)


def hidden(weights, tokens, cfg, cast=None, remat=False):
    """Post-final-norm hidden states (B, S, D) in float32."""
    embed = weights["embed"]
    x = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    one = functools.partial(layer, cfg=cfg, cast=cast)
    if remat:
        one = jax.checkpoint(one)

    def body(x, lw):
        return one(x, lw), None

    x, _ = jax.lax.scan(body, x, {n: weights[n] for n in LAYER_KEYS})
    return rms_norm(x, weights["final_norm"].astype(jnp.float32),
                    cfg["rms_norm_eps"])


def logits_at(weights, tokens, positions, cfg, cast=None):
    """Logits (B, P, V) at the given positions of every row."""
    h = hidden(weights, tokens, cfg, cast)
    h = jnp.take(h, positions, axis=1)
    return matmul(h, weights["embed"].astype(jnp.float32).T, cast)


def next_token_loss(weights, tokens, cfg, cast=None):
    """Mean cross-entropy of tokens[:, 1:] under logits[:, :-1]."""
    h = hidden(weights, tokens, cfg, cast, remat=True)[:, :-1]
    tgt = tokens[:, 1:]
    b, n, d = h.shape
    head = weights["embed"].astype(jnp.float32).T
    blk = LOSS_BLOCK if n > LOSS_BLOCK else n
    pad = (-n) % blk
    h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    live = (jnp.arange(n + pad) < n).astype(jnp.float32)
    nb = (n + pad) // blk
    hb = jnp.moveaxis(h.reshape(b, nb, blk, d), 1, 0)
    tb = jnp.moveaxis(tgt.reshape(b, nb, blk), 1, 0)
    lb = live.reshape(nb, blk)

    @jax.checkpoint
    def block_ll(hc, tc, lc):
        logp = jax.nn.log_softmax(matmul(hc, head, cast), axis=-1)
        ll = jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(ll * lc[None, :])

    def body(acc, xs):
        return acc + block_ll(*xs), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hb, tb, lb))
    return -total / (b * n)


def loss_and_grads(weights, tokens, cfg, cast=None):
    return jax.value_and_grad(next_token_loss)(weights, tokens, cfg, cast)


# -- the optimizer, as the configuration's ``optimizer`` section states it ---

def learning_rate(opt: dict, count):
    """Linear warm-up from 0 to the peak, then cosine decay to 0."""
    peak, warm = opt["learning_rate"], opt["warmup_steps"]
    decay = max(opt["decay_steps"], warm + 1) - warm
    count = jnp.asarray(count, jnp.float32)
    up = peak * count / max(warm, 1)
    frac = jnp.clip((count - warm) / decay, 0.0, 1.0)
    down = peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(count < warm, up, down)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(tree)))


def clip_by_global_norm(grads, max_norm):
    norm = global_norm(grads)
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def adamw_init(weights):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    return {"count": jnp.int32(0), "mu": zeros,
            "nu": jax.tree_util.tree_map(jnp.zeros_like, weights)}


def adamw_update(weights, grads, state, opt: dict):
    """One AdamW update on clipped ``grads``; returns (weights, state)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    t = state["count"] + 1
    lr = learning_rate(opt, state["count"])
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                state["nu"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)

    def step(w, m, v):
        return w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w)

    new = jax.tree_util.tree_map(step, weights, mu, nu)
    return new, {"count": t, "mu": mu, "nu": nu}


def train_step(weights, state, tokens, cfg, opt: dict, cast=None):
    """(weights, state, loss, raw gradient norm, clipped grads)."""
    loss, grads = loss_and_grads(weights, tokens, cfg, cast)
    raw_norm = global_norm(grads)
    grads = clip_by_global_norm(grads, opt["grad_clip"])
    weights, state = adamw_update(weights, grads, state, opt)
    return weights, state, loss, raw_norm, grads
