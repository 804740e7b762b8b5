"""Mamba-2's selective state update: one token as a Pallas kernel, a
prompt in the chunk-wise (SSD) form.

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]

per row, on a float32 state ``S`` of H heads of (P, N): a scalar decay a
head, ``B`` and ``C`` shared by the ``H / G`` heads of a group (``g(h) =
h // (H / G)``). The step is bound by the state's bytes: the kernel reads
each row's state once and writes it once, in place, on the stacked
``(L, B, H / pack, N, pack P)`` cache leaf (:func:`pack_state`), aliased
to its output, and touches layer ``index`` alone.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kubeflow_tpu.ops.attention import resolve_interpret

HIGHEST = jax.lax.Precision.HIGHEST


def ssm_recurrent_step(state, x, dt, A, B, C, D):
    """One token in ``jax.numpy``: what the kernel computes. ``state``
    (B, H, P, N) f32; x (B, H, P); dt (B, H); A, D (H,); B, C (B, G, N).
    Returns (state, y (B, H, P))."""
    H, G = x.shape[1], B.shape[1]
    Bh, Ch = (jnp.repeat(y, H // G, axis=1) for y in (B, C))  # (B, H, N)
    state = (state * jnp.exp(dt * A)[..., None, None]
             + (dt[..., None] * x)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(state * Ch[:, :, None, :], axis=-1) + D[:, None] * x
    return state, y


def pack_state(state, pack: int):
    """(B, H, P, N) -> (B, H / pack, N, pack P): the cache leaf's
    layout. The state is stored transposed, N on the sublanes, with
    ``pack`` heads of one group side by side on the lanes (two heads of
    64 fill the 128), because the step's product with C then reduces over
    sublanes (vector adds) where (P, N) reduces across lanes, and what
    varies along N (B, C) arrives as one column a GROUP: with (P, N) the
    kernel ran at 29 % of the state's bytes over the HBM peak (my chip
    run, PR 35)."""
    Bn, H, P, N = state.shape
    s = state.reshape(Bn, H // pack, pack, P, N)
    return jnp.moveaxis(s, 4, 2).reshape(Bn, H // pack, N, pack * P)


def unpack_state(packed, pack: int):
    """:func:`pack_state`'s inverse."""
    Bn, Hp, N, W = packed.shape
    s = packed.reshape(Bn, Hp, N, pack, W // pack)
    return jnp.moveaxis(s, 2, 4).reshape(Bn, Hp * pack, W // pack, N)


def _ssm_step_kernel(rows_ref, cols_ref, state_ref, out_state_ref, y_ref,
                     *, packs: int, per_group: int):
    for j in range(packs):
        g = j // per_group
        decay, dtx, dx = (rows_ref[0, i, j:j + 1, :] for i in range(3))
        b, c = (cols_ref[0, i, :, g:g + 1] for i in range(2))     # (N, 1)
        s = state_ref[0, 0, j] * decay + b * dtx                  # (N, W)
        out_state_ref[0, 0, j] = s
        y_ref[0, j:j + 1, :] = jnp.sum(s * c, axis=0, keepdims=True) + dx


def ssm_step(state, index: int, x, dt, A, B, C, D, *,
             interpret: Optional[bool] = None):
    """``state`` (L, B, H / pack, N, pack P) f32 (:func:`pack_state`),
    updated at layer ``index`` (static); the rest as
    :func:`ssm_recurrent_step`, all f32. Returns (state, y (B, H, P)).
    One program a row, all its heads."""
    L, Bn, Hp, N, W = state.shape
    H, P = x.shape[1:]
    G = B.shape[1]
    pack = H // Hp
    # what varies along a head's P arrives as rows, a pack of heads side
    # by side as the state has them: [decay | dt x | D x], the decay
    # repeated along its head's lanes; B and C as columns, N on the
    # sublanes and the groups on the lanes
    rows = jnp.stack([
        jnp.broadcast_to(jnp.exp(dt * A)[..., None], x.shape),
        dt[..., None] * x, D[:, None] * x], axis=1).reshape(Bn, 3, Hp, W)
    cols = jnp.swapaxes(jnp.stack([B, C], axis=1), 2, 3)     # (B, 2, N, G)
    st = pl.BlockSpec((1, 1, Hp, N, W), lambda b: (index, b, 0, 0, 0))
    out = pl.BlockSpec((1, Hp, W), lambda b: (b, 0, 0))
    state, y = pl.pallas_call(
        functools.partial(_ssm_step_kernel, packs=Hp,
                          per_group=H // G // pack),
        grid=(Bn,),
        in_specs=[pl.BlockSpec((1, 3, Hp, W), lambda b: (b, 0, 0, 0)),
                  pl.BlockSpec((1, 2, N, G), lambda b: (b, 0, 0, 0)), st],
        out_specs=[st, out],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((Bn, Hp, W), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
    )(rows, cols, state)
    return state, y.reshape(Bn, H, P)


def ssm_chunked(state, x, dt, A, B, C, D, chunk: int, lens=None):
    """The same recurrence over T tokens, chunk by chunk: inside a chunk
    of Q tokens with cumulative log-decay ``a_t = sum_{s<=t} dt_s A``,

        y_t = exp(a_t) S0 C_t + sum_{s<=t} exp(a_t - a_s) (C_t . B_s)
              dt_s x_s + D x_t
        S'  = exp(a_Q) S0 + sum_s exp(a_Q - a_s) dt_s x_s (x) B_s

    Every exponent is <= 0 (A < 0, dt >= 0), so nothing needs a
    reference point. ``state`` (B, H, P, N) f32; x (B, T, H, P); dt
    (B, T, H); B, C (B, T, G, N); all f32. ``lens`` (B,): past a row's
    own length a token gets dt = 0 (decay 1, input 0), so the state
    freezes exactly. Returns (state, y (B, T, H, P))."""
    Bn, T, H, P = x.shape
    G, N = B.shape[2:]
    if lens is not None:
        live = jnp.arange(T)[None, :] < lens[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
    pad = -T % chunk
    if pad:
        x, B, C = (jnp.pad(y, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for y in (x, B, C))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // chunk
    R = H // G

    def chunks(y):   # (B, T, ...) -> (nc, B, Q, ...)
        return jnp.moveaxis(y.reshape((Bn, nc, chunk) + y.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    mm = lambda spec, *ys: jnp.einsum(spec, *ys, precision=HIGHEST)  # noqa: E731

    def body(s0, xs):
        xc, dc, bc, cc = xs          # (B, Q, H, P) (B, Q, H) (B, Q, G, N) x2
        a = jnp.cumsum(dc * A, axis=1)                        # (B, Q, H)
        dtx = (dc[..., None] * xc).reshape(Bn, chunk, G, R, P)
        # masked before the exponential: above the diagonal a_t - a_s > 0
        decay = jnp.exp(jnp.where(
            tri[None, :, :, None], a[:, :, None] - a[:, None], -jnp.inf))
        cb = mm("btgn,bsgn->btsg", cc, bc)                    # (B, Q, Q, G)
        w = decay.reshape(Bn, chunk, chunk, G, R) * cb[..., None]
        y = mm("btsgr,bsgrp->btgrp", w, dtx)
        s0g = s0.reshape(Bn, G, R, P, N)
        y = y + jnp.exp(a).reshape(Bn, chunk, G, R)[..., None] * mm(
            "btgn,bgrpn->btgrp", cc, s0g)
        to_end = jnp.exp(a[:, -1:] - a).reshape(Bn, chunk, G, R)
        s1 = (jnp.exp(a[:, -1]).reshape(Bn, G, R)[..., None, None] * s0g
              + mm("bsgrp,bsgn->bgrpn", to_end[..., None] * dtx, bc))
        return s1.reshape(s0.shape), y.reshape(Bn, chunk, H, P)

    state, y = jax.lax.scan(body, state,
                            (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = jnp.moveaxis(y, 0, 1).reshape(Bn, nc * chunk, H, P)
    return state, (y + D[:, None] * x)[:, :T]
