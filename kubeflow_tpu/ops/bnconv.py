"""Fused BN-apply + ReLU + 1x1-conv for the ResNet bottleneck.

The r3 trace decomposition (PERF.md) shows exact-BN ResNet-50 training
at hbm_bound_fraction 0.96 with ~23 ms/step of pure normalize/ReLU
passes — each one a full read + write of an (N, H, W, C) activation.
The fusable site is ``relu(bn2(y)) -> conv3 (1x1, stride 1)``: a 1x1
conv is a GEMM over pixels, so the BN affine + ReLU can be applied
INLINE while the GEMM streams its input, eliminating the separate
normalize pass entirely (one read of the conv2 output instead of
read + write + read).

Autodiff boundary: the custom_vjp wraps only ``f(x, a, b, w)`` where
``a = gamma * rsqrt(var + eps)`` and ``b = beta - mean * a`` are plain
jnp values computed OUTSIDE the op — so the gradient chain through the
batch statistics (mean/var depend on x) is ordinary XLA autodiff; the
hand-written backward only covers the GEMM sandwich itself.

Reference analog: cuDNN's fused conv-bias-activation epilogues the
reference's CUDA stack gets from the framework (e.g. tf fused_batch_norm
+ conv autotuning); here the fusion is an explicit Pallas kernel because
XLA cannot fuse a producer BN-apply into a conv's input side.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.attention import resolve_interpret


def _pick_block(dim: int, want: int, floor: int = 8) -> int:
    """Largest power-of-two block <= want that divides dim (>= floor).

    ``floor`` encodes the TPU block-layout rule (ops/attention.py): a
    dimension that appears as a *lane* (last) axis of any kernel block
    needs tiles that are multiples of 128 — Mosaic rejects smaller lane
    tiles in compiled mode even though interpret-mode CPU tests accept
    them. K and N are lane axes here (x/a/b and w/o blocks), so their
    floor is 128; M only ever appears as a sublane axis (floor 8).
    Shapes with no legal block fall back to the XLA composition.

    tpulint rule TPU001 (docs/ANALYSIS.md) enforces the lane floor
    statically: dropping a ``floor=128`` from a lane-axis pick is a
    lint error, not a latent Mosaic crash.
    """
    b = want
    while b >= floor:
        if dim % b == 0:
            return b
        b //= 2
    return 0


def _tileable(M: int, K: int, N: int) -> bool:
    return bool(_pick_block(M, 512) and _pick_block(K, 256, floor=128)
                and _pick_block(N, 256, floor=128))


def _reference(x, a, b, w, act_dtype=None):
    """The unfused composition (also the fallback for untileable shapes).

    ``act_dtype`` reproduces the unfused model's normalize rounding: the
    BN output is materialized in ``bn_dtype`` there, so the fused path
    must round the activation through the same dtype before the GEMM or
    an A/B against the unfused model diverges whenever bn_dtype differs
    from the compute dtype."""
    y = jnp.maximum(x.astype(jnp.float32) * a + b, 0.0)
    y = y.astype(act_dtype if act_dtype is not None else x.dtype)
    return jax.lax.dot_general(y.astype(x.dtype), w,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)


def _fwd_kernel(x_ref, a_ref, b_ref, w_ref, o_ref, acc_ref, *, nk: int,
                act_dtype):
    import jax.experimental.pallas as pl

    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...].astype(jnp.float32)
    y = jnp.maximum(xb * a_ref[...] + b_ref[...], 0.0).astype(act_dtype)
    acc_ref[...] += jax.lax.dot_general(
        y.astype(x_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kidx == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw_kernel(x_ref, a_ref, b_ref, g_ref, dw_ref, acc_ref, *, nm: int,
               act_dtype):
    """dW = relu(x*a+b)^T @ dz, recomputing the activation inline while
    streaming x — the backward never materializes y either."""
    import jax.experimental.pallas as pl

    midx = pl.program_id(2)

    @pl.when(midx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...].astype(jnp.float32)
    y = jnp.maximum(xb * a_ref[...] + b_ref[...], 0.0).astype(act_dtype)
    acc_ref[...] += jax.lax.dot_general(
        y.astype(x_ref.dtype), g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(midx == nm - 1)
    def _emit():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_scale_relu_matmul(x, a, b, w, interpret: Optional[bool] = None,
                            act_dtype: Optional[Any] = None):
    """``relu(x * a + b) @ w`` in one pass over ``x``.

    x: (M, K) activations (bf16/f32); a, b: (K,) f32 per-channel affine;
    w: (K, N) weights. Returns (M, N) in x.dtype. Shapes that don't
    tile (tiny test models) fall back to the XLA composition.
    ``act_dtype`` (default: x.dtype) is the dtype the normalized
    activation is rounded through before the GEMM — thread the model's
    ``bn_dtype`` here so the fused path matches the unfused BN's
    materialization numerics.
    """
    return _fused_fwd_impl(x, a, b, w, interpret, act_dtype)


def _fused_fwd_impl(x, a, b, w, interpret, act_dtype=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[1]
    if act_dtype is None:
        act_dtype = x.dtype
    if not _tileable(M, K, N):
        return _reference(x, a, b, w, act_dtype)
    bm = _pick_block(M, 512)
    bk = _pick_block(K, 256, floor=128)
    bn = _pick_block(N, 256, floor=128)
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nk=nk, act_dtype=act_dtype),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((1, bk), lambda m, n, k: (0, k)),
            pl.BlockSpec((1, bk), lambda m, n, k: (0, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, a.astype(jnp.float32)[None, :], b.astype(jnp.float32)[None, :],
      w)


def _fused_vjp_fwd(x, a, b, w, interpret, act_dtype):
    return _fused_fwd_impl(x, a, b, w, interpret, act_dtype), (x, a, b, w)


def _fused_vjp_bwd(interpret, act_dtype, res, dz):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, a, b, w = res
    M, K = x.shape
    N = w.shape[1]
    if act_dtype is None:
        act_dtype = x.dtype
    # chain through the activation: one elementwise recompute of xhat
    # (XLA fuses mask/dx/da/db into a single pass over x and dz@w.T)
    xf = x.astype(jnp.float32)
    xhat = xf * a.astype(jnp.float32) + b.astype(jnp.float32)
    dy = jax.lax.dot_general(dz, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dxhat = jnp.where(xhat > 0.0, dy, 0.0)
    dx = (dxhat * a.astype(jnp.float32)).astype(x.dtype)
    da = jnp.sum(dxhat * xf, axis=0).astype(a.dtype)
    db = jnp.sum(dxhat, axis=0).astype(b.dtype)

    if _tileable(M, K, N):
        bm = _pick_block(M, 512)
        bk = _pick_block(K, 256, floor=128)
        bn = _pick_block(N, 256, floor=128)
        nm = M // bm
        dw = pl.pallas_call(
            functools.partial(_dw_kernel, nm=nm, act_dtype=act_dtype),
            grid=(K // bk, N // bn, nm),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda k, n, m: (m, k)),
                pl.BlockSpec((1, bk), lambda k, n, m: (0, k)),
                pl.BlockSpec((1, bk), lambda k, n, m: (0, k)),
                pl.BlockSpec((bm, bn), lambda k, n, m: (m, n)),
            ],
            out_specs=pl.BlockSpec((bk, bn), lambda k, n, m: (k, n)),
            out_shape=jax.ShapeDtypeStruct((K, N), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
            interpret=resolve_interpret(interpret),
        )(x, a.astype(jnp.float32)[None, :],
          b.astype(jnp.float32)[None, :], dz)
        dw = dw.astype(w.dtype)
    else:
        y = jnp.maximum(xhat, 0.0).astype(act_dtype).astype(x.dtype)
        dw = jax.lax.dot_general(y, dz, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ).astype(w.dtype)
    return dx, da, db, dw


fused_scale_relu_matmul.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)
