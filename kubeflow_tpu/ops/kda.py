"""The one-token step of Kimi Delta Attention as a Pallas kernel.

    S <- diag(exp(a)) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

per row and head, on a float32 state ``S`` (dk x dv). The step is bound
by the state's bytes: the kernel reads each head's state once and writes
it once, in place, where XLA's fusions of the same mathematics pass over
it for each of the two products and again for the update. It works on the
stacked ``(L, B, H, dk, dv)`` cache leaf, aliased to its output, and
touches layer ``index`` alone: no slice of the leaf is taken out or put
back around it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kubeflow_tpu.ops.attention import resolve_interpret

def _kda_step_kernel(cols_ref, bv_ref, state_ref, out_state_ref, o_ref,
                     *, heads: int):
    for h in range(heads):
        decay, k, bk, q = (cols_ref[0, i, :, h:h + 1] for i in range(4))
        s = state_ref[0, 0, h] * decay             # (dk, dv), rows scaled
        u = bv_ref[0, h:h + 1] - jnp.sum(s * bk, axis=0, keepdims=True)
        s = s + k * u                              # (dk, 1) x (1, dv)
        out_state_ref[0, 0, h] = s
        o_ref[0, h:h + 1] = jnp.sum(s * q, axis=0, keepdims=True)


def kda_step(state, index: int, q, k, v, a, beta, *,
             interpret: Optional[bool] = None):
    """``state`` (L, B, H, dk, dv) f32, updated at layer ``index``
    (static); q, k, a (B, H, dk), v (B, H, dv), beta (B, H), all f32.
    Returns (state, o (B, H, dv)). One program a row, all its heads."""
    L, B, H, dk, dv = state.shape
    # the kernel scales and reduces over the state's rows (dk), so what
    # varies along dk arrives as columns, dk on the sublanes and the
    # heads on the lanes (a (dk, 1) array a head would pad its one lane
    # to 128): [exp(a) | k | beta k | q]
    cols = jnp.stack([jnp.exp(a), k, beta[..., None] * k, q], axis=1)
    cols = jnp.swapaxes(cols, 2, 3)                          # (B, 4, dk, H)
    bv = beta[..., None] * v
    vec = pl.BlockSpec((1, H, dv), lambda b: (b, 0, 0))
    st = pl.BlockSpec((1, 1, H, dk, dv), lambda b: (index, b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=H),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 4, dk, H), lambda b: (b, 0, 0, 0)),
                  vec, st],
        out_specs=[st, vec],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
    )(cols, bv, state)
