"""Sparse latent attention's three kernels: the lightning indexer's
scores, the selection of the highest of them, and the attend over the
selected positions.

**The scores.**

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s]),   s <= position of t

over the ``J`` index heads of a query, against the index keys of every
cached position of its row. Written out in XLA the products are a
``(T, J, S)`` float32 array (8.6 GB for a 1024-token chunk at 32768
positions and 64 heads); the kernel holds one block of it in VMEM, takes
the relu-weighted sum over the heads there and writes ``(T, S)``.

It reads the stacked ``(L, B, S, d)`` cache leaf where it lies (layer
``index`` by the block's index map: no slice of the leaf is taken out),
blocked over the keys. A query at position ``p`` scores positions
``<= p``; every later one reads ``-inf``. Blocks of keys wholly past a
block of queries are neither fetched (the index map repeats the last
live block, a fetch the pipeline elides) nor computed, so a decode step
reads a row's index keys as far as the row has grown.

**The selection** (``select_bias``): which ``k`` positions of a query
score highest, as an additive bias (0 where kept, ``-inf`` elsewhere). No
sort: the k-th largest score is found bit by bit (32 counting passes over
the row's scores, resident in VMEM; ``lax.top_k`` lowers to a full sort
of the row, 3.1 ms for 16 rows of 32768 on a v5e), and of the positions
that tie with it the lowest are kept, found the same way over the
position's bits.

**The attend** (``sparse_attend``): the absorbed form under that bias,
flash-style over blocks of the latent: one read of a block serves both
the scores and the values. It reads every live block of the row, kept
positions or not: gathering 2048 rows of 1280 B a query costs a v5e 88 ns
a row (2.9 ms for 16 queries), more than streaming a whole 32768-position
row does (0.8 ms), so below some 100k positions the selection saves the
softmax's work and no bytes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import resolve_interpret

BLOCK_T = 8         # queries a program (the float32 sublanes of a tile)
BLOCK_S = 2048      # keys a program
ATTEND_BLOCK_S = 1024       # cached positions an attend program reads
VMEM_LIMIT = 64 * 1024 * 1024
INT_MIN = -2 ** 31


def _index_kernel(pos_ref, q_ref, w_ref, k_ref, o_ref, *, block_t: int,
                  block_s: int, heads: int):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first = pos_ref[b] + i * block_t        # the block's first query
    live = j * block_s <= first + block_t - 1

    @pl.when(live)
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bt J, bs)
        s = jnp.maximum(s, 0.0) * w_ref[0]
        s = jnp.sum(s.reshape(block_t, heads, block_s), axis=1)
        k_pos = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_s), 1)
        q_pos = first + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_s), 0)
        o_ref[0] = jnp.where(k_pos <= q_pos, s, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[0] = jnp.full((block_t, block_s), -jnp.inf, jnp.float32)


def index_scores(q, w, keys, index: int, pos, *,
                 block_t: int = BLOCK_T, block_s: int = BLOCK_S,
                 interpret: Optional[bool] = None):
    """``q`` (B, T, J, d) and ``w`` (B, T, J) f32, the index queries and
    head weights of T tokens a row; ``keys`` (L, B, S, d), the stacked
    index-key leaf, read at layer ``index`` (static); ``pos`` (B,) the
    position of each row's first token (token t sits at ``pos + t``).
    Returns (B, T, S) float32, ``-inf`` past each token's own position.
    The products take the keys' dtype, the sums float32."""
    B, T, J, d = q.shape
    S = keys.shape[2]
    bt = block_t if T >= block_t else T
    bs = min(block_s, S)
    if S % bs:
        raise ValueError(f"{S} cached positions are not whole blocks of "
                         f"{bs}")
    pad = -T % bt
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
    Tp, n_s = T + pad, S // bs
    q = q.astype(keys.dtype).reshape(B, Tp * J, d)
    w = w.astype(jnp.float32).reshape(B, Tp * J, 1)
    pos = jnp.asarray(pos, jnp.int32)

    def key_map(b, i, j, pos_ref):
        last = (pos_ref[b] + (i + 1) * bt - 1) // bs
        return index, b, jnp.minimum(j, jnp.clip(last, 0, n_s - 1)), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Tp // bt, n_s),
        in_specs=[
            pl.BlockSpec((1, bt * J, d), lambda b, i, j, p: (b, i, 0)),
            pl.BlockSpec((1, bt * J, 1), lambda b, i, j, p: (b, i, 0)),
            pl.BlockSpec((1, 1, bs, d), key_map),
        ],
        out_specs=pl.BlockSpec((1, bt, bs), lambda b, i, j, p: (b, i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, block_t=bt, block_s=bs, heads=J),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tp, S), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(pos, q, w, keys)
    return out[:, :T] if pad else out


def index_scores_reference(q, w, keys, index: int, pos):
    """The same scores by the formula, the heads' products written out:
    what the kernel is tested against."""
    k = keys[index].astype(jnp.float32)
    s = jnp.einsum("btjd,bsd->btjs", q.astype(keys.dtype).astype(jnp.float32),
                   k, precision=jax.lax.Precision.HIGHEST)
    s = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)
    q_pos = pos[:, None] + jnp.arange(q.shape[1])[None, :]
    live = jnp.arange(keys.shape[2])[None, None, :] <= q_pos[..., None]
    return jnp.where(live, s, -jnp.inf)


# -- the selection ---------------------------------------------------------------

def _select_kernel(s_ref, o_ref, *, k: int):
    s = s_ref[...]                                           # (rows, S)
    n = s.shape[-1]
    # an int32 whose order is the float's
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)

    def count(keep):
        return jnp.sum(keep.astype(jnp.float32), axis=-1, keepdims=True)

    # the k-th largest key, from its sign down: the largest value that
    # at least k keys reach
    kth = jnp.where(count(key >= 0) >= k, 0, INT_MIN)        # (rows, 1)
    for bit in range(30, -1, -1):
        higher = kth | (1 << bit)
        kth = jnp.where(count(key >= higher) >= k, higher, kth)
    above, tied = key > kth, key == kth
    room = k - count(above)           # ties to keep: the lowest positions
    at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    bound = jnp.zeros_like(kth)       # the largest with count(< it) <= room
    for bit in range(n.bit_length() - 1, -1, -1):
        wider = bound | (1 << bit)
        bound = jnp.where(count(tied & (at < wider)) <= room, wider, bound)
    keep = (above | (tied & (at < bound))) & (s > -jnp.inf)
    o_ref[...] = jnp.where(keep, 0.0, -jnp.inf)


def select_bias(scores, k: int, *, block_rows: int = BLOCK_T,
                interpret: Optional[bool] = None):
    """``scores`` (B, T, S) float32 with ``-inf`` at positions a query
    may not see. Returns (B, T, S) float32: 0 at each query's ``k``
    highest-scoring positions (all it may see, where fewer), ties to the
    lower position, and ``-inf`` elsewhere."""
    B, T, S = scores.shape
    rows = B * T
    flat = scores.reshape(rows, S)
    br = block_rows if rows >= block_rows else rows
    pad = -rows % br
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    spec = pl.BlockSpec((br, S), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=((rows + pad) // br,),
        in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(flat.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(flat)
    return out[:rows].reshape(B, T, S)


def select_bias_reference(scores, k: int):
    """The same bias through ``lax.top_k`` (stable: ties to the lower
    position) and a scatter: what the kernel is tested against."""
    B, T, S = scores.shape
    k = min(k, S)
    vals, idx = jax.lax.top_k(scores, k)
    keep = jnp.zeros((B, T, S + 1), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None],
        jnp.where(vals > -jnp.inf, idx, S)].set(True)[..., :S]
    return jnp.where(keep, 0.0, -jnp.inf)


# -- the attend ------------------------------------------------------------------

def _attend_kernel(pos_ref, q_ref, bias_ref, lat_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_t: int, block_s: int, heads: int,
                   scale: float, values: int):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = j * block_s <= pos_ref[b] + (i + 1) * block_t - 1

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(live)
    def _():
        lat = lat_ref[0, 0]                                  # (bs, W)
        s = jax.lax.dot_general(
            q_ref[0], lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bt H, bs)
        s = (s.reshape(block_t, heads, block_s)
             + bias_ref[0][:, None, :]).reshape(block_t * heads, block_s)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                   # exp(-inf - finite) = 0
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(lat.dtype), lat[:, :values], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def sparse_attend(q, bias, latent, index: int, pos, *, scale: float,
                  values: int, block_t: int = BLOCK_T,
                  block_s: int = ATTEND_BLOCK_S,
                  interpret: Optional[bool] = None):
    """The absorbed attend of T tokens a row over the positions their
    bias keeps. ``q`` (B, T, H, W): each head's query folded through
    ``kv_b`` beside its rope part, zeros against the row's padding;
    ``bias`` (B, T, S) float32, 0 or ``-inf``; ``latent`` (L, B, S, W),
    the stacked leaf, read at layer ``index`` (static) and only as far as
    the row's tokens reach (``pos`` (B,): token t sits at ``pos + t``).
    Returns (B, T, H, values): the softmax-weighted sum of the rows'
    first ``values`` columns (the kv latent), in the leaf's dtype."""
    B, T, H, W = q.shape
    S = latent.shape[2]
    bt = block_t if T >= block_t else T
    bs = min(block_s, S)
    if S % bs:
        raise ValueError(f"{S} cached positions are not whole blocks of "
                         f"{bs}")
    pad = -T % bt
    if pad:          # a padded query keeps everything: no 0 / 0
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad), (0, 0)))
    Tp, n_s = T + pad, S // bs
    q = q.astype(latent.dtype).reshape(B, Tp * H, W)
    pos = jnp.asarray(pos, jnp.int32)

    def lat_map(b, i, j, pos_ref):
        last = (pos_ref[b] + (i + 1) * bt - 1) // bs
        return index, b, jnp.minimum(j, jnp.clip(last, 0, n_s - 1)), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Tp // bt, n_s),
        in_specs=[
            pl.BlockSpec((1, bt * H, W), lambda b, i, j, p: (b, i, 0)),
            pl.BlockSpec((1, bt, bs), lambda b, i, j, p: (b, i, j)),
            pl.BlockSpec((1, 1, bs, W), lat_map),
        ],
        out_specs=pl.BlockSpec((1, bt * H, values),
                               lambda b, i, j, p: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bt * H, 1), jnp.float32),
                        pltpu.VMEM((bt * H, 1), jnp.float32),
                        pltpu.VMEM((bt * H, values), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_attend_kernel, block_t=bt, block_s=bs, heads=H,
                          scale=scale, values=values),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tp * H, values), latent.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(pos, q, bias, latent)
    return out.reshape(B, Tp, H, values)[:, :T]
