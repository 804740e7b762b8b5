"""One token's attention against the dense decode cache, as a Pallas
kernel that reads each row's K and V only as far as the row has grown.

The dense decoder (``models/transformer.py``) and the hybrid decoder's
``"gqa"`` blocks (``models/hybrid.py``) keep K and V as stacked
``(L, B, Smax, KH·Dh)`` leaves, a position's KV heads merged on the last
axis. Written in XLA (``transformer._merged_step_attention``, which stays
as the reference and as the path off the TPU) a step's two products read
every row's whole ``Smax`` positions, the empty ones and idle rows too.
This kernel reads, for each row, the blocks of ``block_len`` positions up
to the one that holds the row's position, and nothing for an idle row
(position ``>= Smax``: a live row writes at most at ``Smax - 1``, and an
idle one gets there by itself, positions advancing every step). Such a
row's output is zeros.

- **The stack is read where it lies.** K and V stay in HBM
  (``memory_space=ANY``); the layer index and the rows' positions come in
  by scalar prefetch (the dense decoder's layer is a traced scan index),
  and the kernel copies a row's live blocks into VMEM itself. No slice of
  a layer is taken outside the call.
- **A row is its own unit of reading.** A program holds ``block_rows``
  rows and loops over as many blocks as its longest row has; at each it
  starts the next block's copies (two VMEM slots) and, row by row, waits
  for the row's block and folds it into the row's online softmax. A row
  past its last block issues no copy and no compute, so the rows of one
  program need not be alike: an index map could only skip a block that
  every row of a program has passed, and a program of one row is one grid
  step a block (128 rows x 16 blocks would be 2048 steps a layer).
- **The merged-lane products.** As in the XLA form, a query head is zero
  outside its KV head's lanes (``q`` comes in as ``(B, H, KH·Dh)``), QKᵀ
  contracts over all ``KH·Dh`` lanes and PV fills all of them; a head
  keeps its own KV head's ``Dh`` lanes at the end, by a product with a
  0/1 selection matrix (exact: one term a sum). No lane is re-laid at a
  head of 64.
- **Numerics.** Scores in float32 at ``Dh ** -0.5`` after the product,
  the ``kv_pos <= pos`` bound, an online softmax in float32, the values'
  product in the cache's dtype with float32 sums.
- **Window layers** (``models/hybrid.py`` ``"swa"``). V may be narrower
  than K (its own lanes and selection matrix). ``sinks`` starts a row's
  online softmax at ``m = b_h, l = 1`` in place of ``m = -inf, l = 0``:
  a logit a head that takes probability mass and gives no value. A
  leaf shorter than ``max_seq_len`` is a ring (position p at slot
  ``p % Smax``): a row reads ``min(pos + 1, Smax)`` slots, all of them
  once it has wrapped (the ``kv_pos <= pos`` bound then masks none), in
  whatever order they lie, which a softmax does not see.

``rows`` per program and ``block_len`` come from one shape rule,
:func:`kubeflow_tpu.ops.autotune.resolve_step_attention`, keyed by the
rows, ``Smax``, the lanes and the dtype. :func:`live_blocks` is what a
step reads, a row at a time; the decoders count it for the engine's
round (``kv_blocks_read``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import autotune
from kubeflow_tpu.ops.attention import NEG_INF, resolve_interpret


def use_kernel() -> bool:
    """Whether a decoder's one-token step runs this kernel: on the TPU
    backend. Elsewhere it would run in the Pallas interpreter, and the
    step takes the XLA products (tests steer this function)."""
    return jax.default_backend() == "tpu"


def tiling(rows: int, max_seq_len: int, n_kv_heads: int, head_dim: int,
           n_heads: int, dtype,
           v_head_dim: Optional[int] = None) -> Tuple[int, int]:
    """``(block_rows, block_len)`` for a step over ``rows`` rows of
    ``max_seq_len`` positions (the shape rule)."""
    return autotune.resolve_step_attention(
        rows=rows, max_seq_len=max_seq_len, lanes=n_kv_heads * head_dim,
        n_heads=n_heads, head_dim=head_dim, dtype=dtype,
        v_lanes=None if v_head_dim is None else n_kv_heads * v_head_dim
    ).tiling


def live_blocks(pos, max_seq_len: int, block_len: int, ring: int = 0):
    """``(B,)`` int32: the blocks of ``block_len`` positions a row's step
    reads, through the one that holds its position ``pos``, or of a ring
    of ``ring`` slots at most all of them; none for an idle row (``pos
    >= max_seq_len``)."""
    pos = jnp.asarray(pos, jnp.int32)
    if ring:
        return jnp.where(pos < max_seq_len, jnp.minimum(
            pos // block_len + 1, ring // block_len), 0)
    return jnp.where(pos < max_seq_len, pos // block_len + 1, 0)


def window_positions(pos, max_seq_len: int, window: int):
    """What one step of one window layer reads: the ring slots of the
    live rows, ``min(pos + 1, window)`` a row; an int32 scalar."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.sum(jnp.where(pos < max_seq_len,
                             jnp.minimum(pos + 1, window), 0))


def block_counts(pos, max_seq_len: int, block_len: int):
    """What one step of one attention layer reads and what it holds, in
    blocks: ``(kv_blocks_read, kv_blocks_reserved)``, int32 scalars."""
    rows = jnp.shape(pos)[0]
    return (jnp.sum(live_blocks(pos, max_seq_len, block_len)),
            jnp.asarray(rows * (max_seq_len // block_len), jnp.int32))


def _kernel(layer_ref, pos_ref, nblk_ref, last_ref, q_ref, own_ref, sel_ref,
            *refs, rows: int, block_len: int, scale: float, precision,
            sinks: bool):
    if sinks:
        sink_ref, *refs = refs
    (k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref) = refs
    g = pl.program_id(0)
    layer = layer_ref[0]
    base = g * rows
    n = last_ref[g]                   # blocks of the program's longest row

    def copies(j, slot, r):
        at = pl.ds(pl.multiple_of(j * block_len, block_len), block_len)
        return (pltpu.make_async_copy(k_hbm.at[layer, base + r, at],
                                      kbuf.at[slot, r], sem.at[slot, 0, r]),
                pltpu.make_async_copy(v_hbm.at[layer, base + r, at],
                                      vbuf.at[slot, r], sem.at[slot, 1, r]))

    def fetch(j, slot):
        for r in range(rows):
            @pl.when(j < nblk_ref[base + r])
            def _():
                for c in copies(j, slot, r):
                    c.start()

    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    if sinks:
        # the sink is a key of logit b_h and no value: exp(b_h - m) in l
        m_ref[...] = jnp.broadcast_to(sink_ref[...][None], m_ref.shape)
        l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
    else:
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when(n > 0)
    def _():
        fetch(0, 0)

    def update(j, slot, r):
        kb = kbuf[slot, r]                                    # (bs, lanes)
        s = jax.lax.dot_general(
            q_ref[r], kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (H, bs)
        kv_pos = j * block_len + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_len), 1)
        s = jnp.where(kv_pos <= pos_ref[base + r], s, NEG_INF)
        m_old = m_ref[r]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[r] = alpha * acc_ref[r] + jax.lax.dot_general(
            p.astype(kb.dtype), vbuf[slot, r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[r] = m_new

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            fetch(j + 1, 1 - slot)

        for r in range(rows):
            @pl.when(j < nblk_ref[base + r])
            def _():
                for c in copies(j, slot, r):
                    c.wait()
                update(j, slot, r)
        return carry

    jax.lax.fori_loop(0, n, body, 0)

    own, sel = own_ref[...], sel_ref[...]
    for r in range(rows):
        # an idle row gathered nothing: 0 / 1, not 0 / 0
        o = acc_ref[r] / jnp.maximum(l_ref[r], 1e-30)
        o = o.astype(o_ref.dtype) * own                      # (H, lanes)
        o_ref[r] = jax.lax.dot_general(
            o, sel, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def step_attention(q, k, v, layer, pos, sinks=None, *, block_rows: int,
                   block_len: int, max_seq_len: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """One token a row against the cached rows.

    - ``q``: ``(B, KH, G, Dh)``, rotated, the ``G`` query heads of a KV
      head side by side;
    - ``k`` / ``v``: the stacked leaves, ``(L, B, Smax, KH·Dh)`` and
      ``(L, B, Smax, KH·Dv)``, read at layer ``layer`` (an int or an
      int32 scalar, traced or not); the step's own token must already
      be written at ``pos`` (at ``pos % Smax`` in a ring);
    - ``pos``: ``(B,)`` int32, each row's position (keys ``<= pos``
      attend); ``>= max_seq_len`` marks an idle row, whose output is
      zeros;
    - ``sinks``: None, or ``(KH, G)`` float32, a logit a query head;
    - ``max_seq_len``: None (the leaf's ``Smax``), or more than ``Smax``:
      the leaf is a ring of the last ``Smax`` positions.

    Returns ``(B, KH, G, Dv)`` in ``q``'s dtype. ``block_rows`` must
    divide ``B`` and ``block_len`` must divide ``Smax``."""
    B, KH, G, Dh = q.shape
    L, _, Smax, lanes = k.shape
    v_lanes = v.shape[-1]
    Dv = v_lanes // KH
    H = KH * G
    if lanes != KH * Dh or k.shape[1] != B or v_lanes != KH * Dv:
        raise ValueError(f"cache {k.shape} / {v.shape} does not hold {B} "
                         f"rows of {KH} heads x {Dh} lanes")
    if B % block_rows or Smax % block_len:
        raise ValueError(f"{block_rows} rows x {block_len} positions do "
                         f"not tile {B} rows x {Smax}")
    # head (j, g)'s query in KV head j's lanes, zeros elsewhere
    eye = np.eye(KH, dtype=bool)[None, :, None, :, None]
    qz = jnp.where(eye, q[:, :, :, None, :], 0).reshape(B, H, lanes)
    qz = qz.astype(k.dtype)
    lane = np.arange(v_lanes)
    own = (lane[None, :] // Dv == np.arange(H)[:, None] // G)
    sel = (lane[:, None] % Dv == np.arange(Dv)[None, :])
    own, sel = (jnp.asarray(x, q.dtype) for x in (own, sel))
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    if max_seq_len is None:
        nblk = live_blocks(pos, Smax, block_len)
    else:
        nblk = live_blocks(pos, max_seq_len, block_len, ring=Smax)
    last = jnp.max(nblk.reshape(B // block_rows, block_rows), axis=1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    nbytes = jnp.dtype(k.dtype).itemsize
    vmem = autotune.step_vmem_bytes(
        block_rows, block_len, lanes, H, Dh, nbytes,
        **({} if v_lanes == lanes else {"v_lanes": v_lanes,
                                        "v_head_dim": Dv}))
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else None)

    const = lambda g, *_: (0, 0)       # noqa: E731
    per_program = lambda g, *_: (g, 0, 0)  # noqa: E731
    extra, extra_specs = (), []
    if sinks is not None:
        extra = (jnp.asarray(sinks, jnp.float32).reshape(H, 1),)
        extra_specs = [pl.BlockSpec((H, 1), const)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, H, lanes), per_program),
                  pl.BlockSpec((H, v_lanes), const),
                  pl.BlockSpec((v_lanes, Dv), const),
                  *extra_specs,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_rows, H, Dv), per_program),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, block_len, lanes), k.dtype),
            pltpu.VMEM((2, block_rows, block_len, v_lanes), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2, block_rows)),
            pltpu.VMEM((block_rows, H, v_lanes), jnp.float32),
            pltpu.VMEM((block_rows, H, 1), jnp.float32),
            pltpu.VMEM((block_rows, H, 1), jnp.float32)],
    )
    kernel = functools.partial(_kernel, rows=block_rows, block_len=block_len,
                               scale=Dh ** -0.5, precision=precision,
                               sinks=sinks is not None)
    with jax.named_scope("step_attention"):   # the call's name on a trace
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=vmem + 4 * 2 ** 20),
            interpret=resolve_interpret(interpret),
        )(layer, pos, nblk, last, qz, own, sel, *extra, k, v)
    return out.reshape(B, KH, G, Dv)
