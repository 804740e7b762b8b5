"""The grouped matrix product of a routed MLP, as a Pallas kernel whose
tiles this repo chooses.

    out[r] = rows[r] @ experts[g(r)]      for the rows that ``sizes`` covers

``rows`` (M, K) lie sorted by group: the first ``sizes[0]`` belong to
expert 0, the next ``sizes[1]`` to expert 1, and so on; rows past
``sum(sizes)`` belong to none, and what comes back there is NOT
SPECIFIED (see below). Operands in their own dtype (bf16 in every served
configuration), accumulation and result float32.

The work is cut into **items**: one (row tile, group) pair for every
row tile a non-empty group touches, in row order. The grid is
``(N / tn, items, K / tk)``; an item's index maps read its row tile and
its group from two scalar-prefetched lists, so

- an expert no row was routed to is not read at all (Ling hits about 50
  of its 128 held experts a layer-step, DeepSeek-V3.2 6 or 7 of 16);
- a hit expert's matrix is read in few large pieces, ``tk x tn`` of them
  chosen by :func:`kubeflow_tpu.ops.autotune.resolve_gmm` from ``(M, K,
  N)`` and the dtype. A decode step brings a handful of rows an
  expert and is bound by the experts' bytes: its tiles span the whole of
  the narrow axis, so a 2688 x 1856 matrix arrives in three pieces where
  the TPU compiler's ``ragged_dot`` fetched 84 of 128 x 512 at 2048
  stored columns (PERF.md, PRs 35 and 36);
- the grid's item axis is as long as this call's list, a number the
  device computes (the two lists have room for the most there can be,
  ``M / tm + E - 1``: every group but the first can share its first row
  tile with the group before). A fixed axis of that length, its idle
  items repeating the last real one's indices, read every expert piece
  again wherever ``K`` was cut (the ``k`` index still moved): 0.72 against
  0.34 ms in a DeepSeek-V3.2 decode call (my chip runs, PR 36).

A row tile shared by two groups is visited by both, one after the other:
each writes its own rows and keeps what the tile held (zero at the first
visit). Row tiles that no group touches are never written: those rows
of the result are memory as the call found it, and a caller masks them.
``RoutedMlp`` does, by ``held`` after its un-sort, with a select that
passes nothing of the other side on; and no such row can reach a
covered one through a second product, whose rows are independent and
whose store selects a group's own. (Zeroing them in the wrapper was
tried: XLA fuses it into an elementwise reader and not into the
un-sort's gather, where it cost a read and a write of the whole result,
1.6 ms for ``down_proj`` in a 4 x 2048-token prefill of the Nemotron cell
beside a product of 2.1: PERF.md, PR 36.)

This is megablox's algorithm (``jax.experimental.pallas.ops.tpu.
megablox.gmm``). On the chip the two read alike at equal tiles, this
kernel 3-10 % ahead where an expert comes in one piece (no accumulator:
the product is stored as it is), and it is kept for what surrounds the
kernel: a schedule of ten small fusions where megablox's ``cumsum`` /
``repeat`` / ``histogram`` / ``roll`` make a program 8-13 % heavier
(``setup_s`` is judged), and a call that carries the scope's name
(megablox's is a ``jax.jit`` and names its call ``gmm``, which the
benchmark's readers do not find); PERF.md, PR 36.

**An expert tensor is read as the chip holds it.** The TPU's default
layout of an ``(E, K, N)`` array whose last axis is no whole number of
128 lanes while ``K`` is one (Nemotron's ``up_proj``, 2688 x 1856) is
column-major: ``K`` runs along the lanes and nothing is padded. A
Mosaic call fixes its operands row-major, so handing that array over as
it is declared makes XLA re-lay all of it at every call (319 MB a block
a round: seen in the compile for a described v5e, PR 35 and PR 36). For
such a shape the call takes ``swapaxes(experts, 1, 2)``, which on that
layout moves no byte, and contracts over the last axis of both operands.

No path differentiates through the routed MLP of ``models/hybrid.py``
(there is no trainer for it), so the kernel has no backward.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import resolve_interpret
from kubeflow_tpu.ops.autotune import (
    LANE_MULTIPLE,
    gmm_vmem_bytes,
    resolve_gmm,
)

VMEM_HEADROOM = 4 * 1024 * 1024     # Mosaic's own scratch beside the blocks


def schedule(sizes, m_tiles: int, tm: int):
    """The items of a call: ``sizes`` (E,) int32 -> (group of item i,
    row tile of item i, group offsets (E + 1,), number of items),
    the two lists ``m_tiles + E - 1`` long and, past the last item,
    filled with its indices. Prefix sums, the search of an item's group
    and the gathers are written as masked sums over ``E`` (a few
    thousand elements): XLA makes ten small fusions of them, 0.11 MB of
    program, where ``cumsum`` / ``searchsorted`` / indexing made
    thirteen, a loop and two library calls, 0.74 MB, and merges the
    schedules of one routed MLP's products."""
    E = sizes.shape[0]
    e = jnp.arange(E, dtype=jnp.int32)
    upto = e[None, :] <= e[:, None]

    def prefix(v):      # inclusive
        return jnp.sum(jnp.where(upto, v[None, :], 0), axis=1)

    ends = prefix(sizes)
    first = (ends - sizes) // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_ends = prefix(tiles)
    n_items = item_ends[-1]
    i = jnp.minimum(jnp.arange(m_tiles + E - 1, dtype=jnp.int32),
                    jnp.maximum(n_items - 1, 0))
    # an item's group: as many groups as have ended before it
    group = jnp.minimum(jnp.sum(item_ends[None, :] <= i[:, None], axis=1),
                        E - 1).astype(jnp.int32)
    # its row tile: the group's first, plus the item's place in the group
    mine = group[:, None] == e[None, :]
    tile = i + jnp.sum(jnp.where(mine, (first + tiles - item_ends)[None, :],
                                 0), axis=1)
    tile = jnp.clip(tile, 0, m_tiles - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, offsets, n_items


def lies_column_major(k: int, n: int) -> bool:
    """Whether the chip's default layout of an ``(E, k, n)`` array runs
    ``k`` along the lanes (the module's note)."""
    return n % LANE_MULTIPLE != 0 and k % LANE_MULTIPLE == 0


def _gmm_kernel(group_ref, tile_ref, offsets_ref, rows_ref, experts_ref,
                out_ref, *scratch, tm: int, k_tiles: int, k_axis: int):
    i, k = pl.program_id(1), pl.program_id(2)

    def product():
        return jax.lax.dot_general(
            rows_ref[...], experts_ref[0], (((1,), (k_axis,)), ((), ())),
            preferred_element_type=jnp.float32)

    def store(acc):
        g, t = group_ref[i], tile_ref[i]
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        fresh = jnp.logical_or(i == 0, tile_ref[jnp.maximum(i - 1, 0)] != t)
        kept = jnp.where(fresh, 0.0, out_ref[...])
        out_ref[...] = jnp.where(mine, acc, kept)

    if k_tiles == 1:
        store(product())
        return
    acc_ref, = scratch

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += product()       # once in the body: it is most of it

    @pl.when(k == k_tiles - 1)
    def _():
        store(acc_ref[...])


def grouped_matmul(rows, experts, sizes, *,
                   tiling: Optional[Tuple[int, int, int]] = None,
                   interpret: Optional[bool] = None):
    """``rows`` (M, K) sorted by group, ``experts`` (E, K, N), ``sizes``
    (E,) int32 -> (M, N) float32; rows past ``sum(sizes)`` are not
    specified (the module's note). ``tiling`` (tm, tk, tn) is for sweeps
    and tests: left out, the tiles follow the shapes
    (:func:`autotune.resolve_gmm`)."""
    (M, K), N = rows.shape, experts.shape[2]
    tiling = resolve_gmm(m=M, k=K, n=N, dtype=rows.dtype,
                         tiling=tiling).tiling
    if K % tiling[1] or N % tiling[2]:
        raise ValueError(f"tiles {tiling[1:]} do not divide {K} x {N}")
    return _call(rows, experts, sizes, tiling=tiling,
                 interpret=resolve_interpret(interpret))


# a program's calls of one shape (the same product of every routed layer)
# are traced and lowered to Mosaic once, as one function of the module:
# lowered call by call, the 342 calls of Ling's 19 programs took 11 s of
# every set-up, warm compile cache or not (my chip runs, PR 36)
@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _call(rows, experts, sizes, *, tiling, interpret):
    M, K = rows.shape
    N = experts.shape[2]
    tm, tk, tn = tiling
    experts = experts.astype(rows.dtype)
    m_tiles = -(-M // tm)
    if M % tm:
        rows = jnp.pad(rows, ((0, m_tiles * tm - M), (0, 0)))
    k_tiles = K // tk
    group, tile, offsets, n_items = schedule(sizes.astype(jnp.int32),
                                             m_tiles, tm)
    if lies_column_major(K, N):
        experts = jnp.swapaxes(experts, 1, 2)                    # (E, N, K)
        expert_block = pl.BlockSpec(
            (1, tn, tk), lambda j, i, k, g, t, o: (g[i], j, k))
        k_axis = 1
    else:
        expert_block = pl.BlockSpec(
            (1, tk, tn), lambda j, i, k, g, t, o: (g[i], k, j))
        k_axis = 0
    with jax.named_scope("ragged-dot.gmm"):     # the call's name on a trace
        out = pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm, k_tiles=k_tiles,
                              k_axis=k_axis),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(N // tn, n_items, k_tiles),
                in_specs=[
                    pl.BlockSpec((tm, tk),
                                 lambda j, i, k, g, t, o: (t[i], k)),
                    expert_block,
                ],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, i, k, g, t, o: (t[i], j)),
                scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                                if k_tiles > 1 else []),
            ),
            out_shape=jax.ShapeDtypeStruct((m_tiles * tm, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=gmm_vmem_bytes(
                    tm, tk, tn, rows.dtype.itemsize) + VMEM_HEADROOM),
            interpret=interpret,
        )(group, tile, offsets, rows, experts)
    return out[:M]
