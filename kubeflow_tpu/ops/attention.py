"""Attention ops: blockwise, Pallas flash kernel, and ring attention.

Long-context sequence/context parallelism is entirely absent from the
reference platform (SURVEY.md §5: "no ring attention, no context/sequence
parallel, no blockwise attention") — it never sees model internals. Here
they are framework ops:

- :func:`blockwise_attention` — online-softmax attention scanned over KV
  blocks: O(S) memory, differentiable, XLA-fusable. The inner compute for
  ring attention and the portable fallback everywhere.
- :func:`flash_attention` — Pallas TPU kernels for the forward AND backward
  pass (VMEM block tiles, MXU matmuls, f32 accumulators): the forward saves
  the per-row logsumexp, and one fused dQ/dK/dV kernel replays blocks
  against it instead of recomputing the softmax (a dQ and a dK/dV kernel
  where a row's dQ does not fit VMEM); ``interpret=True`` runs the same
  kernels on CPU in tests.
- :func:`ring_attention` — sequence-parallel attention over a mesh axis:
  each device holds a sequence shard of Q/K/V and KV shards rotate around
  the ring via ``ppermute`` (one ICI hop per step when the axis is laid out
  on ICI neighbours — the scheduler's placement contract,
  ``kubeflow_tpu/scheduler/placement.py``), accumulating exactly as
  blockwise attention does. Causality is enforced from global block offsets.

All functions take ``(B, S, H, D)`` q/k/v (GQA repeat happens in the model)
and return ``(B, S, H, D)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from kubeflow_tpu import compat
from kubeflow_tpu.ops.autotune import (
    flash_bwd_fuses,
    fused_vmem_limit_bytes,
    resolve_flash,
)

NEG_INF = -1e30


def _scale(q, sm_scale: Optional[float]) -> float:
    return sm_scale if sm_scale is not None else q.shape[-1] ** -0.5


def gqa_repeat(q, k, v):
    """Repeat grouped KV heads up to q's head count (no-op when equal)."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def reference_attention(q, k, v, *, causal: bool = True,
                        sm_scale: Optional[float] = None, kv_len=None):
    """Plain O(S²)-memory attention; the numerics oracle for the others.

    ``kv_len`` is an optional per-row valid-length ``(B,)`` int32 —
    KV positions at or past a row's length are masked out (the padding
    mask of the bidirectional/BERT path). The XLA parity oracle for the
    flash kernels' masked variant.
    """
    scale = _scale(q, sm_scale)
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    S, T = q.shape[1], k.shape[1]
    if causal:
        mask = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None] + (T - S)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if kv_len is not None:
        valid = jnp.arange(T)[None, :] < kv_len[:, None]        # (B, T)
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# ---------------------------------------------------------------------------
# Blockwise attention: online softmax over KV blocks
# ---------------------------------------------------------------------------


def _block_update(carry, kv_block, q, q_pos, kv_pos, scale, causal):
    """One online-softmax accumulation step over a KV block.

    carry: (o, l, m) f32 accumulators — o (B,Sq,H,D), l,m (B,Sq,H).
    kv_pos/q_pos: global position vectors for masking; negative kv_pos marks
    padding (excluded causal or not).
    """
    o, l, m = carry
    k, v = kv_block
    logits = jnp.einsum("bshd,bthd->bsht", q, k).astype(jnp.float32) * scale
    valid = kv_pos[None, :] >= 0
    if causal:
        valid = valid & (kv_pos[None, :] <= q_pos[:, None])  # (Sq, Skv)
    logits = jnp.where(valid[None, :, None, :], logits, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bsht,bthd->bshd", p.astype(v.dtype), v
    ).astype(jnp.float32)
    return (o, l, m_new)


def blockwise_attention(q, k, v, *, causal: bool = True, block_k: int = 512,
                        sm_scale: Optional[float] = None):
    """Memory-efficient attention: ``lax.scan`` over KV blocks.

    Never materializes the (S, S) score matrix — peak activation memory is
    O(S · block_k). Fully differentiable (the scan transposes); XLA keeps
    the per-block einsums on the MXU.
    """
    B, Sq, H, D = q.shape
    T = k.shape[1]
    block_k = min(block_k, T)
    n_blocks = -(-T // block_k)
    pad = n_blocks * block_k - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = _scale(q, sm_scale)
    q_pos = jnp.arange(Sq) + (T - Sq)  # align ends when Sq != T (decoding)

    ks = k.reshape(B, n_blocks, block_k, H, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n_blocks, block_k, H, D).transpose(1, 0, 2, 3, 4)

    def body(carry, blk):
        kb, vb, j = blk
        kv_pos = j * block_k + jnp.arange(block_k)
        kv_pos = jnp.where(kv_pos < T, kv_pos, -1)  # pad := masked out
        return (
            _block_update(carry, (kb, vb), q, q_pos, kv_pos, scale, causal),
            None,
        )

    # accumulators derive from q so they carry its varying-axes type when
    # running inside shard_map (e.g. ulysses_attention) — the vma checker
    # rejects unvarying zeros as a scan carry, exactly as in ring_attention
    o0 = (q * 0).astype(jnp.float32)
    l0 = o0[..., 0]
    init = (o0, l0, l0 + NEG_INF)
    (o, l, _), _ = jax.lax.scan(body, init, (ks, vs, jnp.arange(n_blocks)))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel
# ---------------------------------------------------------------------------


def _last_live_kv(i, block_q: int, block_k: int):
    """Last kv-block index a causal q block ``i`` can see. The SAME
    expression drives the kv index-map clamp and the kernels' compute
    gates — they must agree exactly, or a fetched-but-skipped (or
    skipped-but-computed) step corrupts the accumulator."""
    return (i * block_q + block_q - 1) // block_k


def _first_live_q(j, block_q: int, block_k: int):
    """First q-block index that attends into causal kv block ``j`` —
    the dkv twin of :func:`_last_live_kv` (same agree-exactly contract
    between the q index map and the compute gate)."""
    return (j * block_k) // block_q


def _causal_block_mask(s, i, j, block_q: int, block_k: int):
    """Apply the per-position causal bound to one (block_q, block_k)
    score tile at q block ``i`` / kv block ``j``."""
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    kv_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    return jnp.where(kv_pos <= q_pos, s, NEG_INF)


def _pad_mask(s, limit, j, block_k: int):
    """Mask KV positions at/past the row's valid length ``limit`` in
    one (block_q, block_k) score tile at kv block ``j`` — the padding
    mask of the bidirectional/BERT flash path. The SAME expression in
    the forward and every backward kernel, or the backward's
    recomputed P diverges from the forward's."""
    kv_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    return jnp.where(kv_pos < limit, s, NEG_INF)


def _flash_fwd_kernel(*refs, block_q: int, block_k: int,
                      scale: float, causal: bool, n_kv: int,
                      masked: bool = False):
    """One (batch·head, q-block, kv-block) grid step.

    The KV stream is a GRID dimension (innermost), not an in-kernel
    loop over a full-sequence VMEM ref: per-step VMEM holds one q block,
    one k/v block, and the f32 (acc, m, l) online-softmax scratch —
    independent of sequence length, so the kernel compiles at any
    context the HBM can hold (the full-S residency variant died at
    seq 16k: 16.75 MB > the 16 MB scoped-vmem limit). Causal q blocks
    clamp their kv index map to the last needed block and gate compute
    with pl.when, so masked-out steps move and compute nothing. Emits
    the per-row logsumexp at the final kv step — the backward kernels
    recompute probabilities from it without a second online-softmax
    pass.

    ``masked`` (static) adds the per-row valid lengths (one int32 per
    fused batch·head row, the whole vector SMEM-resident and indexed by
    the grid's row id) whose padding mask composes with the causal one;
    the unmasked argument list is byte-identical to the pre-mask
    kernel.
    """
    import jax.experimental.pallas as pl  # deferred: test envs without pallas

    if masked:
        q_ref, k_ref, v_ref, len_ref, o_ref, lse_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        len_ref = None

    i = pl.program_id(1)  # q-block index
    j = pl.program_id(2)  # kv-block index
    limit = len_ref[pl.program_id(0)] if masked else None

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv blocks strictly after this q block contribute nothing
    live = (j <= _last_live_kv(i, block_q, block_k)) if causal else True

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        if causal:
            s = _causal_block_mask(s, i, j, block_q, block_k)
        if masked:
            s = _pad_mask(s, limit, j, block_k)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # (1, block_q, 1): the trailing singleton keeps the TPU block
        # layout legal (last dims must divide (8, 128) or equal the
        # array's)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _fuse_heads(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _causal_clamp_kv(block_q: int, block_k: int, causal: bool):
    """kv-block index map for (b, i, j) grids: under causality, blocks
    past the last one this q block can see are never fetched (the map
    clamps to the last live block — a repeat fetch the pipeline elides;
    the bound is the kernels' own compute-gate expression)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (
        b, jnp.minimum(j, _last_live_kv(i, block_q, block_k)), 0)


def _causal_clamp_q(block_q: int, block_k: int, causal: bool):
    """q-block index map for (b, j, i) grids — the dkv twin of
    :func:`_causal_clamp_kv`: under causality, q blocks before this kv
    block's first contributor are never fetched (the bound is the dkv
    kernel's own compute-gate expression, :func:`_first_live_q`)."""
    if not causal:
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (
        b, jnp.maximum(i, _first_live_q(j, block_q, block_k)), 0)


# Per-row control vectors (these kernels' kv lengths, the fused
# sampler's temperature/top-k/top-p) ride SMEM whole: 4 bytes an entry
# for the kernel's life, exactly what a scalar-prefetch operand costs.
# SMEM is small and the CPU interpreter has no such limit, so the size
# is held to what a chip run has compiled (v5e, PR 21: this many
# entries, both kernels) — past it the call fails here, by name, not in
# Mosaic's allocator. Per device: under a mesh the kernels see their
# shard_kernel shard.
MAX_SMEM_CONTROL_ENTRIES = 4096


def check_smem_entries(n: int, what: str) -> None:
    if n > MAX_SMEM_CONTROL_ENTRIES:
        raise ValueError(
            f"{what}: {n} per-row control entries would ride SMEM whole; "
            f"the largest compiled on a chip is {MAX_SMEM_CONTROL_ENTRIES}"
            " — split the batch over more devices, or raise "
            "ops/attention.py:MAX_SMEM_CONTROL_ENTRIES after a chip run")


def _fused_lens(kv_len, H: int):
    """(B,) per-row valid lengths → (B·H,) int32 aligned with the
    kernels' fused batch·head grid axis."""
    check_smem_entries(kv_len.shape[0] * H,
                       "flash_attention kv_len (batch × heads)")
    return jnp.repeat(kv_len.astype(jnp.int32), H)


def _len_spec(pl, pltpu):
    """The whole length vector, SMEM-resident for every grid step
    (control values, not vector data; size held by
    :func:`check_smem_entries`); kernels index it with the grid's row
    id. Not a ``(1, 1)`` block per step: Mosaic holds SMEM blocks to the
    same last-two-dims rule as VMEM ones and refuses it (v5e, CHANGES.md
    PR 21)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_fwd(q, k, v, *, causal: bool, block_q: Optional[int],
               block_k: Optional[int], sm_scale: Optional[float],
               interpret: bool, kv_len=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    cfg = resolve_flash(
        "flash_fwd", seq=S, head_dim=D, n_heads=H, n_kv_heads=k.shape[2],
        dtype=q.dtype, causal=causal, block_q=block_q, block_k=block_k)
    block_q = min(cfg.block_q, S)
    block_k = min(cfg.block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"seq_len {S} must divide by blocks {block_q}/{block_k}")
    scale = _scale(q, sm_scale)
    masked = kv_len is not None

    # fuse batch and heads into the grid's first axis; q blocks second,
    # kv stream innermost
    qf, kf, vf = _fuse_heads(q), _fuse_heads(k), _fuse_heads(v)
    n_kv = S // block_k

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, n_kv=n_kv, masked=masked,
    )
    kv_map = _causal_clamp_kv(block_q, block_k, causal)
    inputs = [qf, kf, vf]
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), kv_map,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), kv_map,
                     memory_space=pltpu.VMEM),
    ]
    if masked:
        inputs.append(_fused_lens(kv_len, H))
        in_specs.append(_len_spec(pl, pltpu))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, S // block_q, n_kv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3), lse


def _bwd_tile(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, limit,
              i, j, *, block_q: int, block_k: int, scale: float,
              causal: bool):
    """What every backward kernel rebuilds of one live (q block ``i``,
    kv block ``j``) tile from the forward's saved logsumexp: the
    pre-scaled q, k and dO in float32, P and dS. One function, so the
    masks and the operand types are the same wherever a tile is
    replayed; ``limit`` is the row's valid length, or None."""
    qs = q_ref[0].astype(jnp.float32) * scale  # pre-scaled, as in fwd
    g = g_ref[0].astype(jnp.float32)
    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(qs, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        s = _causal_block_mask(s, i, j, block_q, block_k)
    if limit is not None:
        s = _pad_mask(s, limit, j, block_k)
    p = jnp.exp(s - lse_ref[0])  # (block_q, block_k); lse (block_q, 1)
    dp = jax.lax.dot_general(g, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    return qs, kb, g, p, ds


def _flash_bwd_dq_kernel(*refs, block_q: int, block_k: int,
                         scale: float, causal: bool, n_kv: int,
                         masked: bool = False):
    """dQ for one (batch·head, q-block, kv-block) grid step: the KV
    stream rides the innermost grid dimension (seq-independent VMEM,
    like the forward), recompute P from the saved logsumexp,
    accumulate dS·K in f32 scratch, emit at the last kv step. Runs,
    with :func:`_flash_bwd_dkv_kernel`, only where a dQ row does not
    fit VMEM (:func:`_flash_bwd`)."""
    import jax.experimental.pallas as pl

    *tile_refs, dq_ref, acc_ref = refs
    len_ref = tile_refs.pop() if masked else None

    i = pl.program_id(1)
    j = pl.program_id(2)
    limit = len_ref[pl.program_id(0)] if masked else None

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (j <= _last_live_kv(i, block_q, block_k)) if causal else True

    @pl.when(live)
    def _update():
        _, kb, _, _, ds = _bwd_tile(
            *tile_refs, limit, i, j, block_q=block_q, block_k=block_k,
            scale=scale, causal=causal)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _emit():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_q_stream(refs, *, fused: bool, block_q: int, block_k: int,
                  scale: float, causal: bool, n_q: int, n_kv: int,
                  masked: bool):
    """One (batch·head, kv-block, q-block) grid step of the backward
    kernels that stream Q innermost; causal steps before this kv
    block's first contributing q block move and compute nothing.
    Recompute P and dS once, accumulate Pᵀ·dO and dSᵀ·Q in f32 scratch
    and emit them at the last q step (which causality never skips).

    ``fused`` adds dQ to the same pass: dS·K goes into the q block's
    rows of an f32 scratch that holds the WHOLE row's dQ, zeroed at the
    row's first grid step and written out at its last. The dQ output's
    block is that whole row with an index map constant in ``j`` and
    ``i``, so it goes back to HBM once a row. Each dQ block sums over
    ``j`` ascending, as :func:`_flash_bwd_dq_kernel` sums it."""
    import jax.experimental.pallas as pl

    if fused:
        *tile_refs, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    else:
        *tile_refs, dk_ref, dv_ref, dk_acc, dv_acc = refs
    len_ref = tile_refs.pop() if masked else None

    j = pl.program_id(1)  # kv-block index
    i = pl.program_id(2)  # q-block index
    limit = len_ref[pl.program_id(0)] if masked else None

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if fused:
        @pl.when((j == 0) & (i == 0))
        def _init_row():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (i >= _first_live_q(j, block_q, block_k)) if causal else True

    @pl.when(live)
    def _update():
        qs, kb, g, p, ds = _bwd_tile(
            *tile_refs, limit, i, j, block_q=block_q, block_k=block_k,
            scale=scale, causal=causal)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dK = dSᵀ·(q·scale) — the scale chains through the pre-scaled q
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if fused:
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if fused:
        @pl.when((j == n_kv - 1) & (i == n_q - 1))
        def _emit_row():
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, **static):
    """dK/dV alone: :func:`_bwd_q_stream` without the dQ row."""
    _bwd_q_stream(refs, fused=False, **static)


def _flash_bwd_fused_kernel(*refs, **static):
    """dQ, dK and dV in one pass over the score tiles: five products
    and one softmax recompute a tile (:func:`_bwd_q_stream`)."""
    _bwd_q_stream(refs, fused=True, **static)


def _flash_bwd(q, k, v, o, lse, g, *, causal: bool,
               block_q: Optional[int], block_k: Optional[int],
               sm_scale: Optional[float], interpret: bool, kv_len=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    scale = _scale(q, sm_scale)
    masked = kv_len is not None

    qf, kf, vf = _fuse_heads(q), _fuse_heads(k), _fuse_heads(v)
    gf, of = _fuse_heads(g), _fuse_heads(o)
    # delta_r = Σ_d dO·O — one cheap fused elementwise+reduce in XLA;
    # trailing singleton for a legal TPU block layout (see lse)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)
    inputs = [qf, kf, vf, gf, lse, delta]
    if masked:
        inputs.append(_fused_lens(kv_len, H))

    def tiles(kernel: str):
        """Each kernel is its own shape class and resolves its own tile
        pair (an explicit override pins them all)."""
        cfg = resolve_flash(
            kernel, seq=S, head_dim=D, n_heads=H, n_kv_heads=k.shape[2],
            dtype=q.dtype, causal=causal, block_q=block_q, block_k=block_k)
        bq, bk = min(cfg.block_q, S), min(cfg.block_k, S)
        if S % bq or S % bk:
            raise ValueError(f"seq_len {S} must divide by blocks {bq}/{bk}")
        return bq, bk

    def call(kernel, grid, bq, bk, q_map, kv_map, outs, scratch,
             vmem_limit=None, **static):
        """One backward kernel over ``inputs``: q-sized blocks (q, dO,
        lse, delta) by ``q_map``, k and v by ``kv_map``; ``outs`` as
        (block rows, index map, dtype), ``scratch`` as rows of f32."""
        in_specs = [
            pl.BlockSpec((1, bq, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
        ]
        if masked:
            in_specs.append(_len_spec(pl, pltpu))
        return pl.pallas_call(
            functools.partial(kernel, block_q=bq, block_k=bk, scale=scale,
                              causal=causal, masked=masked, **static),
            grid=grid,
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, rows, D), index_map,
                                    memory_space=pltpu.VMEM)
                       for rows, index_map, _ in outs],
            out_shape=[jax.ShapeDtypeStruct((B * H, S, D), dtype)
                       for _, _, dtype in outs],
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)
                            for rows in scratch],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit),
            interpret=interpret,
        )(*inputs)

    blk_kv = lambda b, j, i: (b, j, 0)  # noqa: E731
    if flash_bwd_fuses(S, D, q.dtype):
        bq, bk = tiles("flash_bwd_fused")
        dq, dk, dv = call(
            _flash_bwd_fused_kernel, (B * H, S // bk, S // bq), bq, bk,
            _causal_clamp_q(bq, bk, causal), blk_kv,
            outs=[(S, lambda b, j, i: (b, 0, 0), q.dtype),
                  (bk, blk_kv, k.dtype), (bk, blk_kv, v.dtype)],
            scratch=[S, bk, bk], n_q=S // bq, n_kv=S // bk,
            vmem_limit=fused_vmem_limit_bytes(S, D, q.dtype.itemsize))
    else:
        # the row does not fit: dQ streams KV, dK/dV stream Q, and the
        # score tiles are rebuilt in both
        bq, bk = tiles("flash_bwd_dq")
        blk_q = lambda b, i, j: (b, i, 0)  # noqa: E731
        dq, = call(
            _flash_bwd_dq_kernel, (B * H, S // bq, S // bk), bq, bk,
            blk_q, _causal_clamp_kv(bq, bk, causal),
            outs=[(bq, blk_q, q.dtype)], scratch=[bq], n_kv=S // bk)
        bq, bk = tiles("flash_bwd_dkv")
        dk, dv = call(
            _flash_bwd_dkv_kernel, (B * H, S // bk, S // bq), bq, bk,
            _causal_clamp_q(bq, bk, causal), blk_kv,
            outs=[(bk, blk_kv, k.dtype), (bk, blk_kv, v.dtype)],
            scratch=[bk, bk], n_q=S // bq, n_kv=S // bk)

    unfuse = lambda x: x.reshape(B, H, S, D).transpose(0, 2, 1, 3)  # noqa: E731
    return unfuse(dq), unfuse(dk), unfuse(dv)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one interpret-mode decision for every Pallas kernel in
    ``ops/``: an explicit value wins; ``None`` compiles on the TPU
    backend and runs the Pallas interpreter elsewhere (so CPU tests
    execute the real kernel bodies). ``chip_smoke.py`` asserts this is
    False on the chip — a kernel must never reach the device
    interpreted."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


# checkpoint names of what the forward kernel leaves for the backward
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None, kv_len=None):
    """Pallas flash attention: fwd AND bwd kernels (saved-LSE backward).

    The backward recomputes P from the forward's saved logsumexp, so
    training never materializes (S, S) and both passes run on the MXU
    from VMEM tiles. It is ONE kernel streaming Q blocks per KV block,
    with the row's dQ resident in VMEM (five products and one softmax
    recompute a tile), wherever that row fits; past that (the shape
    decides: ``autotune.flash_bwd_fuses``) it is the standard flash
    split, a dQ kernel streaming KV blocks and a dK/dV kernel streaming
    Q blocks (seven products, two recomputes).

    ``block_q``/``block_k`` are INDEPENDENT tile knobs. ``None`` (the
    default) resolves each kernel's tiles from the committed shape-keyed
    tile table — ``flash_fwd``, ``flash_bwd_fused``, ``flash_bwd_dq``
    and ``flash_bwd_dkv`` are separate kernel keys, so the chip sweep
    can tune each pass and a recorded resolution says which backward
    ran — with an analytic VMEM-budget fallback when the shape class
    has no entry (``kubeflow_tpu/ops/autotune.py``). Explicit values
    override the table for every kernel (the pre-PR behavior).

    ``kv_len`` is an optional per-row valid-length ``(B,)`` int32: KV
    positions at/past a row's length are masked out in the forward AND
    every backward kernel — the padding mask of the bidirectional/BERT
    path (``reference_attention(kv_len=...)`` is the parity oracle).
    Rows whose cotangent is zero at padded positions get exact
    gradients; outputs AT padded q positions are unspecified (mask them
    downstream, as the MLM loss weights do).

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (so CPU tests execute the real kernels).
    """
    out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, sm_scale=sm_scale,
                        interpret=resolve_interpret(interpret),
                        kv_len=kv_len)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, sm_scale, interpret,
                   kv_len=None):
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, sm_scale=sm_scale,
                          interpret=resolve_interpret(interpret),
                          kv_len=kv_len)
    # the two residuals only the kernel can produce carry names, so a
    # rematerialised caller can keep them (models/transformer.py:
    # remat_block) and its backward does not run the forward kernel
    # again; q, k and v stay unnamed, cheap to recompute. The names go
    # on the residuals themselves: one on the caller's output alone
    # leaves ``lse`` unsaved and the kernel still reruns.
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse, kv_len)


def _flash_vjp_bwd(causal, block_q, block_k, sm_scale, interpret, res, g):
    q, k, v, out, lse, kv_len = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal=causal,
                            block_q=block_q, block_k=block_k,
                            sm_scale=sm_scale,
                            interpret=resolve_interpret(interpret),
                            kv_len=kv_len)
    if kv_len is None:
        return dq, dk, dv, None
    # integer primal → float0 cotangent (the custom_vjp contract)
    return dq, dk, dv, np.zeros(kv_len.shape, dtype=jax.dtypes.float0)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Ring attention: sequence-parallel over a mesh axis
# ---------------------------------------------------------------------------


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   sm_scale: Optional[float] = None, block_k: int = 512):
    """Sequence-parallel attention inside ``shard_map``: rotate KV via ppermute.

    Call within a ``shard_map`` region whose ``axis_name`` shards the
    sequence dim of q/k/v. Device i holds query block i; KV blocks rotate
    one ring hop per step so after n steps every query block has seen every
    KV block. Per-step masking uses global block offsets, so causality holds
    exactly; a KV block strictly AHEAD of this device's query block is
    skipped entirely via ``lax.cond`` (its contribution is fully masked),
    so causal rings do ~half the attention FLOPs — the ppermute still runs
    every step to keep the ring schedule uniform across devices.

    Gradients flow through ``lax.scan`` + ``ppermute`` + ``cond`` (all
    differentiable), so the same code path trains.
    """
    n = compat.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    scale = _scale(q, sm_scale)
    q_pos = idx * Sq + jnp.arange(Sq)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, step):
        o, l, m, k_cur, v_cur = carry
        src = (idx - step) % n  # who this KV block belongs to globally
        kv_pos = src * Sq + jnp.arange(k_cur.shape[1])

        def attend(acc):
            return _block_update(acc, (k_cur, v_cur), q, q_pos, kv_pos,
                                 scale, causal)

        if causal:
            # src > idx ⇒ every kv position is ahead of every query
            # position on this device: skip the whole block's compute
            o, l, m = jax.lax.cond(src > idx, lambda acc: acc, attend,
                                   (o, l, m))
        else:
            o, l, m = attend((o, l, m))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, l, m, k_nxt, v_nxt), None

    # derive accumulators from q so they carry its varying-axes type (the
    # shard_map vma checker rejects unvarying zeros as a scan carry)
    o0 = q.astype(jnp.float32) * 0.0
    l0 = o0[..., 0]
    init = (o0, l0, l0 + NEG_INF, k, v)
    (o, l, _, _, _), _ = jax.lax.scan(body, init, jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      block_k: int = 512):
    """DeepSpeed-Ulysses-style sequence parallelism inside ``shard_map``.

    The ring's alternative collective pattern: instead of rotating KV
    shards (n-1 ``ppermute`` hops), two ``all_to_all``s re-shard
    sequence↔heads — q/k/v arrive sequence-sharded ``(B, S/n, H, D)``,
    leave the first all_to_all head-sharded with the FULL sequence
    ``(B, S, H/n, D)``, attend locally (blockwise: O(S) memory), and the
    second all_to_all restores sequence sharding. On TPU both all_to_alls
    ride ICI; Ulysses wins when heads divide evenly and S/n is small
    (fewer collective phases), ring wins at extreme S (no full-sequence
    residency).

    GQA: k/v may arrive with fewer heads than q (``KH < H``); the repeat
    to ``H`` happens AFTER the KV all_to_alls so the collectives carry
    only the distinct KV heads. Requires ``H % n == 0`` and
    ``KH % n == 0``.
    """
    n = compat.axis_size(axis_name)
    H, KH = q.shape[2], k.shape[2]
    if H % n or KH % n:
        raise ValueError(
            f"ulysses needs q heads {H} and kv heads {KH} divisible by "
            f"axis size {n}")

    def seq_to_heads(x):
        # (B, S/n, h, D) -> (B, S, h/n, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    kg, vg = gqa_repeat(qg, kg, vg)
    o = blockwise_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale,
                            block_k=block_k)
    return heads_to_seq(o)


def _sharded_seq_attention(core, q, k, v, mesh, seq_axis, batch_axis):
    """Shared shard_map wrapper for the sequence-parallel cores: filters
    ``batch_axis`` names absent from ``mesh`` (plain dp/tp meshes and the
    4-axis dcn mesh both work), shards the sequence dim over ``seq_axis``."""
    from jax.sharding import PartitionSpec as P

    if batch_axis is not None:
        axes = ((batch_axis,) if isinstance(batch_axis, str)
                else tuple(batch_axis))
        axes = tuple(a for a in axes if a in mesh.axis_names)
        batch_axis = (axes[0] if len(axes) == 1 else axes) if axes else None
    spec = P(batch_axis, seq_axis, None, None)
    fn = compat.shard_map(core, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
    return fn(q, k, v)


def ulysses_attention_sharded(q, k, v, mesh, *, seq_axis: str = "tp",
                              batch_axis=("dcn", "dp"),
                              causal: bool = True,
                              sm_scale: Optional[float] = None):
    """``shard_map`` wrapper: full (B, S, H, D) arrays in, Ulysses
    all-to-all sequence parallelism over ``seq_axis``. Usable under jit."""
    return _sharded_seq_attention(
        functools.partial(ulysses_attention, axis_name=seq_axis,
                          causal=causal, sm_scale=sm_scale),
        q, k, v, mesh, seq_axis, batch_axis)


def ring_attention_sharded(q, k, v, mesh, *, seq_axis: str = "tp",
                           batch_axis=("dcn", "dp"), causal: bool = True,
                           sm_scale: Optional[float] = None):
    """``shard_map`` wrapper: full (B, S, H, D) arrays in, ring attention on
    sequence shards over ``seq_axis``. Usable directly under jit.

    ``batch_axis`` may be a name, a tuple of names, or None; names absent
    from ``mesh`` are dropped (see :func:`_sharded_seq_attention`)."""
    return _sharded_seq_attention(
        functools.partial(ring_attention, axis_name=seq_axis,
                          causal=causal, sm_scale=sm_scale),
        q, k, v, mesh, seq_axis, batch_axis)
