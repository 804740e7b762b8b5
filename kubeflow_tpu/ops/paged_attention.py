"""Pallas paged decode attention: page-table-native KV reads.

The paged engine's gather path
(``models/transformer.py:_paged_decode_attend``) materializes each
row's logical KV view back to a dense ``(B, max_seq_len, KH, Dh)``
tensor with ``jnp.take(pool, pages)`` — per layer, per decode step —
and ``gqa_repeat`` then widens it to QH heads, so a row with 100 live
tokens still reads and rewrites the full ``Smax`` footprint. On a part
where decode is bandwidth-read-bound, bytes per step is the number to
attack: this kernel reads K/V **directly through the per-row page
table**, so HBM traffic per step is proportional to live pages only
and nothing QH-wide is ever materialized.

Kernel shape (the flash kernels' streamed-grid pattern,
``ops/attention.py``):

- grid ``(B, n_logical_pages)`` with the page stream innermost; the
  page table and per-row positions ride ``PrefetchScalarGridSpec``
  scalar prefetch, so the K/V **index maps themselves** translate
  logical page ``j`` to its physical pool block — the gather never
  happens;
- causally-dead pages (``j·page_size > pos``) and sentinel/unmapped
  entries clamp the index map to an already-fetched block (a repeat
  fetch the pipeline elides) and gate compute with ``pl.when`` — they
  move and compute nothing, exactly the flash kernels' clamp trick;
- online-softmax ``(QH, Dh)``/``(QH, 1)`` f32 scratch accumulators:
  per-step VMEM holds one q row, one K/V page and the accumulators —
  independent of context length;
- GQA is handled in-kernel by slicing the q-head groups against their
  KV head (a static loop over ``KH``) — no ``gqa_repeat``, no QH-wide
  K/V copy.

Numerics: identical masking and scaling to the gather path (scores in
f32, scale applied post-dot, ``kv_pos <= pos`` causal bound); the
online softmax reorders the same f32 math, so greedy token streams
stay token-identical (the engine parity gate,
``tests/test_engine_paged.py``). ``interpret=None`` auto-selects the
Pallas interpreter off-TPU so CPU tests run the real kernel.

Safety contract (shared with the gather path and
``serving/kvpool.py``): a row's sentinel entries only occur at or
beyond its causal frontier (idle/disarmed rows are all-sentinel and
produce zeros nothing reads), and live pages below the frontier are
always mapped — the engine arms tables before any step that reads
them.

Tile legality (TPU001): every block dim is either 1 or a
shape-derived symbol (``page_size``/``KH``/``Dh``/``QH``) — the lane
axis is ``Dh``, the same lane layout the flash kernels run on chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.attention import NEG_INF, resolve_interpret
from kubeflow_tpu.ops.autotune import resolve_paged


def _paged_decode_kernel(pages_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int,
                         n_log: int, scale: float, n_kv_heads: int,
                         group: int, sentinel: int, head_block: int = 1):
    """One (row, logical-page) grid step of online-softmax attention.

    ``acc``/``m``/``l`` are the f32 running accumulators over the
    row's page stream; the emit at the final page normalizes. Each KV
    head attends its own q-head group (``group = QH // KH``) via
    static scratch slices — GQA without widening K/V.

    ``head_block`` (static, table-resolved — the "head-group blocking"
    knob of ROADMAP item 1's sweep) batches that many KV heads per
    compute step: at 1 the original per-head loop runs byte-identically
    (the parity oracle's path); above 1 the dots batch over the head
    axis so the MXU sees ``head_block·group × page_size`` work per
    issue instead of ``group × page_size``. VMEM residency is
    unchanged either way — the whole K/V page block is fetched
    regardless; the knob trades loop trips for batched-dot width.
    """
    import jax.experimental.pallas as pl  # deferred: envs without pallas

    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip causally-dead pages AND sentinel (unmapped) entries: the
    # index map clamped their fetch; the compute gate must agree
    live = (j * page_size <= pos) & (pages_ref[b, j] != sentinel)

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32)       # (QH, Dh)
        kb = k_ref[0].astype(jnp.float32)      # (page_size, KH, Dh)
        vb = v_ref[0]
        kv_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        dead = kv_pos > pos                    # per-position causal bound
        for h0 in range(0, n_kv_heads, head_block):
            if head_block == 1:
                _attend_one_head(q, kb, vb, dead, h0, group, scale,
                                 acc_ref, m_ref, l_ref)
            else:
                _attend_head_group(q, kb, vb, dead, h0, head_block,
                                   group, scale, acc_ref, m_ref, l_ref)

    @pl.when(j == n_log - 1)
    def _emit():
        # all-sentinel (idle/disarmed) rows never accumulate: l stays
        # 0 and the clamp emits finite zeros nothing reads
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _attend_one_head(q, kb, vb, dead, h, group, scale,
                     acc_ref, m_ref, l_ref):
    """The original per-KV-head online-softmax step (head_block=1) —
    kept verbatim as the bit-parity baseline the batched path and the
    gather oracle are gated against."""
    sl = slice(h * group, (h + 1) * group)
    s = jax.lax.dot_general(
        q[sl], kb[:, h, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (group, page_size)
    s = jnp.where(dead, NEG_INF, s)
    m = m_ref[sl]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_ref[sl] = l_ref[sl] * alpha + jnp.sum(p, axis=-1,
                                            keepdims=True)
    acc_ref[sl] = acc_ref[sl] * alpha + jax.lax.dot_general(
        p.astype(vb.dtype), vb[:, h, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[sl] = m_new


def _attend_head_group(q, kb, vb, dead, h0, hb, group, scale,
                       acc_ref, m_ref, l_ref):
    """``hb`` KV heads per step: the score and value dots batch over
    the head axis (dot_general batch dims), so one issue carries
    ``hb·group`` q rows. Same f32 math per element as the per-head
    loop — only the batching changes."""
    sl = slice(h0 * group, (h0 + hb) * group)
    qh = q[sl].reshape(hb, group, q.shape[-1])
    # scores: batch hb, contract Dh → (hb, group, page_size)
    s = jax.lax.dot_general(
        qh, kb[:, h0:h0 + hb, :], (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(dead[None], NEG_INF, s)
    m = m_ref[sl].reshape(hb, group, 1)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l_ref[sl].reshape(hb, group, 1) * alpha + jnp.sum(
        p, axis=-1, keepdims=True)
    l_ref[sl] = l_new.reshape(hb * group, 1)
    # values: batch hb, contract page_size → (hb, group, Dh)
    pv = jax.lax.dot_general(
        p.astype(vb.dtype), vb[:, h0:h0 + hb, :],
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )
    acc_ref[sl] = (acc_ref[sl] * alpha.reshape(hb * group, 1)
                   + pv.reshape(hb * group, q.shape[-1]))
    m_ref[sl] = m_new.reshape(hb * group, 1)


def paged_decode_attention(q, k_pages, v_pages, pages, positions, *,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           head_block: Optional[int] = None):
    """Single-token decode attention straight off a paged KV pool.

    - ``q``: ``(B, QH, Dh)`` — one rotated query token per row;
    - ``k_pages``/``v_pages``: the shared pool,
      ``(pages_total, page_size, KH, Dh)``;
    - ``pages``: ``(B, n_logical)`` int32 per-row page table; the
      sentinel id ``pages_total`` marks unmapped entries;
    - ``positions``: ``(B,)`` int32 — each row's query position (KV
      positions ``<= positions[b]`` attend; the row's token for this
      step must already be written at that position).

    Returns ``(B, QH, Dh)`` in ``q.dtype``. HBM reads touch each
    row's live pages once — never the dense ``(B, Smax, ...)`` view,
    never a QH-wide GQA copy.

    ``head_block`` is the KV head-group compute knob: ``None`` resolves
    it from the committed tile table (kernel key ``paged_attn``,
    ``kubeflow_tpu/ops/autotune.py``; the safe fallback is the
    per-head loop, 1); an explicit value overrides and must divide the
    pool's KV head count.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, QH, Dh = q.shape
    P, page_size, KH, _ = k_pages.shape
    n_log = pages.shape[1]
    if QH % KH:
        raise ValueError(f"q heads {QH} must be a multiple of kv heads "
                         f"{KH}")
    cfg = resolve_paged(
        max_seq_len=n_log * page_size, page_size=page_size, n_heads=QH,
        n_kv_heads=KH, head_dim=Dh, dtype=q.dtype, head_block=head_block)
    head_block = cfg.head_block
    if head_block < 1 or KH % head_block:
        raise ValueError(f"head_block {head_block} must divide kv heads "
                         f"{KH}")
    scale = sm_scale if sm_scale is not None else Dh ** -0.5
    pages = pages.astype(jnp.int32)
    positions = positions.astype(jnp.int32)

    def q_map(b, j, pages_ref, pos_ref):
        return (b, 0, 0)

    def kv_map(b, j, pages_ref, pos_ref):
        # causal clamp: pages past the row's last live one re-fetch
        # the last live block (elided); sentinel entries clamp into
        # the pool — both are compute-gated off in the kernel
        jj = jnp.minimum(j, pos_ref[b] // page_size)
        return (jnp.minimum(pages_ref[b, jj], P - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_log),
        in_specs=[
            pl.BlockSpec((1, QH, Dh), q_map),
            pl.BlockSpec((1, page_size, KH, Dh), kv_map),
            pl.BlockSpec((1, page_size, KH, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, QH, Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((QH, Dh), jnp.float32),
            pltpu.VMEM((QH, 1), jnp.float32),
            pltpu.VMEM((QH, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, n_log=n_log,
        scale=scale, n_kv_heads=KH, group=QH // KH, sentinel=P,
        head_block=head_block)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, QH, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(pages, positions, q, k_pages, v_pages)
