"""Flagship decoder-only transformer LM, written TPU-first.

The reference platform never sees model internals — its workloads are opaque
container images (``tf_cnn_benchmarks`` via
``/root/reference/kubeflow/examples/prototypes/tf-job-simple-v1.jsonnet:28-38``).
The TPU-native framework ships models in-framework so parallelism axes
(SURVEY.md §2c) are real capabilities: this model exposes logical sharding
axes for DP/TP/SP/EP and stacks its blocks so pipeline stages can shard the
leading layer axis.

Design notes (TPU-first):
- bf16 activations, fp32 params/optimizer; big fused einsums for the MXU.
- ``nn.scan`` over blocks: one traced block, stacked params — fast compiles
  and a natural ``stage`` axis for pipeline parallelism.
- ``nn.remat`` per block trades FLOPs for HBM; the flash forward's output
  and log-sum-exp are kept, so the backward reruns no attention kernel
  (``remat_block``).
- MoE uses exact dense top-k dispatch (one-hot combine einsum): static
  shapes, XLA-friendly; experts shard over the ``expert`` logical axis. A
  capacity-based all_to_all dispatch is the planned fast path for large E.
- Sequence-parallel regions: norms/residual activations carry a ``seq``
  sharding constraint so the tp group shards the sequence dim between the
  matmul regions (Megatron-SP layout).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from kubeflow_tpu.parallel.mesh import (
    AxisRules,
    DEFAULT_RULES,
    logical_to_mesh_axes,
    shard_constraint,
    shard_kernel,
)


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    """One leaf of a decode model's ``cache`` collection, as the model
    declares it. The serving engine takes a leaf's row axis and idle
    value from here, by the leaf's name, never from its rank."""
    shape: Tuple[int, ...]
    dtype: Any
    batch_axis: Optional[int]      # None: a pool that every row shares
    fill: int = 0                  # what a cache the model makes holds
    idle: Optional[int] = None     # what a row with no request holds
    #                                in the engine (None: ``fill``)
    heads_axis: Optional[int] = None   # sharded over heads on a mesh
    head_width: int = 1            # elements of ``heads_axis`` that make
    #                                one head (a mesh splits whole heads)

    @property
    def idle_value(self) -> int:
        return self.fill if self.idle is None else self.idle


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    n_experts: int = 0            # 0 => dense MLP
    experts_per_token: int = 2
    moe_capacity_factor: float = 0.0  # 0 => exact dense dispatch; >0 => GShard
    # capacity dispatch via kubeflow_tpu.ops.moe (the large-E fast path)
    dtype: Any = jnp.bfloat16     # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True
    logits_softcap: float = 0.0
    # attention core: "dense" O(S²) (XLA-fused, fine to moderate S),
    # "blockwise" O(S·block) scan, "flash" Pallas kernel, "ring"/"ulysses"
    # sequence-parallel attention over the seq mesh axis (ppermute KV
    # rotation vs all_to_all seq↔heads re-shard; both long-context),
    # "auto" = flash on the TPU backend and dense (the XLA parity
    # oracle) elsewhere — the BERT/bidirectional route
    attention_impl: str = "dense"
    # flash KV tile edge. None (the default) resolves per kernel key +
    # shape class from the committed tile table
    # (kubeflow_tpu/ops/autotune.py + ops/tile_table.json — seeded with
    # the r5 chip-measured winners: 1024-edge tiles ran fwd+bwd 1.8x
    # the 512 rate at seq 8192; 2048 exceeds scoped VMEM) with an
    # analytic VMEM-budget fallback; an int pins an explicit override
    # for every flash kernel (the pre-PR behavior). Also the blockwise/
    # ring/ulysses KV tile (those cores default to 1024 when None).
    attention_block_k: Optional[int] = None
    # flash q-tile edge, independent of block_k since the autotune
    # plane split the square knob; None = table/auto, int = override
    attention_block_q: Optional[int] = None
    causal: bool = True           # False => bidirectional (encoder/BERT)
    seq_axis: str = "tp"          # mesh axis ring attention shards sequence over
    rules: AxisRules = DEFAULT_RULES  # logical-axis -> mesh-axis sharding rules
    # decode mode only: multi-token applies write from PER-ROW start
    # positions (speculative verification, ragged continuation) instead
    # of the contiguous shared-start prefill fast path
    ragged_decode: bool = False
    # decode mode only: paged KV cache. 0 => dense per-row cache
    # (B, max_seq_len, KH·Dh). >0 => the cache is a POOL of
    # ``kv_pages`` HBM blocks of ``kv_page_size`` tokens each, shared
    # by the batch through a per-row page table ("pages" cache var,
    # (B, max_seq_len/kv_page_size) int32 of physical page ids; the
    # sentinel value ``kv_pages`` marks an unmapped logical page —
    # writes through it scatter-drop). The serving engine owns page
    # allocation (kubeflow_tpu/serving/kvpool.py); the model only
    # reads/writes through the table.
    kv_page_size: int = 0
    kv_pages: int = 0
    # paged decode attention core (kv_page_size > 0, single-token
    # steps): "gather" materializes each row's logical KV view back to
    # a dense (B, max_seq_len, KH, Dh) tensor (the interpret-mode
    # fallback and the bit-parity oracle), "kernel" reads K/V straight
    # through the page table inside a Pallas kernel
    # (ops/paged_attention.py — HBM reads proportional to live pages),
    # "auto" picks the kernel in compiled mode (TPU backend) and the
    # gather elsewhere. Multi-token applies (prefill chunks, ragged
    # continuation) always take the gather path — the kernel is the
    # decode-step hot loop.
    paged_attention_impl: str = "auto"
    # paged kernel KV head-group compute block (ops/paged_attention.py
    # head_block): None = tile-table/auto (safe fallback: the per-head
    # loop, 1); an int overrides and must divide n_kv_heads
    paged_head_block: Optional[int] = None

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    has_recurrent_state = False   # every cache leaf is indexed by position
    prefill_chunk = 0             # every prompt is admitted by one program

    def decoder(self) -> "Transformer":
        """The decode-mode module that ``models/decode.py`` applies."""
        return Transformer(self, decode=True)

    def cache_leaves(self, batch: int,
                     stack: Optional[Tuple[int, ...]] = None) -> dict:
        """The decode cache's leaves by name: per-row write
        ``positions``, ``k`` / ``v`` as ``(B, max_seq_len, KH·Dh)`` rows
        (a position's KV heads side by side on ONE last axis, head
        ``kvh`` in lanes ``[kvh·Dh, (kvh+1)·Dh)``: the same bytes in the
        same order as ``(…, KH, Dh)``, declared so that the last axis
        fills the TPU's 128 lanes at any head size; ``heads_axis`` is
        that merged axis and ``head_width`` is ``Dh``, so a mesh still
        splits whole KV heads of it) or, paged, a ``(kv_pages,
        kv_page_size, KH, Dh)`` pool beside the per-row ``pages`` table
        (every entry the unmapped sentinel ``kv_pages``; an idle row's
        position is ``max_seq_len``, disarmed: its writes scatter-drop).
        ``stack`` is ``(n_layers,)`` where one owner holds every layer's
        leaves (the default under ``scan_layers``)."""
        if stack is None:
            stack = (self.n_layers,) if self.scan_layers else ()
        Smax, KH, Dh = self.max_seq_len, self.n_kv_heads, self.head_dim
        off = len(stack)
        if self.kv_page_size:
            P, ps = self.kv_pages, self.kv_page_size
            kv = CacheLeaf(stack + (P, ps, KH, Dh), self.dtype, None,
                           heads_axis=off + 2)
            return {"positions": CacheLeaf(stack + (batch,), jnp.int32,
                                           off, idle=Smax),
                    "pages": CacheLeaf(stack + (batch, Smax // ps),
                                       jnp.int32, off, fill=P),
                    "k": kv, "v": kv}
        kv = CacheLeaf(stack + (batch, Smax, KH * Dh), self.dtype, off,
                       heads_axis=off + 2, head_width=Dh)
        return {"positions": CacheLeaf(stack + (batch,), jnp.int32, off),
                "k": kv, "v": kv}

    def validate(self) -> None:
        if self.n_heads % max(self.n_kv_heads, 1):
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.n_experts and self.experts_per_token > self.n_experts:
            raise ValueError("experts_per_token > n_experts")
        if self.attention_impl not in ("dense", "blockwise", "flash",
                                       "ring", "ulysses", "auto"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        for knob in ("attention_block_q", "attention_block_k",
                     "paged_head_block"):
            v = getattr(self, knob)
            if v is not None and (not isinstance(v, int)
                                  or isinstance(v, bool) or v < 1):
                raise ValueError(
                    f"{knob} must be None (tile-table/auto) or a "
                    f"positive int, got {v!r}")
        if self.kv_page_size:
            if self.max_seq_len % self.kv_page_size:
                raise ValueError(
                    f"kv_page_size {self.kv_page_size} must divide "
                    f"max_seq_len {self.max_seq_len}")
            if self.kv_pages < 1:
                raise ValueError("paged decode needs kv_pages >= 1")
        if self.paged_attention_impl not in ("auto", "gather", "kernel"):
            raise ValueError(
                f"unknown paged_attention_impl "
                f"{self.paged_attention_impl!r}; valid: auto, gather, "
                "kernel")


def _constrain(x, rules: AxisRules, *names):
    """Logical sharding constraint; silently a no-op outside a mesh context."""
    return shard_constraint(x, names, rules)


# KV tile for the non-Pallas cores (blockwise scan, ring/ulysses inner
# loop) when attention_block_k is None: those cores have no tile table —
# 1024 is simply the pre-autotune default, kept so old behavior holds
_UNTUNED_BLOCK_K = 1024


class RMSNorm(nn.Module):
    eps: float = 1e-6
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype
        )
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + self.eps)
        return (x * scale).astype(dtype)


def rope_tables(seq_len: int, head_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(pos, freqs)  # (S, Dh/2)
    return jnp.sin(angles), jnp.cos(angles)


def _rotate(x: jnp.ndarray, sin: jnp.ndarray,
            cos: jnp.ndarray) -> jnp.ndarray:
    """The rope rotation core; sin/cos arrive pre-broadcast to x's rank.
    ONE definition — training, prefill, and the per-row decode step must
    rotate identically or generation diverges from prefill."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, Dh); rotate pairs (even, odd) halves interleaved as split."""
    return _rotate(x, sin[None, :, None, :].astype(x.dtype),
                   cos[None, :, None, :].astype(x.dtype))


def _declare_cache(module: nn.Module, c: TransformerConfig, batch: int,
                   stack: Tuple[int, ...] = ()):
    """``c.cache_leaves`` as ``cache`` variables of ``module``."""
    return {name: module.variable("cache", name, jnp.full, leaf.shape,
                                  leaf.fill, leaf.dtype)
            for name, leaf in c.cache_leaves(batch, stack).items()}


def _layer_slice(stacked: jnp.ndarray, layer) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(stacked, layer, 0, keepdims=False)


def _merged_step_attention(q, kp, vp, mask, sinks=None):
    """One token's attention against K / V rows whose KV heads lie
    merged on the last axis, without un-merging them.

    ``q``: ``(B, KH, G, Dh)``, rotated, the ``G`` query heads of a KV
    head side by side; ``kp`` / ``vp``: ``(B, T, KH·Dh)`` and ``(B, T,
    KH·Dv)``; ``mask``: ``(B, T)``, True where the row may look;
    ``sinks``: None, or ``(KH, G)`` float32, a logit a head in the
    softmax's denominator with no value. Returns ``(B, KH, G, Dv)``.
    Both products run over the whole merged axis: QKᵀ against a query
    that is zero outside its own KV head's lanes (``qz[b, kvh·Dh + d, h]
    = q[b, h, d]`` where ``kvh = h // G``), PV into all ``KH·Dh`` lanes,
    of which a head keeps its KV head's ``Dh``. The terms added are exact
    zeros, so the sums are those of the per-head products; grouped
    queries live in ``qz`` and K and V are never repeated. It is ``KH``
    times the minimum FLOPs, which a step of one token hides under the
    bytes of K and V and a prefill would not. Whole KV heads are
    independent of each other, so a mesh may split them
    (``shard_kernel``): ``KH`` is read off the shapes.
    """
    from kubeflow_tpu.ops.attention import NEG_INF

    B, KH, G, Dh = q.shape
    own = jnp.eye(KH, dtype=bool)[None, :, None, :, None]
    # (B, kvh, d, j, g): head (j, g)'s query in KV head kvh's lanes
    qz = jnp.where(own, q.transpose(0, 1, 3, 2)[:, :, :, None, :],
                   0).reshape(B, KH * Dh, KH * G)
    logits = jnp.einsum("btl,blh->bht", kp, qz).astype(jnp.float32)
    logits = logits * (Dh ** -0.5)
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    if sinks is None:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    else:
        b = sinks.reshape(1, KH * G, 1).astype(jnp.float32)
        m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), b)
        e = jnp.exp(logits - m)
        probs = (e / (jnp.exp(b - m) + jnp.sum(e, axis=-1, keepdims=True))
                 ).astype(q.dtype)
    full = jnp.einsum("bht,btl->bhl", probs, vp)      # (B, H, KH·Dv)
    Dv = vp.shape[-1] // KH
    # (B, j, g, kvh, d): head (j, g) keeps the lanes of KV head j
    return jnp.where(own, full.reshape(B, KH, G, KH, Dv), 0).sum(axis=3)


def _step_kernel(c, q, ck, cv, layer, pos, sinks=None):
    """One token a row against the stacked ``k`` / ``v`` leaves at
    ``layer`` by ``ops/step_attention.py``'s kernel, which reads a row
    only as far as it has grown, under ``shard_kernel`` (whole KV heads a
    tp rank; nothing is exchanged). ``q`` ``(B, KH, G, Dh)``, ``pos``
    ``(B,)`` (the step's token is written there); ``c`` has
    ``max_seq_len``, ``dtype`` and ``rules``. The dense decoder and the
    hybrid decoder's ``"gqa"`` blocks and ``"attn"`` / ``"swa"`` layers
    call it where ``step_attention.use_kernel()`` says, and
    :func:`_merged_step_attention` (its reference) elsewhere. V may be
    narrower than K; a leaf shorter than ``max_seq_len`` is a window
    layer's ring; ``sinks`` ``(KH, G)`` float32 or None."""
    from kubeflow_tpu.ops import step_attention as sa

    B, KH, G, Dh = q.shape
    Smax, Dv = ck.shape[2], cv.shape[-1] // KH
    rows, block = sa.tiling(B, Smax, KH, Dh, KH * G, c.dtype,
                            None if Dv == Dh else Dv)
    grouped, stacked = (None, "heads", None, None), (None, None, None, "heads")
    args = (q, ck, cv, jnp.asarray(layer, jnp.int32), pos)
    specs = (grouped, stacked, stacked, (), (None,))
    if sinks is not None:
        args, specs = args + (sinks,), specs + (("heads", None),)
    return shard_kernel(
        "step_attention",
        functools.partial(
            sa.step_attention, block_rows=rows, block_len=block,
            max_seq_len=None if Smax == c.max_seq_len else c.max_seq_len),
        args, specs, (B, KH, G, Dv), grouped, c.rules)


def step_block_counts(c, pos, n_kv_heads: int, head_dim: int,
                      n_heads: int, v_head_dim: Optional[int] = None) -> dict:
    """What a one-token step's attention reads of the dense K / V leaves,
    in ``ops/step_attention.py``'s blocks, for the engine's round:
    ``kv_blocks_read`` (the rows' live blocks) and ``kv_blocks_reserved``
    (rows x blocks a row), each for ONE attention layer (every layer of
    the step reads the same). Empty where the step runs no kernel."""
    from kubeflow_tpu.ops import step_attention as sa

    if not sa.use_kernel():
        return {}
    _, block = sa.tiling(pos.shape[0], c.max_seq_len, n_kv_heads, head_dim,
                         n_heads, c.dtype,
                         None if v_head_dim == head_dim else v_head_dim)
    read, reserved = sa.block_counts(pos, c.max_seq_len, block)
    return {"kv_blocks_read": read, "kv_blocks_reserved": reserved}


class Attention(nn.Module):
    config: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, sin, cos, kv_len=None, cache=None, layer=None):
        """Attention output ``(B, S, D)``; in decode mode ``(output,
        cache)``. There ``cache`` holds the ``[L, ...]`` leaves of every
        layer and ``layer`` says which slice is this block's (the carry
        and the scanned index of ``Transformer``'s layer scan); with
        ``cache`` None the block owns its buffers (``scan_layers=False``)
        and runs the same code over them as a stack of one."""
        c = self.config
        B, S, D = x.shape
        H, KH, Dh = c.n_heads, c.n_kv_heads, c.head_dim
        init = nn.initializers.normal(stddev=D ** -0.5)

        wq = self.param("q_proj", init, (D, H, Dh), c.param_dtype)
        wk = self.param("k_proj", init, (D, KH, Dh), c.param_dtype)
        wv = self.param("v_proj", init, (D, KH, Dh), c.param_dtype)
        wo = self.param("o_proj", init, (H, Dh, D), c.param_dtype)

        q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(c.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(c.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(c.dtype))

        if self.decode:
            own = None
            if cache is None:
                own = _declare_cache(self, c, B)
                cache = {name: var.value[None] for name, var in own.items()}
                layer = 0
            out, cache = self._decode_attend(q, k, v, sin, cos, cache,
                                             layer)
            if own is not None:
                for name, var in own.items():
                    var.value = cache[name][0]
                cache = None
            out = jnp.einsum("bshk,hkd->bsd", out, wo.astype(c.dtype))
            return _constrain(out, c.rules, "batch", "seq", None), cache

        if c.attention_impl in ("ring", "ulysses"):
            # sequence stays sharded through attention (SP paths); heads
            # replicate — the inverse of the tensor-parallel dense layout
            q = _constrain(q, c.rules, "batch", "seq", None, None)
        else:
            q = _constrain(q, c.rules, "batch", None, "heads", None)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

        if c.attention_impl != "ulysses":
            # GQA repeat for the cores that want full heads; ulysses
            # repeats AFTER its KV all_to_alls so the collectives carry
            # only the distinct KV heads (kubeflow_tpu/ops/attention.py)
            from kubeflow_tpu.ops.attention import gqa_repeat

            k, v = gqa_repeat(q, k, v)

        out = self._attend(q, k, v, kv_len=kv_len)
        out = jnp.einsum("bshk,hkd->bsd", out, wo.astype(c.dtype))
        return _constrain(out, c.rules, "batch", "seq", None)

    def _decode_attend(self, q, k, v, sin_full, cos_full, cache, layer):
        """Autoregressive attention with a KV cache (static shapes).

        ``sin_full``/``cos_full`` span ``max_seq_len``. The cache carries
        PER-ROW write positions: every row's tokens sit contiguously at
        their logical positions (physical slot == logical position), so
        masking stays purely causal even for ragged batches — everything
        under one jit with no data-dependent shapes (one compiled prefill
        per prompt bucket, one compiled step).

        - multi-token (S > 1): each row writes S tokens from its OWN
          current position (fresh prefill: 0; prefix continuation and
          speculative verification: ragged per-row starts); the caller
          then resets positions to each row's true length (see
          :func:`kubeflow_tpu.models.decode.prefill`) — a row's pad
          tail is masked (kv_pos > its positions) until the generated
          tokens overwrite it;
        - step (S == 1): per-row scatter write + per-row rope position.

        ``cache`` is the dict of ``[L, ...]`` leaves and stays ONE
        buffer per leaf: the new tokens are written into it at
        ``[layer, row, position]`` and this layer's rows are read back
        out of it, so a loop that carries it updates it in place.

        ``k`` / ``v`` hold a position's KV heads MERGED on the last axis
        (``(L, B, Smax, KH·Dh)``, ``TransformerConfig.cache_leaves``),
        and every form writes its tokens as such rows. The one-token
        step attends against the merged rows as they lie (on the TPU
        :func:`_step_kernel`, which reads each row only as far as it has
        grown; elsewhere :func:`_merged_step_attention` over the layer's
        slice): un-merging them would re-lay a whole layer's K and V out
        on every step at a head size under 128 lanes. The multi-token forms, whose FLOPs are not hidden under
        the cache's bytes, attend against the ``(B, Smax, KH, Dh)`` view.
        Returns ``(output, cache)``.
        """
        c = self.config
        if c.kv_page_size:
            return self._paged_decode_attend(q, k, v, sin_full, cos_full,
                                             cache, layer)
        B, S, KH, Dh = k.shape
        Smax = c.max_seq_len
        ck, cv = cache["k"], cache["v"]
        pos = _layer_slice(cache["positions"], layer)  # (B,)

        from kubeflow_tpu.ops.attention import NEG_INF, gqa_repeat

        def merged(x):  # (B, S, KH, Dh) -> the cache's (B, S, KH·Dh) rows
            return x.reshape(B, S, KH * Dh)

        if S == 1:
            # one token per row at its own position
            sin = jnp.take(sin_full, pos, axis=0)[:, None, None, :].astype(
                q.dtype)
            cos = jnp.take(cos_full, pos, axis=0)[:, None, None, :].astype(
                q.dtype)
            q = _rotate(q, sin, cos)
            k = _rotate(k, sin, cos)
            rows = jnp.arange(B)
            ck = ck.at[layer, rows, pos].set(merged(k)[:, 0])
            cv = cv.at[layer, rows, pos].set(merged(v)[:, 0])
            q_pos = pos[:, None]  # (B, 1)
        elif c.ragged_decode:
            # multi-token with per-row starts (speculative verify,
            # ragged prefix continuation): per-row rope gather + one
            # batched scatter. Statically selected — the common
            # shared-start prefill keeps its contiguous slice-update.
            q_pos = pos[:, None] + jnp.arange(S)[None, :]  # (B, S)
            sin = jnp.take(sin_full, q_pos, axis=0)[:, :, None, :].astype(
                q.dtype)
            cos = jnp.take(cos_full, q_pos, axis=0)[:, :, None, :].astype(
                q.dtype)
            q = _rotate(q, sin, cos)
            k = _rotate(k, sin, cos)
            rows2d = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
            ck = ck.at[layer, rows2d, q_pos].set(merged(k))
            cv = cv.at[layer, rows2d, q_pos].set(merged(v))
        else:
            # prefill: rows share a start (a fresh cache starts at 0;
            # the engine's 1-row prefix continuation shares trivially)
            idx = pos[0]
            sin = jax.lax.dynamic_slice_in_dim(sin_full, idx, S, 0)
            cos = jax.lax.dynamic_slice_in_dim(cos_full, idx, S, 0)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
            at = (layer, 0, idx, 0)
            ck = jax.lax.dynamic_update_slice(ck, merged(k)[None], at)
            cv = jax.lax.dynamic_update_slice(cv, merged(v)[None], at)
            q_pos = (idx + jnp.arange(S))[None, :]  # (1, S) → rows share
        cache = dict(cache, k=ck, v=cv,
                     positions=jax.lax.dynamic_update_index_in_dim(
                         cache["positions"], pos + S, layer, 0))

        from kubeflow_tpu.ops import step_attention

        if S == 1 and step_attention.use_kernel():
            H = q.shape[2]
            out = _step_kernel(c, q.reshape(B, KH, H // KH, Dh), ck, cv,
                               layer, pos)
            return out.reshape(B, 1, H, Dh), cache
        kp, vp = _layer_slice(ck, layer), _layer_slice(cv, layer)
        # (B or 1, S, Smax): per-row causal bound
        mask = jnp.arange(Smax)[None, None, :] <= q_pos[:, :, None]
        if S == 1:
            # on a serving mesh each tp rank attends over its own KV
            # heads' lanes and their query heads; nothing is exchanged
            H = q.shape[2]
            grouped, rows = (None, "heads", None, None), (None, None, "heads")
            out = shard_kernel(
                "merged_step_attention", _merged_step_attention,
                (q.reshape(B, KH, H // KH, Dh), kp, vp, mask[:, 0]),
                (grouped, rows, rows, (None, None)),
                (B, KH, H // KH, Dh), grouped, c.rules)
            return out.reshape(B, 1, H, Dh), cache
        kc, vc = gqa_repeat(q, kp.reshape(B, Smax, KH, Dh),
                            vp.reshape(B, Smax, KH, Dh))
        logits = jnp.einsum("bshd,bthd->bhst", q, kc).astype(jnp.float32)
        logits = logits * (Dh ** -0.5)
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, vc), cache

    def _paged_decode_attend(self, q, k, v, sin_full, cos_full, cache,
                             layer):
        """Autoregressive attention over a PAGED KV pool.

        The cache is a pool of ``kv_pages`` HBM blocks of
        ``kv_page_size`` tokens shared by the whole batch; each row maps
        logical pages to physical pages through its "pages" table row.
        One code path serves every S (step, prefill chunk, ragged
        continuation): writes scatter each token to
        ``(pages[b, pos // ps], pos % ps)`` and reads gather the row's
        logical view back to ``(B, max_seq_len, KH, Dh)`` before the
        exact attention math of the dense path — live positions carry
        identical values, garbage positions are masked to NEG_INF
        exactly as dense masks its unwritten tail, so greedy decode is
        token-identical to the dense cache.

        Safety contract with the allocator (serving/kvpool.py):

        - a logical page mapped to the sentinel id ``kv_pages`` (or a
          position past ``max_seq_len``) writes out of bounds, which
          scatter DROPS — idle/disarmed rows can step forever without
          touching live pages;
        - reads through the sentinel clamp to an arbitrary real page;
          those positions are causally masked, and the exactly-zero
          masked probabilities keep garbage out of the output bitwise;
        - two rows never map the same WRITABLE page; prefix pages are
          shared read-only (rows only write at positions >= their own
          start, which the engine keeps past the shared region).
        """
        c = self.config
        B, S, KH, Dh = k.shape
        Smax = c.max_seq_len
        ps = c.kv_page_size
        P = c.kv_pages

        ck, cv = cache["k"], cache["v"]                  # (L, P, ps, KH, Dh)
        pos = _layer_slice(cache["positions"], layer)    # (B,)
        pages = _layer_slice(cache["pages"], layer)      # (B, n_log)

        from kubeflow_tpu.ops.attention import NEG_INF, gqa_repeat

        q_pos = pos[:, None] + jnp.arange(S)[None, :]       # (B, S)
        safe_pos = jnp.minimum(q_pos, Smax - 1)
        sin = jnp.take(sin_full, safe_pos, axis=0)[:, :, None, :].astype(
            q.dtype)
        cos = jnp.take(cos_full, safe_pos, axis=0)[:, :, None, :].astype(
            q.dtype)
        q = _rotate(q, sin, cos)
        k = _rotate(k, sin, cos)
        # physical write targets; overruns and unmapped pages resolve to
        # pool index P, which the scatter drops
        pg = jnp.take_along_axis(pages, safe_pos // ps, axis=1)  # (B, S)
        pg = jnp.where(q_pos < Smax, pg, P)
        off = q_pos % ps
        ck = ck.at[layer, pg, off].set(k, mode="drop")
        cv = cv.at[layer, pg, off].set(v, mode="drop")
        cache = dict(cache, k=ck, v=cv,
                     positions=jax.lax.dynamic_update_index_in_dim(
                         cache["positions"], pos + S, layer, 0))
        k_pool, v_pool = _layer_slice(ck, layer), _layer_slice(cv, layer)

        impl = c.paged_attention_impl
        if S == 1 and (impl == "kernel" or (impl == "auto"
                                            and jax.default_backend()
                                            == "tpu")):
            # decode-step hot loop: read K/V straight through the page
            # table inside the Pallas kernel — HBM traffic proportional
            # to live pages, no dense view, no QH-wide GQA copy. The
            # gather below remains the bit-parity oracle (greedy streams
            # are asserted token-identical, tests/test_engine_paged.py)
            # and the multi-token (chunk/ragged) path.
            from kubeflow_tpu.ops.paged_attention import (
                paged_decode_attention,
            )

            # on a serving mesh each tp rank runs the kernel over its own
            # q-head group and the matching kv heads of the pool
            pool = (None, None, "heads", None)
            out = shard_kernel(
                "paged_attn",
                lambda q1, kp, vp, pg, ps_: paged_decode_attention(
                    q1, kp, vp, pg, ps_, sm_scale=Dh ** -0.5,
                    head_block=c.paged_head_block),
                (q[:, 0], k_pool, v_pool, pages, pos),
                ((None, "heads", None), pool, pool, (None, None), (None,)),
                q[:, 0].shape, (None, "heads", None), c.rules)
            return out[:, None], cache

        # gather each row's logical view: (B, n_log, ps, KH, Dh) ->
        # (B, Smax, KH, Dh); sentinel entries clamp to a real page and
        # are masked below
        kc = jnp.take(k_pool, pages, axis=0,
                      mode="clip").reshape(B, Smax, KH, Dh)
        vc = jnp.take(v_pool, pages, axis=0,
                      mode="clip").reshape(B, Smax, KH, Dh)
        kc, vc = gqa_repeat(q, kc, vc)
        logits = jnp.einsum("bshd,bthd->bhst", q, kc).astype(jnp.float32)
        logits = logits * (Dh ** -0.5)
        kv_pos = jnp.arange(Smax)
        mask = kv_pos[None, None, :] <= q_pos[:, :, None]   # (B, S, Smax)
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, vc), cache

    def _attend(self, q, k, v, kv_len=None):
        """Dispatch to the configured attention core (causal per config).

        ``attention_impl="auto"`` routes through the flash kernels on
        the TPU backend and the dense XLA path elsewhere — dense is the
        parity oracle the flash path is gated against (the BERT
        bidirectional route, tests/test_bert.py). ``kv_len`` is the
        per-row valid-length padding mask; only the dense and flash
        cores implement it, so any other impl refuses it loudly.
        """
        c = self.config
        from kubeflow_tpu.ops import attention as att  # local: no cycle

        impl = c.attention_impl
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "dense"
        if kv_len is not None and impl not in ("dense", "flash"):
            raise ValueError(
                f"kv_len padding mask is not supported by "
                f"attention_impl={impl!r} (dense and flash only)")
        block_k = c.attention_block_k or _UNTUNED_BLOCK_K
        if impl == "dense":
            return att.reference_attention(q, k, v, causal=c.causal,
                                           kv_len=kv_len)
        if impl == "blockwise":
            return att.blockwise_attention(
                q, k, v, causal=c.causal, block_k=block_k
            )
        if impl == "flash":
            from kubeflow_tpu.ops import autotune

            # flash requires block | seq: explicit overrides are fitted
            # to the largest divisor within their budget (the pre-split
            # behavior, now per knob); None stays None so the kernels
            # resolve each kernel key from the tile table. Degenerate
            # divisors fall back to blockwise, as before.
            S = q.shape[1]
            if autotune.fit_block(
                    S, block_k if c.attention_block_k else
                    autotune.MAX_TILE_EDGE) < 16:
                if kv_len is not None:
                    raise ValueError(
                        f"kv_len padding mask needs a flash-tileable "
                        f"seq len, got {S}")
                return att.blockwise_attention(
                    q, k, v, causal=c.causal, block_k=block_k
                )
            bq = (autotune.fit_block(S, c.attention_block_q)
                  if c.attention_block_q else None)
            bk = (autotune.fit_block(S, c.attention_block_k)
                  if c.attention_block_k else None)
            # on a mesh each device runs the kernels over its own batch
            # rows and heads (attention is independent along both)
            bshd = ("batch", None, "heads", None)
            lens = () if kv_len is None else (kv_len,)
            return shard_kernel(
                "flash_attention",
                lambda q, k, v, *lens: att.flash_attention(
                    q, k, v, c.causal, bq, bk, None, None, *lens),
                (q, k, v, *lens),
                (bshd,) * 3 + (("batch",),) * len(lens), q.shape, bshd,
                c.rules)
        # ring / ulysses: sequence-parallel over the seq mesh axis;
        # partial-manual shard_map (batch/other axes stay auto)
        from kubeflow_tpu import compat

        mesh = compat.current_mesh()
        if mesh.empty or c.seq_axis not in mesh.axis_names:
            k, v = att.gqa_repeat(q, k, v)  # ulysses deferred the repeat
            return att.blockwise_attention(
                q, k, v, causal=c.causal, block_k=block_k
            )
        from jax.sharding import PartitionSpec as P

        if c.attention_impl == "ulysses":
            core = functools.partial(
                att.ulysses_attention, axis_name=c.seq_axis,
                causal=c.causal, block_k=block_k)
        else:
            core = functools.partial(
                att.ring_attention, axis_name=c.seq_axis, causal=c.causal)
        spec = P(None, c.seq_axis, None, None)
        fn = compat.shard_map(
            core,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            axis_names={c.seq_axis},
        )
        return fn(q, k, v)


class Mlp(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        D, F = c.d_model, c.d_ff
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_gate = self.param("gate_proj", init, (D, F), c.param_dtype)
        w_up = self.param("up_proj", init, (D, F), c.param_dtype)
        w_down = self.param("down_proj", init, (F, D), c.param_dtype)
        h = jax.nn.silu(x @ w_gate.astype(c.dtype)) * (x @ w_up.astype(c.dtype))
        h = _constrain(h, c.rules, "batch", None, "mlp")
        return _constrain(h @ w_down.astype(c.dtype), c.rules, "batch", "seq", None)


class MoeMlp(nn.Module):
    """Exact top-k MoE with dense one-hot dispatch (static shapes)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        D, F, E, K = c.d_model, c.d_ff, c.n_experts, c.experts_per_token
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_router = self.param("router", init, (D, E), jnp.float32)
        w_gate = self.param("gate_proj", init, (E, D, F), c.param_dtype)
        w_up = self.param("up_proj", init, (E, D, F), c.param_dtype)
        w_down = self.param("down_proj", init, (E, F, D), c.param_dtype)

        gate_logits = x.astype(jnp.float32) @ w_router  # (B, S, E)

        if c.moe_capacity_factor > 0:
            # GShard capacity dispatch: experts run once over (E, C, D)
            # buffers; with "expert"-sharded weights XLA inserts the
            # AllToAll over the ep group (kubeflow_tpu/ops/moe.py)
            from kubeflow_tpu.ops.moe import capacity_moe  # local: no cycle

            B, S, _ = x.shape

            def expert_fn(xe):  # (E, C, D) -> (E, C, D)
                h = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(xe.dtype))
                u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(xe.dtype))
                h = jax.nn.silu(h) * u
                h = _constrain(h, c.rules, "expert", None, "expert_mlp")
                return jnp.einsum("ecf,efd->ecd", h, w_down.astype(xe.dtype))

            y, aux = capacity_moe(
                x.reshape(B * S, D),
                gate_logits.reshape(B * S, E),
                expert_fn,
                k=K,
                capacity_factor=c.moe_capacity_factor,
            )
            self.sow("losses", "moe_aux", aux)
            return _constrain(y.reshape(B, S, D), c.rules, "batch", "seq", None)

        weights, idx = jax.lax.top_k(gate_logits, K)
        weights = jax.nn.softmax(weights, axis=-1)      # (B, S, K)
        # combine[b, s, e] = sum_k weights[b,s,k] * [idx[b,s,k] == e]
        combine = jnp.sum(
            jax.nn.one_hot(idx, E, dtype=jnp.float32) * weights[..., None], axis=2
        )  # (B, S, E)
        combine = combine.astype(c.dtype)

        # Dense dispatch: every expert sees every token, masked by combine.
        # Experts shard over the "expert" logical axis (EP); with E experts on
        # e_p shards each device computes E/e_p of the einsum's leading dim.
        h = jnp.einsum("bsd,edf->bsef", x, w_gate.astype(c.dtype))
        u = jnp.einsum("bsd,edf->bsef", x, w_up.astype(c.dtype))
        h = jax.nn.silu(h) * u
        # batch keeps the dp axis here (expert weights are dp-sharded, so
        # XLA gathers expert shards within the dp group). The paths that
        # keep experts resident exist: capacity dispatch above (drops
        # tokens past capacity), and models/hybrid.py:RoutedMlp, which
        # holds a share of the experts and groups routed tokens by expert
        # (ops/gmm.py:grouped_matmul) without dropping any.
        h = _constrain(h, c.rules, "batch", None, None, "expert_mlp")
        y = jnp.einsum("bsef,efd->bsed", h, w_down.astype(c.dtype))
        y = jnp.einsum("bsed,bse->bsd", y, combine)

        # load-balancing auxiliary loss (Switch-style): mean prob * fraction routed
        probs = jax.nn.softmax(gate_logits, axis=-1)
        density = jnp.mean(combine.astype(jnp.float32) > 0, axis=(0, 1))
        mean_prob = jnp.mean(probs, axis=(0, 1))
        self.sow("losses", "moe_aux", E * jnp.sum(density * mean_prob))
        return _constrain(y, c.rules, "batch", "seq", None)


class Block(nn.Module):
    config: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, aux, layer=None):
        # aux is (sin, cos) or (sin, cos, kv_len) — the optional third
        # element is the per-row valid-length padding mask the BERT
        # encoder threads through every block (models/bert.py)
        sin, cos = aux[0], aux[1]
        kv_len = aux[2] if len(aux) > 2 else None
        c = self.config
        cache = None
        if self.decode:
            # the carry is (hidden, stacked cache or None) and ``layer``
            # the scanned index: see Attention.__call__
            x, cache = x
        h = RMSNorm(param_dtype=c.param_dtype, name="attn_norm")(x)
        attn = Attention(c, decode=self.decode, name="attn")(
            h, sin, cos, kv_len, cache, layer)
        if self.decode:
            attn, cache = attn
        x = x + attn
        h = RMSNorm(param_dtype=c.param_dtype, name="mlp_norm")(x)
        mlp = MoeMlp(c, name="moe") if c.n_experts else Mlp(c, name="mlp")
        x = x + mlp(h)
        return ((x, cache) if self.decode else x), None


def remat_block():
    """``Block`` recomputed in the backward (``remat=True``), except
    what only the flash forward kernel can produce: its output and
    log-sum-exp keep their values, so a layer's backward runs the
    backward kernel and not the forward kernel a second time. Kept per
    layer: B·S·H·Dh activations in the compute dtype plus B·H·S float32.
    The names exist only where the flash kernels ran
    (``ops/attention.py:_flash_vjp_fwd``); under any other
    ``attention_impl`` the policy keeps nothing."""
    from kubeflow_tpu.ops import attention as att  # local: no cycle

    return nn.remat(Block, prevent_cse=False,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        att.FLASH_OUT, att.FLASH_LSE))


class Transformer(nn.Module):
    config: TransformerConfig
    # autoregressive mode: a "cache" collection (K/V + per-row write
    # positions; apply with mutable=["cache"], see models/decode.py).
    # Under scan_layers the [L, ...] leaves are declared here and travel
    # through the layer scan as part of its CARRY, each block writing and
    # reading its own [layer] slice: a loop's scanned input and stacked
    # output are two buffers that cannot alias, so a cache scanned over
    # (variable_axes) was copied whole on every step of an outer loop
    decode: bool = False
    # return the post-final-norm hidden states (B, S, D) instead of
    # logits: the long-context training path computes the vocab
    # projection CHUNKED inside the loss (train/trainer.py:
    # chunked_next_token_loss) — materializing (B, S, V) f32 logits at
    # seq 65536 is ~8.4 GB and capsizes HBM before attention does
    return_hidden: bool = False

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """tokens: (B, S) int32 -> logits (B, S, V) float32."""
        c = self.config
        c.validate()
        B, S = tokens.shape
        # tied in/out embedding: d^-0.5 init keeps untrained logits O(1) so
        # the initial loss sits near ln(vocab) instead of exploding
        embed = self.param(
            "token_embed",
            nn.initializers.normal(stddev=c.d_model ** -0.5),
            (c.vocab_size, c.d_model),
            c.param_dtype,
        )
        x = jnp.take(embed.astype(c.dtype), tokens, axis=0)
        x = _constrain(x, c.rules, "batch", "seq", None)
        # decode mode uses absolute positions: full tables, sliced at the
        # cache index inside each attention
        sin, cos = rope_tables(c.max_seq_len if self.decode else S,
                               c.head_dim, c.rope_theta)

        block_cls = Block
        if c.remat and not self.decode:
            block_cls = remat_block()
        if c.scan_layers:
            scan = functools.partial(
                nn.scan,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True},
                length=c.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"})
            if self.decode:
                leaves = _declare_cache(self, c, B, (c.n_layers,))
                cache = {name: var.value for name, var in leaves.items()}
                if S == 1 and not c.kv_page_size:
                    for name, n in step_block_counts(
                            c, cache["positions"][0], c.n_kv_heads,
                            c.head_dim, c.n_heads).items():
                        self.sow("moe_stats", name, n)
                (x, cache), _ = scan(Block, in_axes=(nn.broadcast, 0))(
                    c, decode=True, name="blocks")(
                        (x, cache), (sin, cos), jnp.arange(c.n_layers))
                for name, var in leaves.items():
                    var.value = cache[name]
            else:
                x, _ = scan(block_cls, in_axes=nn.broadcast)(
                    c, name="blocks")(x, (sin, cos))
        else:
            if self.decode:
                x = (x, None)  # each block owns its buffers
            for i in range(c.n_layers):
                x, _ = block_cls(c, decode=self.decode,
                                 name=f"block_{i}")(x, (sin, cos))
            if self.decode:
                x, _ = x

        x = RMSNorm(param_dtype=c.param_dtype, name="final_norm")(x)
        if self.return_hidden:
            return _constrain(x, c.rules, "batch", "seq", None)
        logits = jnp.einsum(
            "bsd,vd->bsv", x, embed.astype(c.dtype)
        ).astype(jnp.float32)
        if c.logits_softcap:
            logits = c.logits_softcap * jnp.tanh(logits / c.logits_softcap)
        return _constrain(logits, c.rules, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Parameter sharding: param-path -> logical axes -> PartitionSpec
# ---------------------------------------------------------------------------

_PARAM_AXES = {
    "token_embed": ("vocab", "embed"),
    "q_proj": ("embed", "heads", "kv"),
    "k_proj": ("embed", "heads", "kv"),
    "v_proj": ("embed", "heads", "kv"),
    "o_proj": ("heads", "kv", "embed"),
    "gate_proj": ("embed", "mlp"),
    "up_proj": ("embed", "mlp"),
    "down_proj": ("mlp", "embed"),
    "router": ("embed", None),
    "scale": (None,),
}

_MOE_PARAM_AXES = {
    "gate_proj": ("expert", "embed", "expert_mlp"),
    "up_proj": ("expert", "embed", "expert_mlp"),
    "down_proj": ("expert", "expert_mlp", "embed"),
}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
    return tuple(names)


def leaf_logical_axes(path, leaf) -> Tuple[Optional[str], ...]:
    """Logical axes for one pytree leaf, by param-name matching.

    Works on raw param trees and on whole optimizer/train states (optax's
    mu/nu mirror the param tree, so the same trailing names match; unknown
    leaves and scalars fall back to replicated).
    """
    names = _path_names(path)
    name = names[-1] if names else ""
    ndim = getattr(leaf, "ndim", 0)  # non-array leaves (e.g. a python-int
    if ndim == 0:                    # TrainState.step) replicate
        return ()
    in_moe = "moe" in names
    table = _MOE_PARAM_AXES if in_moe and name in _MOE_PARAM_AXES else _PARAM_AXES
    axes = table.get(name)
    if axes is None:
        return (None,) * ndim
    if "blocks" in names:  # scanned: leading layer axis
        axes = (None,) + tuple(axes)
    if len(axes) != ndim:
        raise ValueError(f"axes {axes} rank != leaf {names} rank {ndim}")
    return tuple(axes)


def param_logical_axes(params) -> Any:
    """Logical-axis tuples for every param leaf, keyed by path name matching.

    Scanned blocks carry a leading layer axis; it maps to the ``stage``
    logical axis only under pipeline parallelism, so here it is ``None``
    (replicated layer stack = no pp) — the pipeline wrapper re-annotates it.
    """
    return jax.tree_util.tree_map_with_path(leaf_logical_axes, params)


def param_partition_specs(params, rules: AxisRules = DEFAULT_RULES) -> Any:
    axes = param_logical_axes(params)
    return jax.tree_util.tree_map(
        lambda a: logical_to_mesh_axes(a, rules),
        axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def tiny_config(**overrides) -> TransformerConfig:
    """A config small enough for CPU tests but exercising every code path."""
    base = dict(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=64,
        dtype=jnp.float32,
        remat=False,
    )
    base.update(overrides)
    return TransformerConfig(**base)
