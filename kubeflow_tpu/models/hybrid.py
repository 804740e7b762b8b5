"""A decoder whose layers differ: linear-attention (KDA), latent
attention (MLA) and sparse latent attention (DSA: MLA over the positions
a learned indexer keeps) mixers by a per-layer pattern, a dense SwiGLU
MLP in the leading layers and a sigmoid-routed expert MLP with a shared
expert after them, an untied output head. Served through the same
``models/decode.py`` / ``DecodeEngine`` path as :class:`Transformer`.

``layer_types`` says, entry by entry, what a layer is. ``"kda"``,
``"mla"`` and ``"dsa"`` are a mixer and then an MLP, each under its own
norm and residual. ``"ssm"`` (a Mamba-2 state-space mixer), ``"gqa"``
(grouped-query attention with no rotary embedding) and ``"moe"`` (the
routed MLP) are blocks of ONE sublayer under one norm and one residual.

It exists in decode mode only: every apply reads and writes the ``cache``
collection, whose leaves the model declares
(``HybridConfig.cache_leaves``; the engine takes each leaf's batch axis
and idle value from there, never from its rank):

    positions     (B,)                       tokens each row holds
    latent        (L_mla + L_dsa, B, S_max, W)  a token's normalised kv
                                             latent (r) | rotated shared
                                             rope key (p) | with qk-norm,
                                             1 / rms of each head's key
                                             (H) | zeros up to W, the next
                                             multiple of 128 lanes
    index_k       (L_dsa, B, S_max, d_index) a token's index key
    kda_state     (L_kda, B, H, dk, dv) f32  the delta-rule state
    kda_conv      (L_kda, B, K - 1, 3 H dk)  last conv inputs of q | k | v
    ssm_state     (L_ssm, B, Hs / k, N, k P) f32  the state-space state,
                                             transposed, k heads a lane
                                             tile (``ops/ssm.pack_state``)
    ssm_conv      (L_ssm, B, K - 1, Hs P + 2 G N)  last conv inputs of
                                             x | B | C
    k, v          (L_gqa, B, S_max, KH Dh)   a token's keys / values, the
                                             KV heads merged on the last
                                             axis (the dense decoder's leaf)

(a leaf exists only where some layer needs it). A DSA layer reads
``index_k`` as far as the row has grown and attends, of the ``latent``'s
positions, to the ``index_topk`` it selects for each query.

A recurrent state cannot be pulled back the way ``positions`` can, so
``true_len`` reaches the mixers: past a row's own length a multi-token
apply leaves ``kda_state`` / ``ssm_state`` and the conv tails as they
were.

The layers are few and unlike, so they are unrolled, not scanned; each
reads and writes its own ``[index]`` slice of the stacked leaves, which
travel through the engine's K-step scan as its carry.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.models.transformer import (
    CacheLeaf,
    RMSNorm,
    _merged_step_attention,
    _rotate,
    rope_tables,
)
from kubeflow_tpu.ops.attention import NEG_INF
from kubeflow_tpu.ops.dsa import index_scores, select_bias, sparse_attend
from kubeflow_tpu.ops.gmm import grouped_matmul
from kubeflow_tpu.ops.kda import kda_step
from kubeflow_tpu.ops.ssm import (
    pack_state,
    ssm_chunked,
    ssm_step,
    unpack_state,
)
from kubeflow_tpu.parallel.mesh import DEFAULT_RULES, AxisRules

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
MIXERS = ("kda", "mla", "dsa")       # a layer: this mixer, then an MLP
SUBLAYERS = ("ssm", "gqa", "moe")    # a block: this one sublayer
GQA_Q_BLOCK = 512    # query rows a fresh prefill attends at once


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 1024            # rows of the vocabulary held here
    d_model: int = 64
    n_heads: int = 4
    head_dim: int = 16                # KDA's dk = dv; a field, not d / H
    layer_types: Tuple[str, ...] = ("kda", "kda", "kda", "kda", "mla",
                                    "kda", "kda")
    first_k_dense: int = 1            # leading mixer layers whose MLP is dense
    norm_eps: float = 1e-6            # of the layer / block and final norms
    d_ff: int = 128                   # dense MLP width
    max_seq_len: int = 256
    # MLA ("mla" and "dsa" layers)
    kv_lora_rank: int = 32
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    q_lora_rank: int = 0              # 0: a full-rank query projection
    use_qk_norm: bool = True          # RMSNorm over each head's q and k
    head_gate: bool = True            # sigmoid gate a head on the output
    # YaRN (rope_factor 1: plain rope): the long wavelengths stretched by
    # the factor, and mscale ** 2 in the softmax scale
    rope_factor: float = 1.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # the indexer of a "dsa" layer: a query attends to the index_topk
    # cached positions that score highest
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # a prompt longer than this is admitted by repeating ONE program of
    # this many tokens against the row (0: one program a prompt bucket)
    prefill_chunk: int = 0
    # "gqa" blocks: n_heads query heads of head_dim over this many KV heads
    n_kv_heads: int = 0
    # "ssm" blocks: H heads of P, a state of N a head, B / C a group
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # KDA (the conv's taps are the "ssm" blocks' too)
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    # routed MLP: the router scores all ``n_experts``; this chip holds
    # ``experts_held`` = (lo, n), the contiguous range [lo, lo + n), and
    # computes the part of the result those give (None: all of them)
    n_experts: int = 16
    experts_per_token: int = 2
    n_group: int = 4
    topk_group: int = 2
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    d_expert: int = 32                # published, and stored at that
    d_shared: int = 32
    # "swiglu": down(silu(gate x) * up x), three matrices an expert;
    # "relu2": down(relu(up x) ** 2), two
    expert_act: str = "swiglu"
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16         # activations
    param_dtype: Any = jnp.float32
    rules: AxisRules = DEFAULT_RULES

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def latent_width(self) -> int:
        """A cached token of an MLA layer: the latent, the shared rope
        key and, with qk-norm, the per-head key scales, padded to whole
        128-lane tiles (the chip lays a leaf whose last axis does not
        fill the lanes out otherwise than the step reads it, and re-lays
        it each round)."""
        scales = self.n_heads if self.use_qk_norm else 0
        return -(-(self.kv_lora_rank + self.qk_rope_dim + scales)
                 // 128) * 128

    @property
    def expert_width(self) -> int:
        """The columns a routed expert is STORED at: ``d_expert``, the
        published width. (Under ``jax.lax.ragged_dot`` 1856 columns were
        stored at 2048, the fill zero, for the TPU compiler's tiles;
        ``ops/gmm.py`` takes a block that spans a whole axis, whatever
        its length, and reads the tensor as the chip lays it out:
        PERF.md, PRs 35 and 36.)"""
        return self.d_expert

    @property
    def n_kda(self) -> int:
        return self.layer_types.count("kda")

    @property
    def n_mla(self) -> int:
        return self.layer_types.count("mla")

    @property
    def n_dsa(self) -> int:
        return self.layer_types.count("dsa")

    @property
    def n_ssm(self) -> int:
        return self.layer_types.count("ssm")

    @property
    def n_gqa(self) -> int:
        return self.layer_types.count("gqa")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_pack(self) -> int:
        """Heads of one group that lie side by side on the lanes of the
        ``ssm_state`` leaf (``ops/ssm.py:pack_state``): as many as fill
        128 lanes, and a whole number of packs a group."""
        per_group = self.ssm_heads // self.ssm_groups
        return max(k for k in range(1, per_group + 1)
                   if per_group % k == 0
                   and (k == 1 or k * self.ssm_head_dim <= 128))

    @property
    def ssm_conv_width(self) -> int:
        """The channels the short conv runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def rope_mscale(self) -> float:
        if self.rope_factor <= 1.0:
            return 1.0
        return 0.1 * self.rope_mscale_all_dim * math.log(
            self.rope_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        return ((self.qk_nope_dim + self.qk_rope_dim) ** -0.5
                * self.rope_mscale ** 2)

    @property
    def n_moe(self) -> int:
        """Routed MLPs: the "moe" blocks and the mixer layers past the
        leading dense ones."""
        return sum(kind == "moe" or (kind in MIXERS
                                     and i >= self.first_k_dense)
                   for i, kind in enumerate(self.layer_types))

    @property
    def has_recurrent_state(self) -> bool:
        """A per-row state that no position indexes (KDA's, a
        state-space block's): such a cache cannot be paged, sliced at a
        prefix or rolled back, so the paths that do those refuse the
        model, and a multi-token apply is given each row's length."""
        return any(name.endswith("_state") for name in self.cache_leaves(1))

    def decoder(self) -> "HybridDecoder":
        """The decode-mode module that ``models/decode.py`` applies."""
        return HybridDecoder(self)

    def cache_leaves(self, batch: int) -> dict:
        H, dk, S = self.n_heads, self.head_dim, self.max_seq_len
        leaves = {"positions": CacheLeaf((batch,), jnp.int32, 0)}
        if self.n_mla + self.n_dsa:
            leaves["latent"] = CacheLeaf(
                (self.n_mla + self.n_dsa, batch, S, self.latent_width),
                self.dtype, 1)
        if self.n_dsa:
            leaves["index_k"] = CacheLeaf(
                (self.n_dsa, batch, S, self.index_head_dim), self.dtype, 1)
        if self.n_kda:
            leaves["kda_state"] = CacheLeaf((self.n_kda, batch, H, dk, dk),
                                            jnp.float32, 1)
            leaves["kda_conv"] = CacheLeaf(
                (self.n_kda, batch, self.conv_kernel - 1, 3 * H * dk),
                self.dtype, 1)
        if self.n_ssm:
            k = self.ssm_pack
            leaves["ssm_state"] = CacheLeaf(
                (self.n_ssm, batch, self.ssm_heads // k, self.ssm_state,
                 k * self.ssm_head_dim), jnp.float32, 1)
            leaves["ssm_conv"] = CacheLeaf(
                (self.n_ssm, batch, self.conv_kernel - 1,
                 self.ssm_conv_width), self.dtype, 1)
        if self.n_gqa:
            # the dense decoder's leaf: a position's KV heads merged on
            # the last axis (``TransformerConfig.cache_leaves`` has why)
            kv = CacheLeaf((self.n_gqa, batch, S, self.n_kv_heads * dk),
                           self.dtype, 1, heads_axis=3, head_width=dk)
            leaves["k"] = leaves["v"] = kv
        return leaves

    def validate(self) -> None:
        if set(self.layer_types) - set(MIXERS + SUBLAYERS):
            raise ValueError(f"unknown layer kind in {self.layer_types!r}")
        if self.n_gqa and (self.n_kv_heads < 1
                           or self.n_heads % self.n_kv_heads):
            raise ValueError("a gqa block needs n_kv_heads that divide "
                             "n_heads")
        if self.n_ssm and (min(self.ssm_heads, self.ssm_head_dim,
                               self.ssm_state, self.ssm_groups) < 1
                           or self.ssm_heads % self.ssm_groups):
            raise ValueError("an ssm block needs ssm_heads, ssm_head_dim, "
                             "ssm_state and ssm_groups that divide the "
                             "heads")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert_act {self.expert_act!r}")
        if self.n_dsa and not (
                self.q_lora_rank and self.index_n_heads
                and self.index_topk and self.index_head_dim
                and self.index_head_dim % 2 == 0
                and self.qk_rope_dim <= self.index_head_dim
                and not self.use_qk_norm):
            raise ValueError(
                "a dsa layer needs q_lora_rank (the indexer reads the "
                "query's latent), index_n_heads, index_topk, an "
                "index_head_dim that holds the rope dims, and no qk-norm "
                "(gathered rows carry no key scales)")
        lo, n = self.held
        if lo < 0 or n < 1 or lo + n > self.n_experts:
            raise ValueError(f"experts_held {self.held} outside the "
                             f"router's {self.n_experts} outputs")
        if -self.kda_lower_bound * (SUB - 1) > MAX_EXPONENT:
            raise ValueError("kda_lower_bound too low for the chunk-wise "
                             "form's float32 decay products")
        if self.n_experts % self.n_group or self.qk_rope_dim % 2:
            raise ValueError("n_group must divide n_experts and the rope "
                             "dims be even")


def stored_expert(w, c: HybridConfig, axis: int):
    """A routed expert tensor at its published width ``d_expert`` along
    ``axis`` -> as ``RoutedMlp`` stores it, which is as it comes. What a
    loader of published weights hands its tensors through: the width is
    checked here, and a store that differs from the published width
    would be made here."""
    if w.shape[axis] != c.d_expert:
        raise ValueError(f"axis {axis} of {w.shape} is not d_expert "
                         f"{c.d_expert}")
    return w


def _dense(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def _swiglu(x, w_gate, w_up, w_down, dtype):
    h = jax.nn.silu(_dense(x, w_gate, dtype)) * _dense(x, w_up, dtype)
    return _dense(h, w_down, dtype).astype(dtype)


def _relu2(x, w_up, w_down, dtype):
    h = jnp.square(jax.nn.relu(_dense(x, w_up, dtype)))
    return _dense(h, w_down, dtype).astype(dtype)


def _conv_tail(seq, taps: int, T: int, lens):
    """The last ``taps - 1`` real inputs of each row of ``seq`` = [the
    cached tail | this call's T inputs]: inputs len-3 .. len-1 of a row
    sit at len .. len+2."""
    if lens is None:
        return seq[:, T:]
    return jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
        row, n, taps - 1, 0))(seq, lens)


# -- KDA -------------------------------------------------------------------------

def kda_recurrent_step(state, q, k, v, a, beta):
    """One token of the delta rule with per-channel decay. ``state``
    (B, H, dk, dv) f32; q, k, a (B, H, dk); v (B, H, dv); beta (B, H).
    Returns (state, o (B, H, dv))."""
    state = state * jnp.exp(a)[..., None]
    # S~^T [k | q] in one pass over the state: o = S^T q = S~^T q + u (k.q)
    kq = jnp.stack([k, q], axis=-2)                          # (B, H, 2, dk)
    r = jnp.einsum("bhnk,bhkv->bhnv", kq, state, precision=HIGHEST)
    u = beta[..., None] * (v - r[..., 0, :])
    state = state + k[..., None] * u[..., None, :]
    o = r[..., 1, :] + u * jnp.sum(k * q, -1, keepdims=True)
    return state, o


SUB = 16          # tokens a decay reference serves
MAX_EXPONENT = 80.0   # exp() of it is finite in float32


def kda_chunked(state, q, k, v, a, beta, chunk: int):
    """The same recurrence over T tokens, chunk by chunk (the WY / UT
    form): inside a chunk of C tokens with cumulative log-decay G,

        (I + A) U = beta (V - (K exp G) S0),  A[t,s] = beta_t sum_c
                    k_tc k_sc exp(G_tc - G_sc)  for s < t
        O  = (Q exp G) S0 + (QK masked s <= t, same decay) U
        S' = diag(exp G_C) S0 + (K exp(G_C - G))^T U

    With decays down to exp(-5) a token, exp(-G) alone overflows
    float32, so the decayed products are taken against a reference: rows
    t of a sub-block of ``SUB`` tokens that starts at token r use
    (k_t exp(G_t - G_r)) . (k_s exp(G_r - G_s)); the first exponent is
    <= 0, the second at most (SUB - 1) |lower bound| for the s <= t that
    count (``HybridConfig.validate`` holds that under MAX_EXPONENT).
    q, k, a (B, T, H, dk); v (B, T, H, dv); beta (B, T, H); all f32. A
    token with beta = 0 and a = 0 leaves the state as it was.
    Returns (state, o (B, T, H, dv))."""
    B, T, H, dk = q.shape
    pad = -T % chunk
    if pad:
        q, k, v, a = (jnp.pad(y, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for y in (q, k, v, a))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // chunk
    sub = SUB if chunk % SUB == 0 else chunk
    nb = chunk // sub

    def chunks(y):   # (B, T, H, ...) -> (nc, B, H, C, ...)
        y = y.reshape((B, nc, chunk) + y.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(y, 1, 0), 3, 2)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=HIGHEST)  # noqa: E731

    def body(s0, xs):
        qc, kc, vc, ac, bc = xs                # (B, H, C, dk) ... (B, H, C)
        g = jnp.cumsum(ac, axis=-2)
        blocks = lambda y: y.reshape(B, H, nb, sub, dk)  # noqa: E731
        g_ref = blocks(g)[..., :1, :]                      # (B, H, nb, 1, dk)
        rows = jnp.exp(blocks(g) - g_ref)                  # t against its r
        # every s against r; past MAX_EXPONENT lie only pairs s > t, which
        # the masks below drop
        cols = kc[:, :, None] * jnp.exp(jnp.minimum(
            g_ref - g[:, :, None], MAX_EXPONENT))          # (B, H, nb, C, dk)
        kk = mm("bhitc,bhisc->bhits", blocks(kc) * rows, cols)
        qk = mm("bhitc,bhisc->bhits", blocks(qc) * rows, cols)
        kk = jnp.where(strict, kk.reshape(B, H, chunk, chunk), 0.0)
        qk = jnp.where(tri, qk.reshape(B, H, chunk, chunk), 0.0)
        eg = jnp.exp(g)
        rhs = bc[..., None] * (vc - mm("bhtk,bhkv->bhtv", kc * eg, s0))
        u = jax.lax.linalg.triangular_solve(
            eye + bc[..., None] * kk, rhs, left_side=True, lower=True,
            unit_diagonal=True)
        o = mm("bhtk,bhkv->bhtv", qc * eg, s0) + mm("bhts,bhsv->bhtv", qk, u)
        g_end = g[..., -1:, :]
        s1 = (jnp.exp(g_end[..., 0, :])[..., None] * s0
              + mm("bhsk,bhsv->bhkv", kc * jnp.exp(g_end - g), u))
        return s1, o

    state, o = jax.lax.scan(
        body, state, (chunks(q), chunks(k), chunks(v), chunks(a),
                      chunks(beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)       # (B, nc, C, H, dv)
    return state, o.reshape(B, nc * chunk, H, -1)[:, :T]


class KdaMixer(nn.Module):
    config: HybridConfig

    @nn.compact
    def __call__(self, x, cache, index: int, lens=None):
        """x (B, T, D); ``cache`` the dict of stacked leaves, ``index``
        this layer's place among the KDA layers; ``lens`` (B,) the real
        tokens of each row (None: all T). Returns (out, cache)."""
        c = self.config
        B, T, D = x.shape
        H, dk = c.n_heads, c.head_dim
        C, taps = H * dk, c.conv_kernel
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_qkv = self.param("qkv_proj", init, (D, 3 * C), c.param_dtype)
        w_decay = self.param("decay_proj", init, (D, C), c.param_dtype)
        w_gate = self.param("gate_proj", init, (D, C), c.param_dtype)
        w_beta = self.param("beta_proj", init, (D, H), c.param_dtype)
        w_o = self.param("o_proj", init, (C, D), c.param_dtype)
        w_conv = self.param("conv", nn.initializers.normal(0.4),
                            (taps, 3 * C), c.param_dtype)
        a_log = self.param("a_log", nn.initializers.zeros, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (C,),
                             jnp.float32)

        # short conv over [the cached tail | this call's inputs]
        pre = _dense(x, w_qkv, c.dtype).astype(c.dtype)        # (B, T, 3C)
        seq = jnp.concatenate([cache["kda_conv"][index], pre], axis=1)
        conv = sum(w_conv[j].astype(jnp.float32) * seq[:, j:j + T]
                   for j in range(taps))
        tail = _conv_tail(seq, taps, T, lens)
        q, k, v = (y.reshape(B, T, H, dk) for y in jnp.split(
            jax.nn.silu(conv.astype(jnp.float32)), 3, axis=-1))
        q = _l2_norm(q) * dk ** -0.5
        k = _l2_norm(k)
        gate = (_dense(x, w_decay, c.dtype) + dt_bias).reshape(B, T, H, dk)
        a = c.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * gate)
        beta = jax.nn.sigmoid(_dense(x, w_beta, c.dtype))       # (B, T, H)
        if lens is not None:
            live = jnp.arange(T)[None, :] < lens[:, None]
            a = jnp.where(live[..., None, None], a, 0.0)
            beta = jnp.where(live[..., None], beta, 0.0)

        if T == 1:
            # the kernel updates layer ``index`` of the stacked leaf in place
            with jax.named_scope("kda.step"):
                states, o = kda_step(cache["kda_state"], index, q[:, 0],
                                     k[:, 0], v[:, 0], a[:, 0], beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("kda.prefill"):
                state, o = kda_chunked(cache["kda_state"][index], q, k, v,
                                       a, beta, c.kda_chunk)
            states = cache["kda_state"].at[index].set(state)
        cache = dict(cache, kda_state=states,
                     kda_conv=cache["kda_conv"].at[index].set(tail))

        o = RMSNorm(param_dtype=c.param_dtype, name="o_norm")(o)
        o = jax.nn.sigmoid(_dense(x, w_gate, c.dtype)).reshape(o.shape) * o
        out = _dense(o.reshape(B, T, C), w_o, c.dtype)
        return out.astype(c.dtype), cache


# -- MLA ---------------------------------------------------------------------------

def mla_rope_tables(c: HybridConfig, dim: int):
    """(sin, cos) over ``max_seq_len`` positions for ``dim`` rotated
    dims: plain rope, or YaRN's blend where ``rope_factor`` > 1 (a
    frequency that turns more than ``beta_fast`` times over the original
    context stays, one that turns fewer than ``beta_slow`` times is
    divided by the factor, a linear ramp between)."""
    if c.rope_factor <= 1.0:
        return rope_tables(c.max_seq_len, dim, c.rope_theta)

    def turns_at(n_turns):     # the dim whose wavelength fits n_turns times
        return (dim * math.log(c.rope_original_len / (n_turns * 2 * math.pi))
                / (2 * math.log(c.rope_theta)))

    low = max(math.floor(turns_at(c.rope_beta_fast)), 0)
    high = min(math.ceil(turns_at(c.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    freqs = 1.0 / (c.rope_theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freqs = freqs / c.rope_factor * ramp + freqs * (1.0 - ramp)
    angles = jnp.outer(jnp.arange(c.max_seq_len, dtype=jnp.float32), freqs)
    return jnp.sin(angles), jnp.cos(angles)


class MlaAttention(nn.Module):
    """Latent attention. What a model's config declares decides the
    pieces: a full-rank or a low-rank (``q_lora_rank``) query, qk-norm
    with its cached key scales or none, a head-wise output gate or none,
    plain or YaRN rope. ``sparse`` (a "dsa" layer) adds the indexer: each
    query attends to the ``index_topk`` cached positions its index scores
    rank highest (``ops/dsa.py``: scores, selection and attend are three
    kernels)."""

    config: HybridConfig
    sparse: bool = False

    @nn.compact
    def __call__(self, x, cache, index: int, fresh: bool, k_index: int = 0):
        """x (B, T, D). ``fresh`` (static): the rows' caches are empty,
        so the T tokens attend among themselves in the expanded form;
        otherwise each row's tokens are written at its own position and
        attend to the cached row in the absorbed form (``kv_b`` folded
        into the query and the output), which reads nothing but the
        latent. A sparse layer takes the second form always (an empty
        row scores as one); ``k_index`` is its place among the rows of
        ``index_k``. Returns (out, cache, (scored, selected) | None)."""
        c = self.config
        B, T, D = x.shape
        H, r = c.n_heads, c.kv_lora_rank
        n, p, vd = c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_kva = self.param("kv_a_proj", init, (D, r + p), c.param_dtype)
        w_kvb = self.param("kv_b_proj", nn.initializers.normal(r ** -0.5),
                           (r, H, n + vd), c.param_dtype)
        w_o = self.param("o_proj", init, (H, vd, D), c.param_dtype)
        w_kn = None
        if c.use_qk_norm:
            w_kn = self.param("k_norm", nn.initializers.ones, (n + p,),
                              c.param_dtype).astype(jnp.float32)
        eps = RMSNorm.eps
        ein = lambda spec, a, b: jnp.einsum(  # noqa: E731
            spec, a.astype(c.dtype), b.astype(c.dtype),
            preferred_element_type=jnp.float32)

        pos = cache["positions"]                                    # (B,)
        q_pos = pos[:, None] + jnp.arange(T)[None, :]               # (B, T)
        sin_t, cos_t = mla_rope_tables(c, p)
        # an idle row's position runs past the table: clip, its output is
        # read by nobody
        sin = jnp.take(sin_t, q_pos, axis=0, mode="clip")[:, :, None, :]
        cos = jnp.take(cos_t, q_pos, axis=0, mode="clip")[:, :, None, :]

        # -- the query
        c_q = None
        if c.q_lora_rank:
            w_qa = self.param("q_a_proj", init, (D, c.q_lora_rank),
                              c.param_dtype)
            w_qb = self.param(
                "q_b_proj", nn.initializers.normal(c.q_lora_rank ** -0.5),
                (c.q_lora_rank, H, n + p), c.param_dtype)
            c_q = RMSNorm(param_dtype=c.param_dtype, name="q_a_norm")(
                _dense(x, w_qa, c.dtype)).astype(c.dtype)
            q = ein("btr,rhk->bthk", c_q, w_qb)
        else:
            w_q = self.param("q_proj", init, (D, H, n + p), c.param_dtype)
            q = ein("btd,dhk->bthk", x, w_q)
        if c.use_qk_norm:
            q = RMSNorm(param_dtype=c.param_dtype, name="q_norm")(q)
        q_nope, q_rope = q[..., :n], _rotate(q[..., n:], sin, cos)

        # -- the token's cached row
        kva = _dense(x, w_kva, c.dtype)
        lat = RMSNorm(param_dtype=c.param_dtype, name="kv_norm")(
            kva[..., :r]).astype(c.dtype)                        # (B, T, r)
        k_r = kva[..., r:]                                       # (B, T, p)
        W = c.latent_width
        if c.use_qk_norm:
            k_nope = ein("btr,rhk->bthk", lat, w_kvb[..., :n])   # new tokens
            inv_rms = jax.lax.rsqrt(
                (jnp.sum(jnp.square(k_nope), -1)
                 + jnp.sum(jnp.square(k_r), -1)[..., None]) / (n + p) + eps)
            k_rot = _rotate((k_r * w_kn[n:])[:, :, None, :], sin, cos)[:, :, 0]
            row = jnp.concatenate(
                [lat, k_rot.astype(c.dtype), inv_rms.astype(c.dtype),
                 jnp.zeros((B, T, W - r - p - H), c.dtype)], -1)
        else:
            k_rot = _rotate(k_r[:, :, None, :], sin, cos)[:, :, 0]
            row = jnp.concatenate(
                [lat, k_rot.astype(c.dtype),
                 jnp.zeros((B, T, W - r - p), c.dtype)], -1)
        scale = c.softmax_scale
        counts = None

        def absorbed_query():
            q_lat = ein("bthk,rhk->bthr",
                        q_nope * w_kn[:n] if c.use_qk_norm else q_nope,
                        w_kvb[..., :n])
            # zeros against the scale and pad columns: the products
            # run over whole cached rows, never over a slice of them
            return jnp.concatenate(
                [q_lat, q_rope, jnp.zeros((B, T, H, W - r - p))], -1)

        if self.sparse:
            rows = jnp.arange(B)[:, None]
            latent = cache["latent"].at[index, rows, q_pos].set(row)
            index_k, kept, counts = self._select(
                x, c_q, cache["index_k"], k_index, pos, q_pos, sin, cos)
            cache = dict(cache, index_k=index_k)
            with jax.named_scope("dsa.attend"):
                o_lat = sparse_attend(absorbed_query(), kept, latent, index,
                                      pos, scale=scale, values=r)
                o = ein("bqhr,rhv->bqhv", o_lat, w_kvb[..., n:])
        else:
            with jax.named_scope("mla.attend"):
                if fresh:
                    # rows start at 0 and share the slice
                    at = (index, 0, 0, 0)
                    latent = jax.lax.dynamic_update_slice(
                        cache["latent"], row[None], at)
                    if c.use_qk_norm:
                        k = jnp.concatenate(
                            [k_nope * w_kn[:n],
                             jnp.broadcast_to(k_rot[:, :, None, :],
                                              (B, T, H, p))],
                            -1) * inv_rms[..., None]
                    else:
                        k = jnp.concatenate(
                            [ein("btr,rhk->bthk", lat, w_kvb[..., :n]),
                             jnp.broadcast_to(k_rot[:, :, None, :],
                                              (B, T, H, p))], -1)
                    v = ein("btr,rhv->bthv", lat, w_kvb[..., n:])
                    qf = jnp.concatenate([q_nope, q_rope], -1)
                    s = ein("bqhd,bkhd->bhqk", qf, k) * scale
                    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
                    prob = jax.nn.softmax(jnp.where(mask, s, NEG_INF),
                                          axis=-1)
                    o = ein("bhqk,bkhv->bqhv", prob, v)
                else:
                    rows = jnp.arange(B)[:, None]
                    latent = cache["latent"].at[index, rows, q_pos].set(row)
                    s = ein("bqhl,bkl->bhqk", absorbed_query(), latent[index])
                    if c.use_qk_norm:
                        k_scale = latent[index][..., r + p:r + p + H]
                        s = s * scale * jnp.swapaxes(
                            k_scale, 1, 2)[:, :, None, :].astype(jnp.float32)
                    else:
                        s = s * scale
                    mask = (jnp.arange(c.max_seq_len)[None, None, :]
                            <= q_pos[:, :, None])                # (B, T, S)
                    prob = jax.nn.softmax(
                        jnp.where(mask[:, None], s, NEG_INF), axis=-1)
                    o_lat = ein("bhqk,bkl->bqhl", prob, latent[index])[..., :r]
                    o = ein("bqhr,rhv->bqhv", o_lat, w_kvb[..., n:])
        cache = dict(cache, latent=latent)
        if c.head_gate:
            w_gate = self.param("gate_proj", init, (D, H), c.param_dtype)
            o = jax.nn.sigmoid(_dense(x, w_gate, c.dtype))[..., None] * o
        out = ein("bqhv,hvd->bqd", o, w_o)
        return out.astype(c.dtype), cache, counts

    def _select(self, x, c_q, index_k, k_index: int, pos, q_pos, sin, cos):
        """The indexer: write the tokens' index keys at their positions,
        score every cached position of the row for every query, keep the
        ``index_topk`` highest (ties to the lower position). Returns
        (index_k, kept (B, T, S) float32, 0 at a query's kept positions
        and ``-inf`` elsewhere, (scored, selected)): the counts are
        summed over the rows. ``sin`` / ``cos`` (B, T, 1, p / 2) are the
        mixer's own angles: the indexer's rope turns its FIRST p dims."""
        c = self.config
        B, T, D = x.shape
        J, d, p = c.index_n_heads, c.index_head_dim, c.qk_rope_dim
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_q = self.param("index_q_proj",
                         nn.initializers.normal(c.q_lora_rank ** -0.5),
                         (c.q_lora_rank, J, d), c.param_dtype)
        w_k = self.param("index_k_proj", init, (D, d), c.param_dtype)
        w_w = self.param("index_w_proj", init, (D, J), c.param_dtype)
        ln_scale = self.param("index_k_norm_scale", nn.initializers.ones,
                              (d,), c.param_dtype)
        ln_bias = self.param("index_k_norm_bias", nn.initializers.zeros,
                             (d,), c.param_dtype)

        def turned(y, sin, cos):
            return jnp.concatenate(
                [_rotate(y[..., :p], sin, cos), y[..., p:]], -1)

        with jax.named_scope("dsa.index"):
            k = _dense(x, w_k, c.dtype)                          # (B, T, d)
            mean = jnp.mean(k, -1, keepdims=True)
            k = (k - mean) * jax.lax.rsqrt(
                jnp.mean(jnp.square(k - mean), -1, keepdims=True)
                + RMSNorm.eps)
            k = k * ln_scale.astype(jnp.float32) + ln_bias.astype(jnp.float32)
            k = turned(k, sin[:, :, 0], cos[:, :, 0]).astype(c.dtype)
            rows = jnp.arange(B)[:, None]
            index_k = index_k.at[k_index, rows, q_pos].set(k)
            q = jnp.einsum("btr,rjd->btjd", c_q, w_q.astype(c.dtype),
                           preferred_element_type=jnp.float32)
            q = turned(q, sin, cos)
            w = _dense(x, w_w, c.dtype) * (J ** -0.5 * d ** -0.5)
            scores = index_scores(q, w, index_k, k_index, pos)   # (B, T, S)
        K = min(c.index_topk, c.max_seq_len)
        with jax.named_scope("dsa.select"):
            kept = select_bias(scores, K)
        # for whoever asks (mutable "intermediates"): a test, a probe
        self.sow("intermediates", "index_scores", scores)
        self.sow("intermediates", "kept", kept)
        held = jnp.minimum(q_pos + 1, c.max_seq_len)
        counts = (jnp.sum(held), jnp.sum(jnp.minimum(held, K)))
        return index_k, kept, counts


# -- state-space (Mamba-2) ---------------------------------------------------------

class SsmMixer(nn.Module):
    """[z | xBC | dt] = W_in h; a short causal conv and SiLU over xBC = [x
    | B | C]; the selective state update (``ops/ssm.py``) on H heads of
    P with B and C shared by a group's heads; the output gated by
    silu(z), RMS-normalised within each group's channels, and projected
    out."""

    config: HybridConfig

    @nn.compact
    def __call__(self, x, cache, index: int, lens=None):
        """x (B, T, D); ``index`` this block's place among the "ssm"
        blocks; ``lens`` (B,) the real tokens of each row (None: all T).
        Returns (out, cache)."""
        c = self.config
        B, T, D = x.shape
        H, P, N, G = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
        I, W, taps = c.ssm_inner, c.ssm_conv_width, c.conv_kernel
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_in = self.param("in_proj", init, (D, I + W + H), c.param_dtype)
        w_out = self.param("out_proj", nn.initializers.normal(I ** -0.5),
                           (I, D), c.param_dtype)
        w_conv = self.param("conv", nn.initializers.normal(0.4), (taps, W),
                            c.param_dtype)
        b_conv = self.param("conv_bias", nn.initializers.zeros, (W,),
                            c.param_dtype)
        a_log = self.param("a_log", nn.initializers.zeros, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,),
                             jnp.float32)
        d_skip = self.param("d", nn.initializers.ones, (H,), jnp.float32)
        w_norm = self.param("norm", nn.initializers.ones, (I,),
                            c.param_dtype)

        zxd = _dense(x, w_in, c.dtype)                  # (B, T, I + W + H)
        z, dt = zxd[..., :I], zxd[..., I + W:]
        with jax.named_scope("ssm.conv"):
            # over [the cached tail | this call's inputs]
            seq = jnp.concatenate(
                [cache["ssm_conv"][index],
                 zxd[..., I:I + W].astype(c.dtype)], axis=1)
            conv = sum(w_conv[j].astype(jnp.float32) * seq[:, j:j + T]
                       for j in range(taps)) + b_conv.astype(jnp.float32)
            tail = _conv_tail(seq, taps, T, lens)
            xbc = jax.nn.silu(conv)
        xs = xbc[..., :I].reshape(B, T, H, P)
        Bm, Cm = (y.reshape(B, T, G, N)
                  for y in jnp.split(xbc[..., I:], 2, axis=-1))
        dt = jax.nn.softplus(dt + dt_bias)                      # (B, T, H)
        A = -jnp.exp(a_log)

        if T == 1:
            # the kernel updates block ``index`` of the stacked leaf in place
            with jax.named_scope("ssm.step"):
                states, y = ssm_step(cache["ssm_state"], index, xs[:, 0],
                                     dt[:, 0], A, Bm[:, 0], Cm[:, 0], d_skip)
                y = y[:, None]
        else:
            with jax.named_scope("ssm.chunk"):
                state, y = ssm_chunked(
                    unpack_state(cache["ssm_state"][index], c.ssm_pack), xs,
                    dt, A, Bm, Cm, d_skip, c.ssm_chunk, lens)
            states = cache["ssm_state"].at[index].set(
                pack_state(state, c.ssm_pack))
        cache = dict(cache, ssm_state=states,
                     ssm_conv=cache["ssm_conv"].at[index].set(tail))

        y = (y.reshape(B, T, I) * jax.nn.silu(z)).reshape(B, T, G, I // G)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + c.norm_eps)
        y = y.reshape(B, T, I) * w_norm.astype(jnp.float32)
        return _dense(y, w_out, c.dtype).astype(c.dtype), cache


# -- grouped-query attention, no rotary embedding ----------------------------------

class GqaAttention(nn.Module):
    """Causal softmax attention of ``n_heads`` query heads over
    ``n_kv_heads`` KV heads, positions entering by the mask alone. It
    keeps the dense decoder's ``k`` / ``v`` leaves, KV heads merged on
    the last axis, and one token attends against them as they lie
    (``transformer._merged_step_attention``)."""

    config: HybridConfig

    @nn.compact
    def __call__(self, x, cache, index: int, fresh: bool):
        """x (B, T, D). ``fresh`` (static): the rows' caches are empty,
        so the T tokens attend among themselves; otherwise each row's
        tokens are written at its own position and attend to the cached
        row. Returns (out, cache)."""
        c = self.config
        B, T, D = x.shape
        H, KH, Dh, S = c.n_heads, c.n_kv_heads, c.head_dim, c.max_seq_len
        R = H // KH
        init = nn.initializers.normal(stddev=D ** -0.5)
        w_q = self.param("q_proj", init, (D, H * Dh), c.param_dtype)
        w_k = self.param("k_proj", init, (D, KH * Dh), c.param_dtype)
        w_v = self.param("v_proj", init, (D, KH * Dh), c.param_dtype)
        w_o = self.param("o_proj", nn.initializers.normal((H * Dh) ** -0.5),
                         (H * Dh, D), c.param_dtype)
        # query head h reads KV head h // R: (KH, R) side by side
        q = _dense(x, w_q, c.dtype).astype(c.dtype).reshape(B, T, KH, R, Dh)
        k = _dense(x, w_k, c.dtype).astype(c.dtype)        # (B, T, KH Dh)
        v = _dense(x, w_v, c.dtype).astype(c.dtype)
        pos = cache["positions"]
        q_pos = pos[:, None] + jnp.arange(T)[None, :]               # (B, T)
        ein = lambda spec, a, b: jnp.einsum(  # noqa: E731
            spec, a, b, preferred_element_type=jnp.float32)

        def attend(q, k, v, mask):
            """q (B, Q, KH, R, Dh) against k, v (B, K, KH, Dh) under
            mask (B or 1, Q, K)."""
            s = ein("bqhrd,bkhd->bhrqk", q, k) * Dh ** -0.5
            p = jax.nn.softmax(jnp.where(mask[:, None, None], s, NEG_INF),
                               axis=-1).astype(c.dtype)
            return ein("bhrqk,bkhd->bqhrd", p, v).astype(c.dtype)

        with jax.named_scope("gqa.attend"):
            if fresh:
                # rows start at 0 and share the slice
                at = (index, 0, 0, 0)
                ck = jax.lax.dynamic_update_slice(cache["k"], k[None], at)
                cv = jax.lax.dynamic_update_slice(cache["v"], v[None], at)
                kh, vh = (y.reshape(B, T, KH, Dh) for y in (k, v))
                qb = min(T, GQA_Q_BLOCK)

                def block(i):
                    rows = i * qb + jnp.arange(qb)
                    mask = jnp.arange(T)[None, :] <= rows[:, None]
                    return attend(jax.lax.dynamic_slice_in_dim(
                        q, i * qb, qb, 1), kh, vh, mask[None])

                if T % qb:
                    o = attend(q, kh, vh, jnp.tril(
                        jnp.ones((T, T), bool))[None])
                else:
                    o = jnp.moveaxis(
                        jax.lax.map(block, jnp.arange(T // qb)), 0, 1)
                o = o.reshape(B, T, H * Dh)
            else:
                rows = jnp.arange(B)[:, None]
                ck = cache["k"].at[index, rows, q_pos].set(k)
                cv = cache["v"].at[index, rows, q_pos].set(v)
                mask = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]
                if T == 1:
                    o = _merged_step_attention(q[:, 0], ck[index], cv[index],
                                               mask[:, 0])
                else:
                    o = attend(q, ck[index].reshape(B, S, KH, Dh),
                               cv[index].reshape(B, S, KH, Dh), mask)
                o = o.reshape(B, T, H * Dh)
        out = _dense(o, w_o, c.dtype)
        return out.astype(c.dtype), dict(cache, k=ck, v=cv)


# -- routed MLP ------------------------------------------------------------------

def route(scores_logits, bias, c: HybridConfig):
    """Sigmoid scores, group-limited selection on score + bias (with
    more than one group), weights from the scores. (N, E) f32 -> (ids
    (N, K), weights (N, K))."""
    s = jax.nn.sigmoid(scores_logits)
    sel = s + bias
    E = sel.shape[-1]
    if c.n_group > 1:     # one group: every expert stands
        grouped = sel.reshape(-1, c.n_group, E // c.n_group)
        g_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, g_idx = jax.lax.top_k(g_score, c.topk_group)
        g_keep = jnp.sum(jax.nn.one_hot(g_idx, c.n_group, dtype=jnp.int32),
                         axis=1) > 0
        sel = jnp.where(jnp.repeat(g_keep, E // c.n_group, axis=-1), sel,
                        -jnp.inf)
    _, idx = jax.lax.top_k(sel, c.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, c.routed_scaling * w


class RoutedMlp(nn.Module):
    """Routes over all ``n_experts``, holds ``experts_held`` of them and
    computes what those add for the tokens routed to them, plus the
    shared expert. Tokens are grouped by expert (one sort, then
    ``ops/gmm.py``'s grouped matmul over the groups, one call a
    product): no token is dropped, the work follows the routed (token,
    expert) pairs held here, and an expert no pair reached is not read.
    An expert is stored at its published width ``d_expert``."""

    config: HybridConfig

    @nn.compact
    def __call__(self, x, live=None):
        """x (B, T, D); ``live`` (B, T) bool marks real tokens (pad
        tokens are routed nowhere). Returns (y, experts hit, pairs)."""
        c = self.config
        B, T, D = x.shape
        K, F = c.experts_per_token, c.d_expert
        lo, n = c.held
        init = nn.initializers.normal(stddev=D ** -0.5)

        w_router = self.param("router", init, (D, c.n_experts), jnp.float32)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (c.n_experts,), jnp.float32)
        gated = c.expert_act == "swiglu"     # relu2 experts have no gate
        if gated:
            w_gate = self.param("gate_proj", init, (n, D, F), c.param_dtype)
        w_up = self.param("up_proj", init, (n, D, F), c.param_dtype)
        w_down = self.param("down_proj", init, (n, F, D), c.param_dtype)
        sh = [self.param(f"shared_{name}", init, shape, c.param_dtype)
              for name, shape in (("gate", (D, c.d_shared)),
                                  ("up", (D, c.d_shared)),
                                  ("down", (c.d_shared, D)))
              if gated or name != "gate"]
        flat = x.reshape(B * T, D)
        N = B * T

        with jax.named_scope("moe.route"):
            idx, w = route(jnp.dot(flat.astype(jnp.float32), w_router,
                                   precision=HIGHEST), bias, c)
            local = idx - lo
            held = (local >= 0) & (local < n)
            if live is not None:
                held = held & live.reshape(N, 1)
            key = jnp.where(held, local, n).reshape(N * K)
            order = jnp.argsort(key)              # held pairs first, by expert
            sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
            hit = jnp.sum(sizes > 0).astype(jnp.int32)
            pairs = jnp.sum(sizes).astype(jnp.int32)

        with jax.named_scope("moe.experts"):
            xs = jnp.take(flat, order // K, axis=0).astype(c.dtype)
            rd = lambda a, b: grouped_matmul(a, b, sizes)  # noqa: E731
            if gated:
                h = jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up)
            else:
                h = jnp.square(jax.nn.relu(rd(xs, w_up)))
            ys = rd(h.astype(c.dtype), w_down)                   # (N K, D)
            back = jnp.take(ys, jnp.argsort(order), axis=0).reshape(N, K, D)
            y = jnp.sum(jnp.where(held[..., None],
                                  back * w[..., None], 0.0), axis=1)

        with jax.named_scope("moe.shared"):
            y = y.astype(c.dtype) + (_swiglu if gated else _relu2)(
                flat, *sh, c.dtype)
        return y.reshape(B, T, D), hit, pairs


class DenseMlp(nn.Module):
    config: HybridConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        D, F = c.d_model, c.d_ff
        init = nn.initializers.normal(stddev=D ** -0.5)
        ws = [self.param(name, init, shape, c.param_dtype)
              for name, shape in (("gate_proj", (D, F)), ("up_proj", (D, F)),
                                  ("down_proj", (F, D)))]
        return _swiglu(x, *ws, c.dtype)


# -- the decoder -----------------------------------------------------------------

class HybridLayer(nn.Module):
    config: HybridConfig
    mixer: str
    routed: bool

    @nn.compact
    def __call__(self, x, cache, index, lens, fresh: bool):
        """``index`` is the layer's place in the leaves of its kind, for
        a sparse layer (latent, index_k). Returns (x, cache, what the
        routed MLP counted | None, what the indexer counted | None)."""
        c = self.config
        h = RMSNorm(c.norm_eps, c.param_dtype, name="attn_norm")(x)
        picked = None
        if self.mixer == "kda":
            out, cache = KdaMixer(c, name="mixer")(h, cache, index, lens)
        elif self.mixer == "dsa":
            out, cache, picked = MlaAttention(c, sparse=True, name="mixer")(
                h, cache, index[0], fresh, index[1])
        else:
            out, cache, _ = MlaAttention(c, name="mixer")(h, cache, index,
                                                          fresh)
        x = x + out
        h = RMSNorm(c.norm_eps, c.param_dtype, name="mlp_norm")(x)
        if not self.routed:
            return x + DenseMlp(c, name="mlp")(h), cache, None, picked
        y, hit, pairs = RoutedMlp(c, name="mlp")(h, _live(x, lens))
        return x + y, cache, (hit, pairs), picked


def _live(x, lens):
    """(B, T) bool: a row's real tokens (None: all of them)."""
    if lens is None:
        return None
    return jnp.arange(x.shape[1])[None, :] < lens[:, None]


class HybridBlock(nn.Module):
    """One sublayer under one norm and one residual."""

    config: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, cache, index, lens, fresh: bool):
        """``index`` is the block's place in the leaves of its kind.
        Returns (x, cache, what a routed MLP counted | None)."""
        c = self.config
        h = RMSNorm(c.norm_eps, c.param_dtype, name="norm")(x)
        stat = None
        if self.kind == "ssm":
            out, cache = SsmMixer(c, name="mixer")(h, cache, index, lens)
        elif self.kind == "gqa":
            out, cache = GqaAttention(c, name="mixer")(h, cache, index,
                                                       fresh)
        else:
            out, *stat = RoutedMlp(c, name="mlp")(h, _live(x, lens))
        return x + out, cache, stat


class HybridDecoder(nn.Module):
    """tokens (B, T) -> logits (B, T, V) float32, through the cache."""

    config: HybridConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray,
                 true_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        c = self.config
        c.validate()
        B, T = tokens.shape
        # a cache made by this very apply is empty: the rows start at 0
        fresh = not self.has_variable("cache", "positions")
        leaves = {
            name: self.variable("cache", name, jnp.full, leaf.shape,
                                leaf.fill, leaf.dtype)
            for name, leaf in c.cache_leaves(B).items()}
        cache = {name: var.value for name, var in leaves.items()}
        lens = None
        if true_len is not None:
            lens = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (B,))

        embed = self.param("token_embed",
                           nn.initializers.normal(c.d_model ** -0.5),
                           (c.vocab_size, c.d_model), c.param_dtype)
        head = self.param("lm_head",
                          nn.initializers.normal(c.d_model ** -0.5),
                          (c.vocab_size, c.d_model), c.param_dtype)
        x = jnp.take(embed.astype(c.dtype), tokens, axis=0)
        # "mla" and "dsa" layers share the latent leaf, in layer order
        seen = dict.fromkeys(MIXERS + SUBLAYERS, 0)
        stats, picked = [], []
        for i, mixer in enumerate(c.layer_types):
            latent = seen["mla"] + seen["dsa"]
            index = {"mla": latent, "dsa": (latent, seen["dsa"])}.get(
                mixer, seen[mixer])
            if mixer in SUBLAYERS:
                kept = None
                x, cache, stat = HybridBlock(c, mixer, name=f"layer_{i}")(
                    x, cache, index, lens, fresh)
            else:
                x, cache, stat, kept = HybridLayer(
                    c, mixer, routed=i >= c.first_k_dense,
                    name=f"layer_{i}")(x, cache, index, lens, fresh)
            seen[mixer] += 1
            if stat is not None:
                stats.append(stat)
            if kept is not None:
                picked.append(kept)
        cache["positions"] = cache["positions"] + (
            T if lens is None else lens)
        for name, var in leaves.items():
            var.value = cache[name]
        if stats:
            self.sow("moe_stats", "experts_hit",
                     jnp.stack([s[0] for s in stats]))
            self.sow("moe_stats", "routed_pairs",
                     jnp.stack([s[1] for s in stats]))
        if picked:
            self.sow("moe_stats", "index_scored",
                     jnp.stack([s[0] for s in picked]))
            self.sow("moe_stats", "index_selected",
                     jnp.stack([s[1] for s in picked]))
        x = RMSNorm(c.norm_eps, c.param_dtype, name="final_norm")(x)
        return jnp.einsum("btd,vd->btv", x, head.astype(c.dtype),
                          preferred_element_type=jnp.float32)
