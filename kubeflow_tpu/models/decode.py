"""Autoregressive generation with a KV cache — the LLM serving hot loop.

The reference platform serves models as opaque TF-Serving containers
(``/root/reference/kubeflow/tf-serving/``) and has no generation story;
a TPU-native framework must own it, XLA-style: everything below is
traced once and compiled — static shapes, ``lax.scan`` over decode
steps, no Python in the loop.

Shapes are the whole design:

- prompts are right-padded to a bucket (one compiled prefill per
  bucket, like the model server's padded batch buckets); the cache
  write index is then reset to each row's true length, so the padded
  tail is dead weight that the next real tokens overwrite before any
  attention can see it (masking is by absolute position);
- the per-step state is the flax ``cache`` collection the decode-mode
  :class:`~kubeflow_tpu.models.transformer.Transformer` declares (K/V
  ``(L, B, max_seq_len, KH·Dh)``, a position's KV heads merged on the
  last axis so that it fills the TPU's lanes at any head size, + per-row
  write positions ``(L, B)``).
  It is a loop CARRY all the way down: of the K-step scan here, and of
  the layer scan inside the model, where each layer scatters its tokens
  into ``[layer, row, position]`` and reads its rows back out of the
  same buffer. A cache scanned over by layer (a scanned input and a
  stacked output, two buffers that a loop cannot alias) was copied
  whole on every step, whatever the caller donated;
- sampling is greedy (``temperature=0``) or temperature-scaled
  categorical with a threaded PRNG key.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.transformer import TransformerConfig
from kubeflow_tpu.ops.attention import NEG_INF


def _decode_model(config):
    """The model's decode-mode module; the config knows which."""
    return config.decoder()


def prefill(config: TransformerConfig, params, tokens: jnp.ndarray,
            true_len: Optional[jnp.ndarray] = None):
    """Run the prompt through the decode-mode model, fill the cache.

    ``tokens``: (B, S) right-padded prompts; ``true_len``: the actual
    prompt length(s) — a scalar shared by the batch or a (B,) vector for
    RAGGED batches (defaults to S). Each row's write position resets to
    its own length, so its generated tokens land contiguously after its
    prompt; a shorter row's pad tail stays causally masked until
    overwritten. A recurrent state cannot be reset that way (the pad
    tokens would be folded into it), so a model that has one is given
    the lengths and freezes each row's state at its own. Returns
    (next_token_logits, cache) where logits are each row's LAST REAL
    token's.
    """
    model = _decode_model(config)
    B, S = tokens.shape
    if true_len is None:
        true_len = S
    true_len = jnp.asarray(true_len, jnp.int32)
    if true_len.ndim > 1:
        raise ValueError("true_len must be a scalar or a (B,) vector")
    lens = jnp.broadcast_to(true_len, (B,))

    logits, variables = model.apply({"params": params}, tokens,
                                    *_row_lengths(config, lens),
                                    mutable=["cache"])
    cache = variables["cache"]
    # the write positions advanced to S (the padded bucket); pull each
    # row back to its true length so its next tokens overwrite the pad
    # tail — pad positions are masked (kv_pos <= q_pos) until overwritten
    cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jnp.broadcast_to(lens, leaf.shape)
                            .astype(leaf.dtype)
                            if path[-1].key == "positions" else leaf),
        cache)
    last = jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1)[:, 0]
    return last, cache


def prefill_continue(config: TransformerConfig, params, cache,
                     tokens: jnp.ndarray, suffix_len, total_len):
    """Extend an existing prefilled cache by a (right-padded) suffix.

    The prefix-caching primitive: ``cache`` holds a prompt prefix (its
    write positions sit at the prefix length; rows sharing a start take
    the contiguous fast path — per-row ragged starts need
    ``config.ragged_decode``); ``tokens`` (B, S) is the right-padded
    continuation, ``suffix_len`` its true per-row length (scalar or
    (B,)) and ``total_len`` the full prompt length (prefix + suffix).
    Returns (last real token's logits, cache positioned at total_len) —
    exactly :func:`prefill`'s contract, at the suffix's cost.
    """
    model = _decode_model(config)
    B, S = tokens.shape
    suffix = jnp.broadcast_to(jnp.asarray(suffix_len, jnp.int32), (B,))
    total = jnp.broadcast_to(jnp.asarray(total_len, jnp.int32), (B,))
    logits, variables = model.apply({"params": params, "cache": cache},
                                    tokens, *_row_lengths(config, suffix),
                                    mutable=["cache"])
    new_cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jnp.broadcast_to(total, leaf.shape)
                            .astype(leaf.dtype)
                            if path[-1].key == "positions" else leaf),
        variables["cache"])
    last = jnp.take_along_axis(
        logits, (suffix - 1)[:, None, None], axis=1)[:, 0]
    return last, new_cache


def _row_lengths(config, lens) -> tuple:
    """The extra positional argument of a model with a recurrent state:
    each row's real tokens in this multi-token apply."""
    return (lens,) if config.has_recurrent_state else ()


def refuse_unless_kv_cache(config, what: str) -> None:
    """THE refusal of the paths that work on ``k`` / ``v`` leaves alone
    (paging them, rolling a rejected draft back by resetting positions):
    by what the model declares its cache leaves to be, whatever makes
    them otherwise (a recurrent state, a latent row, an indexer's
    keys)."""
    other = sorted(set(config.cache_leaves(1))
                   - {"positions", "pages", "k", "v"})
    if other:
        raise ValueError(
            f"{what} `k`/`v` cache leaves; this model's cache also holds "
            f"{', '.join(other)}")


def _is_key(path, name: str) -> bool:
    return getattr(path[-1], "key", None) == name


def _slot_view(cache, slot, start):
    """A batch-1 view of one engine slot against the SHARED paged pool.

    ``positions``/``pages`` leaves narrow to the slot's row; pool
    ``k``/``v`` leaves pass through whole (every slot writes the same
    pool, disjoint pages). The view's position is OVERRIDDEN with the
    host-authoritative ``start``: between two chunks of the same slot
    the engine's decode step advances the device-side position of every
    row (idle rows decode garbage by design), so the device value for a
    mid-prefill slot is drift, not truth.
    """
    def narrow(path, leaf):
        if _is_key(path, "positions"):
            return jnp.full(leaf.shape[:-1] + (1,),
                            start).astype(leaf.dtype)
        if _is_key(path, "pages"):
            return jax.lax.dynamic_slice_in_dim(
                leaf, slot, 1, axis=leaf.ndim - 2)
        return leaf

    return jax.tree_util.tree_map_with_path(narrow, cache)


def _slot_merge(cache, view, slot, new_pos):
    """Write a :func:`_slot_view` back: the slot's position becomes
    ``new_pos`` (true tokens, not the padded width the apply advanced
    by), its page row round-trips, and the pool leaves are taken from
    the view (the apply mutated them in place)."""
    def widen(path, big, small):
        if _is_key(path, "positions"):
            row = jnp.full(big.shape[:-1] + (1,),
                           new_pos).astype(big.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                big, row, slot, axis=big.ndim - 1)
        if _is_key(path, "pages"):
            return jax.lax.dynamic_update_slice_in_dim(
                big, small, slot, axis=big.ndim - 2)
        return small

    return jax.tree_util.tree_map_with_path(widen, cache, view)


def arm_slot(cache, slot, start, page_row):
    """Point one slot's device-side position/page-table rows at host
    truth — the paged engine's admission, page growth, and retirement
    are this one tiny program (page-map surgery), never a KV copy.

    Lives beside :func:`_slot_view`/:func:`_slot_merge` because the
    three share the paged-cache leaf contract ("positions" rows on the
    last axis, "pages" rows on the second-to-last); pool leaves pass
    through untouched. Jit with ``donate_argnums=(0,)``.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def upd(path, leaf):
        if _is_key(path, "positions"):
            row = jnp.full(leaf.shape[:-1] + (1,),
                           start).astype(leaf.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, row, slot, axis=leaf.ndim - 1)
        if _is_key(path, "pages"):
            row = jnp.broadcast_to(
                page_row,
                leaf.shape[:-2] + (1,) + page_row.shape).astype(
                    leaf.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, row, slot, axis=leaf.ndim - 2)
        return leaf

    return jax.tree_util.tree_map_with_path(upd, cache)


def copy_page(cache, src, dst):
    """Copy ONE physical pool page (k and v, every layer) ``src`` →
    ``dst`` — the copy-on-write split primitive: sharing a partial
    boundary page costs one page-sized device copy instead of
    re-prefilling up to ``page_size − 1`` tokens through the model.

    Lives beside :func:`arm_slot` because it shares the paged-cache
    leaf contract: pool ``k``/``v`` leaves are ``(…, P, ps, KH, Dh)``
    (page axis at ``ndim − 4``); ``positions``/``pages`` rows pass
    through untouched. Jit with ``donate_argnums=(0,)``.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def upd(path, leaf):
        if _is_key(path, "positions") or _is_key(path, "pages"):
            return leaf
        ax = leaf.ndim - 4
        page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax)
        return jax.lax.dynamic_update_slice_in_dim(leaf, page, dst,
                                                   axis=ax)

    return jax.tree_util.tree_map_with_path(upd, cache)


def prefill_chunk(config: TransformerConfig, params, cache,
                  tokens: jnp.ndarray, slot, start, true_n):
    """One prompt chunk for ONE slot of a PAGED decode cache.

    The chunked-prefill primitive (``config.kv_page_size > 0``): the
    engine splits prompts into fixed-width chunks and runs one chunk
    per scheduler cycle, so a long admission never stalls co-tenant
    decode for more than one chunk's compute — and the whole prompt
    path needs ONE compiled program (one chunk shape), not one per
    prompt bucket.

    ``tokens``: (1, C) right-padded chunk; ``slot``: engine row the
    chunk belongs to; ``start``: the slot's true position before this
    chunk (0 for a fresh prompt, the shared-page boundary on a prefix
    hit, mid-prompt for every later chunk); ``true_n``: real tokens in
    this chunk (< C only on the final, padded chunk — the pad tail's
    garbage KV lands inside the slot's own pages, stays causally masked
    while the position sits at ``start + true_n``, and is overwritten
    by decode before it can be unmasked, exactly like prefill()'s pad
    tail). Returns ``(logits of the last real token (1, V), cache)``.
    """
    model = _decode_model(config)
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    true_n = jnp.asarray(true_n, jnp.int32)
    view = _slot_view(cache, slot, start)
    logits, variables = model.apply({"params": params, "cache": view},
                                    tokens, mutable=["cache"])
    new_cache = _slot_merge(cache, variables["cache"], slot,
                            start + true_n)
    last = jnp.take_along_axis(
        logits, (true_n - 1).reshape(1, 1, 1), axis=1)[:, 0]
    return last, new_cache


def decode_step_stats(config, params, cache, token: jnp.ndarray):
    """One token in, one token's logits out; cache advances by one.
    Returns (logits, cache, stats): ``stats`` holds what the model's
    routed layers counted in this step (``experts_hit`` and
    ``routed_pairs``, one entry a routed layer) and is empty, adding
    nothing to the program, for a model that has none."""
    model = _decode_model(config)
    logits, variables = model.apply(
        {"params": params, "cache": cache}, token[:, None],
        mutable=["cache", "moe_stats"])
    stats = {name: sown[0]
             for name, sown in variables.get("moe_stats", {}).items()}
    return logits[:, 0], variables["cache"], stats


def decode_step(config, params, cache, token: jnp.ndarray):
    """:func:`decode_step_stats` without the stats."""
    return decode_step_stats(config, params, cache, token)[:2]


def sample_logits(logits: jnp.ndarray, rng: jax.Array, *,
                  temperature=1.0, top_k=0, top_p=1.0,
                  bound: Optional[int] = None) -> jnp.ndarray:
    """Sample token ids from ``(B, V)`` logits — the serving sampler.

    Every parameter may be a Python scalar or a ``(B,)`` array, so ONE
    compiled program serves requests with different sampling settings
    sharing a decode batch (the continuous-batching engine's contract):

    - ``temperature``: 0 → greedy (argmax) for that row; >0 scales.
    - ``top_k``: keep only the k highest logits (0 or ≥V → no filter).
    - ``top_p``: nucleus — keep the smallest prefix of the sorted
      distribution with cumulative probability ≥ p (1.0 → no filter).

    Filters compose HF-style: temperature, then top-k, then top-p.
    Fully jittable: one descending sort of the vocab axis drives both
    filters (threshold-based, static shapes, no boolean gather).

    ``bound`` (a STATIC int) selects the bounded TPU-fast path: only the
    top-``bound`` logits per row are extracted with ``lax.top_k`` — no
    full-vocab sort, no (B, V) sorted materialization (at engine batch
    32 the sort is 32 vocab sorts per token). Semantics under the bound:

    - top-k is exact for ``k <= bound``; larger k clamps to ``bound``
      (the serving cap — public APIs cap top_k the same way);
    - top-p nucleus masses are EXACT (the softmax denominator is a
      full-vocab logsumexp — no sort needed), but a flat distribution
      whose nucleus overflows ``bound`` candidates truncates to the
      bound's top tokens;
    - ``k <= 0`` with ``p >= 1`` rows are unfiltered — exact full-vocab
      categorical; ``temperature <= 0`` rows are exact argmax.

    Bounded and unbounded paths draw different (identically
    distributed) samples for the same key — switching the engine's
    sampler changes sampled streams, like any sampler upgrade.
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))

    greedy_row = temp <= 0.0
    scaled = logits / jnp.where(greedy_row, 1.0, temp)[:, None]

    if bound is not None and int(bound) > 0 and int(bound) < V:
        M = int(bound)
        topv, topi = jax.lax.top_k(scaled, M)  # (B, M) descending
        k_eff = jnp.where(k <= 0, M, jnp.minimum(k, M))
        pos = jnp.arange(M)[None, :]
        kmask = pos < k_eff[:, None]
        # compose parity with the sort path: top-p renormalizes over
        # the k-filtered distribution — over the FULL vocab when no k
        # filter is set (exact via logsumexp), over the kept top-k
        # candidates otherwise
        full_lse = jax.scipy.special.logsumexp(scaled, axis=-1)
        k_lse = jax.scipy.special.logsumexp(
            jnp.where(kmask, topv, NEG_INF), axis=-1)
        denom = jnp.where(k <= 0, full_lse, k_lse)
        probs = jnp.exp(topv - denom[:, None]) * kmask
        before = jnp.cumsum(probs, axis=-1) - probs
        keep = kmask & ((before < p[:, None]) | (p[:, None] >= 1.0))
        rng_m, rng_v = jax.random.split(rng)
        choice = jax.random.categorical(
            rng_m, jnp.where(keep, topv, NEG_INF), axis=-1)
        bounded_tok = jnp.take_along_axis(
            topi, choice[:, None], axis=-1)[:, 0]
        unfiltered = (k <= 0) & (p >= 1.0)
        full_tok = jax.random.categorical(rng_v, scaled, axis=-1)
        out = jnp.where(greedy_row, jnp.argmax(logits, axis=-1),
                        jnp.where(unfiltered, full_tok, bounded_tok))
        return out.astype(jnp.int32)

    srt = jnp.sort(scaled, axis=-1)[:, ::-1]  # (B, V) descending
    # top-k: per-row threshold at the k-th largest (k<=0 → keep all)
    k_eff = jnp.where(k <= 0, V, jnp.minimum(k, V))
    kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    keep = scaled >= kth
    # top-p on the k-filtered distribution: renormalised cumulative
    # mass strictly BEFORE each sorted position; a position is kept
    # while that prefix mass is < p (the first is always kept). In
    # sorted order the k-filter is positional: the first k_eff entries.
    srt_masked = jnp.where(jnp.arange(V)[None, :] < k_eff[:, None],
                           srt, NEG_INF)
    probs = jax.nn.softmax(srt_masked, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    # p >= 1.0 must be a strict no-op: f32 cumsum rounding can push
    # `before` to exactly 1.0 for tail tokens, which `< p` would mask
    kept_sorted = (before < p[:, None]) | (p[:, None] >= 1.0)
    # smallest kept sorted logit = the acceptance threshold
    n_kept = jnp.sum(kept_sorted, axis=-1)  # >= 1
    p_thresh = jnp.take_along_axis(srt, (n_kept - 1)[:, None], axis=-1)
    keep = keep & (scaled >= p_thresh)
    masked = jnp.where(keep, scaled, NEG_INF)
    sampled = jax.random.categorical(rng, masked, axis=-1)
    out = jnp.where(greedy_row, jnp.argmax(logits, axis=-1), sampled)
    return out.astype(jnp.int32)


def _sample(logits: jnp.ndarray, temperature, rng: Optional[jax.Array],
            greedy: bool, top_k=0, top_p=1.0) -> jnp.ndarray:
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # the sort-free fast path needs the filters statically off and the
    # temperature scalar — a (B,) temperature (per-row greedy mix) must
    # go through sample_logits, whose broadcasting and temp<=0 handling
    # are per-row. A 0-d TRACED temperature stays on the fast path (the
    # serving closure traces it; its greedy split is static, so a traced
    # temperature is guaranteed > 0 here).
    scalar_temp = (isinstance(temperature, (int, float)) or
                   getattr(temperature, "ndim", None) == 0)
    static_nofilter = (
        scalar_temp and
        isinstance(top_k, int) and top_k == 0 and
        isinstance(top_p, (int, float)) and top_p >= 1.0)
    if static_nofilter:
        return jax.random.categorical(
            rng, logits / temperature, axis=-1).astype(jnp.int32)
    return sample_logits(logits, rng, temperature=temperature,
                         top_k=top_k, top_p=top_p)


def generate(config: TransformerConfig, params, prompt: jnp.ndarray,
             *, max_new_tokens: int,
             true_len: Optional[jnp.ndarray] = None,
             temperature: float = 0.0,
             top_k=0, top_p=1.0,
             rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Prefill + scan decode; returns (B, max_new_tokens) int32.

    Fully traceable: wrap in ``jax.jit`` (static ``config`` and
    ``max_new_tokens``). ``temperature`` may be a traced array — the
    greedy/sampling split is decided statically by whether it is the
    Python float 0.0, so a serving layer can compile ONE sampling
    program for all temperatures. ``top_k``/``top_p`` likewise may be
    traced (scalars or per-row vectors, see :func:`sample_logits`);
    their no-filter defaults are recognised statically so the plain
    temperature path compiles without the vocab sort.
    """
    greedy = isinstance(temperature, (int, float)) and temperature == 0.0
    if not greedy:
        if rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng key")
        if isinstance(temperature, (int, float)) and temperature < 0:
            raise ValueError("temperature must be >= 0")
    if isinstance(top_k, int) and top_k < 0:
        raise ValueError("top_k must be >= 0 (0 = no filter)")
    if isinstance(top_p, (int, float)) and not 0.0 < top_p <= 1.0:
        raise ValueError("top_p must be in (0, 1]")
    if rng is None:
        rng = jax.random.key(0)  # unused by greedy; keeps the scan carry

    # cache writes past max_seq_len silently clamp (scatter semantics) —
    # reject overruns where the start is known eagerly. A traced
    # true_len (inside an outer jit, e.g. the serving wrapper) is the
    # caller's contract: the padded prompt width would over-reject.
    if true_len is None:
        start = prompt.shape[1]
    elif isinstance(true_len, jax.core.Tracer):
        start = None
    else:
        start = int(jnp.max(jnp.asarray(true_len)))
    if start is not None and start + max_new_tokens > config.max_seq_len:
        raise ValueError(
            f"prompt length {start} + max_new_tokens "
            f"{max_new_tokens} exceeds max_seq_len {config.max_seq_len}: "
            "cache writes past the end would silently clamp")

    last_logits, cache = prefill(config, params, prompt, true_len)
    rng, sub = jax.random.split(rng)
    first = _sample(last_logits, temperature, sub, greedy, top_k, top_p)

    def step(carry, _):
        cache, token, rng = carry
        logits, cache = decode_step(config, params, cache, token)
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits, temperature, sub, greedy, top_k, top_p)
        return (cache, nxt, rng), nxt

    if max_new_tokens == 1:
        return first[:, None]
    (_, _, _), rest = jax.lax.scan(
        step, (cache, first, rng), None, length=max_new_tokens - 1)
    # scan stacks on axis 0: (T-1, B) -> (B, T-1)
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def speculative_generate(config: TransformerConfig, params,
                         draft_config: TransformerConfig, draft_params,
                         prompt: jnp.ndarray, *, max_new_tokens: int,
                         draft_len: int = 4,
                         true_len: Optional[jnp.ndarray] = None):
    """Greedy speculative decoding: a small draft model proposes
    ``draft_len`` tokens per round, the target verifies them in ONE
    multi-token forward, and every accepted token costs the target
    1/draft_len of a decode step.

    Output matches ``generate(config, params, prompt, ...)`` token for
    token (greedy verification accepts a proposal iff it equals the
    target's argmax) — speculation changes the cost, never the policy.
    Caveat: the k-token verify and the 1-token step are different XLA
    programs; under reduced precision (bf16) a near-tie argmax can
    resolve differently and diverge the tail. Exactness is guaranteed
    at f32 (the test tier); at bf16 the stream remains a valid greedy
    stream of the target up to tie-breaks.

    TPU-first detail: the decode cache stores token t at physical slot
    t (``transformer.py:_decode_attend``), so rejecting draft tokens is
    a ROLLBACK-BY-RESET — set the per-row write position back to the
    accepted length and the stale tail is dead weight the next tokens
    overwrite before attention can see it. No copies, no re-prefill,
    ragged per-row acceptance for free.

    Returns ``(tokens (B, max_new_tokens) int32, stats)`` with
    ``stats = {"rounds": R, "draft_tokens": R*draft_len, "accepted":
    total draft tokens accepted}`` — acceptance/draft_tokens is the
    acceptance rate that decides whether the draft pays for itself.
    """
    B, S = prompt.shape
    k = int(draft_len)
    _spec_validate(config, draft_config, S, max_new_tokens, k, true_len)

    t_logits, t_cache = _prefill_jit(config)(params, prompt, true_len)
    _, d_cache = _prefill_jit(draft_config)(draft_params, prompt,
                                            true_len)
    first = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)

    spec_round = _spec_round_fn(config, draft_config, k)
    emitted = [[int(first[b])] for b in range(B)]
    pending = first
    rounds = accepted_total = 0
    # Ragged batches (B>1): a fast row keeps decoding past
    # max_new_tokens while slow rows catch up; its overshoot tokens are
    # sliced off below and its cache writes past max_seq_len are
    # DROPPED by jnp scatter out-of-bounds semantics (`.at[pos].set`
    # drops OOB writes — the same invariant the decode engine's idle
    # slots rely on). The kept tokens never depend on an OOB write: a
    # row's first max_new_tokens are all produced from in-bounds cache
    # state (guaranteed by the max_seq_len slack check above), so the
    # reliance is confined to the discarded tail.
    while min(len(e) for e in emitted) < max_new_tokens:
        t_cache, d_cache, out, m, pending, n = spec_round(
            params, draft_params, t_cache, d_cache, pending)
        # the per-round surfacing point BY DESIGN: acceptance counts
        # decide on the host whether another speculative round runs
        out, m, n = np.asarray(out), np.asarray(m), np.asarray(n)  # tpulint: disable=TPU017
        rounds += 1
        accepted_total += int(n.sum())
        for b in range(B):
            emitted[b].extend(int(t) for t in out[b, :m[b]])
    tokens = np.asarray([e[:max_new_tokens] for e in emitted], np.int32)
    stats = {"rounds": rounds, "draft_tokens": rounds * k,
             "accepted": accepted_total}
    return jnp.asarray(tokens), stats


@functools.lru_cache(maxsize=32)
def _prefill_jit(config: TransformerConfig):
    """Compiled prefill per (config, shape) — cached across calls so a
    serving loop never re-traces."""
    return jax.jit(functools.partial(prefill, config))


def _spec_validate(config: TransformerConfig,
                   draft_config: TransformerConfig, prompt_width: int,
                   max_new_tokens: int, k: int, true_len) -> None:
    """Shared eager validation for the speculative variants.

    Each round may advance up to ``k`` cache slots past the final
    output; the real footprint starts at the TRUE prompt length when
    known eagerly (a traced ``true_len`` is the caller's contract, like
    ``generate()``)."""
    if k < 1:
        raise ValueError("draft_len must be >= 1")
    for name, c in (("target", config), ("draft", draft_config)):
        refuse_unless_kv_cache(
            c, f"speculative decoding rolls the {name} model's rejected "
               "draft back by resetting the positions of")
    if config.vocab_size != draft_config.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    if true_len is None:
        start: Optional[int] = prompt_width
    elif isinstance(true_len, jax.core.Tracer):
        start = None
    else:
        start = int(jnp.max(jnp.asarray(true_len)))
    for name, c in (("target", config), ("draft", draft_config)):
        if start is not None and start + max_new_tokens + k > c.max_seq_len:
            raise ValueError(
                f"prompt {start} + max_new_tokens {max_new_tokens} + "
                f"draft_len {k} exceeds {name} max_seq_len "
                f"{c.max_seq_len} (speculation needs slack for "
                "in-flight proposals)")


def _spec_round_body(ragged_config: TransformerConfig,
                     draft_config: TransformerConfig, k: int,
                     params, draft_params, t_cache, d_cache, pending):
    """One propose-verify-rollback round (traceable; shared by the
    per-round jit and the fused while_loop path)."""
    B = pending.shape[0]

    def dstep(carry, _):
        cache, tok = carry
        logits, cache = decode_step(draft_config, draft_params,
                                    cache, tok)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt), nxt

    (d_cache2, _), xs = jax.lax.scan(dstep, (d_cache, pending),
                                     None, length=k)
    xs = xs.T  # (B, k): proposals x1..xk
    # verify: the target processes (pending, x1..x_{k-1}) in one
    # forward; logits[i] is its prediction for position i+1
    seq = jnp.concatenate([pending[:, None], xs[:, :k - 1]], axis=1)
    model = _decode_model(ragged_config)
    logits, variables = model.apply(
        {"params": params, "cache": t_cache}, seq, mutable=["cache"])
    t_cache2 = variables["cache"]
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k)
    match = xs == preds
    # accepted = length of the all-True prefix
    n = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    idx = jnp.arange(k)[None, :]
    rows = jnp.arange(B)
    correction = preds[rows, jnp.minimum(n, k - 1)]
    out = jnp.where(idx < n[:, None], xs, 0)
    # at index n the target's own token replaces the rejected one
    out = jnp.where(idx == n[:, None], correction[:, None], out)
    m = jnp.where(n < k, n + 1, k)  # emitted this round, per row
    new_pending = jnp.where(n < k, correction, xs[:, k - 1])
    # rollback-by-reset: the verify advanced every row k slots, but
    # only (pending, x1..x_n) are valid — n+1 entries on rejection
    # rounds, all k on full acceptance (x_k was proposed, never
    # written). Pull each row back by the overshoot.
    delta = jnp.maximum(k - n - 1, 0)

    def reset(path, leaf):
        if path[-1].key != "positions":
            return leaf
        return (leaf - jnp.broadcast_to(delta, leaf.shape)
                ).astype(leaf.dtype)

    t_cache2 = jax.tree_util.tree_map_with_path(reset, t_cache2)
    d_cache2 = jax.tree_util.tree_map_with_path(reset, d_cache2)
    return t_cache2, d_cache2, out, m, new_pending, n


@functools.lru_cache(maxsize=16)
def _spec_round_fn(config: TransformerConfig,
                   draft_config: TransformerConfig, k: int):
    """Compiled propose-verify round, cached per (configs, draft_len) —
    a fresh closure per generate call would retrace both models every
    time."""
    # the verify writes k tokens from PER-ROW ragged positions
    ragged = dataclasses.replace(config, ragged_decode=True)

    @jax.jit
    def spec_round(params, draft_params, t_cache, d_cache, pending):
        return _spec_round_body(ragged, draft_config, k, params,
                                draft_params, t_cache, d_cache, pending)

    return spec_round


def speculative_generate_fused(config: TransformerConfig, params,
                               draft_config: TransformerConfig,
                               draft_params, prompt: jnp.ndarray, *,
                               max_new_tokens: int, draft_len: int = 4,
                               true_len: Optional[jnp.ndarray] = None):
    """:func:`speculative_generate` as ONE traceable program: prefills,
    every propose-verify-rollback round (``lax.while_loop``), and token
    assembly all compile into a single XLA computation.

    The host-loop variant pays one device dispatch (and one host
    readback) per round; for a small model those round-trips, not the
    device compute, are the wall time.
    Fused, speculation is a single dispatch exactly like the plain
    ``generate`` scan, so the comparison is pure compute: a round costs
    one k-token target verify plus k draft steps for ``1 + acceptance·k``
    emitted tokens.

    Identical round math to ``speculative_generate`` (f32-exact parity
    is test-gated; at bf16 XLA may fuse the two variants differently, so
    near-tie argmaxes can diverge — each stream remains a valid greedy
    stream of the target up to tie-breaks). Ragged rows: a finished row
    keeps stepping until the slowest row completes; its overshoot
    tokens land past ``max_new_tokens`` in the output buffer (scatter-
    drop) and its cache writes past ``max_seq_len`` are dropped by the
    same out-of-bounds semantics the host variant documents.

    Returns ``(tokens (B, max_new_tokens) int32, stats)``; stats values
    are 0-d device arrays under tracing (``int()`` them outside jit).
    Wrap in ``jax.jit`` with params/prompt as ARGUMENTS (closing over
    params embeds the weights as program constants).
    """
    B, S = prompt.shape
    k = int(draft_len)
    _spec_validate(config, draft_config, S, max_new_tokens, k, true_len)

    ragged = dataclasses.replace(config, ragged_decode=True)
    t_logits, t_cache = prefill(config, params, prompt, true_len)
    _, d_cache = prefill(draft_config, draft_params, prompt, true_len)
    first = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)

    # buffer slack: a row at counts==max_new-1 can still write m<=k
    # tokens; masked positions index `cap` and are scatter-dropped
    cap = max_new_tokens + k + 1
    out_buf = jnp.zeros((B, cap), jnp.int32).at[:, 0].set(first)
    counts = jnp.ones((B,), jnp.int32)
    rows = jnp.arange(B)
    idx = jnp.arange(k)[None, :]

    def cond(carry):
        return jnp.min(carry[4]) < max_new_tokens

    def body(carry):
        t_cache, d_cache, pending, out_buf, counts, rounds, acc = carry
        t_cache, d_cache, out, m, pending, n = _spec_round_body(
            ragged, draft_config, k, params, draft_params, t_cache,
            d_cache, pending)
        pos = jnp.where(idx < m[:, None], counts[:, None] + idx, cap)
        out_buf = out_buf.at[rows[:, None], pos].set(out, mode="drop")
        return (t_cache, d_cache, pending, out_buf, counts + m,
                rounds + 1, acc + jnp.sum(n))

    carry = (t_cache, d_cache, first, out_buf, counts,
             jnp.int32(0), jnp.int32(0))
    _, _, _, out_buf, _, rounds, accepted = jax.lax.while_loop(
        cond, body, carry)
    stats = {"rounds": rounds, "draft_tokens": rounds * k,
             "accepted": accepted}
    return out_buf[:, :max_new_tokens], stats


@functools.lru_cache(maxsize=16)
def _spec_fused_fn(config: TransformerConfig,
                   draft_config: TransformerConfig, k: int,
                   max_new_tokens: int):
    @jax.jit
    def fn(params, draft_params, prompt, true_len):
        return speculative_generate_fused(
            config, params, draft_config, draft_params, prompt,
            max_new_tokens=max_new_tokens, draft_len=k,
            true_len=true_len)

    return fn


def speculative_generate_jit(config: TransformerConfig, params,
                             draft_config: TransformerConfig,
                             draft_params, prompt: jnp.ndarray, *,
                             max_new_tokens: int, draft_len: int = 4,
                             true_len: Optional[jnp.ndarray] = None):
    """Serving entry for fused speculation: eager validation (the slack
    ValueError serving maps to 400 fires before any device work) + a
    cached compiled program per (configs, draft_len, max_new_tokens,
    shapes). Stats come back as Python ints like the host-loop
    variant's."""
    B, S = prompt.shape
    _spec_validate(config, draft_config, S, max_new_tokens,
                   int(draft_len), true_len)
    fn = _spec_fused_fn(config, draft_config, int(draft_len),
                        int(max_new_tokens))
    toks, stats = fn(params, draft_params, prompt, true_len)
    return toks, {key: int(np.asarray(v)) for key, v in stats.items()}


def make_generate(config: TransformerConfig, *, max_new_tokens: int,
                  temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 1.0):
    """Jitted generate closure: (params, prompt, true_len, rng) -> tokens."""
    import functools

    @functools.partial(jax.jit, donate_argnums=())
    def fn(params, prompt, true_len, rng):
        return generate(config, params, prompt,
                        max_new_tokens=max_new_tokens,
                        true_len=true_len, temperature=temperature,
                        top_k=top_k, top_p=top_p,
                        rng=rng)

    return fn
