"""The decode engine's cache managers: where "dense rows or paged
pool" is decided.

:class:`~kubeflow_tpu.serving.engine.DecodeEngine` runs one round loop
and builds ONE manager from its ``paged`` option. A manager owns the
device cache tree, every compiled program that writes it other than the
step, the prefix store and the admission scheduler of its kind, and the
host accounting only it needs (:class:`RowCache`, :class:`PagedCache`).

The seam is what the loop calls: ``check_submit``, ``admit`` (which
launches) and ``land`` (which reads the first tokens and arms the
slots, once the loop has read the round before), ``before_step``,
``after_step``, ``retire``, ``reset`` + ``replay`` (recovery),
``drain``, ``snapshot``, the ``in_admission`` / ``waiting`` counts,
``landing`` and ``admission_recovers`` (the manager's error scope). A
manager calls back the engine's host services (clock, tracer, request ledger,
``_note_queue_wait``, ``_next_pending``, ``_arm_slot``, ``_fail``, and
``_open_admission``: :class:`RowCache` runs each device admission inside
one such record and tells it which stretch it is in); the engine imports
this module, nothing here imports the engine.
"""
# tpulint: disable-file=TPU018 — as in engine.py: the per-bucket program
# inventory compiles lazily on first dispatch (billed by the CompileLedger
# listener, and counted as ``compiles`` on the admission that stalled for
# it); timed_compile's AOT path would compile every program twice.

from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.decode import (
    arm_slot,
    copy_page,
    prefill,
    prefill_chunk,
    prefill_continue,
    refuse_unless_kv_cache,
)
from kubeflow_tpu.obs import requests as reqobs
from kubeflow_tpu.serving.kvpool import (
    OutOfPages,
    PagePool,
    PrefixPageStore,
)
from kubeflow_tpu.utils import DEFAULT_REGISTRY

log = logging.getLogger(__name__)

_prefix_hits = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefix_hits_total", "prefix-cache hits at admission")
_prefix_misses = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefix_misses_total", "prefix-cache misses at admission")
_prefix_bytes_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_prefix_cache_bytes",
    "HBM bytes held by cached prompt-prefix KV rows")
_kv_pages_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_kv_pages_in_use",
    "physical KV pages allocated out of the paged engine's pool")
_kv_pages_free_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_kv_pages_free",
    "unallocated KV pages left in the paged engine's pool (the "
    "engine-pages-exhausted alert rule watches this)")
_kv_pages_evictable_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_kv_pages_evictable",
    "prefix-store pages no live slot shares: reclaimable cache, not "
    "load — occupancy/pressure consumers (autoscaler, fleet-edge "
    "admission gate) subtract these from the in-use count")
_prefill_chunks_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefill_chunks_total",
    "prompt chunks prefilled by the paged engine's interleaved scheduler")
_prefix_pages_shared_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefix_pages_shared_total",
    "KV pages mapped from the prefix trie into admitted slots "
    "(full shared pages + COW boundary pages)")
_cow_splits_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_cow_splits_total",
    "copy-on-write splits of shared boundary pages (one device-side "
    "page copy each, in place of a boundary re-prefill)")


class EngineClosed(RuntimeError):
    """The engine was shut down (version rollover) — retryable."""


class _CacheInvalidated(RuntimeError):
    """A donating device call consumed the engine cache and then
    failed: the engine can never step again. Raised THROUGH run_once so
    the loop applies the same close-and-evict protocol as a step
    failure (row-path retries against a consumed cache would fail every
    request while keeping the corpse serving)."""


def pow2_bucket(n: int, cap: int) -> int:
    """Round ``n`` up to a power of two, capped at ``cap`` — the shared
    compiled-program bucketing rule for prompts (one compiled prefill
    per bucket, in both the unary path and engine admission).

    Total on its edges: ``n <= 0`` buckets to the smallest program (1),
    ``n >= cap`` to exactly ``cap`` — even a non-power-of-two cap, which
    is its own terminal bucket (the max_seq_len program)."""
    if cap < 1:
        raise ValueError(f"pow2_bucket cap must be >= 1, got {cap}")
    if n >= cap:
        return cap
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _leaf_name(path) -> str:
    """The name under which the model declared a cache leaf."""
    return path[-1].key


def _padded(tokens: np.ndarray, width: int) -> jnp.ndarray:
    """``tokens`` as one right-padded (1, width) row."""
    row = np.zeros((1, width), np.int32)
    row[0, :tokens.size] = tokens
    return jnp.asarray(row)


def _sampling_args(req) -> tuple:
    """A request's (temperature, top_k, top_p, seed) as device scalars."""
    return (jnp.float32(req.temperature), jnp.int32(req.top_k),
            jnp.float32(req.top_p), jnp.int32(req.seed))


def _no_sampling() -> tuple:
    """:func:`_sampling_args` of a prefill whose token nobody reads."""
    return (jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0), jnp.int32(0))


@dataclasses.dataclass
class _Landing:
    """An admission whose programs are enqueued and whose first tokens
    the host has not read: ``finish`` reads them and arms the slots;
    ``replays`` are the ``replay`` arguments that admit its requests
    anew after a recovery; ``adm`` is its record, where it keeps one."""

    finish: Callable[[], None]
    replays: List[tuple]
    adm: Any = None

    def abandon(self) -> None:
        if self.adm is not None:
            self.adm.abandon()


class _CacheManager:
    """What both cache policies share: the model's leaf contract, the
    cache tree at ``slots`` rows (sharded over the engine's mesh), the
    prefix budget and the counters callers read on the engine. The
    defaults below are the seam for a manager with nothing to do."""

    # the manager's ERROR SCOPE. True: its admission donates the cache
    # (arm / chunk / copy), so a failure there has consumed it and the
    # loop recovers under the engine's budget. False: admission handles
    # errors per request and raises only _CacheInvalidated (the close
    # protocol). Retirement and the step are in the recovery scope for
    # every manager.
    admission_recovers = False
    kv_page_size = 0
    kv_pages = 0
    paged_attention_impl = "gather"

    def __init__(self, eng, cfg) -> None:
        self.eng = eng
        self.cfg = cfg
        # the model's declaration of its cache leaves: each leaf's row
        # axis, idle value and head axis, by name
        self._leaves = cfg.cache_leaves(1)
        self.prefix_hits = self.prefix_misses = 0
        self.prefix_pages_shared = 0  # pages mapped from the trie
        self.cow_splits = 0      # boundary-page copy-on-write splits
        self.batch_prefills = 0  # burst admissions served batched
        self.prefill_chunks = 0  # chunk programs run (paged scheduler)
        # admissions still under way, by slot, oldest first, and the
        # head-of-line requests admission popped but could not place
        # yet (FIFO order is preserved)
        self._prefilling: "collections.OrderedDict[int, _PrefillJob]" = \
            collections.OrderedDict()
        self._waiting: collections.deque = collections.deque()
        # admissions launched and not landed, in launch order
        self._landing: List[_Landing] = []
        # the decode cache shape at batch 1: eval_shape on prefill gives
        # the layout without running it
        probe = jnp.zeros((1, 1), jnp.int32)
        self._shapes = jax.eval_shape(
            lambda p: prefill(cfg, p, probe)[1], eng._params)
        self._zeros = self._zeros_tree if eng.mesh is None else jax.jit(
            self._zeros_tree,
            out_shardings=jax.tree_util.tree_map_with_path(
                self._sharding, self._shapes))
        self.cache = self._fresh_cache()

    def _fresh_cache(self):
        with self.eng._mesh_ctx():
            return self._zeros()

    def _engine_shape(self, path, s) -> tuple:
        """The leaf at ``slots`` rows (a pool every row shares keeps its
        shape: that is how paged cache memory decouples from
        slots x max_len)."""
        ax = self._leaves[_leaf_name(path)].batch_axis
        return tuple(self.eng.slots if a == ax else d
                     for a, d in enumerate(s.shape))

    def _zeros_tree(self):
        # every row idle (paged: disarmed, writes past max_seq_len
        # scatter-drop, and no page mapped)
        return jax.tree_util.tree_map_with_path(
            lambda path, s: jnp.full(
                self._engine_shape(path, s),
                self._leaves[_leaf_name(path)].idle_value, s.dtype),
            self._shapes)

    def _sharding(self, path, s):
        """Leaves that declare a heads axis (k/v) shard it per the
        model's logical rules, so the full-context cache never lies on
        one device; shape_aware_spec drops the axis when it doesn't
        divide (GQA kv heads < tp), in whole heads of ``head_width``."""
        from jax.sharding import NamedSharding

        from kubeflow_tpu.parallel.mesh import (
            logical_to_mesh_axes,
            shape_aware_spec,
        )

        shape = list(self._engine_shape(path, s))
        names = [None] * len(shape)
        leaf = self._leaves[_leaf_name(path)]
        if leaf.heads_axis is not None:
            names[leaf.heads_axis] = "heads"
            shape[leaf.heads_axis] //= leaf.head_width
        spec = shape_aware_spec(
            logical_to_mesh_axes(names, self.cfg.rules), tuple(shape),
            self.eng.mesh)
        return NamedSharding(self.eng.mesh, spec)

    def _set_prefix_budget(self, entries: int,
                           budget_bytes: Optional[int]) -> None:
        """The prefix store is BYTE-budgeted (an entry count hides that
        an entry costs max_seq_len × layers of HBM): the explicit bytes,
        else ``entries`` × one full-context row's."""
        if budget_bytes is None:
            budget_bytes = max(0, int(entries)) * self._prefix_row_bytes
        self._prefix_budget_bytes = max(0, int(budget_bytes))

    def _sample1(self, logits, seed, fold, temperature, top_k, top_p):
        """One row through the engine's sampler (prefill's first token;
        the paged path's post-chunk sample, where ``fold`` continues a
        replayed stream's step index)."""
        return self.eng._sample_rows(
            logits, jnp.reshape(seed, (1,)), jnp.reshape(fold, (1,)),
            jnp.reshape(temperature, (1,)), jnp.reshape(top_k, (1,)),
            jnp.reshape(top_p, (1,)))[0]

    def _count_prefix(self, hit: bool) -> None:
        self.prefix_hits += hit
        self.prefix_misses += not hit
        (_prefix_hits if hit else _prefix_misses).inc(model=self.eng.name)

    # -- the seam ----------------------------------------------------------

    @property
    def in_admission(self) -> int:
        return len(self._prefilling) + sum(len(a.replays)
                                           for a in self._landing)

    @property
    def landing(self) -> bool:
        """Admissions are launched and wait to be landed."""
        return bool(self._landing)

    @property
    def waiting(self) -> int:
        return len(self._waiting)

    def check_submit(self, n_prompt: int, max_new: int,
                     prefix_len: int) -> int:
        """Refuse (ValueError) a request this cache can never hold;
        returns the ``prefix_len`` the request is admitted with."""
        return prefix_len

    def before_step(self, active: List[tuple]) -> None:
        """Ready the cache for K more writes by the ``active`` rows."""

    def after_step(self, active: List[tuple], k: int, t0: float,
                   t1: float) -> None:
        """The K steps of ``[t0, t1]`` are on the host."""

    def retire(self, slot: int) -> None:
        """The slot's stream is over, or its last round is enqueued: the
        slot takes its next request behind that round."""

    def land(self) -> None:
        """Land the launched admissions in launch order: read their
        first tokens and arm their slots. One that raises stays queued,
        so that a recovery replays its requests."""
        while self._landing:
            landing = self._landing.pop(0)
            try:
                landing.finish()
            except _CacheInvalidated:
                raise
            except Exception:
                self._landing.insert(0, landing)
                raise

    def _forget_landings(self) -> List[tuple]:
        """Drop the launched admissions unread (their records closed as
        abandoned); returns their ``replay`` arguments."""
        replays = []
        for landing in self._landing:
            landing.abandon()
            replays.extend(landing.replays)
        self._landing = []
        return replays

    def reset(self) -> List[tuple]:
        """Recovery: a fresh cache in place of the consumed one. Returns
        the ``replay`` arguments of admissions that were under way."""
        self.cache = self._fresh_cache()
        return self._forget_landings()

    def drain(self) -> list:
        """Hand back, and forget, every request held mid-admission."""
        held = [args[1] for args in self._forget_landings()]
        held.extend(j.req for j in self._prefilling.values())
        held.extend(self._waiting)
        self._prefilling.clear()
        self._waiting.clear()
        return held

    def snapshot(self) -> dict:
        """What this cache adds to the engine's occupancy snapshot."""
        return {}


def _insert_programs(leaves) -> tuple:
    """The two donating programs that write prefilled rows into the
    engine cache, ``(_insert, _insert_rows)``. Their function names are
    the programs' (``jit__insert``): the compile cache keys on them."""

    def _insert(engine_cache, row_cache, slot):
        def put(path, big, row):
            ax = leaves[_leaf_name(path)].batch_axis
            return jax.lax.dynamic_update_slice(
                big, row.astype(big.dtype),
                tuple(slot if a == ax else 0 for a in range(big.ndim)))

        return jax.tree_util.tree_map_with_path(put, engine_cache,
                                                row_cache)

    def _insert_rows(engine_cache, batch_cache, slot_ids, valid):
        """Insert every valid batch-prefill row into its engine slot in
        ONE device dispatch (a scan of per-row dynamic updates) instead
        of one dispatch per member. Pad rows (``valid`` False) write a
        slot's current contents back — a no-op."""

        def put(path, big, small, row, slot, ok):
            ax = leaves[_leaf_name(path)].batch_axis
            piece = jax.lax.dynamic_slice_in_dim(
                small, row, 1, axis=ax).astype(big.dtype)
            idx = tuple(slot if a == ax else 0 for a in range(big.ndim))
            cur = jax.lax.dynamic_slice(big, idx, piece.shape)
            return jax.lax.dynamic_update_slice(
                big, jnp.where(ok, piece, cur), idx)

        def body(cache, xs):
            row, slot, ok = xs
            return jax.tree_util.tree_map_with_path(
                lambda path, big, small: put(path, big, small, row, slot,
                                             ok),
                cache, batch_cache), None

        cache, _ = jax.lax.scan(
            body, engine_cache,
            (jnp.arange(slot_ids.shape[0]), slot_ids, valid))
        return cache

    return (jax.jit(_insert, donate_argnums=(0,)),
            jax.jit(_insert_rows, donate_argnums=(0,)))


class RowCache(_CacheManager):
    """Dense rows: ``slots`` × ``max_seq_len``. The parity oracle and
    the default."""

    def __init__(self, eng, *, prefix_cache_entries: int,
                 prefix_cache_bytes: Optional[int]) -> None:
        super().__init__(eng, eng.config)
        self._prefill = jax.jit(self._prefill_and_sample)
        self._continue = jax.jit(self._continue_and_sample)
        # a prompt longer than the model's declared chunk is admitted by
        # this ONE program, run chunk after chunk on the row's own 1-row
        # cache (0: the model declares none, and no prompt is long)
        self._chunk_width = int(self.cfg.prefill_chunk)
        self._chunk = jax.jit(self._chunk_and_sample)
        self._empty_row = jax.jit(self._empty_row_tree)
        self._prefill_batch = jax.jit(self._prefill_batch_and_sample)
        self._insert, self._insert_rows = _insert_programs(self._leaves)
        # LRU of prefilled prompt prefixes: (len, token bytes) → 1-row
        # cache, a batch-1 full-context row whose bytes anchor the
        # budget. _continue never mutates an entry (no donation).
        self._prefix_store: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.prefix_cache_bytes = 0  # bytes currently held
        self._prefix_row_bytes = int(sum(
            int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
            for s in jax.tree_util.tree_leaves(self._shapes)))
        self._set_prefix_budget(prefix_cache_entries, prefix_cache_bytes)

    def _prefill_and_sample(self, params, prompt, true_len, temperature,
                            top_k, top_p, seed, fold):
        logits, cache = prefill(self.cfg, params, prompt, true_len)
        tok = self._sample1(logits, seed, fold, temperature, top_k, top_p)
        return tok, cache

    def _continue_and_sample(self, params, cache, suffix, suffix_len,
                             total_len, temperature, top_k, top_p, seed):
        logits, cache = prefill_continue(
            self.cfg, params, cache, suffix, suffix_len, total_len)
        tok = self._sample1(logits, seed, jnp.int32(0), temperature,
                            top_k, top_p)
        return tok, cache

    def _empty_row_tree(self):
        """A 1-row cache as the model's first apply makes it."""
        return jax.tree_util.tree_map_with_path(
            lambda path, s: jnp.full(
                s.shape, self._leaves[_leaf_name(path)].fill, s.dtype),
            self._shapes)

    def _chunk_and_sample(self, params, cache, chunk, n_real, total_len,
                          temperature, top_k, top_p, seed, fold):
        """One chunk of a long prompt against the row it has filled so
        far; the sample is read after the last chunk only."""
        logits, cache = prefill_continue(
            self.cfg, params, cache, chunk, n_real, total_len)
        tok = self._sample1(logits, seed, fold, temperature, top_k, top_p)
        return tok, cache

    def _is_long(self, n_tokens: int) -> bool:
        return 0 < self._chunk_width < n_tokens

    def _prefill_chunks(self, sampling: tuple, tokens: np.ndarray,
                        fold: int, cache=None, start: int = 0):
        """``tokens[start:]`` through the chunk program (tail padded,
        ``true_len`` masking as in every prefill), continuing ``cache``,
        a 1-row cache that holds the first ``start`` tokens (None: an
        empty row); ``sampling`` is the request's :func:`_sampling_args`.
        Returns (next token, the row's cache, chunks run)."""
        C, L = self._chunk_width, int(tokens.size)
        if cache is None:
            cache = self._empty_row()
        tok, chunks = None, 0
        for at in range(start, L, C):
            n = min(C, L - at)
            tok, cache = self._chunk(
                self.eng._params, cache, _padded(tokens[at:at + n], C),
                jnp.asarray([n], jnp.int32), jnp.asarray([at + n], jnp.int32),
                *sampling, jnp.int32(fold))
            chunks += 1
        self.prefill_chunks += chunks
        _prefill_chunks_c.inc(chunks, model=self.eng.name)
        return tok, cache, chunks

    def _prefill_batch_and_sample(self, params, prompts, true_lens, temps,
                                  top_ks, top_ps, seeds):
        """Burst admission: same-bucket requests prefill TOGETHER — one
        compiled (B, S) prefill instead of B sequential row prefills
        (burst TTFT: ~one prefill, not B), with per-row ragged lengths
        and sampling params (the decode core's contract)."""
        logits, cache = prefill(self.cfg, params, prompts, true_lens)
        toks = self.eng._sample_rows(logits, seeds, jnp.zeros_like(seeds),
                                     temps, top_ks, top_ps)
        return toks, cache

    # -- the seam ----------------------------------------------------------

    def check_submit(self, n_prompt: int, max_new: int,
                     prefix_len: int) -> int:
        # cache disabled, or one full-context row alone would bust the
        # byte budget: honor it by serving the full prefill
        fits = self._prefix_budget_bytes >= self._prefix_row_bytes
        return prefix_len if fits else 0

    def admit(self, head, free: List[int]) -> bool:
        """Move pending requests into free slots. A BURST of them
        sharing a prompt bucket admits through ONE compiled batch
        prefill (``_admit_batch``); singletons and prefix-cached requests
        keep the row path (its compiled programs already exist)."""
        eng = self.eng
        cap = eng.admit_batch_max
        groups: dict = {}  # prompt bucket → [(req, slot)], no prefix reuse
        for slot in free:
            req = head if head is not None else eng._next_pending()
            head = None
            if req is None:
                break
            eng._admitted += 1
            if req.prefix_len or cap <= 1 or self._is_long(req.prompt.size):
                self._admit_row(req, slot)
            else:
                groups.setdefault(
                    pow2_bucket(req.prompt.size, self.cfg.max_seq_len),
                    []).append((req, slot))
        for bucket, members in groups.items():
            # chunk to the batch cap (bounds the transient HBM of the
            # extra full-context rows the batch prefill holds)
            for i in range(0, len(members), cap):
                chunk = members[i:i + cap]
                if len(chunk) == 1:
                    self._admit_row(*chunk[0])
                    continue
                try:
                    self._admit_batch(bucket, chunk)
                except _CacheInvalidated:
                    raise  # run_once/_loop closes the engine
                except Exception:  # noqa: BLE001
                    # the burst shares one device call; don't let it
                    # share the failure — retry each member through the
                    # row path, per request (the cache is intact: the
                    # prefill materialized before any donation)
                    log.exception(
                        "batched admission failed; retrying %d "
                        "request(s) individually", len(chunk))
                    for req, slot in chunk:
                        self._admit_row(req, slot)
        return eng._admitted > 0

    def replay(self, slot: int, req, tokens: np.ndarray, produced: int,
               fold: int) -> None:
        """One bucketed prefill of (prompt + emitted) re-fills the row,
        sampling the stream's NEXT token at the preserved fold index."""
        eng = self.eng
        with eng._mesh_ctx():
            tok, row_cache = self._prefill_row(req, tokens, fold)
            self.cache = self._insert(self.cache, row_cache,
                                      jnp.int32(slot))
        eng._arm_slot(req, slot, int(tok), eng.clock(), produced=produced,
                      emitted=tokens[req.prompt.size:], fold=fold)

    def _prefill_row(self, req, tokens: np.ndarray, fold: int,
                     bucket: Optional[int] = None):
        L = int(tokens.size)
        if self._is_long(L):
            return self._prefill_chunks(_sampling_args(req), tokens,
                                        fold)[:2]
        bucket = bucket or pow2_bucket(L, self.cfg.max_seq_len)
        temperature, top_k, top_p, seed = _sampling_args(req)
        return self._prefill(
            self.eng._params, _padded(tokens, bucket),
            jnp.asarray([L], jnp.int32), temperature, top_k, top_p, seed,
            jnp.int32(fold))

    def _prefix_cache_row(self, prefix: np.ndarray) -> tuple:
        """The 1-row cache holding this prefilled prefix (LRU), and the
        width at which a miss just scanned it (0: a hit ran nothing)."""
        key = (prefix.size, prefix.tobytes())
        cached = self._prefix_store.get(key)
        self._count_prefix(cached is not None)
        if cached is not None:
            self._prefix_store.move_to_end(key)
            return cached, 0
        N = prefix.size
        # sampling args are dummies — only the cache is kept
        if self._is_long(N):
            _, pcache, chunks = self._prefill_chunks(_no_sampling(), prefix,
                                                     0)
            width = chunks * self._chunk_width
        else:
            width = pow2_bucket(N, self.cfg.max_seq_len)
            _, pcache = self._prefill(
                self.eng._params, _padded(prefix, width),
                jnp.asarray([N], jnp.int32), *_no_sampling(), jnp.int32(0))
        # byte-budget admission: evict LRU until the new row fits
        # (check_submit already routed away callers that can never fit)
        while (self._prefix_store and self.prefix_cache_bytes
                + self._prefix_row_bytes > self._prefix_budget_bytes):
            self._prefix_store.popitem(last=False)
            self.prefix_cache_bytes -= self._prefix_row_bytes
        if (self.prefix_cache_bytes + self._prefix_row_bytes
                <= self._prefix_budget_bytes):
            self._prefix_store[key] = pcache
            self.prefix_cache_bytes += self._prefix_row_bytes
        _prefix_bytes_g.set(self.prefix_cache_bytes, model=self.eng.name)
        return pcache, width

    def _admit_row(self, req, slot: int) -> None:
        """Launch the prefill of the request's prompt and its insert into
        ``slot``, and queue the landing that reads the first token and
        arms the slot; a failure surfaces to THIS caller only. One device
        admission, its stretches named in the order this path runs them:
        the programs' launch, the insert's dispatch behind them, then the
        wait for the first token, which the landing ends. The request's
        own ``engine.admit`` (queue's end to insert dispatched) and
        ``engine.prefill`` (the launch) spans are recorded post hoc on
        the same boundaries, as the batch path's."""
        eng = self.eng
        S, Smax = int(req.prompt.size), self.cfg.max_seq_len
        admit = {"model": eng.name, "slot": slot, "prompt_tokens": S,
                 "batched": False, "round": eng.rounds_total}
        prefill_attrs = {"prompt_tokens": S}
        t_launch = t_insert = t_read = None

        def note_spans(end: float, status: str = "OK") -> None:
            span = eng.tracer.record(
                "engine.admit", start=t_admit,
                end=end if t_read is None else t_read,
                parent=req.ctx, attrs=admit, status=status)
            if t_launch is not None:
                eng.tracer.record(
                    "engine.prefill", start=t_launch,
                    end=end if t_insert is None else t_insert,
                    parent=span, attrs=prefill_attrs,
                    status=status if t_insert is None else "OK")

        adm = eng._open_admission(
            "prefix" if req.prefix_len
            else "chunked" if self._is_long(S) else "row",
            prompt_tokens=S)        # less a reused prefix: _continue_row
        t_admit = adm.start
        try:
            with adm:
                t_admit = eng._note_queue_wait(req)
                with eng._mesh_ctx():
                    # prefill phase opens here (prefix-row prep IS
                    # prefill work); admission was the gap since
                    # _note_queue_wait
                    t_launch = adm.enter("launch")
                    eng.rledger.mark(req.rid, reqobs.PREFILL, t_launch)
                    if req.prefix_len:
                        prefill_attrs["prefix_len"] = int(req.prefix_len)
                        tok, row_cache = self._continue_row(req, adm)
                    elif self._is_long(S):
                        prefill_attrs["bucket"] = self._chunk_width
                        tok, row_cache, adm.chunks = self._prefill_chunks(
                            _sampling_args(req), req.prompt, 0)
                        adm.width = adm.chunks * self._chunk_width
                    else:
                        adm.width = pow2_bucket(S, Smax)
                        prefill_attrs["bucket"] = adm.width
                        tok, row_cache = self._prefill_row(
                            req, req.prompt, 0, adm.width)
                    if adm.chunks:
                        prefill_attrs["chunks"] = admit["chunks"] = \
                            adm.chunks
                    t_insert = adm.enter("insert")
                    self.cache = self._insert(self.cache, row_cache,
                                              jnp.int32(slot))
                t_read = adm.suspend()
        except Exception as e:  # noqa: BLE001 — surface to the caller
            note_spans(adm.end, adm.status)
            eng._fail(req, e, adm.end)
            return

        def land() -> None:
            try:
                with adm:
                    adm.resume()
                    # the prefill-sampled first token must surface NOW —
                    # emitting it is what makes TTFT one prefill + one
                    # step
                    first = int(tok)  # tpulint: disable=TPU017
                    t_armed = adm.enter("host")
                    eng._arm_slot(req, slot, first, t_armed)
                    note_spans(t_armed)
            except Exception as e:  # noqa: BLE001 — surface to the caller
                note_spans(adm.end, adm.status)
                eng._fail(req, e, adm.end)

        self._landing.append(
            _Landing(land, [(slot, req, req.prompt, 0, 0)], adm))

    def _continue_row(self, req, adm) -> tuple:
        """A row admission's launch on the prefix path: the stored
        prefix's row (a miss prefills it first, and the admission counts
        that scan too), continued by the request's suffix. Returns
        (first token, the row's cache)."""
        S, N, Smax = int(req.prompt.size), req.prefix_len, \
            self.cfg.max_seq_len
        pcache, adm.width = self._prefix_cache_row(req.prompt[:N])
        suf = S - N
        adm.prompt_tokens = suf + (N if adm.width else 0)
        if self._is_long(suf):
            tok, row_cache, adm.chunks = self._prefill_chunks(
                _sampling_args(req), req.prompt, 0, pcache, N)
            adm.width += adm.chunks * self._chunk_width
            return tok, row_cache
        sbucket = pow2_bucket(suf, Smax)
        if N + sbucket > Smax:
            # a padded suffix would start-clamp its cache write past the
            # context end; serve the exact length (a rare boundary
            # compile)
            sbucket = suf
        adm.width += sbucket
        return self._continue(
            self.eng._params, pcache, _padded(req.prompt[N:], sbucket),
            jnp.asarray([suf], jnp.int32), jnp.asarray([S], jnp.int32),
            *_sampling_args(req))

    def _admit_batch(self, bucket: int, members: List[tuple]) -> None:
        """One shared prefill for same-bucket requests, then their rows'
        inserts as one program. Rows pad to a power-of-two batch (the
        program inventory stays batch buckets × prompt buckets); pad
        rows are length-1 junk nothing reads or inserts. Token-identical
        to the row path: same ragged per-row lengths, same
        ``fold_in(key(seed), 0)`` sampling. One device admission:
        padding on the host, the launch; then its landing: the wait for
        the first tokens, the insert's dispatch, arming."""
        eng = self.eng
        k = len(members)
        bb = pow2_bucket(k, min(eng.slots, eng.admit_batch_max))
        reqs, slot_list = zip(*members)
        adm = eng._open_admission(
            "batch", rows=k, rows_padded=bb, width=bucket,
            prompt_tokens=int(sum(r.prompt.size for r in reqs)))
        with adm:
            for req in reqs:
                eng._note_queue_wait(req)

            def col(values, pad, dtype):  # one per member, padded to bb
                return np.asarray(list(values) + [pad] * (bb - k), dtype)

            prompts = np.zeros((bb, bucket), np.int32)
            for i, req in enumerate(reqs):
                prompts[i, :req.prompt.size] = req.prompt
            lens = col((r.prompt.size for r in reqs), 1, np.int32)
            temps = col((r.temperature for r in reqs), 0.0, np.float32)
            tks = col((r.top_k for r in reqs), 0, np.int32)
            tps = col((r.top_p for r in reqs), 1.0, np.float32)
            seeds = col((r.seed for r in reqs), 0, np.int32)
            slot_ids = col(slot_list, 0, np.int32)
            valid = np.arange(bb) < k
            with eng._mesh_ctx():
                # the shared device call is one leaf on the profiler's
                # timeline and recorded below as a child of each
                # member's admit span (a context-managed span here would
                # be an orphan root: the engine thread has no active
                # span)
                p0 = adm.enter("launch")
                for req in reqs:
                    # the shared device call opens every member's
                    # prefill phase on the same already-read timestamp
                    eng.rledger.mark(req.rid, reqobs.PREFILL, p0)
                toks, bcache = self._prefill_batch(
                    eng._params, jnp.asarray(prompts), jnp.asarray(lens),
                    jnp.asarray(temps), jnp.asarray(tks),
                    jnp.asarray(tps), jnp.asarray(seeds))
            adm.suspend()

        def land() -> None:
            try:
                self._land_batch(adm, members, toks, bcache, lens,
                                 slot_ids, valid, bucket, p0)
            except _CacheInvalidated:
                raise  # run_once/_loop closes the engine
            except Exception:  # noqa: BLE001
                # as a failed launch: retry each member through the row
                # path, per request (the cache is intact: the prefill
                # is read before any donation)
                log.exception("batched admission failed; retrying %d "
                              "request(s) individually", k)
                for req, slot in members:
                    self._admit_row(req, slot)

        self._landing.append(_Landing(
            land, [(slot, req, req.prompt, 0, 0) for req, slot in members],
            adm))

    def _land_batch(self, adm, members, toks, bcache, lens, slot_ids,
                    valid, bucket: int, p0: float) -> None:
        """A batch admission's landing: its first tokens, then the
        inserts of its rows, then each member armed."""
        eng = self.eng
        k = len(members)
        with adm:
            adm.resume()
            with eng._mesh_ctx():
                # force completion (the host needs the tokens anyway)
                # BEFORE the donating inserts: a device-side prefill
                # failure must surface while the cache is intact, so
                # that the row-path fallback retries against a live
                # engine
                toks = np.asarray(toks)  # tpulint: disable=TPU017 — deliberate barrier, see above
                p1 = adm.enter("insert")
                try:
                    self.cache = self._insert_rows(
                        self.cache, bcache, jnp.asarray(slot_ids),
                        jnp.asarray(valid))
                except Exception as e:  # noqa: BLE001 — donation consumed
                    # the cache: fail the chunk retryably and escalate
                    # so that the loop closes the engine
                    t_fail = eng.clock()
                    for req, _ in members:
                        eng._fail(req, EngineClosed(
                            "engine cache invalidated during admission"),
                            t_fail)
                    raise _CacheInvalidated(str(e)) from e
            self.batch_prefills += 1
            t1 = adm.enter("host")
            for i, (req, slot) in enumerate(members):
                span = eng.tracer.record(
                    "engine.admit", start=adm.start, end=t1,
                    parent=req.ctx,
                    attrs={"model": eng.name, "slot": slot,
                           "prompt_tokens": int(lens[i]),
                           "batched": True, "batch": k,
                           "round": eng.rounds_total})
                # the shared prefill's time range, nested in THIS
                # member's trace (same shape as the row path's
                # admit→prefill)
                eng.tracer.record(
                    "engine.prefill", start=p0, end=p1, parent=span,
                    attrs={"prompt_tokens": int(lens[i]), "bucket": bucket,
                           "batched": True, "batch": k})
                eng._arm_slot(req, slot, int(toks[i]), t1)


@dataclasses.dataclass
class _PrefillJob:
    """A slot mid-chunked-prefill: the prompt feeds the pool one
    fixed-width chunk per scheduler cycle, interleaved with co-tenant
    decode steps."""

    req: Any
    slot: int
    tokens: np.ndarray        # full token sequence to prefill
    next: int                 # next position to feed (== start after arm)
    t_admit: float = 0.0
    chunks: int = 0
    # replay (cache-recovery) jobs resume a live stream: the first
    # sampled token continues at the preserved fold index and the
    # delivery counter, instead of starting a fresh request at fold 0
    fold0: int = 0
    produced0: int = 0
    store_prefix: int = 0     # prefix tokens to trie-pin after prefill
    # the sampled next token: the final chunk's, on the device until the
    # job lands
    last_tok: Any = 0


class PagedCache(_CacheManager):
    """A pool of ``kv_pages`` pages of ``kv_page_size`` tokens: only
    positions/pages carry the slot axis, the k/v pool is shared, so
    cache HBM follows LIVE tokens instead of slots × max_len."""

    admission_recovers = True

    def __init__(self, eng, *, kv_page_size: Optional[int],
                 kv_pages: Optional[int],
                 paged_attention_impl: Optional[str],
                 prefill_chunk_tokens: Optional[int],
                 prefill_chunks_per_cycle: int,
                 prefix_cache_entries: int,
                 prefix_cache_bytes: Optional[int]) -> None:
        config = eng.config
        refuse_unless_kv_cache(config, "paged=True maps pages of")
        # geometry: the largest power-of-two divisor of max_seq_len up
        # to 64; a full pool (slots × pages-per-row), where a smaller
        # kv_pages sizes HBM by LIVE tokens (admission gates on pages)
        Smax = config.max_seq_len
        if not kv_page_size:
            kv_page_size = 1
            while kv_page_size < 64 and Smax % (kv_page_size * 2) == 0:
                kv_page_size *= 2
        self.kv_page_size = int(kv_page_size)
        self._n_logical = Smax // self.kv_page_size
        self.kv_pages = int(eng.slots * self._n_logical
                            if kv_pages is None else kv_pages)
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = min(256, Smax)
        self.prefill_chunk_tokens = max(1, int(prefill_chunk_tokens))
        self.prefill_chunks_per_cycle = max(1, int(prefill_chunks_per_cycle))
        # the decode STEP's attention core, kernel / gather / auto
        # (TransformerConfig.paged_attention_impl): greedy streams are
        # token-identical either way (test-gated)
        self.paged_attention_impl = paged_attention_impl or "auto"
        cfg = dataclasses.replace(
            config, kv_page_size=self.kv_page_size, kv_pages=self.kv_pages,
            paged_attention_impl=self.paged_attention_impl)
        cfg.validate()
        super().__init__(eng, cfg)
        self._chunk = jax.jit(self._chunk_and_sample, donate_argnums=(1,))
        # page-map surgery and the COW-split page copy: the paged-cache
        # leaf contract lives in ONE module (models/decode.py)
        self._arm = jax.jit(arm_slot, donate_argnums=(0,))
        self._copy_page = jax.jit(copy_page, donate_argnums=(0,))
        # one physical page's bytes across the stacked k/v pool leaves —
        # the prefix store budgets in PAGES
        self._page_bytes = int(sum(
            int(np.prod(s.shape)) // self.kv_pages
            * jnp.dtype(s.dtype).itemsize
            for p, s in jax.tree_util.tree_leaves_with_path(self._shapes)
            if self._leaves[_leaf_name(p)].batch_axis is None))
        self._prefix_row_bytes = self._page_bytes * self._n_logical
        self._set_prefix_budget(prefix_cache_entries, prefix_cache_bytes)
        self._new_pool()

    def _new_pool(self) -> None:
        slots = self.eng.slots
        self._pool = PagePool(self.kv_pages, self.kv_page_size, slots,
                              self._n_logical)
        budget_pages = self._prefix_budget_bytes // max(1, self._page_bytes)
        self._prefix_pages = PrefixPageStore(self._pool, budget_pages)
        # host-authoritative per-slot position (the device value drifts
        # for idle/prefilling rows by design)
        self._pos_host = np.zeros((slots,), np.int64)
        self._slot_budget = np.zeros((slots,), np.int64)

    def _chunk_and_sample(self, params, cache, tokens, slot, start, true_n,
                          seed, fold, temperature, top_k, top_p):
        """One paged prefill chunk + the post-chunk sample, consumed
        only on a job's FINAL chunk (the logits feed the stream's next
        token); earlier chunks pay the one extra row-sample so that the
        whole prompt path stays a single compiled program."""
        logits, cache = prefill_chunk(self.cfg, params, cache, tokens,
                                      slot, start, true_n)
        tok = self._sample1(logits, seed, fold, temperature, top_k, top_p)
        return tok, cache

    # -- the seam ----------------------------------------------------------

    def check_submit(self, n_prompt: int, max_new: int,
                     prefix_len: int) -> int:
        # a request whose worst case exceeds the whole pool can NEVER
        # reserve — admitted, it would wedge the strict-FIFO head of
        # line forever (prefix_len stays: the store budgets per entry)
        need = self._pool.pages_needed(n_prompt + max_new)
        if need > self._pool.pages_total:
            raise ValueError(
                f"prompt {n_prompt} + max_new {max_new} needs {need} KV "
                f"pages but the pool holds only {self._pool.pages_total} "
                f"— raise kv_pages or shrink the request")
        return prefix_len

    def admit(self, head, free: List[int]) -> bool:
        """Placing a request is page-map surgery (a reservation + one
        tiny arm program); the prompt then streams into the pool through
        the chunk scheduler — no whole-row insert, no per-prompt-bucket
        program. FIFO is strict: a request that cannot reserve pages yet
        holds the line rather than being overtaken."""
        eng = self.eng
        has_active = len(free) < eng.slots
        if head is not None:  # only an idle engine waits: the line is empty
            self._waiting.append(head)
        for slot in free:
            if slot in self._prefilling:
                continue
            if not self._waiting:
                req = eng._next_pending()
                if req is None:
                    break
                self._waiting.append(req)
            if not self._place(self._waiting[0], slot):
                break  # no pages yet: keep FIFO, retry next cycle
            self._waiting.popleft()
            eng._admitted += 1
        return self._prefill_tick(has_active) or eng._admitted > 0

    def before_step(self, active: List[tuple]) -> None:
        """Map pages covering each active slot's next K writes (drawing
        down its reservation) and re-arm rows whose tables changed: page
        growth tracks LIVE tokens. The arm donates: the step's scope.
        The host's positions move on by K here, at the launch: the next
        round is launched before this one is read."""
        eng = self.eng
        for i, st in active:
            need = min(int(self._pos_host[i]) + eng.steps_per_sync,
                       int(self._slot_budget[i]), self.cfg.max_seq_len)
            if self._pool.ensure(i, need):
                # page growth stalls THIS stream's decode (the step
                # waits behind the arm); the clock is read only on
                # growth, never on the per-token emit path
                t0 = eng.clock()
                self._arm_row(i, int(self._pos_host[i]))
                self._export_page_gauges()
                eng.rledger.stall(st.req.rid, reqobs.KV_FAULT, t0,
                                  eng.clock())
        self._pos_host[[i for i, _ in active]] += eng.steps_per_sync

    def after_step(self, active: List[tuple], k: int, t0: float,
                   t1: float) -> None:
        eng = self.eng
        # one span per shared step: the burst-interleave evidence (chunk
        # spans between step spans bound any decode stall)
        eng.tracer.record(
            "engine.step", start=t0, end=t1, parent=eng._run_ctx,
            attrs={"model": eng.name, "rows": len(active), "k": k})

    def retire(self, slot: int) -> None:
        """Free the slot's pages (shared prefix pages drop one ref) and
        disarm its device row so post-retirement garbage decode writes
        scatter-drop instead of landing in reallocated pages."""
        self._pool.release_slot(slot)
        self._arm_row(slot, self.cfg.max_seq_len)
        self._pos_host[slot] = 0
        self._slot_budget[slot] = 0
        self._export_page_gauges()

    def reset(self) -> List[tuple]:
        # the old pool maps a consumed cache; prefix pages died with it.
        # Interrupted prefill jobs restart from token 0.
        jobs = list(self._prefilling.values())
        self._prefilling.clear()
        self._new_pool()
        self._export_page_gauges()
        return super().reset() + [
            (j.slot, j.req, j.tokens, j.produced0, j.fold0) for j in jobs]

    def replay(self, slot: int, req, tokens: np.ndarray, produced: int,
               fold: int) -> None:
        eng = self.eng
        budget = req.prompt.size + req.max_new
        try:
            self._pool.reserve(slot, self._pool.pages_needed(budget))
            self._pool.ensure(slot, int(tokens.size))
        except OutOfPages:
            # replays reserve WITHOUT prefix sharing (the store died
            # with the old pool), so a load that only fit shared may not
            # fit now: fail just those streams, retryably
            log.warning("slot %d replay does not fit the rebuilt pool "
                        "(prefix sharing lost); failing it retryably", slot)
            eng._fail(req, EngineClosed(
                "engine cache recovered; stream evicted — retry"),
                eng.clock())
            return
        self._begin(_PrefillJob(
            req=req, slot=slot, tokens=tokens, next=0,
            t_admit=eng.clock(), fold0=fold, produced0=produced), budget)

    def snapshot(self) -> dict:
        """The page-pool fields the capacity planner reads — token-level
        occupancy, which saturates long before slot count when contexts
        run long."""
        return {
            "paged": True,
            "page_size": self.kv_page_size,
            "pages_total": self._pool.pages_total,
            "pages_free": self._pool.pages_free,
            "pages_in_use": self._pool.pages_in_use,
            "pages_reserved": self._pool.reserved_total,
            # reclaimable prefix-store pins: occupancy consumers
            # (autoscaler) subtract these — cache is not load
            "pages_evictable": self._prefix_pages.pages_evictable,
            "prefill_slots": len(self._prefilling),
            "paged_attention_impl": self.paged_attention_impl,
            # prefix-trie + copy-on-write effectiveness counters
            # (docs/OBSERVABILITY.md; served by /api/metrics/engine)
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_pages_shared": self.prefix_pages_shared,
            "cow_splits": self.cow_splits,
        }

    def _arm_row(self, slot: int, start: int) -> None:
        """Point the slot's device row at the host's table and ``start``
        (``max_seq_len`` disarms it). Donates the cache."""
        with self.eng._mesh_ctx():
            self.cache = self._arm(
                self.cache, jnp.int32(slot), jnp.int32(start),
                jnp.asarray(self._pool.table_row(slot)))

    def _begin(self, job: _PrefillJob, budget: int) -> None:
        """Arm the job's slot at its first position and queue its
        prompt for the chunk scheduler."""
        self._arm_row(job.slot, job.next)
        self._prefilling[job.slot] = job
        self._pos_host[job.slot] = job.next
        self._slot_budget[job.slot] = budget
        self._export_page_gauges()

    def _export_page_gauges(self) -> None:
        """One write site for the pool-occupancy gauges, so in_use /
        free / evictable / held can never drift apart between call
        sites."""
        name, store = self.eng.name, self._prefix_pages
        _kv_pages_g.set(self._pool.pages_in_use, model=name)
        _kv_pages_free_g.set(self._pool.pages_free, model=name)
        _kv_pages_evictable_g.set(store.pages_evictable, model=name)
        _prefix_bytes_g.set(store.pages_held * self._page_bytes,
                            model=name)

    def _place(self, req, slot: int) -> bool:
        """Reserve + map pages for a request and arm its slot; False
        when the pool cannot cover it yet (caller retries).

        Prefix sharing is trie-matched per PAGE: the longest stored
        chain of full pages maps in read-only and, when the WHOLE
        aligned prefix matched, the partial boundary page copy-on-write.
        The COW split (one device page copy) runs HERE, before the slot
        is armed: the shared decode step writes through EVERY armed row
        (a mid-prefill row's device position drifts by design), so a slot
        may never sit armed while its table points a writable logical
        page at KV someone else reads."""
        eng = self.eng
        S = req.prompt.size
        pool = self._pool
        store = self._prefix_pages
        match = (store.match(req.prompt, req.prefix_len)
                 if req.prefix_len else None)
        shared = match.pages if match else []
        # the COW boundary page is NOT subtracted: its split draws a
        # fresh page from this very reservation
        n_res = pool.pages_needed(S + req.max_new) - len(shared)
        # idle prefix pages are reclaimable capacity: evict LRU leaves
        # (never a page this request is about to share) before refusing
        protect = set(shared)
        if match is not None and match.tail_page is not None:
            protect.add(match.tail_page)
        while not pool.can_reserve(n_res) and store.evict_lru(
                protect=protect):
            pass
        if not pool.can_reserve(n_res):
            return False
        pool.reserve(slot, n_res)
        if req.prefix_len:
            # count on the admission that LANDS (placement may retry
            # the same head-of-line request across cycles)
            self._count_prefix(match.hit)
            if match.hit:
                n_shared = len(shared) + (match.tail_page is not None)
                self.prefix_pages_shared += n_shared
                _prefix_pages_shared_c.inc(n_shared, model=eng.name)
        for logical, page in enumerate(shared):
            pool.map_shared(slot, logical, page)
        start = len(shared) * self.kv_page_size
        if match is not None and match.tail_page is not None:
            # map_cow FIRST: the slot's ref keeps the boundary page
            # alive even if store eviction (racing this placement for
            # pages) unpins the entry; then split at once: arming makes
            # the row writable by the very next shared step
            logical = len(shared)
            pool.map_cow(slot, logical, match.tail_page)
            src, dst = pool.cow_split(slot, logical)
            with eng._mesh_ctx():
                self.cache = self._copy_page(
                    self.cache, jnp.int32(src), jnp.int32(dst))
            self.cow_splits += 1
            _cow_splits_c.inc(model=eng.name)
            start += match.tail_len
        pool.ensure(slot, S)  # prompt pages; decode pages grow lazily
        self._begin(_PrefillJob(
            req=req, slot=slot, tokens=req.prompt, next=start,
            t_admit=eng._note_queue_wait(req),
            store_prefix=req.prefix_len), S + req.max_new)
        return True

    def _prefill_tick(self, has_active: bool) -> bool:
        """Run chunked-prefill work for this cycle.

        With co-tenant decode in flight, at most ``prefill_chunks_per_
        cycle`` chunk programs run before the next shared decode step:
        that bounds any decode stall to one chunk during a burst admit.
        On an idle engine the oldest job runs to completion (nobody to
        stall, its TTFT wins), then decode starts and later jobs
        interleave."""
        if not self._prefilling:
            return False
        budget = self.prefill_chunks_per_cycle if has_active else None
        for slot, job in list(self._prefilling.items()):
            done = False
            while not done and (budget is None or budget > 0):
                done = self._run_chunk(job)
                if budget is not None:
                    budget -= 1
            if done:
                del self._prefilling[slot]
                self._landing.append(_Landing(
                    lambda job=job: self._finalize(job),
                    [(job.slot, job.req, job.tokens, job.produced0,
                      job.fold0)]))
            # idle engine: the first stream is live, decode now
            # interleaves with the remaining jobs
            if budget is None or budget <= 0:
                break
        return True

    def _run_chunk(self, job: _PrefillJob) -> bool:
        """One chunk program for one slot; True when the job's token
        stream is fully prefilled (``job.last_tok`` then holds the
        sampled next token)."""
        eng = self.eng
        req = job.req
        C = self.prefill_chunk_tokens
        total = int(job.tokens.size)
        n = min(C, total - job.next)
        final = job.next + n >= total
        t0 = eng.clock()
        if job.chunks == 0:
            # first chunk: the record's prefill phase opens here and
            # runs to the first token (the span below is per chunk)
            eng.rledger.mark(req.rid, reqobs.PREFILL, t0)
        temperature, top_k, top_p, seed = _sampling_args(req)
        with eng._mesh_ctx():
            tok, self.cache = self._chunk(
                eng._params, self.cache,
                _padded(job.tokens[job.next:job.next + n], C),
                jnp.int32(job.slot), jnp.int32(job.next), jnp.int32(n),
                seed, jnp.int32(job.fold0), temperature, top_k, top_p)
            if final:
                # the sampled next token, read when the job lands (the
                # read surfaces a failure while it is still recoverable
                # in this cycle)
                job.last_tok = tok
        job.next += n
        job.chunks += 1
        self.prefill_chunks += 1
        _prefill_chunks_c.inc(model=eng.name)
        eng.rledger.note_chunk(req.rid)
        eng.tracer.record(
            "engine.prefill_chunk", start=t0, end=eng.clock(),
            parent=req.ctx,
            attrs={"model": eng.name, "slot": job.slot,
                   "tokens": int(n), "final": final})
        return final

    def _finalize(self, job: _PrefillJob) -> None:
        """Prompt fully in the pool (the job's landing): read the sampled
        token, pin shareable prefix pages, emit the token and arm the
        slot's host-side decode state."""
        eng = self.eng
        req, slot = job.req, job.slot
        job.last_tok = int(job.last_tok)  # tpulint: disable=TPU017
        now = eng.clock()
        if job.store_prefix:
            # idempotent trie insert: stored chain pages are only
            # LRU-touched; a partial hit pins the NEW pages and the tail
            self._prefix_pages.store(req.prompt, job.store_prefix, slot)
            self._export_page_gauges()
        eng.tracer.record(
            "engine.admit", start=job.t_admit, end=now, parent=req.ctx,
            attrs={"model": eng.name, "slot": slot,
                   "prompt_tokens": int(req.prompt.size),
                   "chunked": True, "chunks": job.chunks,
                   "round": eng.rounds_total})
        self._pos_host[slot] = job.tokens.size
        if not eng._arm_slot(req, slot, job.last_tok, now,
                             produced=job.produced0,
                             emitted=job.tokens[req.prompt.size:],
                             fold=job.fold0):
            self.retire(slot)
