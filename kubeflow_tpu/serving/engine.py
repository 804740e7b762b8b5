"""Continuous-batching decode engine: concurrent generate requests share
one compiled decode step.

The reference platform's serving tier batches at the RPC layer
(TF-Serving's ``enable_batching`` scheduler,
``/root/reference/kubeflow/tf-serving/tf-serving-template.libsonnet:33-48``)
— whole requests queue for a fixed-shape batch. That is the wrong shape
for autoregressive decoding, where a request is a *sequence* of steps:
batching whole requests serializes callers behind the longest
generation. TPU-first, the engine instead owns a persistent device-side
KV cache with ``slots`` independent rows and runs ONE compiled
single-token step over all of them, forever:

- **submit** — a request (prompt + sampling params) joins the admission
  queue; its prompt is prefilled at batch 1 into a fresh cache row
  (one compiled prefill per power-of-two prompt bucket, exactly the
  unary path's bucketing) and the row is written into a free slot of
  the engine cache with one ``dynamic_update_slice`` (the compiled
  *insert* — cheap: it touches one row);
- **step** — every active slot advances one token under one jit:
  per-row cache positions (the decode core's ragged-batch contract,
  ``kubeflow_tpu/models/transformer.py:_decode_attend``), per-row
  sampling parameters, and per-row PRNG keys derived as
  ``fold_in(key(seed), step_index)`` so a request's tokens are
  reproducible regardless of which co-tenants share its batch;
- tokens stream to per-request queues the moment the host sees them —
  time-to-first-token is one prefill + one step, not one full
  generation.

Static shapes everywhere: the engine batch is fixed at ``slots``, idle
rows decode garbage that nothing reads (their writes land in rows the
next insert overwrites), and the compiled-program inventory is small
and bounded: prefill (per prompt bucket), the burst batch-prefill (per
batch-bucket × prompt-bucket — a burst of same-bucket requests admits
through ONE prefill instead of sequential row prefills), insert (whole
row and from-batch-row variants), the general sampled step, the
all-greedy argmax step (dispatched whenever no in-flight request
samples — it skips the per-row sampler entirely), and the
prefix-continuation (per suffix bucket). ``precompile=True`` builds
both STEP programs up front, so a greedy↔sampled workload shift never
pauses co-tenant decode on an XLA compile. Prefill programs (row and
batch) compile lazily on the first request of each shape, and since
admission and stepping share the engine thread that first-shape compile
does pause in-flight streams — pre-existing row-path behavior; the
batch path adds batch-bucket shapes to the inventory
(``KFTPU_ADMIT_BATCH=0`` pins admission back to the row path's one
program per prompt bucket if that matters more than burst TTFT).
"""
# tpulint: disable-file=TPU018 — the engine's per-bucket program
# inventory compiles lazily on first dispatch and is billed by the
# process-wide CompileLedger monitoring listener; routing these sites
# through timed_compile would AOT-compile via .lower().compile(),
# which does NOT populate jax's jit dispatch cache, so every program
# would compile twice. `precompile=True` is the engine's warm path.

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import queue
import threading
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.decode import (
    arm_slot,
    copy_page,
    decode_step_stats,
    prefill,
    prefill_chunk,
    prefill_continue,
    sample_logits,
)
from kubeflow_tpu.serving.kvpool import (
    OutOfPages,
    PagePool,
    PrefixPageStore,
)
from kubeflow_tpu.obs import (
    SpanContext,
    Tracer,
    current_context,
    profiler_annotator,
)
from kubeflow_tpu.obs import requests as reqobs
from kubeflow_tpu.utils import DEFAULT_REGISTRY
from kubeflow_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

_steps_total = DEFAULT_REGISTRY.counter(
    "kftpu_engine_steps_total", "shared decode steps executed")
_tokens_total = DEFAULT_REGISTRY.counter(
    "kftpu_engine_tokens_total", "tokens produced by the decode engine")
_round_seconds = DEFAULT_REGISTRY.counter(
    "kftpu_engine_round_seconds_total",
    "engine-thread seconds by round phase (wait, admit, step, sync, "
    "emit): the rate by phase is where the thread's time goes")
_occupancy = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_active_slots", "active slots in the decode batch")
_slots_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_slots",
    "decode-slot capacity of the engine (static; scrapers read it so "
    "queue depth can be priced in slot units without a config hint)")
_queue_depth = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_pending_requests", "requests waiting for a slot")
_prefix_hits = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefix_hits_total", "prefix-cache hits at admission")
_prefix_misses = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefix_misses_total", "prefix-cache misses at admission")
_prefix_bytes_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_prefix_cache_bytes",
    "HBM bytes held by cached prompt-prefix KV rows")
_prefix_budget_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_prefix_cache_budget_bytes",
    "prefix-cache byte budget (entries evict LRU to stay under it)")
_queue_wait_h = DEFAULT_REGISTRY.histogram(
    "engine_queue_wait_seconds",
    "time a generate request waits for a decode slot")
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

_recoveries_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_recoveries_total",
    "engine cache rebuild-and-replay events after a failed donating "
    "device call (each one is a device fault survived, never routine)")

_moe_pairs_c = DEFAULT_REGISTRY.counter(
    "kftpu_moe_routed_pairs_total",
    "routed (token, expert) pairs that fell on experts held here, summed "
    "over the routed layers and the rows of every decode step")
_moe_hit_c = DEFAULT_REGISTRY.counter(
    "kftpu_moe_experts_hit_total",
    "distinct held experts hit, summed over the routed layers of every "
    "decode step: the expert weights a step had to read")

_END = object()  # per-request stream sentinel

# an ``engine.round`` span's ``<phase>_s`` attrs and the ``phase`` label
# of kftpu_engine_round_seconds_total, in the order a round passes them
_ROUND_PHASES = ("wait", "admit", "step", "sync", "emit")


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

    Total on its edges (chunked prefill makes bucket selection hot, so
    callers no longer pre-clamp): ``n <= 0`` buckets to the smallest
    program (1), ``n >= cap`` to exactly ``cap`` — even a non-power-of-
    two cap, which is its own terminal bucket (the max_seq_len program).
    """
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


@dataclasses.dataclass
class _Request:
    prompt: np.ndarray           # (S,) int32, true length (no padding)
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    seed: int
    eos_id: Optional[int]
    # first N prompt tokens are a reusable prefix (shared system
    # prompt): its prefill is served from the engine's prefix cache
    prefix_len: int = 0
    # trace context captured at submit() — the engine thread parents its
    # queue-wait/admit/decode spans onto the submitting request's span
    ctx: Optional[SpanContext] = None
    t_submit: float = 0.0
    # request-ledger key (docs/OBSERVABILITY.md "Request lifecycle"):
    # the propagated trace id when one exists — so the edge's record
    # and the engine's phases join — else a synthetic 32-hex id
    rid: str = ""
    # queue-wait recorded once: a failed batch admission retries members
    # through the row path, which must not observe the wait twice
    _wait_noted: bool = False
    out: "queue.Queue[Any]" = dataclasses.field(
        default_factory=queue.Queue)
    error: Optional[Exception] = None
    # consumed tokens, so stream()/result() are replayable (a second
    # call must not block on the drained queue)
    _seen: List[int] = dataclasses.field(default_factory=list)
    _done: bool = False

    def stream(self):
        """Yield token ids as the engine produces them (replayable:
        tokens already consumed are yielded first)."""
        yield from list(self._seen)
        while not self._done:
            tok = self.out.get()
            if tok is _END:
                self._done = True
                if self.error is not None:
                    raise self.error
                return
            self._seen.append(tok)
            yield tok
        if self.error is not None:
            raise self.error

    def result(self) -> List[int]:
        return list(self.stream())


@dataclasses.dataclass
class _Slot:
    req: _Request
    produced: int = 0  # tokens emitted so far (1 after the prefill sample);
    # the device-facing step/token state lives in the engine's host-side
    # arrays (_stepidx/_tokens) — the slot only tracks delivery
    t_decode0: float = 0.0  # decode-phase start (the decode span's start)
    # every token emitted, in order — the cache-recovery replay prompt
    # is (request prompt + emitted); delivery itself rides req.out
    emitted: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PrefillJob:
    """A slot mid-chunked-prefill (paged engine): the prompt feeds the
    pool one fixed-width chunk per scheduler cycle, interleaved with
    co-tenant decode steps."""

    req: _Request
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
    last_tok: int = 0         # sampled next token, set by the final chunk


class DecodeEngine:
    """One engine per loaded transformer model version.

    ``submit()`` is thread-safe and returns a handle whose ``stream()``
    yields tokens as decode steps complete. The engine thread runs
    admit → step forever; ``close()`` drains it.
    """

    def __init__(self, config, params, *, slots: int = 8,
                 steps_per_sync: int = 1, mesh=None,
                 prefix_cache_entries: int = 4,
                 prefix_cache_bytes: Optional[int] = None,
                 sampler_bound: Optional[int] = None,
                 sampler_impl: Optional[str] = None,
                 admit_batch_max: Optional[int] = None,
                 paged: Optional[bool] = None,
                 kv_page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 paged_attention_impl: Optional[str] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefill_chunks_per_cycle: int = 1,
                 recoveries: Optional[int] = None,
                 precompile: bool = False,
                 autostart: bool = True, name: str = "",
                 clock: Optional[Clock] = None,
                 tracer: Optional[Tracer] = None,
                 request_ledger: Optional["reqobs.RequestLedger"] = None,
                 hbm_sampler=None) -> None:
        self.config = config
        self.slots = slots
        # paged KV cache + chunked prefill (docs/SERVING.md). Dense mode
        # remains the parity oracle and the default; KFTPU_PAGED=1 flips
        # a deployment fleet-wide without code changes.
        if paged is None:
            paged = os.environ.get("KFTPU_PAGED", "0") not in ("0", "")
        self.paged = bool(paged)
        if self.paged and config.has_recurrent_state:
            raise ValueError(
                "paged=True needs a cache that positions index; this "
                "model keeps a recurrent state per slot")
        # cache-recovery budget: a donated-cache failure rebuilds the
        # pool and replays in-flight slots this many times before the
        # engine gives up and self-closes (the old, always-close path)
        if recoveries is None:
            recoveries = int(os.environ.get("KFTPU_ENGINE_RECOVERIES",
                                            "2"))
        self._recoveries_left = max(0, int(recoveries))
        # host-side timing source for queue-wait/admit/decode spans; a
        # fake clock makes engine span trees deterministic in tests
        self.clock: Clock = clock if clock is not None else time.monotonic
        # spans land in the shared collector; the profiler annotator
        # mirrors live admit/prefill spans onto the XLA host timeline
        # during a capture (docs/OBSERVABILITY.md)
        self.tracer = tracer if tracer is not None else Tracer(
            clock=self.clock, annotator=profiler_annotator())
        # what the engine THREAD did with its time hangs off one
        # ``engine.run`` root per engine (recorded at close()): the
        # per-round ``engine.round`` / ``engine.step`` children then
        # never crowd the collector's root list. Ids are made here, not
        # taken from the constructing thread's span — an engine built
        # inside its first request must not join that request's trace
        self._run_ctx = SpanContext(os.urandom(16).hex(),
                                    os.urandom(8).hex())
        self._t_run0: Optional[float] = self.clock()
        self._admitted = 0  # requests admitted in the round in progress
        # the request-lifecycle ledger (docs/OBSERVABILITY.md "Request
        # lifecycle"): phase marks ride the clock reads this file
        # already takes; the process-wide default joins edge-side
        # phases for the same trace id
        self.rledger = (request_ledger if request_ledger is not None
                        else reqobs.DEFAULT_LEDGER)
        # lax.top_k-bounded sampler (models/decode.py:sample_logits
        # ``bound``): avoids the per-token full-vocab sort the exact
        # sampler pays at every sampled step — 0 selects the exact sort
        # path, None reads KFTPU_SAMPLER_BOUND (default 64)
        if sampler_bound is None:
            sampler_bound = int(os.environ.get("KFTPU_SAMPLER_BOUND",
                                               "64"))
        self.sampler_bound = int(sampler_bound)
        # sampler implementation: "bounded" (lax.top_k, truncating —
        # the historical fast path), "exact_sort" (full-vocab sort —
        # the historical exact path), "fused" (ops/sampling.py Pallas
        # kernel: exact support at bounded cost). "auto" keeps the
        # bounded path when a bound is set and upgrades the exact path
        # (bound 0) to the fused kernel, so sampler_bound stops being a
        # correctness/perf tradeoff.
        if sampler_impl is None:
            sampler_impl = os.environ.get("KFTPU_SAMPLER_IMPL", "auto")
        if sampler_impl == "auto":
            sampler_impl = ("bounded" if self.sampler_bound > 0
                            else "fused")
        if sampler_impl not in ("bounded", "exact_sort", "fused"):
            raise ValueError(
                f"unknown sampler_impl {sampler_impl!r}; valid: auto, "
                "bounded, exact_sort, fused")
        self.sampler_impl = sampler_impl
        # paged-cache geometry: page size defaults to the largest
        # power-of-two divisor of max_seq_len up to 64; the pool
        # defaults to full provisioning (slots × pages-per-row), and a
        # smaller kv_pages sizes HBM by LIVE tokens instead of
        # slots × max_len (admission then gates on free pages)
        Smax = config.max_seq_len
        if self.paged:
            if kv_page_size is None:
                env = os.environ.get("KFTPU_KV_PAGE_SIZE")
                kv_page_size = int(env) if env else 0
            if not kv_page_size:
                kv_page_size = 1
                while (kv_page_size < 64
                       and Smax % (kv_page_size * 2) == 0):
                    kv_page_size *= 2
            self.kv_page_size = int(kv_page_size)
            self._n_logical = Smax // self.kv_page_size
            if kv_pages is None:
                env = os.environ.get("KFTPU_KV_PAGES")
                kv_pages = int(env) if env else slots * self._n_logical
            self.kv_pages = int(kv_pages)
            if prefill_chunk_tokens is None:
                env = os.environ.get("KFTPU_PREFILL_CHUNK")
                prefill_chunk_tokens = int(env) if env else min(256, Smax)
            self.prefill_chunk_tokens = max(1, int(prefill_chunk_tokens))
            self.prefill_chunks_per_cycle = max(
                1, int(prefill_chunks_per_cycle))
            # device-side attention core for the paged decode STEP:
            # "kernel" streams K/V through the page table inside a
            # Pallas kernel (ops/paged_attention.py — HBM reads
            # proportional to live pages), "gather" materializes the
            # dense logical view (the bit-parity oracle and the
            # interpret-mode fallback), "auto" picks the kernel on the
            # TPU backend and the gather elsewhere. Greedy streams are
            # token-identical either way (test-gated).
            if paged_attention_impl is None:
                paged_attention_impl = os.environ.get(
                    "KFTPU_PAGED_ATTN", "auto")
            self.paged_attention_impl = paged_attention_impl
            # paged-kernel head-group compute block: default None =
            # the shape-keyed tile table (ops/autotune.py; safe
            # fallback is the per-head loop); KFTPU_PAGED_HEAD_BLOCK
            # pins an explicit override for a chip experiment
            head_block_env = os.environ.get("KFTPU_PAGED_HEAD_BLOCK")
            paged_head_block = (int(head_block_env) if head_block_env
                                else config.paged_head_block)
            self._cfg = dataclasses.replace(
                config, kv_page_size=self.kv_page_size,
                kv_pages=self.kv_pages,
                paged_attention_impl=paged_attention_impl,
                paged_head_block=paged_head_block)
            self._cfg.validate()
        else:
            self.kv_page_size = 0
            self.kv_pages = 0
            self.paged_attention_impl = "gather"
            self._cfg = config
        # the model's declaration of its cache leaves: each leaf's row
        # axis, idle value and head axis, by name
        self._leaves = self._cfg.cache_leaves(1)
        # burst admission: same-bucket pending requests prefill as ONE
        # batch of up to this many rows. The cap bounds the transient
        # HBM spike (a batch prefill materializes that many extra
        # full-context KV rows until their inserts land) and the
        # compiled-program inventory; <=1 disables batching entirely
        # (every request takes the row path). KFTPU_ADMIT_BATCH.
        if admit_batch_max is None:
            admit_batch_max = int(os.environ.get("KFTPU_ADMIT_BATCH",
                                                 "8"))
        self.admit_batch_max = int(admit_batch_max)
        # multi-chip serving: with a Mesh (params already placed with
        # tensor-parallel shardings, e.g. via models.param_partition_specs)
        # every compiled engine program runs under it, and the model's
        # logical-axis constraints shard the KV cache over the same axes
        self.mesh = mesh
        # decode steps executed on-device per host round-trip: >1
        # amortizes the per-dispatch and readback cost over that many
        # tokens, at the price of admission/EOS reacting up to that
        # many tokens late — tokens past a row's EOS or
        # budget are computed and discarded
        self.steps_per_sync = max(1, int(steps_per_sync))
        self.name = name or "model"
        # the NORMALIZED name: every engine series must share one model
        # label value or per-model joins (slots vs pages) find no row
        _slots_g.set(self.slots, model=self.name)
        # exported at 0 from the start: "never recovered" must be a
        # readable fact, not an absent series
        _recoveries_c.inc(0, model=self.name)
        # an obs.xprof.HbmSampler sampled once per admit cycle, so the
        # admission decision's watermark (weights + KV + transient
        # prefill spike) is what kftpu_hbm_bytes{model=...} shows; CPU
        # backends (memory_stats() is None) degrade to no series
        if hbm_sampler is not None and not getattr(
                hbm_sampler, "model", ""):
            hbm_sampler.model = self.name
        self.hbm_sampler = hbm_sampler
        self._params = params
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._active: List[Optional[_Slot]] = [None] * slots
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()  # guards _active between admit/step

        if mesh is not None:
            from kubeflow_tpu.parallel.mesh import mesh_context

            self._mesh_ctx = lambda: mesh_context(mesh)
        else:
            import contextlib

            self._mesh_ctx = contextlib.nullcontext

        Smax = config.max_seq_len
        impl = self.sampler_impl
        bnd = (self.sampler_bound
               if impl == "bounded" and self.sampler_bound > 0 else None)

        def sample_rows(logits, seeds, idx, temps, tks, tps):
            """Per-row sampling under the engine's fold_in(key(seed),
            step) reproducibility contract, dispatched to the
            configured sampler implementation. (B, V) logits in, (B,)
            int32 tokens out; every parameter is per-row."""
            if impl == "fused":
                from kubeflow_tpu.ops.sampling import fused_sample
                from kubeflow_tpu.parallel.mesh import shard_kernel

                keys = jax.vmap(lambda s, i: jax.random.fold_in(
                    jax.random.key(s), i))(seeds, idx)
                # a row needs its whole vocab: on a serving mesh the
                # logits are gathered and every device samples every row
                return shard_kernel(
                    "fused_sampler",
                    lambda lg, ky, t, k, p: fused_sample(
                        lg, ky, temperature=t, top_k=k, top_p=p),
                    (logits, keys, temps, tks, tps),
                    ((None, None),) + ((None,),) * 4, temps.shape,
                    (None,), config.rules)

            def one(row_logits, seed, i, t, k, p):
                key = jax.random.fold_in(jax.random.key(seed), i)
                return sample_logits(row_logits[None], key,
                                     temperature=t, top_k=k, top_p=p,
                                     bound=bnd)[0]

            return jax.vmap(one)(logits, seeds, idx, temps, tks, tps)

        self._sample_rows = sample_rows

        def _sample1(logits, seed, fold, temperature, top_k, top_p):
            """One row through the shared sampler (prefill's first
            token; the paged path's post-chunk sample, where ``fold``
            continues a replayed stream's step index)."""
            return sample_rows(
                logits, jnp.reshape(seed, (1,)), jnp.reshape(fold, (1,)),
                jnp.reshape(temperature, (1,)), jnp.reshape(top_k, (1,)),
                jnp.reshape(top_p, (1,)))[0]

        @jax.jit
        def _prefill_and_sample(params, prompt, true_len, temperature,
                                top_k, top_p, seed, fold):
            logits, cache = prefill(config, params, prompt, true_len)
            tok = _sample1(logits, seed, fold, temperature, top_k, top_p)
            return tok, cache

        @jax.jit
        def _continue_and_sample(params, cache, suffix, suffix_len,
                                 total_len, temperature, top_k, top_p,
                                 seed):
            logits, cache = prefill_continue(
                config, params, cache, suffix, suffix_len, total_len)
            tok = _sample1(logits, seed, jnp.int32(0), temperature,
                           top_k, top_p)
            return tok, cache

        @jax.jit
        def _prefill_batch_and_sample(params, prompts, true_lens, temps,
                                      top_ks, top_ps, seeds):
            """Burst admission: same-bucket requests prefill TOGETHER —
            one compiled (B, S) prefill instead of B sequential row
            prefills, with per-row ragged lengths and sampling params
            (the decode core's contract). Burst time-to-first-token
            drops from B×prefill to ~one batched prefill."""
            logits, cache = prefill(config, params, prompts, true_lens)
            toks = sample_rows(logits, seeds,
                               jnp.zeros_like(seeds), temps, top_ks,
                               top_ps)
            return toks, cache

        self._prefill_batch = _prefill_batch_and_sample

        def _chunk_and_sample(params, cache, tokens, slot, start, true_n,
                              seed, fold, temperature, top_k, top_p):
            """One paged prefill chunk + the post-chunk sample. The
            sample is only consumed on a job's FINAL chunk (the logits
            feed the stream's next token); earlier chunks pay the one
            extra row-sample so the whole prompt path stays a single
            compiled program."""
            logits, cache = prefill_chunk(self._cfg, params, cache,
                                          tokens, slot, start, true_n)
            tok = _sample1(logits, seed, fold, temperature, top_k, top_p)
            return tok, cache

        self._chunk = jax.jit(_chunk_and_sample, donate_argnums=(1,))

        # page-map surgery program (models/decode.py:arm_slot — the
        # paged-cache leaf contract lives in ONE module)
        self._arm = jax.jit(arm_slot, donate_argnums=(0,))
        # COW-split page copy (models/decode.py:copy_page, same leaf
        # contract): one physical page duplicated device-side
        self._copy_page = jax.jit(copy_page, donate_argnums=(0,))

        def _insert_rows(engine_cache, batch_cache, slot_ids, valid):
            """Insert every valid batch-prefill row into its engine slot
            in ONE device dispatch (a scan of per-row dynamic updates)
            instead of one dispatch per member. Pad rows (``valid``
            False) write a slot's current contents back — a no-op."""

            def put(path, big, small, row, slot, ok):
                ax = self._leaves[_leaf_name(path)].batch_axis
                piece = jax.lax.dynamic_slice_in_dim(
                    small, row, 1, axis=ax).astype(big.dtype)
                idx = tuple(slot if a == ax else 0
                            for a in range(big.ndim))
                cur = jax.lax.dynamic_slice(big, idx, piece.shape)
                return jax.lax.dynamic_update_slice(
                    big, jnp.where(ok, piece, cur), idx)

            def body(cache, xs):
                row, slot, ok = xs
                return jax.tree_util.tree_map_with_path(
                    lambda path, big, small: put(path, big, small, row,
                                                 slot, ok),
                    cache, batch_cache), None

            cache, _ = jax.lax.scan(
                body, engine_cache,
                (jnp.arange(slot_ids.shape[0]), slot_ids, valid))
            return cache

        self._insert_rows = jax.jit(_insert_rows, donate_argnums=(0,))

        self._continue = _continue_and_sample
        # LRU of prefilled prompt prefixes: (len, token bytes) →
        # 1-row cache, BYTE-budgeted (every entry is a full-context row,
        # so the HBM cost scales with max_seq_len × layers — an entry
        # count hides it from the operator). Budget resolution: the
        # explicit ``prefix_cache_bytes`` arg, else KFTPU_PREFIX_CACHE_
        # BYTES, else ``prefix_cache_entries`` × the per-row byte size
        # (computed below once the cache layout is known). _continue
        # never mutates a stored entry (functional apply, no donation).
        self._prefix_store: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_cache_bytes = 0  # bytes currently held

        def _insert(engine_cache, row_cache, slot):
            def put(path, big, row):
                ax = self._leaves[_leaf_name(path)].batch_axis
                return jax.lax.dynamic_update_slice(
                    big, row.astype(big.dtype),
                    tuple(slot if a == ax else 0 for a in range(big.ndim)))

            return jax.tree_util.tree_map_with_path(put, engine_cache,
                                                    row_cache)

        self._insert = jax.jit(_insert, donate_argnums=(0,))

        K = self.steps_per_sync

        def _step(params, cache, tokens, seeds, step_idx, temps, top_k,
                  top_p):
            """K decode steps under one jit; returns (cache, (K, B)
            tokens, stats): what a model's routed layers counted,
            ``experts_hit`` and ``routed_pairs``, (K, L_moe) each; an
            empty dict, no output of the program, for a model with
            none."""

            def body(carry, t):
                cache, tokens = carry
                logits, cache, stats = decode_step_stats(
                    self._cfg, params, cache, tokens)
                nxt = sample_rows(logits, seeds, step_idx + t, temps,
                                  top_k, top_p)
                return (cache, nxt), (nxt, stats)

            (cache, _), (toks, stats) = jax.lax.scan(
                body, (cache, tokens), jnp.arange(K))
            return cache, toks, stats

        def _step_greedy(params, cache, tokens):
            """The all-greedy fast path: no per-row sampler, no vocab
            sort — argmax only. Dispatched when every in-flight request
            is greedy (the host knows each slot's sampling params), the
            common serving load and the bench configuration."""

            def body(carry, _):
                cache, tokens = carry
                logits, cache, stats = decode_step_stats(
                    self._cfg, params, cache, tokens)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (cache, nxt), (nxt, stats)

            (cache, _), (toks, stats) = jax.lax.scan(
                body, (cache, tokens), None, length=K)
            return cache, toks, stats

        self._step = jax.jit(_step, donate_argnums=(1,))
        self._step_greedy = jax.jit(_step_greedy, donate_argnums=(1,))
        self._prefill = _prefill_and_sample

        # engine cache: the decode cache shape at batch = slots. eval_
        # shape on prefill gives the layout without running it. Paged
        # mode: only positions/pages carry the batch axis — the k/v POOL
        # is batch-free (kv_pages blocks shared by every slot), which is
        # exactly how cache HBM decouples from slots × max_len.
        probe = jnp.zeros((1, 1), jnp.int32)
        shapes = jax.eval_shape(
            lambda p: prefill(self._cfg, p, probe)[1], params)

        def _engine_shape(path, s):
            """The leaf at ``slots`` rows (a pool every row shares keeps
            its shape: that is how paged cache memory decouples from
            slots x max_len)."""
            ax = self._leaves[_leaf_name(path)].batch_axis
            return tuple(slots if a == ax else d
                         for a, d in enumerate(s.shape))

        def _init_leaf(path, s):
            # every row idle (paged: disarmed, writes past max_seq_len
            # scatter-drop, and no page mapped)
            return jnp.full(_engine_shape(path, s),
                            self._leaves[_leaf_name(path)].idle_value,
                            s.dtype)

        def _zeros_tree():
            return jax.tree_util.tree_map_with_path(_init_leaf, shapes)

        if self.paged:
            # one physical page's bytes across the stacked k/v pool
            # leaves — the paged prefix store budgets in PAGES
            self._page_bytes = int(sum(
                int(np.prod(s.shape)) // self.kv_pages
                * jnp.dtype(s.dtype).itemsize
                for p, s in jax.tree_util.tree_leaves_with_path(shapes)
                if self._leaves[_leaf_name(p)].batch_axis is None))
            self._prefix_row_bytes = self._page_bytes * self._n_logical
        else:
            # a stored prefix row IS this batch-1 full-context cache —
            # its byte size anchors the prefix-cache budget
            self._prefix_row_bytes = int(sum(
                int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                for s in jax.tree_util.tree_leaves(shapes)))
        if prefix_cache_bytes is None:
            env = os.environ.get("KFTPU_PREFIX_CACHE_BYTES")
            prefix_cache_bytes = int(env) if env else None
        if prefix_cache_bytes is None:
            prefix_cache_bytes = (max(0, int(prefix_cache_entries))
                                  * self._prefix_row_bytes)
        self._prefix_budget_bytes = max(0, int(prefix_cache_bytes))
        _prefix_budget_g.set(self._prefix_budget_bytes, model=self.name)

        if mesh is None:
            self._fresh_cache = _zeros_tree
            self._cache = _zeros_tree()
        else:
            # leaves that declare a heads axis (k/v) shard it
            # per the model's logical rules, so the full-context cache
            # never materializes on one device; shape_aware_spec drops
            # the axis when it doesn't divide (GQA kv heads < tp),
            # counted in whole heads of ``head_width`` elements
            from jax.sharding import NamedSharding

            from kubeflow_tpu.parallel.mesh import (
                logical_to_mesh_axes,
                shape_aware_spec,
            )

            def _sharding(path, s):
                shape = list(_engine_shape(path, s))
                names = [None] * len(shape)
                leaf = self._leaves[_leaf_name(path)]
                if leaf.heads_axis is not None:
                    names[leaf.heads_axis] = "heads"
                    shape[leaf.heads_axis] //= leaf.head_width
                spec = shape_aware_spec(
                    logical_to_mesh_axes(names, config.rules),
                    tuple(shape), mesh)
                return NamedSharding(mesh, spec)

            sharded_zeros = jax.jit(
                _zeros_tree,
                out_shardings=jax.tree_util.tree_map_with_path(
                    _sharding, shapes))

            def _fresh_sharded():
                with self._mesh_ctx():
                    return sharded_zeros()

            self._fresh_cache = _fresh_sharded
            self._cache = _fresh_sharded()
        # host-side per-slot sampling state, padded to the batch
        self._tokens = np.zeros((slots,), np.int32)
        self._seeds = np.zeros((slots,), np.int32)
        self._stepidx = np.zeros((slots,), np.int32)
        self._temps = np.zeros((slots,), np.float32)
        self._topk = np.zeros((slots,), np.int32)
        self._topp = np.ones((slots,), np.float32)
        self.steps_total = 0
        self.rounds_total = 0  # run_once cycles that did work
        self.tokens_total = 0
        self.greedy_steps = 0  # steps served by the argmax fast path
        self.batch_prefills = 0  # burst admissions served batched
        self.prefill_chunks = 0  # chunk programs run (paged scheduler)
        self.recoveries = 0      # cache rebuild-and-replay events
        self.prefix_pages_shared = 0  # pages mapped from the trie
        self.cow_splits = 0      # boundary-page copy-on-write splits
        if self.paged:
            self._pool = PagePool(self.kv_pages, self.kv_page_size,
                                  slots, self._n_logical)
            budget_pages = self._prefix_budget_bytes // max(
                1, self._page_bytes)
            self._prefix_pages = PrefixPageStore(self._pool, budget_pages)
            # slots mid-chunked-prefill, oldest first (insertion order)
            self._prefilling: "collections.OrderedDict[int, _PrefillJob]" \
                = collections.OrderedDict()
            # head-of-line requests admission popped but could not place
            # (no free slot pages yet) — FIFO order is preserved
            self._waiting: "collections.deque[_Request]" = \
                collections.deque()
            # host-authoritative per-slot position (the device value
            # drifts for idle/prefilling rows by design)
            self._pos_host = np.zeros((slots,), np.int64)
            self._slot_budget = np.zeros((slots,), np.int64)
        if precompile:
            self._precompile_steps()
        if autostart:
            self.start()

    def _precompile_steps(self) -> None:
        """Run BOTH step programs once on the empty batch so the
        greedy↔sampled dispatch switch never stalls in-flight streams
        on a mid-serving XLA compile. Every slot is idle, so the junk
        tokens land in rows the next insert fully overwrites."""
        B = self.slots
        toks = jnp.zeros((B,), jnp.int32)
        vec_i = jnp.zeros((B,), jnp.int32)
        ones_f = jnp.ones((B,), jnp.float32)
        with self._mesh_ctx():
            self._cache, _, _ = self._step_greedy(
                self._params, self._cache, toks)
            self._cache, _, _ = self._step(
                self._params, self._cache, toks, vec_i, vec_i, ones_f,
                vec_i, ones_f)

    # -- public API --------------------------------------------------------

    def submit(self, prompt, *, max_new: int, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0,
               eos_id: Optional[int] = None,
               prefix_len: int = 0) -> _Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size + max_new > self.config.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"context {self.config.max_seq_len}")
        if self.paged:
            # a request whose worst case exceeds the whole pool can
            # NEVER reserve (even with every prefix entry evicted) —
            # admitting it would wedge the strict-FIFO head of line
            # forever, so reject it here instead
            need = self._pool.pages_needed(prompt.size + max_new)
            if need > self._pool.pages_total:
                raise ValueError(
                    f"prompt {prompt.size} + max_new {max_new} needs "
                    f"{need} KV pages but the pool holds only "
                    f"{self._pool.pages_total} — raise kv_pages or "
                    f"shrink the request")
        prefix_len = int(prefix_len)
        if prefix_len and not 0 < prefix_len < prompt.size:
            raise ValueError(
                f"prefix_len {prefix_len} must be in (0, prompt length "
                f"{prompt.size}) — the suffix may not be empty")
        if prefix_len and self.config.has_recurrent_state:
            raise ValueError(
                "prefix reuse continues a row from a stored prefix; this "
                "model keeps a recurrent state, which the prefix store "
                "does not snapshot")
        if (not self.paged
                and self._prefix_budget_bytes < self._prefix_row_bytes):
            # cache disabled, or one full-context row alone would bust
            # the byte budget: honor it by serving the full prefill.
            # (Paged mode shares at PAGE granularity — its store
            # enforces the page budget per entry itself.)
            prefix_len = 0
        req = _Request(prompt=prompt, max_new=max_new,
                       temperature=float(temperature), top_k=int(top_k),
                       top_p=float(top_p), seed=int(seed), eos_id=eos_id,
                       prefix_len=prefix_len,
                       # the submitting thread's active span (serving
                       # handler) — engine spans parent onto it
                       ctx=current_context(), t_submit=self.clock())
        # the lock orders this against close()'s drain: a submit must
        # either land before the drain (and be failed by it) or see the
        # stop flag and raise — never sit in a queue nobody reads
        # ledger key: join the propagated trace's record (the edge may
        # already have started it) or open a fresh engine-only record.
        # Started BEFORE the queue put — the engine thread may admit
        # the request immediately, and its marks must find the record
        req.rid = (req.ctx.trace_id if req.ctx is not None
                   else reqobs.synthetic_rid())
        self.rledger.start(req.rid, t=req.t_submit, model=self.name)
        with self._lock:
            if self._stop.is_set():
                # the request is over (503 to the caller): close its
                # record — whichever tier opened it
                self.rledger.finish(req.rid, req.t_submit)
                raise EngineClosed("decode engine closed")
            self._pending.put(req)
        _queue_depth.set(self._pending.qsize(), model=self.name)
        return req

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"decode-engine-{self.name}")
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # fail whatever is still in flight — a hung client is worse than
        # a retried request (version retirement path). The lock pairs
        # with submit(): after this drain no new request can enqueue.
        with self._lock:
            active = [s.req for s in self._active if s is not None]
            self._active = [None] * self.slots
            if self.paged:
                active.extend(j.req for j in self._prefilling.values())
                self._prefilling.clear()
                active.extend(self._waiting)
                self._waiting.clear()
            while True:
                try:
                    active.append(self._pending.get_nowait())
                except queue.Empty:
                    break
        t_close = self.clock()
        for req in active:
            req.error = EngineClosed("decode engine closed")
            req.out.put(_END)
            # the stream is over for its client: fold what we know
            self.rledger.finish(req.rid, t_close)
        if self._t_run0 is not None:  # the rounds' root, once
            self.tracer.record(
                "engine.run", start=self._t_run0, end=t_close,
                trace_id=self._run_ctx.trace_id,
                span_id=self._run_ctx.span_id,
                attrs={"model": self.name, "rounds": self.rounds_total,
                       "steps": self.steps_total})
            self._t_run0 = None

    @property
    def closed(self) -> bool:
        """True once the engine can no longer serve (explicit close or
        a step failure that invalidated the donated cache)."""
        return self._stop.is_set()

    @property
    def active_count(self) -> int:
        """Slots serving a stream: decoding, plus (paged) slots whose
        prompt is still chunk-prefilling — they hold pages and a slot
        either way."""
        with self._lock:
            n = sum(s is not None for s in self._active)
        if self.paged:
            n += len(self._prefilling)
        return n

    @property
    def pending_count(self) -> int:
        """Requests admitted to submit() but not yet holding a slot."""
        n = self._pending.qsize()
        if self.paged:
            n += len(self._waiting)
        return n

    def snapshot(self) -> dict:
        """Occupancy snapshot for the autoscaler's engine poll
        (:meth:`kubeflow_tpu.autoscale.metrics.MetricsAggregator
        .observe_engine`): active slots are the concurrency the proxy
        can't see (one HTTP generate call hides a whole decode stream),
        pending is the admission-queue depth. Paged engines add the
        page-pool fields the capacity planner reads — token-level
        occupancy, which saturates long before slot count when contexts
        run long."""
        snap = {"active_slots": self.active_count,
                "pending": self.pending_count,
                "slots": self.slots,
                "closed": self.closed,
                "recoveries": self.recoveries}
        if self.paged:
            snap.update({
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
            })
        return snap

    # -- engine internals --------------------------------------------------

    def _prefix_cache_row(self, prefix: np.ndarray):
        """The 1-row cache holding this prefilled prefix (LRU)."""
        key = (prefix.size, prefix.tobytes())
        cached = self._prefix_store.get(key)
        if cached is not None:
            self._prefix_store.move_to_end(key)
            self.prefix_hits += 1
            _prefix_hits.inc(model=self.name)
            return cached
        self.prefix_misses += 1
        _prefix_misses.inc(model=self.name)
        N = prefix.size
        bucket = pow2_bucket(N, self.config.max_seq_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :N] = prefix
        # sampling args are dummies — only the cache is kept
        _, pcache = self._prefill(
            self._params, jnp.asarray(padded),
            jnp.asarray([N], jnp.int32), jnp.float32(0.0),
            jnp.int32(0), jnp.float32(1.0), jnp.int32(0), jnp.int32(0))
        # byte-budget admission: evict LRU until the new row fits
        # (submit() already routed away callers that can never fit)
        while (self._prefix_store and self.prefix_cache_bytes
                + self._prefix_row_bytes > self._prefix_budget_bytes):
            self._prefix_store.popitem(last=False)
            self.prefix_cache_bytes -= self._prefix_row_bytes
        if (self.prefix_cache_bytes + self._prefix_row_bytes
                <= self._prefix_budget_bytes):
            self._prefix_store[key] = pcache
            self.prefix_cache_bytes += self._prefix_row_bytes
        _prefix_bytes_g.set(self.prefix_cache_bytes, model=self.name)
        return pcache

    def _note_queue_wait(self, req: _Request) -> float:
        """Close out the request's queue phase: one span + the
        ``engine_queue_wait_seconds`` histogram. Returns now. Idempotent
        per request — the row-path retry after a failed batch admission
        must not observe the wait twice."""
        now = self.clock()
        if req._wait_noted:
            return now
        req._wait_noted = True
        wait = max(0.0, now - req.t_submit)
        # exemplar: the request's propagated trace, so a slow queue-wait
        # bucket opens the trace that actually waited
        _queue_wait_h.observe(
            wait,
            exemplar_trace_id=(req.ctx.trace_id
                               if req.ctx is not None else None),
            model=self.name)
        self.tracer.record("engine.queue_wait", start=req.t_submit,
                           end=now, parent=req.ctx,
                           attrs={"model": self.name})
        # the ledger's queue phase closes on the same timestamp: slot
        # placement / batch assembly time is admission from here on
        self.rledger.mark(req.rid, reqobs.ADMISSION, now)
        return now

    def _admit_one(self, req: _Request, slot: int) -> None:
        """Prefill the request's prompt and write it into ``slot``."""
        self._note_queue_wait(req)
        S = req.prompt.size
        with self.tracer.span("engine.admit", parent=req.ctx, attrs={
                "model": self.name, "slot": slot,
                "prompt_tokens": int(S), "batched": False,
                "round": self.rounds_total}), \
                self._mesh_ctx():
            # prefill phase opens here (prefix-row prep IS prefill
            # work); admission was the gap since _note_queue_wait
            self.rledger.mark(req.rid, reqobs.PREFILL, self.clock())
            if req.prefix_len:
                N = req.prefix_len
                pcache = self._prefix_cache_row(req.prompt[:N])
                suf = S - N
                sbucket = pow2_bucket(suf, self.config.max_seq_len)
                if N + sbucket > self.config.max_seq_len:
                    # a padded suffix would start-clamp its cache write
                    # past the context end; serve the exact length (a
                    # rare boundary compile, like the unary tail case)
                    sbucket = suf
                padded = np.zeros((1, sbucket), np.int32)
                padded[0, :suf] = req.prompt[N:]
                with self.tracer.span("engine.prefill", attrs={
                        "prompt_tokens": int(S),
                        "prefix_len": int(N)}):
                    tok, row_cache = self._continue(
                        self._params, pcache, jnp.asarray(padded),
                        jnp.asarray([suf], jnp.int32),
                        jnp.asarray([S], jnp.int32),
                        jnp.float32(req.temperature),
                        jnp.int32(req.top_k),
                        jnp.float32(req.top_p), jnp.int32(req.seed))
            else:
                bucket = pow2_bucket(S, self.config.max_seq_len)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :S] = req.prompt
                with self.tracer.span("engine.prefill", attrs={
                        "prompt_tokens": int(S), "bucket": bucket}):
                    tok, row_cache = self._prefill(
                        self._params, jnp.asarray(padded),
                        jnp.asarray([S], jnp.int32),
                        jnp.float32(req.temperature),
                        jnp.int32(req.top_k), jnp.float32(req.top_p),
                        jnp.int32(req.seed), jnp.int32(0))
            self._cache = self._insert(self._cache, row_cache,
                                       jnp.int32(slot))
        # the prefill-sampled first token must surface NOW — emitting it
        # is what makes TTFT one prefill + one step
        self._finalize_admission(req, slot, int(tok))  # tpulint: disable=TPU017

    def _finalize_admission(self, req: _Request, slot: int, first: int,
                            t: Optional[float] = None) -> None:
        """Emit the prefill-sampled first token and arm the slot's
        host-side step state — shared by the row and batch admission
        paths so their slot initialization can never diverge. ``t`` is
        the caller's already-read timestamp (the batch path stamps the
        whole chunk once); the row path reads its own, as before."""
        t = t if t is not None else self.clock()
        st = _Slot(req=req, t_decode0=t)
        # the TTFT span: one per request, edge-to-first-token visible
        # in the trace tree the dashboard exemplar opens
        self.tracer.record(
            "engine.first_token", start=req.t_submit, end=t,
            parent=req.ctx,
            attrs={"model": self.name,
                   "ttft_ms": round((t - req.t_submit) * 1000.0, 3)})
        self._emit(st, first, t)
        if not self._finished(st, first, t):
            with self._lock:
                self._active[slot] = st
        self._tokens[slot] = first
        self._seeds[slot] = req.seed
        self._stepidx[slot] = 1
        self._temps[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p

    def _emit(self, slot: _Slot, token: int, t: float) -> None:
        """The per-token hot path. ``t`` is a timestamp the caller
        ALREADY read (run_once stamps one step-end time for every token
        of the sync batch — the moment the host actually saw them);
        neither this method nor the ledger reads a clock here."""
        slot.produced += 1
        slot.emitted.append(token)
        self.tokens_total += 1
        _tokens_total.inc(model=self.name)
        self.rledger.emit(slot.req.rid, t)
        slot.req.out.put(token)

    def _finished(self, slot: _Slot, token: int, t: float) -> bool:
        done = (slot.produced >= slot.req.max_new or
                (slot.req.eos_id is not None and token == slot.req.eos_id))
        if done:
            slot.req.out.put(_END)
            # last token: fold the request's record (histograms +
            # flight ring) on the same already-read timestamp
            self.rledger.finish(slot.req.rid, t)
        return done

    def run_once(self, timeout: float = 0.1) -> bool:
        """One admit + prefill-chunk + step cycle; returns True if any
        work happened. The background loop calls this forever; tests
        call it directly (``autostart=False``) for deterministic
        schedules. A donating device call that fails mid-decode is
        recovered in place (cache rebuild + slot replay) while the
        recovery budget lasts.

        A cycle that did work is one ``engine.round`` span: the loop
        reads its phase boundaries itself (``marks``: admit, then step /
        sync / emit as the round reaches them) and names the same
        phases on the profiler's host timeline, so every instant of the
        engine thread lies inside one ``engine.*`` annotation."""
        marks = [self.clock()]
        self._admitted = 0
        # the wait phase: only an engine with nothing to step or
        # prefill may block on its queue, and that time is no work
        head, wait_s = (self._wait_pending(timeout) if self._idle()
                        else (None, 0.0))
        with self._annotate("engine.admit"):
            if self.paged:
                # admission arms slots (donating) and chunks donate the
                # cache: every paged device call recovers under the same
                # budget. Dense admission keeps its own per-request
                # error handling (and _CacheInvalidated keeps the close
                # protocol).
                try:
                    worked = self._admit(head)
                    worked = self._prefill_tick() or worked
                except _CacheInvalidated:
                    raise
                except Exception:  # noqa: BLE001 — donated cache consumed
                    log.exception("paged admission/prefill failed")
                    if self._maybe_recover("paged admission/prefill"):
                        self._record_round(marks, wait_s)
                        return True
                    raise
            else:
                worked = self._admit(head)
            with self._lock:
                active = [(i, s) for i, s in enumerate(self._active)
                          if s is not None]
            # greedy rows ignore seeds/filters entirely, so when EVERY
            # active slot is greedy the cheap argmax step is
            # bit-identical — and skips the per-row sampler (vocab
            # sort) each token
            all_greedy = all(s.req.temperature <= 0.0 for _, s in active)
        if not active:
            if worked:
                self._record_round(marks, wait_s)
            return worked
        t_step0 = self.clock()
        marks.append(t_step0)
        try:
            with self._annotate("engine.step"):
                if self.paged:
                    # page growth arms device rows (donating) — same
                    # recovery scope as the step itself
                    self._ensure_pages(i for i, _ in active)
                with self._mesh_ctx():
                    if all_greedy:
                        self._cache, toks, stats = self._step_greedy(
                            self._params, self._cache,
                            jnp.asarray(self._tokens))
                    else:
                        self._cache, toks, stats = self._step(
                            self._params, self._cache,
                            jnp.asarray(self._tokens),
                            jnp.asarray(self._seeds),
                            jnp.asarray(self._stepidx),
                            jnp.asarray(self._temps),
                            jnp.asarray(self._topk),
                            jnp.asarray(self._topp))
            # the K steps are enqueued; what follows is the wait for
            # them and the device→host read
            marks.append(self.clock())
            with self._annotate("engine.sync"):
                # (K, B); the transfer surfaces device-side failures
                # HERE, while recovery can still replay. The routed
                # layers' counts come in the SAME readback: device_get
                # starts every leaf's copy before it waits for one, where
                # an np.asarray a leaf pays the device-to-host round
                # trip once each, one after another (and np.sum of a
                # device array would run, and first build, a reduction
                # on the device)
                toks, stats = jax.device_get((toks, stats))
                moe = {name: int(v.sum()) for name, v in stats.items()}
        except Exception:  # noqa: BLE001 — donated cache consumed
            log.exception("decode step failed")
            if self._maybe_recover("decode step"):
                self._record_round(marks, wait_s, rows=len(active))
                return True
            raise
        # ONE wall-clock read per sync batch, after the host transfer:
        # the moment every token of this chunk became user-visible. The
        # emit loop below stamps K×B tokens with it — per-token emit
        # takes zero additional clock reads (the ledger contract)
        t_step_end = self.clock()
        marks.append(t_step_end)
        with self._annotate("engine.emit"):
            K = toks.shape[0]
            self.steps_total += K
            if all_greedy:
                self.greedy_steps += K
            _steps_total.inc(K, model=self.name)
            self._stepidx += K
            self._tokens = toks[-1].copy()
            if self.paged:
                self._pos_host[[i for i, _ in active]] += K
                # one span per shared step: the burst-interleave
                # evidence (chunk spans between step spans bound any
                # decode stall)
                self.tracer.record(
                    "engine.step", start=t_step0, end=t_step_end,
                    parent=self._run_ctx,
                    attrs={"model": self.name, "rows": len(active),
                           "k": K})
            retired: List[int] = []
            for i, slot in active:
                for t in range(K):
                    tok = int(toks[t, i])
                    self._emit(slot, tok, t_step_end)
                    if self._finished(slot, tok, t_step_end):
                        # tokens past EOS/budget in this chunk are
                        # discarded
                        with self._lock:
                            self._active[i] = None
                        if self.paged:
                            retired.append(i)
                        # the request's decode phase is over: one span
                        # with the token count — the per-request cost
                        # record
                        self.tracer.record(
                            "engine.decode", start=slot.t_decode0,
                            end=t_step_end, parent=slot.req.ctx,
                            attrs={"model": self.name,
                                   "tokens": slot.produced})
                        break
            if retired:
                # retirement disarms rows with a donating _arm call: run
                # the batch's retirements AFTER the emit loop so a
                # device failure lands with emitted/fold accounting
                # already complete — recovery replays the surviving
                # streams instead of the close protocol failing them all
                try:
                    for i in retired:
                        self._retire_paged(i)
                except Exception:  # noqa: BLE001 — donated cache consumed
                    log.exception("paged retirement failed")
                    if not self._maybe_recover("paged retirement"):
                        raise
            _occupancy.set(self.active_count, model=self.name)
        self._record_round(marks, wait_s, rows=len(active), k=K,
                           greedy=all_greedy, moe=moe)
        return True

    def _annotate(self, name: str):
        """``name`` on the profiler's host timeline while the block
        runs (the tracer's bridge; nothing where it has none)."""
        ann = self.tracer.annotator
        return ann(name) if ann is not None else contextlib.nullcontext()

    def _record_round(self, marks: List[float], wait_s: float, *,
                      rows: int = 0, k: int = 0, greedy: bool = False,
                      moe: Optional[dict] = None) -> None:
        """Close the round that ``marks`` opened: one ``engine.round``
        span whose five phase durations tile ``[start, end]`` (a phase
        the round never reached is 0; one that a recovery cut short runs
        to the end; ``wait_s`` is carved out of admission's stretch),
        and the same seconds into
        ``kftpu_engine_round_seconds_total{phase}``. ``moe`` is what a
        model's routed layers counted over the round's steps
        (``experts_hit``, ``routed_pairs``)."""
        bounds = marks + [self.clock()]
        secs = dict.fromkeys(_ROUND_PHASES, 0.0)
        for phase, t_a, t_b in zip(_ROUND_PHASES[1:], bounds, bounds[1:]):
            secs[phase] = t_b - t_a
        secs["wait"] = wait_s
        secs["admit"] -= wait_s
        attrs = {"model": self.name, "round": self.rounds_total,
                 "rows": rows, "k": k, "admitted": self._admitted,
                 "greedy": greedy}
        for phase, sec in secs.items():
            attrs[f"{phase}_s"] = sec
            _round_seconds.inc(sec, model=self.name, phase=phase)
        if moe:
            attrs.update(moe)
            _moe_hit_c.inc(moe["experts_hit"], model=self.name)
            _moe_pairs_c.inc(moe["routed_pairs"], model=self.name)
        self.tracer.record("engine.round", start=bounds[0],
                           end=bounds[-1], parent=self._run_ctx,
                           attrs=attrs)
        self.rounds_total += 1

    def _idle(self) -> bool:
        """Nothing to step and nothing mid-admission: the one state in
        which the engine thread may block on its queue."""
        with self._lock:
            if any(s is not None for s in self._active):
                return False
        return not (self.paged and (self._prefilling or self._waiting))

    def _wait_pending(self, timeout: float) -> tuple:
        """An idle engine's first arrival (None once ``timeout`` has
        passed) and the seconds it blocked for it. Only a read that
        really blocks is the round's wait phase (``wait_s``,
        ``engine.wait`` on the profiler's timeline): a queue that
        already holds a request costs no clock read."""
        try:
            return self._pending.get_nowait(), 0.0
        except queue.Empty:
            pass
        t0 = self.clock()
        try:
            with self._annotate("engine.wait"):
                head = self._pending.get(timeout=timeout)
        except queue.Empty:
            head = None
        return head, self.clock() - t0

    def _admit(self, head: Optional[_Request]) -> bool:
        """Admission, never blocking: ``head`` is what the wait phase
        took off the queue, the rest is whatever is pending now."""
        if self.hbm_sampler is not None:
            try:
                self.hbm_sampler.sample()
            except Exception:  # noqa: BLE001 — watermarks never gate admits
                log.debug("hbm sample failed (continuing)", exc_info=True)
        if self.paged:
            return self._admit_paged(head)
        return self._admit_dense(head)

    # -- paged engine internals --------------------------------------------

    def _admit_paged(self, head: Optional[_Request]) -> bool:
        """Paged admission: placing a request is page-map surgery (a
        reservation + one tiny arm program), then the prompt streams
        into the pool through the chunked-prefill scheduler — there is
        no whole-row insert and no per-prompt-bucket program. FIFO is
        strict: a request that cannot reserve pages yet holds the line
        (head-of-line wait) rather than being overtaken."""
        with self._lock:
            busy = {i for i, s in enumerate(self._active)
                    if s is not None}
        busy |= set(self._prefilling)
        free = [i for i in range(self.slots) if i not in busy]
        if head is not None:  # only an idle engine waits: the line is empty
            self._waiting.append(head)
        for slot in free:
            if not self._waiting:
                try:
                    self._waiting.append(self._pending.get_nowait())
                except queue.Empty:
                    break
            if not self._place_paged(self._waiting[0], slot):
                break  # no pages yet: keep FIFO, retry next cycle
            self._waiting.popleft()
            self._admitted += 1
        _queue_depth.set(self.pending_count, model=self.name)
        _occupancy.set(self.active_count, model=self.name)
        return self._admitted > 0

    def _place_paged(self, req: _Request, slot: int) -> bool:
        """Reserve + map pages for a request and arm its slot; False
        when the pool cannot cover it yet (caller retries).

        Prefix sharing is trie-matched per PAGE: the longest stored
        chain of full pages maps in read-only, and when the WHOLE
        aligned prefix matched, the partial boundary page maps in
        copy-on-write. The COW split (one device page copy) runs HERE,
        before the slot is armed: the shared decode step advances and
        writes through EVERY armed row (a mid-prefill row's device
        position drifts by design), so a slot may never sit armed while
        its table points a writable logical page at KV someone else
        reads."""
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
            if match.hit:
                self.prefix_hits += 1
                _prefix_hits.inc(model=self.name)
                n_shared = len(shared) + (match.tail_page is not None)
                self.prefix_pages_shared += n_shared
                _prefix_pages_shared_c.inc(n_shared, model=self.name)
            else:
                self.prefix_misses += 1
                _prefix_misses.inc(model=self.name)
        for logical, page in enumerate(shared):
            pool.map_shared(slot, logical, page)
        start = len(shared) * self.kv_page_size
        if match is not None and match.tail_page is not None:
            # map_cow FIRST: the slot's ref keeps the boundary page
            # alive even if store eviction (racing this placement for
            # pages) unpins the entry; then split immediately — the
            # split is the "first write" boundary, since arming makes
            # the row writable by the very next shared step
            logical = len(shared)
            pool.map_cow(slot, logical, match.tail_page)
            src, dst = pool.cow_split(slot, logical)
            with self._mesh_ctx():
                self._cache = self._copy_page(
                    self._cache, jnp.int32(src), jnp.int32(dst))
            self.cow_splits += 1
            _cow_splits_c.inc(model=self.name)
            start += match.tail_len
        pool.ensure(slot, S)  # prompt pages; decode pages grow lazily
        now = self._note_queue_wait(req)
        with self._mesh_ctx():
            self._cache = self._arm(
                self._cache, jnp.int32(slot), jnp.int32(start),
                jnp.asarray(pool.table_row(slot)))
        job = _PrefillJob(
            req=req, slot=slot, tokens=req.prompt, next=start,
            t_admit=now, store_prefix=req.prefix_len)
        self._prefilling[slot] = job
        self._pos_host[slot] = start
        self._slot_budget[slot] = S + req.max_new
        self._export_page_gauges()
        _prefix_bytes_g.set(store.pages_held * self._page_bytes,
                            model=self.name)
        return True

    def _prefill_tick(self) -> bool:
        """Run chunked-prefill work for this cycle.

        With co-tenant decode in flight, at most ``prefill_chunks_per_
        cycle`` chunk programs run before the next shared decode step —
        the scheduling policy that bounds any decode stall to one chunk
        during a burst admit. On an idle engine the oldest job runs to
        completion (nobody to stall, and its stream's TTFT wins), then
        decode starts while later jobs interleave."""
        if not self._prefilling:
            return False
        with self._lock:
            has_active = any(s is not None for s in self._active)
        budget = self.prefill_chunks_per_cycle if has_active else None
        for slot in list(self._prefilling):
            job = self._prefilling[slot]
            while True:
                done = self._run_chunk(job)
                if budget is not None:
                    budget -= 1
                if done:
                    del self._prefilling[slot]
                    self._finalize_paged(job)
                    break
                if budget is not None and budget <= 0:
                    return True
            if budget is None:
                # idle-engine fast path: first stream is live; decode
                # now interleaves with the remaining jobs
                return True
            if budget <= 0:
                return True
        return True

    def _run_chunk(self, job: _PrefillJob) -> bool:
        """One chunk program for one slot; True when the job's token
        stream is fully prefilled (``job.last_tok`` then holds the
        sampled next token)."""
        req = job.req
        C = self.prefill_chunk_tokens
        total = int(job.tokens.size)
        n = min(C, total - job.next)
        padded = np.zeros((1, C), np.int32)
        padded[0, :n] = job.tokens[job.next:job.next + n]
        final = job.next + n >= total
        t0 = self.clock()
        if job.chunks == 0:
            # first chunk: the record's prefill phase opens here (the
            # span below evidences each chunk; the ledger's prefill
            # interval runs from this mark to the first token)
            self.rledger.mark(req.rid, reqobs.PREFILL, t0)
        with self._mesh_ctx():
            tok, self._cache = self._chunk(
                self._params, self._cache, jnp.asarray(padded),
                jnp.int32(job.slot), jnp.int32(job.next), jnp.int32(n),
                jnp.int32(req.seed), jnp.int32(job.fold0),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.float32(req.top_p))
            if final:
                # host transfer forces completion while the failure is
                # still recoverable in this cycle
                job.last_tok = int(tok)
        job.next += n
        job.chunks += 1
        self.prefill_chunks += 1
        _prefill_chunks_c.inc(model=self.name)
        self.rledger.note_chunk(req.rid)
        self.tracer.record(
            "engine.prefill_chunk", start=t0, end=self.clock(),
            parent=req.ctx,
            attrs={"model": self.name, "slot": job.slot,
                   "tokens": int(n), "final": final})
        return final

    def _finalize_paged(self, job: _PrefillJob) -> None:
        """Prompt fully in the pool: emit the sampled token, arm the
        slot's host-side decode state, pin shareable prefix pages."""
        req, slot = job.req, job.slot
        now = self.clock()
        if job.store_prefix:
            # idempotent trie insert: already-stored chain pages are
            # only LRU-touched; a partial-chain hit pins the NEW pages
            # extending the chain, plus the COW boundary tail
            self._prefix_pages.store(req.prompt, job.store_prefix, slot)
            _prefix_bytes_g.set(
                self._prefix_pages.pages_held * self._page_bytes,
                model=self.name)
        self.tracer.record(
            "engine.admit", start=job.t_admit, end=now, parent=req.ctx,
            attrs={"model": self.name, "slot": slot,
                   "prompt_tokens": int(req.prompt.size),
                   "chunked": True, "chunks": job.chunks,
                   "round": self.rounds_total})
        st = _Slot(req=req, produced=job.produced0, t_decode0=now,
                   emitted=[int(t) for t in
                            job.tokens[req.prompt.size:]])
        if job.produced0 == 0:
            # not on the recovery-replay path: a replayed stream's
            # first token reached the client long ago
            self.tracer.record(
                "engine.first_token", start=req.t_submit, end=now,
                parent=req.ctx,
                attrs={"model": self.name,
                       "ttft_ms": round((now - req.t_submit) * 1000.0,
                                        3)})
        self._emit(st, job.last_tok, now)
        self._tokens[slot] = job.last_tok
        self._seeds[slot] = req.seed
        self._stepidx[slot] = job.fold0 + 1
        self._temps[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._pos_host[slot] = job.tokens.size
        if self._finished(st, job.last_tok, now):
            self._retire_paged(slot)
        else:
            with self._lock:
                self._active[slot] = st

    def _ensure_pages(self, slots) -> None:
        """Map pages covering the next K decode writes for each active
        slot (drawing down its admission reservation) and re-arm rows
        whose tables changed — page growth tracks LIVE tokens."""
        K = self.steps_per_sync
        Smax = self.config.max_seq_len
        for i in slots:
            need = min(int(self._pos_host[i]) + K,
                       int(self._slot_budget[i]), Smax)
            if self._pool.ensure(i, need):
                # page growth stalls THIS stream's decode: the arm call
                # is a device round-trip the step waits behind. Clock
                # reads happen only on growth (every ~page_size/K
                # steps), never on the per-token emit path
                t0 = self.clock()
                with self._mesh_ctx():
                    self._cache = self._arm(
                        self._cache, jnp.int32(i),
                        jnp.int32(self._pos_host[i]),
                        jnp.asarray(self._pool.table_row(i)))
                self._export_page_gauges()
                with self._lock:
                    st = self._active[i]
                if st is not None:
                    self.rledger.stall(st.req.rid, reqobs.KV_FAULT,
                                       t0, self.clock())

    def _export_page_gauges(self) -> None:
        """One write site for the pool-occupancy gauges, so in_use /
        free / evictable can never drift apart between call sites."""
        _kv_pages_g.set(self._pool.pages_in_use, model=self.name)
        _kv_pages_free_g.set(self._pool.pages_free, model=self.name)
        _kv_pages_evictable_g.set(self._prefix_pages.pages_evictable,
                                  model=self.name)

    def _retire_paged(self, slot: int) -> None:
        """Free the slot's pages (shared prefix pages drop one ref) and
        disarm its device row so post-retirement garbage decode writes
        scatter-drop instead of landing in reallocated pages."""
        self._pool.release_slot(slot)
        with self._mesh_ctx():
            self._cache = self._arm(
                self._cache, jnp.int32(slot),
                jnp.int32(self.config.max_seq_len),
                jnp.asarray(self._pool.table_row(slot)))
        self._pos_host[slot] = 0
        self._slot_budget[slot] = 0
        self._export_page_gauges()

    # -- cache recovery ----------------------------------------------------

    def _maybe_recover(self, where: str) -> bool:
        """A donating device call failed: the engine cache is consumed.
        While the recovery budget lasts, rebuild the cache/pool from
        scratch and REPLAY every in-flight stream (prompt + emitted
        tokens re-prefill; sampling resumes at the preserved fold
        index) — the engine keeps serving instead of failing every
        subsequent call against a corpse."""
        if self._recoveries_left <= 0:
            return False
        self._recoveries_left -= 1
        try:
            self._rebuild_and_replay()
        except Exception:  # noqa: BLE001 — recovery itself failed
            log.exception("cache recovery after %s failure failed; "
                          "closing engine", where)
            return False
        self.recoveries += 1
        _recoveries_c.inc(model=self.name)
        log.warning("recovered engine cache after %s failure "
                    "(%d recover(s) left)", where, self._recoveries_left)
        return True

    def _rebuild_and_replay(self) -> None:
        with self._lock:
            live = [(i, s) for i, s in enumerate(self._active)
                    if s is not None]
            self._active = [None] * self.slots
        self._cache = self._fresh_cache()
        replays: List[tuple] = []
        for i, st in live:
            replays.append((i, st.req,
                            np.concatenate([st.req.prompt,
                                            np.asarray(st.emitted,
                                                       np.int32)]),
                            st.produced, int(self._stepidx[i])))
        if self.paged:
            # the old pool maps a consumed cache; prefix pages died with
            # it. Interrupted prefill jobs restart from token 0.
            jobs = list(self._prefilling.values())
            self._prefilling = collections.OrderedDict()
            self._pool = PagePool(self.kv_pages, self.kv_page_size,
                                  self.slots, self._n_logical)
            self._prefix_pages = PrefixPageStore(
                self._pool, self._prefix_pages.budget_pages)
            self._pos_host[:] = 0
            self._slot_budget[:] = 0
            # fresh pool: in_use is 0 and the rebuilt store holds
            # nothing yet
            self._export_page_gauges()
            # replays reserve WITHOUT prefix sharing (the store died
            # with the old pool), so a load that only fit shared may
            # not fully fit the fresh pool: fail just those streams
            # retryably instead of giving up the whole recovery
            for args in (replays
                         + [(j.slot, j.req, j.tokens, j.produced0,
                             j.fold0) for j in jobs]):
                i, req = args[0], args[1]
                try:
                    self._replay_paged(*args)
                except OutOfPages:
                    log.warning(
                        "slot %d replay does not fit the rebuilt pool "
                        "(prefix sharing lost); failing it retryably", i)
                    req.error = EngineClosed(
                        "engine cache recovered; stream evicted — retry")
                    req.out.put(_END)
                    self.rledger.finish(req.rid, self.clock())
        else:
            for i, req, tokens, produced, fold in replays:
                self._replay_dense(i, req, tokens, produced, fold)

    def _replay_paged(self, slot: int, req: _Request,
                      tokens: np.ndarray, produced: int,
                      fold: int) -> None:
        pool = self._pool
        budget = req.prompt.size + req.max_new
        pool.reserve(slot, pool.pages_needed(budget))
        pool.ensure(slot, int(tokens.size))
        with self._mesh_ctx():
            self._cache = self._arm(
                self._cache, jnp.int32(slot), jnp.int32(0),
                jnp.asarray(pool.table_row(slot)))
        self._prefilling[slot] = _PrefillJob(
            req=req, slot=slot, tokens=tokens, next=0,
            t_admit=self.clock(), fold0=fold, produced0=produced)
        self._pos_host[slot] = 0
        self._slot_budget[slot] = budget
        self._export_page_gauges()

    def _replay_dense(self, slot: int, req: _Request,
                      tokens: np.ndarray, produced: int,
                      fold: int) -> None:
        """Dense replay: one bucketed prefill of (prompt + emitted)
        re-fills the row, sampling the stream's NEXT token at the
        preserved fold index."""
        L = int(tokens.size)
        bucket = pow2_bucket(L, self.config.max_seq_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = tokens
        with self._mesh_ctx():
            tok, row_cache = self._prefill(
                self._params, jnp.asarray(padded),
                jnp.asarray([L], jnp.int32),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.float32(req.top_p), jnp.int32(req.seed),
                jnp.int32(fold))
            self._cache = self._insert(self._cache, row_cache,
                                       jnp.int32(slot))
        t_now = self.clock()
        st = _Slot(req=req, produced=produced, t_decode0=t_now,
                   emitted=[int(t) for t in tokens[req.prompt.size:]])
        self._emit(st, int(tok), t_now)
        self._tokens[slot] = int(tok)
        self._seeds[slot] = req.seed
        self._stepidx[slot] = fold + 1
        self._temps[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        if not self._finished(st, int(tok), t_now):
            with self._lock:
                self._active[slot] = st

    def _admit_dense(self, head: Optional[_Request]) -> bool:
        """Move pending requests into free slots.

        A BURST of pending requests sharing a prompt bucket admits
        through ONE compiled batch prefill (``_admit_batch``) instead of
        sequential row prefills; singletons and prefix-cached requests
        keep the row path (its compiled programs already exist)."""
        with self._lock:
            free = [i for i, s in enumerate(self._active) if s is None]
        batchable: List[tuple] = []  # (req, slot) — no prefix reuse
        for slot in free:
            if head is not None:
                req, head = head, None
            else:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
            self._admitted += 1
            if req.prefix_len or self.admit_batch_max <= 1:
                self._admit_row_safe(req, slot)
            else:
                batchable.append((req, slot))
        if batchable:
            groups: dict = {}
            for req, slot in batchable:
                b = pow2_bucket(req.prompt.size, self.config.max_seq_len)
                groups.setdefault(b, []).append((req, slot))
            for bucket, members in groups.items():
                # chunk to the batch cap (bounds the transient HBM of
                # the extra full-context rows the batch prefill holds)
                for i in range(0, len(members), self.admit_batch_max):
                    chunk = members[i:i + self.admit_batch_max]
                    if len(chunk) == 1:
                        self._admit_row_safe(*chunk[0])
                        continue
                    try:
                        self._admit_batch(bucket, chunk)
                    except _CacheInvalidated:
                        raise  # run_once/_loop closes the engine
                    except Exception:  # noqa: BLE001
                        # the burst shares one device call; don't let it
                        # share the failure — retry each member through
                        # the row path, which fails (or succeeds)
                        # per-request (the engine cache is intact: the
                        # prefill materialized before any donation)
                        log.exception(
                            "batched admission failed; retrying %d "
                            "request(s) individually", len(chunk))
                        for req, slot in chunk:
                            self._admit_row_safe(req, slot)
        _queue_depth.set(self._pending.qsize(), model=self.name)
        _occupancy.set(self.active_count, model=self.name)
        return self._admitted > 0

    def _admit_row_safe(self, req: _Request, slot: int) -> None:
        """Row-path admission that surfaces failure to THIS caller only."""
        try:
            self._admit_one(req, slot)
        except Exception as e:  # noqa: BLE001 — surface to the caller
            req.error = e
            req.out.put(_END)
            self.rledger.finish(req.rid, self.clock())

    def _admit_batch(self, bucket: int, members: List[tuple]) -> None:
        """One shared prefill for same-bucket requests, then per-row
        inserts into their slots. Rows pad to a power-of-two batch
        (bounded compiled-program inventory: batch buckets × prompt
        buckets); pad rows are length-1 junk nothing reads or inserts.
        Token-identical to the row path: same ragged per-row lengths,
        same ``fold_in(key(seed), 0)`` sampling."""
        k = len(members)
        t0 = self.clock()
        for req, _slot in members:
            self._note_queue_wait(req)
        bb = pow2_bucket(k, min(self.slots, self.admit_batch_max))
        prompts = np.zeros((bb, bucket), np.int32)
        lens = np.ones((bb,), np.int32)
        temps = np.zeros((bb,), np.float32)
        tks = np.zeros((bb,), np.int32)
        tps = np.ones((bb,), np.float32)
        seeds = np.zeros((bb,), np.int32)
        slot_ids = np.zeros((bb,), np.int32)
        valid = np.zeros((bb,), bool)
        for i, (req, slot) in enumerate(members):
            S = req.prompt.size
            prompts[i, :S] = req.prompt
            lens[i] = S
            temps[i] = req.temperature
            tks[i] = req.top_k
            tps[i] = req.top_p
            seeds[i] = req.seed
            slot_ids[i] = slot
            valid[i] = True
        with self._mesh_ctx():
            # annotate the shared device call on the profiler timeline;
            # span-wise it is recorded below as a per-member child of
            # each admit span (a context-managed span here would be an
            # orphan root — the engine thread has no active span — and
            # would crowd the dashboard's trace list)
            p0 = self.clock()
            for req, _slot in members:
                # the shared device call opens every member's prefill
                # phase on the same already-read timestamp
                self.rledger.mark(req.rid, reqobs.PREFILL, p0)
            with self._annotate("engine.prefill"):
                toks, bcache = self._prefill_batch(
                    self._params, jnp.asarray(prompts),
                    jnp.asarray(lens),
                    jnp.asarray(temps), jnp.asarray(tks),
                    jnp.asarray(tps), jnp.asarray(seeds))
            # force completion (the host needs the tokens anyway) BEFORE
            # the donating inserts: a
            # device-side prefill failure must surface while self._cache
            # is still intact, so _admit's row-path fallback retries
            # against a live engine instead of a consumed cache
            toks = np.asarray(toks)  # tpulint: disable=TPU017 — deliberate barrier, see above
            p1 = self.clock()
            try:
                self._cache = self._insert_rows(
                    self._cache, bcache, jnp.asarray(slot_ids),
                    jnp.asarray(valid))
            except Exception as e:  # noqa: BLE001 — donation consumed
                # the cache; fail the chunk retryably and escalate so
                # the loop closes the engine (no row-path retry can
                # succeed against a consumed cache)
                t_fail = self.clock()
                for req, _ in members:
                    req.error = EngineClosed(
                        "engine cache invalidated during admission")
                    req.out.put(_END)
                    self.rledger.finish(req.rid, t_fail)
                raise _CacheInvalidated(str(e)) from e
        self.batch_prefills += 1
        t1 = self.clock()
        for i, (req, slot) in enumerate(members):
            adm = self.tracer.record(
                "engine.admit", start=t0, end=t1, parent=req.ctx,
                attrs={"model": self.name, "slot": slot,
                       "prompt_tokens": int(lens[i]),
                       "batched": True, "batch": k,
                       "round": self.rounds_total})
            # the shared prefill's time range, nested in THIS member's
            # trace (same shape as the row path's admit→prefill)
            self.tracer.record(
                "engine.prefill", start=p0, end=p1, parent=adm,
                attrs={"prompt_tokens": int(lens[i]), "bucket": bucket,
                       "batched": True, "batch": k})
            self._finalize_admission(req, slot, int(toks[i]), t1)

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.run_once()
            except Exception:  # noqa: BLE001
                log.exception("decode engine step failed; closing engine")
                # the step's donated cache is invalidated — this engine
                # can never step again. Close it: in-flight AND pending
                # requests fail with the retryable EngineClosed (503 /
                # UNAVAILABLE), later submits raise the same, and the
                # repository evicts closed engines so the next request
                # builds a fresh one instead of landing here forever.
                with self._lock:
                    self._stop.set()
                    failed = [s.req for s in self._active
                              if s is not None]
                    self._active = [None] * self.slots
                    if self.paged:
                        # mid-chunked-prefill and head-of-line requests
                        # must fail too — a stream nobody ends hangs its
                        # client forever in result()
                        failed.extend(j.req
                                      for j in self._prefilling.values())
                        self._prefilling.clear()
                        failed.extend(self._waiting)
                        self._waiting.clear()
                    while True:
                        try:
                            failed.append(self._pending.get_nowait())
                        except queue.Empty:
                            break
                t_fail = self.clock()
                for req in failed:
                    req.error = EngineClosed("decode engine step failed")
                    req.out.put(_END)
                    self.rledger.finish(req.rid, t_fail)
                return
