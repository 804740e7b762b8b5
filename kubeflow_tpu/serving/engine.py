"""Continuous-batching decode engine: concurrent generate requests share
one compiled decode step.

The reference platform's serving tier batches at the RPC layer
(TF-Serving's ``enable_batching`` scheduler,
``/root/reference/kubeflow/tf-serving/tf-serving-template.libsonnet:33-48``):
whole requests queue for a fixed-shape batch, which serializes callers
behind the longest generation. A request here is a *sequence* of steps:
the engine owns a persistent device-side KV cache with ``slots``
independent rows and runs ONE compiled single-token step over all of
them, forever (docs/SERVING.md):

- **submit** — a request (prompt + sampling params) joins the admission
  queue; its prompt is prefilled at batch 1 into a fresh cache row (one
  compiled prefill per power-of-two prompt bucket, the unary path's
  bucketing) and the row is written into a free slot with one
  ``dynamic_update_slice`` (the compiled *insert*: it touches one row);
- **step** — every active slot advances one token under one jit:
  per-row cache positions (the decode core's ragged-batch contract,
  ``kubeflow_tpu/models/transformer.py:_decode_attend``), per-row
  sampling parameters, and per-row PRNG keys derived as
  ``fold_in(key(seed), step_index)`` so a request's tokens are
  reproducible regardless of which co-tenants share its batch;
- tokens stream to per-request queues the moment the host sees them:
  time-to-first-token is one prefill + one step, not one generation.

Static shapes everywhere: the batch is fixed at ``slots``, idle rows
decode garbage that nothing reads (the next insert overwrites their
rows), and the program inventory is bounded: prefill (per prompt
bucket), the burst batch-prefill (per batch-bucket × prompt-bucket: a
burst of same-bucket requests admits through ONE prefill), insert (row
and from-batch-row), the sampled step, the all-greedy argmax step
(dispatched whenever no in-flight request samples) and the
prefix-continuation (per suffix bucket). ``precompile=True`` builds both
STEP programs up front, so a greedy↔sampled shift never pauses co-tenant
decode on an XLA compile. Prefill programs compile lazily on the first
request of each shape, and since admission and stepping share the
engine thread that compile does pause in-flight streams
(``admit_batch_max=0`` pins admission to the row path's one program per
prompt bucket if that matters more than burst TTFT).

The loop is the same for every cache: what differs between dense rows
and a paged pool is ONE object built from ``paged``, the cache manager
(``serving/kvcache.py``, which says what it owns and what the loop
asks of it). This file tests no cache kind.
"""
# tpulint: disable-file=TPU018 — the step programs compile lazily on
# first dispatch and are billed by the process-wide CompileLedger
# listener; timed_compile would AOT-compile via .lower().compile(),
# which does NOT populate jax's jit dispatch cache, so every program
# would compile twice. `precompile=True` is the engine's warm path.

from __future__ import annotations

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

from kubeflow_tpu.models.decode import decode_step_stats, sample_logits
from kubeflow_tpu.obs import (
    SpanContext,
    Tracer,
    current_context,
    profiler_annotator,
)
from kubeflow_tpu.obs import requests as reqobs
from kubeflow_tpu.obs import xprof
from kubeflow_tpu.serving.kvcache import (  # noqa: F401 — re-exported
    EngineClosed,
    PagedCache,
    RowCache,
    _CacheInvalidated,
    pow2_bucket,
)
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
_admit_seconds = DEFAULT_REGISTRY.counter(
    "kftpu_engine_admit_seconds_total",
    "engine-thread seconds inside device admissions by phase (host, "
    "launch, read, insert): launch + insert + host over the sum is the "
    "share of admission in which the host is not waiting for the device")
_admissions_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_admissions_total",
    "device admissions (one prefill the engine waits for) by kind (row, "
    "prefix, chunked, batch)")
_prefill_tokens_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_prefill_tokens_total",
    "tokens the admissions' prefill programs ran (what=scanned: rows x "
    "width, pad rows and bucket padding included) and the requests' own "
    "among them (what=prompt)")
_occupancy = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_active_slots", "active slots in the decode batch")
_slots_g = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_slots",
    "decode-slot capacity of the engine (static; scrapers read it so "
    "queue depth can be priced in slot units without a config hint)")
_queue_depth = DEFAULT_REGISTRY.gauge(
    "kftpu_engine_pending_requests", "requests waiting for a slot")
_queue_wait_h = DEFAULT_REGISTRY.histogram(
    "engine_queue_wait_seconds",
    "time a generate request waits for a decode slot")
_rounds_ahead_c = DEFAULT_REGISTRY.counter(
    "kftpu_engine_rounds_ahead_total",
    "rounds whose K steps were enqueued before the host read the tokens "
    "of the round before them (an engine.round's ``ahead``): over the "
    "rounds that stepped, how often the device had work queued while the "
    "host read, emitted and admitted")
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
_dsa_scored_c = DEFAULT_REGISTRY.counter(
    "kftpu_dsa_scored_total",
    "cached positions an indexer scored, summed over the rows, the sparse "
    "attention layers and every decode step")
_dsa_selected_c = DEFAULT_REGISTRY.counter(
    "kftpu_dsa_selected_total",
    "cached positions sparse attention kept and read, summed likewise: "
    "over kftpu_dsa_scored_total, how sparse the traffic made the layer")
_step_attn_blocks_c = DEFAULT_REGISTRY.counter(
    "kftpu_step_attention_blocks_total",
    "blocks of cached K/V positions the one-token step attention read "
    "(what=read: each row's blocks through its position, none for an idle "
    "row) and held (what=reserved: rows x blocks a row), for one attention "
    "layer, summed over every decode step")
_step_window_c = DEFAULT_REGISTRY.counter(
    "kftpu_step_attention_window_positions_total",
    "ring slots of cached K/V positions the one-token step attention of a "
    "window layer read (min(position + 1, window) a live row), for one "
    "window layer, summed over every decode step")
# what a model's layers count in a decode step -> the counter it feeds
# and that counter's labels
_STEP_COUNTERS = {"experts_hit": (_moe_hit_c, {}),
                  "routed_pairs": (_moe_pairs_c, {}),
                  "index_scored": (_dsa_scored_c, {}),
                  "index_selected": (_dsa_selected_c, {}),
                  "kv_blocks_read": (_step_attn_blocks_c, {"what": "read"}),
                  "kv_blocks_reserved": (_step_attn_blocks_c,
                                         {"what": "reserved"}),
                  "window_positions_read": (_step_window_c, {})}

_END = object()  # per-request stream sentinel

# an ``engine.round`` span's ``<phase>_s`` attrs and the ``phase`` label
# of kftpu_engine_round_seconds_total (a round passes ``admit`` twice
# where it lands what it launched: ``run_once``)
_ROUND_PHASES = ("wait", "admit", "step", "sync", "emit")
# an ``engine.admission`` span's ``<phase>_s`` attrs, the ``phase`` label
# of kftpu_engine_admit_seconds_total and the ``engine.admit.<phase>``
# leaf annotations on the profiler's host timeline
_ADMIT_PHASES = ("host", "launch", "read", "insert")


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
    # tokens the stream will have once every round launched with it is
    # read: the launch that reaches max_new is its last
    launched: int = 0
    done: bool = False  # its last token went out (budget or EOS)


@dataclasses.dataclass
class _Flight:
    """A round of K steps enqueued on the device whose tokens the host
    has not read yet."""
    rows: List[tuple]   # (slot, _Slot) of the streams it steps
    toks: Any           # (K, slots) int32 on the device
    stats: dict         # what the model's layers counted, on the device
    greedy: bool
    ahead: bool         # enqueued before the round before it was read
    t_launch: float


class _Round:
    """The ``engine.round`` record of one ``run_once`` cycle while it
    runs: the phase the thread is in and the seconds booked to each.
    ``enter`` is the one place the round reads the clock at a boundary,
    once, so the phases tile ``[start, end]`` whatever order the cycle
    passes them in; what the round read and stepped is filled in as it
    gets there."""

    def __init__(self, eng: "DecodeEngine", phase: str) -> None:
        self.eng, self.phase = eng, phase
        self.secs = dict.fromkeys(_ROUND_PHASES, 0.0)
        self.start = self._t = eng.clock()
        # a cycle with nothing in flight may wait: carved out of admit,
        # the phase it starts in
        self.wait_s = 0.0
        self.rows = self.k = 0
        self.greedy = self.ahead = False
        self.counts: dict = {}
        self.compiles0 = xprof.compiles_total()

    def enter(self, phase: str) -> float:
        """The thread passes into ``phase``; returns the boundary (the
        phase's start where the thread is in it already)."""
        if phase != self.phase:
            now = self.eng.clock()
            self.secs[self.phase] += now - self._t
            self.phase, self._t = phase, now
        return self._t


class _Admission:
    """The record of ONE device admission, one prefill the engine waits
    for, while it runs: a context the cache manager opens where the
    admission begins (``DecodeEngine._open_admission``) and leaves once
    the slots are armed, naming each stretch as it reaches it. ``enter``
    is the one place the engine thread changes its ``engine.admit.*``
    leaf: it closes the open annotation, reads the clock ONCE, books the
    stretch that ended and opens the next, so the four durations tile
    ``[start, end]`` on the very boundaries the profiler's host plane
    shows. An admission launched behind a round in flight is
    ``suspend``-ed once its programs are enqueued: the thread leaves for
    the round's read and emission while ``read`` runs on, and ``resume``
    brings it back to wait for the first tokens and arm the slots, so
    ``read_s`` is the time from the launch's end to the first tokens on
    the host. What the programs ran is counted on the attributes:
    ``rows`` requests in ``rows_padded`` program rows, each scanned
    ``width`` tokens wide (in ``chunks`` chunk programs, where chunked),
    of which ``prompt_tokens`` were the requests' own."""

    def __init__(self, eng: "DecodeEngine", kind: str, *, rows: int = 1,
                 rows_padded: int = 1, width: int = 0,
                 prompt_tokens: int = 0) -> None:
        self.eng, self.kind = eng, kind
        self.rows, self.rows_padded = rows, rows_padded
        self.width, self.prompt_tokens, self.chunks = width, prompt_tokens, 0
        self.secs = dict.fromkeys(_ADMIT_PHASES, 0.0)
        self.status = "OK"
        self._phase = "host"        # the leaf run_once opened
        self._suspended = False
        self._compiles0 = xprof.compiles_total()
        self.start = self.end = self._t = eng.clock()

    def enter(self, phase: str) -> float:
        """The thread passes into ``phase``; returns the boundary."""
        eng = self.eng
        eng._leave_leaf()
        now = eng.clock()
        self.secs[self._phase] += now - self._t
        self._phase, self._t = phase, now
        eng._enter_leaf("engine.admit." + phase)
        return now

    def suspend(self) -> float:
        """The programs are enqueued: ``read`` starts here and runs on
        while the thread goes back to the admission's host code and on
        to the round's other work. Returns the boundary."""
        eng = self.eng
        eng._leave_leaf()
        now = eng.clock()
        self.secs[self._phase] += now - self._t
        self._phase, self._t = "read", now
        self._suspended = True
        eng._enter_leaf("engine.admit.host")
        return now

    def resume(self) -> None:
        """The thread is back to wait for the first tokens: the read
        phase goes on, no clock is read."""
        self._suspended = False
        self.eng._leave_leaf()
        self.eng._enter_leaf("engine.admit.read")

    def abandon(self) -> None:
        """A recovery or the engine's close dropped the launched
        admission unread: record it as failed, touching no leaf (the
        thread is in none of admission's)."""
        self.status = "ERROR: abandoned"
        self.end = self.eng.clock()
        self.secs[self._phase] += self.end - self._t
        self._record()

    def __enter__(self) -> "_Admission":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        """One ``engine.admission`` span, child of the engine's run as
        the rounds are, and the same seconds and counts into the three
        counters; the thread is left in the host leaf. A failure is
        recorded too (``status``), with what had been counted. A
        suspended admission is recorded by its landing."""
        eng = self.eng
        if exc_type is None and self._suspended:
            return
        if exc_type is not None:
            self.status = f"ERROR: {exc_type.__name__}"
        self.end = (self.enter("host") if self._phase != "host"
                    else eng.clock())
        self.secs["host"] += self.end - self._t
        self._record()

    def _record(self) -> None:
        eng = self.eng
        scanned = self.rows_padded * self.width
        attrs = {"model": eng.name, "round": eng.rounds_total,
                 "kind": self.kind, "rows": self.rows,
                 "rows_padded": self.rows_padded, "width": self.width,
                 "prompt_tokens": self.prompt_tokens,
                 "scanned_tokens": scanned,
                 "compiles": xprof.compiles_total() - self._compiles0}
        if self.chunks:
            attrs["chunks"] = self.chunks
        for phase, sec in self.secs.items():
            attrs[phase + "_s"] = sec
            _admit_seconds.inc(sec, model=eng.name, phase=phase)
        _admissions_c.inc(model=eng.name, kind=self.kind)
        _prefill_tokens_c.inc(self.prompt_tokens, model=eng.name,
                              what="prompt")
        _prefill_tokens_c.inc(scanned, model=eng.name, what="scanned")
        eng.tracer.record("engine.admission", start=self.start,
                          end=self.end, parent=eng._run_ctx, attrs=attrs,
                          status=self.status)


def _put(host: np.ndarray) -> jax.Array:
    """A host array on the device, from a copy of its own: the transfer
    may read its source after the call returns, and the engine's host
    arrays change while a round is in flight."""
    return jnp.asarray(host.copy())


def _kv_attr(name: str) -> property:
    return property(lambda self: getattr(self._kv, name),
                    lambda self, value: setattr(self._kv, name, value))


class DecodeEngine:
    """One engine per loaded transformer model version.

    ``submit()`` is thread-safe and returns a handle whose ``stream()``
    yields tokens as decode steps complete. The engine thread runs the
    round loop forever (``run_once``), one round ahead of the tokens it
    reads; ``close()`` drains it. An option left ``None``
    takes the default written here: the engine reads no environment
    (``serving/server.py`` reads a deployment's sizing into keywords).
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
        # paged KV cache + chunked prefill (docs/SERVING.md); dense is
        # the parity oracle and the default
        self.paged = bool(paged)
        # cache-recovery budget: a donated-cache failure rebuilds the
        # cache and replays in-flight slots this many times before the
        # engine gives up and self-closes
        self._recoveries_left = max(
            0, 2 if recoveries is None else int(recoveries))
        # host-side timing source for queue-wait/admit/decode spans; a
        # fake clock makes engine span trees deterministic in tests
        self.clock: Clock = clock if clock is not None else time.monotonic
        # spans land in the shared collector; through the profiler
        # annotator the loop names the leaf the thread is in on the XLA
        # host timeline during a capture (docs/OBSERVABILITY.md)
        self.tracer = tracer if tracer is not None else Tracer(
            clock=self.clock, annotator=profiler_annotator())
        # what the engine THREAD did with its time hangs off one
        # ``engine.run`` root (recorded at close()), so the per-round
        # children never crowd the collector's root list. Its ids are
        # made here: an engine built inside its first request must not
        # join that request's trace
        self._run_ctx = SpanContext(os.urandom(16).hex(),
                                    os.urandom(8).hex())
        self._t_run0: Optional[float] = self.clock()
        self._admitted = 0  # requests admitted in the round in progress
        # the ONE ``engine.admit.*`` annotation open on the engine thread
        # while admission runs (None outside it, and without a bridge)
        self._leaf = None
        # programs built or loaded, process-wide: read at a round's and
        # an admission's two ends (``compiles`` on their spans)
        xprof.install_compile_count()
        # the request-lifecycle ledger (docs/OBSERVABILITY.md): phase
        # marks ride the clock reads this file already takes; the
        # process-wide default joins the edge's phases by trace id
        self.rledger = (request_ledger if request_ledger is not None
                        else reqobs.DEFAULT_LEDGER)
        # lax.top_k-bounded sampler (models/decode.py:sample_logits
        # ``bound``): avoids the per-token full-vocab sort the exact
        # sampler pays at every sampled step — 0 selects the exact sort
        self.sampler_bound = 64 if sampler_bound is None else int(
            sampler_bound)
        # "bounded" (lax.top_k, truncating), "exact_sort" (full-vocab
        # sort), "fused" (ops/sampling.py Pallas kernel: exact support
        # at bounded cost). "auto": bounded when a bound is set, fused
        # for the exact path (bound 0), so sampler_bound stops being a
        # correctness/perf tradeoff
        if sampler_impl is None or sampler_impl == "auto":
            sampler_impl = ("bounded" if self.sampler_bound > 0
                            else "fused")
        if sampler_impl not in ("bounded", "exact_sort", "fused"):
            raise ValueError(
                f"unknown sampler_impl {sampler_impl!r}; valid: auto, "
                "bounded, exact_sort, fused")
        self.sampler_impl = sampler_impl
        # burst admission: same-bucket pending requests prefill as ONE
        # batch of up to this many rows. The cap bounds the transient
        # HBM spike (that many extra full-context KV rows until their
        # inserts land) and the program inventory; <=1: row path only
        self.admit_batch_max = 8 if admit_batch_max is None else int(
            admit_batch_max)
        # multi-chip serving: with a Mesh (params already placed, e.g.
        # via models.param_partition_specs) every engine program runs
        # under it, and the model's logical axes shard the KV cache
        self.mesh = mesh
        if mesh is not None:
            from kubeflow_tpu.parallel.mesh import mesh_context

            self._mesh_ctx = lambda: mesh_context(mesh)
        else:
            self._mesh_ctx = contextlib.nullcontext
        # decode steps on-device per host round-trip: >1 amortizes
        # dispatch and readback over that many tokens, at the price of
        # admission/EOS reacting that many tokens late (tokens past a
        # row's EOS or budget are computed and discarded)
        self.steps_per_sync = max(1, int(steps_per_sync))
        self.name = name or "model"
        # the NORMALIZED name: every engine series must share one model
        # label value or per-model joins (slots vs pages) find no row
        _slots_g.set(self.slots, model=self.name)
        # exported at 0 from the start: "never recovered" must be a
        # readable fact, not an absent series
        _recoveries_c.inc(0, model=self.name)
        _rounds_ahead_c.inc(0, model=self.name)
        # an obs.xprof.HbmSampler sampled once per admit cycle, so that
        # kftpu_hbm_bytes{model=...} shows admission's watermark
        # (weights + KV + prefill spike); no series on CPU backends
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
        # host-side per-slot sampling state, padded to the batch
        self._tokens = np.zeros((slots,), np.int32)
        # armed since the last launch: its next input token is the host's
        # (``_tokens``), the others' the last token of the round in flight
        self._fresh = np.zeros((slots,), bool)
        self._seeds = np.zeros((slots,), np.int32)
        self._stepidx = np.zeros((slots,), np.int32)
        self._temps = np.zeros((slots,), np.float32)
        self._topk = np.zeros((slots,), np.int32)
        self._topp = np.ones((slots,), np.float32)
        self.steps_total = 0
        self.rounds_total = 0  # run_once cycles that did work
        self.tokens_total = 0
        self._tokens_exported = 0  # of them in kftpu_engine_tokens_total
        self.greedy_steps = 0  # steps served by the argmax fast path
        self.recoveries = 0      # cache rebuild-and-replay events
        # rounds enqueued and not yet read, oldest first (at most two,
        # inside a cycle); guarded by _lock, as _active is
        self._flights: List[_Flight] = []

        self._sample_rows = self._build_sampler()
        # the cache manager: the ONE place that knows "rows or pages"
        prefix = dict(prefix_cache_entries=prefix_cache_entries,
                      prefix_cache_bytes=prefix_cache_bytes)
        if self.paged:
            self._kv = PagedCache(
                self, kv_page_size=kv_page_size, kv_pages=kv_pages,
                paged_attention_impl=paged_attention_impl,
                prefill_chunk_tokens=prefill_chunk_tokens,
                prefill_chunks_per_cycle=prefill_chunks_per_cycle,
                **prefix)
        else:
            self._kv = RowCache(self, **prefix)
        self._step, self._step_greedy = self._build_steps(self._kv.cfg)
        self._splice = self._build_splice()
        # what a launch with no round in flight splices its (host)
        # tokens over: every row is fresh then (a transfer, where
        # jnp.zeros would build a program of its own)
        self._no_round = _put(np.zeros((self.steps_per_sync, slots),
                                       np.int32))
        if precompile:
            self._precompile_steps()
        if autostart:
            self.start()

    def _build_sampler(self):
        impl = self.sampler_impl
        rules = self.config.rules
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
                    (None,), rules)

            def one(row_logits, seed, i, t, k, p):
                key = jax.random.fold_in(jax.random.key(seed), i)
                return sample_logits(row_logits[None], key,
                                     temperature=t, top_k=k, top_p=p,
                                     bound=bnd)[0]

            return jax.vmap(one)(logits, seeds, idx, temps, tks, tps)

        return sample_rows

    def _build_steps(self, cfg) -> tuple:
        """``(_step, _step_greedy)``, the cache-writing programs every
        manager shares, over the manager's ``cfg`` (the model's config
        plus any paged geometry)."""
        K = self.steps_per_sync
        sample_rows = self._sample_rows

        def _step(params, cache, tokens, seeds, step_idx, temps, top_k,
                  top_p):
            """K decode steps under one jit; returns (cache, (K, B)
            tokens, stats): what a model's layers counted, e.g. the
            routed ones' ``experts_hit`` and ``routed_pairs``, (K, L_moe)
            each, or the step attention's ``kv_blocks_read``, (K,); an
            empty dict, no output of the program, for a model with
            none."""

            def body(carry, t):
                cache, tokens = carry
                logits, cache, stats = decode_step_stats(
                    cfg, params, cache, tokens)
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
                    cfg, params, cache, tokens)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (cache, nxt), (nxt, stats)

            (cache, _), (toks, stats) = jax.lax.scan(
                body, (cache, tokens), None, length=K)
            return cache, toks, stats

        return (jax.jit(_step, donate_argnums=(1,)),
                jax.jit(_step_greedy, donate_argnums=(1,)))

    def _build_splice(self):
        """The engine's own splice program, through which every round's
        input tokens pass, so that each step program sees one kind of
        token argument (on a mesh: replicated, as the host's were)."""

        def _splice_tokens(fresh, host, toks):
            """A round's (slots,) input tokens: the host's where
            ``fresh`` (a row armed since the last launch), else the last
            of the (K, slots) tokens of the round enqueued before it,
            which never leave the device. One program for every
            admission size and prompt bucket."""
            return jnp.where(fresh, host, toks[-1])

        if self.mesh is None:
            return jax.jit(_splice_tokens)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.jit(_splice_tokens, out_shardings=NamedSharding(
            self.mesh, PartitionSpec()))

    def _round_tokens(self, prev: Optional["_Flight"]) -> jax.Array:
        """The next round's (slots,) input tokens: the host's for the
        rows armed since the last launch (every row, with nothing in
        flight), the last tokens of ``prev`` for the others."""
        fresh = self._fresh if prev is not None else np.ones_like(self._fresh)
        return self._splice(_put(fresh), _put(self._tokens),
                            prev.toks if prev is not None else self._no_round)

    def _precompile_steps(self) -> None:
        """Run BOTH step programs once on the empty batch so the
        greedy↔sampled dispatch switch never stalls in-flight streams
        on a mid-serving XLA compile. Every slot is idle, so the junk
        tokens land in rows the next insert fully overwrites. The
        splice of a round's input tokens is built here too."""
        kv = self._kv
        vec_i = jnp.zeros((self.slots,), jnp.int32)
        ones_f = jnp.ones((self.slots,), jnp.float32)
        with self._mesh_ctx():
            tokens = self._round_tokens(None)
            kv.cache, _, _ = self._step_greedy(self._params, kv.cache,
                                               tokens)
            kv.cache, _, _ = self._step(
                self._params, kv.cache, tokens, vec_i, vec_i, ones_f,
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
        prefix_len = int(prefix_len)
        if prefix_len and not 0 < prefix_len < prompt.size:
            raise ValueError(
                f"prefix_len {prefix_len} must be in (0, prompt length "
                f"{prompt.size}) — the suffix may not be empty")
        if prefix_len and self.config.has_recurrent_state:
            raise ValueError(
                "prefix reuse continues a row from a stored prefix; this "
                "model keeps a recurrent state or a window ring, which "
                "the prefix store does not snapshot")
        # what only this cache can refuse (a request over the whole
        # pool) or must downgrade (a prefix its store can never hold)
        prefix_len = self._kv.check_submit(prompt.size, max_new,
                                           prefix_len)
        req = _Request(prompt=prompt, max_new=max_new,
                       temperature=float(temperature), top_k=int(top_k),
                       top_p=float(top_p), seed=int(seed), eos_id=eos_id,
                       prefix_len=prefix_len,
                       # the submitting thread's active span (serving
                       # handler) — engine spans parent onto it
                       ctx=current_context(), t_submit=self.clock())
        # ledger key: join the propagated trace's record (the edge may
        # have started it) or open an engine-only one — BEFORE the queue
        # put: the engine thread may admit at once, and marks the record
        req.rid = (req.ctx.trace_id if req.ctx is not None
                   else reqobs.synthetic_rid())
        self.rledger.start(req.rid, t=req.t_submit, model=self.name)
        # the lock orders this against close()'s drain: a submit must
        # either land before the drain (and be failed by it) or see the
        # stop flag and raise — never sit in a queue nobody reads
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
        # a hung client is worse than a retried request
        t_close = self._drain("decode engine closed")
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
        """Streams being served: decoding (a stream whose last round is
        enqueued has left its slot but not yet delivered), or
        mid-admission (a chunk-prefilling prompt holds pages and a slot
        either way)."""
        with self._lock:
            n = len(self._live_streams())
        return n + self._kv.in_admission

    @property
    def pending_count(self) -> int:
        """Requests admitted to submit() but not yet holding a slot."""
        return self._pending.qsize() + self._kv.waiting

    def snapshot(self) -> dict:
        """Occupancy snapshot for the autoscaler's engine poll
        (:meth:`kubeflow_tpu.autoscale.metrics.MetricsAggregator
        .observe_engine`): active slots are the concurrency the proxy
        can't see (one HTTP generate call hides a whole decode stream),
        pending is the queue depth; a paged manager adds its pool's
        fields (``pages_total``, ``pages_free``, ``pages_in_use`` …)."""
        return {"active_slots": self.active_count,
                "pending": self.pending_count,
                "slots": self.slots,
                "closed": self.closed,
                "recoveries": self.recoveries,
                **self._kv.snapshot()}

    # -- host services the cache manager calls -----------------------------

    def _next_pending(self) -> Optional[_Request]:
        try:
            return self._pending.get_nowait()
        except queue.Empty:
            return None

    def _fail(self, req: _Request, error: Exception, t: float) -> None:
        """End ``req``'s stream with ``error`` and fold its record."""
        req.error = error
        req.out.put(_END)
        self.rledger.finish(req.rid, t)

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

    def _open_admission(self, kind: str, **counted: int) -> _Admission:
        """A device admission of ``kind`` (``row`` | ``prefix`` |
        ``chunked`` | ``batch``) begins on the engine thread; ``counted``
        is what the caller knows of its program already."""
        return _Admission(self, kind, **counted)

    def _arm_slot(self, req: _Request, slot: int, token: int, t: float, *,
                  produced: int = 0, emitted=(), fold: int = 0) -> bool:
        """``slot`` starts (or, replayed, resumes) decoding ``req``: emit
        ``token`` — sampled at fold index ``fold`` after the prompt and
        the ``emitted`` tokens a replay re-prefilled — at the caller's
        already-read ``t`` and arm the slot's host-side step state. THE
        one place a slot is armed (row, batch and chunked admission, both
        replays). False when that token already ended the stream."""
        st = _Slot(req=req, produced=produced, t_decode0=t,
                   emitted=[int(x) for x in emitted])
        if produced == 0:
            # the TTFT span: one per request (a replayed stream's first
            # token reached its client long ago), edge-to-first-token
            # visible in the trace tree the dashboard exemplar opens
            self.tracer.record(
                "engine.first_token", start=req.t_submit, end=t,
                parent=req.ctx,
                attrs={"model": self.name,
                       "ttft_ms": round((t - req.t_submit) * 1000.0, 3)})
        self._emit(st, token, t)
        st.launched = st.produced
        self._tokens[slot] = token
        self._fresh[slot] = True
        self._seeds[slot] = req.seed
        self._stepidx[slot] = fold + 1
        self._temps[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        live = not self._finished(st, token, t)
        if live:
            with self._lock:
                self._active[slot] = st
        return live

    # -- engine internals --------------------------------------------------

    def _emit(self, slot: _Slot, token: int, t: float) -> None:
        """The per-token hot path. ``t`` is a timestamp the caller
        ALREADY read (run_once stamps one step-end time for every token
        of the sync batch — the moment the host actually saw them);
        neither this method nor the ledger reads a clock here."""
        slot.produced += 1
        slot.emitted.append(token)
        self.tokens_total += 1  # the series follows once a round
        self.rledger.emit(slot.req.rid, t)
        slot.req.out.put(token)

    def _finished(self, slot: _Slot, token: int, t: float) -> bool:
        done = (slot.produced >= slot.req.max_new or
                (slot.req.eos_id is not None and token == slot.req.eos_id))
        if done:
            slot.done = True
            slot.req.out.put(_END)
            # last token: fold the request's record (histograms +
            # flight ring) on the same already-read timestamp
            self.rledger.finish(slot.req.rid, t)
        return done

    def run_once(self, timeout: float = 0.1) -> bool:
        """One cycle of the round loop; True if any work happened. The
        background loop calls this forever; tests call it directly
        (``autostart=False``) for deterministic schedules.

        The device runs a round ahead of the host. While round n runs,
        the host has all that round n + 1 needs (each row's last token
        is round n's, on the device; its step index moves on by K; its
        sampling parameters hold), so a cycle

        1. launches round n + 1 on round n's last tokens;
        2. launches this cycle's admissions, whose prefills queue behind
           round n + 1 on the device;
        3. reads round n's tokens and counts in one transfer;
        4. emits them, and retires the streams that ended, while round
           n + 1 runs;
        5. lands the admissions: reads their first tokens and arms their
           slots, which join the next launch with those tokens spliced
           into the device's token vector (``_build_splice``).

        With nothing in flight (an idle engine, or after a recovery) a
        cycle admits first, then launches the first round and the one
        after it, and reads the first. A row that ends by EOS in round
        n also runs in round n + 1, its tokens there discarded as those
        past EOS inside a round are. A stream leaves its slot at the
        launch that covers its budget, so the slot takes an admission
        behind that round. A cycle after which no stream is live leaves
        nothing in flight. A donating device call that fails is
        recovered in place (cache rebuild, every surviving stream
        replayed from what it has emitted) while the recovery budget
        lasts.

        A cycle that did work is one ``engine.round`` span (``_Round``):
        its phases tile it, every instant of the engine thread lies
        inside exactly one ``engine.*`` annotation, and ``rows``, ``k``
        and ``ahead`` are those of the round it read."""
        kv = self._kv
        self._admitted = 0
        steady = bool(self._flights)
        rnd = _Round(self, "step" if steady else "admit")
        head = None
        if not steady and self._idle():
            # only an engine with nothing to step or prefill may block
            # on its queue, and that time is no work
            head, rnd.wait_s = self._wait_pending(timeout)
        launched = False
        where = "decode step"
        try:
            if steady:
                launched = self._launch(rnd)
            where = "admission/prefill"
            worked = self._admit_stage(rnd, head, land=not steady)
            if not steady:
                where = "decode step"
                launched = self._launch(rnd)
                if launched:
                    self._launch(rnd)          # the round after it, ahead
            if self._flights:
                where = "decode step"
                flight, toks, counts, t_read = self._read(rnd)
                # emission only delivers; its retirements may donate
                where = "slot retirement"
                self._emit_round(flight, toks, counts, t_read, rnd)
            if kv.landing:
                where = "admission/prefill"
                self._admit_stage(rnd, None, launch=False, land=True)
        except _CacheInvalidated:
            raise  # the close protocol, whatever the manager
        except Exception:  # noqa: BLE001 — donated cache consumed
            # the manager's error scope: admission that donates the cache
            # recovers under the step's budget; the other kind has
            # handled its errors per request
            if where == "admission/prefill" and not kv.admission_recovers:
                raise
            log.exception("%s failed", where)
            if self._maybe_recover(where):
                self._record_round(rnd)
                return True
            raise
        with self._lock:
            if not self._live_streams():
                # what is still in flight delivers to no one
                self._flights.clear()
        worked = worked or launched or rnd.k > 0
        if worked:
            self._record_round(rnd)
        return worked

    def _admit_stage(self, rnd: _Round, head: Optional[_Request], *,
                     launch: bool = True, land: bool = False) -> bool:
        """Admission's stretch of the cycle, in its host leaf: launch
        what is pending into the free slots, and/or land what was
        launched (read the first tokens, arm the slots)."""
        rnd.enter("admit")
        self._enter_leaf("engine.admit.host")
        try:
            worked = self._admit(head) if launch else False
            if land:
                self._kv.land()
        finally:
            self._leave_leaf()
        return worked

    def _launch(self, rnd: _Round) -> bool:
        """Enqueue the next round over every stream that needs tokens
        past those already launched; False where none does. Its input
        tokens are the host's where nothing is in flight, else the last
        tokens of the round enqueued before it with the rows armed since
        spliced in. A stream whose budget this round covers leaves its
        slot here (the manager retires it behind the round)."""
        kv = self._kv
        with self._lock:
            rows = [(i, s) for i, s in enumerate(self._active)
                    if s is not None]
            prev = self._flights[-1] if self._flights else None
        if not rows:
            return False
        t_launch = rnd.enter("step")
        K = self.steps_per_sync
        # greedy rows ignore seeds/filters entirely, so when EVERY row is
        # greedy the cheap argmax step is bit-identical and skips the
        # per-row sampler (vocab sort) each token
        greedy = all(s.req.temperature <= 0.0 for _, s in rows)
        with self._annotate("engine.step"):
            # readying the cache may donate it (page growth arms device
            # rows) — same recovery scope as the step itself
            kv.before_step(rows)
            with self._mesh_ctx():
                tokens = self._round_tokens(prev)
                if greedy:
                    kv.cache, toks, stats = self._step_greedy(
                        self._params, kv.cache, tokens)
                else:
                    kv.cache, toks, stats = self._step(
                        self._params, kv.cache, tokens, _put(self._seeds),
                        _put(self._stepidx), _put(self._temps),
                        _put(self._topk), _put(self._topp))
            self._stepidx += K
            self._fresh[:] = False
            covered = []
            with self._lock:
                self._flights.append(_Flight(rows, toks, stats, greedy,
                                             prev is not None, t_launch))
                for i, s in rows:
                    s.launched += K
                    if s.launched >= s.req.max_new:
                        self._active[i] = None
                        covered.append(i)
            for i in covered:
                kv.retire(i)
        return True

    def _read(self, rnd: _Round) -> tuple:
        """Wait for the oldest round in flight and read its (K, slots)
        tokens and what the model's layers counted in ONE transfer (the
        read surfaces device-side failures HERE, while recovery can
        still replay). Returns the round, its tokens, the counts and
        the moment they became user-visible."""
        flight = self._flights[0]
        rnd.enter("sync")
        with self._annotate("engine.sync"):
            # device_get starts every leaf's copy before it waits for one
            # (an np.asarray a leaf pays one round trip each, in turn;
            # np.sum of a device array would build a reduction)
            toks, stats = jax.device_get((flight.toks, flight.stats))
            counts = {name: int(v.sum()) for name, v in stats.items()}
        # ONE wall-clock read per round read: the emit loop stamps K×B
        # tokens with it — per-token emit takes zero clock reads (the
        # ledger contract)
        return flight, toks, counts, rnd.enter("emit")

    def _emit_round(self, flight: _Flight, toks: np.ndarray, counts: dict,
                    t_read: float, rnd: _Round) -> None:
        """Hand out a round's tokens to its streams, end those that
        reached EOS or their budget (tokens past that are discarded) and
        retire the slots they still held."""
        kv = self._kv
        with self._annotate("engine.emit"):
            K = toks.shape[0]
            rnd.rows, rnd.k, rnd.counts = len(flight.rows), K, counts
            rnd.greedy, rnd.ahead = flight.greedy, flight.ahead
            self.steps_total += K
            if flight.greedy:
                self.greedy_steps += K
            _steps_total.inc(K, model=self.name)
            with self._lock:
                self._flights.pop(0)
            kv.after_step(flight.rows, K, flight.t_launch, t_read)
            retired: List[int] = []
            for i, slot in flight.rows:
                if slot.done:       # ended in the round before this one
                    continue
                for t in range(K):
                    tok = int(toks[t, i])
                    self._emit(slot, tok, t_read)
                    if self._finished(slot, tok, t_read):
                        with self._lock:
                            if self._active[i] is slot:
                                self._active[i] = None
                                retired.append(i)
                        # the request's decode phase is over: one span
                        # with the token count — the per-request cost
                        # record
                        self.tracer.record(
                            "engine.decode", start=slot.t_decode0,
                            end=t_read, parent=slot.req.ctx,
                            attrs={"model": self.name,
                                   "tokens": slot.produced})
                        break
            # retirement may disarm rows with a donating call: it runs
            # AFTER the emit loop, so a device failure lands with the
            # emitted accounting complete and recovery replays the
            # surviving streams (not the close protocol failing all)
            for i in retired:
                kv.retire(i)
            _occupancy.set(self.active_count, model=self.name)

    def _annotate(self, name: str):
        """``name`` on the profiler's host timeline while the block
        runs (the tracer's bridge; nothing where it has none)."""
        ann = self.tracer.annotator
        return ann(name) if ann is not None else contextlib.nullcontext()

    def _enter_leaf(self, name: str) -> None:
        """``name`` becomes THE annotation the engine thread lies in,
        until :meth:`_leave_leaf` (admission's leaves change mid-block,
        which a ``with`` cannot say)."""
        ann = self.tracer.annotator
        if ann is not None:
            self._leaf = ann(name)
            self._leaf.__enter__()

    def _leave_leaf(self) -> None:
        leaf, self._leaf = self._leaf, None
        if leaf is not None:
            leaf.__exit__(None, None, None)

    def _record_round(self, rnd: _Round) -> None:
        """Close the round ``rnd`` opened: one ``engine.round`` span whose
        five phase durations tile ``[start, end]`` (a phase the round
        never reached is 0; one that a recovery cut short runs to the
        end; the wait is carved out of admission's first stretch), and
        the same seconds into ``kftpu_engine_round_seconds_total{phase}``.
        ``counts`` is what a model's layers counted over the steps of the
        round read (the routed ones ``experts_hit`` and ``routed_pairs``,
        the sparse-attention ones ``index_scored`` and
        ``index_selected``, the step attention over dense K / V
        ``kv_blocks_read`` and ``kv_blocks_reserved``). ``ahead`` says
        the round read was launched before the round before it was read
        (``kftpu_engine_rounds_ahead_total``). ``compiles`` is the
        programs the process built or loaded while the cycle ran, and
        the tokens the cycle emitted (first tokens armed in admission
        included) reach ``kftpu_engine_tokens_total`` here, once."""
        end = self.clock()
        secs = rnd.secs
        secs[rnd.phase] += end - rnd._t
        secs["wait"] = rnd.wait_s
        secs["admit"] -= rnd.wait_s
        attrs = {"model": self.name, "round": self.rounds_total,
                 "rows": rnd.rows, "k": rnd.k, "admitted": self._admitted,
                 "greedy": rnd.greedy, "ahead": rnd.ahead,
                 "compiles": xprof.compiles_total() - rnd.compiles0}
        _tokens_total.inc(self.tokens_total - self._tokens_exported,
                          model=self.name)
        self._tokens_exported = self.tokens_total
        for phase, sec in secs.items():
            attrs[f"{phase}_s"] = sec
            _round_seconds.inc(sec, model=self.name, phase=phase)
        for counted, total in rnd.counts.items():
            attrs[counted] = total
            counter, labels = _STEP_COUNTERS[counted]
            counter.inc(total, model=self.name, **labels)
        if rnd.ahead:
            _rounds_ahead_c.inc(model=self.name)
        self.tracer.record("engine.round", start=rnd.start, end=end,
                           parent=self._run_ctx, attrs=attrs)
        self.rounds_total += 1

    def _live_streams(self) -> List[tuple]:
        """``(slot, stream)`` of every stream not yet ended: those that
        hold a slot first, then those whose last round is in flight (the
        slot they stepped in may hold the next request already). Call
        under ``_lock``."""
        live = [(i, s) for i, s in enumerate(self._active) if s is not None]
        seen = {id(s) for _, s in live}
        for flight in self._flights:
            for i, s in flight.rows:
                if not s.done and id(s) not in seen:
                    seen.add(id(s))
                    live.append((i, s))
        return live

    def _idle(self) -> bool:
        """Nothing to step, nothing in flight and nothing mid-admission:
        the one state in which the engine thread may block on its
        queue."""
        with self._lock:
            if self._flights or any(s is not None for s in self._active):
                return False
        return not (self._kv.in_admission or self._kv.waiting)

    def _wait_pending(self, timeout: float) -> tuple:
        """An idle engine's first arrival (None once ``timeout`` has
        passed) and the seconds it blocked for it. Only a read that
        really blocks is the round's wait phase (``wait_s``, the
        profiler's ``engine.wait``): a queued request costs no clock."""
        head = self._next_pending()
        if head is not None:
            return head, 0.0
        t0 = self.clock()
        try:
            with self._annotate("engine.wait"):
                head = self._pending.get(timeout=timeout)
        except queue.Empty:
            head = None
        return head, self.clock() - t0

    def _admit(self, head: Optional[_Request]) -> bool:
        """Admission, never blocking: ``head`` is what the wait phase
        took off the queue, the rest is whatever is pending now; the
        manager places them in the slots no stream holds."""
        if self.hbm_sampler is not None:
            try:
                self.hbm_sampler.sample()
            except Exception:  # noqa: BLE001 — watermarks never gate admits
                log.debug("hbm sample failed (continuing)", exc_info=True)
        with self._lock:
            free = [i for i, s in enumerate(self._active) if s is None]
        worked = self._kv.admit(head, free)
        _queue_depth.set(self.pending_count, model=self.name)
        _occupancy.set(self.active_count, model=self.name)
        return worked

    # -- cache recovery ----------------------------------------------------

    def _maybe_recover(self, where: str) -> bool:
        """A donating device call failed: the engine cache is consumed.
        While the recovery budget lasts, rebuild the cache from scratch
        and REPLAY every in-flight stream (prompt + emitted tokens
        re-prefill; sampling resumes at the preserved fold index): the
        engine keeps serving instead of failing every later call."""
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
        """Every stream not yet ended resumes from what it has emitted:
        the rounds in flight are dropped unread, and a stream's next
        token is sampled at fold index ``produced`` (its tokens' folds
        run 0, 1, …), so none is lost or sent twice. A stream whose last
        round was in flight goes back to its slot, or to a free one
        where an admission took it; with none free it fails retryably."""
        with self._lock:
            live = self._live_streams()
            self._active = [None] * self.slots
            self._flights.clear()
        # the manager's fresh cache, and the admissions it held mid-way
        held = self._kv.reset()
        taken = {args[0] for args in held}
        replays = []
        for i, st in live:
            if i in taken:
                free = [j for j in range(self.slots) if j not in taken]
                if not free:
                    log.warning("slot %d's stream has no slot to replay "
                                "into; failing it retryably", i)
                    st.done = True
                    self._fail(st.req, EngineClosed(
                        "engine cache recovered; stream evicted — retry"),
                        self.clock())
                    continue
                i = free[0]
            taken.add(i)
            replays.append((i, st.req, np.concatenate(
                [st.req.prompt, np.asarray(st.emitted, np.int32)]),
                st.produced, st.produced))
        # the live streams first, then whatever the manager held
        for args in replays + held:
            self._kv.replay(*args)

    def _drain(self, why: str) -> float:
        """Stop, and fail with the retryable :class:`EngineClosed`
        (503 / UNAVAILABLE) every request still held — decoding,
        mid-admission, head-of-line, pending: a stream nobody ends hangs
        its client forever in ``result()``. The lock pairs with submit():
        after this none can enqueue. Returns when the streams ended."""
        with self._lock:
            self._stop.set()
            held = [s.req for _, s in self._live_streams()]
            self._active = [None] * self.slots
            self._flights.clear()
            held.extend(self._kv.drain())
            while (req := self._next_pending()) is not None:
                held.append(req)
        t = self.clock()
        for req in held:
            self._fail(req, EngineClosed(why), t)
        return t

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.run_once()
            except Exception:  # noqa: BLE001
                log.exception("decode engine step failed; closing engine")
                # the donated cache is invalidated: this engine can
                # never step again. Closed, later submits raise
                # EngineClosed too and the repository evicts it, so the
                # next request builds a fresh engine
                self._drain("decode engine step failed")
                return


# what lives on the cache manager, under the names callers read (and
# tests patch) on the engine
DecodeEngine._cache = _kv_attr("cache")
for _name in ("kv_page_size", "kv_pages", "paged_attention_impl",
              "prefix_hits", "prefix_misses", "prefix_pages_shared",
              "cow_splits", "batch_prefills", "prefill_chunks",
              "prefix_cache_bytes", "_prefix_store", "_prefix_row_bytes",
              "_prefix_budget_bytes", "_pool", "_prefix_pages",
              "_prefill", "_prefill_batch", "_continue", "_insert",
              "_insert_rows", "_chunk", "_arm", "_copy_page"):
    setattr(DecodeEngine, _name, _kv_attr(_name))
