"""JAX model server: REST predict API with tf-serving-parity surface.

Replaces TF-Serving / TensorRT-IS (reference surface: gRPC :9000 + REST
:8500, ``tf-serving-template.libsonnet:33-48``; JSON→gRPC bridge
``components/k8s-model-server/http-proxy/server.py``). Endpoints:

- ``GET /v1/models``                       list models + versions
- ``GET /v1/models/<name>``                per-model version status
- ``POST /v1/models/<name>:predict``       ``{"instances": [...]}``
- ``POST /v1/models/<name>/versions/<v>:predict``  pin a version
- ``GET /metrics`` / ``GET /healthz``

TPU-minded serving details: inputs are padded to fixed batch shapes so XLA
never recompiles per request; version hot-reload polls the base path the way
TF-Serving watches its model dir.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.obs import TRACER, extract
from kubeflow_tpu.obs import requests as reqobs
from kubeflow_tpu.serving.engine import EngineClosed, pow2_bucket
from kubeflow_tpu.serving.model_store import (
    LoadedModel,
    list_versions,
    load_version,
)
from kubeflow_tpu.utils import DEFAULT_REGISTRY

log = logging.getLogger(__name__)

_requests = DEFAULT_REGISTRY.counter(
    "kftpu_serving_requests_total", "predict requests")
_latency = DEFAULT_REGISTRY.gauge(
    "kftpu_serving_last_latency_seconds", "last predict latency")
_gen_requests = DEFAULT_REGISTRY.counter(
    "kftpu_serving_generate_requests_total", "generate requests")
_gen_latency = DEFAULT_REGISTRY.gauge(
    "kftpu_serving_generate_last_latency_seconds", "last generate latency")
# a streamed-generate yield suspended longer than this charges the
# request ledger's stream_stall phase; below it is scheduling jitter
STREAM_STALL_MIN_S = 0.05
_spec_requests = DEFAULT_REGISTRY.counter(
    "kftpu_serving_speculative_requests_total",
    "generate requests served through a speculative draft pair")
_spec_draft_tokens = DEFAULT_REGISTRY.counter(
    "kftpu_serving_speculative_draft_tokens_total",
    "draft tokens proposed to the target verifier")
_spec_accepted_tokens = DEFAULT_REGISTRY.counter(
    "kftpu_serving_speculative_accepted_tokens_total",
    "draft tokens the target verifier accepted")
_spec_rate = DEFAULT_REGISTRY.gauge(
    "kftpu_serving_speculative_last_acceptance_rate",
    "acceptance rate (accepted/proposed) of the last speculative request")

_warmup_failures = DEFAULT_REGISTRY.counter(
    "kftpu_serving_warmup_failures_total",
    "model-load warm-ups that raised (the version still serves; its "
    "first requests pay the compiles)")

_PAD_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


def run_generate(model, body: Dict[str, Any], max_batch_size: int, *,
                 model_name: str = "", stream: bool = False,
                 engine=None) -> Tuple[int, Dict[str, Any]]:
    """The generate core shared by the REST ``:generate`` endpoint and
    the gRPC ``Generate`` RPC: validation, prompt/new-token bucketing,
    the compiled decode call. Returns (http-style status, payload).

    With ``stream=True`` the payload carries ``token_stream`` — an
    iterator of per-step token lists (one ``(B,)`` row per decode
    position) — instead of the dense ``tokens`` matrix.

    With ``engine`` set (a :class:`~kubeflow_tpu.serving.engine.DecodeEngine`),
    each prompt row becomes an engine request sharing the engine's
    decode batch with every other in-flight caller: tokens stream as
    steps complete, ``eos_id`` stops a row early (the dense response
    right-pads finished rows with their final token), and row *i*
    samples reproducibly from ``seed + i`` regardless of co-tenants.
    Greedy output is identical to the bucketed batch path; sampled
    output is reproducible but not bitwise-equal to it."""
    if model.generate is None:
        return 400, {"error": f"model {model_name!r} (kind "
                              f"{model.kind!r}) does not support generate"}
    prompts = body.get("prompt_tokens")
    if prompts is None:
        return 400, {"error": "request must carry 'prompt_tokens' "
                              "(batch of int token lists)"}
    try:
        max_new = int(body.get("max_new_tokens", 16))
        temperature = float(body.get("temperature", 0.0))
        top_k = int(body.get("top_k", 0))
        top_p = float(body.get("top_p", 1.0))
        seed = int(body.get("seed", 0))
        # RAGGED batches are first-class: each row keeps its own length
        # (per-row cache positions in the decode core); iterating also
        # rejects scalars/0-d tensors (TypeError → 400)
        row_lens = [len(p) for p in prompts]
        if not row_lens:
            return 400, {"error": "prompt_tokens batch is empty"}
        if min(row_lens) < 1:
            return 400, {"error": "empty prompt row"}
        width = max(row_lens)
        if isinstance(prompts, np.ndarray):
            arr = prompts.astype(np.int32)
        else:
            arr = np.zeros((len(prompts), width), np.int32)
            for i, p in enumerate(prompts):
                arr[i, :row_lens[i]] = np.asarray(p, dtype=np.int32)
        # an explicit scalar true_len marks the shared real length of
        # every (right-padded) row — the gRPC tensor convention, also
        # honored for REST clients that pad client-side. The array is
        # sliced to it so the prompt bucket never undershoots the data.
        explicit = int(body.get("true_len", 0))
        if explicit:
            if not 1 <= explicit <= width:
                return 400, {"error": f"true_len {explicit} must be in "
                                      f"[1, {width}]"}
            row_lens = [explicit] * arr.shape[0]
            width = explicit
            arr = arr[:, :explicit]
    except (TypeError, ValueError) as e:
        return 400, {"error": f"bad prompt_tokens: {e}"}
    if max_new < 1:
        return 400, {"error": "max_new_tokens must be >= 1"}
    if temperature < 0:
        # a negative temperature silently inverts the distribution
        return 400, {"error": "temperature must be >= 0"}
    if not 0 <= top_k < 2**31:
        return 400, {"error": "top_k must be in [0, 2**31) (0 = no filter)"}
    if not 0.0 < top_p <= 1.0:
        return 400, {"error": "top_p must be in (0, 1]"}
    if not -2**31 <= seed < 2**31:
        # the seed is a traced int32 in the compiled sampler
        return 400, {"error": "seed must fit in int32"}
    try:
        prefix_len = int(body.get("prefix_len", 0))
    except (TypeError, ValueError):
        return 400, {"error": "prefix_len must be an int"}
    if prefix_len:
        if engine is None:
            return 400, {"error": "prefix_len requires the decode "
                                  "engine (server started with "
                                  "decode_slots=0)"}
        if not 0 < prefix_len < min(row_lens):
            return 400, {"error": f"prefix_len {prefix_len} must be in "
                                  f"(0, shortest prompt row "
                                  f"{min(row_lens)})"}
    eos_id = body.get("eos_id")
    if eos_id is not None:
        try:
            eos_id = int(eos_id)
        except (TypeError, ValueError):
            return 400, {"error": "eos_id must be an int token id"}
        if model.vocab_size and not 0 <= eos_id < model.vocab_size:
            return 400, {"error": f"eos_id must be in [0, "
                                  f"{model.vocab_size})"}
        if engine is None:
            # only the engine path watches for EOS; honoring it half the
            # time silently would be worse than refusing
            return 400, {"error": "eos_id requires the decode engine "
                                  "(server started with decode_slots=0)"}
    if arr.ndim != 2:
        return 400, {"error": f"prompt_tokens must be a 2-D batch of "
                              f"token lists, got shape {arr.shape}"}
    if arr.shape[0] > max_batch_size:
        return 400, {"error": f"batch {arr.shape[0]} exceeds max "
                              f"{max_batch_size}"}
    lens_arr = np.asarray(row_lens, np.int32)
    # pad columns never reach the model — check only real tokens
    col = np.arange(width)[None, :]
    real_mask = col < lens_arr[:, None]
    real_vals = arr[real_mask]
    if model.vocab_size and real_vals.size and (
            real_vals.min() < 0 or real_vals.max() >= model.vocab_size):
        # out-of-range ids would silently clamp in the embedding take
        return 400, {"error": f"token ids must be in [0, "
                              f"{model.vocab_size})"}
    true_len = int(lens_arr.max())
    ctx = model.max_seq_len or 0

    if body.get("speculative"):
        # draft-assisted greedy decoding through the paired draft
        # (models/decode.py:speculative_generate); bypasses the engine —
        # speculation optimizes single-stream latency, the engine
        # optimizes aggregate throughput
        try:
            draft_len = int(body.get("draft_len", 4))
        except (TypeError, ValueError):
            return 400, {"error": "draft_len must be an int"}
        if not 1 <= draft_len <= 16:
            return 400, {"error": "draft_len must be in [1, 16]"}
        draft = model.draft  # one atomic snapshot (see DraftPair)
        if draft is None:
            return 400, {"error": f"model {model_name!r} has no paired "
                                  "speculative draft (export one with "
                                  "export_model(..., draft_of=...); see "
                                  "kubeflow_tpu/train/distill.py)"}
        if temperature != 0.0:
            return 400, {"error": "speculative decoding is greedy-only "
                                  "(temperature must be 0)"}
        if stream:
            return 400, {"error": "speculative decoding does not "
                                  "stream (tokens emit in verified "
                                  "chunks)"}
        if eos_id is not None or prefix_len:
            return 400, {"error": "eos_id/prefix_len require the "
                                  "engine path; drop 'speculative' to "
                                  "use them"}
        return _run_generate_speculative(
            model, draft, arr, lens_arr, max_new=max_new, ctx=ctx,
            draft_len=draft_len, model_name=model_name)

    if engine is not None:
        return _run_generate_engine(
            engine, arr, row_lens, max_new=max_new, ctx=ctx,
            temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed, eos_id=eos_id, prefix_len=prefix_len,
            stream=stream,
            model_name=model_name, model_version=model.version)

    # prompt bucket: one compiled prefill per bucket, capped at the
    # model context (3072-context models serve 2100-token prompts) —
    # the same rule engine admission uses (pow2_bucket)
    bucket = pow2_bucket(true_len, ctx)
    # new-token bucket likewise (a client sweeping max_new_tokens
    # must not mint unbounded compiled programs); decode the bucket,
    # return the first max_new. Decode writes start at true_len (the
    # cache index resets there), so the budget is ctx - true_len —
    # NOT ctx - bucket, which would reject any prompt past half the
    # context. The clamped value is rounded DOWN to a power of two:
    # a raw ctx - true_len clamp would mint one compiled program per
    # distinct prompt length near the context end.
    budget = max(ctx - true_len, 0)
    new_bucket = pow2_bucket(max_new, 1 << 30)
    while new_bucket > budget:
        new_bucket //= 2
    if new_bucket < max_new <= budget:
        # the pow2 bucket doesn't fit but the exact ask does (prompt
        # 29 + max_new 3 in a 32-context model): serve it exactly —
        # a rare tail case, so the per-value compile is acceptable
        new_bucket = max_new
    if bucket < true_len or new_bucket < max_new:
        return 400, {"error": f"prompt ({true_len}) + max_new_tokens "
                              f"({max_new}) exceed the model context "
                              f"({ctx}); cache writes past it would "
                              "silently clamp"}
    padded = np.zeros((arr.shape[0], bucket), np.int32)
    padded[:, :width] = arr
    # batch padded like the predict path: one compiled shape; filler
    # rows get length 1 (length 0 would index position -1 at prefill)
    padded, n = _pad_batch(padded, max_batch_size)
    lens_padded = np.ones((padded.shape[0],), np.int32)
    lens_padded[:n] = lens_arr
    t0 = time.perf_counter()
    try:
        greedy = temperature == 0.0
        out = np.asarray(model.generate(
            jnp.asarray(padded), jnp.asarray(lens_padded), new_bucket,
            jnp.float32(temperature), seed,
            greedy=greedy,
            top_k=jnp.int32(top_k), top_p=jnp.float32(top_p),
            # greedy ignores the filters — don't mint a second compiled
            # program for greedy+filtered requests
            filtered=(top_k > 0 or top_p < 1.0) and not greedy,
            ))[:n, :max_new]
    except (TypeError, ValueError) as e:
        # JAX surfaces shape/dtype mismatches as TypeError/ValueError —
        # request-data problems the schema checks above can't see
        return 400, {"error": f"generate failed: "
                              f"{type(e).__name__}: {e}"}
    except Exception as e:  # noqa: BLE001
        # anything else is the model / runtime (XLA faults, OOM) — a
        # server error, not a client one
        return 500, {"error": f"generate failed: "
                              f"{type(e).__name__}: {e}"}
    dt = time.perf_counter() - t0
    _gen_requests.inc(model=model_name)
    _gen_latency.set(dt, model=model_name)
    if stream:
        return 200, {"token_stream": (out[:, t].tolist()
                                      for t in range(out.shape[1])),
                     "model_version": str(model.version)}
    return 200, {"tokens": out.tolist(),
                 "model_version": str(model.version),
                 "tokens_per_sec": round(out.size / dt, 1)}


def _run_generate_speculative(model, draft, arr, lens_arr, *, max_new,
                              ctx, draft_len,
                              model_name) -> Tuple[int, Dict[str, Any]]:
    """Speculative half of :func:`run_generate`: the paired draft
    proposes ``draft_len`` tokens per round, the target verifies them in
    one multi-token forward. Greedy output matches the plain path token
    for token (at f32 exactly; at bf16 up to argmax tie-breaks); the
    response and /metrics carry the acceptance stats that decide whether
    the draft pays for itself. Batches are served at their exact size
    (no filler-row padding — filler would contaminate the acceptance
    rate)."""
    # the FUSED variant: the whole propose-verify loop is one compiled
    # program per (configs, draft_len, max_new, shape bucket) — the
    # host-loop variant pays a device dispatch and a readback per
    # round, which dominates request latency for a small model
    from kubeflow_tpu.models.decode import speculative_generate_jit

    true_len = int(lens_arr.max())
    bucket = pow2_bucket(true_len, ctx)
    # max_new buckets like the plain path (server.py:237) — the fused
    # program is keyed by (configs, draft_len, max_new, shapes), so a
    # client sweeping max_new_tokens must not mint unbounded compiled
    # two-model while_loop programs. The budget subtracts draft_len
    # from BOTH contexts: speculation keeps up to draft_len in-flight
    # proposals past the output.
    budget = max(min(ctx, draft.config.max_seq_len)
                 - true_len - draft_len, 0)
    new_bucket = pow2_bucket(max_new, 1 << 30)
    while new_bucket > budget:
        new_bucket //= 2
    if new_bucket < max_new <= budget:
        # exact ask fits but its pow2 bucket doesn't — rare tail, the
        # per-value compile is acceptable
        new_bucket = max_new
    if bucket < true_len or new_bucket < max_new:
        return 400, {"error": f"prompt ({true_len}) + max_new_tokens "
                              f"({max_new}) + draft_len ({draft_len}) "
                              f"exceed the model context ({ctx}); "
                              "speculation needs slack for in-flight "
                              "proposals"}
    padded = np.zeros((arr.shape[0], bucket), np.int32)
    padded[:, :arr.shape[1]] = arr
    t0 = time.perf_counter()
    try:
        toks, stats = speculative_generate_jit(
            model.lm_config, model.lm_params,
            draft.config, draft.params,
            jnp.asarray(padded), max_new_tokens=new_bucket,
            draft_len=draft_len, true_len=jnp.asarray(lens_arr))
    except ValueError as e:
        # the context-slack check (prompt + max_new + draft_len must fit
        # BOTH models) raises eagerly — a request-shape problem
        return 400, {"error": f"generate failed: {e}"}
    except Exception as e:  # noqa: BLE001
        return 500, {"error": f"generate failed: "
                              f"{type(e).__name__}: {e}"}
    dt = time.perf_counter() - t0
    # stats (rounds/draft/accepted) describe the bucket-width run — the
    # actual work done — while tokens return only the requested width
    out = np.asarray(toks)[:, :max_new]
    rate = stats["accepted"] / max(stats["draft_tokens"], 1)
    _gen_requests.inc(model=model_name)
    _gen_latency.set(dt, model=model_name)
    _spec_requests.inc(model=model_name)
    _spec_draft_tokens.inc(stats["draft_tokens"], model=model_name)
    _spec_accepted_tokens.inc(stats["accepted"], model=model_name)
    _spec_rate.set(rate, model=model_name)
    return 200, {"tokens": out.tolist(),
                 "model_version": str(model.version),
                 "tokens_per_sec": round(out.size / dt, 1),
                 "speculative": {
                     "draft": draft.ref,
                     "draft_len": draft_len,
                     "rounds": stats["rounds"],
                     "draft_tokens": stats["draft_tokens"],
                     "accepted": stats["accepted"],
                     "acceptance_rate": round(rate, 3),
                 }}


def parse_serving_mesh(raw: Optional[str]):
    """``"tp=4"`` / ``"dp=2,tp=4"`` → a device mesh (None when unset).
    The env-facing twin of the trainer's MeshConfig."""
    if not raw:
        return None
    from kubeflow_tpu.parallel import MeshConfig, create_mesh

    kw = {}
    for part in raw.split(","):
        k, _, v = part.partition("=")
        k = k.strip()
        if k not in ("dcn", "dp", "pp", "tp"):
            raise ValueError(f"KFTPU_SERVING_MESH axis {k!r} (want "
                             "dcn/dp/pp/tp)")
        if k in kw:
            raise ValueError(f"KFTPU_SERVING_MESH repeats axis {k!r}")
        try:
            kw[k] = int(v)
        except ValueError:
            raise ValueError(
                f"KFTPU_SERVING_MESH axis {k!r} needs an integer size, "
                f"got {v.strip()!r} (format: 'tp=4' or 'dp=2,tp=4')"
            ) from None
    return create_mesh(MeshConfig(**kw))


def _run_generate_engine(engine, arr, row_lens, *, max_new, ctx,
                         temperature, top_k, top_p, seed, eos_id,
                         prefix_len, stream, model_name,
                         model_version) -> Tuple[int, Dict[str, Any]]:
    """Engine half of :func:`run_generate`: one engine request per
    prompt row, sharing the decode batch with all other callers."""
    over = [l for l in row_lens if l + max_new > ctx]
    if over:
        return 400, {"error": f"prompt ({max(over)}) + max_new_tokens "
                              f"({max_new}) exceed the model context "
                              f"({ctx})"}
    t0 = time.perf_counter()
    try:
        # per-row seeds derive from the request seed; int32 wraparound
        # keeps row seeds valid for any validated base seed
        reqs = [engine.submit(arr[i, :row_lens[i]], max_new=max_new,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p,
                              seed=int((np.int64(seed) + i) & 0x7FFFFFFF),
                              eos_id=eos_id, prefix_len=prefix_len)
                for i in range(arr.shape[0])]
    except ValueError as e:
        return 400, {"error": str(e)}
    except EngineClosed as e:
        # engine closed mid-request (version rollover) — retryable
        return 503, {"error": str(e)}
    _gen_requests.inc(model=model_name)

    if stream:
        def steps():
            # time suspended at each yield is the CLIENT not draining:
            # the writer thread is parked in wfile.write/flush, so the
            # gap charges the rows' lifecycle records as stream_stall
            # (threshold-gated; sub-threshold scheduling jitter is not
            # a stall). Same clock domain as the engine's ledger marks.
            rledger = getattr(engine, "rledger", None)
            clock = getattr(engine, "clock", time.monotonic)
            try:
                iters = [r.stream() for r in reqs]
                lasts = [0] * len(iters)
                done = [False] * len(iters)
                while True:
                    fresh = False
                    for i, it in enumerate(iters):
                        if done[i]:
                            continue
                        try:
                            lasts[i] = next(it)
                            fresh = True
                        except StopIteration:
                            done[i] = True
                    if not fresh:
                        return
                    # finished rows repeat their final token (EOS) so
                    # the line stays a full (B,) row
                    ty0 = clock()
                    yield [int(t) for t in lasts]
                    ty1 = clock()
                    if (rledger is not None
                            and ty1 - ty0 >= STREAM_STALL_MIN_S):
                        for r in reqs:
                            rledger.stall(getattr(r, "rid", ""),
                                          reqobs.STREAM_STALL, ty0, ty1)
            finally:
                _gen_latency.set(time.perf_counter() - t0,
                                 model=model_name)

        return 200, {"token_stream": steps(),
                     "model_version": str(model_version)}

    try:
        rows = [r.result() for r in reqs]
    except ValueError as e:
        return 400, {"error": f"generate failed: {e}"}
    except EngineClosed as e:
        # rollover killed the in-flight generation — retryable, not a
        # server fault
        return 503, {"error": f"generate failed: {e}"}
    except Exception as e:  # noqa: BLE001 — engine/runtime fault
        return 500, {"error": f"generate failed: "
                              f"{type(e).__name__}: {e}"}
    dt = time.perf_counter() - t0
    produced = sum(len(r) for r in rows)
    # EOS-terminated rows are right-padded with their final token so the
    # response keeps the dense (B, max_new) contract
    out = [row + [row[-1]] * (max_new - len(row)) for row in rows]
    _gen_latency.set(dt, model=model_name)
    return 200, {"tokens": out,
                 "model_version": str(model_version),
                 "tokens_per_sec": round(produced / dt, 1)}


def _pad_batch(arr: np.ndarray, max_batch: int) -> Tuple[np.ndarray, int]:
    """Pad the leading dim up to a fixed bucket to keep XLA shapes stable."""
    n = arr.shape[0]
    bucket = next((b for b in _PAD_BUCKETS if b >= n and b <= max_batch),
                  max_batch)
    if n == bucket:
        return arr, n
    pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0), n


class ModelRepository:
    """Models under ``<base>/<model_name>/<version>/`` with hot reload."""

    def __init__(self, base_path: str, *, poll_interval_s: float = 10.0,
                 pin_version: Optional[int] = None,
                 warmup_batches: Tuple[int, ...] = (),
                 decode_slots: int = 0,
                 decode_steps_per_sync: int = 1,
                 decode_mesh=None,
                 engine_options: Optional[Dict[str, Any]] = None) -> None:
        self.base_path = base_path
        self.poll_interval_s = poll_interval_s
        # padded batch buckets to precompile at load time, before the new
        # version is swapped in — no client request pays the XLA compile
        self.warmup_batches = tuple(warmup_batches)
        # When set (KFTPU_MODEL_VERSION from the per-version traffic-split
        # Deployment), serve exactly this version instead of hot-loading the
        # latest — otherwise every canary backend converges on the same model
        # and the Istio weight split is a no-op.
        self.pin_version = pin_version
        # > 0: transformer models serve :generate through a shared
        # continuous-batching DecodeEngine with this many slots
        # (concurrent callers share one compiled decode step)
        self.decode_slots = decode_slots
        self.decode_steps_per_sync = decode_steps_per_sync
        # a jax.sharding.Mesh: LMs too big for one chip serve through
        # the engine with tensor-parallel-sharded params + KV cache
        # (KFTPU_SERVING_MESH, e.g. "tp=4"); params are sharded once at
        # engine creation via the models' logical partition specs
        self.decode_mesh = decode_mesh
        # further DecodeEngine keywords (the deployment's cache sizing)
        self.engine_options = dict(engine_options or {})
        self._models: Dict[str, LoadedModel] = {}
        self._pinned: Dict[Tuple[str, int], LoadedModel] = {}
        self._engines: Dict[Tuple[str, int], Any] = {}
        # (version, store signature) of the last draft scan per model —
        # the poll loop skips unchanged stores (see _attach_draft)
        self._draft_scans: Dict[str, Any] = {}
        self._lock = threading.Lock()
        # engine construction allocates a full KV cache on device —
        # serialize it so racing first-callers can't transiently double
        # the HBM footprint
        self._engine_create_lock = threading.Lock()
        self._stop = threading.Event()
        self.refresh()

    def engine_for(self, name: str, model: LoadedModel):
        """The continuous-batching engine for this model version (created
        lazily), or None when disabled / not an LM. None also during a
        version rollover race (the model handed in is no longer served),
        so the caller falls back to the unary bucketed path rather than
        resurrecting a just-retired engine's KV cache."""
        if self.decode_slots <= 0 or model.lm_config is None:
            return None
        key = (name, model.version)

        def allowed_locked() -> bool:
            current = self._models.get(name)
            return ((current is not None and
                     current.version == model.version) or
                    key in self._pinned)

        with self._lock:
            eng = self._engines.get(key)
            if eng is not None and eng.closed:
                # a step failure self-closed it (its donated KV cache is
                # invalid) — evict so a fresh engine replaces it
                self._engines.pop(key, None)
                eng = None
            if eng is None and not allowed_locked():
                return None
        if eng is not None:
            return eng
        from kubeflow_tpu.serving.engine import DecodeEngine

        with self._engine_create_lock:
            with self._lock:
                eng = self._engines.get(key)  # a racer built it first
                if eng is not None and not eng.closed:
                    return eng
            # lm_params were sharded over decode_mesh at LOAD time
            # (load_version), so the engine shares the one in-HBM copy
            eng = DecodeEngine(model.lm_config, model.lm_params,
                               slots=self.decode_slots,
                               steps_per_sync=self.decode_steps_per_sync,
                               mesh=self.decode_mesh,
                               # same opt-in as predict bucket warmup:
                               # compile both step programs up front
                               precompile=bool(self.warmup_batches),
                               name=name, **self.engine_options)
            with self._lock:
                if not allowed_locked():
                    race = None  # retired while we were building
                else:
                    prior = self._engines.get(key)
                    if prior is not None and prior.closed:
                        self._engines.pop(key, None)  # evict the corpse
                    race = self._engines.setdefault(key, eng)
        if race is not eng:
            eng.close()
        return race

    def model_names(self) -> list:
        if not os.path.isdir(self.base_path):
            return []
        return sorted(
            d for d in os.listdir(self.base_path)
            if os.path.isdir(os.path.join(self.base_path, d)) and
            list_versions(os.path.join(self.base_path, d))
        )

    def refresh(self) -> None:
        for name in self.model_names():
            mdir = os.path.join(self.base_path, name)
            versions = list_versions(mdir)
            if not versions:
                continue
            if self.pin_version is not None:
                if self.pin_version not in versions:
                    log.warning("pinned version %d absent for model %s "
                                "(have %s); waiting", self.pin_version, name,
                                versions)
                    continue
                latest = self.pin_version
            else:
                latest = versions[-1]
            with self._lock:
                current = self._models.get(name)
            if current is not None and current.version == latest:
                # drafts pair/replace/detach on later polls without a
                # target version bump (cheap: _attach_draft gates on
                # the store signature and no-ops when nothing changed)
                if current.lm_config is not None:
                    self._attach_draft(name, current)
                continue
            # load + warm up outside the lock (disk read + jit can take
            # seconds); only the swap is serialized, so predicts never
            # stall on reload
            log.info("loading model %s version %d", name, latest)
            loaded = load_version(mdir, latest, mesh=self.decode_mesh)
            if loaded.lm_config is not None:
                self._attach_draft(name, loaded)
            self._warmup(name, loaded)
            with self._lock:
                self._models[name] = loaded
                # retire the outgoing version's decode engine (it holds a
                # full KV cache) — but keep engines for versions still
                # served from _pinned (explicit-version canary clients).
                # close() fails that engine's in-flight requests; clients
                # retry against the new version.
                stale = [k for k in self._engines
                         if k[0] == name and k[1] != latest
                         and k not in self._pinned]
                retired = [self._engines.pop(k) for k in stale]
            for eng in retired:
                eng.close()

    def _store_signature(self) -> Any:
        """A cheap change marker for the store: one stat per model dir
        (a new export touches its model dir's mtime). Lets the poll loop
        skip the O(models × versions) model.yaml walk of a draft scan
        when nothing was exported since the last scan."""
        try:
            names = sorted(os.listdir(self.base_path))
            return tuple(
                (d, os.path.getmtime(os.path.join(self.base_path, d)))
                for d in names
                if os.path.isdir(os.path.join(self.base_path, d)))
        except OSError:
            return None

    def _attach_draft(self, name: str, loaded: LoadedModel) -> None:
        """Pair a speculative-decoding draft from the same store (a
        sibling model whose ``model.yaml`` declares ``draft_of`` this
        model, exported by the ``train/distill.py`` recipe). Pairing is
        best-effort: a broken draft must never stop its target from
        serving. Negative results are cached against the store
        signature so a draft-less store isn't re-walked every poll."""
        from kubeflow_tpu.serving.model_store import find_draft_for

        sig = (loaded.version, self._store_signature())
        if self._draft_scans.get(name) == sig:
            return
        self._draft_scans[name] = sig
        try:
            pair = find_draft_for(self.base_path, name, loaded.version)
        except Exception:  # noqa: BLE001 — a broken store entry must
            # never abort the poll round that swaps in new versions
            log.warning("draft scan failed for %s", name, exc_info=True)
            return
        if pair is None:
            if loaded.draft is not None:
                # the draft export was deleted: one atomic detach
                log.info("draft %s for model %s removed — detaching",
                         loaded.draft.ref, name)
                loaded.draft = None
            return
        dname, dver = pair
        if loaded.draft is not None and \
                loaded.draft.ref == f"{dname}@{dver}":
            return  # unchanged pairing
        try:
            # the draft stays replicated (no mesh): it is small by
            # construction, and speculative_generate runs it alongside
            # the (possibly sharded) target
            d = load_version(os.path.join(self.base_path, dname), dver)
        except Exception:  # noqa: BLE001
            log.exception("failed to load draft %s@%d for %s",
                          dname, dver, name)
            return
        if d.lm_config is None:
            log.warning("draft %s@%d for %s is not a transformer — "
                        "ignoring", dname, dver, name)
            return
        if d.lm_config.vocab_size != loaded.lm_config.vocab_size:
            log.warning("draft %s@%d vocab %d != target %s vocab %d — "
                        "ignoring", dname, dver, d.lm_config.vocab_size,
                        name, loaded.lm_config.vocab_size)
            return
        # one atomic reference swap: request threads snapshot the whole
        # pair, so attach/replace can never expose torn config/params
        from kubeflow_tpu.serving.model_store import DraftPair

        loaded.draft = DraftPair(config=d.lm_config, params=d.lm_params,
                                 ref=f"{dname}@{dver}")
        log.info("paired speculative draft %s with model %s@%d",
                 loaded.draft.ref, name, loaded.version)

    def _warmup(self, name: str, loaded: LoadedModel) -> None:
        if not self.warmup_batches:
            return
        # export the series at 0 so "no warm-up failed" is a readable
        # fact, not an absent line
        _warmup_failures.inc(0, model=name)
        t0 = time.perf_counter()
        try:
            n = loaded.warmup(self.warmup_batches)
        except Exception:  # noqa: BLE001 — warmup is best-effort
            log.exception("warmup failed for %s v%d", name, loaded.version)
            _warmup_failures.inc(model=name)
            return
        if n:
            log.info("warmed %d batch buckets for %s v%d in %.1fs",
                     n, name, loaded.version, time.perf_counter() - t0)

    def get(self, name: str, version: Optional[int] = None) -> Optional[LoadedModel]:
        with self._lock:
            model = self._models.get(name)
        if model is None:
            return None
        if version is not None and model.version != version:
            with self._lock:
                cached = self._pinned.get((name, version))
            if cached is not None:
                return cached
            mdir = os.path.join(self.base_path, name)
            if version in list_versions(mdir):
                # no warmup here: this runs inside a client request, and
                # compiling every bucket synchronously would multiply the
                # first-request latency it is meant to prevent — the request
                # compiles just its own bucket
                loaded = load_version(mdir, version,
                                      mesh=self.decode_mesh)
                with self._lock:
                    self._pinned[(name, version)] = loaded
                return loaded
            return None
        return model

    def status(self, name: str) -> Optional[Dict[str, Any]]:
        mdir = os.path.join(self.base_path, name)
        versions = list_versions(mdir)
        if not versions:
            return None
        with self._lock:
            served = self._models.get(name)
        out: Dict[str, Any] = {
            "model_version_status": [
                {"version": str(v),
                 "state": "AVAILABLE" if served and served.version == v
                 else "END_OF_LIFE"}
                for v in versions
            ]
        }
        draft = served.draft if served is not None else None
        if draft is not None:
            # the paired speculative draft is part of the serving
            # surface — operators must be able to see the pairing
            out["speculative_draft"] = draft.ref
        return out

    def start_polling(self) -> None:
        def loop():
            while not self._stop.wait(self.poll_interval_s):
                try:
                    self.refresh()
                except Exception:  # noqa: BLE001
                    log.exception("model refresh failed")

        threading.Thread(target=loop, daemon=True).start()

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            engines = list(self._engines.values())
            self._engines.clear()
        for eng in engines:
            eng.close()


class ModelServer:
    def __init__(self, base_path: str, *, port: int = 8500,
                 max_batch_size: int = 8, poll_interval_s: float = 10.0,
                 pin_version: Optional[int] = None,
                 warmup: bool = False, decode_slots: int = 0,
                 decode_steps_per_sync: int = 1,
                 decode_mesh=None,
                 engine_options: Optional[Dict[str, Any]] = None) -> None:
        buckets = tuple(b for b in _PAD_BUCKETS if b <= max_batch_size)
        self.repo = ModelRepository(base_path, poll_interval_s=poll_interval_s,
                                    pin_version=pin_version,
                                    warmup_batches=buckets if warmup else (),
                                    decode_slots=decode_slots,
                                    decode_steps_per_sync=decode_steps_per_sync,
                                    decode_mesh=decode_mesh,
                                    engine_options=engine_options)
        self.port = port
        self.max_batch_size = max_batch_size
        self._httpd: Optional[ThreadingHTTPServer] = None

    # -- request handling --------------------------------------------------

    def handle_predict(self, name: str, version: Optional[int],
                       body: Dict[str, Any]) -> Tuple[int, Dict[str, Any]]:
        model = self.repo.get(name, version)
        if model is None:
            return 404, {"error": f"model {name!r}"
                         f"{f' version {version}' if version else ''} not found"}
        instances = body.get("instances")
        if instances is None:
            return 400, {"error": "request body must contain 'instances'"}
        try:
            arr = np.asarray(instances)
            if arr.ndim == 0 or arr.dtype == object:
                raise ValueError("instances must be a non-empty array")
            if arr.dtype == np.float64:
                arr = arr.astype(np.float32)
        except Exception as e:  # noqa: BLE001
            return 400, {"error": f"bad instances: {e}"}
        if arr.shape[0] > self.max_batch_size:
            return 400, {"error": f"batch {arr.shape[0]} exceeds max "
                                  f"{self.max_batch_size}"}
        if model.input_shape and tuple(arr.shape[1:]) != tuple(model.input_shape):
            # catch shape mismatches here so they stay client errors —
            # inside the jitted predict they'd surface as opaque 500s
            return 400, {"error": f"instance shape {tuple(arr.shape[1:])} "
                                  f"!= model input {tuple(model.input_shape)}"}
        t0 = time.perf_counter()
        padded, n = _pad_batch(arr, self.max_batch_size)
        try:
            out = np.asarray(model.predict(jnp.asarray(padded)))[:n]
        except (TypeError, ValueError) as e:
            # JAX surfaces shape/dtype mismatches as TypeError/ValueError;
            # models without input_shape metadata can't be pre-checked
            return 400, {"error": f"predict failed: {type(e).__name__}: {e}"}
        except Exception as e:  # noqa: BLE001
            # anything else is an execution fault (XLA runtime, OOM)
            return 500, {"error": f"predict failed: {type(e).__name__}: {e}"}
        dt = time.perf_counter() - t0
        _requests.inc(model=name)
        _latency.set(dt, model=name)
        return 200, {"predictions": out.tolist(),
                     "model_version": str(model.version)}

    def handle_generate(self, name: str, version: Optional[int],
                        body: Dict[str, Any],
                        stream: bool = False) -> Tuple[int, Dict[str, Any]]:
        """Autoregressive generation (transformer models): prompts are
        right-padded to a power-of-two bucket, so the compiled prefill is
        reused across prompt lengths (one compile per bucket, like the
        predict path's padded batch buckets)."""
        model = self.repo.get(name, version)
        if model is None:
            return 404, {"error": f"model {name!r} not found"}
        return run_generate(model, body, self.max_batch_size,
                            model_name=name, stream=stream,
                            engine=self.repo.engine_for(name, model))

    # -- HTTP plumbing -----------------------------------------------------

    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            # chunked transfer (the streaming generate path) needs 1.1;
            # every non-streamed response still sets Content-Length
            protocol_version = "HTTP/1.1"

            def _send(self, code: int, payload: Dict[str, Any]) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                path = self.path.rstrip("/")
                if path == "/healthz":
                    self._send(200, {"status": "ok"})
                elif path == "/metrics":
                    from kubeflow_tpu.utils.metrics import exposition

                    # the one exposition policy: exemplar suffixes only
                    # for a scraper that requested the extension — a
                    # classic prometheus must get a clean 0.0.4 body
                    body, ctype = exposition(DEFAULT_REGISTRY,
                                             dict(self.headers))
                    self.send_response(200)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif path == "/v1/models":
                    self._send(200, {"models": server.repo.model_names()})
                elif path.startswith("/v1/models/"):
                    name = path[len("/v1/models/"):]
                    status = server.repo.status(name)
                    if status is None:
                        self._send(404, {"error": f"model {name!r} not found"})
                    else:
                        self._send(200, status)
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):  # noqa: N802
                length = int(self.headers.get("Content-Length", "0"))
                try:
                    body = json.loads(self.rfile.read(length) or b"{}")
                except json.JSONDecodeError:
                    self._send(400, {"error": "invalid JSON"})
                    return
                path = self.path
                handlers = {":predict": server.handle_predict,
                            ":generate": server.handle_generate}
                verb = next((s for s in handlers if path.endswith(s)), None)
                if verb and path.startswith("/v1/models/"):
                    target = path[len("/v1/models/"):-len(verb)]
                    version: Optional[int] = None
                    if "/versions/" in target:
                        name, _, v = target.partition("/versions/")
                        if not v.isdigit():
                            self._send(400, {"error": f"bad version {v!r}"})
                            return
                        version = int(v)
                    else:
                        name = target
                    # continue the edge proxy's trace (or start one for
                    # direct in-mesh callers); engine submits made inside
                    # inherit this span via the context-local current span
                    remote = extract(dict(self.headers))
                    span_name = "serving" + verb.replace(":", ".")
                    if verb == ":generate" and body.get("stream"):
                        with TRACER.span(span_name, remote=remote,
                                         attrs={"model": name,
                                                "stream": True}) as sp:
                            code, payload = server.handle_generate(
                                name, version, body, stream=True)
                            sp.attrs["http.status"] = code
                        if code != 200:
                            self._send(code, payload)
                            return
                        # JSON-lines over chunked transfer: one line per
                        # decode step, flushed as the generation core
                        # yields it
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/jsonlines")
                        self.send_header("Transfer-Encoding", "chunked")
                        self.end_headers()

                        def chunk(obj):
                            line = json.dumps(obj).encode() + b"\n"
                            self.wfile.write(
                                f"{len(line):x}\r\n".encode() + line +
                                b"\r\n")
                            self.wfile.flush()

                        try:
                            for toks in payload["token_stream"]:
                                chunk({"tokens": toks})
                            chunk({"done": True,
                                   "model_version":
                                       payload["model_version"]})
                        except Exception as e:  # noqa: BLE001
                            # mid-stream failure: the 200 is already on
                            # the wire, so the error becomes a line
                            chunk({"error": f"{type(e).__name__}: {e}"})
                        self.wfile.write(b"0\r\n\r\n")
                        return
                    with TRACER.span(span_name, remote=remote,
                                     attrs={"model": name}) as sp:
                        code, payload = handlers[verb](name, version, body)
                        sp.attrs["http.status"] = code
                    self._send(code, payload)
                else:
                    self._send(404, {"error": "not found"})

            def log_message(self, *a):
                pass

        return Handler

    def start(self) -> int:
        """Start serving on a daemon thread; returns the bound port."""
        self._httpd = ThreadingHTTPServer(("0.0.0.0", self.port),
                                          self._make_handler())
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()
        self.repo.start_polling()
        log.info("model server on :%d (base_path=%s)", self.port,
                 self.repo.base_path)
        return self.port

    def stop(self) -> None:
        self.repo.stop()
        if self._httpd:
            self._httpd.shutdown()


def parse_pin_version(raw: Optional[str]) -> Optional[int]:
    """``"3"`` or the manifest's version label ``"v3"`` → 3; empty → None."""
    if not raw:
        return None
    digits = raw[1:] if raw[:1] in ("v", "V") else raw
    if not digits.isdigit():
        raise ValueError(f"KFTPU_MODEL_VERSION must be N or vN, got {raw!r}")
    return int(digits)


def server_options(env) -> Dict[str, Any]:
    """:class:`ModelServer`'s keywords from a pod's environment
    (``os.environ``): every deployment setting is read HERE, the decode
    engine reads none. A cache-sizing name (docs/SERVING.md "Knobs")
    left unset or empty leaves its keyword out: the engine's default."""
    engine_options: Dict[str, Any] = {}
    if env.get("KFTPU_PAGED", "0") not in ("0", ""):
        engine_options["paged"] = True  # fleet-wide, no code change
    for key, name in (("kv_page_size", "KFTPU_KV_PAGE_SIZE"),
                      ("kv_pages", "KFTPU_KV_PAGES"),
                      ("prefill_chunk_tokens", "KFTPU_PREFILL_CHUNK"),
                      ("prefix_cache_bytes", "KFTPU_PREFIX_CACHE_BYTES")):
        if env.get(name):
            engine_options[key] = int(env[name])
    return dict(
        port=int(env.get("KFTPU_REST_PORT", "8500")),
        max_batch_size=int(env.get("KFTPU_MAX_BATCH_SIZE", "8")),
        pin_version=parse_pin_version(env.get("KFTPU_MODEL_VERSION")),
        warmup=env.get("KFTPU_WARMUP", "1") != "0",
        # continuous batching is the production default; 0 falls back
        # to whole-request bucketed batches
        decode_slots=int(env.get("KFTPU_DECODE_SLOTS", "8")),
        decode_steps_per_sync=int(
            env.get("KFTPU_DECODE_STEPS_PER_SYNC", "4")),
        # "tp=4": serve LMs tensor-parallel over the pod's chips
        # (params + KV cache sharded)
        decode_mesh=parse_serving_mesh(env.get("KFTPU_SERVING_MESH")),
        engine_options=engine_options)


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    base = os.environ.get("KFTPU_MODEL_BASE_PATH", "/models")
    grpc_port = int(os.environ.get("KFTPU_GRPC_PORT", "9000"))
    # version reloads and pod restarts reuse compiled executables; the
    # pod places the cache with JAX_COMPILATION_CACHE_DIR
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    log.info("XLA compile cache at %s", enable_compile_cache())
    opts = server_options(os.environ)
    max_batch = opts["max_batch_size"]
    server = ModelServer(base, **opts)
    server.start()
    grpc_server = None  # keep the reference: grpc.Server dies when GC'd
    if grpc_port:
        try:
            from kubeflow_tpu.serving.grpc_server import serve_grpc

            grpc_server, _ = serve_grpc(server.repo, grpc_port,
                                        max_batch_size=max_batch)
        except ImportError as e:
            log.warning("gRPC disabled (grpc not importable: %s); "
                        "serving REST only", e)
    # serve until the pod ends: SIGTERM (kubelet) or Ctrl-C. Handling
    # SIGTERM — not dying by it — lets the engines close and the
    # process leave through interpreter exit, which is what hands the
    # chip back cleanly to the next process
    done = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: done.set())
    try:
        done.wait()
    except KeyboardInterrupt:
        pass
    server.stop()
    if grpc_server is not None:
        grpc_server.stop(grace=1.0)


if __name__ == "__main__":
    main()
