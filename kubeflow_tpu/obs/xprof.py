"""Compile-event ledger + device-memory watermarks: the profile plane.

The goodput ledger (PR 13) prices ``startup_compile``/``recompile``
per second — but until now those seconds were *inferred* from
step-beacon gaps, no ``kftpu_*`` series recorded an actual XLA compile
event, and HBM occupancy was metered only for KV pages. This module
closes the platform's last accounting blind spot with two pieces:

- :class:`CompileLedger` — subscribes to ``jax.monitoring`` duration
  events (filtered to the single ``backend_compile_duration`` event
  per compilation; jax also emits jaxpr-trace and MLIR-lowering
  durations for the same program, which must NOT triple-count) with a
  wrapper fallback (:meth:`CompileLedger.timed_compile`) for backends
  that don't emit them. Every compilation becomes one
  ``kftpu_compile_seconds{module,shape_class,generation}``
  observation, a ``compile`` span in the job's identity-derived trace
  tree, and an HLO fingerprint keyed with the tile table's vocabulary
  (:func:`~kubeflow_tpu.ops.autotune.seq_bucket` ×
  :func:`~kubeflow_tpu.ops.autotune.backend_generation`) — the same
  key the fleet-shared compile cache will be adjudicated against.
  Per-job cumulative totals feed the goodput fold a *ground-truth*
  attribution source (:func:`job_compile_seconds`) that takes
  precedence over beacon inference.
- :class:`HbmSampler` — per-step / per-admit sampling of
  ``device.memory_stats()`` into ``kftpu_hbm_bytes{kind}``
  (``in_use``/``peak``/``limit``) and ``kftpu_hbm_utilization``,
  wired into the trainer's :class:`~kubeflow_tpu.obs.steps.
  StepTelemetry` beacon and the serving engine's admit path. Static
  budgets from ``compiled.memory_analysis()`` (temp/argument/output
  bytes) land in ``kftpu_hbm_budget_bytes{kind}`` beside the
  fingerprint at compile time — every executable carries its
  predicted footprint, every job its live watermark.

Beside the ledger, :func:`compiles_total` is one process-wide count of
backend compiles (cache loads too) that needs no ledger: the decode
engine installs it and brackets its rounds and admissions with it
(``compiles`` on ``engine.round`` / ``engine.admission``).

Both degrade by contract: CPU backends return ``memory_stats() is
None`` and the sampler goes silent; a backend without monitoring
events simply never fires the listener (the wrapper fallback still
works); nothing here may fail a training step or an admit.

Exported series (docs/OBSERVABILITY.md "Compile & memory"):

- ``kftpu_compile_seconds{module,shape_class,generation[,namespace,
  job]}`` — histogram, one observation per backend compile;
- ``kftpu_hbm_bytes{kind[,identity...]}`` — live watermark gauges;
- ``kftpu_hbm_utilization{[identity...]}`` — ``in_use/limit``, the
  ``hbm-headroom`` alert's input (absent when the backend reports no
  limit);
- ``kftpu_hbm_budget_bytes{kind,module,shape_class,generation}`` —
  the static ``memory_analysis`` prediction per executable.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from kubeflow_tpu.obs.steps import tpujob_trace_ids
from kubeflow_tpu.obs.trace import SpanContext, Tracer
from kubeflow_tpu.ops.autotune import (
    backend_generation,
    dtype_name,
    seq_bucket,
)
from kubeflow_tpu.utils.clock import Clock
from kubeflow_tpu.utils.metrics import DEFAULT_REGISTRY, STEP_TIME_BUCKETS

log = logging.getLogger(__name__)

# jax emits THREE duration events per compilation (jaxpr trace, MLIR
# lowering, backend compile); counting any but the last would
# triple-bill every compile, and only backend_compile is the XLA wall
# time the goodput ledger carves
COMPILE_EVENT_SUFFIX = "backend_compile_duration"

HBM_KINDS = ("in_use", "peak", "limit")
BUDGET_KINDS = ("temp", "argument", "output", "generated_code", "alias")

# -- exported series ---------------------------------------------------------

_compile_h = DEFAULT_REGISTRY.histogram(
    "kftpu_compile_seconds",
    "XLA compilation wall time, one observation per backend compile, "
    "keyed by module / shape class / backend generation",
    buckets=STEP_TIME_BUCKETS)
_hbm_g = DEFAULT_REGISTRY.gauge(
    "kftpu_hbm_bytes",
    "device memory watermark (kind=in_use|peak|limit), sampled from "
    "device.memory_stats()")
_hbm_util_g = DEFAULT_REGISTRY.gauge(
    "kftpu_hbm_utilization",
    "device memory in_use/limit fraction (absent when the backend "
    "reports no limit)")
_hbm_budget_g = DEFAULT_REGISTRY.gauge(
    "kftpu_hbm_budget_bytes",
    "static memory_analysis budget per compiled executable "
    "(kind=temp|argument|output|generated_code|alias)")


def observe_compile(seconds: float, *, module: str, shape_class: str,
                    generation: str, namespace: str = "",
                    job: str = "") -> None:
    """One compile event into the histogram. Job identity labels the
    series the goodput fold reads back through the tsdb; an unlabeled
    observation (no job context) still lands in the fleet series."""
    labels = {"module": module, "shape_class": shape_class,
              "generation": generation}
    if job:
        labels.update({"namespace": namespace, "job": job})
    _compile_h.observe(max(float(seconds), 0.0), **labels)


def set_hbm_bytes(kind: str, value: float, *, namespace: str = "",
                  job: str = "", worker: Optional[int] = None,
                  model: str = "") -> None:
    labels: Dict[str, str] = {"kind": kind}
    if job:
        labels.update({"namespace": namespace, "job": job})
    if worker is not None:
        labels["worker"] = str(worker)
    if model:
        labels["model"] = model
    _hbm_g.set(float(value), **labels)


def set_hbm_utilization(value: float, *, namespace: str = "",
                        job: str = "", worker: Optional[int] = None,
                        model: str = "") -> None:
    labels: Dict[str, str] = {}
    if job:
        labels.update({"namespace": namespace, "job": job})
    if worker is not None:
        labels["worker"] = str(worker)
    if model:
        labels["model"] = model
    _hbm_util_g.set(float(value), **labels)


# -- shape-class / fingerprint vocabulary ------------------------------------


def shape_class_of(*args: Any) -> str:
    """Shape-class slug for a compile's call arguments, in the tile
    table's vocabulary: the pow2 :func:`seq_bucket` of the largest
    dimension seen plus the widest array dtype. Scalar-only calls
    class as ``scalar``."""
    max_dim = 0
    dt = ""
    queue: List[Any] = list(args)
    i = 0
    while i < len(queue):           # FIFO: first arg's dtype wins
        a = queue[i]
        i += 1
        if isinstance(a, (tuple, list)):
            queue.extend(a)
            continue
        if isinstance(a, dict):
            queue.extend(a.values())
            continue
        shape = getattr(a, "shape", None)
        if shape is None:
            continue
        for d in shape:
            try:
                max_dim = max(max_dim, int(d))
            except (TypeError, ValueError):
                continue
        dtype = getattr(a, "dtype", None)
        if dtype is not None and not dt:
            dt = dtype_name(dtype)
    if max_dim <= 0:
        return "scalar"
    return f"seq{seq_bucket(max_dim)}_{dt or 'any'}"


def hlo_fingerprint(lowered: Any) -> str:
    """16-hex HLO module hash from a lowered computation's text — the
    compile-cache key beside shape class × generation. Empty string
    when the backend declines to stringify."""
    try:
        text = lowered.as_text()
    except Exception:  # noqa: BLE001 — fingerprint is best-effort
        return ""
    return hashlib.sha256(str(text).encode()).hexdigest()[:16]


def compile_span_id(trace_id: str, worker: int, module: str,
                    seq: int) -> str:
    """Stable span id for one worker's Nth compile of ``module`` — a
    replayed emission re-records the identical span instead of
    forking (the :func:`~kubeflow_tpu.obs.steps.step_span_id`
    scheme)."""
    h = hashlib.sha256(
        f"{trace_id}/w{worker}/compile/{module}/{seq}".encode())
    return h.hexdigest()[:16]


# -- memory_analysis budgets -------------------------------------------------

_BUDGETS: Dict[str, Dict[str, Any]] = {}
_BUDGETS_LOCK = threading.Lock()


def memory_budget(compiled: Any) -> Dict[str, int]:
    """Static byte budget from a compiled executable's
    ``memory_analysis()``; empty dict when the backend declines
    (budgets are a prediction, never a requirement)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return {}
    if ma is None:
        return {}
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
        if ma is None:
            return {}
    out: Dict[str, int] = {}
    for kind in BUDGET_KINDS:
        v = getattr(ma, f"{kind}_size_in_bytes", None)
        if v is not None:
            try:
                out[kind] = int(v)
            except (TypeError, ValueError):
                continue
    return out


def record_memory_budget(compiled: Any, *, module: str, shape_class: str,
                         generation: str,
                         fingerprint: str = "") -> Dict[str, int]:
    """Record an executable's predicted footprint beside its
    fingerprint: one ``kftpu_hbm_budget_bytes{kind}`` gauge row per
    budget kind, plus the per-fingerprint registry
    :func:`budget_for` serves."""
    budget = memory_budget(compiled)
    for kind, v in budget.items():
        labels = {"kind": kind, "module": module,
                  "shape_class": shape_class, "generation": generation}
        _hbm_budget_g.set(float(v), **labels)
    if fingerprint and budget:
        with _BUDGETS_LOCK:
            _BUDGETS[fingerprint] = {
                "module": module, "shape_class": shape_class,
                "generation": generation, "bytes": dict(budget)}
    return budget


def budget_for(fingerprint: str) -> Optional[Dict[str, Any]]:
    with _BUDGETS_LOCK:
        b = _BUDGETS.get(fingerprint)
        return dict(b) if b else None


def budgets() -> Dict[str, Dict[str, Any]]:
    """Snapshot of every recorded fingerprint → budget."""
    with _BUDGETS_LOCK:
        return {fp: dict(b) for fp, b in _BUDGETS.items()}


# -- per-job ground-truth compile totals -------------------------------------

# (namespace, job) -> {"seconds": float, "count": int}; the in-process
# source the goodput fold prefers over beacon inference when no tsdb
# has scraped the histogram yet (the all-in-one-process tier)
_JOB_COMPILE_TOTALS: Dict[Tuple[str, str], Dict[str, float]] = {}
_TOTALS_LOCK = threading.Lock()


def job_compile_seconds(namespace: str, job: str) -> Optional[float]:
    """Cumulative event-sourced compile seconds for one job; ``None``
    when no ledger has recorded for it (the goodput fold then keeps
    its beacon-inference path — absence of evidence is not zero)."""
    with _TOTALS_LOCK:
        t = _JOB_COMPILE_TOTALS.get((namespace, job))
        return float(t["seconds"]) if t else None


def job_compile_totals(namespace: str, job: str) -> Dict[str, float]:
    with _TOTALS_LOCK:
        t = _JOB_COMPILE_TOTALS.get((namespace, job))
        return (dict(t) if t
                else {"seconds": 0.0, "count": 0})


def _reset_job_totals() -> None:
    """Test/smoke isolation hook."""
    with _TOTALS_LOCK:
        _JOB_COMPILE_TOTALS.clear()


# -- the process-wide compile count -------------------------------------------

# programs this process built or loaded (a hit in the persistent cache
# fires the backend-compile event too: it stalls the caller all the
# same). One integer, written under a lock by the listener, READ bare by
# whoever brackets a stretch of work with it (the decode engine: the two
# ends of a round and of an admission, never a per-token path)
_compiles = 0
_COMPILES_LOCK = threading.Lock()
_compiles_listening = False


def compiles_total() -> int:
    """Backend compiles (and cache loads) since
    :func:`install_compile_count`; 0 where it was never installed."""
    return _compiles


def _count_compile(event: str, _duration: float, **_kwargs: Any) -> None:
    global _compiles
    if str(event).endswith(COMPILE_EVENT_SUFFIX):
        with _COMPILES_LOCK:
            _compiles += 1


def install_compile_count() -> bool:
    """Feed :func:`compiles_total` from ``jax.monitoring``'s duration
    events, once a process (the first decode engine built calls this; it
    needs no :class:`CompileLedger`, and a ledger's stale-listener sweep
    leaves it alone). True when this call registered the listener."""
    global _compiles_listening
    with _COMPILES_LOCK:
        if _compiles_listening:
            return False
        try:
            from jax import monitoring
        except Exception:  # noqa: BLE001 — no jax: the count stays 0
            return False
        monitoring.register_event_duration_secs_listener(_count_compile)
        _compiles_listening = True
    return True


# -- the compile-event ledger ------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompileEvent:
    """One recorded compilation."""

    module: str
    seconds: float
    shape_class: str
    generation: str
    fingerprint: str
    start: float
    end: float


def _evict_stale_listeners() -> None:
    """Unregister compile listeners left by a PREVIOUS import of this
    module (importlib.reload re-executes the module and orphans its
    registered callback — the double-count path the satellite task
    names). Best-effort: reaches into jax's private listener list,
    degrades silently when the internals move."""
    try:
        from jax._src import monitoring as _mon

        stale = [cb for cb in list(
            getattr(_mon, "_event_duration_secs_listeners", []))
            if getattr(cb, "_kftpu_compile_listener", False)]
        for cb in stale:
            _unregister_listener(cb)
    except Exception:  # noqa: BLE001
        log.debug("stale-listener sweep failed (continuing)",
                  exc_info=True)


def _unregister_listener(cb: Callable[..., None]) -> bool:
    try:
        from jax._src import monitoring as _mon

        unreg = getattr(
            _mon, "_unregister_event_duration_listener_by_callback", None)
        if unreg is not None:
            unreg(cb)
            return True
        listeners = getattr(_mon, "_event_duration_secs_listeners", None)
        if listeners is not None and cb in listeners:
            listeners.remove(cb)
            return True
    except Exception:  # noqa: BLE001
        log.debug("listener unregister failed (continuing)",
                  exc_info=True)
    return False


class CompileLedger:
    """Records every XLA compilation as metric + span + job total.

    >>> ledger = CompileLedger(namespace="default", job="lm", worker=0)
    >>> ledger.install()                 # jax.monitoring subscription
    >>> ...                              # jit compiles are now ledgered
    >>> ledger.uninstall()               # explicit teardown

    Everything is injectable (clock, tracer, generation) per the
    TPU003 contract; the clock is wall time so compile spans join the
    job's identity-derived trace next to the operator's epoch-clock
    root span. ``install`` is idempotent per ledger and sweeps
    listeners orphaned by a module re-import, so one compilation can
    never double-count.
    """

    def __init__(self, *, namespace: str = "", job: str = "",
                 uid: str = "", worker: int = 0,
                 clock: Optional[Clock] = None,
                 tracer: Optional[Tracer] = None,
                 generation: Optional[str] = None,
                 capacity: int = 256) -> None:
        self.namespace = namespace
        self.job = job
        self.worker = worker
        self.clock: Clock = clock if clock is not None else time.time
        self.tracer = (tracer if tracer is not None
                       else Tracer(clock=self.clock))
        self.trace_id, self.root_span_id = tpujob_trace_ids(
            namespace, job, uid)
        # resolved lazily so a ledger constructed before jax init (or
        # with no jax at all on the edge tier) still works
        self._generation = generation
        self.capacity = max(1, int(capacity))
        self.events: List[CompileEvent] = []
        self._seq_by_module: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._listener: Optional[Callable[..., None]] = None
        # constructing with job identity ANNOUNCES the ground-truth
        # source: job_compile_seconds() flips from None to 0.0 and the
        # goodput fold's beacon inference stands down from worker boot
        # — otherwise the window before the first compile event would
        # still be inferred and the measured total could never match
        # the attributed startup_compile exactly
        if self.job:
            with _TOTALS_LOCK:
                _JOB_COMPILE_TOTALS.setdefault(
                    (self.namespace, self.job),
                    {"seconds": 0.0, "count": 0})

    @property
    def generation(self) -> str:
        if self._generation is None:
            try:
                self._generation = backend_generation()
            except Exception:  # noqa: BLE001
                self._generation = "unknown"
        return self._generation

    # -- recording ---------------------------------------------------------

    def record(self, module: str, seconds: float, *,
               shape_class: str = "", generation: str = "",
               fingerprint: str = "",
               end: Optional[float] = None) -> CompileEvent:
        """Ledger one compilation: histogram observation, ``compile``
        span parented on the job's root, per-job total, bounded event
        list. Never raises — a broken tracer must not fail the
        compile it measures."""
        seconds = max(float(seconds), 0.0)
        end_ts = float(end) if end is not None else float(self.clock())
        gen = generation or self.generation
        sc = shape_class or "unknown"
        ev = CompileEvent(module=module, seconds=seconds,
                          shape_class=sc, generation=gen,
                          fingerprint=fingerprint,
                          start=end_ts - seconds, end=end_ts)
        with self._lock:
            seq = self._seq_by_module.get(module, 0)
            self._seq_by_module[module] = seq + 1
            self.events.append(ev)
            if len(self.events) > self.capacity:
                del self.events[:len(self.events) - self.capacity]
        try:
            observe_compile(seconds, module=module, shape_class=sc,
                            generation=gen, namespace=self.namespace,
                            job=self.job)
        except Exception:  # noqa: BLE001
            log.debug("compile metric failed (continuing)", exc_info=True)
        if self.job:
            with _TOTALS_LOCK:
                t = _JOB_COMPILE_TOTALS.setdefault(
                    (self.namespace, self.job),
                    {"seconds": 0.0, "count": 0})
                t["seconds"] += seconds
                t["count"] += 1
        try:
            attrs: Dict[str, Any] = {
                "module": module, "shape_class": sc, "generation": gen,
                "seconds": round(seconds, 6), "worker": self.worker}
            if fingerprint:
                attrs["fingerprint"] = fingerprint
            self.tracer.record(
                f"compile/{module}", start=ev.start, end=ev.end,
                parent=SpanContext(self.trace_id, self.root_span_id),
                span_id=compile_span_id(self.trace_id, self.worker,
                                        module, seq),
                attrs=attrs)
        except Exception:  # noqa: BLE001
            log.debug("compile span failed (continuing)", exc_info=True)
        return ev

    def total_seconds(self) -> float:
        with self._lock:
            return sum(e.seconds for e in self.events)

    def summary(self) -> Dict[str, Any]:
        """The bench-artifact ``compile`` block shape."""
        with self._lock:
            evs = list(self.events)
        out: Dict[str, Any] = {
            "count": len(evs),
            "seconds": round(sum(e.seconds for e in evs), 6),
        }
        if evs:
            by_mod: Dict[str, float] = {}
            for e in evs:
                by_mod[e.module] = by_mod.get(e.module, 0.0) + e.seconds
            out["by_module"] = {m: round(s, 6)
                                for m, s in sorted(by_mod.items())}
            out["generation"] = evs[-1].generation
        return out

    def events_payload(self) -> Dict[str, Any]:
        """The ``--compile-audit`` artifact shape: every ledgered
        event, JSON-serializable, keyed for the static jit-site join
        (``kubeflow_tpu/analysis/compileaudit.py``)."""
        with self._lock:
            evs = list(self.events)
        return {"compile_events": [dataclasses.asdict(e) for e in evs]}

    # -- jax.monitoring subscription ---------------------------------------

    def install(self) -> bool:
        """Subscribe to jax's compile duration events. Idempotent per
        ledger (a second call is a no-op) and sweeps stale listeners
        from a prior module import first, so an event is ledgered at
        most once per process. Returns True when a new listener was
        registered."""
        with self._lock:
            if self._listener is not None:
                return False
        try:
            from jax import monitoring
        except Exception:  # noqa: BLE001 — no jax: wrapper fallback only
            return False

        def _cb(event: str, duration: float, **kwargs: Any) -> None:
            # one compilation fires three duration events; only
            # backend_compile is the XLA wall time (see module doc)
            if not str(event).endswith(COMPILE_EVENT_SUFFIX):
                return
            try:
                self.record(str(kwargs.get("module_name", "") or "xla"),
                            float(duration))
            except Exception:  # noqa: BLE001 — never fail the compile
                log.debug("compile listener failed (continuing)",
                          exc_info=True)

        _cb._kftpu_compile_listener = True  # re-import eviction marker
        _evict_stale_listeners()
        with self._lock:
            if self._listener is not None:  # lost an install race
                return False
            monitoring.register_event_duration_secs_listener(_cb)
            self._listener = _cb
        return True

    def uninstall(self) -> bool:
        """Explicit teardown of the monitoring subscription. Targets
        ONLY this ledger's callback — never jax's global
        clear_event_listeners, which would destroy other subscribers."""
        with self._lock:
            cb, self._listener = self._listener, None
        if cb is None:
            return False
        return _unregister_listener(cb)

    def __enter__(self) -> "CompileLedger":
        self.install()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.uninstall()

    # -- wrapper fallback (AOT path) ---------------------------------------

    def timed_compile(self, fn: Any, *args: Any,
                      module: str = "", **kwargs: Any) -> Any:
        """Lower + compile ``fn`` under the ledger's clock — the
        fallback for backends that emit no monitoring events, and the
        AOT path that ALSO fingerprints the HLO and records the
        ``memory_analysis`` budget beside it. Returns the compiled
        executable (or ``fn`` itself when it has no AOT surface)."""
        lower = getattr(fn, "lower", None)
        if lower is None:
            return fn
        name = module or getattr(fn, "__name__", "") or "xla"
        sc = shape_class_of(*args)
        t0 = self.clock()
        lowered = lower(*args, **kwargs)
        compiled = lowered.compile()
        t1 = self.clock()
        fp = hlo_fingerprint(lowered)
        self.record(name, t1 - t0, shape_class=sc, fingerprint=fp,
                    end=t1)
        try:
            record_memory_budget(compiled, module=name, shape_class=sc,
                                 generation=self.generation,
                                 fingerprint=fp)
        except Exception:  # noqa: BLE001
            log.debug("memory budget failed (continuing)", exc_info=True)
        return compiled


# -- device-memory watermarks ------------------------------------------------


def _device_memory_stats(index: int = 0) -> Optional[Mapping[str, Any]]:
    """``memory_stats()`` of one local device; None on CPU backends
    (which return None) and on any probe failure — the sampler's
    silent-degrade contract."""
    try:
        import jax

        devices = jax.local_devices()
        if not devices:
            return None
        return devices[min(index, len(devices) - 1)].memory_stats()
    except Exception:  # noqa: BLE001
        return None


class HbmSampler:
    """Samples device-memory watermarks into the ``kftpu_hbm_*``
    gauges and a beacon-ready snapshot.

    ``source`` is the injectable stats callable (tests and the CPU
    smoke inject a fake; production defaults to
    ``jax.local_devices()[i].memory_stats()``). A source returning
    None — every CPU backend — degrades silently: no gauges, no
    beacon fields, no errors. ``peak`` is max-seen across samples so
    a between-sample spike the allocator remembers is never lost."""

    def __init__(self, *, namespace: str = "", job: str = "",
                 worker: Optional[int] = None, model: str = "",
                 source: Optional[Callable[[], Optional[
                     Mapping[str, Any]]]] = None,
                 device_index: int = 0) -> None:
        self.namespace = namespace
        self.job = job
        self.worker = worker
        self.model = model
        self.source = source
        self.device_index = device_index
        self.peak_seen = 0.0
        self.last: Dict[str, float] = {}

    def sample(self) -> Optional[Dict[str, float]]:
        """One watermark sample → gauges; returns the kind → bytes
        dict, or None on silent degrade. Never raises."""
        try:
            stats = (self.source() if self.source is not None
                     else _device_memory_stats(self.device_index))
        except Exception:  # noqa: BLE001 — sampling never fails a step
            log.debug("hbm sample failed (continuing)", exc_info=True)
            return None
        if not stats:
            return None
        try:
            in_use = float(stats.get("bytes_in_use", 0) or 0)
            limit = float(stats.get("bytes_limit", 0) or 0)
            peak = float(stats.get("peak_bytes_in_use", 0) or 0)
            self.peak_seen = max(self.peak_seen, peak, in_use)
            out = {"in_use": in_use, "peak": self.peak_seen,
                   "limit": limit}
            ident = {"namespace": self.namespace, "job": self.job,
                     "worker": self.worker, "model": self.model}
            for kind in HBM_KINDS:
                set_hbm_bytes(kind, out[kind], **ident)
            if limit > 0:
                set_hbm_utilization(in_use / limit, **ident)
            self.last = out
            return out
        except Exception:  # noqa: BLE001
            log.debug("hbm sample failed (continuing)", exc_info=True)
            return None

    def beacon_fields(self) -> Dict[str, Any]:
        """The ``hbm`` block a :class:`~kubeflow_tpu.obs.steps.
        StepTelemetry` beacon carries; empty dict before the first
        successful sample (CPU tier: always empty)."""
        if not self.last:
            return {}
        return {"inUseBytes": int(self.last["in_use"]),
                "peakBytes": int(self.last["peak"]),
                "limitBytes": int(self.last["limit"])}
