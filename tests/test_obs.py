"""Observability tier: distributed tracing + latency histograms.

The acceptance shape this file pins down (docs/OBSERVABILITY.md):

- W3C ``traceparent`` round-trips, with garbage/truncation degrading to
  "start a new trace", never an exception;
- deterministic span trees on a fake clock for the serving plane
  (proxy → HTTP server → decode engine: one trace, correct parent
  links, monotonically nested timestamps) and the workflow plane
  (steps share the workflow's identity-derived trace_id);
- the ring buffer evicts oldest-first at capacity;
- histogram bucket math (cumulative ``_bucket``/``_sum``/``_count``)
  and the registry's kind-mismatch guard;
- ``GET /api/traces`` + ``GET /api/traces/<trace_id>`` on the dashboard
  and the trace-collector service.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.obs import (
    REQUEST_ID_HEADER,
    SpanCollector,
    SpanContext,
    Tracer,
    current_span,
    extract,
    format_traceparent,
    grpc_metadata,
    otlp_lines,
    parse_otlp_lines,
    parse_traceparent,
)
from kubeflow_tpu.obs import trace as trace_mod
from kubeflow_tpu.utils.metrics import Histogram, Registry


class FakeClock:
    """Thread-safe tick clock: every read advances 1 ms — monotone and
    deterministic regardless of scheduling."""

    def __init__(self, start: float = 1000.0, step: float = 0.001):
        self.t = start
        self.step = step
        self._lock = threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            self.t += self.step
            return self.t


# -- traceparent round-trip --------------------------------------------------


def test_traceparent_round_trip():
    ctx = SpanContext("0af7651916cd43dd8448eb211c80319c",
                      "b7ad6b7169203331")
    header = format_traceparent(ctx)
    assert header == ("00-0af7651916cd43dd8448eb211c80319c-"
                      "b7ad6b7169203331-01")
    assert parse_traceparent(header) == ctx


@pytest.mark.parametrize("bad", [
    "",
    "garbage",
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331",  # truncated
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b716920333-01",  # short span
    "00-0af7651916cd43dd8448eb211c8031-b7ad6b7169203331-01",  # short trace
    "00-" + "0" * 32 + "-b7ad6b7169203331-01",               # zero trace
    "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",  # zero span
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",  # bad ver
    "00-0AF7651916CD43DD8448EB211C80319C-b7ad6b7169203331-01",  # uppercase
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01-xx",  # extra
    None,
    42,
])
def test_traceparent_garbage_degrades_to_none(bad):
    assert parse_traceparent(bad) is None


def test_extract_from_headers_and_grpc_metadata():
    ctx = SpanContext("0af7651916cd43dd8448eb211c80319c",
                      "b7ad6b7169203331")
    # header mapping, any casing
    assert extract({"TraceParent": format_traceparent(ctx)}) == ctx
    # gRPC invocation-metadata shape: iterable of pairs
    assert extract([("traceparent", format_traceparent(ctx))]) == ctx
    assert extract({}) is None
    assert extract(None) is None


def test_grpc_metadata_carries_current_span():
    tracer = Tracer(collector=SpanCollector(), clock=FakeClock())
    assert grpc_metadata() == ()
    with tracer.span("outer") as sp:
        md = grpc_metadata()
        assert md and extract(md) == sp.context()


# -- tracer / span trees -----------------------------------------------------


def test_span_tree_deterministic_on_fake_clock():
    clock = FakeClock(start=0.0, step=1.0)
    collector = SpanCollector()
    tracer = Tracer(collector=collector, clock=clock)
    with tracer.span("root", attrs={"k": "v"}) as root:
        with tracer.span("child_a"):
            pass
        with tracer.span("child_b") as b:
            assert current_span() is b
            with tracer.span("grandchild"):
                pass
    assert current_span() is None
    spans = {s.name: s for s in collector.spans()}
    assert set(spans) == {"root", "child_a", "child_b", "grandchild"}
    # one trace, correct parent links
    assert len({s.trace_id for s in spans.values()}) == 1
    assert spans["root"].parent_id is None
    assert spans["child_a"].parent_id == spans["root"].span_id
    assert spans["child_b"].parent_id == spans["root"].span_id
    assert spans["grandchild"].parent_id == spans["child_b"].span_id
    # fake-clock ticks: start order root < a < b < grandchild, and
    # every child nests inside its parent's [start, end]
    assert spans["root"].start == 1.0
    for name, parent in (("child_a", "root"), ("child_b", "root"),
                         ("grandchild", "child_b")):
        assert spans[parent].start < spans[name].start
        assert spans[name].end < spans[parent].end


def test_span_remote_parent_and_error_status():
    tracer = Tracer(collector=SpanCollector(), clock=FakeClock())
    remote = SpanContext("ab" * 16, "cd" * 8)
    with pytest.raises(RuntimeError):
        with tracer.span("handler", remote=remote):
            raise RuntimeError("boom")
    (sp,) = tracer.collector.spans()
    assert sp.trace_id == remote.trace_id
    assert sp.parent_id == remote.span_id
    assert sp.status == "ERROR: RuntimeError"


def test_ring_buffer_evicts_oldest():
    clock = FakeClock(start=0.0, step=1.0)
    collector = SpanCollector(capacity=8)
    tracer = Tracer(collector=collector, clock=clock)
    for i in range(20):
        with tracer.span(f"s{i}"):
            pass
    assert len(collector) == 8
    assert collector.recorded_total == 20
    names = [s.name for s in collector.spans()]
    assert names == [f"s{i}" for i in range(12, 20)]  # oldest evicted


def test_otlp_lines_round_trip():
    clock = FakeClock(start=5.0, step=1.0)
    collector = SpanCollector()
    tracer = Tracer(collector=collector, clock=clock)
    with tracer.span("a", attrs={"n": 1}):
        with tracer.span("b"):
            pass
    text = otlp_lines(collector.spans())
    assert len(text.splitlines()) == 2
    back = parse_otlp_lines(text + "\n{garbage\n")
    assert [s.name for s in back] == ["b", "a"]  # record order (end time)
    orig = {s.span_id: s for s in collector.spans()}
    for s in back:
        assert s.trace_id == orig[s.span_id].trace_id
        assert s.parent_id == orig[s.span_id].parent_id
        assert abs(s.start - orig[s.span_id].start) < 1e-6


# -- histograms --------------------------------------------------------------


def test_histogram_bucket_math():
    h = Histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):
        h.observe(v, route="/x")
    counts = h.bucket_counts(route="/x")
    # cumulative: le=0.1 includes 0.05 and the boundary value 0.1
    assert counts == {"0.1": 2, "1": 3, "10": 4, "+Inf": 5}
    assert h.get(route="/x") == 5
    assert h.sum(route="/x") == pytest.approx(102.65)
    text = h.expose()
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{route="/x",le="0.1"} 2' in text
    assert 'lat_bucket{route="/x",le="+Inf"} 5' in text
    assert 'lat_count{route="/x"} 5' in text
    assert 'lat_sum{route="/x"}' in text


def test_histogram_no_labels_and_misuse():
    h = Histogram("h", "", buckets=(1.0,))
    h.observe(0.5)
    assert "h_bucket{le=\"1\"} 1" in h.expose()
    with pytest.raises(TypeError):
        h.inc()
    with pytest.raises(TypeError):
        h.set(3.0)


def test_registry_kind_mismatch_raises():
    reg = Registry()
    reg.counter("m", "a counter")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("m")
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("m")
    # same kind re-registration still returns the shared instance
    assert reg.counter("m") is reg.counter("m")
    h = reg.histogram("h", buckets=(1.0, 2.0))
    assert reg.histogram("h") is h
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("h")


def test_serve_metrics_exact_paths():
    from kubeflow_tpu.utils.metrics import serve_metrics

    reg = Registry()
    reg.counter("c", "help").inc()
    t = serve_metrics(0, reg)
    port = t.server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.headers["Content-Type"] == "text/plain; version=0.0.4"
            assert b"c 1" in r.read()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            # health probe: no exposition version suffix
            assert r.headers["Content-Type"] == "text/plain"
            assert r.read() == b"ok\n"
        # the old substring test served the exposition for any path
        # merely containing "metrics"
        for bad in ("/healthz-metrics", "/foometrics", "/metrics/x"):
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + bad, timeout=10)
            assert e.value.code == 404
        # query strings route on the path alone
        with urllib.request.urlopen(base + "/healthz?x=metrics",
                                    timeout=10) as r:
            assert r.read() == b"ok\n"
    finally:
        t.server.shutdown()


# -- serving plane: proxy -> HTTP server -> engine ---------------------------


@pytest.fixture(scope="module")
def serving_stack(tmp_path_factory):
    """Edge proxy routing /serving/ to a ModelServer whose :generate
    runs through the continuous-batching DecodeEngine."""
    from kubeflow_tpu.edge.proxy import EdgeProxy, Route
    from kubeflow_tpu.models import Transformer, TransformerConfig
    from kubeflow_tpu.serving import (
        ModelServer,
        export_model,
        transformer_export_config,
    )

    config = TransformerConfig(vocab_size=97, d_model=32, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=64,
                               max_seq_len=32, dtype=jnp.float32,
                               remat=False)
    prompt = jax.random.randint(jax.random.key(1), (1, 5), 0,
                                config.vocab_size)
    params = Transformer(config).init(jax.random.key(0), prompt)["params"]
    base = tmp_path_factory.mktemp("models")
    export_model(str(base / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(base), port=0, poll_interval_s=3600,
                      decode_slots=4)
    srv_port = srv.start()
    proxy = EdgeProxy([Route("/serving/", f"http://127.0.0.1:{srv_port}")])
    proxy_port = proxy.start(0)
    yield f"http://127.0.0.1:{proxy_port}", np.asarray(prompt)
    proxy.stop()
    srv.stop()


def _post(url, body, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def _wait_for_trace(collector, trace_id, names, timeout=10.0):
    """Engine spans are recorded by the engine thread; poll briefly."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = collector.trace(trace_id)
        if names <= {s.name for s in spans}:
            return spans
        time.sleep(0.02)
    return collector.trace(trace_id)


def test_proxy_server_engine_single_trace(serving_stack, monkeypatch):
    """The acceptance trace: one request, proxy -> server -> engine,
    >= 4 spans sharing a trace_id with correct parent links and
    monotonically nested timestamps."""
    base, prompt = serving_stack
    collector = SpanCollector()
    # every default-constructed tracer (proxy/server TRACER, the
    # engine's private fake-clock-capable tracer) resolves the module
    # DEFAULT_COLLECTOR dynamically — swap it for a private buffer
    monkeypatch.setattr(trace_mod, "DEFAULT_COLLECTOR", collector)
    status, headers, out = _post(
        base + "/serving/v1/models/lm:generate",
        {"prompt_tokens": prompt.tolist(), "max_new_tokens": 4},
        # forged trace context must NOT graft onto our trace
        headers={"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
                 REQUEST_ID_HEADER: "forged-id"})
    assert status == 200
    assert len(out["tokens"][0]) == 4
    rid = headers.get(REQUEST_ID_HEADER)
    assert rid and rid != "forged-id" and rid != "ab" * 16
    spans = _wait_for_trace(
        collector, rid,
        {"edge.request", "serving.generate", "engine.queue_wait",
         "engine.admit", "engine.decode"})
    by_name = {s.name: s for s in spans}
    assert {"edge.request", "serving.generate", "engine.queue_wait",
            "engine.admit", "engine.prefill",
            "engine.decode"} <= set(by_name)
    assert len(spans) >= 4
    # one trace
    assert {s.trace_id for s in spans} == {rid}
    # parent links: edge is root; server continues it; engine spans
    # parent onto the server's span (captured at submit time)
    edge = by_name["edge.request"]
    serving = by_name["serving.generate"]
    assert edge.parent_id is None
    assert serving.parent_id == edge.span_id
    for name in ("engine.queue_wait", "engine.admit", "engine.decode"):
        assert by_name[name].parent_id == serving.span_id, name
    assert by_name["engine.prefill"].parent_id == \
        by_name["engine.admit"].span_id
    # monotonically nested timestamps: every child starts after its
    # parent started and within the parent's window
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.parent_id and s.parent_id in by_id:
            parent = by_id[s.parent_id]
            assert parent.start <= s.start, s.name
            assert s.start <= parent.end, s.name
    # the decode span carries its token count
    assert by_name["engine.decode"].attrs["tokens"] == 4
    assert edge.attrs["http.status"] == 200
    # the same trace is retrievable through the dashboard API
    from kubeflow_tpu.dashboard.server import DashboardApi
    from kubeflow_tpu.k8s import FakeKubeClient
    from kubeflow_tpu.tenancy.authz import allow_all

    api = DashboardApi(FakeKubeClient(), authorize=allow_all,
                       collector=collector)
    code, payload = api.handle("GET", f"/api/traces/{rid}", None)
    assert code == 200
    assert {s["name"] for s in payload["spans"]} >= {
        "edge.request", "serving.generate", "engine.decode"}
    code, roots = api.handle("GET", "/api/traces", None)
    assert code == 200
    ours = [r for r in roots if r["trace_id"] == rid]
    assert ours and ours[0]["name"] == "edge.request"
    assert ours[0]["spans"] >= 4
    code, _ = api.handle("GET", "/api/traces/ffff", None)
    assert code == 404


def test_request_latency_histogram_in_exposition(serving_stack):
    """request_latency_seconds{route,code} appears in the /metrics
    exposition with correct cumulative bucket counts."""
    from kubeflow_tpu.edge.proxy import _latency_h
    from kubeflow_tpu.utils import DEFAULT_REGISTRY

    base, prompt = serving_stack
    before = _latency_h.get(route="/serving/", code="200")
    status, _, _ = _post(base + "/serving/v1/models/lm:generate",
                         {"prompt_tokens": prompt.tolist(),
                          "max_new_tokens": 2})
    assert status == 200
    # the proxy observes AFTER writing the response (the span's finally
    # block), so the client can get here before the handler thread has
    # ticked the histogram — wait for the observation, bounded
    deadline = time.monotonic() + 5.0
    while (_latency_h.get(route="/serving/", code="200") < before + 1
           and time.monotonic() < deadline):
        time.sleep(0.01)
    after = _latency_h.get(route="/serving/", code="200")
    assert after == before + 1
    counts = _latency_h.bucket_counts(route="/serving/", code="200")
    assert counts["+Inf"] == after  # cumulative top bucket == _count
    text = DEFAULT_REGISTRY.expose()
    assert "# TYPE request_latency_seconds histogram" in text
    assert 'request_latency_seconds_bucket{code="200",route="/serving/"' \
        in text
    assert 'request_latency_seconds_count{code="200",route="/serving/"}' \
        in text
    # the engine queue-wait histogram observed the admissions too
    assert "# TYPE engine_queue_wait_seconds histogram" in text
    assert 'engine_queue_wait_seconds_count{model="lm"}' in text


def test_proxy_strips_inbound_trace_headers(serving_stack):
    """Client-supplied X-Request-Id / traceparent never reach the
    backend; the proxy's verified values replace them (the
    X-Kubeflow-Userid treatment, applied to trace context)."""
    from kubeflow_tpu.edge.proxy import EdgeProxy, Route
    from kubeflow_tpu.utils.jsonhttp import serve_json

    seen = {}

    def handle(method, path, body, user, headers):
        seen.update(headers)
        return 200, {"ok": True}

    backend = serve_json(handle, 0, background=True, host="127.0.0.1")
    proxy = EdgeProxy([Route(
        "/", f"http://127.0.0.1:{backend.server_address[1]}",
        strip_prefix=False)])
    port = proxy.start(0)
    try:
        status, headers, _ = _post(
            f"http://127.0.0.1:{port}/echo", {},
            headers={"traceparent":
                     "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
                     "X-Request-ID": "forged",
                     "tracestate": "vendor=1"})
        assert status == 200
        rid = headers[REQUEST_ID_HEADER]
        lower = {k.lower(): v for k, v in seen.items()}
        assert lower["x-request-id"] == rid != "forged"
        assert lower["traceparent"].split("-")[1] == rid != "ab" * 16
        assert "tracestate" not in lower
    finally:
        proxy.stop()
        backend.shutdown()


# -- engine spans on a fake clock (no HTTP) ----------------------------------


@pytest.fixture(scope="module")
def lm():
    from kubeflow_tpu.models import Transformer, TransformerConfig

    config = TransformerConfig(vocab_size=97, d_model=32, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=64,
                               max_seq_len=48, dtype=jnp.float32,
                               remat=False)
    params = Transformer(config).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    return config, params


def test_engine_spans_deterministic_fake_clock(lm):
    from kubeflow_tpu.serving.engine import DecodeEngine

    config, params = lm
    clock = FakeClock(start=0.0, step=1.0)
    collector = SpanCollector()
    tracer = Tracer(collector=collector, clock=clock)
    eng = DecodeEngine(config, params, slots=2, autostart=False,
                       clock=clock, tracer=tracer)
    parent = Tracer(collector=collector, clock=clock)
    with parent.span("caller") as sp:
        req = eng.submit([5, 11, 17], max_new=3)
    assert req.ctx == sp.context()
    for _ in range(6):
        eng.run_once(timeout=0.01)
    assert len(req.result()) == 3
    by_name = {s.name: s for s in collector.spans()}
    for name in ("engine.queue_wait", "engine.admit", "engine.prefill",
                 "engine.decode"):
        assert name in by_name, name
        assert by_name[name].trace_id == sp.trace_id
    # queue_wait starts at submit time, before admission
    assert by_name["engine.queue_wait"].start < \
        by_name["engine.admit"].start
    assert by_name["engine.admit"].start < \
        by_name["engine.decode"].start < by_name["engine.decode"].end
    assert by_name["engine.decode"].attrs["tokens"] == 3
    assert by_name["engine.admit"].attrs["prompt_tokens"] == 3


# -- workflow plane ----------------------------------------------------------


def test_workflow_steps_share_trace(monkeypatch):
    from kubeflow_tpu.k8s import FakeKubeClient
    from kubeflow_tpu.workflows import (
        WorkflowController,
        container_step,
        resource_step,
        workflow,
    )
    from kubeflow_tpu.workflows.controller import workflow_trace_ids

    client = FakeKubeClient()
    collector = SpanCollector()
    now = {"t": 1_700_000_000.0}
    clock = lambda: now["t"]  # noqa: E731
    ctrl = WorkflowController(client, clock=clock,
                              tracer=Tracer(collector=collector,
                                            clock=clock))
    target = {"apiVersion": "kubeflow-tpu.org/v1alpha1", "kind": "TpuJob",
              "metadata": {"name": "job", "namespace": "default"},
              "spec": {"image": "x"}}
    client.create(workflow("w", "default", [
        resource_step("launch", "create", target,
                      success_condition="status.startTime"),
        container_step("report", "img", dependencies=["launch"]),
    ]))
    ctrl.reconcile("default", "w")
    now["t"] += 30.0
    created = client.get("kubeflow-tpu.org/v1alpha1", "TpuJob",
                         "default", "job")
    created.setdefault("status", {})["startTime"] = "t"
    client.update_status(created)
    ctrl.reconcile("default", "w")  # launch succeeds, report launches
    now["t"] += 10.0
    for pod in client.list("v1", "Pod", "default"):
        pod.setdefault("status", {})["phase"] = "Succeeded"
        client.update_status(pod)
    ctrl.reconcile("default", "w")
    from kubeflow_tpu.workflows import WORKFLOW_API_VERSION, WORKFLOW_KIND

    wf = client.get(WORKFLOW_API_VERSION, WORKFLOW_KIND, "default", "w")
    assert wf["status"]["phase"] == "Succeeded"

    uid = wf["metadata"].get("uid", "")
    tid, root_id = workflow_trace_ids("default", "w", uid)
    spans = collector.trace(tid)
    by_name = {s.name: s for s in spans}
    assert set(by_name) == {"workflow/w", "workflow.step/launch",
                            "workflow.step/report"}
    root = by_name["workflow/w"]
    assert root.span_id == root_id and root.parent_id is None
    for step in ("workflow.step/launch", "workflow.step/report"):
        assert by_name[step].trace_id == tid
        assert by_name[step].parent_id == root_id
    # step spans carry the persisted start/finish times: launch ran 30s
    launch = by_name["workflow.step/launch"]
    assert launch.end - launch.start == pytest.approx(30.0)
    assert root.start <= launch.start and launch.end <= root.end
    # replaying reconcile on the terminal CR records nothing new
    n = len(collector.spans())
    ctrl.reconcile("default", "w")
    assert len(collector.spans()) == n


# -- trace-collector service -------------------------------------------------


def test_trace_collector_service_ingest_and_query():
    from kubeflow_tpu.obs.export import _span_record
    from kubeflow_tpu.obs.service import TraceCollectorService

    clock = FakeClock(start=0.0, step=1.0)
    src = SpanCollector()
    tracer = Tracer(collector=src, clock=clock)
    with tracer.span("push.root"):
        with tracer.span("push.child"):
            pass
    svc = TraceCollectorService(SpanCollector(capacity=128))
    code, out = svc.handle("POST", "/api/traces:ingest",
                           {"spans": [_span_record(s)
                                      for s in src.spans()] + ["junk"]})
    assert code == 200 and out["accepted"] == 2 and out["rejected"] == 1
    code, roots = svc.handle("GET", "/api/traces", None)
    assert code == 200 and roots[0]["name"] == "push.root"
    tid = roots[0]["trace_id"]
    code, detail = svc.handle("GET", f"/api/traces/{tid}", None)
    assert code == 200
    assert [s["name"] for s in detail["spans"]] == ["push.root",
                                                    "push.child"]
    code, chrome = svc.handle("GET", f"/api/traces/{tid}:chrome", None)
    assert code == 200
    assert {e["name"] for e in chrome["traceEvents"]} == {"push.root",
                                                          "push.child"}
    code, _ = svc.handle("GET", "/api/traces/nope", None)
    assert code == 404
    code, _ = svc.handle("POST", "/api/traces:ingest", {"spans": "x"})
    assert code == 400


def test_trace_collector_component_renders():
    from kubeflow_tpu.config.deployment import (
        ComponentSpec,
        DeploymentConfig,
    )
    from kubeflow_tpu.manifests.registry import render_component

    config = DeploymentConfig(name="demo", components=[])
    objs = render_component(config, ComponentSpec("trace-collector"))
    kinds = {o["kind"] for o in objs}
    assert {"ServiceAccount", "ClusterRole", "ClusterRoleBinding",
            "Deployment", "Service"} <= kinds
    svc = next(o for o in objs if o["kind"] == "Service")
    assert svc["metadata"]["name"] == "trace-collector"
    assert svc["spec"]["ports"][0]["port"] == 8095
    annotations = svc["metadata"]["annotations"]
    assert annotations["prometheus.io/scrape"] == "true"


def test_engine_round_phases_on_the_profiler_timeline(lm):
    """With a recording annotator in the profiler bridge's place: the
    ``engine.*`` annotations TILE the engine thread, none ever nested in
    another. A round is admission's leaves (``engine.admit.host``, and
    inside a row admission ``.launch``, ``.insert``, ``.read`` in the
    order that path runs them), then ``engine.step``, ``engine.sync``,
    ``engine.emit``; no clock read of ``run_once`` falls outside one of
    them except the round's own phase boundaries and the admission's,
    each of which is read once, between two leaves."""
    import contextlib

    from kubeflow_tpu.serving.engine import DecodeEngine

    config, params = lm
    tick = FakeClock(start=0.0, step=1.0)
    events = []                      # ("B"|"E", name, depth before / after)
    reads = []                       # (t, annotation depth at the read)
    depth = [0]

    def clock():
        t = tick()
        reads.append((t, depth[0]))
        return t

    @contextlib.contextmanager
    def annotator(name):
        events.append(("B", name, depth[0]))
        depth[0] += 1
        try:
            yield
        finally:
            depth[0] -= 1
            events.append(("E", name, depth[0]))

    collector = SpanCollector()
    eng = DecodeEngine(config, params, slots=2, autostart=False,
                       clock=clock, name="rounds-annotated",
                       tracer=Tracer(collector=collector, clock=clock,
                                     annotator=annotator))
    eng.submit([5, 11, 17], max_new=4)
    t_first = tick.t
    for _ in range(3):
        assert eng.run_once(timeout=0.01)
    rounds = [s for s in collector.spans() if s.name == "engine.round"]
    assert len(rounds) == 3
    # the tiling: depth never above 0, every begin closed by its own end
    # before the next begins
    assert {d for _kind, _n, d in events} == {0} and depth[0] == 0
    assert [k for k, _n, _d in events] == ["B", "E"] * (len(events) // 2)
    assert all(b[1] == e[1] for b, e in zip(events[::2], events[1::2]))
    assert all(n.startswith("engine.") for _k, n, _d in events)
    opened = [n for kind, n, _d in events if kind == "B"]
    tail = ["engine.step", "engine.sync", "engine.emit"]
    assert opened == (
        ["engine.admit.host", "engine.admit.launch", "engine.admit.insert",
         "engine.admit.read", "engine.admit.host"] + tail
        + (["engine.admit.host"] + tail) * 2)
    boundaries = set()
    for r in rounds:
        t = r.start
        boundaries.add(t)
        for p in ("admit_s", "step_s", "sync_s", "emit_s"):
            assert r.attrs["wait_s"] == 0.0 and r.attrs[p] > 0
            t += r.attrs[p]
            boundaries.add(t)
        assert t == r.end
    # round 0's row admission: its four leaf changes are the only other
    # clock reads between two annotations, and they are the boundaries of
    # its four durations (host is two stretches: before the launch and
    # after the read)
    (adm,) = [s for s in collector.spans() if s.name == "engine.admission"]
    outside = sorted(t for t, d in reads if d == 0 and t > t_first)
    inner = [t for t in outside if t not in boundaries]
    assert set(outside) - set(inner) == boundaries
    assert len(inner) == 4 and adm.start < inner[0] and inner[-1] < adm.end
    assert [b - a for a, b in zip(inner, inner[1:])] == [
        adm.attrs["launch_s"], adm.attrs["insert_s"], adm.attrs["read_s"]]
    assert (inner[0] - adm.start) + (adm.end - inner[-1]) == \
        adm.attrs["host_s"]
    # an engine with nothing to step blocks on its queue under its own
    # name, before (not inside) the admission phase
    while eng.run_once(timeout=0.01):
        pass
    opened = [n for kind, n, _d in events if kind == "B"]
    assert opened[-2:] == ["engine.wait", "engine.admit.host"]
    assert "engine.wait" not in opened[:-2]
