"""Training-plane telemetry (kubeflow_tpu/obs/steps.py).

The acceptance shape this file pins down (docs/OBSERVABILITY.md,
training-plane section):

- deterministic per-step accounting on a FakeClock: wall time into the
  ``train_step_seconds`` histogram, tokens/s / examples/s / MFU gauges;
- recompile detection via jit-cache-size delta (real jax.jit shape
  change) AND the step-time-outlier fallback for opaque callables;
- the flight recorder: bounded-ring eviction, dump-on-failure,
  dump-on-slow-step with cooldown, Chrome-trace/ndjson round-trips;
- straggler policy: K-behind-median flagging;
- the full loop on the fake API server: wrapped train steps → per-host
  beacons → operator status with a flagged straggler → dashboard
  ``GET /api/jobs/<ns>/<name>/telemetry``;
- identity-derived training traces: operator root span + per-N-step
  worker child spans share one computable trace id;
- the tuning plane reading its objective series from telemetry;
- `Histogram.time()` + STEP_TIME_BUCKETS exposition;
- `StepProfiler` clock threading.
"""

import json
import threading

import pytest

from kubeflow_tpu.k8s import FakeKubeClient
from kubeflow_tpu.obs import SpanCollector, Tracer
from kubeflow_tpu.obs.export import parse_otlp_lines
from kubeflow_tpu.obs.steps import (
    FlightRecorder,
    StepRecord,
    StepTelemetry,
    flag_stragglers,
    kube_beacon_sink,
    publish_beacon,
    read_beacons,
    step_span_id,
    telemetry_view,
    tpujob_trace_ids,
)
from kubeflow_tpu.utils.metrics import Registry, STEP_TIME_BUCKETS


class FakeClock:
    """Thread-safe tick clock: every read advances ``step`` — monotone
    and deterministic regardless of scheduling."""

    def __init__(self, start: float = 1000.0, step: float = 1.0):
        self.t = start
        self.step = step
        self._lock = threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            self.t += self.step
            return self.t


def make_telemetry(**kw):
    kw.setdefault("job", "train")
    kw.setdefault("namespace", "default")
    kw.setdefault("clock", FakeClock())
    kw.setdefault("registry", Registry())
    kw.setdefault("use_cost_analysis", False)
    return StepTelemetry(**kw)


# -- per-step accounting on a fake clock -------------------------------------


def test_step_accounting_deterministic():
    reg = Registry()
    telem = make_telemetry(registry=reg, tokens_per_step=512,
                           examples_per_step=8, flops_per_step=1e9,
                           peak_flops_per_chip=1e12, n_chips=1)
    step = telem.wrap(lambda s: (s, {"loss": 1.0}))
    for i in range(5):
        step(i)
    # every step took exactly 1 fake second (start tick + end tick)
    assert telem.step == 5
    h = reg.histogram("train_step_seconds")
    assert h.get(job="train") == 5
    assert h.sum(job="train") == pytest.approx(5.0)
    assert reg.gauge("train_last_step").get(job="train") == 5
    assert reg.gauge("train_steps_per_sec").get(job="train") == \
        pytest.approx(1.0)
    assert reg.gauge("train_tokens_per_sec").get(job="train") == \
        pytest.approx(512.0)
    assert reg.gauge("train_examples_per_sec").get(job="train") == \
        pytest.approx(8.0)
    # MFU: 1 GFLOP / 1 s on a 1 TFLOP/s chip
    assert reg.gauge("train_mfu").get(job="train") == pytest.approx(0.001)
    b = telem.beacon()
    assert b["step"] == 5 and b["mfu"] == pytest.approx(0.001)
    s = telem.summary()
    assert s["p50_step_s"] == pytest.approx(1.0)
    assert s["p99_step_s"] == pytest.approx(1.0)
    assert s["recompiles"] == 0
    text = reg.expose()
    assert "# TYPE train_step_seconds histogram" in text
    assert 'train_step_seconds_count{job="train"} 5' in text


def test_wrap_passes_through_and_extracts_sync_metrics():
    telem = make_telemetry(sync=True)
    step = telem.wrap(lambda s, k=None: (s + 1, {"loss": 2.5, "bad": "x"}))
    out = step(41)
    assert out[0] == 42  # the wrapped callable's result is untouched
    rec = telem.recorder.records()[-1]
    assert rec.metrics["loss"] == 2.5
    assert "bad" not in rec.metrics  # non-floatables dropped
    assert telem.objective_series("loss") == [(1, 2.5)]


# -- sync=False: completion is stamped one step late ---------------------------


class _FakeDevice:
    """One queue of programs on a manual clock. A step costs the host
    ``enqueue_s`` and the device ``device_s``; its outputs are ready once
    the device has finished the step before it and then this one. Like a
    trainer's step it returns ``(state, metrics)`` and is given the state
    to reuse: the state's arrays of the step before are deleted."""

    def __init__(self, clock, enqueue_s=0.03, device_s=0.2):
        self.clock, self.enqueue_s, self.device_s = clock, enqueue_s, device_s
        self.done_at = []            # per step enqueued, when it completes
        self.depth_at_enqueue = []   # steps still running as one more joins
        self.state = None

    def step(self, state=0):
        now = self.clock.t
        self.depth_at_enqueue.append(sum(t > now for t in self.done_at))
        self.clock.t = now + self.enqueue_s
        free = max([self.clock.t] + self.done_at[-1:])
        self.done_at.append(free + self.device_s)
        if self.state is not None:
            self.state.deleted = True            # donated to this step
        self.state = _FakeArray(self.clock, self.done_at[-1])
        return ({"step": self.state},
                {"loss": _FakeArray(self.clock, self.done_at[-1])})


class _FakeArray:
    size = 1

    def __init__(self, clock, done_at, deleted=False):
        self.clock, self.done_at, self.deleted = clock, done_at, deleted

    def block_until_ready(self):
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        self.clock.t = max(self.clock.t, self.done_at)
        return self


def test_async_steps_are_stamped_at_their_completion():
    """The defect this pins: under ``sync=False`` a record used to end at
    the enqueue (0.03 s here) for a step that takes the device 0.2 s."""
    clock = FakeClock(start=0.0, step=0.0)
    reg = Registry()
    dev = _FakeDevice(clock)
    telem = make_telemetry(clock=clock, registry=reg, tokens_per_step=100)
    step = telem.wrap(dev.step)
    for i in range(5):
        step(i)
        assert telem.step == i       # the step just enqueued is still open
    telem.close()
    recs = telem.recorder.records()
    assert [r.step for r in recs] == [1, 2, 3, 4, 5]
    assert [r.end for r in recs] == pytest.approx(dev.done_at)
    # the first from its own start (it had to be enqueued first), the
    # rest from completion to completion
    assert recs[0].start == 0.0
    assert recs[0].duration == pytest.approx(0.23)
    for prev, rec in zip(recs, recs[1:]):
        assert rec.start == prev.end
        assert rec.duration == pytest.approx(0.2)
    assert reg.histogram("train_step_seconds").sum(job="train") == \
        pytest.approx(1.03)
    assert reg.gauge("train_tokens_per_sec").get(job="train") == \
        pytest.approx(500 / 1.03)
    # the device queue is one deep: a step joins at most one still running
    assert max(dev.depth_at_enqueue) == 1
    telem.close()                     # nothing left to flush
    assert telem.step == 5


def test_summary_flushes_and_a_drained_device_restarts_the_interval():
    clock = FakeClock(start=0.0, step=0.0)
    dev = _FakeDevice(clock)
    telem = make_telemetry(clock=clock)
    step = telem.wrap(dev.step)
    for i in range(3):
        step(i)
    assert telem.summary()["steps"] == 3          # waited for the third
    assert telem.summary()["p50_step_s"] == pytest.approx(0.2)
    clock.t += 5.0                                # the loop logs, saves …
    t_resume = clock.t
    step(3)
    step(4)
    telem.close()
    recs = telem.recorder.records()
    # the idle 5 s are no part of step 4: it began when it was enqueued
    assert recs[3].start == t_resume
    assert recs[3].duration == pytest.approx(0.23)
    assert recs[4].duration == pytest.approx(0.2)


def test_failure_closes_the_enqueued_step_first():
    clock = FakeClock(start=0.0, step=0.0)
    dev = _FakeDevice(clock)
    calls = {"n": 0}

    def run(state):
        calls["n"] += 1
        if calls["n"] == 3:
            raise FloatingPointError("nan")
        return dev.step(state)

    telem = make_telemetry(clock=clock)
    step = telem.wrap(run)
    step(0)
    step(1)
    with pytest.raises(FloatingPointError):
        step(2)
    recs = telem.recorder.records()
    assert [(r.step, r.status) for r in recs] == [
        (1, "OK"), (2, "OK"), (3, "ERROR: FloatingPointError")]
    assert recs[1].end == pytest.approx(dev.done_at[1])
    assert telem.last_dump[0] == "failure"


def test_sync_true_still_blocks_inside_the_step():
    clock = FakeClock(start=0.0, step=0.0)
    dev = _FakeDevice(clock)
    telem = make_telemetry(clock=clock, sync=True)
    step = telem.wrap(dev.step)
    for i in range(3):
        step(i)
        assert telem.step == i + 1
    # enqueue, then the whole device time, nothing overlapped
    assert [r.duration for r in telem.recorder.records()] == \
        pytest.approx([0.23] * 3)


def test_donating_jit_step_is_waited_on_through_its_metrics():
    """A real trainer's shape: the state (its scalar ``n`` first among
    the smallest outputs) is donated to the next call, so the wait has to
    fall on the metrics beside it."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.obs.steps import _completion_handles

    @functools.partial(jax.jit, donate_argnums=0)
    def train(state):
        return ({"n": state["n"] + 1, "w": state["w"] * 2.0},
                {"loss": state["w"].sum()})

    telem = make_telemetry()
    step = telem.wrap(train)
    state = {"n": jnp.zeros((), jnp.int32), "w": jnp.ones((8, 8))}
    outs = []
    for _ in range(3):
        state, metrics = step(state)
        outs.append((state, metrics))
    handles = _completion_handles(outs[0])
    assert [h.is_deleted() for h in handles] == [False, True]   # loss, n
    telem.close()
    assert telem.step == 3 and int(state["n"]) == 3
    assert [r.status for r in telem.recorder.records()] == ["OK"] * 3


def test_output_given_away_to_the_next_step_never_fails_a_step():
    """Where the only output was donated to the next call there is
    nothing left to wait on: the record closes without the wait."""
    clock = FakeClock(start=0.0, step=0.0)
    outs = []

    def run():
        clock.t += 0.03
        for arr in outs:
            arr.deleted = True
        outs.append(_FakeArray(clock, clock.t + 0.2))
        return outs[-1]

    telem = make_telemetry(clock=clock)
    step = telem.wrap(run)
    for _ in range(3):
        step()
    telem.close()
    assert telem.step == 3
    assert [r.status for r in telem.recorder.records()] == ["OK"] * 3


# -- recompile detection -----------------------------------------------------


def test_recompile_via_jit_cache_delta():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    telem = make_telemetry()
    step = telem.wrap(f)
    step(jnp.ones((4,)))          # initial compile: counted
    assert telem.recompiles == 1
    step(jnp.ones((4,)))          # cache hit
    assert telem.recompiles == 1
    step(jnp.ones((8,)))          # new shape: recompile
    assert telem.recompiles == 2
    telem.close()                 # device arrays: the last closes late
    recs = telem.recorder.records()
    assert [r.recompile for r in recs] == [True, False, True]


def test_recompile_fallback_step_time_outlier():
    """Opaque callables (no jit cache surface) fall back to flagging
    step-time outliers against the rolling median."""
    clock = FakeClock(step=0.0)  # manual time control

    def tick(dt):
        clock.t += dt

    telem = make_telemetry(clock=clock, slow_step_factor=3.0,
                           min_slow_history=5, dump_cooldown_steps=1000)

    durations = [1.0] * 6 + [10.0]  # the 7th step stalls 10x

    i = {"n": 0}

    def fn():
        tick(durations[i["n"]])
        i["n"] += 1

    step = telem.wrap(fn)
    for _ in durations:
        step()
    recs = telem.recorder.records()
    assert [r.recompile for r in recs[:-1]] == [False] * 6
    assert recs[-1].recompile  # the outlier flagged as likely recompile
    assert telem.recompiles == 1


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_ring_eviction():
    ring = FlightRecorder(capacity=8)
    for i in range(1, 21):
        ring.record(StepRecord(step=i, start=float(i), end=float(i) + 0.5))
    assert len(ring) == 8
    assert ring.recorded_total == 20
    assert [r.step for r in ring.records()] == list(range(13, 21))
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_dump_on_failure_round_trips_chrome_trace(tmp_path):
    telem = make_telemetry(dump_dir=str(tmp_path), worker=3)

    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("device wedged")

    step = telem.wrap(fn)
    for _ in range(3):
        step()
    with pytest.raises(RuntimeError):
        step()
    # the failure dumped the ring — and re-raised
    assert telem.dumps == 1
    reason, chrome = telem.last_dump
    assert reason == "failure"
    events = chrome["traceEvents"]
    assert [e["args"]["step"] for e in events] == [1, 2, 3, 4]
    assert events[-1]["args"]["status"].startswith("ERROR: RuntimeError")
    assert all(e["args"]["worker"] == 3 for e in events)
    # on-disk artifacts: Chrome trace + ndjson, both loadable
    trace_files = sorted(tmp_path.glob("flight-w3-failure-*.trace.json"))
    nd_files = sorted(tmp_path.glob("flight-w3-failure-*.ndjson"))
    assert len(trace_files) == 1 and len(nd_files) == 1
    disk = json.loads(trace_files[0].read_text())
    assert disk["traceEvents"] == events
    spans = parse_otlp_lines(nd_files[0].read_text())
    assert [s.name for s in spans] == [f"train.step/{i}"
                                       for i in (1, 2, 3, 4)]
    # all step spans share the identity-derived trace
    tid, _ = tpujob_trace_ids("default", "train", "")
    assert {s.trace_id for s in spans} == {tid}


def test_dump_on_slow_step_with_cooldown():
    clock = FakeClock(step=0.0)
    telem = make_telemetry(clock=clock, slow_step_factor=3.0,
                           min_slow_history=5, dump_cooldown_steps=10)
    durations = [1.0] * 6 + [20.0] + [1.0] * 3 + [20.0] + [1.0] * 10 + [20.0]
    i = {"n": 0}

    def fn():
        clock.t += durations[i["n"]]
        i["n"] += 1

    step = telem.wrap(fn)
    for _ in durations:
        step()
    # first slow step dumped; the second fell inside the cooldown
    # window; the third (>=10 steps later) dumped again
    assert telem.dumps == 2
    assert telem.last_dump[0] == "slow_step"


# -- straggler policy --------------------------------------------------------


def test_flag_stragglers_k_behind_median():
    steps = {"w0": 100, "w1": 101, "w2": 99, "w3": 88}
    median, lags, stragglers = flag_stragglers(steps, k=10)
    assert median == pytest.approx(99.5)
    assert lags["w3"] == 11 and lags["w1"] == 0
    assert stragglers == ["w3"]
    # k is a floor: lag == k flags, lag < k does not
    _, _, s9 = flag_stragglers({"a": 100, "b": 100, "c": 91}, k=9)
    assert s9 == ["c"]
    _, _, s10 = flag_stragglers({"a": 100, "b": 100, "c": 91}, k=10)
    assert s10 == []
    # one runaway-AHEAD worker must not flag the healthy rest
    _, _, s = flag_stragglers({"a": 100, "b": 101, "c": 5000}, k=10)
    assert s == []
    assert flag_stragglers({}, k=10) == (0.0, {}, [])


def test_telemetry_view_aggregates_beacons():
    beacons = {
        0: {"step": 100, "stepsPerSec": 2.0, "mfu": 0.4, "recompiles": 1,
            "tokensPerSec": 1000.0},
        1: {"step": 100, "stepsPerSec": 2.1, "mfu": 0.41, "recompiles": 0,
            "tokensPerSec": 1050.0},
        2: {"step": 80, "stepsPerSec": 1.0, "mfu": None, "recompiles": 5,
            "tokensPerSec": 500.0},
    }
    view = telemetry_view(beacons, straggler_k=10)
    assert view["lastStep"] == 100
    assert view["stepsPerSec"] == pytest.approx(2.0)  # median worker rate
    assert view["recompiles"] == 6
    assert view["stragglers"] == ["2"]
    assert view["workers"]["2"]["lag"] == 20
    assert view["mfu"] == pytest.approx(0.405)
    assert view["tokensPerSec"] == pytest.approx(2550.0)
    empty = telemetry_view({}, straggler_k=10)
    assert empty["stragglers"] == [] and empty["lastStep"] == 0


# -- beacons over the fake API server ----------------------------------------


def test_beacon_publish_read_round_trip():
    client = FakeKubeClient()
    publish_beacon(client, "default", "train", 0, {"step": 10})
    publish_beacon(client, "default", "train", 1, {"step": 12})
    publish_beacon(client, "default", "train", 0, {"step": 11})  # update
    publish_beacon(client, "default", "other", 0, {"step": 99})
    beacons = read_beacons(client, "default", "train")
    assert beacons == {0: {"step": 11}, 1: {"step": 12}}
    # world-size filter: an elastic downsize must exclude departed
    # workers' frozen beacons
    assert read_beacons(client, "default", "train",
                        max_workers=1) == {0: {"step": 11}}
    # a garbled beacon must not hide the others
    cm = client.get("v1", "ConfigMap", "default", "train-telemetry-w1")
    cm = dict(cm)
    cm["data"] = {"worker": "not-an-int", "beacon": "{}"}
    client.update(cm)
    assert read_beacons(client, "default", "train") == {0: {"step": 11}}


def test_beacons_gc_with_job_and_after_downsize():
    """Beacons with a job_uid carry an ownerReference (GC'd with the
    CR); the operator deletes and excludes beacons beyond the current
    world size, so a downsized gang is never self-flagged."""
    from kubeflow_tpu.operators.tpujob import TpuJobOperator, tpujob

    client = FakeKubeClient()
    operator = TpuJobOperator(client)
    job = client.create(tpujob("train", "default", {
        "image": "x", "slices": 2, "hostsPerSlice": 1,
        "stragglerSteps": 5}))
    uid = job["metadata"]["uid"]
    operator.reconcile("default", "train")
    for pod in client.list("v1", "Pod", "default"):
        pod.setdefault("status", {})["phase"] = "Running"
        client.update_status(pod)
    for w, step in ((0, 5000), (1, 5000), (2, 5000), (3, 5000)):
        # workers 2/3 are leftovers from a previous 4-wide shape
        publish_beacon(client, "default", "train", w,
                       {"step": step if w < 2 else 5000, "stepsPerSec": 1},
                       job_uid=uid)
    # live workers restarted their counters near zero after the re-gang
    publish_beacon(client, "default", "train", 0,
                   {"step": 10, "stepsPerSec": 1}, job_uid=uid)
    publish_beacon(client, "default", "train", 1,
                   {"step": 12, "stepsPerSec": 1}, job_uid=uid)
    operator.reconcile("default", "train")
    got = client.get("kubeflow-tpu.org/v1alpha1", "TpuJob",
                     "default", "train")
    telem = got["status"]["telemetry"]
    assert set(telem["workers"]) == {"0", "1"}  # ghosts excluded
    assert telem["stragglers"] == []            # live gang not self-flagged
    assert telem["lastStep"] == 12
    # the out-of-range ConfigMaps were GC'd by the operator
    names = {cm["metadata"]["name"]
             for cm in client.list("v1", "ConfigMap", "default")}
    assert "train-telemetry-w2" not in names
    assert "train-telemetry-w3" not in names
    # deleting the CR cascades to the remaining beacons (ownerReference)
    client.delete("kubeflow-tpu.org/v1alpha1", "TpuJob", "default",
                  "train")
    assert client.list("v1", "ConfigMap", "default") == []


# -- the full loop: train step -> beacons -> operator -> dashboard -----------


def _run_fake_workers(client, job_name, ns, n_workers, steps_by_worker,
                      uid=""):
    """One StepTelemetry per fake host, publishing beacons like a real
    gang; worker i runs steps_by_worker[i] wrapped train steps."""
    collector = SpanCollector()
    for w in range(n_workers):
        clock = FakeClock(start=1000.0 * (w + 1))
        telem = StepTelemetry(
            job=job_name, namespace=ns, uid=uid, worker=w, clock=clock,
            registry=Registry(), use_cost_analysis=False,
            tokens_per_step=256, flops_per_step=1e9,
            peak_flops_per_chip=1e12, span_every=5,
            tracer=Tracer(collector=collector, clock=clock),
            beacon_sink=kube_beacon_sink(client, ns, job_name, w))
        step = telem.wrap(lambda s: (s, {"loss": 1.0}))
        for i in range(steps_by_worker[w]):
            step(i)
    return collector


def test_full_loop_beacons_operator_status_dashboard():
    """The ISSUE acceptance fixture: a fake multi-worker TpuJob where one
    worker lags — wrapped steps emit beacons, the operator aggregates
    them into CR status and flags the straggler, and the dashboard
    serves it all at GET /api/jobs/<ns>/<name>/telemetry."""
    from kubeflow_tpu.dashboard.server import DashboardApi
    from kubeflow_tpu.operators.tpujob import (
        PHASE_RUNNING,
        PHASE_SUCCEEDED,
        TpuJobOperator,
        tpujob,
    )
    from kubeflow_tpu.tenancy.authz import allow_all

    client = FakeKubeClient()
    clock = FakeClock(start=1_700_000_000.0)
    collector = SpanCollector()
    operator = TpuJobOperator(client, clock=clock,
                              tracer=Tracer(collector=collector,
                                            clock=clock))
    job = client.create(tpujob("train", "default", {
        "image": "kubeflow-tpu/examples:latest",
        "slices": 3, "hostsPerSlice": 1, "stragglerSteps": 5}))
    uid = job["metadata"]["uid"]
    operator.reconcile("default", "train")
    pods = client.list("v1", "Pod", "default")
    assert len(pods) == 3
    # the operator hands every worker the CR identity for trace derivation
    env = {e["name"]: e["value"]
           for e in pods[0]["spec"]["containers"][0]["env"]}
    assert env["KFTPU_JOB_UID"] == uid
    for pod in pods:
        pod.setdefault("status", {})["phase"] = "Running"
        client.update_status(pod)

    # workers 0/1 reach step 30; worker 2 straggles at step 20 (>=5 behind)
    worker_spans = _run_fake_workers(client, "train", "default",
                                     3, [30, 30, 20], uid=uid)
    operator.reconcile("default", "train")
    job = client.get("kubeflow-tpu.org/v1alpha1", "TpuJob",
                     "default", "train")
    assert job["status"]["phase"] == PHASE_RUNNING
    telem = job["status"]["telemetry"]
    assert telem["lastStep"] == 30
    assert telem["stragglers"] == ["2"]
    assert telem["workers"]["2"]["lag"] == 10
    assert telem["stepsPerSec"] == pytest.approx(1.0)
    assert telem["mfu"] == pytest.approx(0.001)
    conds = [(c["type"], c["reason"]) for c in job["status"]["conditions"]]
    assert ("Straggling", "WorkerBehindMedian") in conds

    # dashboard: the telemetry surface over the same beacons
    api = DashboardApi(client, authorize=allow_all)
    code, out = api.handle("GET", "/api/jobs/default/train/telemetry",
                           None)
    assert code == 200
    assert out["phase"] == PHASE_RUNNING
    assert out["lastStep"] == 30
    assert out["stepsPerSec"] == pytest.approx(1.0)
    assert out["mfu"] == pytest.approx(0.001)
    assert out["recompiles"] == 0
    assert out["stragglers"] == ["2"]
    assert out["stragglerThreshold"] == 5
    tid, root_id = tpujob_trace_ids("default", "train", uid)
    assert out["traceId"] == tid
    code, _ = api.handle("GET", "/api/jobs/default/nope/telemetry", None)
    assert code == 404
    code, _ = api.handle("GET", "/api/jobs/default/train", None)
    assert code == 404  # only the telemetry leaf exists

    # workers' per-N-step spans landed in the identity-derived trace
    spans = worker_spans.trace(tid)
    assert spans and {s.trace_id for s in spans} == {tid}
    assert all(s.parent_id == root_id for s in spans)
    assert step_span_id(tid, 0, 5) in {s.span_id for s in spans}

    # terminal: the operator closes the root span in the SAME trace
    for pod in client.list("v1", "Pod", "default"):
        pod.setdefault("status", {})["phase"] = "Succeeded"
        client.update_status(pod)
    operator.reconcile("default", "train")
    job = client.get("kubeflow-tpu.org/v1alpha1", "TpuJob",
                     "default", "train")
    assert job["status"]["phase"] == PHASE_SUCCEEDED
    roots = [s for s in collector.trace(tid) if s.span_id == root_id]
    assert len(roots) == 1
    assert roots[0].name == "tpujob/train"
    assert roots[0].attrs["phase"] == PHASE_SUCCEEDED
    assert roots[0].attrs["lastStep"] == 30


def test_operator_records_root_span_on_failure():
    from kubeflow_tpu.operators.tpujob import (
        PHASE_FAILED,
        TpuJobOperator,
        tpujob,
    )

    client = FakeKubeClient()
    clock = FakeClock(start=1_700_000_000.0)
    collector = SpanCollector()
    operator = TpuJobOperator(client, clock=clock,
                              tracer=Tracer(collector=collector,
                                            clock=clock))
    job = client.create(tpujob("bad", "default", {
        "image": "x", "restartPolicy": "Never"}))
    operator.reconcile("default", "bad")
    for pod in client.list("v1", "Pod", "default"):
        pod.setdefault("status", {})["phase"] = "Failed"
        client.update_status(pod)
    operator.reconcile("default", "bad")
    got = client.get("kubeflow-tpu.org/v1alpha1", "TpuJob",
                     "default", "bad")
    assert got["status"]["phase"] == PHASE_FAILED
    tid, root_id = tpujob_trace_ids("default", "bad",
                                    job["metadata"]["uid"])
    (sp,) = collector.trace(tid)
    assert sp.span_id == root_id
    assert sp.status == f"ERROR: {PHASE_FAILED}"


def test_straggler_steps_spec_validation():
    from kubeflow_tpu.operators.tpujob import TpuJobSpec

    assert TpuJobSpec.from_dict({"image": "x"}).straggler_steps == 10
    assert TpuJobSpec.from_dict(
        {"image": "x", "stragglerSteps": 3}).straggler_steps == 3
    with pytest.raises(ValueError, match="stragglerSteps"):
        TpuJobSpec.from_dict({"image": "x", "stragglerSteps": 0})


def test_job_label_contract_matches_operator():
    """obs.steps carries its own copy of the job-name label (the operator
    imports obs.steps, not vice versa) — the two must never drift."""
    from kubeflow_tpu.obs.steps import JOB_NAME_LABEL
    from kubeflow_tpu.operators.tpujob import JOB_LABEL

    assert JOB_NAME_LABEL == JOB_LABEL


# -- MFU from XLA compiled cost analysis -------------------------------------


def test_mfu_from_cost_analysis_real_jit():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32))
    telem = make_telemetry(use_cost_analysis=True,
                           peak_flops_per_chip=1e12)
    step = telem.wrap(f)
    step(x)
    telem.close()                 # a device-array step closes a call late
    # the probe read real FLOPs off the compiled executable
    assert telem.flops_per_step and telem.flops_per_step > 0
    assert telem.mfu() is not None and telem.mfu() > 0


def test_cost_analysis_degrades_on_opaque_callable():
    telem = make_telemetry(use_cost_analysis=True)
    step = telem.wrap(lambda: None)
    step()
    assert telem.flops_per_step is None
    assert telem.mfu() is None  # MFU absent, never wrong


# -- tuning reads its objective series from telemetry ------------------------


def test_tuning_history_from_telemetry():
    from kubeflow_tpu.tuning.study import (
        append_history_from_telemetry,
        read_trial_history,
    )

    client = FakeKubeClient()
    telem = make_telemetry(sync=True)
    step = telem.wrap(lambda s, loss: (s, {"loss": loss}))
    for i, loss in enumerate([3.0, 2.0, 1.5]):
        step(i, loss)
    n = append_history_from_telemetry(client, "default", "study-t0",
                                      telem, "loss")
    assert n == 3
    assert read_trial_history(client, "default", "study-t0") == \
        [(1, 3.0), (2, 2.0), (3, 1.5)]
    # idempotent: re-reporting the same series appends nothing
    assert append_history_from_telemetry(client, "default", "study-t0",
                                         telem, "loss") == 0
    step(3, 1.2)
    assert append_history_from_telemetry(client, "default", "study-t0",
                                         telem, "loss") == 1
    # derived throughput series work as objectives too
    n = append_history_from_telemetry(client, "default", "study-t1",
                                      telem, "steps_per_sec")
    assert n == 4
    hist = read_trial_history(client, "default", "study-t1")
    assert all(v == pytest.approx(1.0) for _, v in hist)


def test_report_tuning_metrics_uses_telemetry(monkeypatch):
    from kubeflow_tpu.examples.common import report_tuning_metrics
    from kubeflow_tpu.tuning.study import (
        read_trial_history,
        read_trial_metrics,
    )

    monkeypatch.setenv("KFTPU_TRIAL_NAME", "s-t0")
    monkeypatch.setenv("KFTPU_NAMESPACE", "default")
    monkeypatch.setenv("KFTPU_OBJECTIVE_METRIC", "loss")
    client = FakeKubeClient()
    telem = make_telemetry(sync=True)
    step = telem.wrap(lambda loss: ({}, {"loss": loss}))
    for loss in (2.0, 1.0):
        step(loss)
    report_tuning_metrics(2, {"loss": 1.0}, client=client, telemetry=telem)
    assert read_trial_history(client, "default", "s-t0") == \
        [(1, 2.0), (2, 1.0)]
    report_tuning_metrics(2, {"loss": 1.0}, final=True, client=client,
                          telemetry=telem)
    # the final pass must not duplicate already-persisted history points
    assert read_trial_history(client, "default", "s-t0") == \
        [(1, 2.0), (2, 1.0)]
    harvest = read_trial_metrics(client, "default", "s-t0")
    assert harvest["loss"] == 1.0
    assert "p50_step_s" in harvest and "recompiles" in harvest

    # an objective the telemetry CANNOT resolve (not a recorded step
    # metric, not a derived series) must fall back to the explicit
    # value — telemetry presence never silently drops the history
    monkeypatch.setenv("KFTPU_TRIAL_NAME", "s-t1")
    monkeypatch.setenv("KFTPU_OBJECTIVE_METRIC", "accuracy")
    report_tuning_metrics(1, {"accuracy": 0.9}, client=client,
                          telemetry=telem)
    assert read_trial_history(client, "default", "s-t1") == [(1, 0.9)]


# -- Histogram.time() + step-time buckets ------------------------------------


def test_histogram_time_context_manager_fake_clock():
    from kubeflow_tpu.utils.metrics import Histogram

    clock = FakeClock(start=0.0, step=1.0)
    h = Histogram("step_s", "steps", buckets=STEP_TIME_BUCKETS)
    with h.time(clock=clock, job="j") as t:
        pass
    assert t.elapsed == pytest.approx(1.0)
    assert h.get(job="j") == 1
    assert h.sum(job="j") == pytest.approx(1.0)
    # observed even when the block raises
    with pytest.raises(RuntimeError):
        with h.time(clock=clock, job="j"):
            raise RuntimeError("boom")
    assert h.get(job="j") == 2
    text = h.expose()
    # step-time bounds resolve the recompile tail the request-latency
    # defaults fold into +Inf
    assert 'step_s_bucket{job="j",le="60"}' in text
    assert 'step_s_bucket{job="j",le="300"}' in text
    assert 'step_s_bucket{job="j",le="1"} 2' in text
    assert 'step_s_count{job="j"} 2' in text


# -- StepProfiler clock threading --------------------------------------------


def test_step_profiler_injectable_clock(tmp_path, monkeypatch):
    import kubeflow_tpu.utils.profiler as prof_mod

    class _NoopProfiler:
        def start_trace(self, logdir):
            pass

        def stop_trace(self):
            pass

    import jax

    monkeypatch.setattr(jax, "profiler", _NoopProfiler())
    clock = FakeClock(start=0.0, step=1.0)
    prof = prof_mod.StepProfiler(str(tmp_path), start=2, n_steps=3,
                                 clock=clock)
    for step in range(10):
        prof.step(step)
    # window [2, 5): start tick at step 2, stop tick at step 5
    assert prof.last_capture_s == pytest.approx(1.0)
    prof2 = prof_mod.StepProfiler.from_env(
        environ={"KFTPU_PROFILE_DIR": str(tmp_path)}, clock=clock)
    assert prof2.clock is clock
