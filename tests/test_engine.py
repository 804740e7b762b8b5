"""Continuous-batching decode engine tests.

The oracle is the plain bucketed ``generate`` path: a request decoded
through the shared engine batch must produce exactly the tokens it
would produce alone (greedy — sampling is seed-reproducible instead).
Plus the engine's whole reason to exist: two concurrent callers must
share decode steps, not run back-to-back.

Reference surface being beaten: TF-Serving's whole-request batch
scheduler (``/root/reference/kubeflow/tf-serving/tf-serving-template.libsonnet:33-48``),
which cannot interleave autoregressive requests at the step level.
"""

import dataclasses
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import Transformer, TransformerConfig
from kubeflow_tpu.models.decode import generate
from kubeflow_tpu.serving.engine import DecodeEngine

from conftest import KV_GEOMETRIES, full_forward_greedy


@pytest.fixture(scope="module")
def lm():
    config = TransformerConfig(vocab_size=97, d_model=32, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=64,
                               max_seq_len=48, dtype=jnp.float32,
                               remat=False)
    params = Transformer(config).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    return config, params


def _oracle(config, params, prompt, n, **kw):
    out = generate(config, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out)[0].tolist()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the ragged twin
def test_single_request_matches_unary_greedy(lm):
    config, params = lm
    eng = DecodeEngine(config, params, slots=4, autostart=False)
    prompt = [5, 11, 17]
    req = eng.submit(prompt, max_new=6)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    assert req.result() == _oracle(config, params, prompt, 6)


def test_two_ragged_requests_share_steps_and_match_oracles(lm):
    """Different prompt lengths + different max_new in one batch, each
    matching its solo greedy decode — the per-row cache position
    contract under the engine."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=4, autostart=False)
    r1 = eng.submit([5, 11, 17], max_new=8)
    r2 = eng.submit([3, 2, 9, 23, 41], max_new=4)
    for _ in range(12):
        eng.run_once(timeout=0.01)
    assert r1.result() == _oracle(config, params, [5, 11, 17], 8)
    assert r2.result() == _oracle(config, params, [3, 2, 9, 23, 41], 4)
    # sharing: 1 (r1 prefill-sample) + 7 more for r1; r2's 3 post-prefill
    # tokens ride steps r1 was taking anyway
    assert eng.steps_total <= 8
    assert eng.tokens_total == 12


def test_admission_into_running_batch(lm):
    """A request submitted mid-flight joins the live batch and still
    matches its solo decode."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=4, autostart=False)
    r1 = eng.submit([5, 11, 17], max_new=10)
    for _ in range(3):
        eng.run_once(timeout=0.01)
    r2 = eng.submit([7, 2], max_new=3)
    for _ in range(12):
        eng.run_once(timeout=0.01)
    assert r1.result() == _oracle(config, params, [5, 11, 17], 10)
    assert r2.result() == _oracle(config, params, [7, 2], 3)


def _eos_pick(toks):
    """First (index, token) whose token has no earlier occurrence — a
    valid "EOS observed mid-sequence" probe even when the tiny model's
    greedy decode repeats tokens (an earlier duplicate would stop the
    row before the probed position)."""
    for i in range(1, len(toks)):
        if toks[i] not in toks[:i]:
            return i, toks[i]
    pytest.skip("degenerate greedy sequence: every token repeats")


def test_eos_frees_slot_early(lm):
    config, params = lm
    # discover a greedy token to use as "EOS" for the test
    toks = _oracle(config, params, [5, 11, 17], 8)
    stop, eos = _eos_pick(toks)
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    req = eng.submit([5, 11, 17], max_new=8, eos_id=eos)
    for _ in range(10):
        eng.run_once(timeout=0.01)
    got = req.result()
    assert got == toks[:stop + 1]   # stopped AT the eos token
    assert eng.active_count == 0    # slot freed


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_more_requests_than_slots_queue(lm):
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    reqs = [eng.submit([3 + i, 7], max_new=4) for i in range(5)]
    for _ in range(30):
        eng.run_once(timeout=0.01)
    for i, r in enumerate(reqs):
        assert r.result() == _oracle(config, params, [3 + i, 7], 4), i


@pytest.mark.slow  # two engine builds; tier-1 runs the lighter seed-repro twins
def test_sampling_reproducible_regardless_of_cotenants(lm):
    """Same seed -> same tokens whether the request runs alone or
    shares the batch: the fold_in(key(seed), step) contract."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=4, autostart=False)
    solo = eng.submit([5, 11, 17], max_new=6, temperature=0.8, seed=42)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    eng2 = DecodeEngine(config, params, slots=4, autostart=False)
    crowd = [eng2.submit([9 + i], max_new=6, temperature=1.3, seed=i)
             for i in range(3)]
    shared = eng2.submit([5, 11, 17], max_new=6, temperature=0.8, seed=42)
    for _ in range(10):
        eng2.run_once(timeout=0.01)
    assert solo.result() == shared.result()
    for c in crowd:
        assert len(c.result()) == 6


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_multi_step_sync_matches_single_step(lm):
    """steps_per_sync>1 (K on-device steps per host round-trip) must be
    token-identical to K=1, including EOS cutoff mid-chunk."""
    config, params = lm
    want = _oracle(config, params, [5, 11, 17], 9)
    eng = DecodeEngine(config, params, slots=2, steps_per_sync=4,
                       autostart=False)
    r1 = eng.submit([5, 11, 17], max_new=9)
    r2 = eng.submit([7, 2], max_new=5, temperature=0.9, seed=3)
    for _ in range(6):
        eng.run_once(timeout=0.01)
    assert r1.result() == want
    assert len(r2.result()) == 5
    # sampled co-tenant must be reproducible under a different K
    eng1 = DecodeEngine(config, params, slots=2, autostart=False)
    r2b = eng1.submit([7, 2], max_new=5, temperature=0.9, seed=3)
    for _ in range(8):
        eng1.run_once(timeout=0.01)
    assert r2.result() == r2b.result()
    # EOS inside a chunk stops the row at the right token
    stop, eos = _eos_pick(want)
    eng2 = DecodeEngine(config, params, slots=2, steps_per_sync=4,
                        autostart=False)
    r3 = eng2.submit([5, 11, 17], max_new=9, eos_id=eos)
    for _ in range(6):
        eng2.run_once(timeout=0.01)
    assert r3.result() == want[:stop + 1]


def test_context_overrun_rejected(lm):
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(list(range(1, 41)), max_new=20)


def test_concurrent_clients_share_one_decode_step(lm):
    """THE continuous-batching proof: two threads generating at the same
    time cost far fewer engine steps than running back-to-back."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=4)  # autostarted thread
    try:
        n = 24
        results = {}

        def client(tag, prompt):
            req = eng.submit(prompt, max_new=n)
            results[tag] = req.result()

        t1 = threading.Thread(target=client, args=("a", [5, 11, 17]))
        t2 = threading.Thread(target=client, args=("b", [3, 2, 9]))
        t1.start(); t2.start()
        t1.join(timeout=120); t2.join(timeout=120)
        assert results["a"] == _oracle(config, params, [5, 11, 17], n)
        assert results["b"] == _oracle(config, params, [3, 2, 9], n)
        # back-to-back would cost ~2n steps; sharing keeps it near n
        # (small slack for steps taken before the second admit)
        assert eng.steps_total < 2 * n - 4, eng.steps_total
    finally:
        eng.close()


_PAGED_KW = dict(paged=True, kv_page_size=8, prefill_chunk_tokens=8)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_close_fails_inflight_requests(lm, paged):
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, autostart=False,
                       **(_PAGED_KW if paged else {}))
    req = eng.submit([5, 11], max_new=8)
    eng.run_once(timeout=0.01)  # admitted, partially decoded
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        req.result()


def test_step_failure_self_closes_and_repo_rebuilds(tmp_path, lm):
    """A step failure invalidates the donated cache, so the engine must
    self-close (in-flight + pending fail with the retryable
    EngineClosed) and the repository must evict it so the next request
    gets a fresh engine instead of a permanent 500 well."""
    from kubeflow_tpu.serving import (export_model,
                                      transformer_export_config)
    from kubeflow_tpu.serving.engine import EngineClosed
    from kubeflow_tpu.serving.server import ModelRepository

    config, params = lm
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    repo = ModelRepository(str(tmp_path), poll_interval_s=3600,
                           decode_slots=2)
    model = repo._models["lm"]
    eng = repo.engine_for("lm", model)
    assert eng is not None

    def boom(*a, **k):
        raise RuntimeError("injected step failure")

    eng._step_greedy = boom
    eng._step = boom
    req = eng.submit([5, 11], max_new=4)
    pend = eng.submit([7, 2], max_new=4)  # may land active or pending
    with pytest.raises(EngineClosed):
        req.result()
    with pytest.raises(EngineClosed):
        pend.result()
    assert eng.closed
    with pytest.raises(EngineClosed):
        eng.submit([3], max_new=2)
    # the repository replaces the corpse with a working engine
    eng2 = repo.engine_for("lm", model)
    assert eng2 is not None and eng2 is not eng and not eng2.closed
    try:
        r = eng2.submit([5, 11, 17], max_new=4)
        assert r.result() == _oracle(config, params, [5, 11, 17], 4)
    finally:
        eng2.close()


def test_server_integration_engine_path(tmp_path, lm):
    """ModelServer(decode_slots>0): unary + streamed + eos through the
    engine, greedy identical to the non-engine server."""
    import http.client
    import json

    from kubeflow_tpu.serving import (ModelServer, export_model,
                                      transformer_export_config)

    config, params = lm
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600,
                      decode_slots=4)
    port = srv.start()

    def post(body):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/models/lm:generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        conn.close()
        if body.get("stream") and resp.status == 200:
            return resp.status, [json.loads(l) for l in raw.splitlines()
                                 if l]
        return resp.status, json.loads(raw)

    try:
        prompt = [[5, 11, 17], [3, 2]]
        code, out = post({"prompt_tokens": prompt, "max_new_tokens": 5})
        assert code == 200
        want = [_oracle(config, params, p, 5) for p in prompt]
        assert out["tokens"] == want
        # engine metrics moved: model.generate was never called
        eng = srv.repo.engine_for("lm", srv.repo.get("lm"))
        assert eng.tokens_total >= 10

        code, lines = post({"prompt_tokens": prompt, "max_new_tokens": 5,
                            "stream": True})
        assert code == 200 and lines[-1]["done"]
        steps = [ln["tokens"] for ln in lines[:-1]]
        assert np.asarray(steps).T.tolist() == want

        # eos_id: row stops early, dense reply right-pads with eos
        eos = want[0][1]
        code, out = post({"prompt_tokens": [prompt[0]],
                          "max_new_tokens": 5, "eos_id": eos})
        assert code == 200
        assert out["tokens"][0][:2] == want[0][:2]
        assert all(t == eos for t in out["tokens"][0][1:])
    finally:
        srv.stop()


def test_server_without_engine_rejects_eos(tmp_path, lm):
    import http.client
    import json

    from kubeflow_tpu.serving import (ModelServer, export_model,
                                      transformer_export_config)

    config, params = lm
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600)
    port = srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/models/lm:generate",
                     json.dumps({"prompt_tokens": [[1, 2]], "eos_id": 3}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        conn.close()
        assert resp.status == 400 and "decode engine" in out["error"]
    finally:
        srv.stop()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_engine_on_sharded_mesh(lm):
    """Multi-chip serving: the engine with tensor-parallel-sharded
    params on the virtual mesh must match unsharded greedy decode
    exactly (the sharded twin of test_decode_on_sharded_mesh, through
    the continuous-batching path)."""
    from jax.sharding import NamedSharding

    from conftest import shard_params
    from kubeflow_tpu.parallel import MeshConfig, create_mesh

    config, params = lm
    mesh = create_mesh(MeshConfig(dp=2, tp=4))
    sharded = shard_params(params, mesh)
    eng = DecodeEngine(config, sharded, slots=2, mesh=mesh,
                       autostart=False)
    # tp=4 divides the merged k/v axis (2 heads x 8) but no whole head:
    # the leaves replicate, as the heads they hold cannot be split
    assert all(leaf.sharding.is_fully_replicated
               for leaf in jax.tree_util.tree_leaves(eng._cache))
    r1 = eng.submit([5, 11, 17], max_new=6)
    r2 = eng.submit([3, 2, 9, 23], max_new=4)
    for _ in range(10):
        eng.run_once(timeout=0.01)
    assert r1.result() == _oracle(config, params, [5, 11, 17], 6)
    assert r2.result() == _oracle(config, params, [3, 2, 9, 23], 4)

    # tp=2 divides the 2 kv heads: the engine cache k/v leaves must be
    # CREATED sharded over tp (never one full copy per device)
    mesh2 = create_mesh(MeshConfig(dp=4, tp=2))
    sharded2 = shard_params(params, mesh2)
    eng2 = DecodeEngine(config, sharded2, slots=2, mesh=mesh2,
                        autostart=False)
    kv_specs = [leaf.sharding.spec
                for leaf in jax.tree_util.tree_leaves(eng2._cache)
                if leaf.ndim >= 3]
    assert kv_specs and all("tp" in str(s) for s in kv_specs), kv_specs
    r3 = eng2.submit([5, 11, 17], max_new=6)
    for _ in range(8):
        eng2.run_once(timeout=0.01)
    assert r3.result() == _oracle(config, params, [5, 11, 17], 6)


def test_model_server_sharded_serving(tmp_path, lm):
    """KFTPU_SERVING_MESH end to end: the server shards a loaded LM's
    params over the mesh at engine creation and :generate matches the
    unsharded oracle — multi-chip serving as a product surface."""
    import http.client
    import json

    from kubeflow_tpu.serving import (ModelServer, export_model,
                                      transformer_export_config)
    from kubeflow_tpu.serving.server import parse_serving_mesh

    config, params = lm
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    mesh = parse_serving_mesh("dp=2,tp=4")
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600,
                      decode_slots=2, decode_mesh=mesh)
    port = srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/v1/models/lm:generate",
                     json.dumps({"prompt_tokens": [[5, 11, 17]],
                                 "max_new_tokens": 5}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert out["tokens"][0] == _oracle(config, params, [5, 11, 17], 5)
        eng = srv.repo.engine_for("lm", srv.repo.get("lm"))
        assert eng.mesh is mesh
        # params were sharded, not replicated wholesale on one device
        leaf = jax.tree_util.tree_leaves(eng._params)[0]
        assert len(leaf.sharding.device_set) == 8
    finally:
        srv.stop()


def test_parse_serving_mesh_validation():
    from kubeflow_tpu.serving.server import parse_serving_mesh

    assert parse_serving_mesh("") is None and parse_serving_mesh(None) is None
    with pytest.raises(ValueError, match="axis"):
        parse_serving_mesh("tpx=4")
    with pytest.raises(ValueError, match="integer size"):
        parse_serving_mesh("tp=")
    with pytest.raises(ValueError, match="integer size"):
        parse_serving_mesh("tp=abc")
    with pytest.raises(ValueError, match="repeats"):
        parse_serving_mesh("tp=2,tp=4")


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_burst_admission_batches_prefills_and_matches_oracles(lm):
    """A burst of same-bucket requests admits through ONE batched
    prefill (batch_prefills counts it) and every request still matches
    its solo greedy decode — ragged lengths included."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=8, autostart=False)
    prompts = [[5, 11, 17], [3, 2], [9, 23, 41, 7], [13]]
    reqs = [eng.submit(p, max_new=5) for p in prompts]
    for _ in range(10):
        eng.run_once(timeout=0.01)
    for p, r in zip(prompts, reqs):
        assert r.result() == _oracle(config, params, p, 5), p
    assert eng.batch_prefills >= 1


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_burst_admission_sampled_matches_row_path(lm):
    """Sampled requests admitted through the batch prefill produce the
    SAME first token as the row path (same fold_in(seed, 0), same
    bounded sampler) — the reproducibility contract survives batching."""
    config, params = lm
    # row path: submit alone (singleton group -> RowCache._admit_row)
    eng1 = DecodeEngine(config, params, slots=4, autostart=False)
    solo = eng1.submit([5, 11, 17], max_new=6, temperature=0.8, seed=42)
    for _ in range(8):
        eng1.run_once(timeout=0.01)
    # batch path: same request inside a same-bucket burst
    eng2 = DecodeEngine(config, params, slots=4, autostart=False)
    burst = [eng2.submit([5, 11, 17], max_new=6, temperature=0.8,
                         seed=42),
             eng2.submit([9, 23, 41], max_new=6, temperature=1.2,
                         seed=7)]
    for _ in range(8):
        eng2.run_once(timeout=0.01)
    assert eng2.batch_prefills >= 1
    assert burst[0].result() == solo.result()
    assert len(burst[1].result()) == 6


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_burst_admission_mixed_buckets_and_prefix(lm):
    """Different prompt buckets split into groups (each exact); a
    prefix_len request rides the row path inside the same burst."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=8, autostart=False)
    sys_prompt = [7, 3, 19, 4]
    reqs = {
        "short_a": eng.submit([5, 11], max_new=4),
        "short_b": eng.submit([3, 2], max_new=4),
        "long_a": eng.submit([9, 23, 41, 7, 2], max_new=4),
        "long_b": eng.submit([1, 2, 3, 4, 5, 6], max_new=4),
        "prefixed": eng.submit(sys_prompt + [5, 11], max_new=4,
                               prefix_len=4),
    }
    for _ in range(10):
        eng.run_once(timeout=0.01)
    assert reqs["short_a"].result() == _oracle(config, params, [5, 11], 4)
    assert reqs["short_b"].result() == _oracle(config, params, [3, 2], 4)
    assert reqs["long_a"].result() == _oracle(config, params,
                                              [9, 23, 41, 7, 2], 4)
    assert reqs["long_b"].result() == _oracle(config, params,
                                              [1, 2, 3, 4, 5, 6], 4)
    assert reqs["prefixed"].result() == _oracle(config, params,
                                                sys_prompt + [5, 11], 4)
    assert eng.prefix_misses == 1  # the prefixed one used the row path
    assert eng.batch_prefills >= 1


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_burst_admission_caps_batch_and_falls_back(lm):
    """admit_batch_max chunks a burst (bounding the transient HBM of
    extra prefill rows); a failing batch prefill retries every member
    through the row path instead of failing innocents collectively."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=8, admit_batch_max=2,
                       autostart=False)
    prompts = [[5, 11], [3, 2], [9, 23], [13, 7]]
    reqs = [eng.submit(p, max_new=3) for p in prompts]
    for _ in range(6):
        eng.run_once(timeout=0.01)
    for p, r in zip(prompts, reqs):
        assert r.result() == _oracle(config, params, p, 3), p
    assert eng.batch_prefills == 2  # 4 same-bucket rows, cap 2 → 2 batches

    # batch prefill blows up → row-path fallback still serves everyone
    eng2 = DecodeEngine(config, params, slots=4, autostart=False)

    def boom(*a, **k):
        raise RuntimeError("injected batch prefill failure")

    eng2._prefill_batch = boom
    reqs2 = [eng2.submit(p, max_new=3) for p in prompts[:2]]
    for _ in range(6):
        eng2.run_once(timeout=0.01)
    for p, r in zip(prompts[:2], reqs2):
        assert r.result() == _oracle(config, params, p, 3), p
    assert eng2.batch_prefills == 0

    # admit_batch_max<=1 disables batching outright
    eng3 = DecodeEngine(config, params, slots=4, admit_batch_max=0,
                        autostart=False)
    reqs3 = [eng3.submit(p, max_new=3) for p in prompts[:2]]
    for _ in range(6):
        eng3.run_once(timeout=0.01)
    for p, r in zip(prompts[:2], reqs3):
        assert r.result() == _oracle(config, params, p, 3), p
    assert eng3.batch_prefills == 0


def test_burst_insert_failure_closes_engine(lm):
    """A donating insert that fails mid-burst has consumed the engine
    cache: the chunk fails retryably (EngineClosed, 503-class), the
    engine self-closes, and the repository-eviction path can rebuild —
    NOT the row-path retry (which can never succeed against a consumed
    cache)."""
    from kubeflow_tpu.serving.engine import EngineClosed

    config, params = lm
    # the loop starts once both are queued: started first, its thread
    # may admit the first alone (the row path) before the second arrives
    eng = DecodeEngine(config, params, slots=4, autostart=False)
    try:
        def boom(*a, **k):
            raise RuntimeError("injected insert failure")

        eng._insert_rows = boom
        reqs = [eng.submit([5, 11, 17], max_new=4),
                eng.submit([3, 2, 9], max_new=4)]
        eng.start()
        for r in reqs:
            with pytest.raises(EngineClosed):
                r.result()
        deadline = 50
        while not eng.closed and deadline:
            deadline -= 1
            import time as _t
            _t.sleep(0.1)
        assert eng.closed
        with pytest.raises(EngineClosed):
            eng.submit([7], max_new=2)
    finally:
        eng.close()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_prefix_cache_matches_full_prefill(lm):
    """prefix_len requests must be token-identical to full prefill —
    hit and miss paths both — and the store must actually be hit."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    sys_prompt = [7, 3, 19, 4]
    p1 = sys_prompt + [5, 11]
    p2 = sys_prompt + [9, 23, 2]
    want1 = _oracle(config, params, p1, 5)
    want2 = _oracle(config, params, p2, 5)

    r1 = eng.submit(p1, max_new=5, prefix_len=4)  # miss
    for _ in range(8):
        eng.run_once(timeout=0.01)
    r2 = eng.submit(p2, max_new=5, prefix_len=4)  # hit
    for _ in range(8):
        eng.run_once(timeout=0.01)
    assert r1.result() == want1
    assert r2.result() == want2
    assert eng.prefix_misses == 1 and eng.prefix_hits == 1

    # a stored prefix row is immutable: re-serving the FIRST prompt
    # after the second's continuation must still be exact
    r3 = eng.submit(p1, max_new=5, prefix_len=4)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    assert r3.result() == want1
    assert eng.prefix_hits == 2


def test_prefix_cache_sampled_reproducibility(lm):
    """Sampling through the prefix path must equal the full-prefill
    path for the same seed (same logits, same fold_in(seed, 0))."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    p = [7, 3, 19, 4, 5, 11]
    a = eng.submit(p, max_new=6, temperature=0.9, seed=5)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    b = eng.submit(p, max_new=6, temperature=0.9, seed=5, prefix_len=4)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    assert a.result() == b.result()


def test_prefix_cache_eviction_and_validation(lm):
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, prefix_cache_entries=2,
                       autostart=False)
    for i in range(3):  # 3 distinct prefixes, cap 2 → first evicted
        r = eng.submit([10 + i, 3, 19, 4, 5], max_new=2, prefix_len=4)
        for _ in range(4):
            eng.run_once(timeout=0.01)
        r.result()
    assert len(eng._prefix_store) == 2
    r = eng.submit([10, 3, 19, 4, 5], max_new=2, prefix_len=4)  # miss again
    for _ in range(4):
        eng.run_once(timeout=0.01)
    r.result()
    assert eng.prefix_misses == 4
    with pytest.raises(ValueError, match="prefix_len"):
        eng.submit([1, 2, 3], max_new=2, prefix_len=3)  # empty suffix
    with pytest.raises(ValueError, match="prefix_len"):
        eng.submit([1, 2, 3], max_new=2, prefix_len=-1)


def test_prefix_cache_byte_budget(lm):
    """The cache is budgeted in BYTES (each entry is a full-context KV
    row): a 1.5-row budget holds exactly one entry and evicts LRU; the
    held-bytes accounting tracks the store and never exceeds budget."""
    config, params = lm
    probe = DecodeEngine(config, params, slots=2, autostart=False)
    row = probe._prefix_row_bytes
    assert row > 0
    eng = DecodeEngine(config, params, slots=2,
                       prefix_cache_bytes=int(1.5 * row),
                       autostart=False)
    assert eng._prefix_budget_bytes == int(1.5 * row)
    for i in range(3):
        r = eng.submit([10 + i, 3, 19, 4, 5], max_new=2, prefix_len=4)
        for _ in range(4):
            eng.run_once(timeout=0.01)
        r.result()
        assert len(eng._prefix_store) == 1           # 2nd row never fits
        assert eng.prefix_cache_bytes == row
        assert eng.prefix_cache_bytes <= eng._prefix_budget_bytes
    assert eng.prefix_misses == 3                    # every new prefix evicts
    # LRU: the LAST prefix is the survivor
    r = eng.submit([12, 3, 19, 4, 5], max_new=2, prefix_len=4)
    for _ in range(4):
        eng.run_once(timeout=0.01)
    r.result()
    assert eng.prefix_hits == 1


def test_prefix_cache_entry_larger_than_budget(lm):
    """When ONE full-context row exceeds the budget the budget wins:
    nothing is cached, prefix requests are served by full prefill, and
    output is still exact."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, prefix_cache_bytes=128,
                       autostart=False)
    assert eng._prefix_row_bytes > 128
    p = [7, 3, 19, 4, 5, 11]
    want = _oracle(config, params, p, 5)
    r = eng.submit(p, max_new=5, prefix_len=4)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    assert r.result() == want
    assert len(eng._prefix_store) == 0
    assert eng.prefix_cache_bytes == 0
    assert eng.prefix_hits == 0 and eng.prefix_misses == 0


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_prefix_cache_near_context_end(lm):
    """Suffix bucket that would overflow the context falls back to the
    exact length instead of clamp-corrupting the cache write."""
    config, params = lm  # max_seq_len 48
    # 47 tokens, prefix 42, suffix 5: pow2(5)=8 and 42+8 > 48, so the
    # exact-length fallback branch MUST fire (and stay correct)
    prompt = list(range(1, 48))
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    r = eng.submit(prompt, max_new=1, prefix_len=42)
    for _ in range(4):
        eng.run_once(timeout=0.01)
    assert r.result() == _oracle(config, params, prompt, 1)
    # and the non-overflow case still buckets (different prefix)
    p2 = list(range(2, 45))  # 43 tokens, prefix 41, suffix 2
    r2 = eng.submit(p2, max_new=3, prefix_len=41)
    for _ in range(6):
        eng.run_once(timeout=0.01)
    assert r2.result() == _oracle(config, params, p2, 3)


def test_server_prefix_len_validation(tmp_path, lm):
    import http.client
    import json

    from kubeflow_tpu.serving import (ModelServer, export_model,
                                      transformer_export_config)

    config, params = lm
    export_model(str(tmp_path / "lm"), "transformer", params, version=1,
                 config=transformer_export_config(config))
    srv = ModelServer(str(tmp_path), port=0, poll_interval_s=3600,
                      decode_slots=2)
    port = srv.start()

    def post(body):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/models/lm:generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        conn.close()
        return resp.status, out

    try:
        code, out = post({"prompt_tokens": [[7, 3, 19, 4, 5, 11]],
                          "max_new_tokens": 4, "prefix_len": 4})
        assert code == 200
        assert out["tokens"][0] == _oracle(config, params,
                                           [7, 3, 19, 4, 5, 11], 4)
        code, out = post({"prompt_tokens": [[1, 2]], "prefix_len": 2})
        assert code == 400 and "prefix_len" in out["error"]
    finally:
        srv.stop()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_engine_with_moe_model():
    """The engine's prefill/insert/step must handle an MoE transformer
    (aux-loss collections + expert dispatch under decode mode)."""
    config = TransformerConfig(vocab_size=61, d_model=32, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=64,
                               max_seq_len=32, n_experts=4,
                               experts_per_token=2,
                               dtype=jnp.float32, remat=False)
    params = Transformer(config).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    r1 = eng.submit([5, 11, 17], max_new=5)
    r2 = eng.submit([9, 2], max_new=4)
    for _ in range(8):
        eng.run_once(timeout=0.01)
    assert r1.result() == _oracle(config, params, [5, 11, 17], 5)
    assert r2.result() == _oracle(config, params, [9, 2], 4)


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_greedy_fast_path_dispatch(lm):
    """All-greedy batches take the argmax step (no per-row sampler);
    a sampled co-tenant switches to the general step, and the greedy
    request's tokens are identical either way."""
    config, params = lm
    want = _oracle(config, params, [5, 11, 17], 8)
    eng = DecodeEngine(config, params, slots=4, autostart=False)
    g = eng.submit([5, 11, 17], max_new=8)
    for _ in range(10):
        eng.run_once(timeout=0.01)
    assert g.result() == want
    assert eng.greedy_steps == eng.steps_total > 0

    eng2 = DecodeEngine(config, params, slots=4, autostart=False)
    g2 = eng2.submit([5, 11, 17], max_new=8)
    s2 = eng2.submit([9, 2], max_new=8, temperature=0.9, seed=1)
    for _ in range(12):
        eng2.run_once(timeout=0.01)
    assert g2.result() == want          # same tokens on the general path
    assert len(s2.result()) == 8
    assert eng2.greedy_steps < eng2.steps_total  # sampler path was used


@pytest.mark.slow  # multi-second XLA compiles; warmup also covered in serving
def test_precompile_steps_then_serve(lm):
    """precompile=True warms both step programs on the empty batch and
    serving afterwards is still oracle-exact (the junk rows are fully
    overwritten at admission)."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, precompile=True,
                       autostart=False)
    r = eng.submit([5, 11, 17], max_new=6)
    s = eng.submit([9, 2], max_new=6, temperature=0.8, seed=4)
    for _ in range(10):
        eng.run_once(timeout=0.01)
    assert r.result() == _oracle(config, params, [5, 11, 17], 6)
    assert len(s.result()) == 6


# -- the round record: what the engine thread did with its time --------------


class _Tick:
    """Every read advances one second: phase durations are whole numbers,
    so the tiling below is asserted with ``==``, not a tolerance."""

    def __init__(self):
        self.t = 0.0
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            self.t += 1.0
            return self.t


_PHASES = ("wait_s", "admit_s", "step_s", "sync_s", "emit_s")


def _round_engine(lm, name, **kw):
    from kubeflow_tpu.obs import SpanCollector, Tracer

    config, params = lm
    clock = _Tick()
    collector = SpanCollector()
    eng = DecodeEngine(config, params, autostart=False, clock=clock,
                       tracer=Tracer(collector=collector, clock=clock),
                       name=name, **kw)
    return eng, collector


def _rounds(collector):
    return [s for s in collector.spans() if s.name == "engine.round"]


def _round_seconds(name, phase):
    from kubeflow_tpu.utils import DEFAULT_REGISTRY

    return DEFAULT_REGISTRY.counter(
        "kftpu_engine_round_seconds_total").get(model=name, phase=phase)


@pytest.mark.parametrize("steps_per_sync", [1, 4])
def test_round_phases_tile_and_counts_agree(lm, steps_per_sync):
    """One ``engine.round`` per run_once that did work; its five phase
    durations tile [start, end]; ``rounds_total`` and the registry's
    seconds by phase say what the spans say."""
    name = f"rounds-tile-{steps_per_sync}"
    eng, collector = _round_engine(lm, name, slots=4,
                                   steps_per_sync=steps_per_sync)
    g = eng.submit([5, 11, 17], max_new=13)
    s = eng.submit([9, 2], max_new=5, temperature=0.9, seed=1)
    while eng.run_once(timeout=0.01):
        pass
    assert len(g.result()) == 13 and len(s.result()) == 5
    rounds = _rounds(collector)
    assert len(rounds) == eng.rounds_total >= 3
    assert [r.attrs["round"] for r in rounds] == list(range(len(rounds)))
    for r in rounds:
        assert sum(r.attrs[p] for p in _PHASES) == r.end - r.start
        assert r.attrs["wait_s"] == 0.0     # the queue was never empty
        assert r.attrs["model"] == name
        if r.attrs["k"]:
            assert min(r.attrs[p] for p in _PHASES[1:]) > 0
    first = rounds[0].attrs
    assert first["admitted"] == 2 and first["rows"] == 2
    assert first["k"] == steps_per_sync and not first["greedy"]
    assert sum(r.attrs["admitted"] for r in rounds) == 2
    assert sum(r.attrs["k"] for r in rounds) == eng.steps_total
    # once the sampled row has left, the argmax program runs
    assert rounds[-1].attrs["greedy"] and rounds[-1].attrs["rows"] == 1
    for p in _PHASES:
        assert _round_seconds(name, p[:-2]) == \
            sum(r.attrs[p] for r in rounds)


def test_round_wait_is_the_blocked_get_and_nothing_else(lm):
    name = "rounds-wait"
    eng, collector = _round_engine(lm, name, slots=2)
    # an idle time-out is no round at all
    assert eng.run_once(timeout=0.01) is False
    assert _rounds(collector) == [] and eng.rounds_total == 0
    assert _round_seconds(name, "wait") == 0.0
    # a request that was already queued costs no wait
    eng.submit([5, 11, 17], max_new=1)
    assert eng.run_once(timeout=0.01) is True
    (r0,) = _rounds(collector)
    assert r0.attrs["wait_s"] == 0.0 and r0.attrs["admitted"] == 1
    # max_new=1 ends at the prefill's own token: nothing to step
    assert r0.attrs["k"] == 0 and r0.attrs["rows"] == 0
    assert r0.attrs["admit_s"] == r0.end - r0.start
    assert r0.attrs["step_s"] == r0.attrs["sync_s"] == \
        r0.attrs["emit_s"] == 0.0
    # an arrival while the engine is blocked: wait_s is the blocked get
    # (its two clock reads, and the submit's one read between them)
    blocked = threading.Event()
    real_get = eng._pending.get

    def get(*a, **kw):
        blocked.set()
        return real_get(*a, **kw)

    eng._pending.get = get

    def late():
        assert blocked.wait(timeout=30)
        eng.submit([7, 2], max_new=1)

    t = threading.Thread(target=late)
    t.start()
    assert eng.run_once(timeout=30) is True
    t.join(timeout=30)
    assert not t.is_alive()
    r1 = _rounds(collector)[-1]
    assert r1.attrs["wait_s"] == 2.0
    assert r1.attrs["wait_s"] + r1.attrs["admit_s"] == r1.end - r1.start
    assert _round_seconds(name, "wait") == 2.0
    assert eng.rounds_total == 2


def test_rounds_hang_off_one_engine_run_root(lm):
    from kubeflow_tpu.obs import SpanCollector, Tracer

    config, params = lm
    clock = _Tick()
    collector = SpanCollector()
    tracer = Tracer(collector=collector, clock=clock)
    # built inside a caller's span (the server builds an engine inside
    # its first request): the run still gets a trace of its own
    with tracer.span("caller") as caller:
        eng = DecodeEngine(config, params, slots=2, autostart=False,
                           clock=clock, tracer=tracer, name="rounds-root")
    eng.submit([5, 11, 17], max_new=4)
    while eng.run_once(timeout=0.01):
        pass
    assert not [s for s in collector.spans() if s.name == "engine.run"]
    eng.close()
    eng.close()                      # the root is recorded once
    (run,) = [s for s in collector.spans() if s.name == "engine.run"]
    assert run.parent_id is None and run.trace_id != caller.trace_id
    assert run.attrs["rounds"] == eng.rounds_total
    rounds = _rounds(collector)
    assert rounds and all(r.trace_id == run.trace_id
                          and r.parent_id == run.span_id for r in rounds)
    assert run.start <= rounds[0].start and rounds[-1].end <= run.end
    assert "engine.round" not in {s.name for s in collector.roots()}


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_the_round_loop_keeps_its_contract_over_either_cache(lm, paged):
    """The loop's contract, once, for both cache managers: a greedy
    stream through ``run_once`` equals ``generate``; one injected step
    failure is survived by replay, with the same tokens; the engine ends
    with nothing active, mid-admission or pending, and one closed
    ``engine.run`` root over its rounds."""
    config, params = lm
    eng, collector = _round_engine(
        lm, f"loop-contract-{paged}", slots=2,
        **(_PAGED_KW if paged else {}))
    assert eng.paged is paged and eng.snapshot().get("paged", False) is paged
    prompts = [[5, 11, 17], [3, 2, 9, 23, 7, 13, 19, 29, 31]]
    reqs = [eng.submit(p, max_new=8) for p in prompts]
    while not all(s is not None for s in eng._active):
        assert eng.run_once(timeout=0.01)    # both streams are decoding
    real = eng._step_greedy

    def boom(*a, **kw):
        raise RuntimeError("injected donating-call failure")

    eng._step_greedy = boom
    assert eng.run_once(timeout=0.01) is True    # fails, rebuilds, replays
    eng._step_greedy = real
    assert eng.recoveries == 1 and not eng.closed
    while eng.run_once(timeout=0.01):
        pass
    for p, r in zip(prompts, reqs):
        assert r.result() == _oracle(config, params, p, 8), p
    assert eng.active_count == eng.pending_count == 0
    snap = eng.snapshot()
    assert (snap["active_slots"], snap["pending"], snap["recoveries"],
            snap["closed"]) == (0, 0, 1, False)
    if paged:
        eng._pool.check_idle()
        assert snap["pages_in_use"] == snap["prefill_slots"] == 0
    # the recovered round is a round like any other, under the one root
    assert not [s for s in collector.spans() if s.name == "engine.run"]
    eng.close()
    (run,) = [s for s in collector.spans() if s.name == "engine.run"]
    rounds = _rounds(collector)
    assert run.attrs["rounds"] == eng.rounds_total == len(rounds)
    assert all(r.parent_id == run.span_id for r in rounds)
    assert run.attrs["steps"] == eng.steps_total


def test_engine_options_do_not_come_from_the_environment(lm, monkeypatch):
    """A keyword left out takes the default the code has, whatever the
    process environment says: deployment names are read by
    ``serving/server.py:server_options`` and arrive as keywords."""
    config, params = lm
    monkeypatch.setenv("KFTPU_PAGED", "1")
    monkeypatch.setenv("KFTPU_SAMPLER_IMPL", "exact_sort")
    monkeypatch.setenv("KFTPU_ADMIT_BATCH", "1")
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    assert eng.paged is False and eng.kv_page_size == 0
    assert eng.sampler_impl == "bounded" and eng.sampler_bound == 64
    assert eng.admit_batch_max == 8
    eng.close()


def test_admit_spans_name_their_round(lm):
    """Row and batch admissions both carry the ordinal of the round that
    stalled for them, so a slow request's trace names its rounds."""
    eng, collector = _round_engine(lm, "rounds-admit", slots=4)
    eng.submit([5, 11, 17], max_new=3)                 # alone: row path
    eng.run_once(timeout=0.01)
    for i in range(3):                                 # a burst: batch path
        eng.submit([3 + i, 2, 9], max_new=2)
    while eng.run_once(timeout=0.01):
        pass
    rounds = {r.attrs["round"]: r for r in _rounds(collector)}
    admits = [s for s in collector.spans() if s.name == "engine.admit"]
    assert sorted(a.attrs["batched"] for a in admits) == \
        [False, True, True, True]
    for a in admits:
        r = rounds[a.attrs["round"]]
        assert r.start < a.start and a.end < r.end
    assert {a.attrs["round"] for a in admits} == {0, 1}


_ADMIT_S = ("host_s", "launch_s", "read_s", "insert_s")


def _admissions(collector):
    return [s for s in collector.spans() if s.name == "engine.admission"]


def _engine_counter(series, **labels):
    from kubeflow_tpu.utils import DEFAULT_REGISTRY

    return DEFAULT_REGISTRY.counter(series).get(**labels)


# what each path's admissions must have counted, in the order they ran:
# (rows, rows_padded, width, prompt_tokens, chunks)
_ADMISSION_CASES = {
    # 3 tokens in the bucket of 4
    "row": ([dict(prompt=[5, 11, 17])], [(1, 1, 4, 3, 0)]),
    # a miss prefills the 6-token prefix (bucket 8) and continues the
    # 5-token suffix (bucket 8); the hit after it runs the suffix alone
    "prefix": ([dict(prompt=list(range(1, 12)), prefix_len=6)] * 2,
               [(1, 1, 16, 11, 0), (1, 1, 8, 5, 0)]),
    # 20 tokens through a chunk program 8 wide: 3 chunks, read once
    "chunked": ([dict(prompt=list(range(1, 21)))], [(1, 1, 24, 20, 3)]),
    # a burst of 3 in the bucket of 4 runs as 4 rows
    "batch": ([dict(prompt=[3, 2, 9]), dict(prompt=[4, 2, 9]),
               dict(prompt=[5, 2, 9])], [(3, 4, 4, 9, 0)]),
}


@pytest.mark.parametrize("kind", sorted(_ADMISSION_CASES))
def test_admission_durations_tile_and_counters_agree(lm, kind):
    """One ``engine.admission`` span a device admission on every
    RowCache path: its four durations tile [start, end] on the fake
    clock, it counts what the program ran, a round's admissions fit in
    its ``admit_s``, and the three counters say what the spans say."""
    name = f"admission-{kind}"
    eng, collector = _round_engine(lm, name, slots=4, admit_batch_max=4)
    if kind == "chunked":
        # the dense toy declares no chunk: give its manager one, as a
        # model with ``prefill_chunk`` would (tests/test_dsa.py has one)
        eng._kv._chunk_width = 8
    submits, expected = _ADMISSION_CASES[kind]
    reqs = []
    for kw in submits:
        kw = dict(kw)
        reqs.append(eng.submit(kw.pop("prompt"), max_new=3, **kw))
        if kind != "batch":              # one admission a round
            assert eng.run_once(timeout=0.01)
    while eng.run_once(timeout=0.01):
        pass
    assert all(len(r.result()) == 3 for r in reqs)
    adms = _admissions(collector)
    got = [(a.attrs["rows"], a.attrs["rows_padded"], a.attrs["width"],
            a.attrs["prompt_tokens"], a.attrs.get("chunks", 0))
           for a in adms]
    assert got == expected
    run_span_parent = {r.parent_id for r in _rounds(collector)}
    for a in adms:
        assert a.attrs["kind"] == kind and a.attrs["model"] == name
        assert a.status == "OK" and {a.parent_id} == run_span_parent
        assert sum(a.attrs[p] for p in _ADMIT_S) == a.end - a.start
        assert min(a.attrs[p] for p in _ADMIT_S) > 0
        assert a.attrs["scanned_tokens"] == \
            a.attrs["rows_padded"] * a.attrs["width"]
    rounds = {r.attrs["round"]: r for r in _rounds(collector)}
    for n, r in rounds.items():
        mine = [a for a in adms if a.attrs["round"] == n]
        assert all(r.start < a.start and a.end < r.end for a in mine)
        assert sum(a.end - a.start for a in mine) <= r.attrs["admit_s"]
    assert {a.attrs["round"] for a in adms} <= set(rounds)
    for p in _ADMIT_S:
        assert _engine_counter("kftpu_engine_admit_seconds_total",
                               model=name, phase=p[:-2]) == \
            sum(a.attrs[p] for a in adms)
    assert _engine_counter("kftpu_engine_admissions_total", model=name,
                           kind=kind) == len(adms)
    for what in ("prompt", "scanned"):
        assert _engine_counter("kftpu_engine_prefill_tokens_total",
                               model=name, what=what) == \
            sum(a.attrs[f"{what}_tokens"] for a in adms)
    # the request's own spans keep their names, parents and attributes
    spans = collector.spans()
    admits = [s for s in spans if s.name == "engine.admit"]
    prefills = [s for s in spans if s.name == "engine.prefill"]
    assert len(admits) == len(prefills) == len(reqs)
    assert {p.parent_id for p in prefills} == {a.span_id for a in admits}
    assert all(a.attrs["model"] == name for a in admits)


def test_a_burst_of_three_counts_its_pad_row(lm):
    """3 prompts of one bucket at ``admit_batch_max`` 4 run as ONE
    program of 4 rows, each scanned at the bucket's width: the pad row
    and the buckets' padding are both in ``scanned_tokens``."""
    eng, collector = _round_engine(lm, "admission-burst", slots=4,
                                   admit_batch_max=4)
    for n in (5, 6, 7):                              # all in the bucket of 8
        eng.submit(list(range(1, n + 1)), max_new=2)
    while eng.run_once(timeout=0.01):
        pass
    (a,) = _admissions(collector)
    assert (a.attrs["kind"], a.attrs["rows"], a.attrs["rows_padded"]) == \
        ("batch", 3, 4)
    assert a.attrs["width"] == 8 and a.attrs["scanned_tokens"] == 4 * 8
    assert a.attrs["prompt_tokens"] == 5 + 6 + 7
    # what the members' own spans can say: the buckets' padding alone
    prefills = [s for s in collector.spans() if s.name == "engine.prefill"]
    assert sum(p.attrs["bucket"] for p in prefills) == 3 * 8


def test_a_failed_admission_is_recorded_and_leaves_the_host_leaf(lm):
    """A prefill that raises fails its own request; its admission is
    still one span (status ERROR, the four durations tiling it), and the
    annotations still tile: the leaf it died in is closed, the round
    goes on in ``engine.admit.host``."""
    import contextlib

    from kubeflow_tpu.obs import SpanCollector, Tracer

    config, params = lm
    clock = _Tick()
    collector = SpanCollector()
    open_now, names = [], []

    @contextlib.contextmanager
    def annotator(name):
        assert not open_now, (name, open_now)       # never two at once
        open_now.append(name)
        names.append(name)
        try:
            yield
        finally:
            open_now.pop()

    eng = DecodeEngine(config, params, slots=2, autostart=False,
                       clock=clock, name="admission-fails",
                       tracer=Tracer(collector=collector, clock=clock,
                                     annotator=annotator))
    real = eng._kv._prefill

    def boom(*a, **kw):
        raise RuntimeError("injected prefill failure")

    eng._kv._prefill = boom
    bad = eng.submit([5, 11, 17], max_new=3)
    assert eng.run_once(timeout=0.01) is True
    eng._kv._prefill = real
    good = eng.submit([5, 11, 17], max_new=3)
    while eng.run_once(timeout=0.01):
        pass
    with pytest.raises(RuntimeError, match="injected"):
        bad.result()
    assert len(good.result()) == 3
    failed, ok = _admissions(collector)
    assert failed.status == "ERROR: RuntimeError" and ok.status == "OK"
    for a in (failed, ok):
        assert sum(a.attrs[p] for p in _ADMIT_S) == a.end - a.start
    assert failed.attrs["read_s"] == failed.attrs["insert_s"] == 0.0
    assert not open_now
    assert names[:3] == ["engine.admit.host", "engine.admit.launch",
                         "engine.admit.host"]
    # the failed request's own spans say so too
    (admit,) = [s for s in collector.spans() if s.name == "engine.admit"
                and s.status != "OK"]
    assert admit.status == "ERROR: RuntimeError"


def test_compiles_name_the_admission_that_built_a_program(lm):
    """A prompt bucket that no earlier admission ran (a shape a warm-up
    missed) shows as ``compiles`` >= 1 on ITS admission and round, and 0
    on its neighbours of buckets already built."""
    eng, collector = _round_engine(lm, "admission-compiles", slots=4,
                                   admit_batch_max=0)
    eng.submit([5, 11, 17], max_new=40)              # builds bucket 4
    assert eng.run_once(timeout=0.01)
    assert eng.run_once(timeout=0.01)                # a step of its own
    for prompt in ([7, 2, 9], list(range(1, 14)), [9, 9, 2]):
        eng.submit(prompt, max_new=2)                # 4, then 16, then 4
        assert eng.run_once(timeout=0.01)
    first, warm, cold, warm_again = _admissions(collector)
    assert first.attrs["compiles"] >= 1              # prefill and insert
    assert (warm.attrs["width"], warm.attrs["compiles"]) == (4, 0)
    assert cold.attrs["width"] == 16 and cold.attrs["compiles"] >= 1
    assert (warm_again.attrs["width"], warm_again.attrs["compiles"]) == (4, 0)
    rounds = {r.attrs["round"]: r.attrs["compiles"]
              for r in _rounds(collector)}
    assert rounds[cold.attrs["round"]] == cold.attrs["compiles"]
    assert rounds[warm.attrs["round"]] == rounds[
        warm_again.attrs["round"]] == 0
    assert rounds[0] >= first.attrs["compiles"] + 1  # + the step program


def test_tokens_series_follows_the_integer_once_a_round(lm):
    """``_emit`` counts a token on the engine's own integer; the series
    ``kftpu_engine_tokens_total`` gets a round's tokens (first tokens
    armed in admission included) when the round is recorded, so it reads
    ``tokens_total`` at every round's end."""
    name = "tokens-once-a-round"
    eng, _collector = _round_engine(lm, name, slots=2, steps_per_sync=4)
    eng.submit([5, 11, 17], max_new=9)
    eng.submit([9, 2], max_new=1)                    # ends at its first token
    seen = []
    while eng.run_once(timeout=0.01):
        seen.append(eng.tokens_total)
        assert _engine_counter("kftpu_engine_tokens_total",
                               model=name) == eng.tokens_total
    assert seen[0] == 2 + 4 and seen[-1] == 10       # two first tokens + K


# The benchmark finds the engine's compiled programs in a device trace by
# the names of these private functions (``jit__step`` …):
# benchmark/harness/readers.py:decode_step_s matches
# ``^jit__step(_greedy)?\b`` and benchmark/metrics/flash_roofline.py:CALL
# finds the flash kernels as custom calls inside ``attn._attend``. A
# rename here silences decode_step_ms, decode_step_roofline and
# flash_roofline, and no PR but a `benchmark` one may edit those readers:
# renaming takes a benchmark issue that changes the reader with the name.
@pytest.mark.parametrize("attr, program", [
    ("_step", "_step"),
    ("_step_greedy", "_step_greedy"),
    ("_prefill", "_prefill_and_sample"),
    ("_continue", "_continue_and_sample"),
    ("_insert", "_insert"),
    ("_insert_rows", "_insert_rows"),
])
def test_program_names_the_benchmark_reads(lm, attr, program):
    config, params = lm
    eng = DecodeEngine(config, params, slots=2, autostart=False)
    assert getattr(eng, attr).__name__ == program


# -- the carried cache (PR 28) ----------------------------------------------

_HLO_INSTR = re.compile(
    r"^\s+(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")
_HLO_CALLEE = re.compile(
    r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")


def _hlo_computations(text):
    """``{computation: [(shape, op, line)]}`` of a compiled module's
    text, with each computation's callees under ``(None, "calls", …)``."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split("(")[0].split()[-1].lstrip("%")
            cur = comps.setdefault(name, [])
        elif cur is not None:
            m = _HLO_INSTR.match(line)
            if m:
                cur.append((m.group(1).split("{")[0], m.group(2), line))
                for callee in _HLO_CALLEE.findall(line):
                    cur.append((None, "calls", callee))
    return comps


def _layout(cache):
    return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), cache)


def _by_name(cache):
    """The stacked cache's leaves by leaf name, wherever they nest."""
    return {path[-1].key: leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(cache)}


def _step_args(eng):
    B = eng.slots
    toks = jnp.zeros((B,), jnp.int32)
    ones = jnp.ones((B,), jnp.float32)
    return {"_step_greedy": (toks,),
            "_step": (toks, toks, toks, ones, toks, ones)}


@pytest.mark.parametrize("program", ["_step", "_step_greedy"])
def test_step_program_updates_the_cache_in_place(lm, program):
    """The K-step program holds ONE buffer per stacked cache leaf from
    entry to exit: nothing inside a loop copies or re-creates a buffer
    of the stack's shape, and outside the fused computations exactly one
    instruction per leaf (the token scatter) produces one. An f32 cache:
    the CPU backend widens bf16 scatters through whole-cache converts
    that the TPU backend does not have."""
    config, params = lm
    eng = DecodeEngine(config, params, slots=4, steps_per_sync=4,
                       autostart=False)
    stack = _by_name(eng._cache)["k"]
    assert stack.shape == config.cache_leaves(eng.slots)["k"].shape
    assert stack.dtype == jnp.float32
    shape = "f32[%s]" % ",".join(str(d) for d in stack.shape)
    text = getattr(eng, program).lower(
        eng._params, eng._cache, *_step_args(eng)[program]
    ).compile().as_text()
    comps = _hlo_computations(text)

    def callees(name):
        return [x for s, op, x in comps.get(name, ()) if op == "calls"]

    loops = [c for instrs in comps.values() for s, op, line in instrs
             if op == "while" for c in _HLO_CALLEE.findall(line)]
    assert loops, "no while loop in the step program"
    inside, todo = set(), list(loops)
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo.extend(callees(name))
    moved = [line.strip()[:120] for name in inside
             for s, op, line in comps.get(name, ())
             if s == shape and op in ("copy", "broadcast")]
    assert not moved, moved

    fused = {c for instrs in comps.values() for s, op, line in instrs
             if op == "fusion" for c in _HLO_CALLEE.findall(line)}
    passes = ("parameter", "get-tuple-element", "tuple", "bitcast",
              "while", "call", "conditional")
    written = [line.strip()[:120] for name, instrs in comps.items()
               if name not in fused for s, op, line in instrs
               if s == shape and op not in passes]
    assert len(written) <= 2, written     # one for k, one for v


def _per_layer_params(params, n_layers):
    """``scan_layers=True`` params as ``scan_layers=False`` names them."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(n_layers):
        out[f"block_{i}"] = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                   params["blocks"])
    return out


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_carried_cache_equals_per_layer_buffers(lm, paged, temperature):
    """Ragged prompts through K-step rounds: the layer scan that carries
    the stacked cache and the unrolled layers that own one buffer each
    give the same tokens and the same cache (positions and page tables
    exactly, K and V to rounding)."""
    config, params = lm
    prompts = [[5, 11, 17], [3, 2, 9, 23, 41, 8, 1], [7]]
    kw = (dict(paged=True, kv_page_size=8, prefill_chunk_tokens=8)
          if paged else {})
    ran = []
    for cfg, p in ((config, params),
                   (dataclasses.replace(config, scan_layers=False),
                    _per_layer_params(params, config.n_layers))):
        eng = DecodeEngine(cfg, p, slots=4, steps_per_sync=4,
                           autostart=False, **kw)
        before = _layout(eng._cache)
        reqs = [eng.submit(pr, max_new=10, temperature=temperature,
                           top_k=7, top_p=0.9, seed=3 + i)
                for i, pr in enumerate(prompts)]
        for _ in range(30):
            eng.run_once(timeout=0.01)
        assert before == _layout(eng._cache)
        ran.append(([r.result() for r in reqs], eng._cache, eng))
    (toks, stacked, eng), (loop_toks, per_layer, loop_eng) = ran
    stacked = _by_name(stacked)
    assert toks == loop_toks
    assert eng.steps_total == loop_eng.steps_total > 0
    assert sorted(stacked) == sorted(per_layer["block_0"]["attn"])
    for i in range(config.n_layers):
        for name, leaf in per_layer[f"block_{i}"]["attn"].items():
            got = stacked[name][i]
            assert (got.shape, got.dtype) == (leaf.shape, leaf.dtype), name
            # the two programs fuse differently: float leaves agree to
            # rounding (seen: 14 of 3072 elements one ulp apart)
            np.testing.assert_allclose(np.asarray(got), np.asarray(leaf),
                                       rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("geometry", sorted(KV_GEOMETRIES))
def test_greedy_tokens_at_every_kv_geometry(geometry):
    """Batch admission, row admission into a reused slot and a prefix
    miss and hit, on two slots: every request's greedy tokens are those
    of the full forward with no cache, whatever the width of the merged
    K/V axis, and the engine's leaves are the contract's."""
    H, KH, Dh = KV_GEOMETRIES[geometry]
    config = TransformerConfig(vocab_size=97, d_model=H * Dh, n_layers=2,
                               n_heads=H, n_kv_heads=KH, d_ff=64,
                               max_seq_len=48, dtype=jnp.float32,
                               remat=False)
    params = Transformer(config).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    eng = DecodeEngine(config, params, slots=2, steps_per_sync=2,
                       autostart=False)
    for name, leaf in config.cache_leaves(eng.slots).items():
        assert _by_name(eng._cache)[name].shape == leaf.shape, name
    assert _by_name(eng._cache)["k"].shape == (2, 2, 48, KH * Dh)

    sys_prompt = [7, 3, 19, 4]
    burst = [[5, 11, 17], [3, 2, 9], [13, 1, 8]]    # one bucket: a batch
    late = [9, 23, 41, 7, 2]                        # alone: the row path
    reqs = [(p, eng.submit(p, max_new=5)) for p in burst]
    for _ in range(6):
        eng.run_once(timeout=0.01)
    reqs.append((late, eng.submit(late, max_new=4)))
    for tail in ([5, 11], [9, 23, 2]):              # a miss, then a hit
        for _ in range(6):
            eng.run_once(timeout=0.01)
        reqs.append((sys_prompt + tail,
                     eng.submit(sys_prompt + tail, max_new=4,
                                prefix_len=4)))
    for _ in range(8):
        eng.run_once(timeout=0.01)
    for prompt, req in reqs:
        want = full_forward_greedy(Transformer(config), params, [prompt],
                                   len(req.result()))
        assert req.result() == np.asarray(want)[0].tolist(), prompt
    assert eng.batch_prefills >= 1
    assert (eng.prefix_misses, eng.prefix_hits) == (1, 1)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("program", ["_step", "_step_greedy"])
def test_step_returns_the_cache_layout_it_was_given(lm, program, paged):
    """The pytree the engine builds at construction (from ``prefill``'s
    ``eval_shape``) is the one every step hands back: same treedef, leaf
    names, shapes and dtypes, layers stacked on axis 0."""
    config, params = lm
    kw = dict(paged=True, kv_page_size=8) if paged else {}
    eng = DecodeEngine(config, params, slots=4, steps_per_sync=2,
                       autostart=False, **kw)
    out, _, stats = jax.eval_shape(getattr(eng, program), eng._params,
                                   eng._cache, *_step_args(eng)[program])
    assert stats == {}       # no routed layers: nothing beside the tokens
    assert _layout(out) == _layout(eng._cache)
    spec = _by_name(out)
    L, B = config.n_layers, eng.slots
    assert spec["positions"].shape == (L, B)
    assert spec["k"].shape[0] == spec["v"].shape[0] == L
    assert sorted(spec) == (["k", "pages", "positions", "v"] if paged
                            else ["k", "positions", "v"])
