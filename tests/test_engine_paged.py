"""Paged decode engine: block/paged KV cache, chunked prefill, page-
refcounted prefix sharing, and cache recovery.

The oracles are (a) the plain bucketed ``generate`` path and (b) the
DENSE engine — the pre-paged implementation kept precisely so greedy
token streams can be asserted bit-identical across the cache rebuild
(ISSUE 6 acceptance), and (c) the page pool's own refcounts, which must
return to zero when streams retire (no leaked or copied pages).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import Transformer, TransformerConfig
from kubeflow_tpu.models.decode import generate
from kubeflow_tpu.serving.engine import DecodeEngine, pow2_bucket


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


def _paged(config, params, **kw):
    kw.setdefault("kv_page_size", 8)
    kw.setdefault("prefill_chunk_tokens", 8)
    kw.setdefault("autostart", False)
    return DecodeEngine(config, params, paged=True, **kw)


def _drain(eng, n=60):
    for _ in range(n):
        eng.run_once(timeout=0.01)


# -- pow2_bucket edges (chunked prefill makes bucket selection hot) ---------


def test_pow2_bucket_edges():
    assert pow2_bucket(0, 64) == 1
    assert pow2_bucket(1, 64) == 1
    assert pow2_bucket(3, 64) == 4
    assert pow2_bucket(64, 64) == 64      # n == cap exactly
    assert pow2_bucket(65, 64) == 64      # past the cap clamps
    assert pow2_bucket(10 ** 9, 64) == 64
    # a non-power-of-two cap is its own terminal bucket
    assert pow2_bucket(5, 6) == 6
    assert pow2_bucket(6, 6) == 6
    assert pow2_bucket(3, 6) == 4
    assert pow2_bucket(0, 1) == 1
    with pytest.raises(ValueError, match="cap"):
        pow2_bucket(4, 0)


# -- paged correctness ------------------------------------------------------


def test_paged_matches_oracle_and_dense_engine(lm):
    """Greedy streams through the paged engine are bit-identical to the
    pre-paged (dense) engine on the same prompts — the paged rebuild
    changes the memory layout, never the tokens."""
    config, params = lm
    prompts = [[5, 11, 17], [3, 2, 9, 23, 41]]
    dense = DecodeEngine(config, params, slots=4, autostart=False)
    d1 = dense.submit(prompts[0], max_new=8)
    d2 = dense.submit(prompts[1], max_new=4)
    _drain(dense, 15)
    eng = _paged(config, params, slots=4, prefill_chunk_tokens=4)
    r1 = eng.submit(prompts[0], max_new=8)
    r2 = eng.submit(prompts[1], max_new=4)
    _drain(eng)
    assert r1.result() == d1.result() == _oracle(config, params,
                                                 prompts[0], 8)
    assert r2.result() == d2.result() == _oracle(config, params,
                                                 prompts[1], 4)
    assert eng.prefill_chunks >= 2
    # retirement reclaimed every page
    eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_admission_into_running_batch(lm):
    config, params = lm
    eng = _paged(config, params, slots=4)
    r1 = eng.submit([5, 11, 17], max_new=10)
    for _ in range(4):
        eng.run_once(timeout=0.01)
    r2 = eng.submit([7, 2], max_new=3)
    _drain(eng)
    assert r1.result() == _oracle(config, params, [5, 11, 17], 10)
    assert r2.result() == _oracle(config, params, [7, 2], 3)
    eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_eos_frees_pages_early(lm):
    config, params = lm
    toks = _oracle(config, params, [5, 11, 17], 8)
    eos = next((toks[i] for i in range(1, len(toks))
                if toks[i] not in toks[:i]), None)
    if eos is None:
        pytest.skip("degenerate greedy sequence")
    eng = _paged(config, params, slots=2)
    req = eng.submit([5, 11, 17], max_new=8, eos_id=eos)
    _drain(eng, 20)
    got = req.result()
    assert got == toks[:toks.index(eos) + 1]
    assert eng.active_count == 0
    eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_sampled_reproducible_with_fused_sampler(lm):
    """fold_in(key(seed), step) reproducibility survives both the paged
    cache and the fused Pallas sampler: same seed, same stream, with or
    without co-tenants."""
    config, params = lm
    eng = _paged(config, params, slots=4, sampler_impl="fused")
    solo = eng.submit([5, 11, 17], max_new=6, temperature=0.8, seed=42)
    _drain(eng, 20)
    eng2 = _paged(config, params, slots=4, sampler_impl="fused")
    crowd = [eng2.submit([9 + i], max_new=6, temperature=1.3, seed=i)
             for i in range(3)]
    shared = eng2.submit([5, 11, 17], max_new=6, temperature=0.8,
                         seed=42)
    _drain(eng2, 25)
    assert solo.result() == shared.result()
    assert len(solo.result()) == 6
    for c in crowd:
        assert len(c.result()) == 6


def test_paged_snapshot_reports_page_pool(lm):
    config, params = lm
    eng = _paged(config, params, slots=4)
    snap = eng.snapshot()
    assert snap["paged"] and snap["pages_total"] == eng._pool.pages_total
    assert snap["pages_free"] == snap["pages_total"]
    req = eng.submit([5, 11, 17], max_new=6)
    for _ in range(3):
        eng.run_once(timeout=0.01)
    mid = eng.snapshot()
    assert mid["pages_in_use"] > 0
    assert mid["pages_free"] < mid["pages_total"]
    assert mid["active_slots"] >= 1  # prefilling or decoding
    _drain(eng, 20)
    req.result()
    end = eng.snapshot()
    assert end["pages_in_use"] == 0 and end["active_slots"] == 0


# -- paged-attention kernel: the bit-parity gate ----------------------------


def test_parity_three_way_dense_gather_kernel(lm):
    """THE acceptance gate: greedy token streams are identical across
    the dense engine, the paged-GATHER path, and the paged Pallas
    KERNEL path (interpret mode on CPU) — same prompts, chunked prefill
    (3 chunks for the long prompt) and shared decode steps, GQA shapes
    (the fixture is 4 q-heads over 2 kv-heads)."""
    config, params = lm
    p_short, p_long = [5, 11, 17], [3, 2, 9, 23, 41, 8, 1, 30, 12]
    streams = {}
    for mode in ("dense", "gather", "kernel"):
        if mode == "dense":
            eng = DecodeEngine(config, params, slots=4, autostart=False)
        else:
            eng = _paged(config, params, slots=4,
                         prefill_chunk_tokens=4,
                         paged_attention_impl=mode)
        rs = [eng.submit(p_short, max_new=10),
              eng.submit(p_long, max_new=6)]
        _drain(eng)
        streams[mode] = [r.result() for r in rs]
        if mode != "dense":
            eng._pool.check_idle()
    want = [_oracle(config, params, p_short, 10),
            _oracle(config, params, p_long, 6)]
    assert streams["dense"] == streams["gather"] == streams["kernel"] \
        == want


def test_parity_kernel_non_gqa():
    """Non-GQA (n_kv_heads == n_heads): the kernel's in-kernel head
    grouping degenerates to group size 1 and must stay token-identical
    to gather and dense."""
    config = TransformerConfig(vocab_size=61, d_model=32, n_layers=2,
                               n_heads=2, n_kv_heads=2, d_ff=64,
                               max_seq_len=32, dtype=jnp.float32,
                               remat=False)
    params = Transformer(config).init(
        jax.random.key(1), np.zeros((1, 8), np.int32))["params"]
    prompt = [7, 3, 2, 9, 23]
    want = _oracle(config, params, prompt, 8)
    for mode in ("gather", "kernel"):
        eng = _paged(config, params, slots=2, paged_attention_impl=mode)
        r = eng.submit(prompt, max_new=8)
        _drain(eng, 30)
        assert r.result() == want, f"{mode} diverged"
        eng._pool.check_idle()


def test_parity_kernel_ragged_continuation_and_cow(lm):
    """Ragged continuation through the kernel path: a prefix hit with a
    NON-page-aligned boundary admits mid-page (chunks run from a ragged
    start, decode steps read through the COW-split copy) — streams stay
    identical to the gather engine and the unary oracle, and the
    boundary page is copied EXACTLY once per sharing admission."""
    config, params = lm
    pfx = list(range(1, 13))                    # 1 full page + 4 tokens
    p1, p2 = pfx + [5, 11], pfx + [9, 3, 7]
    for mode in ("gather", "kernel"):
        eng = _paged(config, params, slots=4, paged_attention_impl=mode)
        copies = []
        real = eng._copy_page

        def counted(cache, s, d, _real=real, _c=copies):
            _c.append((int(s), int(d)))
            return _real(cache, s, d)

        eng._copy_page = counted
        r1 = eng.submit(p1, max_new=4, prefix_len=12)
        _drain(eng, 25)
        r2 = eng.submit(p2, max_new=4, prefix_len=12)
        _drain(eng, 25)
        assert r1.result() == _oracle(config, params, p1, 4)
        assert r2.result() == _oracle(config, params, p2, 4)
        # r1 misses (stores 1 node + 1 COW tail); r2 shares both and
        # splits the boundary page exactly once — ONE device page copy
        # instead of a 4-token boundary re-prefill
        assert eng.prefix_hits == 1 and eng.prefix_misses == 1
        assert eng.prefix_pages_shared == 2
        assert eng.cow_splits == 1 and len(copies) == 1
        assert eng._pool.cow_splits == 1
        snap = eng.snapshot()
        assert snap["cow_splits"] == 1 and snap["prefix_hits"] == 1
        assert snap["prefix_pages_shared"] == 2
        eng._prefix_pages.clear()
        eng._pool.check_idle()


def test_parity_kernel_fused_sampler(lm):
    """Fused-sampler interaction: sampled streams through the kernel
    path reproduce the gather path's (same fold_in(key(seed), step)
    draws over logits that agree to f32 round-off) and are seed-stable
    across engines."""
    config, params = lm
    kw = dict(max_new=6, temperature=0.8, top_k=12, top_p=0.9, seed=11)
    outs = {}
    for mode in ("gather", "kernel"):
        eng = _paged(config, params, slots=2, sampler_impl="fused",
                     paged_attention_impl=mode)
        r = eng.submit([5, 11, 17, 2], **kw)
        _drain(eng, 25)
        outs[mode] = r.result()
        eng._pool.check_idle()
    assert outs["gather"] == outs["kernel"]
    assert len(outs["kernel"]) == 6


# -- prefix pages: shared by refcount, never copied -------------------------


def test_prefix_pages_shared_by_refcount(lm):
    """A prefix-cache hit maps the STORED pages into the new slot's
    table (refcount 2: store + slot) instead of copying a row; retiring
    every sharer and evicting the store returns the pool to idle."""
    config, params = lm
    eng = _paged(config, params, slots=4)
    sys_prompt = list(range(1, 17))            # 16 tokens = 2 full pages
    p1 = sys_prompt + [5, 11]
    p2 = sys_prompt + [9, 23, 2]
    r1 = eng.submit(p1, max_new=4, prefix_len=16)
    _drain(eng, 20)
    assert r1.result() == _oracle(config, params, p1, 4)
    # the trie stores one node per page: 2 full pages pinned
    assert eng.prefix_misses == 1 and eng._prefix_pages.pages_held == 2
    stored = set(eng._prefix_pages._held)
    r2 = eng.submit(p2, max_new=4, prefix_len=16)
    shared_seen = False
    for _ in range(40):
        eng.run_once(timeout=0.01)
        # while the hit decodes, its table rows point AT the stored
        # pages and their refcount is 2 — pages shared, not copied
        if any(eng._pool.ref[p] >= 2 for p in stored):
            shared_seen = True
    assert shared_seen
    assert r2.result() == _oracle(config, params, p2, 4)
    assert eng.prefix_hits == 1
    assert eng._pool.pages_in_use == 2        # only the store's pin left
    eng._prefix_pages.clear()
    eng._pool.check_idle()


def test_trie_hit_on_prefix_the_exact_store_missed(lm):
    """A request sharing only the FIRST page of a stored two-page
    prefix still hits: the pre-trie store keyed on the ENTIRE aligned
    prefix, so this exact workload shared nothing — page-granular
    matching is the point of the trie."""
    config, params = lm
    sys_prompt = list(range(1, 17))            # 16 tokens = 2 pages
    eng = _paged(config, params, slots=4)
    r1 = eng.submit(sys_prompt + [5], max_new=3, prefix_len=16)
    _drain(eng, 25)
    assert r1.result() == _oracle(config, params, sys_prompt + [5], 3)
    assert eng.prefix_misses == 1 and eng._prefix_pages.pages_held == 2
    # only the first page in common — old key (8, tokens[:8]) ∉ store
    p2 = sys_prompt[:8] + [40, 41, 42]
    first_page = eng._prefix_pages._held[0]    # insertion order: page 0
    r2 = eng.submit(p2, max_new=3, prefix_len=8)
    shared_seen = False
    release_slot = eng._pool.release_slot

    def release(slot):
        # the slot gives its pages back at the launch that covers its
        # budget, inside the cycle that admitted it: look there too
        nonlocal shared_seen
        shared_seen |= bool(eng._pool.ref[first_page] >= 2)
        release_slot(slot)

    eng._pool.release_slot = release
    for _ in range(30):
        eng.run_once(timeout=0.01)
        if eng._pool.ref[first_page] >= 2:
            shared_seen = True
    assert r2.result() == _oracle(config, params, p2, 3)
    assert eng.prefix_hits == 1 and eng.prefix_pages_shared == 1
    assert shared_seen, "the common first page was never mapped shared"
    assert eng.cow_splits == 0                 # aligned hit: no COW
    eng._prefix_pages.clear()
    eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_prefix_pages_sampled_reproducibility(lm):
    """Sampling through the shared-page path equals the full prefill
    path for the same seed (same logits, same fold indices)."""
    config, params = lm
    p = list(range(1, 17)) + [5, 11]
    eng = _paged(config, params, slots=2)
    a = eng.submit(p, max_new=5, temperature=0.9, seed=5)
    _drain(eng, 20)
    b = eng.submit(p, max_new=5, temperature=0.9, seed=5, prefix_len=16)
    _drain(eng, 20)
    c = eng.submit(p, max_new=5, temperature=0.9, seed=5, prefix_len=16)
    _drain(eng, 20)
    assert a.result() == b.result() == c.result()
    assert eng.prefix_hits >= 1


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_undersized_pool_gates_admission(lm):
    """A pool smaller than slots × max_len serves FIFO under page
    pressure: admissions wait for retirements, nobody deadlocks, and
    every stream is exact."""
    config, params = lm
    eng = _paged(config, params, slots=4, kv_pages=6)
    # each stream needs ceil((3+21)/8) = 3 pages; only two fit at once
    reqs = [eng.submit([5, 11, 17], max_new=21) for _ in range(3)]
    _drain(eng, 250)
    want = _oracle(config, params, [5, 11, 17], 21)
    for q in reqs:
        assert q.result() == want
    eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_submit_rejects_never_admittable(lm):
    """A request whose worst-case page need exceeds the WHOLE pool can
    never reserve, even with every prefix entry evicted — submit() must
    reject it up front instead of wedging the strict-FIFO head of line
    (and everything queued behind it) forever."""
    config, params = lm
    eng = _paged(config, params, slots=2, kv_pages=2)
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit([5, 11, 17], max_new=21)   # 3 pages > the pool's 2
    # a fitting request still serves — the queue never saw the reject
    r = eng.submit([5, 11, 17], max_new=8)    # 11 tokens: 2 pages
    _drain(eng, 30)
    assert r.result() == _oracle(config, params, [5, 11, 17], 8)
    eng._pool.check_idle()


# -- chunked prefill: burst admits never stall decode > one chunk -----------


def test_chunked_prefill_interleaves_with_decode(lm):
    """THE burst-TTFT contract: while a decode stream is live, a burst
    admit runs at most ONE prefill chunk between consecutive shared
    decode steps — asserted from the DecodeEngine spans on a fake
    clock, chunk/step span interleaving being the whole point of
    chunked prefill."""
    from kubeflow_tpu.obs import SpanCollector, Tracer

    config, params = lm
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    collector = SpanCollector()
    tracer = Tracer(collector=collector, clock=clock)
    eng = _paged(config, params, slots=4, prefill_chunk_tokens=4,
                 clock=clock, tracer=tracer)
    r0 = eng.submit([5, 11, 17], max_new=30)   # long-lived co-tenant
    for _ in range(5):
        eng.run_once(timeout=0.01)
    assert eng.active_count == 1
    # burst: 3 prompts × 2 chunks each land while r0 keeps decoding
    burst = [eng.submit([1 + i, 2, 3, 4, 5, 6, 7, 8], max_new=2)
             for i in range(3)]
    _drain(eng, 60)
    assert r0.result() == _oracle(config, params, [5, 11, 17], 30)
    for i, r in enumerate(burst):
        assert r.result() == _oracle(config, params,
                                     [1 + i, 2, 3, 4, 5, 6, 7, 8], 2)
    seq = sorted((s for s in collector.spans()
                  if s.name in ("engine.step", "engine.prefill_chunk")),
                 key=lambda s: s.start)
    names = [s.name for s in seq]
    assert names.count("engine.prefill_chunk") >= 6
    for a, b in zip(names, names[1:]):
        assert not (a == b == "engine.prefill_chunk"), (
            "two prefill chunks ran back-to-back while a decode stream "
            f"was live — decode stalled longer than one chunk: {names}")


# -- cache recovery: rebuild + replay instead of a permanent corpse ---------


def _inject_step_failure(eng):
    real = (eng._step_greedy, eng._step)
    state = {"fired": False}

    def boom(*a, **k):
        state["fired"] = True
        raise RuntimeError("injected donating-call failure")

    eng._step_greedy = boom
    eng._step = boom
    return real, state


@pytest.mark.parametrize("paged", [True, False])
def test_cache_invalidated_recovery_replays_slots(lm, paged):
    """A donating call that fails mid-decode consumes the engine cache.
    The engine must rebuild the cache and REPLAY the affected slots —
    the greedy stream completes bit-identically — rather than erroring
    every subsequent row-path call (the pre-recovery corpse mode)."""
    config, params = lm
    if paged:
        eng = _paged(config, params, slots=2)
    else:
        eng = DecodeEngine(config, params, slots=2, autostart=False)
    want = _oracle(config, params, [5, 11, 17], 8)
    r = eng.submit([5, 11, 17], max_new=8)
    for _ in range(4):
        eng.run_once(timeout=0.01)
    real, state = _inject_step_failure(eng)
    eng.run_once(timeout=0.01)          # fails mid-decode + recovers
    assert state["fired"] and eng.recoveries == 1 and not eng.closed
    # survived, but never silently: the snapshot and the exported
    # counter both say so (chip_smoke.py asserts they read 0)
    assert eng.snapshot()["recoveries"] == 1
    from kubeflow_tpu.serving import engine as engine_mod

    assert engine_mod._recoveries_c.get(model=eng.name) >= 1
    eng._step_greedy, eng._step = real
    _drain(eng, 30)
    assert r.result() == want           # replayed, stream intact
    # the engine still serves new requests (no corpse, no 500 well)
    r2 = eng.submit([3, 2, 9], max_new=4)
    _drain(eng, 20)
    assert r2.result() == _oracle(config, params, [3, 2, 9], 4)
    if paged:
        eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_retirement_failure_recovers(lm):
    """The donating disarm at slot retirement sits inside the recovery
    scope: a device failure while retiring a finished stream rebuilds
    the cache and replays the SURVIVING streams (the finished one
    already holds all its tokens) instead of tearing the engine down."""
    config, params = lm
    eng = _paged(config, params, slots=2)
    want_a = _oracle(config, params, [5, 11, 17], 2)
    want_b = _oracle(config, params, [3, 2, 9], 12)
    a = eng.submit([5, 11, 17], max_new=2)    # finishes first
    b = eng.submit([3, 2, 9], max_new=12)     # survives the failure
    real = eng._arm
    state = {"fired": False}

    def boom_on_disarm(cache, slot, start, table):
        # retirement is the only arm call with start == max_seq_len
        if int(start) == config.max_seq_len and not state["fired"]:
            state["fired"] = True
            raise RuntimeError("injected disarm failure")
        return real(cache, slot, start, table)

    eng._arm = boom_on_disarm
    _drain(eng, 40)
    assert state["fired"] and eng.recoveries == 1 and not eng.closed
    assert a.result() == want_a     # finished stream kept its tokens
    assert b.result() == want_b     # survivor replayed bit-identically
    eng._pool.check_idle()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_recovery_budget_exhaustion_closes(lm):
    """A persistently failing step exhausts the recovery budget and
    falls back to the close-and-evict protocol (retryable errors)."""
    from kubeflow_tpu.serving.engine import EngineClosed

    config, params = lm
    eng = DecodeEngine(config, params, slots=2, recoveries=1,
                       autostart=False)
    r = eng.submit([5, 11], max_new=4)
    eng.run_once(timeout=0.01)
    _inject_step_failure(eng)
    eng.run_once(timeout=0.01)          # recovery 1: replay queued
    with pytest.raises(RuntimeError):
        for _ in range(5):              # budget gone: raises through
            eng.run_once(timeout=0.01)
    # the loop-thread protocol (here: the caller) closes the engine
    eng.close()
    with pytest.raises(EngineClosed):
        r.result()


@pytest.mark.slow  # multi-second XLA compiles; tier-1 runs the fast twin paths
def test_paged_close_fails_waiting_and_prefilling(lm):
    from kubeflow_tpu.serving.engine import EngineClosed

    config, params = lm
    eng = _paged(config, params, slots=2, kv_pages=3)
    held = eng.submit([5, 11, 17], max_new=17)   # 3 pages: fills pool
    for _ in range(3):
        eng.run_once(timeout=0.01)
    waiting = eng.submit([3, 2], max_new=17)     # cannot place: waits
    eng.run_once(timeout=0.01)
    assert eng.pending_count == 1
    eng.close()
    for req in (held, waiting):
        with pytest.raises(EngineClosed):
            req.result()


# -- the Pallas kernels under a serving mesh ---------------------------------


def test_paged_kernel_and_fused_sampler_on_a_tp_mesh(lm):
    """XLA cannot partition a Mosaic kernel, so under a mesh the paged
    decode kernel and the fused sampler run inside a full-manual
    shard_map (parallel/mesh.py:shard_kernel — a multi-device jit on
    the TPU backend refuses a bare pallas_call outright; the CPU
    interpreter does not, which is why this path went unseen until the
    four-chip host). tp=2 divides both head counts: the kernel runs per
    head group. Greedy tokens must match the unsharded oracle; a
    sampled request must reproduce itself."""
    from conftest import shard_params
    from kubeflow_tpu.parallel import MeshConfig, create_mesh

    config, params = lm
    mesh = create_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    eng = _paged(config, shard_params(params, mesh), slots=2, mesh=mesh,
                 paged_attention_impl="kernel", sampler_impl="fused",
                 sampler_bound=0)
    r1 = eng.submit([5, 11, 17], max_new=8)
    r2 = eng.submit([3, 2, 9, 23, 41], max_new=6, temperature=0.8,
                    top_k=5, seed=3)
    _drain(eng)
    assert r1.result() == _oracle(config, params, [5, 11, 17], 8)
    sampled = r2.result()
    assert len(sampled) == 6 and all(0 <= t < 97 for t in sampled)
    r3 = eng.submit([3, 2, 9, 23, 41], max_new=6, temperature=0.8,
                    top_k=5, seed=3)
    _drain(eng)
    assert r3.result() == sampled
    assert eng.recoveries == 0 and not eng.closed
    eng.close()


# -- the round record on the paged path --------------------------------------


def test_paged_rounds_tile_and_steps_share_the_run(lm):
    """Paged rounds tile like dense ones (chunked prefill is admission
    time; a round of chunks alone has k 0), the per-step span the
    interleave test reads keeps its shape and gains the run's root as
    parent, and a chunked ``engine.admit`` names the round that finished
    it."""
    from kubeflow_tpu.obs import SpanCollector, Tracer
    from kubeflow_tpu.utils import DEFAULT_REGISTRY

    config, params = lm
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    collector = SpanCollector()
    eng = _paged(config, params, slots=4, prefill_chunk_tokens=4,
                 clock=clock, name="rounds-paged",
                 tracer=Tracer(collector=collector, clock=clock))
    r0 = eng.submit([5, 11, 17], max_new=12)
    for _ in range(3):
        eng.run_once(timeout=0.01)
    # two chunks of prompt while r0 decodes: admission spans two rounds
    r1 = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new=2)
    while eng.run_once(timeout=0.01):
        pass
    assert r0.result() == _oracle(config, params, [5, 11, 17], 12)
    assert r1.result() == _oracle(config, params,
                                  [1, 2, 3, 4, 5, 6, 7, 8], 2)
    eng.close()
    spans = collector.spans()
    (run,) = [s for s in spans if s.name == "engine.run"]
    rounds = [s for s in spans if s.name == "engine.round"]
    steps = [s for s in spans if s.name == "engine.step"]
    phases = ("wait_s", "admit_s", "step_s", "sync_s", "emit_s")
    assert len(rounds) == eng.rounds_total
    for r in rounds:
        assert sum(r.attrs[p] for p in phases) == r.end - r.start
        assert (r.trace_id, r.parent_id) == (run.trace_id, run.span_id)
    counter = DEFAULT_REGISTRY.counter("kftpu_engine_round_seconds_total")
    for p in phases:
        assert counter.get(model="rounds-paged", phase=p[:-2]) == \
            sum(r.attrs[p] for r in rounds)
    assert sum(r.attrs["admitted"] for r in rounds) == 2
    stepped = [r for r in rounds if r.attrs["k"]]
    assert len(stepped) == len(steps) > 0
    for r, st in zip(stepped, steps):
        assert (st.trace_id, st.parent_id) == (run.trace_id, run.span_id)
        # the step span runs from the round's launch to its read: read
        # inside the cycle the record is of, launched in that cycle or,
        # a round ahead, in the one before
        assert r.start < st.end < r.end
        assert (st.start < r.start) == r.attrs["ahead"]
        assert st.attrs["rows"] == r.attrs["rows"]
    by_round = {r.attrs["round"]: r for r in rounds}
    admits = [s for s in spans if s.name == "engine.admit"]
    assert len(admits) == 2
    for a in admits:
        r = by_round[a.attrs["round"]]
        assert r.start < a.end < r.end      # finished inside that round
    late = max(admits, key=lambda a: a.end)
    assert late.attrs["chunks"] == 2
    assert late.start < by_round[late.attrs["round"]].start
