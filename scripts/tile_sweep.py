"""Chip experiment: flash/paged tile sweep across seq×head shape classes.

The generalization of ``sync_sweep.py`` for ROADMAP items 1+3: measure
the candidate ``(block_q, block_k)`` grid per shape class ON CHIP — fwd
and fwd+bwd timed separately, skip-on-compile-failure — and emit a
table update for ``kubeflow_tpu/ops/tile_table.json`` plus a JSON
artifact, so the next TPU-attached round regenerates the table from
measurement the same way the bench adjudicates every other lever.
One JSON line per point for PERF.md.

    python scripts/tile_sweep.py                       # sweep, print lines
    python scripts/tile_sweep.py --out sweep.json      # + artifact
    python scripts/tile_sweep.py --update-table        # merge winners
    python scripts/tile_sweep.py --paged               # head-group sweep
    python scripts/tile_sweep.py --gmm                 # grouped matmul only
    python scripts/tile_sweep.py --step-attention      # decode step only
    python scripts/tile_sweep.py --validate            # no chip needed

``--validate`` is the preflight stage: strict table legality
(divisibility, VMEM estimate, dtype-lane legality — the same
``autotune.validate_entry`` the loader and TPU001 use) plus a CPU-tier
parity smoke that runs the three flash kernels and the paged kernel
with every committed tile config against the default-tile oracle in
the Pallas interpreter. Exits nonzero on an illegal entry or a parity
break, so a bad table edit fails before a bench round burns chip time.
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the r05-anchored shape grid: the three measured longcontext shapes
# (d1024/L8 ≙ head_dim 64 × 16 heads) plus the BERT-base bidirectional
# shape ROADMAP item 3 names
SWEEP_SHAPES = [
    dict(seq=8192, n_heads=16, head_dim=64, causal=True),
    dict(seq=16384, n_heads=16, head_dim=64, causal=True),
    dict(seq=32768, n_heads=16, head_dim=64, causal=True),
    dict(seq=512, n_heads=12, head_dim=64, causal=False),
]
EDGES = (256, 512, 1024, 2048)


def _sync(x):
    import jax

    jax.block_until_ready(x)


def _time_best(fn, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        _sync(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _bwd_keys(shape) -> tuple:
    """The backward kernels a shape's fwd+bwd timing ran: the fused
    one where its dQ row fits, else the dQ and dK/dV pair."""
    from kubeflow_tpu.ops import autotune

    if autotune.flash_bwd_fuses(shape["seq"], shape["head_dim"],
                                "bfloat16"):
        return ("flash_bwd_fused",)
    return ("flash_bwd_dq", "flash_bwd_dkv")


def sweep(args) -> dict:
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import autotune
    from kubeflow_tpu.ops.attention import flash_attention

    gen = autotune.backend_generation()
    dtype = jnp.bfloat16
    points, winners = [], {}
    seqs = [int(s) for s in args.seq] if args.seq else None
    for shape in SWEEP_SHAPES:
        if seqs and shape["seq"] not in seqs:
            continue
        S, H, D = shape["seq"], shape["n_heads"], shape["head_dim"]
        causal = shape["causal"]
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, S, H, D),
                                     dtype) for i in range(3))
        nbytes = autotune.DTYPE_BYTES[autotune.dtype_name(dtype)]
        best_fwd, best_bwd = (None, float("inf")), (None, float("inf"))
        for bq, bk in itertools.product(EDGES, EDGES):
            point = {"shape": shape, "block_q": bq, "block_k": bk,
                     "dtype": "bfloat16", "generation": gen}
            if S % min(bq, S) or S % min(bk, S):
                point["skip"] = "blocks do not divide seq"
                print(json.dumps(point), flush=True)
                continue
            vm = max(autotune.flash_vmem_bytes(kname, bq, bk, D, nbytes,
                                               seq=S)
                     for kname in ("flash_fwd", *_bwd_keys(shape)))
            if vm > autotune.VMEM_BUDGET_BYTES:
                point["skip"] = (f"VMEM estimate {vm} over budget "
                                 f"{autotune.VMEM_BUDGET_BYTES}")
                print(json.dumps(point), flush=True)
                continue

            def fwd(q=q, k=k, v=v, bq=bq, bk=bk):
                return flash_attention(q, k, v, causal, bq, bk)

            def fwdbwd(q=q, k=k, v=v, bq=bq, bk=bk):
                return jax.grad(lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal, bq, bk)
                    .astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)

            try:
                point["fwd_ms"] = round(_time_best(jax.jit(fwd)), 3)
                point["fwdbwd_ms"] = round(_time_best(jax.jit(fwdbwd)), 3)
            except Exception as e:  # noqa: BLE001 — skip-on-compile-failure
                point["skip"] = f"{type(e).__name__}: {e}"
                print(json.dumps(point), flush=True)
                continue
            print(json.dumps(point), flush=True)
            points.append(point)
            if point["fwd_ms"] < best_fwd[1]:
                best_fwd = ((bq, bk), point["fwd_ms"])
            if point["fwdbwd_ms"] < best_bwd[1]:
                best_bwd = ((bq, bk), point["fwdbwd_ms"])
        skey = f"s{S}/{'causal' if causal else 'bidir'}"
        if best_fwd[0]:
            winners[skey] = {"shape": shape, "fwd": best_fwd,
                             "fwdbwd": best_bwd}
    return {"generation": gen, "points": points, "winners": winners}


def sweep_paged(args) -> dict:
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import autotune
    from kubeflow_tpu.ops.paged_attention import paged_decode_attention

    gen = autotune.backend_generation()
    B, QH, KH, Dh, ps = 32, 16, 16, 64, 64
    n_log, P = 32, 256
    q = jax.random.normal(jax.random.PRNGKey(0), (B, QH, Dh), jnp.bfloat16)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, KH, Dh),
                           jnp.bfloat16)
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, KH, Dh),
                           jnp.bfloat16)
    pages = jax.random.randint(jax.random.PRNGKey(3), (B, n_log), 0, P)
    pos = jax.random.randint(jax.random.PRNGKey(4), (B,), ps,
                             n_log * ps - 1)
    points, best = [], (1, float("inf"))
    hb = 1
    while hb <= KH:
        point = {"paged": True, "head_block": hb, "generation": gen,
                 "page_size": ps, "n_kv_heads": KH}
        try:
            ms = _time_best(jax.jit(
                lambda hb=hb: paged_decode_attention(q, kp, vp, pages, pos,
                                                     head_block=hb)))
            point["step_ms"] = round(ms, 3)
            points.append(point)
            if ms < best[1]:
                best = (hb, ms)
        except Exception as e:  # noqa: BLE001 — skip-on-compile-failure
            point["skip"] = f"{type(e).__name__}: {e}"
        print(json.dumps(point), flush=True)
        hb *= 2
    return {"generation": gen, "points": points,
            "winner": {"head_block": best[0], "step_ms": best[1],
                       "n_kv_heads": KH, "page_size": ps}}


# the routed experts of the three hybrid-decoder cells: (k, n, held
# experts, [(rows, held pairs)] of a decode step and a prefill)
GMM_SHAPES = [
    (2688, 1856, 32, [(768, 192), (49152, 12288)]),     # Nemotron up
    (1856, 2688, 32, [(768, 192), (49152, 12288)]),     # Nemotron down
    (2560, 768, 128, [(256, 64), (16384, 4096)]),       # Ling gate / up
    (768, 2560, 128, [(256, 64), (16384, 4096)]),       # Ling down
    (7168, 2048, 16, [(128, 8), (8192, 512)]),          # DeepSeek-V3.2 up
    (2048, 7168, 16, [(128, 8), (8192, 512)]),          # DeepSeek-V3.2 down
]
GMM_REPS = 10


def sweep_gmm(args) -> dict:
    """The grouped matmul (``ops/gmm.py``) beside ``jax.lax.ragged_dot``
    and megablox's ``gmm`` on the same operands: at the tiles the rule
    gives (``autotune.resolve_gmm``; megablox at the same), at row tiles
    of 64 and 256, and at the two largest other legal expert pieces up
    to 8 MiB. The held pairs are spread at random over the experts, ten
    calls are chained in one program, the best of three counts. One JSON
    line a point; ``share`` is the hit experts' bytes over 819 GB/s (or
    the pairs' operations over 197 TFLOP/s) over the time."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops import autotune
    from kubeflow_tpu.ops.gmm import grouped_matmul, lies_column_major

    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm

    def call(impl, tiling, column_major):
        if impl == "ragged_dot":
            return lambda r, w, s: jax.lax.ragged_dot(
                r, w, s, preferred_element_type=jnp.float32)
        if impl == "gmm":
            return lambda r, w, s: grouped_matmul(r, w, s, tiling=tiling)
        if column_major:        # as ``ops/gmm.py`` reads such an expert
            return lambda r, w, s: megablox(
                r, jnp.swapaxes(w, 1, 2), s, jnp.float32, tiling,
                transpose_rhs=True)
        return lambda r, w, s: megablox(r, w, s, jnp.float32, tiling)

    def timed(fn, rows, experts, sizes):
        # operands are arguments: closed over, an expert tensor becomes a
        # constant of 300-470 MB in the program and a point takes 15 s
        # of compile (PR 36's second session lost a chip call to that)
        @jax.jit
        def chain(rows, experts, sizes):
            def body(r, _):
                y = fn(r, experts, sizes)
                return r + (y[:, :1] * 0).astype(r.dtype), None
            return jax.lax.scan(body, rows, None, length=GMM_REPS)[0]
        return _time_best(lambda: chain(rows, experts, sizes)) / GMM_REPS

    gen, points = autotune.backend_generation(), []
    for k, n, groups, regimes in GMM_SHAPES:
        experts = jax.random.normal(jax.random.PRNGKey(0), (groups, k, n),
                                    jnp.bfloat16) * k ** -0.5
        for m, pairs in regimes:
            rule = autotune.resolve_gmm(m=m, k=k, n=n,
                                        dtype=jnp.bfloat16).tiling
            pieces = sorted(
                ((tk, tn) for tk in autotune._gmm_axis_tiles(k)
                 for tn in autotune._gmm_axis_tiles(n)
                 if tk * tn * 2 <= 2 ** 23 and (tk, tn) != rule[1:]),
                key=lambda p: -p[0] * p[1])[:2]
            tried = ([("ragged_dot", None), ("megablox", rule),
                      ("gmm", rule)]
                     + [("gmm", (tm,) + rule[1:]) for tm in (64, 256)]
                     + [("gmm", (rule[0],) + piece) for piece in pieces])
            sizes = np.bincount(np.random.default_rng(0).integers(
                0, groups, pairs), minlength=groups).astype(np.int32)
            rows = jax.random.normal(jax.random.PRNGKey(1), (m, k),
                                     jnp.bfloat16)
            least = max(int((sizes > 0).sum()) * k * n * 2 / 819e9,
                        2 * pairs * k * n / 197e12) * 1e3
            for impl, tiling in tried:
                point = {"gmm": True, "impl": impl, "tiling": tiling,
                         "m": m, "k": k, "n": n, "groups": groups,
                         "pairs": pairs, "generation": gen}
                try:
                    ms = timed(call(impl, tiling, lies_column_major(k, n)),
                               rows, experts, jnp.asarray(sizes))
                    point.update(call_ms=round(ms, 4),
                                 share=round(100 * least / ms, 1))
                    points.append(point)
                except Exception as e:  # noqa: BLE001 — skip-on-failure
                    point["skip"] = f"{type(e).__name__}: {e}"[:200]
                print(json.dumps(point), flush=True)
    return {"generation": gen, "points": points}


# the one-token step attention of the served dense caches: (name, rows,
# max_seq_len, KV heads, query heads a KV head, head size, stacked
# layers, (median prompt, prompt range), (median output, output range))
# as the cells' traffic mixes draw them; a row stands at a uniform point
# of its output
STEP_SHAPES = [
    ("smollm2-1.7b", 8, 1024, 32, 1, 64, 24, (192, 32, 768), (96, 8, 256)),
    ("nemotron-3-nano", 128, 8192, 2, 16, 128, 2, (512, 64, 2048),
     (1024, 128, 4096)),
]
STEP_BLOCKS = (16, 32, 64, 128, 256, 512, 1024, 2048)
# the points tried: one row's K block of 128 KiB to 1 MiB (a copy is one
# DMA a row), and from a sixteenth of the rows to all of them a program
STEP_SWEEP_BYTES = (128 * 1024, 1024 * 1024)
STEP_REPS = 4


def _step_positions(rows, smax, prompt, output, live, seed=0):
    """Row positions as a cell's mix leaves them mid-run: a lognormal
    prompt and output (median and range as the mix gives them, sigma
    0.6), the row a uniform share through its output; the rows past
    ``live`` idle (``smax``)."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def draw(median, lo, hi):
        return np.clip(np.exp(rng.normal(np.log(median), 0.6, rows)), lo, hi)

    pos = draw(*prompt) + rng.uniform(0, 1, rows) * draw(*output)
    pos = np.minimum(pos.astype(np.int64), smax - 1)
    pos[live:] = smax
    return pos.astype(np.int32)


def sweep_step_attention(args) -> dict:
    """The step attention (``ops/step_attention.py``) at the served
    shapes, rows per program x positions per block, beside the XLA
    merged-lane products it replaces (``_merged_step_attention`` over
    the layer's slice), on the same operands: every row live, and
    (SmolLM2) two rows of eight as chat-steady keeps them. A program
    runs the call once a stacked layer, ``STEP_REPS`` times over, the
    layer a traced index as in the decoder's scan; the best of three
    counts. One JSON line a point: ``call_ms``, the live K and V bytes
    (``live_mb``), what the blocks round them to (``read_mb``), and
    ``share``, the live bytes over 819 GB/s over the time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import _merged_step_attention
    from kubeflow_tpu.ops import autotune
    from kubeflow_tpu.ops import step_attention as sa

    gen, points = autotune.backend_generation(), []
    for (name, B, S, KH, G, Dh, L, prompt, output) in STEP_SHAPES:
        lanes = KH * Dh
        kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
        k = jax.random.normal(kk, (L, B, S, lanes), jnp.bfloat16)
        v = jax.random.normal(kv, (L, B, S, lanes), jnp.bfloat16)
        q = jax.random.normal(kq, (B, KH, G, Dh), jnp.bfloat16)
        rule = sa.tiling(B, S, KH, Dh, KH * G, jnp.bfloat16)
        regimes = [("full", B)] + ([("chat", 2)] if B <= 8 else [])

        def timed(fn, q, k, v, pos):
            # operands are arguments (closed over, the stack would be a
            # constant of the program)
            @jax.jit
            def chain(q, k, v, pos):
                def body(q, i):
                    o = fn(q, k, v, i % L, pos)
                    return q + (o * 0).astype(q.dtype), None
                return jax.lax.scan(body, q, jnp.arange(L * STEP_REPS))[0]
            return _time_best(lambda: chain(q, k, v, pos)) / (L * STEP_REPS)

        def xla(q, k, v, layer, pos):
            mask = jnp.arange(S)[None, :] <= pos[:, None]
            return _merged_step_attention(q, k[layer], v[layer], mask)

        for regime, live in regimes:
            pos_np = _step_positions(B, S, prompt, output, live)
            pos = jnp.asarray(pos_np)
            live_b = int(np.sum(np.where(pos_np < S, pos_np + 1, 0)))
            per_pos = 2 * lanes * 2          # K and V, bf16
            base = {"step_attention": True, "shape": name, "rows": B,
                    "max_seq_len": S, "lanes": lanes, "regime": regime,
                    "live_rows": live, "rule": list(rule),
                    "live_mb": round(live_b * per_pos / 1e6, 3),
                    "generation": gen}
            least = live_b * per_pos / 819e9 * 1e3
            lo, hi = STEP_SWEEP_BYTES
            tiles = [(br, bl) for br in range(max(1, B // 16), B + 1)
                     for bl in STEP_BLOCKS
                     if B % br == 0 and S % bl == 0 and (br, bl) != rule
                     and (br & (br - 1)) == 0 and lo <= bl * lanes * 2 <= hi]
            tried = [("xla", None)] + [("kernel", t) for t in tiles + [rule]]
            for impl, tiles in tried:
                point = dict(base, impl=impl, tiling=tiles)
                if impl == "kernel":
                    br, bl = tiles
                    vm = autotune.step_vmem_bytes(br, bl, lanes, KH * G, Dh, 2)
                    if vm > 100 * 2 ** 20:
                        continue
                    point["read_mb"] = round(int(np.sum(
                        sa.live_blocks(pos_np, S, bl))) * bl * per_pos / 1e6,
                        3)
                    fn = (lambda q, k, v, i, p, br=br, bl=bl:
                          sa.step_attention(q, k, v, i, p, block_rows=br,
                                            block_len=bl))
                else:
                    point["read_mb"] = round(B * S * per_pos / 1e6, 3)
                    fn = xla
                try:
                    ms = timed(fn, q, k, v, pos)
                    point.update(call_ms=round(ms, 4),
                                 share=round(100 * least / ms, 1))
                    points.append(point)
                except Exception as e:  # noqa: BLE001 — skip-on-failure
                    point["skip"] = f"{type(e).__name__}: {e}"[:300]
                print(json.dumps(point), flush=True)
    return {"generation": gen, "points": points}


def update_table(result: dict, paged_result: dict, path: str) -> None:
    """Merge sweep winners into the committed table: one entry per
    (kernel key, shape class), fwd winner → flash_fwd, fwd+bwd winner →
    the two backward keys (timed jointly by construction)."""
    from kubeflow_tpu.ops import autotune

    table = autotune.load_table(path) if os.path.exists(path) else (
        autotune.TileTable([], [], path=path))
    gen = (result or paged_result)["generation"]

    def put(entry):
        errs = autotune.validate_entry(entry)
        if errs:
            print(f"tile_sweep: refusing illegal winner "
                  f"{autotune.entry_key(entry)}: {errs}", file=sys.stderr)
            return
        table.entries = [e for e in table.entries
                         if not all(e.get(f) == entry.get(f)
                                    for f in ("kernel", "seq_bucket",
                                              "dtype", "causal",
                                              "generation", "head_dim"))]
        table.entries.append(entry)

    for w in (result or {}).get("winners", {}).values():
        shape = w["shape"]
        base = dict(seq_bucket=autotune.seq_bucket(shape["seq"]),
                    head_dim=shape["head_dim"], n_heads=shape["n_heads"],
                    n_kv_heads=None, dtype="bfloat16",
                    causal=shape["causal"], generation=gen)
        (bq, bk), ms = w["fwd"]
        put(dict(kernel="flash_fwd", block_q=bq, block_k=bk,
                 provenance=f"tile_sweep {gen}: fwd {ms} ms", **base))
        (bq, bk), ms = w["fwdbwd"]
        for kname in _bwd_keys(shape):
            put(dict(kernel=kname, block_q=bq, block_k=bk,
                     provenance=f"tile_sweep {gen}: fwd+bwd {ms} ms",
                     **base))
    if paged_result:
        w = paged_result["winner"]
        put(dict(kernel="paged_attn", seq_bucket=None, head_dim=None,
                 n_heads=None, n_kv_heads=w["n_kv_heads"],
                 page_size=w["page_size"], dtype="bfloat16", causal=None,
                 generation=gen, head_block=w["head_block"],
                 provenance=f"tile_sweep {gen}: decode step "
                            f"{round(w['step_ms'], 3)} ms"))
    autotune.save_table(table, path)
    print(f"tile_sweep: wrote {len(table.entries)} entries to {path}")


# ---------------------------------------------------------------------------
# --validate: table legality + CPU-tier parity smoke (preflight stage)
# ---------------------------------------------------------------------------


def _flash_parity(entry, autotune) -> str:
    """Run the three flash kernels with this entry's tiles on a small
    shape against the default-tile oracle; '' = pass. Small shapes clamp
    every tile to the sequence, so configs whose effective tiles match
    the oracle's must be bit-consistent; larger tiles only reorder the
    online softmax, so the remainder gates at tight tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.attention import flash_attention

    causal = bool(entry.get("causal", True))
    S, H, D = 64, 4, 16
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, S, H, D),
                                 jnp.float32) for i in range(3))
    bq = autotune.fit_block(S, entry["block_q"])
    bk = autotune.fit_block(S, entry["block_k"])
    oracle_b = autotune.fit_block(S, 16)
    try:
        out = flash_attention(q, k, v, causal, bq, bk)
        ref = flash_attention(q, k, v, causal, oracle_b, oracle_b)
        exact = (bq, bk) == (oracle_b, oracle_b)
        if exact and not np.array_equal(np.asarray(out), np.asarray(ref)):
            return "fwd not bit-consistent with the default-tile oracle"
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        g_out = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal, bq, bk) ** 2), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal, oracle_b, oracle_b) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)
    except Exception as e:  # noqa: BLE001 — a parity break IS the verdict
        return f"{type(e).__name__}: {e}"
    return ""


def _paged_parity(entry, autotune) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.paged_attention import paged_decode_attention

    B, QH, KH, Dh, ps, P, n_log = 2, 8, 4, 16, 8, 6, 3
    hb = int(entry.get("head_block", 1))
    if KH % hb:
        hb = 1  # the resolve-time degradation; smoke what would run
    q = jax.random.normal(jax.random.PRNGKey(0), (B, QH, Dh), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, KH, Dh),
                           jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, KH, Dh),
                           jnp.float32)
    pages = jnp.array([[0, 1, 2], [3, 4, P]], jnp.int32)
    pos = jnp.array([20, 11], jnp.int32)
    try:
        out = paged_decode_attention(q, kp, vp, pages, pos, head_block=hb)
        ref = paged_decode_attention(q, kp, vp, pages, pos, head_block=1)
        if hb == 1 and not np.array_equal(np.asarray(out), np.asarray(ref)):
            return "head_block=1 not bit-consistent with itself"
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
    except Exception as e:  # noqa: BLE001
        return f"{type(e).__name__}: {e}"
    return ""


def validate(table_path: str) -> int:
    from kubeflow_tpu.ops import autotune

    try:
        table = autotune.load_table(table_path, strict=True)
    except (ValueError, FileNotFoundError) as e:
        print(f"tile_sweep --validate: ILLEGAL table: {e}", file=sys.stderr)
        return 1
    failures = []
    for entry in table.entries:
        check = (_paged_parity if entry["kernel"] == "paged_attn"
                 else _flash_parity)
        err = check(entry, autotune)
        status = err or "ok"
        print(f"  {autotune.entry_key(entry)}: {status}")
        if err:
            failures.append((autotune.entry_key(entry), err))
    # the fallback path must stay parity-clean too: resolve a shape no
    # entry covers and run what resolution returns
    import jax.numpy as jnp

    with autotune.table_override(table):
        cfg = autotune.resolve_flash(
            "flash_fwd", seq=64, head_dim=16, n_heads=4, n_kv_heads=4,
            dtype=jnp.float32, causal=True)
    if cfg.source != "fallback":
        # a table edit covering the probe shape would silently stop
        # exercising the fallback — that is a gate failure, not a note
        print(f"  fallback probe resolved from {cfg.source}, expected "
              "fallback", file=sys.stderr)
        failures.append(("fallback-probe",
                         f"resolved from {cfg.source}"))
    err = _flash_parity({"block_q": cfg.block_q, "block_k": cfg.block_k,
                         "causal": True}, autotune)
    print(f"  fallback({cfg.block_q},{cfg.block_k}): {err or 'ok'}")
    if err:
        failures.append(("fallback", err))
    if failures:
        print(f"tile_sweep --validate: {len(failures)} failure(s)",
              file=sys.stderr)
        return 1
    print(f"tile_sweep --validate: ok ({len(table.entries)} entries, "
          f"{len(table.rejected)} rejected)")
    return 0


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--validate", action="store_true",
                   help="table legality + CPU parity smoke; no chip")
    p.add_argument("--table", default=None,
                   help="tile table path (default: the committed one)")
    p.add_argument("--seq", nargs="*", default=None,
                   help="restrict the sweep to these seq lens")
    p.add_argument("--paged", action="store_true",
                   help="also sweep the paged kernel's head_block")
    p.add_argument("--gmm", action="store_true",
                   help="sweep the grouped matmul's tiles beside "
                        "ragged_dot and megablox, and nothing else (it "
                        "has no table rows: ops/autotune.py's rule)")
    p.add_argument("--step-attention", action="store_true",
                   help="sweep the step attention's rows per program x "
                        "positions per block at the served shapes beside "
                        "the XLA products, and nothing else (no table "
                        "rows: ops/autotune.py's rule)")
    p.add_argument("--out", default=None, help="write the JSON artifact")
    p.add_argument("--update-table", action="store_true",
                   help="merge measured winners into the table")
    args = p.parse_args()

    from kubeflow_tpu.ops import autotune

    table_path = args.table or autotune.DEFAULT_TABLE_PATH
    if args.validate:
        sys.exit(validate(table_path))

    if args.gmm or args.step_attention:
        artifact = ({"gmm": sweep_gmm(args)} if args.gmm else
                    {"step_attention": sweep_step_attention(args)})
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                json.dump(artifact, f, indent=2)
        return

    # --seq restricts the flash grid (an empty intersection skips it —
    # the "paged only" spelling is --paged --seq 0)
    result = sweep(args)
    paged_result = sweep_paged(args) if args.paged else None
    artifact = {"flash": result, "paged": paged_result}
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(artifact, f, indent=2)
        print(f"tile_sweep: artifact written to {args.out}")
    if args.update_table:
        update_table(result, paged_result, table_path)


if __name__ == "__main__":
    main()
