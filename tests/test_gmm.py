"""The grouped matmul of ``ops/gmm.py`` (CPU: the kernel runs in the
Pallas interpreter) against a plain per-expert product in float32, its
tiles' resolution, and ``RoutedMlp`` on top of it against a per-token
loop over the chosen experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import hybrid
from kubeflow_tpu.ops import autotune, gmm


def _operands(m, k, n, e, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((m, k)), dtype)
    experts = jnp.asarray(rng.standard_normal((e, k, n)) * k ** -0.5, dtype)
    return rows, experts


def _per_expert(rows, experts, sizes):
    """Each group's rows times its expert, in float32; zero elsewhere."""
    out = np.zeros((rows.shape[0], experts.shape[2]), np.float32)
    start = 0
    for g, size in enumerate(sizes):
        out[start:start + size] = (
            np.asarray(rows[start:start + size], np.float32)
            @ np.asarray(experts[g], np.float32))
        start += size
    return out


# (M, K, N, sizes, tiling): what each case is for, in its id
CASES = {
    "eight-rows": (8, 64, 32, [3, 0, 2, 1], None),
    "empty-experts-between": (64, 128, 128, [10, 0, 0, 30, 0, 5], None),
    "all-on-one-expert": (96, 128, 128, [0, 0, 96, 0], (32, 128, 128)),
    "nothing-routed-here": (48, 128, 128, [0, 0, 0], None),
    "trailing-rows-uncovered": (128, 128, 256, [7, 20, 1], (16, 128, 128)),
    "groups-share-a-row-tile": (64, 128, 128, [5, 9, 3, 30, 2],
                                (32, 128, 128)),
    "rows-no-multiple-of-the-tile": (300, 128, 128, [100, 150, 7],
                                     (64, 128, 128)),
    # the shape class of 1856 and 768 beside 2688 and 2560: one axis a
    # whole number of 128 lanes, the other not
    "narrow-axis-no-multiple-of-128": (96, 256, 232, [40, 0, 50, 6], None),
    "contraction-no-multiple-of-128": (96, 232, 256, [40, 0, 50, 6], None),
    "neither-axis-a-multiple": (40, 72, 40, [11, 29], None),
    # both tile regimes: an expert in one piece, and cut along K and N
    "expert-in-one-piece": (64, 384, 256, [20, 44], (64, 384, 256)),
    "expert-cut-along-k": (64, 384, 256, [20, 44], (32, 128, 256)),
    "expert-cut-along-n": (64, 384, 256, [20, 44], (32, 384, 128)),
    "transposed-cut-along-k": (64, 384, 232, [20, 0, 44], (16, 128, 232)),
    "prefill-thousands-of-rows": (4096, 128, 128,
                                  [700, 0, 1300, 5, 900, 0, 0, 64],
                                  (256, 128, 128)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matmul_matches_a_per_expert_dot(case):
    """Rows past ``sum(sizes)`` belong to no expert: their operands are
    poisoned with NaN, and no covered row shows it. What comes back in
    those rows is not specified (the interpreter leaves NaN where the
    kernel wrote nothing): ``RoutedMlp`` masks them, tested below."""
    m, k, n, sizes, tiling = CASES[case]
    rows, experts = _operands(m, k, n, len(sizes))
    covered = sum(sizes)
    want = _per_expert(rows, experts, sizes)
    poisoned = rows.at[covered:].set(jnp.nan)
    got = jax.jit(lambda a, b, s: gmm.grouped_matmul(a, b, s, tiling=tiling))(
        poisoned, experts, jnp.asarray(sizes, jnp.int32))
    assert got.shape == (m, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(got[:covered], want[:covered], atol=1e-4,
                               rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_experts_are_cast_to_the_rows_dtype_and_the_result_is_float32(dtype):
    """float32 parameters under bf16 activations (the tests' models) are
    rounded to the rows' dtype, as ``RoutedMlp`` rounded them before."""
    rows, experts = _operands(32, 64, 48, 3, dtype=jnp.float32)
    sizes = [10, 12, 10]
    got = gmm.grouped_matmul(rows.astype(dtype), experts,
                             jnp.asarray(sizes, jnp.int32))
    want = _per_expert(rows.astype(dtype), experts.astype(dtype), sizes)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)  # all covered


@pytest.mark.parametrize("sizes, tm", [([3, 0, 70, 1, 0, 26], 32),
                                       ([0, 0, 0], 16), ([128], 16),
                                       ([1] * 9, 8)])
def test_schedule_visits_each_touched_row_tile_of_each_group_once(sizes, tm):
    m_tiles = -(-max(sum(sizes), 1) // tm) + 1
    group, tile, offsets, n_items = gmm.schedule(
        jnp.asarray(sizes, jnp.int32), m_tiles, tm)
    n_items = int(n_items)
    assert len(group) == len(tile) == m_tiles + len(sizes) - 1
    want, start = [], 0
    for g, size in enumerate(sizes):
        if size:
            want += [(g, t) for t in range(start // tm,
                                           (start + size - 1) // tm + 1)]
        start += size
    items = list(zip(np.asarray(group).tolist(), np.asarray(tile).tolist()))
    assert items[:n_items] == want
    # past the last item its indices repeat: nothing new is fetched
    assert all(item == items[max(n_items - 1, 0)]
               for item in items[n_items:])
    assert np.asarray(offsets).tolist() == [0] + np.cumsum(sizes).tolist()


def test_layout_rule_reads_a_column_major_tensor_transposed():
    """2688 x 1856 lies column-major on the chip (the compile for a
    described v5e holds that, ``tests/test_compile_tpu.py``); the other
    served shapes row-major."""
    assert gmm.lies_column_major(2688, 1856)
    for k, n in ((1856, 2688), (2560, 768), (768, 2560), (7168, 2048),
                 (2048, 7168), (64, 32), (72, 40)):
        assert not gmm.lies_column_major(k, n)


# -- the tiles ---------------------------------------------------------------------

SERVED = [  # (m, k, n): the decode step's calls of the three cells
    (768, 2688, 1856), (768, 1856, 2688), (256, 2560, 768),
    (256, 768, 2560), (128, 7168, 2048), (128, 2048, 7168),
    # and a prefill of each: 4 x 2048 x 6, 2 x 1024 x 8, a chunk of 1024 x 8
    (49152, 2688, 1856), (49152, 1856, 2688), (16384, 2560, 768),
    (16384, 768, 2560), (8192, 7168, 2048), (8192, 2048, 7168),
]


@pytest.mark.parametrize("m, k, n", SERVED)
def test_served_shapes_resolve_to_legal_tiles(m, k, n):
    """The rule's tiles divide the expert, a piece is at most
    ``GMM_TILE_BYTES`` and large, and never ``ragged_dot``'s 128 x 128;
    the table has no say (it holds no row of this kernel)."""
    with autotune.record_resolutions() as seen:
        cfg = autotune.resolve_gmm(m=m, k=k, n=n, dtype=jnp.bfloat16)
    tm, tk, tn = cfg.tiling
    assert seen[0]["tiling"] == [tm, tk, tn] and seen[0]["kernel"] == "gmm"
    assert cfg.source == "fallback"
    assert tk * tn * 2 <= autotune.GMM_TILE_BYTES
    assert tm == autotune.GMM_ROW_TILE
    assert tk in autotune._gmm_axis_tiles(k)
    assert tn in autotune._gmm_axis_tiles(n)
    assert tk * tn >= 512 * 1024          # few large pieces
    # Mosaic's default scope would do; the kernel asks for what it needs
    assert autotune.gmm_vmem_bytes(tm, tk, tn, 2) <= 16 * 2 ** 20


def test_the_table_holds_no_row_of_the_grouped_matmul():
    """The rows PR 36 first wrote equalled the rule at every served
    shape: a second path that chose nothing (PERF.md, PR 36)."""
    assert "gmm" not in autotune.KERNELS
    assert not [e for e in autotune.load_table(strict=True).entries
                if e["kernel"] == "gmm"]


def test_resolution_follows_the_rows_the_dtype_and_an_override():
    def tiles(m, k=256, dtype=jnp.bfloat16, **kw):
        return autotune.resolve_gmm(m=m, k=k, n=384, dtype=dtype, **kw)
    assert tiles(4096).tiling == (autotune.GMM_ROW_TILE, 256, 384)
    assert tiles(20).tiling == (32, 256, 384)   # never past the rows
    assert tiles(20, dtype=jnp.float32).tiling == (24, 256, 384)
    assert tiles(4096, k=512).tiling == (autotune.GMM_ROW_TILE, 512, 384)
    assert tiles(128, tiling=(8, 128, 128)).source == "override"
    assert tiles(128, tiling=(8, 128, 128)).tiling == (8, 128, 128)
    # an expert too large for one piece is cut along the axis that
    # keeps the pieces' rows whole
    assert autotune._fallback_gmm(7168, 2048, 2) == (128, 1024, 2048)
    assert autotune._fallback_gmm(1856, 2688, 2) == (128, 1856, 896)
    assert autotune._fallback_gmm(2688, 1856, 2) == (128, 896, 1856)
    assert autotune._fallback_gmm(2560, 768, 2) == (128, 2560, 768)
    assert autotune._fallback_gmm(64, 40, 4) == (128, 64, 40)


# -- beside megablox, and the chip sweep's harness ----------------------------------

def _megablox(monkeypatch=None):
    import functools
    import importlib

    module = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    interpreted = functools.partial(module.gmm, interpret=True)
    if monkeypatch is not None:         # what the sweep will import
        monkeypatch.setattr(module, "gmm", interpreted)
    return interpreted


@pytest.mark.parametrize("k, n, tiling", [
    (256, 72, (32, 128, 72)),       # column-major on the chip: read as (E, N, K)
    (72, 256, (32, 72, 128)),       # an axis that is no multiple of 128, whole
    (256, 384, (64, 256, 384)),     # an expert in one piece: no accumulator
])
def test_the_kernel_agrees_with_megablox_on_the_covered_rows(k, n, tiling):
    """The kernel the chip sweep holds this one against (PERF.md, PR 36),
    in the interpreter at the same tiles: bit for bit where the order of
    the sums is the same, which it is (one piece, or ``k`` in order)."""
    sizes = [9, 0, 70, 1, 0, 26]
    rows, experts = _operands(128, k, n, len(sizes), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    ours = gmm.grouped_matmul(rows, experts, group_sizes, tiling=tiling)
    if gmm.lies_column_major(k, n):
        theirs = _megablox()(rows, jnp.swapaxes(experts, 1, 2), group_sizes,
                             jnp.float32, tiling, transpose_rhs=True)
    else:
        theirs = _megablox()(rows, experts, group_sizes, jnp.float32, tiling)
    covered = sum(sizes)
    np.testing.assert_array_equal(np.asarray(ours)[:covered],
                                  np.asarray(theirs)[:covered])


@pytest.mark.parametrize("k, n", [(256, 72), (72, 256)],
                         ids=["column-major", "row-major"])
def test_the_sweep_times_all_three_arms_with_its_operands_as_arguments(
        monkeypatch, k, n):
    """``scripts/tile_sweep.py --gmm`` at a toy size: the rule's tiles
    for this kernel and megablox, ``ragged_dot``, two more row tiles and
    the other legal pieces (two at most), no point skipped; and the timed program takes its
    operands as arguments (closed over, an expert tensor of 300-470 MB
    became a constant of every point's program: a chip call of PR 36
    spent 25 minutes on 29 points)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location("_tile_sweep", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "tile_sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    _megablox(monkeypatch)
    monkeypatch.setattr(sweep, "GMM_SHAPES", [(k, n, 4, [(256, 40)])])
    monkeypatch.setattr(sweep, "GMM_REPS", 2)
    operands = []

    def time_once(fn):
        programs = [e for e in jax.make_jaxpr(fn)().eqns
                    if "jit" in e.primitive.name]
        operands.append([v.aval.shape for v in programs[0].invars])
        fn()
        return 1.0

    monkeypatch.setattr(sweep, "_time_best", time_once)
    points = sweep.sweep_gmm(None)["points"]
    assert [p["impl"] for p in points] == (
        ["ragged_dot", "megablox"] + ["gmm"] * 4)   # one other legal piece
    assert points[1]["tiling"] == points[2]["tiling"] == (
        autotune.resolve_gmm(m=256, k=k, n=n, dtype=jnp.bfloat16).tiling)
    assert len({tuple(p["tiling"]) for p in points[2:]}) == 4
    # rows, experts and sizes reach the timed program as its arguments
    assert operands == [[(256, k), (4, k, n), (4,)]] * len(points)


# -- RoutedMlp on top of it --------------------------------------------------------

def _by_token(c, params, x):
    """What ``RoutedMlp`` computes, token by token in float32: the held
    experts among a token's chosen ones, weighted, plus the shared
    expert. The router is the module's own (``hybrid.route``)."""
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    flat = np.asarray(x, np.float32).reshape(-1, x.shape[-1])
    idx, wts = hybrid.route(jnp.asarray(flat @ p["router"]),
                            jnp.asarray(p["router_bias"]), c)
    gated = c.expert_act == "swiglu"

    def expert(v, up, down, gate=None):
        if gated:
            h = np.asarray(jax.nn.silu(jnp.asarray(v @ gate))) * (v @ up)
        else:
            h = np.square(np.maximum(v @ up, 0.0))
        return h @ down

    lo, n = c.held
    out = np.zeros_like(flat)
    for t, v in enumerate(flat):
        for e, w in zip(np.asarray(idx[t]), np.asarray(wts[t])):
            if lo <= e < lo + n:
                j = e - lo
                out[t] += w * expert(v, p["up_proj"][j], p["down_proj"][j],
                                     p["gate_proj"][j] if gated else None)
        out[t] += expert(v, p["shared_up"], p["shared_down"],
                         p.get("shared_gate"))
    return out.reshape(x.shape)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("held", [None, (4, 8)], ids=["all-held", "a-share"])
def test_routed_mlp_matches_a_loop_over_tokens(act, held):
    """Both expert forms, with every expert held and with a share of
    them (pairs of the experts held elsewhere sort last and belong to no
    group), a width that is no multiple of 128, pad tokens routed
    nowhere; float32 throughout, so the loop is the same arithmetic."""
    c = hybrid.HybridConfig(
        d_model=64, n_experts=16, experts_per_token=3, n_group=4,
        topk_group=2, d_expert=40, d_shared=24, expert_act=act,
        experts_held=held, dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 7, 64)),
                    jnp.float32)
    mlp = hybrid.RoutedMlp(c)
    params = mlp.init(jax.random.key(1), x)["params"]
    assert params["up_proj"].shape == (c.held[1], 64, 40)
    live = jnp.arange(7)[None, :] < jnp.asarray([7, 4])[:, None]
    got, hit, pairs = jax.jit(lambda p, x: mlp.apply({"params": p}, x, live))(
        params, x)
    want = _by_token(c, params, x)
    np.testing.assert_allclose(np.asarray(got)[np.asarray(live)],
                               want[np.asarray(live)], atol=2e-5)
    # the products' rows past the held pairs (pad tokens, experts held
    # elsewhere) are memory nobody wrote, NaN under the interpreter:
    # none of it reaches y, a pad token's row included
    assert np.all(np.isfinite(np.asarray(got)))
    idx, _ = hybrid.route(
        jnp.dot(x.reshape(-1, 64), params["router"],
                precision=jax.lax.Precision.HIGHEST),
        params["router_bias"], c)
    lo, n = c.held
    mine = np.asarray(idx)[np.asarray(live).reshape(-1)]
    mine = mine[(mine >= lo) & (mine < lo + n)]
    assert int(pairs) == mine.size and int(hit) == len(np.unique(mine))


def test_the_package_calls_no_ragged_dot():
    """One grouped-matmul function under ``ops/``; nothing in the
    package hands a grouped product to the compiler."""
    import pathlib

    import kubeflow_tpu

    root = pathlib.Path(kubeflow_tpu.__file__).parent
    callers = [str(p.relative_to(root)) for p in root.rglob("*.py")
               if "ragged_dot(" in p.read_text()]
    assert callers == []
