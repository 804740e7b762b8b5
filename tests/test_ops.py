"""Attention kernels, collectives, and MoE dispatch (kubeflow_tpu.ops).

Numerics tier: every op is checked against the dense reference on the
8-device virtual CPU mesh (conftest), including gradients — the collective
paths (ring attention, shard_map wrappers) run the same code that lowers to
ICI collectives on real slices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kubeflow_tpu.ops import (
    all_gather,
    all_reduce,
    all_to_all,
    bench_collective,
    blockwise_attention,
    capacity_dispatch,
    capacity_moe,
    expert_capacity,
    flash_attention,
    ppermute_shift,
    reference_attention,
    reduce_scatter,
    ring_attention_sharded,
)


def qkv(B=2, S=64, H=4, D=16, dtype=jnp.float32):
    return tuple(
        jax.random.normal(jax.random.key(i), (B, S, H, D), dtype)
        for i in range(3)
    )


@pytest.fixture(scope="module")
def mesh_dp_tp():
    devs = np.array(jax.devices()[:8]).reshape(2, 1, 4)
    return Mesh(devs, ("dp", "pp", "tp"))


@pytest.fixture(scope="module")
def mesh_dp():
    devs = np.array(jax.devices()[:8]).reshape(8, 1, 1)
    return Mesh(devs, ("dp", "pp", "tp"))


class TestBlockwise:
    def test_matches_reference(self):
        q, k, v = qkv()
        ref = reference_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_k=16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_block_not_dividing_seq(self):
        q, k, v = qkv(S=60)
        ref = reference_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_k=16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_non_causal(self):
        q, k, v = qkv()
        ref = reference_attention(q, k, v, causal=False)
        out = blockwise_attention(q, k, v, causal=False, block_k=16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_non_causal_padded_blocks(self):
        # regression: pad positions must stay masked without causality
        q, k, v = qkv(S=60)
        ref = reference_attention(q, k, v, causal=False)
        out = blockwise_attention(q, k, v, causal=False, block_k=16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_gradients_match(self):
        q, k, v = qkv()
        g_ref = jax.grad(lambda q: jnp.sum(reference_attention(q, k, v) ** 2))(q)
        g_blk = jax.grad(
            lambda q: jnp.sum(blockwise_attention(q, k, v, block_k=16) ** 2)
        )(q)
        np.testing.assert_allclose(g_blk, g_ref, atol=1e-4)


class TestFlash:
    def test_matches_reference(self):
        q, k, v = qkv()
        ref = reference_attention(q, k, v)
        out = flash_attention(q, k, v, True, 16, 16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_gradients_match(self):
        q, k, v = qkv()
        g_ref = jax.grad(lambda q: jnp.sum(reference_attention(q, k, v) ** 2))(q)
        g_fl = jax.grad(
            lambda q: jnp.sum(flash_attention(q, k, v, True, 16, 16) ** 2)
        )(q)
        np.testing.assert_allclose(g_fl, g_ref, atol=1e-4)

    def test_rejects_ragged_blocks(self):
        q, k, v = qkv(S=60)
        with pytest.raises(ValueError, match="must divide"):
            flash_attention(q, k, v, True, 16, 16)

    def test_all_gradients_match_reference(self):
        """The Pallas backward kernels (dQ + dK/dV from saved LSE) must
        agree with autodiff through reference attention for every input,
        causal and not, including uneven block_q != block_k."""
        q, k, v = qkv()
        for causal in (True, False):
            for bq, bk in ((16, 16), (32, 16), (16, 32)):
                def loss(fn):
                    return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

                refs = jax.grad(
                    loss(lambda q, k, v: reference_attention(
                        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
                fls = jax.grad(
                    loss(lambda q, k, v: flash_attention(
                        q, k, v, causal, bq, bk)), argnums=(0, 1, 2))(q, k, v)
                for g_ref, g_fl, name in zip(refs, fls, "qkv"):
                    np.testing.assert_allclose(
                        g_fl, g_ref, atol=1e-4,
                        err_msg=f"d{name} causal={causal} bq={bq} bk={bk}")

    def test_forward_matches_reference_all_block_shapes(self):
        """Forward parity across causal×block-shape combos, including
        ratios where the causal clamp maps and live gates diverge most
        (block_q = 4×block_k and the reverse)."""
        q, k, v = qkv(S=64)
        for causal in (True, False):
            ref = reference_attention(q, k, v, causal=causal)
            for bq, bk in ((16, 16), (64, 16), (16, 64), (32, 8),
                           (8, 32)):
                out = flash_attention(q, k, v, causal, bq, bk)
                np.testing.assert_allclose(
                    out, ref, atol=1e-5,
                    err_msg=f"causal={causal} bq={bq} bk={bk}")

    def test_gradients_match_bf16(self):
        q, k, v = (x.astype(jnp.bfloat16) for x in qkv())
        g_ref = jax.grad(lambda k: jnp.sum(
            reference_attention(q, k, v) ** 2))(k)
        g_fl = jax.grad(lambda k: jnp.sum(
            flash_attention(q, k, v, True, 16, 16) ** 2))(k)
        np.testing.assert_allclose(np.asarray(g_fl, np.float32),
                                   np.asarray(g_ref, np.float32),
                                   atol=0.15, rtol=0.1)


# name → what differs from a causal float32 (2, 64, 4, 16) problem on
# 16 × 16 tiles
FUSED_BACKWARD_CASES = {
    "causal": {},
    "bidirectional": dict(causal=False),
    "kv_len": dict(causal=False, kv_len=(40, 64)),
    "kv_len_causal": dict(kv_len=(23, 57)),
    "gqa_15_5_heads": dict(H=15, KH=5),
    "block_q_wider": dict(bq=32, bk=16),
    "block_k_wider": dict(bq=16, bk=32),
    "block_q_4x": dict(bq=64, bk=16),
    "one_block": dict(bq=64, bk=64),
    "bfloat16": dict(dtype=jnp.bfloat16, atol=0.15, rtol=0.1),
    "bfloat16_bidirectional_kv_len": dict(
        dtype=jnp.bfloat16, causal=False, kv_len=(40, 64), atol=0.15,
        rtol=0.1),
}


def _flash_problem(causal=True, kv_len=None, H=4, KH=None, bq=16, bk=16,
                   dtype=jnp.float32, atol=1e-4, rtol=1e-7):
    """(gradient of the flash kernels, gradient of the reference,
    inputs, tolerances) of one loss; the cotangent is zero at padded q
    rows, as the MLM loss weights guarantee."""
    from kubeflow_tpu.ops.attention import gqa_repeat

    q = jax.random.normal(jax.random.key(0), (2, 64, H, 16), dtype)
    k, v = (jax.random.normal(jax.random.key(i), (2, 64, KH or H, 16),
                              dtype) for i in (1, 2))
    lens = None if kv_len is None else jnp.array(kv_len, jnp.int32)
    w = 1.0 if lens is None else (
        jnp.arange(64)[None, :] < lens[:, None]).astype(
            jnp.float32)[..., None, None]

    def grad_of(attend):
        def loss(q, k, v):
            out = attend(q, *gqa_repeat(q, k, v))
            return jnp.sum((out.astype(jnp.float32) * w) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))

    flash = grad_of(lambda q, k, v: flash_attention(
        q, k, v, causal, bq, bk, None, None, lens))
    ref = grad_of(lambda q, k, v: reference_attention(
        q, k, v, causal=causal, kv_len=lens))
    return flash, ref, (q, k, v), dict(atol=atol, rtol=rtol)


def _resolved(rec):
    from kubeflow_tpu.ops import autotune

    return {d["kernel"] for d in autotune.summarize_resolutions(rec)}


class TestFlashFusedBackward:
    """One kernel for dQ, dK and dV wherever a dQ row fits VMEM, the
    dQ and dK/dV pair past that: the shape decides
    (``autotune.flash_bwd_fuses``), and the recorded resolutions say
    which ran."""

    @pytest.mark.parametrize("case", list(FUSED_BACKWARD_CASES))
    def test_fused_matches_reference_and_the_kernel_pair(self, case,
                                                         monkeypatch):
        from kubeflow_tpu.ops import autotune

        flash, ref, args, tol = _flash_problem(**FUSED_BACKWARD_CASES[case])
        with autotune.record_resolutions() as rec:
            fused = flash(*args)
        assert _resolved(rec) == {"flash_fwd", "flash_bwd_fused"}
        for got, want, name in zip(fused, ref(*args), "qkv"):
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                err_msg=f"d{name} against the reference", **tol)
        # the same sums in the same order as the two kernels it replaces
        monkeypatch.setattr(autotune, "VMEM_BUDGET_BYTES", 1024)
        with autotune.record_resolutions() as rec:
            pair = flash(*args)
        assert _resolved(rec) == {"flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv"}
        for got, want, name in zip(fused, pair, "qkv"):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"d{name} against the dQ + dK/dV kernels")

    def test_row_that_does_not_fit_takes_the_kernel_pair(self,
                                                         monkeypatch):
        """Past the budget the fallback runs, and only there: one row
        of (64, 16) float32 and its output block is 8 KB."""
        from kubeflow_tpu.ops import autotune

        assert autotune.flash_bwd_fuses(64, 16, jnp.float32)
        row = 64 * 16 * (4 + 4)
        tile = autotune.flash_vmem_bytes("flash_bwd_dkv", 128, 128, 16, 4)
        monkeypatch.setattr(autotune, "VMEM_BUDGET_BYTES", tile + row - 1)
        assert not autotune.flash_bwd_fuses(64, 16, jnp.float32)
        flash, ref, args, tol = _flash_problem()
        with autotune.record_resolutions() as rec:
            got = flash(*args)
        assert _resolved(rec) == {"flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv"}
        for g, want in zip(got, ref(*args)):
            np.testing.assert_allclose(g, want, **tol)

    def test_pretrain_shape_takes_the_fused_kernel(self):
        """SmolLM2-360M at 8192 tokens (15 heads of 64, bfloat16): the
        row is 2 MB + 1 MB; traced, not run."""
        from kubeflow_tpu.ops import autotune

        x = jax.ShapeDtypeStruct((1, 8192, 15, 64), jnp.bfloat16)
        with autotune.record_resolutions() as rec:
            jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)), x, x, x)
        fused = [d for d in rec if d["kernel"] == "flash_bwd_fused"]
        assert _resolved(rec) == {"flash_fwd", "flash_bwd_fused"}
        assert (fused[0]["source"], fused[0]["block_q"],
                fused[0]["block_k"]) == ("table", 1024, 1024)
        # head size 64 fuses through the 32768 bucket and not past it
        assert autotune.flash_bwd_fuses(32768, 64, jnp.bfloat16)
        assert not autotune.flash_bwd_fuses(65536, 64, jnp.bfloat16)


class TestRing:
    def test_matches_reference(self, mesh_dp_tp):
        q, k, v = qkv()
        ref = reference_attention(q, k, v)
        out = ring_attention_sharded(q, k, v, mesh_dp_tp)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_gradients_match(self, mesh_dp_tp):
        q, k, v = qkv()
        g_ref = jax.grad(lambda q: jnp.sum(reference_attention(q, k, v) ** 2))(q)
        g_ring = jax.grad(
            lambda q: jnp.sum(ring_attention_sharded(q, k, v, mesh_dp_tp) ** 2)
        )(q)
        np.testing.assert_allclose(g_ring, g_ref, atol=1e-4)

    def test_long_context_sharded_sequence(self, mesh_dp_tp):
        # sequence 4x longer than any single shard sees
        q, k, v = qkv(B=1, S=256)
        ref = reference_attention(q, k, v)
        out = ring_attention_sharded(q, k, v, mesh_dp_tp, batch_axis=None)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestUlysses:
    def test_matches_reference(self, mesh_dp_tp):
        from kubeflow_tpu.ops import ulysses_attention_sharded

        q, k, v = qkv()
        ref = reference_attention(q, k, v)
        out = ulysses_attention_sharded(q, k, v, mesh_dp_tp)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_gradients_match(self, mesh_dp_tp):
        from kubeflow_tpu.ops import ulysses_attention_sharded

        q, k, v = qkv()
        g_ref = jax.grad(
            lambda q: jnp.sum(reference_attention(q, k, v) ** 2))(q)
        g_uly = jax.grad(lambda q: jnp.sum(
            ulysses_attention_sharded(q, k, v, mesh_dp_tp) ** 2))(q)
        np.testing.assert_allclose(g_uly, g_ref, atol=1e-4)

    def test_non_causal_long_sequence(self, mesh_dp_tp):
        from kubeflow_tpu.ops import ulysses_attention_sharded

        q, k, v = qkv(B=1, S=256)
        ref = reference_attention(q, k, v, causal=False)
        out = ulysses_attention_sharded(q, k, v, mesh_dp_tp,
                                        batch_axis=None, causal=False)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_rejects_indivisible_heads(self, mesh_dp_tp):
        from kubeflow_tpu.ops import ulysses_attention_sharded

        q, k, v = qkv(H=3)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention_sharded(q, k, v, mesh_dp_tp)

    def test_gqa_repeat_after_all_to_all(self, mesh_dp_tp):
        """kv may carry fewer (grouped) heads; the repeat happens after
        the KV collectives and the result matches repeated-dense."""
        from kubeflow_tpu.ops import ulysses_attention_sharded

        q, _, _ = qkv(H=8)
        k = jax.random.normal(jax.random.key(7), (2, 64, 4, 16))
        v = jax.random.normal(jax.random.key(8), (2, 64, 4, 16))
        ref = reference_attention(q, jnp.repeat(k, 2, axis=2),
                                  jnp.repeat(v, 2, axis=2))
        out = ulysses_attention_sharded(q, k, v, mesh_dp_tp)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestCollectives:
    def test_all_reduce_sums_shards(self, mesh_dp):
        x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
        out = all_reduce(x, mesh_dp)
        np.testing.assert_allclose(out[0], np.asarray(x).sum(0))

    def test_all_gather_roundtrip(self, mesh_dp):
        x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
        np.testing.assert_allclose(all_gather(x, mesh_dp), x)

    def test_reduce_scatter(self, mesh_dp):
        out = reduce_scatter(jnp.ones((8, 8)), mesh_dp)
        assert out.shape == (8, 1)
        np.testing.assert_allclose(out, 8.0)

    def test_all_to_all_preserves_global_view(self, mesh_dp):
        # a2a transposes which axis is sharded; the global matrix is unchanged
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        out = all_to_all(x, mesh_dp)
        np.testing.assert_allclose(out, np.asarray(x))

    def test_ppermute_rotates(self, mesh_dp):
        x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
        out = ppermute_shift(x, mesh_dp, shift=1)
        np.testing.assert_allclose(np.asarray(out)[:, 0], np.roll(np.arange(8), 1))

    def test_bench_returns_bandwidth(self, mesh_dp):
        r = bench_collective("all_reduce", mesh_dp, size_mb=0.5, iters=2,
                             warmup=1)
        assert r.n_devices == 8
        assert r.mean_s > 0 and r.bus_gb_s > 0


class TestMoeDispatch:
    def test_capacity_rounding(self):
        assert expert_capacity(128, 8, 2, 1.0) % 8 == 0
        assert expert_capacity(128, 8, 2, 1.0) >= 128 * 2 // 8

    def test_dispatch_is_permutation_when_ample(self):
        G, E, K, C = 32, 4, 2, 32
        logits = jax.random.normal(jax.random.key(0), (G, E))
        dispatch, combine, _ = capacity_dispatch(logits, K, C)
        # every token placed exactly K times with ample capacity
        np.testing.assert_allclose(dispatch.sum(axis=(1, 2)), K)
        # each slot holds at most one token
        assert float(jnp.max(dispatch.sum(axis=0))) <= 1.0
        # combine weights per token sum to 1 (renormalized top-k)
        np.testing.assert_allclose(combine.sum(axis=(1, 2)), 1.0, atol=1e-5)

    def test_overflow_drops_tokens(self):
        G, E, K, C = 32, 2, 1, 4
        logits = jnp.zeros((G, E)).at[:, 0].set(10.0)  # all want expert 0
        dispatch, _, _ = capacity_dispatch(logits, K, C)
        assert float(dispatch.sum()) == C  # only C fit

    def test_moe_identity_experts(self):
        # identity expert_fn + ample capacity => y ≈ x (combine sums to 1)
        G, D, E = 16, 8, 4
        x = jax.random.normal(jax.random.key(0), (G, D))
        logits = jax.random.normal(jax.random.key(1), (G, E))
        y, aux = capacity_moe(x, logits, lambda e: e, k=2, capacity=G)
        np.testing.assert_allclose(y, x, atol=1e-5)
        assert float(aux) > 0


class TestFlashAutotuneAndPadding:
    """The autotune-plane surface of flash_attention: None blocks
    resolve from the tile table/fallback, and the kv_len padding mask
    (the BERT bidirectional route) is exact against the dense oracle
    in forward AND both backward kernels."""

    def test_default_none_blocks_match_reference(self):
        q, k, v = qkv()
        out = flash_attention(q, k, v)  # table/fallback resolution
        np.testing.assert_allclose(out, reference_attention(q, k, v),
                                   atol=1e-5)

    def test_padding_mask_forward_matches_reference(self):
        q, k, v = qkv()
        kv_len = jnp.array([40, 64], jnp.int32)
        for causal in (False, True):
            ref = reference_attention(q, k, v, causal=causal,
                                      kv_len=kv_len)
            out = flash_attention(q, k, v, causal, 16, 16, None, None,
                                  kv_len)
            # valid positions only: outputs AT padded q rows are
            # unspecified by contract (masked downstream)
            np.testing.assert_allclose(
                np.asarray(out[0, :40]), np.asarray(ref[0, :40]),
                atol=1e-5, err_msg=f"causal={causal}")
            np.testing.assert_allclose(
                np.asarray(out[1]), np.asarray(ref[1]), atol=1e-5)

    def test_padding_mask_is_real(self):
        """Perturbing a padded KV position must not change any valid
        output — the kernel mask, not numerics, is in charge."""
        q, k, v = qkv()
        kv_len = jnp.array([40, 64], jnp.int32)
        k2 = k.at[0, 50].set(99.0)
        v2 = v.at[0, 50].set(-99.0)
        a = flash_attention(q, k, v, False, 16, 16, None, None, kv_len)
        b = flash_attention(q, k2, v2, False, 16, 16, None, None, kv_len)
        assert np.array_equal(np.asarray(a[0, :40]),
                              np.asarray(b[0, :40]))

    def test_padding_mask_gradients_match_reference(self):
        """Both backward kernels must apply the SAME mask when
        recomputing P, or valid-position gradients absorb garbage from
        padded columns. Cotangent zeroed at padded q rows, as the MLM
        loss weights guarantee."""
        q, k, v = qkv()
        kv_len = jnp.array([40, 64], jnp.int32)
        w = (jnp.arange(64)[None, :] < kv_len[:, None]).astype(
            jnp.float32)[..., None, None]
        for causal in (False, True):
            refs = jax.grad(
                lambda q, k, v: jnp.sum((reference_attention(
                    q, k, v, causal=causal, kv_len=kv_len) * w) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            fls = jax.grad(
                lambda q, k, v: jnp.sum((flash_attention(
                    q, k, v, causal, 16, 16, None, None,
                    kv_len) * w) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            for g_ref, g_fl, name in zip(refs, fls, "qkv"):
                np.testing.assert_allclose(
                    g_fl, g_ref, atol=1e-4,
                    err_msg=f"d{name} causal={causal}")

    def test_smem_resident_control_vectors_are_bounded(self):
        """The kv lengths (and the fused sampler's per-row scalars) sit
        in SMEM whole; the interpreter has no SMEM, so the size is held
        to what compiled on a chip and fails by name past it."""
        from kubeflow_tpu.ops.attention import MAX_SMEM_CONTROL_ENTRIES
        from kubeflow_tpu.ops.sampling import fused_sample

        B = MAX_SMEM_CONTROL_ENTRIES // 2 + 1  # x 2 heads: one too many
        x = jnp.zeros((B, 16, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="flash_attention kv_len"):
            flash_attention(x, x, x, False, 16, 16, None, None,
                            jnp.full((B,), 16, jnp.int32))
        rows = MAX_SMEM_CONTROL_ENTRIES + 1
        with pytest.raises(ValueError, match="fused_sample rows"):
            jax.eval_shape(
                lambda l, k: fused_sample(l, k, temperature=1.0),
                jax.ShapeDtypeStruct((rows, 128), jnp.float32),
                jax.eval_shape(lambda: jax.random.split(
                    jax.random.key(0), rows)))

    def test_padding_mask_with_uneven_blocks(self):
        """Mask correctness must not depend on the tile shape — a
        length landing mid-block masks the partial block exactly."""
        q, k, v = qkv()
        kv_len = jnp.array([23, 57], jnp.int32)
        ref = reference_attention(q, k, v, causal=False, kv_len=kv_len)
        for bq, bk in ((32, 8), (8, 32), (64, 16)):
            out = flash_attention(q, k, v, False, bq, bk, None, None,
                                  kv_len)
            np.testing.assert_allclose(
                np.asarray(out[0, :23]), np.asarray(ref[0, :23]),
                atol=1e-5, err_msg=f"bq={bq} bk={bk}")
            np.testing.assert_allclose(
                np.asarray(out[1, :57]), np.asarray(ref[1, :57]),
                atol=1e-5, err_msg=f"bq={bq} bk={bk}")
