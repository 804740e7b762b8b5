"""Kernel autotune plane: shape-keyed tile tables (ops/autotune.py).

The acceptance pins: the r05 bench shapes (seq 8192/16384/32768, d1024
≙ head_dim 64 × 16 heads, bf16, causal) resolve the measured 1024-edge
tiles FROM THE TABLE (not the fallback); illegal entries are rejected
at load with a warning and the analytic fallback serves their shape
class (never a compile failure from a bad table row); and every
committed entry runs the kernels bit-consistent/parity-clean against
the default-tile oracle on small shapes (the CPU-interpreter sweep).
"""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import autotune
from kubeflow_tpu.ops.attention import flash_attention, reference_attention
from kubeflow_tpu.ops.paged_attention import paged_decode_attention

R05_SHAPE = dict(head_dim=64, n_heads=16, n_kv_heads=16,
                 dtype=jnp.bfloat16, causal=True)


class TestResolution:
    @pytest.mark.parametrize("seq", [8192, 16384, 32768])
    @pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                        "flash_bwd_dkv"])
    def test_r05_shapes_resolve_from_table(self, kernel, seq):
        """The acceptance anchor: the r05-measured winners come from
        the committed table, not the fallback."""
        cfg = autotune.resolve_flash(kernel, seq=seq, **R05_SHAPE)
        assert cfg.source == "table"
        assert (cfg.block_q, cfg.block_k) == (1024, 1024)

    @pytest.mark.parametrize("seq, causal, tile", [
        (512, False, 512), (8192, True, 1024), (16384, True, 1024),
        (32768, True, 512)])
    def test_fused_backward_rows_resolve_from_table(self, seq, causal,
                                                    tile):
        """The fused backward is its own key, so a recorded resolution
        says which backward a shape took; its rows were seeded from the
        dK/dV ones, less the tile a 32768-long dQ row leaves no room
        for."""
        assert autotune.flash_bwd_fuses(seq, 64, jnp.bfloat16)
        cfg = autotune.resolve_flash(
            "flash_bwd_fused", seq=seq, **dict(R05_SHAPE, causal=causal))
        assert (cfg.source, cfg.block_q, cfg.block_k) == (
            "table", tile, tile)

    def test_fused_backward_falls_back_to_a_tile_beside_its_row(self):
        """No row for head size 128: the analytic choice is the largest
        edge that fits WITH the dQ row, smaller than the dK/dV kernel's
        at the same shape."""
        shape = dict(seq=16384, head_dim=128, n_heads=8, n_kv_heads=8,
                     dtype=jnp.bfloat16, causal=True)
        assert autotune.flash_bwd_fuses(16384, 128, jnp.bfloat16)
        fused = autotune.resolve_flash("flash_bwd_fused", **shape)
        pair = autotune.resolve_flash("flash_bwd_dkv", **shape)
        assert (fused.source, pair.source) == ("fallback", "fallback")
        assert fused.block_q < pair.block_q == 1024
        assert autotune.flash_vmem_bytes(
            "flash_bwd_fused", fused.block_q, fused.block_k, 128, 2,
            seq=16384) <= autotune.VMEM_BUDGET_BYTES
        assert not autotune.flash_bwd_fuses(32768, 128, jnp.bfloat16)

    def test_bert_bidirectional_shape_resolves_from_table(self):
        cfg = autotune.resolve_flash(
            "flash_fwd", seq=512, head_dim=64, n_heads=12, n_kv_heads=12,
            dtype=jnp.bfloat16, causal=False)
        assert cfg.source == "table"
        assert (cfg.block_q, cfg.block_k) == (512, 512)

    def test_uncovered_shape_falls_back_legal(self):
        cfg = autotune.resolve_flash(
            "flash_fwd", seq=4096, head_dim=128, n_heads=8, n_kv_heads=8,
            dtype=jnp.float32, causal=True)
        assert cfg.source == "fallback"
        assert 4096 % cfg.block_q == 0 and 4096 % cfg.block_k == 0
        assert autotune.flash_vmem_bytes(
            "flash_fwd", cfg.block_q, cfg.block_k, 128,
            4) <= autotune.VMEM_BUDGET_BYTES

    def test_table_value_fitted_to_seq_divisors(self):
        """An 8192-bucket entry serves seq 6144 too — blocks fit to the
        largest divisor within the measured value."""
        cfg = autotune.resolve_flash("flash_fwd", seq=6144, **R05_SHAPE)
        assert cfg.source == "table"
        assert 6144 % cfg.block_q == 0 and cfg.block_q <= 1024

    def test_override_wins_untouched(self):
        cfg = autotune.resolve_flash("flash_fwd", seq=8192, block_q=256,
                                     block_k=512, **R05_SHAPE)
        assert cfg.source == "override"
        assert (cfg.block_q, cfg.block_k) == (256, 512)

    def test_partial_override_resolves_other_knob(self):
        cfg = autotune.resolve_flash("flash_fwd", seq=8192, block_q=256,
                                     **R05_SHAPE)
        assert cfg.source == "override"
        assert cfg.block_q == 256
        assert cfg.block_k == 1024  # the table's half

    def test_paged_fallback_is_per_head_loop(self):
        with autotune.table_override(autotune.TileTable([], [])):
            cfg = autotune.resolve_paged(
                max_seq_len=2048, page_size=64, n_heads=16, n_kv_heads=8,
                head_dim=64, dtype=jnp.bfloat16)
        assert (cfg.head_block, cfg.source) == (1, "fallback")

    def test_paged_entry_not_dividing_kv_heads_degrades(self):
        """A table row legal for ITS pinned shape but not this one
        degrades to the safe loop instead of raising."""
        table = autotune.TileTable([{
            "kernel": "paged_attn", "seq_bucket": None, "head_dim": None,
            "n_heads": None, "n_kv_heads": None, "page_size": None,
            "dtype": "*", "causal": None, "generation": "*",
            "head_block": 4}], [])
        # head_block 4 with wildcard n_kv_heads would be rejected at
        # load; construct directly to exercise the resolve-time guard
        with autotune.table_override(table):
            cfg = autotune.resolve_paged(
                max_seq_len=2048, page_size=64, n_heads=6, n_kv_heads=6,
                head_dim=64, dtype=jnp.bfloat16)
        assert (cfg.head_block, cfg.source) == (1, "fallback")

    def test_generation_specific_entry_outranks_wildcard(self):
        entries = [
            {"kernel": "flash_fwd", "seq_bucket": 8192, "head_dim": 64,
             "n_heads": None, "n_kv_heads": None, "dtype": "bfloat16",
             "causal": True, "generation": "*", "block_q": 1024,
             "block_k": 1024},
            {"kernel": "flash_fwd", "seq_bucket": 8192, "head_dim": 64,
             "n_heads": None, "n_kv_heads": None, "dtype": "bfloat16",
             "causal": True, "generation": autotune.backend_generation(),
             "block_q": 512, "block_k": 512},
        ]
        with autotune.table_override(autotune.TileTable(entries, [])):
            cfg = autotune.resolve_flash("flash_fwd", seq=8192,
                                         **R05_SHAPE)
        assert (cfg.block_q, cfg.block_k) == (512, 512)


class TestTableIO:
    def test_round_trip(self, tmp_path):
        table = autotune.load_table()
        out = tmp_path / "t.json"
        autotune.save_table(table, str(out))
        again = autotune.load_table(str(out), strict=True)
        assert again.to_dict() == table.to_dict()
        # and the committed file IS in canonical saved form
        committed = json.load(open(autotune.DEFAULT_TABLE_PATH))
        assert committed == table.to_dict()

    def test_illegal_entry_rejected_with_warning_then_fallback(self,
                                                               tmp_path):
        """Never a compile failure from a bad table row: the row is
        dropped at load with a warning and resolution falls back."""
        bad = {"version": 1, "entries": [{
            "kernel": "flash_fwd", "seq_bucket": 8192, "head_dim": 64,
            "n_heads": None, "n_kv_heads": None, "dtype": "bfloat16",
            "causal": True, "generation": "*",
            "block_q": 768, "block_k": 768}]}
        path = tmp_path / "bad.json"
        path.write_text(json.dumps(bad))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            table = autotune.load_table(str(path))
        assert not table.entries and len(table.rejected) == 1
        assert any("rejected" in str(w.message) for w in caught)
        with autotune.table_override(table):
            cfg = autotune.resolve_flash("flash_fwd", seq=8192,
                                         **R05_SHAPE)
        assert cfg.source == "fallback"
        assert 8192 % cfg.block_q == 0

    def test_oversized_vmem_entry_rejected(self):
        """The analytic estimate reproduces the measured r05 wall:
        2048-edge tiles exceed the scoped budget, 1024 fits."""
        entry = {"kernel": "flash_fwd", "seq_bucket": 8192,
                 "head_dim": 64, "dtype": "bfloat16", "causal": True,
                 "generation": "*", "block_q": 2048, "block_k": 2048}
        errs = autotune.validate_entry(entry)
        assert any("VMEM" in e for e in errs)
        entry.update(block_q=1024, block_k=1024)
        assert autotune.validate_entry(entry) == []

    @pytest.mark.parametrize("nbytes", [2, 4], ids=["bf16", "f32"])
    def test_fused_backward_estimate_grows_by_the_row(self, nbytes):
        """The dK/dV kernel's blocks plus, for the life of a row, the
        f32 dQ accumulator and the output block it leaves in."""
        for seq in (512, 8192, 32768):
            assert autotune.flash_vmem_bytes(
                "flash_bwd_fused", 512, 256, 64, nbytes, seq=seq
            ) - autotune.flash_vmem_bytes(
                "flash_bwd_dkv", 512, 256, 64, nbytes
            ) == seq * 64 * (4 + nbytes)

    def test_fused_backward_entry_validated_with_its_row(self):
        """A bucket's longest sequence is the bucket: 1024-edge tiles
        fit beside an 8192 or 16384 row and not beside a 32768 one."""
        entry = {"kernel": "flash_bwd_fused", "seq_bucket": 8192,
                 "head_dim": 64, "dtype": "bfloat16", "causal": True,
                 "generation": "*", "block_q": 1024, "block_k": 1024}
        assert autotune.validate_entry(entry) == []
        assert autotune.validate_entry(dict(entry, seq_bucket=16384)) == []
        errs = autotune.validate_entry(dict(entry, seq_bucket=32768))
        assert any("VMEM" in e for e in errs)
        assert autotune.validate_entry(dict(
            entry, seq_bucket=32768, block_q=512, block_k=512)) == []
        # the same tile is legal for the kernel that keeps no row
        assert autotune.validate_entry(dict(
            entry, kernel="flash_bwd_dkv", seq_bucket=32768)) == []

    def test_strict_load_raises_on_illegal(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"entries": [{
            "kernel": "flash_fwd", "seq_bucket": 8192, "head_dim": 64,
            "dtype": "bfloat16", "causal": True, "block_q": 2048,
            "block_k": 2048}]}))
        with pytest.raises(ValueError, match="VMEM"):
            autotune.load_table(str(path), strict=True)

    def test_unparseable_table_never_fails_runtime(self, tmp_path):
        path = tmp_path / "garbage.json"
        path.write_text("{not json")
        with warnings.catch_warnings(record=True):
            warnings.simplefilter("always")
            table = autotune.load_table(str(path))
        assert table.entries == []

    def test_head_block_needs_concrete_kv_heads(self):
        errs = autotune.validate_entry({
            "kernel": "paged_attn", "head_block": 2, "dtype": "*"})
        assert any("n_kv_heads" in e for e in errs)
        assert autotune.validate_entry({
            "kernel": "paged_attn", "head_block": 2, "n_kv_heads": 4,
            "dtype": "*"}) == []


class TestRecorder:
    def test_resolutions_recorded_with_source(self):
        with autotune.record_resolutions() as rec:
            autotune.resolve_flash("flash_fwd", seq=8192, **R05_SHAPE)
            autotune.resolve_flash("flash_fwd", seq=8192, block_q=128,
                                   block_k=128, **R05_SHAPE)
            autotune.resolve_paged(max_seq_len=2048, page_size=64,
                                   n_heads=16, n_kv_heads=16, head_dim=64,
                                   dtype=jnp.bfloat16)
        summary = autotune.summarize_resolutions(rec)
        sources = {(d["kernel"], d["source"]) for d in summary}
        assert ("flash_fwd", "table") in sources
        assert ("flash_fwd", "override") in sources
        assert ("paged_attn", "table") in sources

    def test_summarize_dedupes(self):
        with autotune.record_resolutions() as rec:
            for _ in range(3):
                autotune.resolve_flash("flash_fwd", seq=8192, **R05_SHAPE)
        assert len(autotune.summarize_resolutions(rec)) == 1


def _qkv(S=64, dtype=jnp.float32):
    return tuple(jax.random.normal(jax.random.PRNGKey(i), (2, S, 4, 16),
                                   dtype) for i in range(3))


class TestCommittedTableParity:
    """The CPU-interpreter parity sweep: every committed entry (and the
    fallback) runs the kernels consistent with the default-tile oracle
    on small shapes. Tiles larger than the smoke sequence clamp to it,
    so effective-equal configs must be BIT-consistent; differing
    effective tiles only reorder the online softmax and gate at tight
    tolerance."""

    @pytest.mark.parametrize(
        "entry", [e for e in autotune.load_table().entries
                  if e["kernel"] != "paged_attn"],
        ids=autotune.entry_key)
    def test_flash_entry_parity(self, entry):
        S = 64
        causal = bool(entry.get("causal", True))
        q, k, v = _qkv(S)
        bq = autotune.fit_block(S, entry["block_q"])
        bk = autotune.fit_block(S, entry["block_k"])
        oracle = 16
        out = flash_attention(q, k, v, causal, bq, bk)
        ref = flash_attention(q, k, v, causal, oracle, oracle)
        if (bq, bk) == (oracle, oracle):
            assert np.array_equal(np.asarray(out), np.asarray(ref))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference_attention(q, k, v, causal=causal)),
            atol=1e-5)
        g_out = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal, bq, bk) ** 2), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
            q, k, v, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_out, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, err_msg=f"d{name}")

    @pytest.mark.parametrize(
        "entry", [e for e in autotune.load_table().entries
                  if e["kernel"] == "paged_attn"],
        ids=autotune.entry_key)
    def test_paged_entry_parity(self, entry):
        B, QH, KH, Dh, ps, P = 2, 8, 4, 16, 8, 6
        hb = int(entry.get("head_block", 1))
        if KH % hb:
            hb = 1
        q = jax.random.normal(jax.random.PRNGKey(0), (B, QH, Dh))
        kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, KH, Dh))
        vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, KH, Dh))
        pages = jnp.array([[0, 1, 2], [3, 4, P]], jnp.int32)
        pos = jnp.array([20, 11], jnp.int32)
        out = paged_decode_attention(q, kp, vp, pages, pos, head_block=hb)
        oracle = paged_decode_attention(q, kp, vp, pages, pos,
                                        head_block=1)
        if hb == 1:
            assert np.array_equal(np.asarray(out), np.asarray(oracle))
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   atol=1e-5)

    def test_fallback_path_parity(self):
        """The no-entry path must stay parity-clean too."""
        q, k, v = _qkv()
        with autotune.table_override(autotune.TileTable([], [])):
            out = flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(reference_attention(q, k, v)),
            atol=1e-5)


class TestBuckets:
    def test_seq_bucket_pow2(self):
        assert autotune.seq_bucket(1) == 128
        assert autotune.seq_bucket(512) == 512
        assert autotune.seq_bucket(513) == 1024
        assert autotune.seq_bucket(8192) == 8192

    def test_fit_block(self):
        assert autotune.fit_block(8192, 1024) == 1024
        assert autotune.fit_block(6144, 1024) == 1024
        assert autotune.fit_block(60, 16) == 15
        assert autotune.fit_block(64, 4096) == 64

    def test_dtype_name(self):
        assert autotune.dtype_name(jnp.bfloat16) == "bfloat16"
        assert autotune.dtype_name(jnp.zeros((), jnp.float32).dtype) == \
            "float32"
        assert autotune.dtype_name("int8") == "int8"


class TestTableLint:
    """TPU001 lints the committed table at the autotune owner module —
    the tile-legality obligation the now-dynamic kernel call sites
    shed (zero findings on the committed table)."""

    def _run(self, monkeypatch, table_path):
        from kubeflow_tpu.analysis.checkers import tile_legality
        from kubeflow_tpu.analysis.walker import ModuleInfo

        monkeypatch.setattr(tile_legality, "_table_path",
                            lambda: str(table_path))
        checker = tile_legality.TileLegalityChecker()
        module = ModuleInfo.from_source("kubeflow_tpu/ops/autotune.py",
                                        "x = 1\n")
        return list(checker.check(module))

    def test_committed_table_zero_findings(self, monkeypatch):
        findings = self._run(monkeypatch, autotune.DEFAULT_TABLE_PATH)
        assert findings == []

    @pytest.mark.parametrize("kernel, seq_bucket, block", [
        ("flash_fwd", 8192, 2048), ("flash_bwd_fused", 32768, 1024)],
        ids=["tile-over-budget", "fused-row-over-budget"])
    def test_illegal_entry_flagged_against_json(self, monkeypatch,
                                                tmp_path, kernel,
                                                seq_bucket, block):
        path = tmp_path / "t.json"
        path.write_text(json.dumps({"entries": [{
            "kernel": kernel, "seq_bucket": seq_bucket, "head_dim": 64,
            "dtype": "bfloat16", "causal": True, "block_q": block,
            "block_k": block}]}))
        findings = self._run(monkeypatch, path)
        assert findings
        assert all(f.path == "kubeflow_tpu/ops/tile_table.json"
                   and f.rule == "TPU001" for f in findings)
        assert any("VMEM" in f.message for f in findings)

    def test_dynamic_kernel_call_sites_stay_silent(self):
        """The flash kernels' BlockSpec dims are now resolved values —
        unresolvable statically, so detection 1/2 must not fire."""
        from kubeflow_tpu.analysis.checkers.tile_legality import (
            TileLegalityChecker,
        )
        from kubeflow_tpu.analysis.walker import ModuleInfo

        module = ModuleInfo.from_file(
            os.path.join(os.path.dirname(autotune.__file__),
                         "attention.py"),
            root=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(autotune.__file__)))))
        findings = list(TileLegalityChecker().check(module))
        assert findings == []


class TestSweepValidateCli:
    """Pin the preflight-stage contract: tile_sweep.py --validate exits
    nonzero on an injected illegal entry. (The exit-0 side runs the
    full CPU parity smoke and lives in preflight stage 11; the
    underlying legality verdicts are pinned above in TestTableIO.)"""

    @pytest.mark.parametrize("block", [2048, 768],
                             ids=["oversized-vmem", "non-divisible"])
    def test_validate_rejects_injected_illegal_entry(self, tmp_path,
                                                     block):
        import subprocess
        import sys

        bad = json.load(open(autotune.DEFAULT_TABLE_PATH))
        bad["entries"].append({
            "kernel": "flash_fwd", "seq_bucket": 8192, "head_dim": 64,
            "n_heads": None, "n_kv_heads": None, "dtype": "bfloat16",
            "causal": True, "generation": "*", "block_q": block,
            "block_k": block, "provenance": "injected"})
        path = tmp_path / "bad.json"
        path.write_text(json.dumps(bad))
        script = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(autotune.__file__)))),
            "scripts", "tile_sweep.py")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, script, "--validate", "--table", str(path)],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode != 0
        assert "ILLEGAL" in proc.stderr


class TestReviewRegressions:
    """Pins for the PR-15 review findings."""

    def test_unreadable_table_falls_back_not_raises(self, tmp_path):
        """An existing-but-unreadable table (here: a directory at the
        path) must take the same never-fail fallback path as a missing
        one — OSError, not just ValueError, is absorbed."""
        path = tmp_path / "tile_table.json"
        path.mkdir()
        with warnings.catch_warnings(record=True):
            warnings.simplefilter("always")
            table = autotune.load_table(str(path))
        assert table.entries == [] and table.rejected
        with autotune.table_override(table):
            cfg = autotune.resolve_flash("flash_fwd", seq=8192,
                                         **R05_SHAPE)
        assert cfg.source == "fallback"

    def test_tpu001_flags_unparseable_table(self, monkeypatch, tmp_path):
        """A corrupted-JSON commit must fail the lint gate, not lint
        green as an empty table."""
        from kubeflow_tpu.analysis.checkers import tile_legality
        from kubeflow_tpu.analysis.walker import ModuleInfo

        path = tmp_path / "t.json"
        path.write_text("{not json")
        monkeypatch.setattr(tile_legality, "_table_path",
                            lambda: str(path))
        checker = tile_legality.TileLegalityChecker()
        module = ModuleInfo.from_source("kubeflow_tpu/ops/autotune.py",
                                        "x = 1\n")
        findings = list(checker.check(module))
        assert findings and any("JSON" in f.message for f in findings)

    def test_bool_tile_knob_rejected(self):
        from kubeflow_tpu.models import tiny_config

        with pytest.raises(ValueError, match="attention_block_q"):
            tiny_config(attention_block_q=True).validate()
