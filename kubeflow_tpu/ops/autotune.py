"""Kernel autotune plane: shape-keyed tile tables for the Pallas kernels.

PERF.md r05 proved tile choice is a first-order lever (1024-edge flash
tiles ran the fwd+bwd pair 1.8× faster than 512 at seq 8192) AND that
the optimum is shape-dependent (2048 exceeds scoped VMEM; 256 loses the
MXU) — yet every kernel shipped ONE hardcoded default. This module is
the selection plane every tuned kernel consults instead of growing
another constant:

- a **kernel key** (``flash_fwd`` / ``flash_bwd_fused`` /
  ``flash_bwd_dq`` / ``flash_bwd_dkv`` / ``paged_attn``) plus a
  **shape class** (seq bucket, head_dim, n_heads / n_kv_heads, dtype,
  causal, backend generation) maps to a measured tile config — ``(block_q, block_k)`` as independent knobs
  for the flash kernels, the KV ``head_block`` group for the paged
  kernel;
- the table is a versioned, committed JSON file
  (``kubeflow_tpu/ops/tile_table.json``) seeded with the r05-measured
  winners and regenerated on chip by ``scripts/tile_sweep.py``;
- an analytic VMEM-budget legality check is both the **load-time
  guard** (an illegal table row is rejected with a warning and never
  becomes a compile failure — the fallback is used instead) and the
  **fallback selector** when a shape class has no entry;
- every resolution can be recorded (:func:`record_resolutions`) so the
  bench artifact attributes a throughput move to a table change
  (``tile_config`` rows: resolved blocks + source
  ``table|fallback|override``).

The module keeps its top level stdlib-only on purpose: tpulint's TPU001
checker loads it standalone (without ``kubeflow_tpu.ops.__init__``'s
jax import) to lint the table itself at preflight. jax is imported
lazily inside :func:`backend_generation` only.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import warnings
from typing import Any, Dict, Iterator, List, Optional, Tuple

KERNELS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv",
           "paged_attn")

# the scoped-VMEM limit the r05 round hit at 16.75 MB of residency —
# the budget every analytic estimate is checked against
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
# fallback tile cap: 1024 is the r05-measured optimum edge and 2048
# failed to compile (PERF.md "Flash attention: sequence-independent
# VMEM") — the analytic fallback never guesses past what measurement
# established
MAX_TILE_EDGE = 1024
MIN_SEQ_BUCKET = 128

LANE_MULTIPLE = 128
# Mosaic sublane tile floors per dtype (the TPU001 table); wildcard
# dtypes validate at the STRICTEST floor so a wildcard entry is legal
# for every dtype it can match
SUBLANE_FLOOR = {"float32": 8, "bfloat16": 16, "float16": 16,
                 "int8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32}
SUBLANE_FLOOR_STRICTEST = 32
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
               "float8_e4m3fn": 1, "float8_e5m2": 1}

_WILDCARD = (None, "*")


def dtype_name(dtype: Any) -> str:
    """Canonical dtype string for table keys (``jnp.bfloat16``,
    ``np.dtype``, and plain strings all normalize the same way)."""
    if isinstance(dtype, str):
        return dtype
    name = getattr(dtype, "name", None)
    if name:
        return str(name)
    name = getattr(dtype, "__name__", None)
    if name:
        return str(name)
    return str(dtype)


def seq_bucket(seq: int) -> int:
    """Power-of-two shape-class bucket covering ``seq`` (min 128)."""
    b = MIN_SEQ_BUCKET
    while b < seq:
        b *= 2
    return b


def fit_block(seq: int, block: int) -> int:
    """Largest divisor of ``seq`` that is ≤ ``block`` — the flash
    kernels require blocks dividing the sequence, so a table value is
    fitted to the actual shape instead of failing the call."""
    block = max(1, min(int(block), int(seq)))
    for b in range(block, 0, -1):
        if seq % b == 0:
            return b
    return 1


def backend_generation() -> str:
    """Chip-generation component of the shape class: ``tpu_v4``-style
    for TPU backends (from ``device_kind``), the backend name
    otherwise. Deferred jax import — callers that only validate tables
    never pay it."""
    try:
        import jax
    except Exception:  # pragma: no cover - jax is always present in-tree
        return "cpu"
    backend = jax.default_backend()
    if backend != "tpu":
        return backend
    kind = jax.devices()[0].device_kind
    slug = "".join(ch if ch.isalnum() else "_" for ch in kind.lower())
    while "__" in slug:
        slug = slug.replace("__", "_")
    return slug.strip("_") or "tpu"


# ---------------------------------------------------------------------------
# Analytic VMEM estimates: the legality core shared by the load-time
# guard, the fallback selector, the sweep's skip-list, and TPU001
# ---------------------------------------------------------------------------


def flash_vmem_bytes(kernel: str, block_q: int, block_k: int,
                     head_dim: int, dtype_bytes: int, seq: int = 0) -> int:
    """Per-grid-step VMEM residency estimate for one flash kernel.

    I/O blocks are doubled for the grid pipeline's double buffering;
    the f32 score/probability tile (``block_q × block_k``) is the term
    that reproduces the r05 wall — it is exactly what pushes 2048-edge
    tiles past the 16 MB scoped budget while 1024 fits. ``seq`` counts
    only for ``flash_bwd_fused``, the one kernel that keeps a whole row.
    """
    f32 = 4
    d = head_dim
    score = block_q * block_k * f32
    if kernel == "flash_bwd_fused":
        # the dkv kernel's blocks, and for the life of a batch·head row
        # its dQ: the f32 accumulator and the output block it leaves in
        row = seq * d * (f32 + dtype_bytes)
        return row + flash_vmem_bytes("flash_bwd_dkv", block_q, block_k,
                                      head_dim, dtype_bytes)
    if kernel == "flash_fwd":
        # in: q, k, v; out: o, lse — scratch: f32 acc + m + l
        io = (2 * block_q * d + 2 * block_k * d) * dtype_bytes + block_q * f32
        scratch = (block_q * d + 2 * block_q) * f32
    elif kernel == "flash_bwd_dq":
        # in: q, k, v, g, lse, delta; out: dq — scratch: f32 acc
        io = ((3 * block_q * d + 2 * block_k * d) * dtype_bytes
              + 2 * block_q * f32)
        scratch = block_q * d * f32
    elif kernel == "flash_bwd_dkv":
        # in: q, k, v, g, lse, delta; out: dk, dv — scratch: 2× f32 acc
        io = ((2 * block_q * d + 4 * block_k * d) * dtype_bytes
              + 2 * block_q * f32)
        scratch = 2 * block_k * d * f32
    else:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    return 2 * io + scratch + score


def fused_vmem_limit_bytes(seq: int, head_dim: int, dtype_bytes: int) -> int:
    """The scope the fused backward asks Mosaic for
    (``vmem_limit_bytes``), where :func:`flash_vmem_bytes` is the
    estimate its tile is chosen by: the default scope, which the tile
    fits as the dK/dV kernel's does, and the dQ row as VMEM lays it out,
    a head under 128 lanes padded to them, the f32 accumulator once and
    the output block in its two pipeline buffers. Compiled for a v5e at
    (8192, 64) bf16 and 1024-edge tiles the kernel takes 24.1 MiB of
    the 25 this gives; the chip has 128 MiB."""
    lanes = -(-head_dim // LANE_MULTIPLE) * LANE_MULTIPLE
    return (VMEM_BUDGET_BYTES + seq * lanes * (4 + 2 * dtype_bytes)
            + 2 ** 20)


def paged_vmem_bytes(page_size: int, n_heads: int, n_kv_heads: int,
                     head_dim: int, dtype_bytes: int) -> int:
    """Per-grid-step VMEM residency for the paged decode kernel: one
    K/V page pair, one q row/out row, f32 accumulators. Independent of
    ``head_block`` (the whole page block is fetched either way — the
    knob changes compute batching, not residency)."""
    f32 = 4
    io = (2 * page_size * n_kv_heads * head_dim
          + 2 * n_heads * head_dim) * dtype_bytes
    scratch = (n_heads * head_dim + 2 * n_heads) * f32
    return 2 * io + scratch


# The grouped matmul (``ops/gmm.py``) has no table rows: the rule below
# is within 3 % of the best tiles the chip sweep found at every served
# shape but two prefill ``up`` products (PERF.md, PR 36, sections 6 and
# 7), and rows that repeat a rule choose nothing. Its expert tile: the
# largest ``tk x tn`` piece of an expert under this many bytes (two are
# in flight); pieces of half the size read alike, 128-wide ones 20-25 %
# slower
GMM_TILE_BYTES = 4 * 1024 * 1024
# the row tile: in a decode step 64 reads like 128 and 256 reads 4-10 %
# slower; in a prefill the best of 64 / 128 / 256 differs by shape, and
# 512 lost a tenth (a group's last, part-filled tile)
GMM_ROW_TILE = 128


def gmm_vmem_bytes(tm: int, tk: int, tn: int, dtype_bytes: int) -> int:
    """VMEM residency of one grouped-matmul grid step: the row and
    expert blocks in their two pipeline buffers, the f32 result block
    in its two, the f32 accumulator and the product it adds, every last
    axis padded to whole 128-lane tiles as VMEM lays it out."""
    f32 = 4
    lanes = lambda x: -(-x // LANE_MULTIPLE) * LANE_MULTIPLE  # noqa: E731
    blocks = (tm * lanes(tk) + tk * lanes(tn)) * dtype_bytes
    return 2 * blocks + 4 * tm * lanes(tn) * f32


def _gmm_axis_tiles(size: int) -> List[int]:
    """The legal tiles of an expert axis: the whole of it, or a whole
    number of 128 lanes that divides it."""
    return [size] + [t for t in range(LANE_MULTIPLE, size, LANE_MULTIPLE)
                     if size % t == 0]


# ---------------------------------------------------------------------------
# Table entries: schema, validation, matching
# ---------------------------------------------------------------------------

# Entry schema (one JSON object per shape class):
#   kernel      str, one of KERNELS                          (required)
#   seq_bucket  int pow2 — required for flash kernels, optional
#               (wildcard) for paged_attn
#   head_dim / n_heads / n_kv_heads   int or null (wildcard)
#   dtype       canonical dtype str or "*"/null
#   causal      bool or null
#   generation  backend_generation() slug or "*"/null
#   page_size   int or null — paged_attn only
#   block_q / block_k   int — flash kernels
#   head_block  int — paged_attn (KV heads per compute group)
#   provenance  str — where the numbers came from (r05 sweep, seed, …)

_MATCH_FIELDS = ("head_dim", "n_heads", "n_kv_heads", "dtype", "causal",
                 "generation", "page_size")


def entry_key(entry: Dict[str, Any]) -> str:
    """Compact human identity for messages and sweep output."""
    parts = [str(entry.get("kernel", "?"))]
    sb = entry.get("seq_bucket")
    parts.append(f"s{sb}" if sb else "s*")
    for field, tag in (("head_dim", "d"), ("n_heads", "h"),
                       ("n_kv_heads", "kv"), ("page_size", "p")):
        v = entry.get(field)
        if v not in _WILDCARD:
            parts.append(f"{tag}{v}")
    dt = entry.get("dtype")
    parts.append(dt if dt not in _WILDCARD else "*")
    causal = entry.get("causal")
    if causal is not None:
        parts.append("causal" if causal else "bidir")
    gen = entry.get("generation")
    if gen not in _WILDCARD:
        parts.append(str(gen))
    return "/".join(parts)


def _int_field(entry: Dict[str, Any], field: str,
               errs: List[str]) -> Optional[int]:
    v = entry.get(field)
    if v in _WILDCARD:
        return None
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        errs.append(f"{field} must be a positive int or null, got {v!r}")
        return None
    return v


def validate_entry(entry: Dict[str, Any],
                   budget: int = VMEM_BUDGET_BYTES) -> List[str]:
    """All the reasons ``entry`` is illegal (empty list = legal):
    divisibility, dtype-lane/sublane legality, and the analytic VMEM
    estimate vs the scoped budget. Shared verbatim by the loader's
    reject-with-warning path, ``tile_sweep.py --validate``, and the
    TPU001 table lint — one legality definition, three gates."""
    errs: List[str] = []
    kernel = entry.get("kernel")
    if kernel not in KERNELS:
        return [f"unknown kernel {kernel!r}; valid: {KERNELS}"]
    dtype = entry.get("dtype")
    if dtype in _WILDCARD:
        floor, nbytes = SUBLANE_FLOOR_STRICTEST, 4
    elif dtype in SUBLANE_FLOOR:
        floor, nbytes = SUBLANE_FLOOR[dtype], DTYPE_BYTES[dtype]
    else:
        errs.append(f"unknown dtype {dtype!r}; known: "
                    f"{sorted(SUBLANE_FLOOR)} or \"*\"")
        floor, nbytes = SUBLANE_FLOOR_STRICTEST, 4
    sb = _int_field(entry, "seq_bucket", errs)
    if sb is not None and sb & (sb - 1):
        errs.append(f"seq_bucket {sb} must be a power of two")
        sb = None
    head_dim = _int_field(entry, "head_dim", errs) or 128
    n_heads = _int_field(entry, "n_heads", errs) or 16
    n_kv = _int_field(entry, "n_kv_heads", errs)

    if kernel == "paged_attn":
        hb = entry.get("head_block", 1)
        if not isinstance(hb, int) or isinstance(hb, bool) or hb < 1:
            errs.append(f"head_block must be a positive int, got {hb!r}")
        elif hb > 1:
            if n_kv is None:
                errs.append("head_block > 1 requires a concrete "
                            "n_kv_heads (divisibility is unknowable "
                            "against a wildcard)")
            elif n_kv % hb:
                errs.append(f"head_block {hb} does not divide "
                            f"n_kv_heads {n_kv}")
        page_size = _int_field(entry, "page_size", errs) or 64
        vm = paged_vmem_bytes(page_size, n_heads, n_kv or n_heads,
                              head_dim, nbytes)
        if vm > budget:
            errs.append(f"VMEM estimate {vm} bytes exceeds the "
                        f"{budget}-byte scoped budget")
        return errs

    # flash kernels: (block_q, block_k) as independent knobs
    if sb is None and "seq_bucket must" not in " ".join(errs):
        errs.append(f"{kernel} entries require a concrete seq_bucket")
    bq = _int_field(entry, "block_q", errs)
    bk = _int_field(entry, "block_k", errs)
    if bq is None or bk is None:
        if "block_q" not in entry or "block_k" not in entry:
            errs.append(f"{kernel} entries require block_q and block_k")
        return errs
    if sb is not None:
        if sb % bq:
            errs.append(f"block_q {bq} does not divide seq_bucket {sb}")
        if sb % bk:
            errs.append(f"block_k {bk} does not divide seq_bucket {sb}")
    if bq % floor:
        errs.append(f"block_q {bq} is not a multiple of the "
                    f"{dtype or '*'} sublane floor {floor}")
    if bk % LANE_MULTIPLE:
        errs.append(f"block_k {bk} is not a multiple of the 128 lane "
                    "tile (the score tile's lane axis)")
    # the longest sequence of the bucket is the bucket itself
    vm = flash_vmem_bytes(kernel, bq, bk, head_dim, nbytes, seq=sb or 0)
    if vm > budget:
        errs.append(f"VMEM estimate {vm} bytes exceeds the "
                    f"{budget}-byte scoped budget (the r05 wall that "
                    "rejected 2048-edge tiles)")
    return errs


def _entry_sort_key(entry: Dict[str, Any]) -> Tuple:
    return (str(entry.get("kernel", "")),
            entry.get("seq_bucket") or 0,
            str(entry.get("dtype") or "*"),
            not bool(entry.get("causal")),
            str(entry.get("generation") or "*"),
            entry.get("head_dim") or 0,
            entry.get("n_heads") or 0)


@dataclasses.dataclass
class TileTable:
    """A loaded tile table: validated entries plus the rejects (kept so
    ``tile_sweep.py --validate`` and TPU001 can report them)."""

    entries: List[Dict[str, Any]]
    rejected: List[Tuple[Dict[str, Any], List[str]]]
    path: Optional[str] = None
    version: int = 1

    def lookup(self, kernel: str, *, seq: int, head_dim: int,
               n_heads: int, n_kv_heads: int, dtype: Any, causal: bool,
               generation: str,
               page_size: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Most-specific entry matching the shape class, or None.

        A field matches when the entry pins the same value or carries a
        wildcard; specificity = count of concretely-matched fields, so
        a chip-generation-pinned row outranks a ``"*"`` seed row.
        """
        bucket = seq_bucket(seq)
        want = {"head_dim": head_dim, "n_heads": n_heads,
                "n_kv_heads": n_kv_heads, "dtype": dtype_name(dtype),
                "causal": bool(causal), "generation": generation,
                "page_size": page_size}
        best, best_score = None, -1
        for e in self.entries:
            if e.get("kernel") != kernel:
                continue
            esb = e.get("seq_bucket")
            if esb is not None and esb != bucket:
                continue
            score = 1 if esb is not None else 0
            ok = True
            for field in _MATCH_FIELDS:
                ev = e.get(field)
                if ev in _WILDCARD:
                    continue
                if want[field] is None or ev != want[field]:
                    ok = False
                    break
                score += 1
            if ok and score > best_score:
                best, best_score = e, score
        return best

    def to_dict(self) -> Dict[str, Any]:
        entries = sorted(self.entries, key=_entry_sort_key)
        return {"version": self.version,
                "vmem_budget_bytes": VMEM_BUDGET_BYTES,
                "entries": entries}


DEFAULT_TABLE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "tile_table.json")


def load_table(path: Optional[str] = None, *, strict: bool = False,
               warn: bool = True) -> TileTable:
    """Load and validate a tile table.

    Non-strict (the runtime path): an unreadable file or an illegal
    entry is NEVER a failure — bad rows are dropped with a warning and
    the analytic fallback serves their shape classes. Strict (the
    ``tile_sweep.py --validate`` gate): any problem raises.
    """
    path = path or DEFAULT_TABLE_PATH
    if not os.path.exists(path):
        if strict:
            raise FileNotFoundError(f"tile table missing: {path}")
        return TileTable([], [], path=path)
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (ValueError, OSError) as e:
        # unreadable (permissions, replaced by a directory) and
        # unparseable tables take the same never-fail path; the parse
        # failure rides `rejected` so the TPU001 table lint still sees
        # a broken commit (a missing-entries table lints green only
        # when it is GENUINELY empty)
        if strict:
            raise ValueError(f"tile table {path} is unreadable or not "
                             f"valid JSON: {e}")
        if warn:
            warnings.warn(f"tile table {path} unreadable ({e}); "
                          "falling back to analytic tile selection",
                          stacklevel=2)
        return TileTable([], [({}, [f"table unreadable or not valid "
                                    f"JSON: {e}"])], path=path)
    entries: List[Dict[str, Any]] = []
    rejected: List[Tuple[Dict[str, Any], List[str]]] = []
    for entry in raw.get("entries", []):
        errs = validate_entry(entry)
        if errs:
            if strict:
                raise ValueError(
                    f"tile table {path} entry {entry_key(entry)} is "
                    f"illegal: {'; '.join(errs)}")
            if warn:
                warnings.warn(
                    f"tile table entry {entry_key(entry)} rejected "
                    f"({'; '.join(errs)}); the analytic fallback serves "
                    "this shape class", stacklevel=2)
            rejected.append((entry, errs))
        else:
            entries.append(entry)
    return TileTable(entries, rejected, path=path,
                     version=int(raw.get("version", 1)))


def save_table(table: TileTable, path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(table.to_dict(), f, indent=2, sort_keys=True)
        f.write("\n")


_TABLE_CACHE: Optional[TileTable] = None


def active_table() -> TileTable:
    global _TABLE_CACHE
    if _TABLE_CACHE is None:
        _TABLE_CACHE = load_table()
    return _TABLE_CACHE


@contextlib.contextmanager
def table_override(table) -> Iterator[TileTable]:
    """Swap the active table for a test or an experiment: accepts a
    :class:`TileTable` or a path."""
    global _TABLE_CACHE
    prev = _TABLE_CACHE
    _TABLE_CACHE = table if isinstance(table, TileTable) else load_table(
        table)
    try:
        yield _TABLE_CACHE
    finally:
        _TABLE_CACHE = prev


# ---------------------------------------------------------------------------
# Resolution: kernel key + shape class -> TileConfig
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One resolved tile choice plus where it came from (``table``:
    committed measurement, ``fallback``: analytic VMEM fit,
    ``override``: caller pinned it)."""

    kernel: str
    block_q: int = 0
    block_k: int = 0
    head_block: int = 0
    source: str = "fallback"
    tiling: Tuple[int, ...] = ()      # gmm: (tm, tk, tn)

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"kernel": self.kernel, "source": self.source}
        if self.kernel == "paged_attn":
            d["head_block"] = self.head_block
        elif self.kernel == "gmm":
            d["tiling"] = list(self.tiling)
        else:
            d["block_q"] = self.block_q
            d["block_k"] = self.block_k
        return d


_RECORDERS: List[List[Dict[str, Any]]] = []


@contextlib.contextmanager
def record_resolutions() -> Iterator[List[Dict[str, Any]]]:
    """Collect every tile resolution made inside the block — the bench
    harness wraps a config's run in this so the artifact row carries
    ``tile_config`` (resolved blocks + source) and an A/B round can
    attribute a throughput move to a table change."""
    buf: List[Dict[str, Any]] = []
    _RECORDERS.append(buf)
    try:
        yield buf
    finally:
        _RECORDERS.remove(buf)


def _record(cfg: TileConfig, shape: Dict[str, Any]) -> TileConfig:
    if _RECORDERS:
        d = cfg.as_dict()
        d["shape"] = shape
        for buf in _RECORDERS:
            buf.append(d)
    return cfg


def summarize_resolutions(buf: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Order-preserving dedup of a recorder buffer for the bench row."""
    seen, out = set(), []
    for d in buf:
        key = (d["kernel"], d.get("block_q"), d.get("block_k"),
               d.get("head_block"), tuple(d.get("tiling", ())), d["source"])
        if key in seen:
            continue
        seen.add(key)
        out.append(d)
    return out


def _fallback_flash(kernel: str, seq: int, head_dim: int,
                    dtype: Any) -> Tuple[int, int]:
    """Analytic tile choice when the table has no entry: the largest
    square pow2 edge ≤ the measured cap that fits the VMEM budget."""
    nbytes = DTYPE_BYTES.get(dtype_name(dtype), 4)
    edge = min(MAX_TILE_EDGE, seq_bucket(seq))
    while edge > 1:
        if flash_vmem_bytes(kernel, edge, edge, head_dim, nbytes,
                            seq=seq) <= VMEM_BUDGET_BYTES:
            return edge, edge
        edge //= 2
    return 1, 1


def flash_bwd_fuses(seq: int, head_dim: int, dtype: Any) -> bool:
    """Which backward a shape takes (``ops/attention.py:_flash_bwd``):
    the fused kernel wherever a dQ row of ``seq × head_dim`` fits the
    budget beside the smallest legal tile, the dQ and dK/dV pair past
    that. The shape decides alone, so a recorded resolution
    (:func:`record_resolutions`) says which path ran."""
    nbytes = DTYPE_BYTES.get(dtype_name(dtype), 4)
    return flash_vmem_bytes("flash_bwd_fused", LANE_MULTIPLE, LANE_MULTIPLE,
                            head_dim, nbytes, seq=seq) <= VMEM_BUDGET_BYTES


def resolve_flash(kernel: str, *, seq: int, head_dim: int, n_heads: int,
                  n_kv_heads: int, dtype: Any, causal: bool,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None,
                  generation: Optional[str] = None) -> TileConfig:
    """Resolve one flash kernel's ``(block_q, block_k)``.

    Explicit knobs win untouched (``source="override"`` — the kernel's
    own divisibility check stays the loud guard for a bad override);
    otherwise the table's most-specific entry, fitted to divisors of
    the actual ``seq``; otherwise the analytic VMEM fallback. A partial
    override pins one knob and resolves the other.
    """
    if kernel not in KERNELS or kernel == "paged_attn":
        raise ValueError(f"not a flash kernel key: {kernel!r}")
    shape = {"seq": seq, "head_dim": head_dim, "n_heads": n_heads,
             "n_kv_heads": n_kv_heads, "dtype": dtype_name(dtype),
             "causal": bool(causal)}
    if block_q is not None and block_k is not None:
        return _record(TileConfig(kernel, int(block_q), int(block_k),
                                  source="override"), shape)
    gen = generation or backend_generation()
    entry = active_table().lookup(
        kernel, seq=seq, head_dim=head_dim, n_heads=n_heads,
        n_kv_heads=n_kv_heads, dtype=dtype, causal=causal, generation=gen)
    if entry is not None:
        bq, bk, source = entry["block_q"], entry["block_k"], "table"
    else:
        bq, bk = _fallback_flash(kernel, seq, head_dim, dtype)
        source = "fallback"
    bq, bk = fit_block(seq, bq), fit_block(seq, bk)
    if block_q is not None:
        bq, source = int(block_q), "override"
    if block_k is not None:
        bk, source = int(block_k), "override"
    return _record(TileConfig(kernel, bq, bk, source=source), shape)


def resolve_paged(*, max_seq_len: int, page_size: int, n_heads: int,
                  n_kv_heads: int, head_dim: int, dtype: Any,
                  head_block: Optional[int] = None,
                  generation: Optional[str] = None) -> TileConfig:
    """Resolve the paged decode kernel's KV ``head_block`` group size.

    Same precedence as the flash path; a table entry whose head_block
    does not divide THIS shape's ``n_kv_heads`` degrades to the safe
    per-head loop (1) rather than raising — never a compile failure
    from a table row.
    """
    shape = {"max_seq_len": max_seq_len, "page_size": page_size,
             "n_heads": n_heads, "n_kv_heads": n_kv_heads,
             "head_dim": head_dim, "dtype": dtype_name(dtype)}
    if head_block is not None:
        return _record(TileConfig("paged_attn",
                                  head_block=int(head_block),
                                  source="override"), shape)
    gen = generation or backend_generation()
    entry = active_table().lookup(
        "paged_attn", seq=max_seq_len, head_dim=head_dim,
        n_heads=n_heads, n_kv_heads=n_kv_heads, dtype=dtype, causal=True,
        generation=gen, page_size=page_size)
    hb, source = 1, "fallback"
    if entry is not None:
        hb, source = int(entry.get("head_block", 1)), "table"
        if n_kv_heads % hb:
            hb, source = 1, "fallback"
    return _record(TileConfig("paged_attn", head_block=hb, source=source),
                   shape)


def _fallback_gmm(k: int, n: int, nbytes: int) -> Tuple[int, int, int]:
    """The rule: an expert arrives in the largest legal ``tk x tn``
    pieces under :data:`GMM_TILE_BYTES` (of two equal ones the wider,
    whose rows lie whole in HBM)."""
    fits = [(tk * tn, tn, tk) for tk in _gmm_axis_tiles(k)
            for tn in _gmm_axis_tiles(n) if tk * tn * nbytes <= GMM_TILE_BYTES]
    _, tn, tk = max(fits) if fits else (0, min(_gmm_axis_tiles(n)),
                                        min(_gmm_axis_tiles(k)))
    return GMM_ROW_TILE, tk, tn


def resolve_gmm(*, m: int, k: int, n: int, dtype: Any,
                tiling: Optional[Tuple[int, int, int]] = None) -> TileConfig:
    """Resolve the grouped matmul's ``(tm, tk, tn)`` for ``(m, k) x
    (groups, k, n)``: an explicit ``tiling`` untouched, else the
    analytic rule (:func:`_fallback_gmm`). The row tile never exceeds
    the rows there are (rounded up to the dtype's sublane tile)."""
    name = dtype_name(dtype)
    shape = {"m": m, "k": k, "n": n, "dtype": name}
    if tiling is not None:
        return _record(TileConfig("gmm", tiling=tuple(map(int, tiling)),
                                  source="override"), shape)
    tm, tk, tn = _fallback_gmm(k, n, DTYPE_BYTES.get(name, 4))
    floor = SUBLANE_FLOOR.get(name, SUBLANE_FLOOR_STRICTEST)
    return _record(TileConfig("gmm", tiling=(min(tm, -(-m // floor) * floor),
                                             tk, tn)), shape)
