"""TPU001 — Mosaic tile legality for Pallas BlockSpec shapes.

The TPU vector layout tiles the last two axes of every kernel block:
the last (*lane*) axis in units of 128, the second-to-last (*sublane*)
axis in units of 8 for f32 (16 for bf16, 32 for int8/fp8 — 8 is the
weakest legal floor, so that is what a static checker can enforce
without dtype inference). The only other legal value is the array's
own size in that dim. A block that breaks this compiles in interpret
mode — where CPU tests run — and then fails Mosaic lowering on real
hardware. That is exactly the PR 1 ``ops/bnconv.py`` bug: block sizes
came from ``_pick_block(dim, want)`` whose default ``floor=8`` happily
returns lane tiles of 8 — and the PR 6 ``ops/sampling.py`` one: a
``(1, Vp)`` block over a ``(B, Vp)`` array, which this rule used to
wave through as "a size-1 dim", and which the v5e refused.

Three detections:

1. a **literal** lane/sublane dim in a ``BlockSpec((...), ...)`` tuple
   that violates the floor — suppressed when the enclosing function
   guards untileable shapes with an XLA fallback branch (a call to a
   ``*tileable*`` predicate), because then the literal is only reached
   for shapes the guard admitted;
2. a dim that resolves to a ``_pick_block(..., floor=F)`` helper call
   with a lane-position ``F < 128`` — flagged even under a fallback
   guard, because the guard itself is typically computed with the same
   wrong floor (the PR 1 failure mode: ``_tileable`` said yes, Mosaic
   said no).

3. a **literal 1** in the lane/sublane position whose index-map entry
   is not the constant ``0``: a size-1 block dim is legal only when the
   array's dim is 1 too, and an index that moves along the dim says it
   is not. ``(1, block_q, 1)`` with ``lambda b, i, j: (b, i, 0)`` is
   fine; ``(1, Vp)`` with ``lambda b: (b, 0)`` is not.

**Table-resolved tiles** (the autotune plane): the flash/paged kernels'
block dims are now dynamic values resolved from
``kubeflow_tpu/ops/tile_table.json`` — unresolvable at the call site,
so detections 1/2 correctly stay silent there. The legality obligation
moves to the TABLE: when this checker reaches the plane's owner module
(``ops/autotune.py``) it lints every committed entry with the plane's
own ``validate_entry`` (divisibility, analytic VMEM estimate,
dtype-lane/sublane legality) and reports illegal rows against the JSON
file. The autotune module is loaded standalone (stdlib-only top level)
so the lint run never pays the ``kubeflow_tpu.ops`` jax import.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import sys
from typing import Iterable, Optional

from kubeflow_tpu.analysis import astutil
from kubeflow_tpu.analysis.findings import Finding
from kubeflow_tpu.analysis.registry import Checker, register_checker
from kubeflow_tpu.analysis.walker import ModuleInfo

LANE_MULTIPLE = 128
SUBLANE_MULTIPLE = 8  # f32 floor; bf16/int8 need 16/32 (see docstring)
PICK_BLOCK_DEFAULT_FLOOR = 8

# the autotune plane's owner module (triggers the table lint) and the
# committed table the findings anchor to
TABLE_OWNER = "kubeflow_tpu/ops/autotune.py"
TABLE_REL = "kubeflow_tpu/ops/tile_table.json"


def _ops_dir() -> str:
    return os.path.normpath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
        "ops"))


def _table_path() -> str:
    """Monkeypatch point for tests; the real table sits beside the
    autotune module."""
    return os.path.join(_ops_dir(), "tile_table.json")


def _autotune_module():
    """The validation logic lives in ONE place — the autotune plane.
    Reuse an already-imported module when present; otherwise load it
    standalone from file, skipping ``kubeflow_tpu.ops.__init__`` (whose
    attention import pulls jax — a multi-second tax per lint run the
    +25%-wall budget cannot afford)."""
    mod = sys.modules.get("kubeflow_tpu.ops.autotune")
    if mod is not None:
        return mod
    path = os.path.join(_ops_dir(), "autotune.py")
    spec = importlib.util.spec_from_file_location("_tpulint_autotune", path)
    mod = importlib.util.module_from_spec(spec)
    # register BEFORE exec: the module's dataclasses resolve their
    # (string) annotations through sys.modules[cls.__module__]
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(spec.name, None)
        raise
    return mod


def _pick_block_floor(scope: ast.AST, node: ast.AST) -> Optional[int]:
    """If ``node`` is a Name assigned from a ``*_pick_block(...)`` call
    in ``scope``, return that call's ``floor`` (3rd positional or
    keyword; helper default 8 when the argument is absent). None = not
    a pick-block value, OR a floor expression that is not a literal —
    an unprovable floor stays silent (astutil contract), it does not
    get assumed to be the default."""
    if not isinstance(node, ast.Name) or scope is None:
        return None
    values = list(astutil.assignments_to(scope, node.id))
    if len(values) != 1 or not isinstance(values[0], ast.Call):
        return None
    call = values[0]
    name = astutil.call_name(call) or ""
    if not name.split(".")[-1].endswith("pick_block"):
        return None
    if len(call.args) >= 3:
        return astutil.const_int(call.args[2])
    for kw in call.keywords:
        if kw.arg == "floor":
            return astutil.const_int(kw.value)
    return PICK_BLOCK_DEFAULT_FLOOR


def _has_fallback_guard(fn: Optional[ast.AST]) -> bool:
    """Heuristic: the function consults a ``*tileable*`` predicate
    somewhere (the canonical shape-guard spelling in ops/)."""
    if fn is None:
        return False
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = astutil.call_name(node) or ""
            if "tileable" in name.split(".")[-1]:
                return True
    return False


def _index_moves(call: ast.Call, pos: int) -> bool:
    """True when the BlockSpec's index map is a lambda returning a
    tuple whose entry at ``pos`` (from the end) is anything but the
    constant 0. Maps the checker cannot read (a named function, a
    non-tuple body) say nothing."""
    index_map = call.args[1] if len(call.args) > 1 else next(
        (kw.value for kw in call.keywords if kw.arg == "index_map"), None)
    if not isinstance(index_map, ast.Lambda):
        return False
    body = index_map.body
    if not isinstance(body, ast.Tuple) or len(body.elts) < -pos:
        return False
    entry = body.elts[pos]
    return not (isinstance(entry, ast.Constant) and entry.value == 0)


@register_checker
class TileLegalityChecker(Checker):
    rule = "TPU001"
    name = "tile-legality"
    severity = "error"

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.call_name(node) or ""
            if name.split(".")[-1] != "BlockSpec" or not node.args:
                continue
            shape = node.args[0]
            if not isinstance(shape, (ast.Tuple, ast.List)):
                continue
            dims = shape.elts
            if not dims:
                continue
            fn = module.enclosing_function(node)
            guarded = _has_fallback_guard(fn)
            yield from self._check_dim(
                module, node, fn, dims[-1], guarded, lane=True)
            if len(dims) >= 2:
                yield from self._check_dim(
                    module, node, fn, dims[-2], guarded, lane=False)
        if module.rel.replace("\\", "/") == TABLE_OWNER:
            yield from self._check_table()

    def _check_table(self) -> Iterable[Finding]:
        """Lint the committed tile table with the autotune plane's own
        legality check — the table is where the kernels' now-dynamic
        block values actually come from, so it carries the tile-
        legality obligation the silent call sites shed."""
        path = _table_path()
        if not os.path.exists(path):
            yield Finding(
                rule=self.rule, severity=self.severity, path=TABLE_REL,
                line=1,
                message="committed tile table is missing (every tuned "
                        "kernel silently degrades to the analytic "
                        "fallback)",
                hint="restore kubeflow_tpu/ops/tile_table.json or "
                     "regenerate it with scripts/tile_sweep.py "
                     "--update-table")
            return
        at = _autotune_module()
        table = at.load_table(path, warn=False)
        for entry, errs in table.rejected:
            for err in errs:
                yield Finding(
                    rule=self.rule, severity=self.severity,
                    path=TABLE_REL, line=1,
                    message=f"tile table entry {at.entry_key(entry)}: "
                            f"{err}",
                    hint="fix the entry (or drop it — the analytic "
                         "fallback covers the shape class) and rerun "
                         "scripts/tile_sweep.py --validate")

    def _check_dim(self, module: ModuleInfo, call: ast.Call,
                   fn: Optional[ast.AST], dim: ast.AST, guarded: bool,
                   lane: bool) -> Iterable[Finding]:
        axis = "lane" if lane else "sublane"
        multiple = LANE_MULTIPLE if lane else SUBLANE_MULTIPLE

        floor = _pick_block_floor(fn, dim)
        if floor is not None:
            # detection 2: wrong pick-block floor; fallback guard does
            # not excuse this (the guard shares the floor)
            if floor % multiple != 0:
                src = getattr(dim, "id", "?")
                yield self.finding(
                    module, call,
                    f"{axis} block dim {src!r} comes from a pick-block "
                    f"helper with floor {floor}; Mosaic requires {axis} "
                    f"tiles in multiples of {multiple}",
                    hint=f"pass floor={multiple} (or larger) when picking "
                         f"a {axis}-axis block size, and use the same "
                         f"floor in the tileable-shape guard")
            return

        value = astutil.resolve_int(fn, dim)
        if value is None:
            return  # unresolvable (dynamic): the table lint covers it
        if value == 1:
            # detection 3: legal only as the array's whole (size-1) dim,
            # e.g. ops/attention.py's (1, block_q, 1) lse blocks — an
            # index map that moves along it says the array's is larger
            if _index_moves(call, -1 if lane else -2):
                yield self.finding(
                    module, call,
                    f"{axis} block dim 1 is walked by its index map, so "
                    f"the array's {axis} dim is larger than 1; Mosaic "
                    "accepts a size-1 block dim only when it is the "
                    "array's whole dim (interpret-mode CPU tests will "
                    "not catch it)",
                    hint="fold the walked dim out of the last two (e.g. "
                         "reshape (B, N) to (B, N // 128, 128) and block "
                         "(1, N // 128, 128)), or pass per-row scalars "
                         "through SMEM whole")
            return
        if value % multiple != 0 and not guarded:
            yield self.finding(
                module, call,
                f"{axis} block dim {value} is not a multiple of "
                f"{multiple}; Mosaic rejects this tile in compiled mode "
                f"(interpret-mode CPU tests will not catch it)",
                hint=f"use {axis} tiles in multiples of {multiple}, or "
                     "guard the kernel with an XLA fallback for "
                     "untileable shapes")
