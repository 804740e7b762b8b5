"""TPU006 — version-gated jax APIs outside ``compat/``.

The jax SPMD surface the platform stands on — ``shard_map``, the
varying-axes cast, ``axis_size``, the ambient mesh and its context
manager — moved, was renamed or was removed in every jax release from
0.5 to 0.9. A direct call parses, imports and passes every test that
does not trace it, then fails at trace time on a runtime with the
other jax. The repo policy (docs/COMPAT.md) is that
``kubeflow_tpu/compat/`` — written for the installed jax 0.9 — is the
single call site; this rule makes the policy mechanical, so the next
upgrade edits one module.

Table-driven: :data:`GATED_APIS` maps a dotted jax name to where it
stands across versions and the compat function to call instead.
Flagged, anywhere outside ``compat/``:

- attribute chains (``jax.shard_map(...)``, a bare
  ``jax.sharding.get_abstract_mesh`` reference);
- ``from jax import shard_map`` / ``from jax.sharding import set_mesh``
  style imports of a gated name;
- any import touching ``jax.experimental.shard_map`` (removed
  upstream).

``hasattr(jax, "shard_map")`` / ``getattr(..., None)`` probes pass the
name as a string and are deliberately not flagged — a probe cannot
crash.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, Tuple

from kubeflow_tpu.analysis import astutil
from kubeflow_tpu.analysis.findings import Finding
from kubeflow_tpu.analysis.registry import Checker, register_checker
from kubeflow_tpu.analysis.walker import ModuleInfo

SANCTIONED_DIR = "kubeflow_tpu/compat/"

# dotted api -> (availability window, sanctioned replacement)
GATED_APIS: Dict[str, Tuple[str, str]] = {
    "jax.shard_map":
        ("top-level since jax 0.6; signature changed with it",
         "kubeflow_tpu.compat.shard_map"),
    "jax.experimental.shard_map.shard_map":
        ("removed upstream", "kubeflow_tpu.compat.shard_map"),
    "jax.sharding.get_abstract_mesh":
        ("jax>=0.5", "kubeflow_tpu.compat.current_mesh"),
    "jax.sharding.use_mesh":
        ("renamed set_mesh; gone from the installed jax 0.9",
         "kubeflow_tpu.compat.mesh_context"),
    "jax.sharding.set_mesh":
        ("the jax>=0.9 name of use_mesh",
         "kubeflow_tpu.compat.mesh_context"),
    "jax.lax.pvary":
        ("deprecated in jax 0.9 for pcast", "kubeflow_tpu.compat.pvary"),
    "jax.lax.pcast":
        ("jax>=0.7", "kubeflow_tpu.compat.pvary"),
    "jax.lax.axis_size":
        ("jax>=0.5", "kubeflow_tpu.compat.axis_size"),
}

# gated import roots: importing the module at all is version-sensitive
GATED_MODULES: Dict[str, Tuple[str, str]] = {
    "jax.experimental.shard_map":
        ("removed upstream", "kubeflow_tpu.compat.shard_map"),
}


@register_checker
class VersionGateChecker(Checker):
    rule = "TPU006"
    name = "version-gated-api"
    severity = "error"

    def _emit(self, module: ModuleInfo, node: ast.AST, api: str,
              window: str, use: str) -> Finding:
        return self.finding(
            module, node,
            f"{api} is version-gated ({window}); only compat/ may "
            "touch version-sensitive jax APIs",
            hint=f"call {use} instead — compat/ is the one module a "
                 "jax upgrade has to edit")

    def check(self, module: ModuleInfo) -> Iterable[Finding]:
        # exact path-component prefix, not a substring: a sibling
        # "netcompat/" or a nested "*/compat/" must NOT be exempt
        if module.rel.startswith(SANCTIONED_DIR):
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute):
                name = astutil.dotted_name(node)
                if name in GATED_APIS:
                    window, use = GATED_APIS[name]
                    yield self._emit(module, node, name, window, use)
            elif isinstance(node, ast.ImportFrom) and node.module:
                mod = node.module
                if node.level:  # relative import: not a jax module
                    continue
                if mod in GATED_MODULES:
                    window, use = GATED_MODULES[mod]
                    yield self._emit(module, node, mod, window, use)
                    continue
                for alias in node.names:
                    full = f"{mod}.{alias.name}"
                    if full in GATED_APIS:
                        window, use = GATED_APIS[full]
                        yield self._emit(module, node, full, window, use)
                    elif full in GATED_MODULES:
                        # `from jax.experimental import shard_map`: the
                        # gated module pulled in via its parent package
                        window, use = GATED_MODULES[full]
                        yield self._emit(module, node, full, window, use)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    for root, (window, use) in GATED_MODULES.items():
                        if alias.name == root \
                                or alias.name.startswith(root + "."):
                            yield self._emit(module, node, alias.name,
                                             window, use)
