"""The single call site for the jax SPMD surface (``shard_map``, the
varying-axes cast, ``axis_size``, the ambient mesh and its context
manager).

Everything here targets the installed jax (``pyproject.toml`` pins
``jax>=0.9``); there are no branches for older releases. The package
exists because these names moved or were renamed in every jax release
from 0.5 to 0.9: one module to edit on the next upgrade, enforced by
tpulint rule **TPU006** (``docs/COMPAT.md``) — the rest of the package
calls the functions re-exported here.
"""

from kubeflow_tpu.compat.jaxshim import (  # noqa: F401
    axis_size,
    bound_axes,
    current_mesh,
    mesh_context,
    pvary,
    shard_map,
)

__all__ = [
    "axis_size",
    "bound_axes",
    "current_mesh",
    "mesh_context",
    "pvary",
    "shard_map",
]
