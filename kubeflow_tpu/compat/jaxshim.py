"""The jax surface the platform's SPMD code stands on, in one place.

Written against the installed jax (0.9): ``jax.shard_map`` with
``axis_names``/``check_vma``, the varying-manual-axes type system
(``jax.lax.pcast``), ``jax.lax.axis_size``, the abstract-mesh accessor
and the ``set_mesh`` context manager. There is no branch for an older
jax — ``pyproject.toml`` pins ``jax>=0.9``. What this module still buys
is a single call site: these APIs moved or were renamed in every jax
release from 0.5 to 0.9, and tpulint TPU006 keeps the rest of the
package calling them through here.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence, Set

import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Iterable[str]] = None,
              check_vma: bool = True):
    """``jax.shard_map``; ``axis_names=None`` means manual over every
    mesh axis (jax's own default), a subset makes the region
    partial-manual with the other axes left to the partitioner."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def axis_size(axis_name: str) -> int:
    """Size of a bound named axis — a Python int, so callers can build
    static ``ppermute`` permutations from it."""
    return jax.lax.axis_size(axis_name)


def pvary(x, axis_names: Sequence[str]):
    """Type ``x`` as varying over ``axis_names`` for the shard_map vma
    checker (scan carries initialised from replicated values need it).
    Axes ``x`` already varies over are skipped: ``pcast`` refuses to
    re-vary them."""
    have = getattr(jax.typeof(x), "vma", frozenset())
    need = tuple(a for a in axis_names if a not in have)
    if not need:
        return x
    return jax.lax.pcast(x, need, to="varying")


def bound_axes(axis_names: Iterable[str]) -> Set[str]:
    """Which of ``axis_names`` are bound as named axes at the current
    trace point (i.e. we are inside a shard_map manual region over
    them)."""
    out: Set[str] = set()
    for name in axis_names:
        try:
            jax.lax.axis_size(name)
        except NameError:
            continue
        out.add(name)
    return out


def current_mesh():
    """The ambient (abstract) mesh; ``.empty`` outside any mesh
    context."""
    return jax.sharding.get_abstract_mesh()


def mesh_context(mesh):
    """Context manager making ``mesh`` current for bare-PartitionSpec
    sharding constraints."""
    return jax.sharding.set_mesh(mesh)
