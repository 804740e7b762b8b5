"""Device-mesh construction and logical-axis sharding rules.

This is the heart of the parallelism the reference platform *lacks* (SURVEY.md
§2c): the reference only offers process-level data parallelism (TFJob PS mode,
MPIJob ring-allreduce, PyTorchJob DDP — see
``/root/reference/kubeflow/tf-training/tf-job-operator.libsonnet:14-46``,
``/root/reference/kubeflow/mpi-job/mpi-operator.libsonnet``). Here TP/PP/SP/EP
are first-class mesh axes, and XLA emits the collectives over ICI.

Physical mesh axes
------------------
``("dcn", "dp", "pp", "tp")`` — cross-slice data, in-slice data,
pipeline-stage, and tensor axes. ``dcn`` is the multi-slice axis: its
collectives ride the data-center network between TPU slices (the
reference's analogue is multi-host MPI ring allreduce over the pod
network, ``/root/reference/kubeflow/mpi-job/mpi-operator.libsonnet:283-289``),
so only the once-per-step gradient allreduce is mapped onto it — never
per-layer tensor collectives. On a single slice ``dcn`` has size 1 and
vanishes from the compiled program. Two further *logical* parallelism
forms ride these physical axes, which is the standard TPU mapping:

- **sequence/context parallel (sp)** shards activations' sequence dimension
  over the ``tp`` group (Megatron-style sequence parallelism: the tensor
  group is already exchanging activations per layer, so the sequence shards
  ride the same ICI neighbours; ring attention runs over the same axis).
- **expert parallel (ep)** shards MoE experts over the ``dp`` group
  (DeepSpeed-MoE-style EP-on-DP: tokens all_to_all within the dp group).

Logical axis names used by models are mapped to mesh axes through a rules
table so a model is written once and resharded by swapping rules.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
from typing import (
    Any,
    Dict,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu import compat

log = logging.getLogger(__name__)

MESH_AXES = ("dcn", "dp", "pp", "tp")

# logical axis -> mesh axis (or None = replicated). Order matters only for
# first-match lookup; each logical name appears once.
AxisRules = Tuple[Tuple[str, Optional[Union[str, Tuple[str, ...]]]], ...]

DEFAULT_RULES: AxisRules = (
    ("batch", ("dcn", "dp")),  # per-example batch dim: outer-dp over DCN × dp
    ("stage", ("pp",)),        # stacked pipeline-stage dim on stage-stacked params
    ("embed", None),           # d_model dim of activations: replicated in tp group
    ("seq", ("tp",)),          # sequence-parallel regions (norms/residual)
    ("heads", ("tp",)),        # attention heads
    ("kv", None),              # per-head dim
    ("mlp", ("tp",)),          # ffn hidden
    ("vocab", ("tp",)),        # embedding/unembedding vocab dim
    ("expert", ("dp",)),       # MoE experts ride the dp axis (EP-on-DP)
    ("expert_mlp", ("tp",)),   # within-expert ffn hidden
)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Shape of the device mesh. Product must equal the device count.

    ``dcn`` is the number of TPU slices joined over DCN (outer data
    parallelism); ``dp``/``pp``/``tp`` describe the per-slice layout."""

    dp: int = 1
    pp: int = 1
    tp: int = 1
    dcn: int = 1

    @property
    def size(self) -> int:
        return self.dcn * self.dp * self.pp * self.tp

    @property
    def slice_size(self) -> int:
        """Chips per slice (mesh size within one ICI domain)."""
        return self.dp * self.pp * self.tp

    def axis_sizes(self) -> Tuple[int, int, int, int]:
        return (self.dcn, self.dp, self.pp, self.tp)


def auto_mesh_config(
    n_devices: int, *, pp: int = 1, tp: Optional[int] = None
) -> MeshConfig:
    """Pick a mesh shape for ``n_devices``.

    Defaults to pure data parallelism with a modest tp dimension when the
    device count allows: tp = gcd(n/pp, 2) unless given. Callers with real
    topology knowledge should construct :class:`MeshConfig` directly.
    """
    if n_devices % pp:
        raise ValueError(f"pp={pp} does not divide device count {n_devices}")
    rem = n_devices // pp
    if tp is None:
        tp = 2 if rem % 2 == 0 and rem > 1 else 1
    if rem % tp:
        raise ValueError(f"tp={tp} does not divide {rem}")
    return MeshConfig(dp=rem // tp, pp=pp, tp=tp)


def create_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with axes ``("dcn", "dp", "pp", "tp")``.

    On real TPU slices, ``mesh_utils.create_device_mesh`` lays the axes out so
    the innermost (tp) axis falls on ICI-adjacent chips — tp/sp collectives
    (the per-layer ones) ride the fastest links, dp allreduce amortises over
    the step. With ``dcn > 1`` (multi-slice), the hybrid mesh builder places
    the dcn axis across slices so exactly one collective — the gradient
    allreduce — crosses DCN, and everything else stays on ICI.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if config is None:
        config = auto_mesh_config(len(devs))
    if config.size != len(devs):
        raise ValueError(
            f"mesh {config.axis_sizes()} needs {config.size} devices, have {len(devs)}"
        )
    if devices is None and devs[0].platform == "tpu":
        from jax.experimental import mesh_utils

        if config.dcn > 1:
            arr = mesh_utils.create_hybrid_device_mesh(
                (1, config.dp, config.pp, config.tp),
                dcn_mesh_shape=(config.dcn, 1, 1, 1),
                devices=devs,
            )
        else:
            arr = mesh_utils.create_device_mesh(
                config.axis_sizes(), devices=devs)
    else:
        # virtual/explicit devices: dcn-major order, i.e. devices are grouped
        # into contiguous per-slice blocks (matches how jax orders devices by
        # process and how the operator assigns ranks slice-major)
        arr = np.asarray(devs).reshape(config.axis_sizes())
    return Mesh(arr, MESH_AXES)


def logical_to_mesh_axes(
    logical_axes: Sequence[Optional[str]], rules: AxisRules = DEFAULT_RULES
) -> PartitionSpec:
    """Map a tuple of logical axis names (None = replicated) to a PartitionSpec."""
    table = dict(rules)
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        if name not in table:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        mesh_axes = table[name]
        if mesh_axes is None:
            out.append(None)
        elif isinstance(mesh_axes, str):
            out.append(mesh_axes)
        elif len(mesh_axes) == 1:
            out.append(mesh_axes[0])
        else:
            out.append(tuple(mesh_axes))
    # trim trailing Nones for canonical form
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def data_parallel_size(mesh: Mesh) -> int:
    """Global batch-sharding width: product of the dcn and dp axis sizes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("dcn", 1) * sizes.get("dp", 1)


def _filter_spec(spec: PartitionSpec, keep) -> PartitionSpec:
    """Rebuild ``spec`` keeping only axis names where ``keep(name)``,
    collapsing emptied entries to None and trimming trailing Nones."""
    out = []
    for entry in spec:
        if entry is None or entry is PartitionSpec.UNCONSTRAINED:
            out.append(entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes if keep(a))
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def spec_for_mesh(spec: PartitionSpec, mesh) -> PartitionSpec:
    """Drop axis names ``mesh`` does not have.

    Models and train steps are written against the full 4-axis rules
    (batch over ``("dcn", "dp")``); this keeps them runnable on reduced
    meshes — a plain dp/tp mesh, a collective-test mesh — where the
    missing axis would otherwise be a hard error. Dropping an absent axis
    is exact: an axis the mesh lacks has size 1, and sharding over a
    size-1 axis is replication."""
    names = set(mesh.axis_names)
    return _filter_spec(spec, names.__contains__)


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: AxisRules = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_mesh_axes(logical_axes, rules))


def shard_constraint(x, logical_axes, rules: AxisRules = DEFAULT_RULES):
    """``with_sharding_constraint`` by logical axis names.

    No-op only when no mesh is current (plain eager/test use); inside a mesh
    a malformed spec raises rather than silently dropping the constraint.
    Axis names the current mesh lacks are dropped (see
    :func:`spec_for_mesh`), as are axes that are *manual* at the current
    trace point: inside a shard_map region a manual axis is already a
    per-device dim, so a constraint over it is meaningless. A
    fully-manual region (every mesh axis bound) skips the constraint
    entirely.
    """
    spec = logical_to_mesh_axes(logical_axes, rules)
    mesh = compat.current_mesh()
    if getattr(mesh, "empty", True):
        return x
    spec = spec_for_mesh(spec, mesh)
    manual = compat.bound_axes(mesh.axis_names)
    if manual:
        if manual >= set(mesh.axis_names):
            return x
        spec = _filter_spec(spec, lambda a: a not in manual)
    return jax.lax.with_sharding_constraint(x, spec)


_PLACEMENT_RECORDERS: List[List[Dict[str, Any]]] = []
_PLACEMENTS_LOGGED: set = set()


@contextlib.contextmanager
def record_kernel_placements() -> Iterator[List[Dict[str, Any]]]:
    """Collect how ``shard_kernel`` placed every kernel traced inside
    the block: one entry per distinct (kernel, placement) — over how
    many devices, which logical axes split it how many ways, which it
    named but had to drop. Empty when nothing was traced under a
    multi-device mesh. The bench rows carry it next to ``tile_config``
    (``ops/autotune.py:record_resolutions`` is the same shape), and
    chip_smoke.py asserts on it."""
    buf: List[Dict[str, Any]] = []
    _PLACEMENT_RECORDERS.append(buf)
    try:
        yield buf
    finally:
        _PLACEMENT_RECORDERS.remove(buf)


def shard_kernel(name: str, fn, args, arg_axes, out_shape, out_axes,
                 rules: AxisRules = DEFAULT_RULES):
    """Run ``fn(*args)`` — a function whose body is the Pallas (Mosaic)
    kernel ``name`` — under whatever mesh is current.

    XLA cannot partition a Mosaic kernel. On the TPU backend a
    ``pallas_call`` traced under a multi-device jit raises ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map"), and inside a ``shard_map`` it is accepted only
    when EVERY mesh axis is manual. The CPU interpreter lowers kernels
    to plain HLO, which XLA partitions without complaint, so the
    virtual-device tests never saw this; the four-chip v5e host did
    (CHANGES.md PR 21). So: with no mesh, one device, or every axis
    already manual, ``fn`` is called directly; otherwise it runs in a
    ``shard_map`` that makes every not-yet-manual axis manual, each
    array split by its logical axes (``arg_axes``/``out_axes``: one
    tuple of logical names per array; ``out_shape``/``out_axes`` for the
    single output). A logical axis is applied only if it divides EVERY
    dim that carries it — q heads split without their kv heads would
    pair the wrong groups — and is dropped everywhere otherwise: every
    device then runs the kernel whole along that axis behind an
    all-gather. That costs, so it is never silent: each distinct
    placement is logged once (a warning when an axis was dropped) and
    handed to :func:`record_kernel_placements`. The caller vouches that ``fn``
    is independent along every split dim; nothing is exchanged between
    devices. (Plain XLA code whose split XLA cannot see goes the same
    way: the dense decode step's attention over merged KV-head lanes,
    ``models/transformer.py:_merged_step_attention``.)
    """
    mesh = compat.current_mesh()
    if mesh.size <= 1:  # no mesh (size 0) or a single device
        return fn(*args)
    free = set(mesh.axis_names) - compat.bound_axes(mesh.axis_names)
    if not free:
        return fn(*args)
    table = dict(rules)

    def width(name: str) -> int:
        entry = table.get(name) or ()
        entry = (entry,) if isinstance(entry, str) else entry
        return int(np.prod([mesh.shape[a] for a in entry if a in free]))

    labelled = list(zip(arg_axes, (a.shape for a in args)))
    labelled.append((out_axes, tuple(out_shape)))
    named = {a for axes, _ in labelled for a in axes if a is not None}
    uneven = {a for axes, shape in labelled for a, dim in zip(axes, shape)
              if a is not None and dim % width(a)}
    placement = {
        "kernel": name,
        "devices": int(mesh.size),
        "split": {a: width(a) for a in sorted(named - uneven)
                  if width(a) > 1},
        "dropped": sorted(a for a in uneven),
    }
    for buf in _PLACEMENT_RECORDERS:
        if placement not in buf:
            buf.append(placement)
    seen = json.dumps(placement, sort_keys=True)
    if seen not in _PLACEMENTS_LOGGED:
        _PLACEMENTS_LOGGED.add(seen)
        if uneven:
            log.warning(
                "kernel %s on %d devices: %s cannot split every dim "
                "carrying it (shapes %s) and is dropped, so every device "
                "runs the kernel whole along it; split %s", name,
                mesh.size, {a: width(a) for a in placement["dropped"]},
                [shape for _, shape in labelled], placement["split"])
        else:
            log.info("kernel %s on %d devices: split %s", name, mesh.size,
                     placement["split"])

    def spec(axes) -> PartitionSpec:
        kept = [None if a in uneven else a for a in axes]
        return _filter_spec(
            spec_for_mesh(logical_to_mesh_axes(kept, rules), mesh),
            free.__contains__)

    # check_vma off: pallas_call outputs carry no varying-axes type
    return compat.shard_map(
        fn, mesh=mesh, in_specs=tuple(spec(a) for a in arg_axes),
        out_specs=spec(out_axes), axis_names=free, check_vma=False)(*args)


def mesh_context(mesh: Mesh):
    """Context manager making ``mesh`` current for bare-PartitionSpec
    sharding constraints (``kubeflow_tpu/compat`` owns the jax call)."""
    return compat.mesh_context(mesh)


def shape_aware_spec(
    spec: PartitionSpec, shape: Tuple[int, ...], mesh: Mesh
) -> PartitionSpec:
    """Drop sharding on dims the mesh cannot divide evenly.

    Lets one rules table serve models whose small dims (e.g. GQA kv heads)
    don't divide a large tp axis: those dims replicate instead of erroring.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    padded = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    for dim, axis in zip(shape, padded):
        if axis is None:
            out.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        out.append(axis if dim % n == 0 else None)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def validate_mesh_for_model(
    config: MeshConfig, *, n_heads: int, d_ff: int, n_experts: int = 0
) -> None:
    """Fail fast when a mesh shape cannot shard a model's dimensions."""
    if n_heads % config.tp:
        raise ValueError(f"tp={config.tp} must divide n_heads={n_heads}")
    if d_ff % config.tp:
        raise ValueError(f"tp={config.tp} must divide d_ff={d_ff}")
    if n_experts and n_experts % config.dp != 0:
        raise ValueError(
            f"dp={config.dp} must divide n_experts={n_experts} "
            f"(experts shard over the dp axis)"
        )
