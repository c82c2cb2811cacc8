"""Sharding policy and logical-axis rules.

Parameters declare *logical* shard axes (e.g. ``(None, "model")``) next to
their shapes in ``models/params.py`` -- a single source of truth.  The rules
here turn them into concrete ``PartitionSpec``s with a divisibility fallback
(a dim that does not divide the mesh axis is replicated instead, and the
fallback is recorded so EXPERIMENTS.md can report it).

Activation constraints go through :func:`constrain`, which is a no-op unless
a mesh has been installed via :func:`set_mesh` -- so the exact same model code
runs in single-device CPU smoke tests and under the 512-device dry-run.

``ShardingPolicy`` carries the performance knobs that the §Perf hillclimb
flips (sequence-parallel residuals, ZeRO-1 optimizer sharding, remat).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Performance-relevant distribution knobs (hillclimb levers)."""

    seq_parallel: bool = True  # residual stream seq-sharded over 'model' between blocks
    shard_heads: bool = True  # attention projections column-sharded over 'model'
    zero1: bool = True  # optimizer moments additionally sharded over 'data'
    remat: bool = True  # activation checkpointing on the layer scan
    fsdp: bool = True  # shard params (and moments) over 'data' too (ZeRO-3-style)
    attn_chunk: int = 2048  # query-chunked attention for long sequences (0 = off)
    donate: bool = True  # donate train state / decode cache buffers (aliasing)
    cache_seq_axis: Optional[str] = "model"  # decode KV-cache sequence shard axis
    scan_unroll: bool = False  # fully unroll layer scans (dry-run cost accounting)
    batch_axes: tuple[str, ...] = ("data",)  # expanded to ("pod","data") multi-pod


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


class mesh_context:
    """``with mesh_context(mesh): ...`` installs the mesh for constrain()."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        self.prev = get_mesh()
        set_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_mesh(self.prev)
        return False


def _prune_absent(mesh: Mesh, axis):
    """Drop axis names the mesh does not define from a logical axis entry,
    so logical specs naming 'model' degrade to replicated on data-only
    meshes (the host mesh train.py/fedzoo.py build on CPU).  A tuple entry
    keeps only its present names -- emitting an absent name inside a
    PartitionSpec would fail at NamedSharding placement."""
    if axis is None or not isinstance(axis, (tuple, list)):
        return axis if (axis is None or axis in mesh.axis_names) else None
    kept = tuple(a for a in axis if a in mesh.axis_names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def _axis_size(mesh: Mesh, axis) -> int:
    """Product of mesh-axis sizes; absent axis names count as size 1."""
    axis = _prune_absent(mesh, axis)
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= _axis_size(mesh, a)
        return n
    return mesh.shape[axis]


def spec_with_fallback(mesh: Mesh, shape: tuple[int, ...], axes: tuple[Any, ...]) -> P:
    """Logical axes -> PartitionSpec, replicating any non-divisible dim."""
    out = []
    for dim, ax in zip(shape, axes):
        ax = _prune_absent(mesh, ax)
        if ax is None:
            out.append(None)
            continue
        n = _axis_size(mesh, ax)
        out.append(ax if (n > 1 and dim % n == 0) else None)
    return P(*out)


def constrain(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint against the installed mesh (no-op without).

    IMPORTANT semantics: a ``None`` entry here means UNCONSTRAINED (leave the
    dim to GSPMD propagation), NOT replicated.  Pinning activations to
    replicated on the batch dim was measured to cost 80 GB/device of
    all-gathered attention temporaries on qwen1.5 train_4k (EXPERIMENTS.md
    §Perf).  Input/param shardings (spec_with_fallback) keep None=replicated.

    The hint steers GSPMD propagation, which only Auto axes take part in
    (``launch.mesh.make_mesh`` builds every mesh of the repo so).  On a mesh
    with Explicit or Manual axes the sharding is part of the array's type
    and UNCONSTRAINED dims are refused, so the hint is skipped there.
    """
    mesh = get_mesh()
    if mesh is None or any(t != AxisType.Auto for t in mesh.axis_types):
        return x
    unc = P.UNCONSTRAINED
    full = tuple(axes) + (None,) * (x.ndim - len(axes))
    out = []
    for dim, ax in zip(x.shape, full):
        ax = _prune_absent(mesh, ax)
        if ax is None:
            out.append(unc)
            continue
        n = _axis_size(mesh, ax)
        out.append(ax if (n > 1 and dim % n == 0) else unc)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*out)))


def batch_axes(policy: ShardingPolicy, mesh: Optional[Mesh] = None) -> tuple[str, ...]:
    """Client/batch data axes; includes 'pod' when the mesh has one."""
    mesh = mesh or get_mesh()
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod",) + tuple(policy.batch_axes)
    return tuple(policy.batch_axes)


def param_pspecs_from_axes(mesh: Mesh, shape: tuple[int, ...], axes: tuple[Any, ...]) -> P:
    """Single-leaf convenience alias of :func:`spec_with_fallback`."""
    return spec_with_fallback(mesh, shape, axes)


def zero1_extend(mesh: Mesh, shape: tuple[int, ...], spec: P, data_axes: tuple[str, ...] = ("data",)) -> P:
    """ZeRO-1: extend a param spec with a 'data' shard on the largest
    still-replicated divisible dim.  Applied to optimizer moments so the
    Adam state never replicates across the data axis (DESIGN.md Sec. 6).
    """
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    st = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    free = [
        (dim, i)
        for i, (dim, s) in enumerate(zip(shape, st))
        if s is None and n_data > 1 and dim % n_data == 0 and dim >= n_data
    ]
    if not free:
        return P(*st)
    _, idx = max(free)
    new = list(st)
    new[idx] = data_axes if len(data_axes) > 1 else data_axes[0]
    return P(*new)
