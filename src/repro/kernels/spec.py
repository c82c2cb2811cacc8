"""Declarative Pallas launch geometry: ``KernelSpec`` (DESIGN.md Sec. 4/7).

Every ``pallas_call`` in ``repro.kernels`` is constructed from a
``KernelSpec`` -- a declarative record of the launch geometry (grid, block
shapes, index maps, scratch accumulators, revisit semantics) that serves
two masters:

* ``spec.pallas_call(kernel)`` builds the REAL ``pl.pallas_call`` from the
  declaration, so the geometry the static linter sees is, by construction,
  the geometry the kernel launches with -- there is no parallel
  bookkeeping to drift out of sync;
* ``repro.analysis.kernel_audit`` enumerates the grid through the declared
  index maps and statically proves write-race freedom, accumulator
  init/dtype discipline, in-bounds addressing and VMEM-budget fit without
  executing (or even lowering) anything.

The ``revisit_axes`` / ``init_axes`` fields make the accumulator protocol
of the tiled kernels explicit:

* ``revisit_axes`` are the grid axes over which an output block is visited
  more than once (the reduction axes of a tiled accumulator kernel; TPU
  grids execute sequentially, so revisits of trailing axes are
  consecutive);
* ``init_axes`` are the grid axes whose ``program_id == 0`` conjunction
  guards the accumulator initialization (the ``pl.when`` zero/overwrite at
  the start of each reduction sweep).

A well-formed accumulator kernel has ``init_axes == revisit_axes`` -- a
strict subset means the accumulator is either stale across output blocks
or clobbered mid-sweep.  ``out_accumulates`` marks kernels (rff_grad) that
accumulate IN the output ref instead of a scratch buffer, so the
accumulator-dtype rule knows where the running sum lives.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: f32 tile alignment of the TPU vector unit: (sublane, lane).  Blocks are
#: physically padded up to these in VMEM, so the footprint model rounds the
#: two minor axes accordingly (the f32 figures; narrower dtypes pack denser,
#: making this a conservative over-estimate for bf16).
_SUBLANE = 8
_LANE = 128

#: Precision of every matmul inside the kernels.  On a TPU an f32 matmul at
#: the default precision is one bf16 pass, too coarse for the kernels'
#: parity tolerances at d in the thousands, so the kernels ask for f32
#: accuracy and never inherit the caller's ``default_matmul_precision``.
#: The multi-pass f32 matmul splits its f32 operands into bf16 parts held in
#: VMEM; ``repro.kernels.autotune`` charges those copies.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _padded_nbytes(shape: tuple[int, ...], dtype: Any) -> int:
    """VMEM bytes of one block, minor axes tile-padded."""
    shape = tuple(shape)
    if len(shape) >= 2:
        shape = shape[:-2] + (_round_up(shape[-2], _SUBLANE),
                              _round_up(shape[-1], _LANE))
    elif len(shape) == 1:
        shape = (_round_up(shape[0], _LANE),)
    return math.prod(shape) * jnp.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Logical (padded) shape + dtype of one kernel operand."""

    shape: tuple[int, ...]
    dtype: Any


@dataclasses.dataclass(frozen=True)
class BlockDecl:
    """One operand's BlockSpec: block shape + grid-cell -> block-index map."""

    block_shape: tuple[int, ...]
    index_map: Callable[..., tuple[int, ...]]


@dataclasses.dataclass(frozen=True)
class ScratchDecl:
    """One VMEM scratch buffer (accumulators of the tiled kernels)."""

    shape: tuple[int, ...]
    dtype: Any


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Declarative, introspectable geometry of one ``pallas_call``."""

    name: str  # e.g. "gp_score.tiled" -- carried into every violation
    grid: tuple[int, ...]
    in_shapes: tuple[ArraySpec, ...]
    in_specs: tuple[BlockDecl, ...]
    out_shapes: tuple[ArraySpec, ...]
    out_specs: tuple[BlockDecl, ...]
    scratch: tuple[ScratchDecl, ...] = ()
    revisit_axes: tuple[int, ...] = ()
    init_axes: tuple[int, ...] = ()
    out_accumulates: bool = False

    def __post_init__(self):
        assert len(self.in_shapes) == len(self.in_specs), self.name
        assert len(self.out_shapes) == len(self.out_specs), self.name

    # -- launch ------------------------------------------------------------

    def pallas_call(self, kernel: Callable, *, interpret: bool = False):
        """Build the real ``pl.pallas_call`` from this declaration."""
        single = len(self.out_shapes) == 1
        out_shape = [jax.ShapeDtypeStruct(tuple(o.shape), o.dtype)
                     for o in self.out_shapes]
        out_specs = [pl.BlockSpec(tuple(d.block_shape), d.index_map)
                     for d in self.out_specs]
        return pl.pallas_call(
            kernel,
            out_shape=out_shape[0] if single else out_shape,
            grid=tuple(self.grid),
            in_specs=[pl.BlockSpec(tuple(d.block_shape), d.index_map)
                      for d in self.in_specs],
            out_specs=out_specs[0] if single else out_specs,
            scratch_shapes=[pltpu.VMEM(tuple(s.shape), s.dtype)
                            for s in self.scratch],
            interpret=interpret,
        )

    # -- introspection (consumed by repro.analysis.kernel_audit) -----------

    def operands(self) -> Iterator[tuple[str, int, ArraySpec, BlockDecl]]:
        """Yield ``(role, index, ArraySpec, BlockDecl)`` for every operand."""
        for i, (a, b) in enumerate(zip(self.in_shapes, self.in_specs)):
            yield "in", i, a, b
        for i, (a, b) in enumerate(zip(self.out_shapes, self.out_specs)):
            yield "out", i, a, b

    def grid_cells(self) -> Iterator[tuple[int, ...]]:
        return itertools.product(*(range(g) for g in self.grid))

    def n_grid_cells(self) -> int:
        return math.prod(self.grid)

    def vmem_cell_bytes(self) -> int:
        """Modeled per-grid-cell VMEM: block buffers x2 (double buffering)
        + scratch, minor axes tile-padded.  Kernel-internal intermediates
        are not modeled (the autotuner's per-kind cost model covers those);
        this is the geometry floor every launch must clear."""
        blocks = sum(_padded_nbytes(b.block_shape, a.dtype)
                     for _, _, a, b in self.operands())
        scratch = sum(_padded_nbytes(s.shape, s.dtype) for s in self.scratch)
        return 2 * blocks + scratch

    def accumulators(self) -> list[tuple[str, int, Any]]:
        """Where the running partial state lives: ``(kind, index, dtype)``.

        Scratch buffers when declared; otherwise the output refs when the
        kernel accumulates in place (``out_accumulates``)."""
        if self.scratch:
            return [("scratch", i, s.dtype) for i, s in enumerate(self.scratch)]
        if self.out_accumulates:
            return [("out", i, o.dtype) for i, o in enumerate(self.out_shapes)]
        return []
