"""Static Pallas kernel-launch verifier (DESIGN.md Sec. 7).

Audits a declarative ``repro.kernels.spec.KernelSpec`` -- the same object
that constructs the real ``pl.pallas_call`` -- WITHOUT executing or even
lowering anything.  The auditor enumerates the grid through the declared
index maps and proves:

* **write-race freedom** (``kernel-write-race``): every output block is
  written by exactly one grid cell, except revisits along the DECLARED
  reduction axes (``revisit_axes``) -- two cells that map to the same
  output block while differing in a non-revisit axis would race (or, on
  the sequentially-executed TPU grid, silently clobber partial sums);
* **output coverage** (``kernel-unwritten-block``): every block of every
  output array is written by at least one grid cell -- an index-map typo
  that strands a block leaves uninitialized memory in the result;
* **revisit ordering** (``kernel-revisit-order``): revisit axes must be
  the TRAILING grid axes, so all revisits of one output block are
  consecutive under the TPU's sequential row-major grid execution (a
  leading revisit axis interleaves partial sums of different blocks
  through one scratch accumulator);
* **accumulator discipline** (``kernel-accum-missing`` /
  ``kernel-accum-init`` / ``kernel-accum-dtype``): a kernel whose output
  blocks are revisited must declare where the partial state lives
  (scratch or the output ref itself), must initialize it exactly when the
  revisit sweep restarts (``init_axes == revisit_axes``: a strict subset
  is a stale or mid-sweep-clobbered accumulator), and must keep it in
  >= 32-bit float when any input is sub-f32 (bf16 partial sums lose the
  low bits of every accumulation step);
* **in-bounds addressing** (``kernel-oob-index``): no grid cell's block
  index addresses past the padded array bounds on any axis;
* **block alignment** (``kernel-block-misaligned``): every array axis is
  a whole multiple of its block axis (ops.py pads to guarantee this; a
  spec that violates it silently truncates the trailing partial block);
* **VMEM budget** (``kernel-vmem-budget``): the per-grid-cell footprint
  (double-buffered blocks + scratch, minor axes tile-padded) fits the
  ``DEVICE_ROOFLINE`` budget -- checked for every block candidate the
  autotuner can emit and for user-pinned ``AlgoConfig`` blocks.

Every violation carries the kernel name and the offending grid cell.
"""

from __future__ import annotations

import itertools
from typing import Optional

import jax.numpy as jnp

from repro.analysis.jaxpr_lint import Violation
from repro.kernels.spec import KernelSpec
from repro.launch.mesh import TPU_V5E, roofline


def _cell(c) -> str:
    return "(" + ", ".join(map(str, c)) + ")"


def check_geometry(spec: KernelSpec) -> list[Violation]:
    """Grid-enumeration rules: races, coverage, bounds, accumulators."""
    out: list[Violation] = []
    name = spec.name

    # revisit axes must be a trailing suffix of the grid
    k = len(spec.revisit_axes)
    trailing = tuple(range(len(spec.grid) - k, len(spec.grid)))
    if tuple(sorted(spec.revisit_axes)) != trailing:
        out.append(Violation(
            rule="kernel-revisit-order",
            message=(
                f"{name}: revisit_axes {spec.revisit_axes} are not the "
                f"trailing grid axes {trailing}; revisits of one output "
                "block would not be consecutive under sequential grid "
                "execution, interleaving partial sums through the "
                "accumulator"
            ),
            source=name,
        ))

    cells = list(spec.grid_cells())
    for role, idx, arr, blk in spec.operands():
        opname = f"{role}[{idx}]"
        if len(blk.block_shape) != len(arr.shape):
            out.append(Violation(
                rule="kernel-block-misaligned",
                message=(f"{name}: {opname} block rank "
                         f"{len(blk.block_shape)} != array rank "
                         f"{len(arr.shape)}"),
                source=name,
            ))
            continue
        misaligned = [ax for ax, (s, b) in
                      enumerate(zip(arr.shape, blk.block_shape)) if s % b]
        if misaligned:
            out.append(Violation(
                rule="kernel-block-misaligned",
                message=(
                    f"{name}: {opname} axes {misaligned} are not whole "
                    f"multiples of the block {blk.block_shape} (array "
                    f"{arr.shape}); the trailing partial block would be "
                    "silently truncated"
                ),
                source=name,
            ))
            continue

        writers: dict[tuple[int, ...], tuple[int, ...]] = {}
        raced: set[tuple[int, ...]] = set()
        oob_reported = 0
        for cell in cells:
            bi = tuple(blk.index_map(*cell))
            if len(bi) != len(arr.shape):
                out.append(Violation(
                    rule="kernel-oob-index",
                    message=(f"{name}: {opname} index map returned rank "
                             f"{len(bi)} for rank-{len(arr.shape)} array "
                             f"at grid cell {_cell(cell)}"),
                    source=name,
                ))
                break
            bad_axis = next(
                (ax for ax in range(len(bi))
                 if bi[ax] < 0
                 or (bi[ax] + 1) * blk.block_shape[ax] > arr.shape[ax]),
                None,
            )
            if bad_axis is not None:
                if oob_reported < 3:  # first few cells, not the whole grid
                    lo = bi[bad_axis] * blk.block_shape[bad_axis]
                    out.append(Violation(
                        rule="kernel-oob-index",
                        message=(
                            f"{name}: {opname} grid cell {_cell(cell)} "
                            f"addresses block {_cell(bi)} -> elements "
                            f"[{lo}, {lo + blk.block_shape[bad_axis]}) "
                            f"beyond padded bound {arr.shape[bad_axis]} "
                            f"on axis {bad_axis}"
                        ),
                        source=name,
                    ))
                oob_reported += 1
                continue
            if role != "out":
                continue
            prev = writers.setdefault(bi, cell)
            if prev is not cell and bi not in raced:
                diff = [ax for ax in range(len(cell)) if cell[ax] != prev[ax]]
                if any(ax not in spec.revisit_axes for ax in diff):
                    raced.add(bi)
                    out.append(Violation(
                        rule="kernel-write-race",
                        message=(
                            f"{name}: output block {_cell(bi)} of {opname} "
                            f"is written by grid cells {_cell(prev)} and "
                            f"{_cell(cell)}, which differ outside the "
                            f"declared revisit axes {spec.revisit_axes}"
                        ),
                        source=name,
                    ))
        if role == "out" and not oob_reported:
            nblocks = tuple(s // b for s, b in
                            zip(arr.shape, blk.block_shape))
            missing = [b for b in itertools.product(*(range(x) for x in nblocks))
                       if b not in writers]
            for b in missing[:3]:
                out.append(Violation(
                    rule="kernel-unwritten-block",
                    message=(f"{name}: output block {_cell(b)} of {opname} "
                             "is written by NO grid cell (uninitialized "
                             "result memory)"),
                    source=name,
                ))

    # accumulator protocol of revisiting kernels
    if spec.revisit_axes:
        accs = spec.accumulators()
        if not accs:
            out.append(Violation(
                rule="kernel-accum-missing",
                message=(
                    f"{name}: output blocks are revisited over grid axes "
                    f"{spec.revisit_axes} but the spec declares neither "
                    "scratch accumulators nor out_accumulates; partial "
                    "state has nowhere to live across revisits"
                ),
                source=name,
            ))
        if tuple(sorted(spec.init_axes)) != tuple(sorted(spec.revisit_axes)):
            first_revisit = tuple(
                1 if ax == spec.revisit_axes[-1] else 0
                for ax in range(len(spec.grid))
            )
            out.append(Violation(
                rule="kernel-accum-init",
                message=(
                    f"{name}: accumulator init is guarded on grid axes "
                    f"{spec.init_axes} but output blocks are revisited "
                    f"over {spec.revisit_axes}; the accumulator is stale "
                    "or clobbered by the first revisiting grid step "
                    f"(e.g. cell {_cell(first_revisit)})"
                ),
                source=name,
            ))
        sub_f32 = [
            (f"in[{i}]", a.dtype) for i, a in enumerate(spec.in_shapes)
            if jnp.issubdtype(jnp.dtype(a.dtype), jnp.floating)
            and jnp.dtype(a.dtype).itemsize < 4
        ]
        if sub_f32:
            for kind, i, dt in accs:
                dt = jnp.dtype(dt)
                if not (jnp.issubdtype(dt, jnp.floating) and dt.itemsize >= 4):
                    out.append(Violation(
                        rule="kernel-accum-dtype",
                        message=(
                            f"{name}: {kind}[{i}] accumulator is {dt.name} "
                            f"while inputs {[n for n, _ in sub_f32]} are "
                            "sub-f32; partial sums must accumulate in f32 "
                            "(bf16 accumulation loses the low bits of "
                            "every revisiting grid step)"
                        ),
                        source=name,
                    ))
    return out


def check_vmem(spec: KernelSpec, *, device_kind: str = TPU_V5E,
               budget: Optional[int] = None) -> list[Violation]:
    """Per-grid-cell VMEM footprint vs the device's roofline budget."""
    if budget is None:
        budget = roofline(device_kind)["vmem_bytes"]
    need = spec.vmem_cell_bytes()
    if need <= budget:
        return []
    blocks = {f"{role}[{i}]": tuple(b.block_shape)
              for role, i, _, b in spec.operands()}
    return [Violation(
        rule="kernel-vmem-budget",
        message=(
            f"{spec.name}: per-grid-cell VMEM footprint {need} B (blocks "
            f"{blocks}, x2 double-buffered, + scratch) exceeds the "
            f"{device_kind} budget {budget} B at every grid cell (e.g. "
            f"{_cell(tuple(0 for _ in spec.grid))})"
        ),
        source=spec.name,
    )]


def audit_spec(spec: KernelSpec, *, device_kind: str = TPU_V5E,
               budget: Optional[int] = None) -> list[Violation]:
    """Full static audit: geometry rules + VMEM budget."""
    return check_geometry(spec) + check_vmem(spec, device_kind=device_kind,
                                             budget=budget)
