"""Block-size autotuner for the tiled GP kernels (DESIGN.md Sec. 4).

``select_blocks(kind, ...)`` picks ``(block_n, block_cap)`` for the
cap-tiled scoring / grad-mean kernels from a VMEM-footprint +
arithmetic-intensity model keyed on the per-device roofline peaks in
``repro.launch.mesh.DEVICE_ROOFLINE``.  The choice is a pure function
of ``(device_kind, kind, n_clients, n, cap, d)`` -- deterministic and therefore
reproducible -- and is memoized in a process-level cache under exactly that
key.  Callers that need a specific tiling (tests, `AlgoConfig` overrides)
bypass the tuner by passing explicit block sizes to the ops wrappers.

The model is intentionally small:

* **feasibility** -- the per-grid-cell VMEM working set (input tiles,
  intermediate (bn, bc) tiles, accumulators, x2 for double buffering) must
  fit the device's ``vmem_bytes`` budget;
* **cost** -- per-cell ``max(flops/peak, hbm_bytes/bw)`` summed over the
  padded grid, so oversized blocks pay their padding waste and undersized
  ones pay the re-streamed (bc, bc) Gram tiles and recomputed h tiles.

A device missing from the table raises ``UnknownDeviceError``: a VMEM budget
is never guessed.  ``measure_blocks`` is the measured sweep that times
real kernel calls over the feasible candidate grid and caches the argmin
under the same key (an explicit API: it blocks on device results, so it
cannot run under a jit trace the way ``select_blocks`` can).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Optional

import jax
import numpy as np

from repro.launch.mesh import local_device_kind, roofline

#: f32 tile alignment of the TPU vector unit: (sublane, lane).
_SUBLANE = 8
_LANE = 128

#: Candidate grids.  block_cap candidates are lane-aligned (the cap axis is
#: the minor axis of the (bn, bc) h tiles and both axes of the Gram tiles);
#: block_n candidates are sublane-aligned.
_BLOCK_N_CANDIDATES = (8, 16, 32, 64, 128, 256)
_BLOCK_CAP_CANDIDATES = (128, 256, 512, 1024)

_CACHE: dict[tuple, tuple[int, int]] = {}


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _dtype_name(dtype: Any) -> str:
    """Canonical dtype tag for the cache key / footprint model (f32 default).

    The VMEM-footprint model used to assume f32 implicitly, so a bf16
    caller would silently reuse f32 block picks under the same key; the
    dtype is now an explicit key component and feeds the per-word byte
    width of the model.
    """
    return np.dtype(jax.dtypes.canonicalize_dtype(dtype or np.float32)).name


def cache_key(kind: str, device_kind: str, n_clients: int, n: int, cap: int,
              d: int, dtype: Any = None):
    return (device_kind, kind, n_clients, n, cap, d, _dtype_name(dtype))


def clear_cache() -> None:
    _CACHE.clear()


def _vmem_cell_bytes(kind: str, bn: int, bc: int, d: int,
                     itemsize: int = 4, *, resident: bool = False) -> int:
    """Per-grid-cell VMEM working set: block buffers x2 for double
    buffering, plus the f32 matmul's split operands.

    score: c tile + x tile(s) (two when cap-tiled, one when ``resident``)
    + two (bc, bc) Gram tiles + the h / cross / g1 / g2 (bn, bc)
    intermediates + the (bn, 1) accumulator.
    grad:  c tile + x tile + alpha row + the (bn, bc) w tile + the (bn, d)
    accumulator + the (bn, 1) running sum.
    ``itemsize`` is the element byte width of the caller's dtype (4 = the
    historical f32 assumption; the f32 accumulator scratch is charged at
    the same width, a deliberate over-estimate that keeps bf16 feasible
    sets conservative).

    f32 operands add the bf16 split copies that the multi-pass f32 matmul
    (``spec.MXU_PRECISION``) keeps in VMEM, held once.  Their coefficients
    (per (bc, d) and (bn, d) f32 tile) make the total an upper bound of
    what the v5e compiler allocated at d in {512, 3072}, block_n in
    {8, ..., 128}, block_cap in {128, 256} (``tests/test_tpu_compile.py``
    compiles every feasible pick at the engine's widths).
    """
    dl = _round_up(d, _LANE)  # minor axes are lane-padded by the compiler
    if kind == "score":
        x_tiles = 1 if resident else 2
        words = bn * dl + x_tiles * bc * dl + 2 * bc * bc + 5 * bn * bc + 2 * bn
        split_words = (x_tiles + 1) * bc * dl + 2 * bn * dl
    elif kind == "grad":
        words = bn * dl + bc * dl + bc + 3 * bn * bc + bn * dl + 2 * bn
        split_words = 5 * bc * dl + 2 * bn * dl
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")
    if itemsize < 4:
        split_words = 0  # sub-f32 operands feed the MXU as they are
    return 2 * itemsize * words + 4 * split_words


def _cell_cost(kind: str, bn: int, bc: int, d: int, hw: dict,
               itemsize: int = 4) -> float:
    """max(compute, memory) seconds for ONE grid cell."""
    if kind == "score":
        flops = 2 * 2 * bn * bc * d + 2 * 2 * bn * bc * bc + 8 * bn * bc
        bytes_ = itemsize * (bn * d + 2 * bc * d + 2 * bc * bc + bn)
    else:
        flops = 2 * 2 * bn * bc * d + 6 * bn * bc
        bytes_ = itemsize * (bn * d + bc * d + bc + bn * d)
    return max(flops / hw["peak_flops"], bytes_ / hw["hbm_bw"])


def _grid_cells(kind: str, bn: int, bc: int, n: int, cap: int, n_clients: int) -> int:
    caps = _round_up(cap, bc) // bc
    rows = _round_up(n, bn) // bn
    per_client = rows * caps * caps if kind == "score" else rows * caps
    return n_clients * per_client


def _feasible(kind: str, n: int, cap: int, d: int, hw: dict,
              itemsize: int = 4):
    budget = 0.75 * hw["vmem_bytes"]
    for bn in _BLOCK_N_CANDIDATES:
        if bn > _round_up(max(n, 1), _SUBLANE):
            continue  # pure padding beyond the candidate count
        for bc in _BLOCK_CAP_CANDIDATES:
            if bc > _round_up(max(cap, 1), _LANE):
                continue
            need = _vmem_cell_bytes(kind, bn, bc, d, itemsize, resident=bc >= cap)
            if need <= budget:
                yield bn, bc


def validate_blocks(
    kind: str,
    *,
    block_n: int,
    block_cap: int,
    cap: int,
    d: int,
    device_kind: Optional[str] = None,
    dtype: Any = None,
) -> tuple[int, int]:
    """Validate a USER-PINNED ``(block_n, block_cap)`` pair against the
    device's VMEM budget -- the same footprint model and 0.75 budget the
    tuner's feasibility filter uses -- and raise a loud ``ValueError``
    naming the block and the budget when it cannot fit.  Tuner-chosen
    blocks are feasible by construction; explicit ``AlgoConfig`` pins are
    not, and an infeasible pin would otherwise surface as an opaque
    Mosaic/XLA allocation failure deep inside the round body.
    """
    device_kind = device_kind or local_device_kind()
    hw = roofline(device_kind)
    budget = int(0.75 * hw["vmem_bytes"])
    itemsize = np.dtype(_dtype_name(dtype)).itemsize
    # block_cap >= cap routes to the VMEM-resident kernel: the working set
    # is the lane-padded cap, not the nominal (possibly huge) pin.
    bc_eff = min(block_cap, _round_up(max(cap, 1), _LANE))
    need = _vmem_cell_bytes(kind, block_n, bc_eff, d, itemsize,
                            resident=block_cap >= cap)
    if need > budget:
        raise ValueError(
            f"pinned {kind} blocks (block_n={block_n}, block_cap={block_cap})"
            f" need {need} bytes of VMEM per grid cell at d={d} "
            f"({_dtype_name(dtype)}), exceeding the {device_kind!r} budget of "
            f"{budget} bytes (0.75 x vmem_bytes={hw['vmem_bytes']}); pick "
            "smaller AlgoConfig block pins or leave them unset for the "
            "autotuner"
        )
    return block_n, block_cap


def select_blocks(
    kind: str,
    *,
    n: int,
    cap: int,
    d: int,
    n_clients: int = 1,
    device_kind: Optional[str] = None,
    dtype: Any = None,
) -> tuple[int, int]:
    """Deterministic ``(block_n, block_cap)`` for a kernel ``kind``/shape.

    ``kind`` is ``"score"`` (uncertainty scoring) or ``"grad"`` (grad mean);
    ``n`` is the per-client candidate count, ``cap`` the trajectory ring
    capacity, ``d`` the search dimension, ``n_clients`` the client batch.
    ``dtype`` is the element dtype of the kernel operands (default f32 --
    bitwise-identical picks to the pre-dtype model for every f32 caller);
    narrower dtypes widen the feasible set and shift the roofline balance,
    and are cached under their own key.
    """
    device_kind = device_kind or local_device_kind()
    key = cache_key(kind, device_kind, n_clients, n, cap, d, dtype)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = np.dtype(_dtype_name(dtype)).itemsize
    hw = roofline(device_kind)
    best: Optional[tuple[float, tuple[int, int]]] = None
    for bn, bc in _feasible(kind, n, cap, d, hw, itemsize):
        cost = _cell_cost(kind, bn, bc, d, hw, itemsize) * _grid_cells(kind, bn, bc, n, cap, n_clients)
        # Deterministic tie-break: prefer LARGER tiles at equal modeled cost
        # (fewer grid cells, less accumulator traffic the model can't see).
        cand = (cost, (bn, bc))
        if best is None or cost < best[0] or (cost == best[0] and cand[1] > best[1]):
            best = cand
    if best is None:  # nothing fits (tiny VMEM budget): minimum legal tile
        best = (0.0, (_SUBLANE, _LANE))
    _CACHE[key] = best[1]
    return best[1]


def measure_blocks(
    kind: str,
    run_fn: Callable[[int, int], jax.Array],
    *,
    n: int,
    cap: int,
    d: int,
    n_clients: int = 1,
    device_kind: Optional[str] = None,
    dtype: Any = None,
    candidates: Optional[Iterable[tuple[int, int]]] = None,
    reps: int = 3,
) -> tuple[int, int]:
    """Measured-sweep fallback: time ``run_fn(block_n, block_cap)`` over the
    feasible candidate grid, cache the winner under the model's key, and
    return it.  Subsequent ``select_blocks`` calls for the same key return
    the measured choice.  Explicit API only -- it calls
    ``block_until_ready`` and so cannot run under a jit trace.
    """
    device_kind = device_kind or local_device_kind()
    hw = roofline(device_kind)
    itemsize = np.dtype(_dtype_name(dtype)).itemsize
    cands = list(candidates) if candidates is not None else list(
        _feasible(kind, n, cap, d, hw, itemsize)
    )
    if not cands:
        cands = [(_SUBLANE, _LANE)]
    best: Optional[tuple[float, tuple[int, int]]] = None
    for bn, bc in cands:
        run_fn(bn, bc).block_until_ready()  # compile outside the timing
        dt = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run_fn(bn, bc).block_until_ready()
            dt = min(dt, time.perf_counter() - t0)
        if best is None or dt < best[0]:
            best = (dt, (bn, bc))
    _CACHE[cache_key(kind, device_kind, n_clients, n, cap, d, dtype)] = best[1]
    return best[1]
