"""Device meshes and per-device roofline peaks.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init -- the dry-run must
set XLA_FLAGS before any jax call).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """Every mesh of the repo is built here, with Auto axes.

    The round engines are written for ``shard_map`` and GSPMD propagation,
    not for explicit sharding: under Explicit axes (``jax.make_mesh``'s
    default since jax 0.7) the chunk step's ``dynamic_update_slice`` and
    ``P.UNCONSTRAINED`` activation constraints are refused.  ``devices``
    places the mesh on given devices (e.g. a described topology's).
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Target: TPU v5e, 256 chips/pod.

    single-pod: (data=16, model=16); multi-pod: (pod=2, data=16, model=16).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (CPU smoke / examples)."""
    return make_mesh((len(jax.devices()),), ("data",))


#: Device kind of one TPU v5e chip as jax reports it (``device_kind``).
TPU_V5E = "TPU v5 lite"

# v5e per-chip peaks.  Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip).
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link
HBM_PER_CHIP = 16e9  # bytes

#: Roofline peaks keyed by ``jax.Device.device_kind`` -- the SINGLE source
#: for ``benchmarks/roofline.py`` (communication/FLOP envelopes) and
#: ``repro.kernels.autotune`` (block-size selection).  ``vmem_bytes`` is the
#: fast on-chip working-set budget the kernel tiles must fit in.  The
#: ``cpu`` entry is the interpret-mode stand-in (an L2-sized budget keeps
#: interpret-mode block choices moderate); its numbers are not a device's.
#: A device missing from the table is an error (``roofline``), never a
#: default.
DEVICE_ROOFLINE = {
    TPU_V5E: {
        "peak_flops": PEAK_FLOPS_BF16,
        "hbm_bw": HBM_BW,
        "hbm_bytes": HBM_PER_CHIP,
        "vmem_bytes": 16 * 2**20,
    },
    "cpu": {
        "peak_flops": 100e9,
        "hbm_bw": 20e9,
        "hbm_bytes": 16e9,
        "vmem_bytes": 16 * 2**20,
    },
}


class UnknownDeviceError(LookupError):
    """The device kind has no entry in ``DEVICE_ROOFLINE``."""


def local_device_kind() -> str:
    """``device_kind`` of the first local device."""
    return jax.devices()[0].device_kind


def roofline(device_kind: Optional[str] = None) -> dict:
    """Peaks of ``device_kind`` (default: the first local device's)."""
    kind = device_kind or local_device_kind()
    try:
        return DEVICE_ROOFLINE[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no roofline peaks for device kind {kind!r}; add its published "
            f"numbers to DEVICE_ROOFLINE (known: {sorted(DEVICE_ROOFLINE)})"
        ) from None
