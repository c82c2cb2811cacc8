"""Pallas TPU kernel: fused RFF featurization  phi(X) = sqrt(2/M) cos(X V^T + b).

The paper evaluates phi over the whole trajectory every round on every client
(M up to 10^4 features, d up to ~2.2k in the Covertype experiment), which is a
matmul immediately followed by a transcendental -- exactly the fusion XLA will
not always give us and the MXU+VPU pipeline handles well when tiled for VMEM.

Tiling: grid (n/bn, M/bm).  Each program loads an (bn, d) slab of X and a
(bm, d) slab of V (d kept whole -- the contraction dim must be resident),
issues one MXU matmul (bn x d x bm), adds the phase slab and applies cos on
the VPU, writing an (bn, bm) output tile.  Block sizes default to 128 so the
matmul dims are MXU-aligned; VMEM footprint per program is
(bn*d + bm*d + bn*bm) * 4B  ~=  4.2 MB at d=4096, within the ~16 MB VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.spec import MXU_PRECISION, ArraySpec, BlockDecl, KernelSpec


def _kernel(x_ref, v_ref, b_ref, o_ref, *, scale: float):
    x = x_ref[...]  # (bn, d)
    v = v_ref[...]  # (bm, d)
    b = b_ref[...]  # (1, bm)
    proj = jax.lax.dot_general(
        x, v, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )  # (bn, bm)
    o_ref[...] = (scale * jnp.cos(proj + b)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret", "n_features"))
def rff_features_kernel(
    x: jax.Array,
    v: jax.Array,
    b: jax.Array,
    *,
    n_features: int,
    block_n: int = 128,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x (n,d), v (M,d), b (M,) -> (n, M).  Shapes must be block-aligned
    (ops.py pads); ``n_features`` is the TRUE M for the sqrt(2/M) scale.
    """
    n, d = x.shape
    m = v.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    b2 = b.reshape(1, m)
    scale = math.sqrt(2.0 / n_features)
    spec = features_spec(n, m, d, x.dtype, block_n=block_n, block_m=block_m)
    return spec.pallas_call(
        functools.partial(_kernel, scale=scale), interpret=interpret
    )(x, v, b2)


def features_spec(n: int, m: int, d: int, dtype, *, block_n: int,
                  block_m: int) -> KernelSpec:
    """Launch geometry of the RFF featurization kernel: every grid cell
    writes its own (block_n, block_m) output tile exactly once."""
    return KernelSpec(
        name="rff_features",
        grid=(n // block_n, m // block_m),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((m, d), dtype),
            ArraySpec((1, m), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i, j: (i, 0)),
            BlockDecl((block_m, d), lambda i, j: (j, 0)),
            BlockDecl((1, block_m), lambda i, j: (0, j)),
        ),
        out_shapes=(ArraySpec((n, m), dtype),),
        out_specs=(BlockDecl((block_n, block_m), lambda i, j: (i, j)),),
    )
