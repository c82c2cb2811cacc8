"""Pallas TPU kernel: squared-exponential Gram matrix

    K(X1, X2)_ij = exp( -||x1_i - x2_j||^2 / (2 l^2) )

Built every local iteration from the trajectory buffer (gp_surrogate eq. 5).
Fuses the pairwise-distance matmul with the exp so the distance matrix never
round-trips to HBM.  Tiling: grid (n/bn, m/bm), d resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.spec import MXU_PRECISION, ArraySpec, BlockDecl, KernelSpec


def _kernel(x1_ref, x2_ref, o_ref, *, inv_two_l2: float):
    x1 = x1_ref[...]  # (bn, d)
    x2 = x2_ref[...]  # (bm, d)
    n1 = jnp.sum(x1 * x1, axis=-1, keepdims=True)  # (bn, 1)
    n2 = jnp.sum(x2 * x2, axis=-1, keepdims=True).T  # (1, bm)
    cross = jax.lax.dot_general(
        x1, x2, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    d2 = jnp.maximum(n1 + n2 - 2.0 * cross, 0.0)
    o_ref[...] = jnp.exp(-d2 * inv_two_l2).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lengthscale", "block_n", "block_m", "interpret"))
def sqexp_kernel(
    x1: jax.Array,
    x2: jax.Array,
    *,
    lengthscale: float,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    n, d = x1.shape
    m = x2.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    spec = sqexp_spec(n, m, d, x1.dtype, block_n=block_n, block_m=block_m)
    return spec.pallas_call(
        functools.partial(_kernel, inv_two_l2=0.5 / (lengthscale**2)),
        interpret=interpret,
    )(x1, x2)


def sqexp_spec(n: int, m: int, d: int, dtype, *, block_n: int,
               block_m: int) -> KernelSpec:
    """Launch geometry of the SE Gram kernel: one writer per output tile."""
    return KernelSpec(
        name="sqexp",
        grid=(n // block_n, m // block_m),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((m, d), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i, j: (i, 0)),
            BlockDecl((block_m, d), lambda i, j: (j, 0)),
        ),
        out_shapes=(ArraySpec((n, m), dtype),),
        out_specs=(BlockDecl((block_n, block_m), lambda i, j: (i, j)),),
    )
