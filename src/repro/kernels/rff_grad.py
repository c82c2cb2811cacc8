"""Pallas TPU kernel: fused RFF gradient-surrogate contraction

    G = grad phi(X)^T w = -sqrt(2/M) * ( sin(X V^T + b) * w ) @ V     (n, d)

This is the inner loop of FZooS eq. (8): evaluated TWICE per local step per
client (global and local surrogate) at the current iterate.  Done naively it
materializes the (n, M) sine matrix in HBM; the fused kernel keeps each
(bn, bm) sine tile in VMEM and accumulates the (bn, d) output across the M
grid axis, so HBM traffic is O(n*d + M*d) instead of O(n*M).

Tiling: grid (n/bn, M/bm) with the second axis the reduction ("arbitrary"
semantics).  Two MXU matmuls per program: (bn x d x bm) for the projection
and (bn x bm x d) for the back-contraction, cos/sin on the VPU in between.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.spec import MXU_PRECISION, ArraySpec, BlockDecl, KernelSpec


def _kernel(x_ref, v_ref, b_ref, w_ref, o_ref, *, scale: float):
    j = pl.program_id(1)
    x = x_ref[...]  # (bn, d)
    v = v_ref[...]  # (bm, d)
    b = b_ref[...]  # (1, bm)
    w = w_ref[...]  # (1, bm)
    proj = jax.lax.dot_general(
        x, v, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )  # (bn, bm)
    s = jnp.sin(proj + b) * w  # (bn, bm)
    contrib = -scale * jax.lax.dot_general(
        s, v, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )  # (bn, d)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = contrib.astype(o_ref.dtype)

    @pl.when(j > 0)
    def _acc():
        o_ref[...] = (o_ref[...] + contrib).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret", "n_features"))
def rff_grad_kernel(
    x: jax.Array,
    v: jax.Array,
    b: jax.Array,
    w: jax.Array,
    *,
    n_features: int,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x (n,d), v (M,d), b (M,), w (M,) -> (n,d).  Block-aligned inputs;
    padded M slots must carry w == 0 and v == 0 (then they contribute 0).
    """
    n, d = x.shape
    m = v.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    b2 = b.reshape(1, m)
    w2 = w.reshape(1, m)
    scale = math.sqrt(2.0 / n_features)
    spec = grad_spec(n, m, d, x.dtype, block_n=block_n, block_m=block_m)
    return spec.pallas_call(
        functools.partial(_kernel, scale=scale), interpret=interpret
    )(x, v, b2, w2)


def grad_spec(n: int, m: int, d: int, dtype, *, block_n: int,
              block_m: int) -> KernelSpec:
    """Launch geometry of the RFF gradient-contraction kernel.  The M grid
    axis is the reduction: each (block_n, d) output block is revisited
    across it and the kernel accumulates IN the output ref (init write at
    j == 0), so the output itself is the accumulator
    (``out_accumulates``)."""
    return KernelSpec(
        name="rff_grad",
        grid=(n // block_n, m // block_m),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((m, d), dtype),
            ArraySpec((1, m), dtype),
            ArraySpec((1, m), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i, j: (i, 0)),
            BlockDecl((block_m, d), lambda i, j: (j, 0)),
            BlockDecl((1, block_m), lambda i, j: (0, j)),
            BlockDecl((1, block_m), lambda i, j: (0, j)),
        ),
        out_shapes=(ArraySpec((n, d), dtype),),
        out_specs=(BlockDecl((block_n, d), lambda i, j: (i, 0)),),
        revisit_axes=(1,),
        init_axes=(1,),
        out_accumulates=True,
    )
