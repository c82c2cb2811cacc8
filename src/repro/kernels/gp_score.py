"""Pallas TPU kernel: fused active-query uncertainty scoring.

One VMEM-resident pass over a block of candidates computes, per candidate c,

    score(c) = max(prior - corr(c), 0)
    corr(c)  = (1/l^4) [ h^T P h - 2 (h o Xc)^T B h + (c.c) h^T B h ]

with h_t = k(c, x_t) generated IN the kernel (fused with the pairwise
distance matmul, so the (block_n, cap) kernel-vector tile never round-trips
to HBM), B the masked Gram inverse and P = B o XX^T both precomputed once
per trajectory state from the cached Cholesky factor (core/gp_surrogate
``GramFactor``).  This replaces the seed's per-candidate O(cap^2 d)
triangular-solve scoring with O(cap^2) of MXU matmuls per candidate.

All variants evaluate the three expansion terms through ONE fused epilogue,

    corr(c) * l^4 = sum_k [ g1 - (2 cross - c.c) o g2 ]_k h_k,
    g1 = h @ P,  g2 = h @ B,

which is algebraically identical to t1 - 2 t2 + t3 (the per-element
cancellation before the reduction is also the numerically kinder order) and
needs one elementwise pass + one reduction instead of three.

Two kernel families share the tile numerics:

* **resident** (``uncertainty_scores_kernel``): grid (n / block_n,); xs, B
  and P stay fully VMEM-resident across programs.  Cheapest when the whole
  (cap, cap) factor pair fits VMEM (cap <~ 256).
* **cap-tiled** (``uncertainty_scores_tiled_kernel``): grid
  (n/block_n, cap/block_cap, cap/block_cap) -- the trailing two grid
  dimensions sweep (bc, bc) tiles of B/P while a (block_n, 1) f32 VMEM
  scratch accumulates the bilinear form, so VMEM residency is
  O(bn d + bc d + bc^2 + bn bc) INDEPENDENT of cap and the kernel scales to
  cap >= 1024.  The h_j / h_k tiles are recomputed per cell from the x
  tiles (~2d/bc^2 flop overhead vs the GEMMs).  Padded trajectory slots
  (zero rows of xs, zero rows AND columns of B/P) contribute exactly zero:
  every product in the accumulated cell touches a B/P entry.

``*_clients_kernel`` variants add a leading CLIENT grid dimension for the
batched federated engine: one launch scores the whole client batch instead
of N vmapped launches.

Every launch is constructed from a declarative ``KernelSpec``
(``score_*_spec`` builders below): the spec both builds the real
``pl.pallas_call`` and feeds the static auditor in
``repro.analysis.kernel_audit`` (DESIGN.md Sec. 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.spec import MXU_PRECISION, ArraySpec, BlockDecl, KernelSpec, ScratchDecl


def _h_tile(c, n1, x, inv_two_l2: float):
    """SE kernel-vector tile h and the c.x_t table.  c (bn, d), n1 (bn, 1),
    x (bc, d) -> (h (bn, bc), cross (bn, bc))."""
    n2 = jnp.sum(x * x, axis=-1, keepdims=True).T  # (1, bc)
    cross = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    d2 = jnp.maximum(n1 + n2 - 2.0 * cross, 0.0)
    return jnp.exp(-d2 * inv_two_l2), cross


def _score_block(c, x, binv, pmat, *, inv_two_l2: float, inv_l4: float, prior: float):
    """Shared VMEM-tile numerics of the resident kernels.  c (bn, d),
    x (cap, d), binv/pmat (cap, cap) -> (bn, 1)."""
    n1 = jnp.sum(c * c, axis=-1, keepdims=True)  # (bn, 1)
    h, cross = _h_tile(c, n1, x, inv_two_l2)
    g1 = jax.lax.dot_general(
        h, pmat, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    g2 = jax.lax.dot_general(
        h, binv, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    corr = jnp.sum((g1 - (2.0 * cross - n1) * g2) * h, axis=-1, keepdims=True) * inv_l4
    return jnp.maximum(prior - corr, 0.0)


def _kernel(c_ref, x_ref, b_ref, p_ref, o_ref, **kw):
    o_ref[...] = _score_block(
        c_ref[...], x_ref[...], b_ref[...], p_ref[...], **kw
    ).astype(o_ref.dtype)


def score_resident_spec(n: int, cap: int, d: int, dtype, *,
                        block_n: int) -> KernelSpec:
    """Launch geometry of the VMEM-resident scoring kernel."""
    return KernelSpec(
        name="gp_score.resident",
        grid=(n // block_n,),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((cap, d), dtype),
            ArraySpec((cap, cap), dtype),
            ArraySpec((cap, cap), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i: (i, 0)),
            BlockDecl((cap, d), lambda i: (0, 0)),
            BlockDecl((cap, cap), lambda i: (0, 0)),
            BlockDecl((cap, cap), lambda i: (0, 0)),
        ),
        out_shapes=(ArraySpec((n, 1), dtype),),
        out_specs=(BlockDecl((block_n, 1), lambda i: (i, 0)),),
    )


@functools.partial(
    jax.jit, static_argnames=("lengthscale", "prior", "block_n", "interpret")
)
def uncertainty_scores_kernel(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    *,
    lengthscale: float,
    prior: float,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    n, d = cands.shape
    cap = xs.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert binv.shape == pmat.shape == (cap, cap), (binv.shape, pmat.shape, cap)
    spec = score_resident_spec(n, cap, d, cands.dtype, block_n=block_n)
    out = spec.pallas_call(
        functools.partial(
            _kernel,
            inv_two_l2=0.5 / (lengthscale**2),
            inv_l4=1.0 / (lengthscale**4),
            prior=prior,
        ),
        interpret=interpret,
    )(cands, xs, binv, pmat)
    return out[:, 0]


def _kernel_clients(c_ref, x_ref, b_ref, p_ref, o_ref, **kw):
    # Leading block dim of every ref is the (size-1) client slot; the tile
    # numerics are shared with the unbatched kernel (_score_block).
    o_ref[0] = _score_block(
        c_ref[0], x_ref[0], b_ref[0], p_ref[0], **kw
    ).astype(o_ref.dtype)


def score_clients_spec(nb: int, n: int, cap: int, d: int, dtype, *,
                       block_n: int) -> KernelSpec:
    """Launch geometry of the client-batched resident scoring kernel."""
    return KernelSpec(
        name="gp_score.clients",
        grid=(nb, n // block_n),
        in_shapes=(
            ArraySpec((nb, n, d), dtype),
            ArraySpec((nb, cap, d), dtype),
            ArraySpec((nb, cap, cap), dtype),
            ArraySpec((nb, cap, cap), dtype),
        ),
        in_specs=(
            BlockDecl((1, block_n, d), lambda b, i: (b, i, 0)),
            BlockDecl((1, cap, d), lambda b, i: (b, 0, 0)),
            BlockDecl((1, cap, cap), lambda b, i: (b, 0, 0)),
            BlockDecl((1, cap, cap), lambda b, i: (b, 0, 0)),
        ),
        out_shapes=(ArraySpec((nb, n, 1), dtype),),
        out_specs=(BlockDecl((1, block_n, 1), lambda b, i: (b, i, 0)),),
    )


@functools.partial(
    jax.jit, static_argnames=("lengthscale", "prior", "block_n", "interpret")
)
def uncertainty_scores_clients_kernel(
    cands: jax.Array,  # (N, n, d)
    xs: jax.Array,  # (N, cap, d)
    binv: jax.Array,  # (N, cap, cap)
    pmat: jax.Array,  # (N, cap, cap)
    *,
    lengthscale: float,
    prior: float,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Client-batched scoring: grid (N, n/block_n) -> (N, n)."""
    nb, n, d = cands.shape
    cap = xs.shape[1]
    assert n % block_n == 0, (n, block_n)
    assert xs.shape == (nb, cap, d), (xs.shape, cands.shape)
    assert binv.shape == pmat.shape == (nb, cap, cap), (binv.shape, pmat.shape)
    spec = score_clients_spec(nb, n, cap, d, cands.dtype, block_n=block_n)
    out = spec.pallas_call(
        functools.partial(
            _kernel_clients,
            inv_two_l2=0.5 / (lengthscale**2),
            inv_l4=1.0 / (lengthscale**4),
            prior=prior,
        ),
        interpret=interpret,
    )(cands, xs, binv, pmat)
    return out[:, :, 0]


# ---------------------------------------------------------------------------
# Cap-tiled kernels: the (cap, cap) factors never sit fully in VMEM.
# ---------------------------------------------------------------------------


def _score_cell(c, xj, xk, b, p, acc_ref, *, inv_two_l2: float):
    """Accumulate one (j, k) tile pair of the bilinear form into ``acc_ref``.

    c (bn, d); xj/xk (bc, d) trajectory tiles; b/p (bc, bc) tiles of
    B/P at block (j, k).  The cell's contribution to corr * l^4 is

        rowsum( [ h_j @ P_jk - (2 cross_k - c.c) o (h_j @ B_jk) ] o h_k )

    -- every product carries a B/P entry, so zero-padded trajectory tiles
    (zero B/P rows AND columns) contribute exactly zero even though the
    recomputed h at padded slots is nonzero junk.  Accumulation is f32.
    """
    n1 = jnp.sum(c * c, axis=-1, keepdims=True)  # (bn, 1)
    hj, _ = _h_tile(c, n1, xj, inv_two_l2)
    hk, cross_k = _h_tile(c, n1, xk, inv_two_l2)
    g1 = jax.lax.dot_general(
        hj, p, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    g2 = jax.lax.dot_general(
        hj, b, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    contrib = jnp.sum((g1 - (2.0 * cross_k - n1) * g2) * hk, axis=-1, keepdims=True)
    acc_ref[...] += contrib.astype(jnp.float32)


def _finalize(acc, *, inv_l4: float, prior: float):
    return jnp.maximum(prior - acc * inv_l4, 0.0)


def _kernel_tiled(c_ref, xj_ref, xk_ref, b_ref, p_ref, o_ref, acc_ref, *,
                  inv_two_l2: float, inv_l4: float, prior: float):
    j, k = pl.program_id(1), pl.program_id(2)
    last_j, last_k = pl.num_programs(1) - 1, pl.num_programs(2) - 1

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _score_cell(c_ref[...], xj_ref[...], xk_ref[...], b_ref[...], p_ref[...],
                acc_ref, inv_two_l2=inv_two_l2)

    @pl.when((j == last_j) & (k == last_k))
    def _done():
        o_ref[...] = _finalize(
            acc_ref[...], inv_l4=inv_l4, prior=prior
        ).astype(o_ref.dtype)


def score_tiled_spec(n: int, cap: int, d: int, dtype, *, block_n: int,
                     block_cap: int) -> KernelSpec:
    """Launch geometry of the cap-tiled scoring kernel.  The trailing two
    grid axes revisit each (block_n, 1) output block while the f32 scratch
    accumulates the bilinear form; xs is passed twice (the j- and k-tile
    views of the same trajectory array)."""
    return KernelSpec(
        name="gp_score.tiled",
        grid=(n // block_n, cap // block_cap, cap // block_cap),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((cap, d), dtype),
            ArraySpec((cap, d), dtype),
            ArraySpec((cap, cap), dtype),
            ArraySpec((cap, cap), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i, j, k: (i, 0)),
            BlockDecl((block_cap, d), lambda i, j, k: (j, 0)),
            BlockDecl((block_cap, d), lambda i, j, k: (k, 0)),
            BlockDecl((block_cap, block_cap), lambda i, j, k: (j, k)),
            BlockDecl((block_cap, block_cap), lambda i, j, k: (j, k)),
        ),
        out_shapes=(ArraySpec((n, 1), dtype),),
        out_specs=(BlockDecl((block_n, 1), lambda i, j, k: (i, 0)),),
        scratch=(ScratchDecl((block_n, 1), jnp.float32),),
        revisit_axes=(1, 2),
        init_axes=(1, 2),
    )


@functools.partial(
    jax.jit,
    static_argnames=("lengthscale", "prior", "block_n", "block_cap", "interpret"),
)
def uncertainty_scores_tiled_kernel(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    *,
    lengthscale: float,
    prior: float,
    block_n: int = 128,
    block_cap: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Cap-tiled scoring: grid (n/block_n, cap/block_cap, cap/block_cap)."""
    n, d = cands.shape
    cap = xs.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert cap % block_cap == 0, (cap, block_cap)
    assert binv.shape == pmat.shape == (cap, cap), (binv.shape, pmat.shape, cap)
    spec = score_tiled_spec(n, cap, d, cands.dtype,
                            block_n=block_n, block_cap=block_cap)
    out = spec.pallas_call(
        functools.partial(
            _kernel_tiled,
            inv_two_l2=0.5 / (lengthscale**2),
            inv_l4=1.0 / (lengthscale**4),
            prior=prior,
        ),
        interpret=interpret,
    )(cands, xs, xs, binv, pmat)
    return out[:, 0]


def _kernel_tiled_clients(c_ref, xj_ref, xk_ref, b_ref, p_ref, o_ref, acc_ref, *,
                          inv_two_l2: float, inv_l4: float, prior: float):
    j, k = pl.program_id(2), pl.program_id(3)
    last_j, last_k = pl.num_programs(2) - 1, pl.num_programs(3) - 1

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _score_cell(c_ref[0], xj_ref[0], xk_ref[0], b_ref[0], p_ref[0],
                acc_ref, inv_two_l2=inv_two_l2)

    @pl.when((j == last_j) & (k == last_k))
    def _done():
        o_ref[0] = _finalize(
            acc_ref[...], inv_l4=inv_l4, prior=prior
        ).astype(o_ref.dtype)


def score_tiled_clients_spec(nb: int, n: int, cap: int, d: int, dtype, *,
                             block_n: int, block_cap: int) -> KernelSpec:
    """Launch geometry of the client-batched cap-tiled scoring kernel."""
    return KernelSpec(
        name="gp_score.tiled_clients",
        grid=(nb, n // block_n, cap // block_cap, cap // block_cap),
        in_shapes=(
            ArraySpec((nb, n, d), dtype),
            ArraySpec((nb, cap, d), dtype),
            ArraySpec((nb, cap, d), dtype),
            ArraySpec((nb, cap, cap), dtype),
            ArraySpec((nb, cap, cap), dtype),
        ),
        in_specs=(
            BlockDecl((1, block_n, d), lambda b, i, j, k: (b, i, 0)),
            BlockDecl((1, block_cap, d), lambda b, i, j, k: (b, j, 0)),
            BlockDecl((1, block_cap, d), lambda b, i, j, k: (b, k, 0)),
            BlockDecl((1, block_cap, block_cap), lambda b, i, j, k: (b, j, k)),
            BlockDecl((1, block_cap, block_cap), lambda b, i, j, k: (b, j, k)),
        ),
        out_shapes=(ArraySpec((nb, n, 1), dtype),),
        out_specs=(BlockDecl((1, block_n, 1), lambda b, i, j, k: (b, i, 0)),),
        scratch=(ScratchDecl((block_n, 1), jnp.float32),),
        revisit_axes=(2, 3),
        init_axes=(2, 3),
    )


@functools.partial(
    jax.jit,
    static_argnames=("lengthscale", "prior", "block_n", "block_cap", "interpret"),
)
def uncertainty_scores_tiled_clients_kernel(
    cands: jax.Array,  # (N, n, d)
    xs: jax.Array,  # (N, cap, d)
    binv: jax.Array,  # (N, cap, cap)
    pmat: jax.Array,  # (N, cap, cap)
    *,
    lengthscale: float,
    prior: float,
    block_n: int = 128,
    block_cap: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Client-batched cap-tiled scoring:
    grid (N, n/block_n, cap/block_cap, cap/block_cap) -> (N, n)."""
    nb, n, d = cands.shape
    cap = xs.shape[1]
    assert n % block_n == 0, (n, block_n)
    assert cap % block_cap == 0, (cap, block_cap)
    assert xs.shape == (nb, cap, d), (xs.shape, cands.shape)
    assert binv.shape == pmat.shape == (nb, cap, cap), (binv.shape, pmat.shape)
    spec = score_tiled_clients_spec(nb, n, cap, d, cands.dtype,
                                    block_n=block_n, block_cap=block_cap)
    out = spec.pallas_call(
        functools.partial(
            _kernel_tiled_clients,
            inv_two_l2=0.5 / (lengthscale**2),
            inv_l4=1.0 / (lengthscale**4),
            prior=prior,
        ),
        interpret=interpret,
    )(cands, xs, xs, binv, pmat)
    return out[:, :, 0]
