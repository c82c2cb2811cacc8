"""Pallas TPU kernel: fused batched derived-GP gradient mean (eq. 5).

For a block of query points C the posterior gradient mean under the SE
kernel is

    grad_mu(c) = (1/l^2) [ (h o alpha) @ X - (h . alpha) c ],   h_t = k(c, x_t)

where alpha = (K + s^2 I)^{-1} y comes from the cached Gram factor
(core/gp_surrogate ``GramFactor``) with the validity mask already folded in
(masked solves leave invalid slots exactly zero).  The kernel fuses the
kernel-vector generation with both contractions, so neither the (bn, cap)
h-tile nor the explicit (cap, d) dkdx Jacobian ever materializes in HBM --
the seed path built J per query point.

Two kernel families share the tile numerics:

* **resident** (``grad_mean_kernel``): grid (n / block_n,); xs and alpha
  stay fully VMEM-resident across programs.
* **cap-tiled** (``grad_mean_tiled_kernel``): grid
  (n/block_n, cap/block_cap) -- the trailing grid dimension streams
  (block_cap, d) trajectory tiles while a (block_n, d) f32 VMEM scratch
  holds the running ``(h o alpha) @ X`` accumulator and a (block_n, 1)
  scratch the running ``h . alpha``, so VMEM residency is independent of
  cap.  Padded trajectory slots carry alpha == 0 and contribute exactly
  zero (w = h o alpha vanishes there).  The finalize step applies
  ``(acc - s o c) / l^2`` at the last cap tile.

``*_clients_kernel`` variants add a CLIENT grid dimension for the batched
federated engine: one launch computes the gradient mean for the whole
client batch instead of N vmapped launches.

Every launch is constructed from a declarative ``KernelSpec``
(``grad_*_spec`` builders below): the spec both builds the real
``pl.pallas_call`` and feeds the static auditor in
``repro.analysis.kernel_audit`` (DESIGN.md Sec. 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.spec import MXU_PRECISION, ArraySpec, BlockDecl, KernelSpec, ScratchDecl


def _grad_block(c, x, alpha, *, inv_two_l2: float, inv_l2: float):
    """Shared VMEM-tile numerics of both kernels.  c (bn, d), x (cap, d),
    alpha (1, cap) -> (bn, d)."""
    n1 = jnp.sum(c * c, axis=-1, keepdims=True)  # (bn, 1)
    n2 = jnp.sum(x * x, axis=-1, keepdims=True).T  # (1, cap)
    cross = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    d2 = jnp.maximum(n1 + n2 - 2.0 * cross, 0.0)
    w = jnp.exp(-d2 * inv_two_l2) * alpha  # (bn, cap), alpha row-broadcast
    acc = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )  # (bn, d)
    s = jnp.sum(w, axis=-1, keepdims=True)
    return (acc - s * c) * inv_l2


def _kernel(c_ref, x_ref, a_ref, o_ref, **kw):
    o_ref[...] = _grad_block(c_ref[...], x_ref[...], a_ref[...], **kw).astype(o_ref.dtype)


def grad_resident_spec(n: int, cap: int, d: int, dtype, *,
                       block_n: int) -> KernelSpec:
    """Launch geometry of the VMEM-resident gradient-mean kernel."""
    return KernelSpec(
        name="gp_grad.resident",
        grid=(n // block_n,),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((cap, d), dtype),
            ArraySpec((1, cap), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i: (i, 0)),
            BlockDecl((cap, d), lambda i: (0, 0)),
            BlockDecl((1, cap), lambda i: (0, 0)),
        ),
        out_shapes=(ArraySpec((n, d), dtype),),
        out_specs=(BlockDecl((block_n, d), lambda i: (i, 0)),),
    )


@functools.partial(jax.jit, static_argnames=("lengthscale", "block_n", "interpret"))
def grad_mean_kernel(
    cands: jax.Array,
    xs: jax.Array,
    alpha: jax.Array,  # (1, cap) -- row vector for TPU-friendly layout
    *,
    lengthscale: float,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    n, d = cands.shape
    cap = xs.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert alpha.shape == (1, cap), alpha.shape
    spec = grad_resident_spec(n, cap, d, cands.dtype, block_n=block_n)
    return spec.pallas_call(
        functools.partial(
            _kernel, inv_two_l2=0.5 / (lengthscale**2), inv_l2=1.0 / (lengthscale**2)
        ),
        interpret=interpret,
    )(cands, xs, alpha)


def _kernel_clients(c_ref, x_ref, a_ref, o_ref, **kw):
    # Leading block dim of every ref is the (size-1) client slot; the tile
    # numerics are shared with the unbatched kernel (_grad_block).
    o_ref[0] = _grad_block(c_ref[0], x_ref[0], a_ref[0], **kw).astype(o_ref.dtype)


def grad_clients_spec(nb: int, n: int, cap: int, d: int, dtype, *,
                      block_n: int) -> KernelSpec:
    """Launch geometry of the client-batched resident gradient-mean kernel."""
    return KernelSpec(
        name="gp_grad.clients",
        grid=(nb, n // block_n),
        in_shapes=(
            ArraySpec((nb, n, d), dtype),
            ArraySpec((nb, cap, d), dtype),
            ArraySpec((nb, 1, cap), dtype),
        ),
        in_specs=(
            BlockDecl((1, block_n, d), lambda b, i: (b, i, 0)),
            BlockDecl((1, cap, d), lambda b, i: (b, 0, 0)),
            BlockDecl((1, 1, cap), lambda b, i: (b, 0, 0)),
        ),
        out_shapes=(ArraySpec((nb, n, d), dtype),),
        out_specs=(BlockDecl((1, block_n, d), lambda b, i: (b, i, 0)),),
    )


@functools.partial(jax.jit, static_argnames=("lengthscale", "block_n", "interpret"))
def grad_mean_clients_kernel(
    cands: jax.Array,  # (N, n, d)
    xs: jax.Array,  # (N, cap, d)
    alpha: jax.Array,  # (N, 1, cap) -- row vectors for TPU-friendly layout
    *,
    lengthscale: float,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Client-batched gradient mean: grid (N, n/block_n) -> (N, n, d)."""
    nb, n, d = cands.shape
    cap = xs.shape[1]
    assert n % block_n == 0, (n, block_n)
    assert xs.shape == (nb, cap, d), (xs.shape, cands.shape)
    assert alpha.shape == (nb, 1, cap), alpha.shape
    spec = grad_clients_spec(nb, n, cap, d, cands.dtype, block_n=block_n)
    return spec.pallas_call(
        functools.partial(
            _kernel_clients, inv_two_l2=0.5 / (lengthscale**2), inv_l2=1.0 / (lengthscale**2)
        ),
        interpret=interpret,
    )(cands, xs, alpha)


# ---------------------------------------------------------------------------
# Cap-tiled kernels: the (cap, d) trajectory / (cap,) alpha stream through
# VMEM one (block_cap, d) tile at a time with a running (bn, d) accumulator.
# ---------------------------------------------------------------------------


def _grad_cell(c, x, alpha, acc_ref, s_ref, *, inv_two_l2: float):
    """Accumulate one cap tile:  acc += (h o alpha) @ x,  s += (h . alpha).

    c (bn, d), x (bc, d), alpha (1, bc).  Padded trajectory slots arrive
    with alpha == 0, so w vanishes there exactly.  Accumulation is f32.
    """
    n1 = jnp.sum(c * c, axis=-1, keepdims=True)  # (bn, 1)
    n2 = jnp.sum(x * x, axis=-1, keepdims=True).T  # (1, bc)
    cross = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    )
    d2 = jnp.maximum(n1 + n2 - 2.0 * cross, 0.0)
    w = jnp.exp(-d2 * inv_two_l2) * alpha  # (bn, bc)
    acc_ref[...] += jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32
    ).astype(jnp.float32)
    s_ref[...] += jnp.sum(w, axis=-1, keepdims=True).astype(jnp.float32)


def _kernel_tiled(c_ref, x_ref, a_ref, o_ref, acc_ref, s_ref, *,
                  inv_two_l2: float, inv_l2: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    _grad_cell(c_ref[...], x_ref[...], a_ref[...], acc_ref, s_ref,
               inv_two_l2=inv_two_l2)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = (
            (acc_ref[...] - s_ref[...] * c_ref[...]) * inv_l2
        ).astype(o_ref.dtype)


def grad_tiled_spec(n: int, cap: int, d: int, dtype, *, block_n: int,
                    block_cap: int) -> KernelSpec:
    """Launch geometry of the cap-tiled gradient-mean kernel.  The trailing
    grid axis revisits each (block_n, d) output block while two f32
    scratch buffers hold the running contraction and weight sum."""
    return KernelSpec(
        name="gp_grad.tiled",
        grid=(n // block_n, cap // block_cap),
        in_shapes=(
            ArraySpec((n, d), dtype),
            ArraySpec((cap, d), dtype),
            ArraySpec((1, cap), dtype),
        ),
        in_specs=(
            BlockDecl((block_n, d), lambda i, j: (i, 0)),
            BlockDecl((block_cap, d), lambda i, j: (j, 0)),
            BlockDecl((1, block_cap), lambda i, j: (0, j)),
        ),
        out_shapes=(ArraySpec((n, d), dtype),),
        out_specs=(BlockDecl((block_n, d), lambda i, j: (i, 0)),),
        scratch=(
            ScratchDecl((block_n, d), jnp.float32),
            ScratchDecl((block_n, 1), jnp.float32),
        ),
        revisit_axes=(1,),
        init_axes=(1,),
    )


@functools.partial(
    jax.jit, static_argnames=("lengthscale", "block_n", "block_cap", "interpret")
)
def grad_mean_tiled_kernel(
    cands: jax.Array,
    xs: jax.Array,
    alpha: jax.Array,  # (1, cap)
    *,
    lengthscale: float,
    block_n: int = 128,
    block_cap: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Cap-tiled gradient mean: grid (n/block_n, cap/block_cap)."""
    n, d = cands.shape
    cap = xs.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert cap % block_cap == 0, (cap, block_cap)
    assert alpha.shape == (1, cap), alpha.shape
    spec = grad_tiled_spec(n, cap, d, cands.dtype,
                           block_n=block_n, block_cap=block_cap)
    return spec.pallas_call(
        functools.partial(
            _kernel_tiled, inv_two_l2=0.5 / (lengthscale**2), inv_l2=1.0 / (lengthscale**2)
        ),
        interpret=interpret,
    )(cands, xs, alpha)


def _kernel_tiled_clients(c_ref, x_ref, a_ref, o_ref, acc_ref, s_ref, *,
                          inv_two_l2: float, inv_l2: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    _grad_cell(c_ref[0], x_ref[0], a_ref[0], acc_ref, s_ref,
               inv_two_l2=inv_two_l2)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (
            (acc_ref[...] - s_ref[...] * c_ref[0]) * inv_l2
        ).astype(o_ref.dtype)


def grad_tiled_clients_spec(nb: int, n: int, cap: int, d: int, dtype, *,
                            block_n: int, block_cap: int) -> KernelSpec:
    """Launch geometry of the client-batched cap-tiled gradient-mean kernel."""
    return KernelSpec(
        name="gp_grad.tiled_clients",
        grid=(nb, n // block_n, cap // block_cap),
        in_shapes=(
            ArraySpec((nb, n, d), dtype),
            ArraySpec((nb, cap, d), dtype),
            ArraySpec((nb, 1, cap), dtype),
        ),
        in_specs=(
            BlockDecl((1, block_n, d), lambda b, i, j: (b, i, 0)),
            BlockDecl((1, block_cap, d), lambda b, i, j: (b, j, 0)),
            BlockDecl((1, 1, block_cap), lambda b, i, j: (b, 0, j)),
        ),
        out_shapes=(ArraySpec((nb, n, d), dtype),),
        out_specs=(BlockDecl((1, block_n, d), lambda b, i, j: (b, i, 0)),),
        scratch=(
            ScratchDecl((block_n, d), jnp.float32),
            ScratchDecl((block_n, 1), jnp.float32),
        ),
        revisit_axes=(2,),
        init_axes=(2,),
    )


@functools.partial(
    jax.jit, static_argnames=("lengthscale", "block_n", "block_cap", "interpret")
)
def grad_mean_tiled_clients_kernel(
    cands: jax.Array,  # (N, n, d)
    xs: jax.Array,  # (N, cap, d)
    alpha: jax.Array,  # (N, 1, cap)
    *,
    lengthscale: float,
    block_n: int = 128,
    block_cap: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Client-batched cap-tiled gradient mean:
    grid (N, n/block_n, cap/block_cap) -> (N, n, d)."""
    nb, n, d = cands.shape
    cap = xs.shape[1]
    assert n % block_n == 0, (n, block_n)
    assert cap % block_cap == 0, (cap, block_cap)
    assert xs.shape == (nb, cap, d), (xs.shape, cands.shape)
    assert alpha.shape == (nb, 1, cap), alpha.shape
    spec = grad_tiled_clients_spec(nb, n, cap, d, cands.dtype,
                                   block_n=block_n, block_cap=block_cap)
    return spec.pallas_call(
        functools.partial(
            _kernel_tiled_clients,
            inv_two_l2=0.5 / (lengthscale**2),
            inv_l2=1.0 / (lengthscale**2),
        ),
        interpret=interpret,
    )(cands, xs, alpha)
