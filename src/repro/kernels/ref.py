"""Pure-jnp oracles for every Pallas kernel in this package.

These are the numerical ground truth: each kernel's test sweeps shapes and
dtypes and asserts allclose against these functions (interpret=True on CPU).
They are also the CPU execution path used by ops.py when no TPU is present.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rff_features(x: jax.Array, v: jax.Array, b: jax.Array, n_features: int | None = None) -> jax.Array:
    """phi(X) = sqrt(2/M) cos(X V^T + b).   x (n,d), v (M,d), b (M,) -> (n,M)."""
    m = n_features if n_features is not None else v.shape[0]
    proj = x @ v.T + b[None, :]
    return (math.sqrt(2.0 / m) * jnp.cos(proj)).astype(x.dtype)


def rff_grad(x: jax.Array, v: jax.Array, b: jax.Array, w: jax.Array, n_features: int | None = None) -> jax.Array:
    """grad phi(X)^T w = -sqrt(2/M) (sin(X V^T + b) * w) V.

    x (n,d), v (M,d), b (M,), w (M,) -> (n,d).
    """
    m = n_features if n_features is not None else v.shape[0]
    s = jnp.sin(x @ v.T + b[None, :])  # (n, M)
    return (-math.sqrt(2.0 / m) * ((s * w[None, :]) @ v)).astype(x.dtype)


def sqexp(x1: jax.Array, x2: jax.Array, lengthscale: float) -> jax.Array:
    """K(X1, X2) = exp(-||x1-x2||^2 / (2 l^2)).  (n,d),(m,d) -> (n,m)."""
    n1 = jnp.sum(x1 * x1, axis=-1)
    n2 = jnp.sum(x2 * x2, axis=-1)
    d2 = jnp.maximum(n1[:, None] + n2[None, :] - 2.0 * (x1 @ x2.T), 0.0)
    return jnp.exp(-0.5 * d2 / (lengthscale**2)).astype(x1.dtype)


def uncertainty_scores(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    lengthscale: float,
    prior: float,
) -> jax.Array:
    """Gradient-surrogate uncertainty scores for a candidate batch.

    For the SE kernel the data correction of tr d_sigma2(c) expands through
    the structure of J(c) = d_c k(c, X):

        corr(c) = (1/l^4) [ h^T (B o XX^T) h  -  2 (h o Xc)^T B h
                            + (c.c) h^T B h ],     h_t = k(c, x_t),

    where ``binv`` is the MASKED inverse M (K + s^2 I)^{-1} M and
    ``pmat = binv o (X X^T)`` is precomputed once per trajectory state.  The
    per-candidate cost is O(cap^2) -- one matvec against each cached matrix
    -- instead of the O(cap^2 d) triangular solves of the direct form.

    cands (n, d), xs (cap, d), binv/pmat (cap, cap) -> (n,).
    """
    n1 = jnp.sum(cands * cands, axis=-1)
    n2 = jnp.sum(xs * xs, axis=-1)
    cross = cands @ xs.T  # (n, cap) -- doubles as the c.x_t table
    d2 = jnp.maximum(n1[:, None] + n2[None, :] - 2.0 * cross, 0.0)
    h = jnp.exp(-0.5 * d2 / (lengthscale**2))
    g1 = h @ pmat
    g2 = h @ binv
    t1 = jnp.sum(g1 * h, axis=-1)
    t2 = jnp.sum(h * cross * g2, axis=-1)
    t3 = n1 * jnp.sum(h * g2, axis=-1)
    corr = (t1 - 2.0 * t2 + t3) / (lengthscale**4)
    return jnp.maximum(prior - corr, 0.0).astype(cands.dtype)


def uncertainty_scores_clients(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    lengthscale: float,
    prior: float,
) -> jax.Array:
    """Client-batched ``uncertainty_scores``: one batched contraction pass.

    cands (N, n, d), xs (N, cap, d), binv/pmat (N, cap, cap) -> (N, n).
    Per-client math identical to the unbatched oracle (property-tested);
    mirrors the client grid dimension of the batched Pallas kernel.
    """
    n1 = jnp.sum(cands * cands, axis=-1)  # (N, n)
    n2 = jnp.sum(xs * xs, axis=-1)  # (N, cap)
    cross = jnp.einsum("bnd,bcd->bnc", cands, xs)  # doubles as the c.x_t table
    d2 = jnp.maximum(n1[..., None] + n2[:, None, :] - 2.0 * cross, 0.0)
    h = jnp.exp(-0.5 * d2 / (lengthscale**2))
    g1 = jnp.einsum("bnc,bck->bnk", h, pmat)
    g2 = jnp.einsum("bnc,bck->bnk", h, binv)
    t1 = jnp.sum(g1 * h, axis=-1)
    t2 = jnp.sum(h * cross * g2, axis=-1)
    t3 = n1 * jnp.sum(h * g2, axis=-1)
    corr = (t1 - 2.0 * t2 + t3) / (lengthscale**4)
    return jnp.maximum(prior - corr, 0.0).astype(cands.dtype)


def uncertainty_scores_clients_fused(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    lengthscale: float,
    prior: float,
) -> jax.Array:
    """Fused-epilogue ``uncertainty_scores_clients``: the CPU execution path.

    Identical math through the identity

        t1 - 2 t2 + t3 = sum_k [ g1 - (2 cross - c.c) o g2 ]_k h_k,

    which XLA fuses into one elementwise pass + one reduction over the
    (N, n, cap) intermediates instead of the textbook form's three -- the
    measured batched-over-vmapped scoring win on CPU.  The per-element cancellation before the reduction
    is also the numerically kinder order.  The textbook
    ``uncertainty_scores_clients`` above stays as the ground-truth oracle
    the tests compare against; the Pallas tile kernels use this same
    epilogue (kernels/gp_score.py).
    """
    n1 = jnp.sum(cands * cands, axis=-1)  # (N, n)
    n2 = jnp.sum(xs * xs, axis=-1)  # (N, cap)
    cross = jnp.einsum("bnd,bcd->bnc", cands, xs)
    d2 = jnp.maximum(n1[..., None] + n2[:, None, :] - 2.0 * cross, 0.0)
    h = jnp.exp(-0.5 * d2 / (lengthscale**2))
    g1 = jnp.einsum("bnc,bck->bnk", h, pmat)
    g2 = jnp.einsum("bnc,bck->bnk", h, binv)
    m = g1 - (2.0 * cross - n1[..., None]) * g2
    corr = jnp.sum(m * h, axis=-1) / (lengthscale**4)
    return jnp.maximum(prior - corr, 0.0).astype(cands.dtype)


def grad_mean_clients(
    cands: jax.Array, xs: jax.Array, alpha: jax.Array, lengthscale: float
) -> jax.Array:
    """Client-batched ``grad_mean_batch``.

    cands (N, n, d), xs (N, cap, d), alpha (N, cap) -> (N, n, d).
    """
    n1 = jnp.sum(cands * cands, axis=-1)
    n2 = jnp.sum(xs * xs, axis=-1)
    cross = jnp.einsum("bnd,bcd->bnc", cands, xs)
    d2 = jnp.maximum(n1[..., None] + n2[:, None, :] - 2.0 * cross, 0.0)
    h = jnp.exp(-0.5 * d2 / (lengthscale**2))
    w = h * alpha[:, None, :]
    out = jnp.einsum("bnc,bcd->bnd", w, xs) - jnp.sum(w, axis=-1, keepdims=True) * cands
    return (out / (lengthscale**2)).astype(cands.dtype)


def grad_mean_batch(
    cands: jax.Array, xs: jax.Array, alpha: jax.Array, lengthscale: float
) -> jax.Array:
    """Batched posterior gradient mean  J(c)^T alpha  (eq. 5).

    grad_mu(c) = (1/l^2) [ (h o alpha) @ X  -  (h . alpha) c ],
    h_t = k(c, x_t).  ``alpha`` must already carry the validity mask (solves
    of masked targets leave invalid slots exactly zero).

    cands (n, d), xs (cap, d), alpha (cap,) -> (n, d).
    """
    n1 = jnp.sum(cands * cands, axis=-1)
    n2 = jnp.sum(xs * xs, axis=-1)
    cross = cands @ xs.T
    d2 = jnp.maximum(n1[:, None] + n2[None, :] - 2.0 * cross, 0.0)
    h = jnp.exp(-0.5 * d2 / (lengthscale**2))
    w = h * alpha[None, :]
    out = (w @ xs - jnp.sum(w, axis=-1, keepdims=True) * cands) / (lengthscale**2)
    return out.astype(cands.dtype)
