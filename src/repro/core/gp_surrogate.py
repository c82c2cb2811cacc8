"""Trajectory-informed derived-GP gradient surrogates (paper Sec. 4.1, eq. 4-5).

Every client keeps the history of its own function queries (the *optimization
trajectory*).  Under the paper's assumption ``f_i ~ GP(mu, k)`` with a
shift-invariant kernel, the gradient follows a *derived* posterior GP whose mean

    grad_mu(x) = d_x k(x, X)^T (K + sigma^2 I)^{-1} y            (eq. 5)

is used as the local gradient surrogate, and whose covariance at ``x``

    d_sigma2(x) = d_x d_x' k|_{x,x} - d_x k(x,X)^T (K+s^2 I)^{-1} d_x' k(X,x)

provides the uncertainty measure driving active queries (Thm. 1 terms (1)/(2)).

Implementation notes (hardware adaptation, see DESIGN.md Sec. 2):

* The trajectory grows during optimization, which would force re-tracing under
  JIT.  We therefore keep a **fixed-capacity ring buffer** with a validity mask;
  the padded Gram system is block-diagonal ``[K_n + s^2 I, I]`` so the masked
  Cholesky solve returns *exactly* the un-padded answer (property-tested).
* The paper keeps the full trajectory; for long runs the ring buffer keeps the
  most recent ``capacity`` queries.  Appx. C.3 of the paper shows distant
  queries are uninformative for the surrogate at the current iterate, so a
  recency window is the faithful finite-memory realization.
* All hot math below is pure jnp; the TPU Pallas kernels in
  ``repro.kernels`` implement the same contractions with explicit VMEM tiling
  and are validated against these functions.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Trajectory(NamedTuple):
    """Fixed-capacity ring buffer of (x, y) function queries."""

    xs: jax.Array  # (capacity, d)
    ys: jax.Array  # (capacity,)
    count: jax.Array  # () int32 -- total number of appends (may exceed capacity)

    @property
    def capacity(self) -> int:
        return self.xs.shape[0]

    @property
    def dim(self) -> int:
        return self.xs.shape[1]

    def n_valid(self) -> jax.Array:
        return jnp.minimum(self.count, self.capacity)

    def valid_mask(self) -> jax.Array:
        return (jnp.arange(self.capacity) < self.n_valid()).astype(self.xs.dtype)


def traj_init(capacity: int, dim: int, dtype=jnp.float32) -> Trajectory:
    return Trajectory(
        xs=jnp.zeros((capacity, dim), dtype),
        ys=jnp.zeros((capacity,), dtype),
        count=jnp.zeros((), jnp.int32),
    )


def traj_append(traj: Trajectory, x: jax.Array, y: jax.Array) -> Trajectory:
    """Append one query; overwrites the oldest entry when full."""
    idx = jnp.mod(traj.count, traj.capacity)
    xs = jax.lax.dynamic_update_slice(traj.xs, x[None, :].astype(traj.xs.dtype), (idx, 0))
    ys = jax.lax.dynamic_update_slice(traj.ys, jnp.reshape(y, (1,)).astype(traj.ys.dtype), (idx,))
    return Trajectory(xs=xs, ys=ys, count=traj.count + 1)


def traj_append_batch(traj: Trajectory, xs: jax.Array, ys: jax.Array) -> Trajectory:
    """Append a batch of queries as ONE masked scatter (batch size is static).

    Semantically identical to folding ``traj_append`` over the rows (later
    rows win when the batch itself wraps the ring), but issues a single
    scatter instead of a length-k chain of ``dynamic_update_slice`` calls --
    this sits on the same per-step hot path as the Gram-factor cache.
    """
    k = xs.shape[0]
    cap = traj.capacity
    total = traj.count + k
    if k > cap:
        # Only the last `cap` rows survive a full wrap; slicing keeps every
        # write index distinct so the scatter stays order-independent.
        xs, ys = xs[k - cap :], ys[k - cap :]
        offset = k - cap
        k_eff = cap
    else:
        offset = 0
        k_eff = k
    idx = jnp.mod(traj.count + offset + jnp.arange(k_eff), cap)
    new_xs = traj.xs.at[idx].set(xs.astype(traj.xs.dtype))
    new_ys = traj.ys.at[idx].set(ys.astype(traj.ys.dtype))
    return Trajectory(xs=new_xs, ys=new_ys, count=total)


# ---------------------------------------------------------------------------
# Squared-exponential kernel and its derivatives (Appx. B kernel choice).
# ---------------------------------------------------------------------------


def sqexp(x1: jax.Array, x2: jax.Array, lengthscale: float) -> jax.Array:
    """k(X1, X2) pairwise SE kernel.  x1: (n,d)  x2: (m,d) -> (n,m)."""
    d2 = pairwise_sqdist(x1, x2)
    return jnp.exp(-0.5 * d2 / (lengthscale**2))


def pairwise_sqdist(x1: jax.Array, x2: jax.Array) -> jax.Array:
    """||x1_i - x2_j||^2 through the norm expansion, in coordinates centred
    on x1's first row (a live trajectory slot: the ring fills from slot 0).
    Distances are shift-invariant, but in the original frame the expansion
    cancels |x|^2 ~ d/4 against the cross term: at d=3072 the f32 rounding
    (~1e-4) exceeds the 1e-4 jitter floor, the Gram turns numerically
    indefinite and most factor updates fail."""
    c = x1[0]
    x1, x2 = x1 - c, x2 - c
    n1 = jnp.sum(x1 * x1, axis=-1)
    n2 = jnp.sum(x2 * x2, axis=-1)
    cross = x1 @ x2.T
    d2 = n1[:, None] + n2[None, :] - 2.0 * cross
    return jnp.maximum(d2, 0.0)


def dkdx(x: jax.Array, xs: jax.Array, lengthscale: float) -> jax.Array:
    """d_x k(x, X) for the SE kernel.

    x: (d,), xs: (n, d) -> (n, d) with row tau = -(x - x_tau)/l^2 * k(x, x_tau).
    """
    diff = x[None, :] - xs  # (n, d)
    k = jnp.exp(-0.5 * jnp.sum(diff * diff, axis=-1) / (lengthscale**2))  # (n,)
    return (-diff / (lengthscale**2)) * k[:, None]


class GPHyper(NamedTuple):
    lengthscale: jax.Array  # ()
    noise: jax.Array  # () observation noise variance sigma^2


def default_hyper(lengthscale: float = 1.0, noise: float = 1e-4) -> GPHyper:
    return GPHyper(jnp.asarray(lengthscale, jnp.float32), jnp.asarray(noise, jnp.float32))


def _jitter_of(hyper: GPHyper) -> jax.Array:
    return jnp.maximum(hyper.noise, 1e-4)


def _padded_gram(traj: Trajectory, hyper: GPHyper) -> tuple[jax.Array, jax.Array]:
    """Padded Gram system [K_n + s^2 I, I] and the validity mask.

    Invalid rows/cols are zeroed and their diagonal set to 1, so the solve on
    masked targets is exactly the solve of the live n x n system.
    """
    mask = traj.valid_mask()  # (cap,)
    k = sqexp(traj.xs, traj.xs, hyper.lengthscale)
    m2 = mask[:, None] * mask[None, :]
    jitter = _jitter_of(hyper)
    gram = k * m2 + jnp.diag(jitter * mask + (1.0 - mask))
    return gram, mask


def _masked_gram_chol(traj: Trajectory, hyper: GPHyper) -> tuple[tuple[jax.Array, jax.Array], jax.Array]:
    """Eigh factorization of the padded Gram system.

    Float32 + clustered active queries make the Gram numerically indefinite
    -- a trajectory full of points within the 0.01 active-query ball produced
    NaN Cholesky pivots in practice -- so we factor with eigh and CLAMP the
    spectrum at the jitter floor: a principled pseudo-solve that never
    explodes (capacity <= a few hundred, so the O(cap^3) is negligible).
    Returns ((eigvecs, eigvals), mask).

    This is the from-scratch ORACLE; the per-step hot path uses the
    incrementally maintained ``GramFactor`` below (DESIGN.md Sec. 2).
    """
    gram, mask = _padded_gram(traj, hyper)
    jitter = _jitter_of(hyper)
    w, v = jnp.linalg.eigh(gram)
    w = jnp.maximum(w, jitter)
    return (v, w), mask


def _gram_solve(factors: tuple[jax.Array, jax.Array], b: jax.Array) -> jax.Array:
    """(K+jitter)^-1 b via the clamped eigh factors.  b: (cap,) or (cap, d)."""
    v, w = factors
    vb = v.T @ b
    if b.ndim == 1:
        return v @ (vb / w)
    return v @ (vb / w[:, None])


def gp_alpha(traj: Trajectory, hyper: GPHyper) -> jax.Array:
    """alpha = (K + s^2 I)^{-1} y with masking.  (capacity,)"""
    factors, mask = _masked_gram_chol(traj, hyper)
    return _gram_solve(factors, traj.ys * mask)


def grad_mean(traj: Trajectory, hyper: GPHyper, x: jax.Array, alpha: jax.Array | None = None) -> jax.Array:
    """Posterior gradient mean  grad_mu(x)  (eq. 5).  x: (d,) -> (d,)."""
    if alpha is None:
        alpha = gp_alpha(traj, hyper)
    j = dkdx(x, traj.xs, hyper.lengthscale) * traj.valid_mask()[:, None]  # (cap, d)
    return j.T @ alpha


def grad_mean_batch(traj: Trajectory, hyper: GPHyper, xs: jax.Array) -> jax.Array:
    alpha = gp_alpha(traj, hyper)
    return jax.vmap(lambda x: grad_mean(traj, hyper, x, alpha))(xs)


def grad_uncertainty_trace(traj: Trajectory, hyper: GPHyper, x: jax.Array, chol_mask=None) -> jax.Array:
    """tr d_sigma2(x) -- the uncertainty score used for active queries.

    For the SE kernel  d_x d_x' k|_{x=x'} = I / l^2, so the prior trace is
    d / l^2 and the data correction is  sum_ij J A^{-1} J  with
    J = d_x k(x, X).  Trace is the cheap principled surrogate for the matrix
    norm in Thm. 1 (it upper-bounds the spectral norm up to d and preserves
    the ranking used to select active queries).
    """
    if chol_mask is None:
        factors, mask = _masked_gram_chol(traj, hyper)
    else:
        factors, mask = chol_mask
    d = x.shape[-1]
    j = dkdx(x, traj.xs, hyper.lengthscale) * mask[:, None]  # (cap, d)
    sol = _gram_solve(factors, j)  # (cap, d)
    prior = d / (hyper.lengthscale**2)
    corr = jnp.sum(j * sol)
    return jnp.maximum(prior - corr, 0.0)


def grad_uncertainty_batch(traj: Trajectory, hyper: GPHyper, xs: jax.Array) -> jax.Array:
    cm = _masked_gram_chol(traj, hyper)
    return jax.vmap(lambda x: grad_uncertainty_trace(traj, hyper, x, cm))(xs)


def select_active_queries(
    key: jax.Array,
    traj: Trajectory,
    hyper: GPHyper,
    center: jax.Array,
    n_candidates: int,
    n_select: int,
    radius: float,
    lo: float = 0.0,
    hi: float = 1.0,
) -> jax.Array:
    """Paper Appx. E general settings: sample ``n_candidates`` points
    uniformly in ``center +- radius``, return the ``n_select`` with the
    highest gradient-surrogate uncertainty.  -> (n_select, d)
    """
    d = center.shape[-1]
    delta = jax.random.uniform(key, (n_candidates, d), minval=-radius, maxval=radius)
    cands = jnp.clip(center[None, :] + delta, lo, hi)
    scores = grad_uncertainty_batch(traj, hyper, cands)
    _, top = jax.lax.top_k(scores, n_select)
    return cands[top]


def mean_value(traj: Trajectory, hyper: GPHyper, x: jax.Array) -> jax.Array:
    """Plain GP posterior mean of f itself (used in tests/benchmarks)."""
    alpha = gp_alpha(traj, hyper)
    kvec = sqexp(x[None, :], traj.xs, hyper.lengthscale)[0] * traj.valid_mask()
    return kvec @ alpha


# ---------------------------------------------------------------------------
# Incremental Gram-factor cache (DESIGN.md Sec. 2).
#
# The seed implementation refactorized the padded Gram system from scratch --
# an O(cap^3) eigh with iterative-QR constants -- at EVERY surrogate
# evaluation: once inside active-query scoring and once for the gradient
# estimate, i.e. twice per local step per client.  A step only appends
# ``1 + active_per_iter`` rows to the ring buffer, so the factorization is
# now carried in ``ClientState`` and maintained incrementally:
#
#   * the padded Gram MATRIX is updated by exact row/col replacement,
#     O(k * cap * d) per append event instead of O(cap^2 * d) rebuilds;
#   * while the buffer is still filling, the Cholesky factor is extended by
#     BORDERING: one triangular solve + a k x k factorization, O(cap^2 * k);
#   * once the ring wraps, row replacement invalidates trailing columns of
#     the factor, and the factor is refreshed with ONE blocked potrf of the
#     updated Gram.  That is O(cap^3 / 3) with LAPACK-grade constants --
#     measured ~8x cheaper than a single eigh at cap=128 -- and, unlike
#     hyperbolic-rotation cholupdate chains (implemented below, and
#     benchmarked slower on CPU because the column recurrence serializes),
#     it is a single fused XLA op with zero drift: every refresh factors the
#     true current Gram;
#   * if any live Cholesky pivot dips below the jitter floor (clustered
#     active queries can make the f32 Gram numerically indefinite), we fall
#     back to the seed's full clamped-eigh refactorization and KEEP the eigh
#     factors, so the pseudo-solve in that regime is identical to the
#     from-scratch oracle.  This preserves the NaN-robustness guarantee.
# ---------------------------------------------------------------------------

#: A live pivot below ``PIVOT_FLOOR_SCALE * sqrt(jitter)`` triggers the
#: clamped-eigh fallback.  sqrt(jitter) is the exact-arithmetic lower bound
#: for live pivots of the padded system, so 0.5x flags only genuine f32
#: indefiniteness, not the benign rounding of pivots sitting AT the floor.
PIVOT_FLOOR_SCALE = 0.5


class GramFactor(NamedTuple):
    """Cached factorization state of the padded Gram system.

    ``chol`` is the lower Cholesky factor of ``gram`` whenever ``exact`` is
    True.  After a clamped-eigh fallback ``exact`` is False and solves route
    through ``(eigvecs, eigvals)`` -- the clamped spectrum -- instead; the
    next append event always refreshes from ``gram`` directly, so inexact
    factors never compound.

    ``needs_repair`` is the deferred-repair flag (DESIGN.md Sec. 2.6): the
    branch-free update path (``factor_update_deferred``) never eigh-repairs
    inline.  An unhealthy candidate factor raises the flag and FREEZES the
    factor -- solves keep routing through the last-good factors -- until the
    chunk-boundary repair pass (``factor_repair_masked`` /
    ``core.rounds.repair_flagged_clients``) refactorizes the exact cached
    Gram.  The inline path (``factor_update``) never sets it.
    """

    gram: jax.Array  # (cap, cap) padded Gram matrix (always exact)
    chol: jax.Array  # (cap, cap) lower Cholesky factor (valid iff exact)
    eigvecs: jax.Array  # (cap, cap) fallback eigh factors (valid iff not exact)
    eigvals: jax.Array  # (cap,) clamped spectrum (valid iff not exact)
    exact: jax.Array  # () bool -- solve route selector
    n_updates: jax.Array  # () int32 incremental append events applied
    n_refactors: jax.Array  # () int32 clamped-eigh fallbacks/repairs taken
    needs_repair: jax.Array  # () bool -- deferred-repair flag (frozen factors)


def _factor_health(chol: jax.Array, mask: jax.Array, jitter: jax.Array) -> jax.Array:
    """True when every live pivot is finite and above the pivot floor."""
    floor = PIVOT_FLOOR_SCALE * jnp.sqrt(jitter)
    diag = jnp.diagonal(chol)
    live_diag = jnp.where(mask > 0, diag, 1.0)
    return jnp.isfinite(chol).all() & (live_diag >= floor).all()


def _clamped_eigh(gram: jax.Array, jitter: jax.Array) -> tuple[jax.Array, jax.Array]:
    w, v = jnp.linalg.eigh(gram)
    return v, jnp.maximum(w, jitter)


def factor_init(traj: Trajectory, hyper: GPHyper) -> GramFactor:
    """Build the factor cache from scratch (once per client, at init)."""
    gram, mask = _padded_gram(traj, hyper)
    jitter = _jitter_of(hyper)
    chol = jnp.linalg.cholesky(gram)
    ok = _factor_health(chol, mask, jitter)

    def fallback(_):
        return _clamped_eigh(gram, jitter)

    def keep(_):
        cap = gram.shape[0]
        return jnp.eye(cap, dtype=gram.dtype), jnp.ones((cap,), gram.dtype)

    v, w = jax.lax.cond(ok, keep, fallback, None)
    return GramFactor(
        gram=gram,
        chol=jnp.where(ok, chol, jnp.eye(gram.shape[0], dtype=gram.dtype)),
        eigvecs=v,
        eigvals=w,
        exact=ok,
        n_updates=jnp.zeros((), jnp.int32),
        n_refactors=(~ok).astype(jnp.int32),
        needs_repair=jnp.zeros((), bool),
    )


def _border_extend(
    chol: jax.Array, gram: jax.Array, start: jax.Array, k: int, jitter: jax.Array
) -> jax.Array:
    """Extend a Cholesky factor by k contiguous appended rows (no wrap).

    Rows ``start .. start+k-1`` of ``gram`` are newly valid; rows at and
    beyond ``start`` of ``chol`` are still identity (the invalid-slot
    padding), so the bordered update is one masked triangular solve plus a
    k x k factorization -- O(cap^2 * k), no refactorization.
    """
    cap = chol.shape[0]
    cols = jax.lax.dynamic_slice(gram, (0, start), (cap, k))  # (cap, k)
    prefix = (jnp.arange(cap) < start).astype(cols.dtype)[:, None]
    # Invalid rows of `chol` are e_i, so zeroing their rhs keeps z supported
    # on the live prefix: the full-size solve equals the p x p solve.
    z = jax.scipy.linalg.solve_triangular(chol, cols * prefix, lower=True)  # (cap, k)
    c22 = jax.lax.dynamic_slice(gram, (start, start), (k, k))
    s = c22 - z.T @ z
    ls = jnp.linalg.cholesky(s)  # (k, k) lower; NaN here -> health check fails
    rows = z.T * prefix.T  # (k, cap) -- left border, zero at/after `start`
    rows = jax.lax.dynamic_update_slice(rows, ls, (0, start))
    return jax.lax.dynamic_update_slice(chol, rows, (start, 0))


def chol_rank1_update(chol: jax.Array, x: jax.Array, sign: float, floor: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Rank-1 Cholesky update (+1) / downdate (-1) via hyperbolic rotations.

    Returns (L', ok) where ok is False if any pivot fell below ``floor``;
    on failure L' is unusable by contract (callers refactor).  O(cap^2) but a
    length-cap SEQUENTIAL column recurrence -- measured slower than one
    blocked potrf at cap=128 on CPU (see benchmarks/kernels_bench.py), which
    is why the hot path refreshes with potrf instead.  Kept as the textbook
    O(cap^2) row-replace primitive and validated against refactorization.
    """
    n = chol.shape[0]
    floor2 = floor * floor

    def body(k, carry):
        L, x, ok = carry
        lkk = L[k, k]
        xk = x[k]
        r2 = lkk * lkk + sign * xk * xk
        ok = ok & (r2 > floor2)
        r = jnp.sqrt(jnp.maximum(r2, floor2))
        c = r / lkk
        s = xk / lkk
        below = jnp.arange(n) > k
        col = L[:, k]
        newcol = jnp.where(below, (col + sign * s * x) / c, col).at[k].set(r)
        xnew = jnp.where(below, c * x - s * newcol, x)
        return L.at[:, k].set(newcol), xnew, ok

    L, _, ok = jax.lax.fori_loop(0, n, body, (chol, x, jnp.asarray(True)))
    return L, ok


def _gram_replace_rows(
    factor: GramFactor,
    traj_new: Trajectory,
    hyper: GPHyper,
    k: int,
    old_count: jax.Array,
) -> jax.Array:
    """Exact incremental row/col replacement of the padded Gram: O(k*cap*d)."""
    cap = traj_new.capacity
    jitter = _jitter_of(hyper)
    mask = traj_new.valid_mask()
    idx = jnp.mod(old_count + jnp.arange(k), cap)  # replaced slots
    xb = traj_new.xs[idx]  # (k, d)
    rows = sqexp(xb, traj_new.xs, hyper.lengthscale) * mask[None, :]
    rows = rows.at[jnp.arange(k), idx].add(jitter)  # live diagonal = 1 + jitter
    gram = factor.gram.at[idx, :].set(rows)
    return gram.at[:, idx].set(rows.T)


def factor_update(
    factor: GramFactor,
    traj_new: Trajectory,
    hyper: GPHyper,
    k: int,
    old_count: jax.Array,
) -> GramFactor:
    """Maintain the factor cache across one append event of k rows.

    ``traj_new`` must be ``traj_append_batch(traj_old, ...)`` with a static
    batch size ``k <= capacity``; ``old_count`` is ``traj_old.count``.
    """
    cap = traj_new.capacity
    if k > cap:
        raise ValueError(f"append event of {k} rows exceeds capacity {cap}")
    jitter = _jitter_of(hyper)
    mask = traj_new.valid_mask()
    gram = _gram_replace_rows(factor, traj_new, hyper, k, old_count)

    # --- factor maintenance: border while filling, blocked refresh after wrap
    fits = old_count + k <= cap

    def border(_):
        return _border_extend(factor.chol, gram, old_count, k, jitter)

    def refresh(_):
        return jnp.linalg.cholesky(gram)

    chol = jax.lax.cond(fits & factor.exact, border, refresh, None)
    ok = _factor_health(chol, mask, jitter)

    # --- spectral-clamp fallback: identical to the from-scratch oracle
    def fallback(_):
        return _clamped_eigh(gram, jitter)

    def keep(_):
        return factor.eigvecs, factor.eigvals

    v, w = jax.lax.cond(ok, keep, fallback, None)
    return GramFactor(
        gram=gram,
        chol=jnp.where(ok, chol, jnp.eye(cap, dtype=gram.dtype)),
        eigvecs=v,
        eigvals=w,
        exact=ok,
        n_updates=factor.n_updates + 1,
        n_refactors=factor.n_refactors + (~ok).astype(jnp.int32),
        needs_repair=jnp.zeros((), bool),
    )


def factor_update_deferred(
    factor: GramFactor,
    traj_new: Trajectory,
    hyper: GPHyper,
    k: int,
    old_count: jax.Array,
) -> GramFactor:
    """Branch-free Cholesky-only factor maintenance: NO eigh, ever.

    Same inputs/contract as ``factor_update``, but the rare unhealthy case
    no longer falls back to the clamped-eigh refactorization inline (under a
    client vmap ``lax.cond`` computes both branches, so the inline fallback
    costs one O(cap^3) eigh per client per append event whether taken or
    not).  Instead:

      * a healthy candidate factor (border pre-wrap, blocked potrf refresh
        post-wrap) is adopted as before;
      * an unhealthy candidate raises ``needs_repair`` and the factor
        FREEZES: solves keep routing through the last-good factors (the
        stale Cholesky factor when ``exact``, the retained eigh factors
        otherwise) via the same masked selection ``factor_solve`` already
        uses.  The cached Gram keeps its exact row/col updates, so nothing
        is lost -- the repair pass refactorizes it whole;
      * a flagged factor adopts NOTHING until ``factor_repair_masked``
        (driven at chunk boundaries by ``core.rounds.repair_flagged_clients``)
        clears the flag with one batched clamped-eigh over the flagged
        clients -- amortizing the eigh from per-step-per-client to
        per-chunk-per-flagged-client.

    Inexact factors still never compound: the first update after a repair
    refreshes from the (always-exact) cached Gram, exactly like the inline
    path.
    """
    cap = traj_new.capacity
    if k > cap:
        raise ValueError(f"append event of {k} rows exceeds capacity {cap}")
    jitter = _jitter_of(hyper)
    mask = traj_new.valid_mask()
    gram = _gram_replace_rows(factor, traj_new, hyper, k, old_count)

    fits = old_count + k <= cap
    use_border = fits & factor.exact & ~factor.needs_repair

    # Border vs blocked refresh under lax.cond: the unbatched per-device path
    # skips the untaken O(cap^3/3) potrf; under a client vmap both candidates
    # are computed and masked -- still no eigh anywhere in the graph.
    cand = jax.lax.cond(
        use_border,
        lambda: _border_extend(factor.chol, gram, old_count, k, jitter),
        lambda: jnp.linalg.cholesky(gram),
    )
    ok = _factor_health(cand, mask, jitter)
    adopt = ok & ~factor.needs_repair
    return GramFactor(
        gram=gram,
        chol=jnp.where(adopt, cand, factor.chol),
        eigvecs=factor.eigvecs,
        eigvals=factor.eigvals,
        exact=jnp.where(adopt, True, factor.exact),
        n_updates=factor.n_updates + 1,
        n_refactors=factor.n_refactors,  # repairs are counted at the boundary
        needs_repair=factor.needs_repair | ~ok,
    )


def factor_repair_masked(factor: GramFactor, jitter: jax.Array) -> GramFactor:
    """Clamped-eigh repair of flagged clients over a STACKED factor batch.

    ``factor`` leaves carry a leading client axis.  One batched eigh of the
    exact cached Grams; only flagged clients adopt the new (clamped) eigh
    factors -- identical to the inline fallback's pseudo-solve -- and drop
    their flag.  Runs under jit/shard_map with no collectives, so the
    distributed engine repairs per-shard.  (The vmap front door gathers the
    flagged subset on the host first -- see ``core.rounds`` -- so the eigh
    batch really is flagged-clients-only there.)
    """
    w, v = jnp.linalg.eigh(factor.gram)
    w = jnp.maximum(w, jitter)
    flag = factor.needs_repair  # (N,)
    fv = flag[:, None, None]
    return factor._replace(
        eigvecs=jnp.where(fv, v.astype(factor.eigvecs.dtype), factor.eigvecs),
        eigvals=jnp.where(flag[:, None], w.astype(factor.eigvals.dtype), factor.eigvals),
        exact=jnp.where(flag, False, factor.exact),
        n_refactors=factor.n_refactors + flag.astype(jnp.int32),
        needs_repair=jnp.zeros_like(flag),
    )


def factor_repair_gated(factor: GramFactor, jitter: jax.Array) -> GramFactor:
    """``factor_repair_masked`` behind a DEVICE-side flag-count gate.

    ``factor`` leaves carry a leading client axis.  The repair decision is
    made on device -- ``lax.cond`` on the scalar count of raised
    ``needs_repair`` flags -- so the caller never reads the flag vector to
    host: the all-healthy boundary (the measured ~1.0 case) costs one O(N)
    reduction and the untaken batched-eigh branch is skipped at runtime
    (the cond predicate is unbatched).  This is the zero-host-sync chunk
    boundary of DESIGN.md Sec. 3; ``core.rounds.repair_flagged_clients``
    keeps the host-read decision as the loop-driver oracle.
    """
    n_flagged = jnp.sum(factor.needs_repair.astype(jnp.int32))
    return jax.lax.cond(
        n_flagged > 0,
        lambda: factor_repair_masked(factor, jitter),
        lambda: factor,
    )


def traj_extend(
    traj: Trajectory,
    factor: GramFactor,
    xs: jax.Array,
    ys: jax.Array,
    hyper: GPHyper,
    deferred: bool = False,
) -> tuple[Trajectory, GramFactor]:
    """Append a (static-size) batch of queries and maintain the factor.

    ``deferred=True`` selects the branch-free Cholesky-only update
    (``factor_update_deferred``); the default keeps the inline clamped-eigh
    fallback as the equivalence oracle.
    """
    old_count = traj.count
    traj2 = traj_append_batch(traj, xs, ys)
    upd = factor_update_deferred if deferred else factor_update
    return traj2, upd(factor, traj2, hyper, xs.shape[0], old_count)


def factor_solve(factor: GramFactor, b: jax.Array) -> jax.Array:
    """(K + jitter)^-1 b through the cached factors.  b: (cap,) or (cap, m).

    Routes through the Cholesky factor in the exact regime and through the
    clamped-eigh factors after a fallback.  ``lax.cond`` lets the unbatched
    (per-device / benchmark) path skip the untaken branch entirely; under a
    client vmap the cond degenerates to computing both O(cap^2) branches,
    which is still far below one eigh.
    """
    return jax.lax.cond(
        factor.exact,
        lambda: jax.scipy.linalg.cho_solve((factor.chol, True), b),
        lambda: _gram_solve((factor.eigvecs, factor.eigvals), b),
    )


def factor_inverse(factor: GramFactor) -> jax.Array:
    """Explicit (K + jitter)^-1 -- feeds the fused candidate-scoring kernel."""
    eye = jnp.eye(factor.gram.shape[0], dtype=factor.gram.dtype)

    def from_chol():
        return jax.scipy.linalg.cho_solve((factor.chol, True), eye)

    def from_eigh():
        v, w = factor.eigvecs, factor.eigvals
        return (v / w[None, :]) @ v.T

    return jax.lax.cond(factor.exact, from_chol, from_eigh)


def gp_alpha_cached(traj: Trajectory, factor: GramFactor, hyper: GPHyper) -> jax.Array:
    """alpha = (K + s^2 I)^{-1} y via the cached factor.  O(cap^2)."""
    del hyper  # hyperparameters are baked into the factor
    return factor_solve(factor, traj.ys * traj.valid_mask())


def grad_mean_cached(
    traj: Trajectory,
    factor: GramFactor,
    hyper: GPHyper,
    x: jax.Array,
    alpha: jax.Array | None = None,
) -> jax.Array:
    """Posterior gradient mean (eq. 5) from cached factors."""
    if alpha is None:
        alpha = gp_alpha_cached(traj, factor, hyper)
    j = dkdx(x, traj.xs, hyper.lengthscale) * traj.valid_mask()[:, None]
    return j.T @ alpha


def grad_uncertainty_batch_cached(
    traj: Trajectory, factor: GramFactor, hyper: GPHyper, xs_q: jax.Array
) -> jax.Array:
    """Uncertainty scores for a candidate batch, O(cap^2) per candidate.

    Expands tr(J^T A^{-1} J) through the SE-kernel structure of J so the
    per-candidate cost drops from O(cap^2 d) triangular solves to one
    matvec against the masked inverse (see kernels/ref.py:uncertainty_scores
    for the algebra); the whole batch is one fused pass in
    ``repro.kernels.ops.uncertainty_scores``.

    The contraction is evaluated in coordinates SHIFTED to the candidate
    centroid: the expansion's three terms cancel against each other, and in
    the original frame their magnitudes scale with ||x||^2, costing ~10x in
    f32 accuracy.  Distances (hence h and the scores) are shift-invariant,
    so this is numerics only.
    """
    from repro.kernels import ops  # deferred: keep core importable without kernels

    mask = traj.valid_mask()
    binv = factor_inverse(factor) * (mask[:, None] * mask[None, :])
    c0 = jnp.mean(xs_q, axis=0)
    xs_sh = (traj.xs - c0[None, :]) * mask[:, None]
    pmat = binv * (xs_sh @ xs_sh.T)
    d = traj.dim
    prior = d / (hyper.lengthscale**2)
    return ops.uncertainty_scores(
        xs_q - c0[None, :], xs_sh, binv, pmat, lengthscale=hyper.lengthscale, prior=prior
    )


def grad_uncertainty_trace_cached(
    traj: Trajectory, factor: GramFactor, hyper: GPHyper, x: jax.Array
) -> jax.Array:
    return grad_uncertainty_batch_cached(traj, factor, hyper, x[None, :])[0]


def select_active_queries_cached(
    key: jax.Array,
    traj: Trajectory,
    factor: GramFactor,
    hyper: GPHyper,
    center: jax.Array,
    n_candidates: int,
    n_select: int,
    radius: float,
    lo: float = 0.0,
    hi: float = 1.0,
) -> jax.Array:
    """``select_active_queries`` scoring through the cached factor."""
    d = center.shape[-1]
    delta = jax.random.uniform(key, (n_candidates, d), minval=-radius, maxval=radius)
    cands = jnp.clip(center[None, :] + delta, lo, hi)
    scores = grad_uncertainty_batch_cached(traj, factor, hyper, cands)
    _, top = jax.lax.top_k(scores, n_select)
    return cands[top]


# ---------------------------------------------------------------------------
# Client-batched cached surrogate (DESIGN.md Sec. 2.6 / Sec. 4).
#
# Under the vmapped simulation engine every client evaluates the SAME
# surrogate contraction shapes at every local step, so the scoring and
# gradient-mean kernels take the whole client batch in ONE launch (a client
# grid dimension in the Pallas kernels) instead of N vmapped launches.  All
# stacked arguments carry a leading client axis N; the math per client is
# identical to the unbatched functions above (tested).
# ---------------------------------------------------------------------------


def traj_extend_clients(
    trajs: Trajectory,
    factors: GramFactor,
    xs: jax.Array,  # (N, k, d)
    ys: jax.Array,  # (N, k)
    hyper: GPHyper,
    deferred: bool = False,
) -> tuple[Trajectory, GramFactor]:
    """``traj_extend`` over a stacked client batch (same default as there)."""
    return jax.vmap(lambda tr, fa, x, y: traj_extend(tr, fa, x, y, hyper, deferred))(
        trajs, factors, xs, ys
    )


def gp_alpha_cached_clients(trajs: Trajectory, factors: GramFactor) -> jax.Array:
    """Stacked alpha = (K + s^2 I)^{-1} y, (N, cap)."""
    masks = jax.vmap(Trajectory.valid_mask)(trajs)
    return jax.vmap(factor_solve)(factors, trajs.ys * masks)


def grad_mean_cached_clients(
    trajs: Trajectory,
    factors: GramFactor,
    hyper: GPHyper,
    xs: jax.Array,
    *,
    block_n: int | None = None,
    block_cap: int | None = None,
) -> jax.Array:
    """Posterior gradient mean at one point per client: (N, d) -> (N, d).

    One client-batched fused kernel launch (``ops.grad_mean_clients``)
    instead of N vmapped launches.  Unset block sizes defer to the
    autotuner, which resolves the single-query candidate axis to the f32
    sublane tile (block_n=8: a 128-row block would be ~99% padding work);
    ``AlgoConfig.grad_block_*`` pins them instead.
    """
    from repro.kernels import ops  # deferred: keep core importable without kernels

    alpha = gp_alpha_cached_clients(trajs, factors)
    # evaluated in coordinates centred on each query point: the gradient
    # mean is shift-invariant, and the kernel's distance expansion then
    # cancels nothing (see pairwise_sqdist)
    out = ops.grad_mean_clients(
        jnp.zeros_like(xs)[:, None, :], trajs.xs - xs[:, None, :], alpha,
        lengthscale=hyper.lengthscale, block_n=block_n, block_cap=block_cap,
    )
    return out[:, 0, :]


def grad_uncertainty_batch_cached_clients(
    trajs: Trajectory,
    factors: GramFactor,
    hyper: GPHyper,
    xs_q: jax.Array,
    *,
    block_n: int | None = None,
    block_cap: int | None = None,
) -> jax.Array:
    """Uncertainty scores for a per-client candidate batch: (N, nc, d) -> (N, nc).

    Client-batched analogue of ``grad_uncertainty_batch_cached`` (same
    centroid-shifted contraction, see that docstring for the numerics); the
    whole client batch is ONE fused pass in ``ops.uncertainty_scores_clients``.
    Unset block sizes defer to the autotuner; ``AlgoConfig.score_block_*``
    pins them.
    """
    from repro.kernels import ops  # deferred: keep core importable without kernels

    masks = jax.vmap(Trajectory.valid_mask)(trajs)  # (N, cap)
    binv = jax.vmap(factor_inverse)(factors) * (masks[:, :, None] * masks[:, None, :])
    c0 = jnp.mean(xs_q, axis=1)  # (N, d) per-client candidate centroid
    xs_sh = (trajs.xs - c0[:, None, :]) * masks[:, :, None]
    pmat = binv * jnp.einsum("ncd,nkd->nck", xs_sh, xs_sh)
    d = trajs.xs.shape[-1]
    prior = d / (hyper.lengthscale**2)
    return ops.uncertainty_scores_clients(
        xs_q - c0[:, None, :], xs_sh, binv, pmat,
        lengthscale=hyper.lengthscale, prior=prior,
        block_n=block_n, block_cap=block_cap,
    )


def select_active_queries_cached_clients(
    keys: jax.Array,  # (N, 2) per-client PRNG keys
    trajs: Trajectory,
    factors: GramFactor,
    hyper: GPHyper,
    centers: jax.Array,  # (N, d)
    n_candidates: int,
    n_select: int,
    radius: float,
    lo: float = 0.0,
    hi: float = 1.0,
    *,
    block_n: int | None = None,
    block_cap: int | None = None,
) -> jax.Array:
    """``select_active_queries_cached`` for the whole client batch: (N, n_select, d)."""
    d = centers.shape[-1]
    delta = jax.vmap(
        lambda k: jax.random.uniform(k, (n_candidates, d), minval=-radius, maxval=radius)
    )(keys)
    cands = jnp.clip(centers[:, None, :] + delta, lo, hi)
    scores = grad_uncertainty_batch_cached_clients(
        trajs, factors, hyper, cands, block_n=block_n, block_cap=block_cap
    )
    _, top = jax.lax.top_k(scores, n_select)  # batched over the client axis
    return jnp.take_along_axis(cands, top[:, :, None], axis=1)
