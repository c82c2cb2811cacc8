"""Public jit'd wrappers around the Pallas kernels.

Handles block-size padding (zero-pad, slice back), block-size selection and
backend selection: on TPU the Pallas kernels run compiled; elsewhere the
jnp oracles in ref.py run, which are numerically identical, and the kernels
run in interpret mode only when ``force_pallas`` (used by tests).  The route
is decided by the backend alone: the kernels bake their scalars
(lengthscale, prior) into the program, so on the kernel route those must be
static Python floats, and a traced one is an error (``_static_float``).

The GP kernels (scoring / grad mean) take ``block_n`` / ``block_cap``; when
left ``None`` the tuner in ``kernels/autotune.py`` picks them
deterministically per (backend, shape).  ``block_cap >= cap`` routes to the
VMEM-resident kernels; smaller ``block_cap`` routes to the cap-tiled
kernels, with the trajectory axis zero-padded to a tile multiple -- padded
slots contribute EXACTLY zero (zero B/P rows+columns for scoring, zero
alpha for the grad mean), so tiling never perturbs results.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.gp_grad import (
    grad_mean_clients_kernel,
    grad_mean_kernel,
    grad_mean_tiled_clients_kernel,
    grad_mean_tiled_kernel,
)
from repro.kernels.gp_score import (
    uncertainty_scores_clients_kernel,
    uncertainty_scores_kernel,
    uncertainty_scores_tiled_clients_kernel,
    uncertainty_scores_tiled_kernel,
)
from repro.kernels.rff_features import rff_features_kernel
from repro.kernels.rff_grad import rff_grad_kernel
from repro.kernels.sqexp import sqexp_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _static_float(x) -> float:
    """A kernel scalar as a Python float.

    The Pallas kernels bake scalars (lengthscale, prior) into the program as
    compile-time constants.  The engine passes its static ``AlgoConfig``
    floats (``algorithms._hyper_of``); a traced scalar cannot be baked in,
    and taking the oracle instead would hide that the kernel never ran.
    """
    if isinstance(x, jax.core.Tracer):
        raise TypeError(
            "a traced kernel scalar (lengthscale/prior) reached a Pallas "
            "kernel wrapper; pass static Python floats on the kernel route"
        )
    return float(x)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_rows(a: jax.Array, target: int) -> jax.Array:
    pad = target - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def _pad_axis1(a: jax.Array, target: int) -> jax.Array:
    """Zero-pad the second axis (the per-client candidate axis)."""
    pad = target - a.shape[1]
    if pad == 0:
        return a
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def _pad_axis(a: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad one axis to ``target`` (cap-axis padding for tiled kernels)."""
    pad = target - a.shape[axis]
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _pad_gram(a: jax.Array, target: int) -> jax.Array:
    """Zero-pad BOTH trailing axes of a (..., cap, cap) Gram-shaped array.
    Zero rows AND columns make padded trajectory slots contribute exactly
    zero in the tiled bilinear form (see kernels/gp_score.py)."""
    return _pad_axis(_pad_axis(a, a.ndim - 1, target), a.ndim - 2, target)


def _resolve_blocks(kind, n, cap, d, n_clients, block_n, block_cap, dtype=None):
    """Fill in unset block sizes from the deterministic autotuner; validate
    user-pinned ones against the VMEM budget (tuner picks are feasible by
    construction, explicit pins are not)."""
    pinned = block_n is not None or block_cap is not None
    if block_n is None or block_cap is None:
        bn, bc = autotune.select_blocks(
            kind, n=n, cap=cap, d=d, n_clients=n_clients, dtype=dtype
        )
        block_n = bn if block_n is None else block_n
        block_cap = bc if block_cap is None else block_cap
    if pinned:
        autotune.validate_blocks(kind, block_n=block_n, block_cap=block_cap,
                                 cap=cap, d=d, dtype=dtype)
    return block_n, block_cap


def rff_features(
    x: jax.Array,
    v: jax.Array,
    b: jax.Array,
    *,
    block_n: int = 128,
    block_m: int = 256,
    force_pallas: bool = False,
) -> jax.Array:
    """phi(X): (n,d),(M,d),(M,) -> (n,M)."""
    if not (_on_tpu() or force_pallas):
        return ref.rff_features(x, v, b)
    n, m = x.shape[0], v.shape[0]
    npad, mpad = _round_up(n, block_n), _round_up(m, block_m)
    out = rff_features_kernel(
        _pad_rows(x, npad), _pad_rows(v, mpad), _pad_rows(b, mpad),
        n_features=m, block_n=block_n, block_m=block_m, interpret=not _on_tpu(),
    )
    return out[:n, :m]


def rff_grad(
    x: jax.Array,
    v: jax.Array,
    b: jax.Array,
    w: jax.Array,
    *,
    block_n: int = 128,
    block_m: int = 128,  # 256 overruns the 16 MiB scoped VMEM of a v5e at d=3072
    force_pallas: bool = False,
) -> jax.Array:
    """grad phi(X)^T w: (n,d),(M,d),(M,),(M,) -> (n,d)."""
    if not (_on_tpu() or force_pallas):
        return ref.rff_grad(x, v, b, w)
    n, m = x.shape[0], v.shape[0]
    npad, mpad = _round_up(n, block_n), _round_up(m, block_m)
    # Padded feature slots carry v == 0 AND w == 0 => zero contribution.
    out = rff_grad_kernel(
        _pad_rows(x, npad), _pad_rows(v, mpad), _pad_rows(b, mpad), _pad_rows(w, mpad),
        n_features=m, block_n=block_n, block_m=block_m, interpret=not _on_tpu(),
    )
    return out[:n, :]


def sqexp(
    x1: jax.Array,
    x2: jax.Array,
    lengthscale: float,
    *,
    block_n: int = 128,
    block_m: int = 128,
    force_pallas: bool = False,
) -> jax.Array:
    """SE Gram matrix: (n,d),(m,d) -> (n,m).

    Note: padded rows produce exp(-||x||^2/2l^2) junk values that are sliced
    away before returning (padding uses zeros, never NaN).
    """
    if not (_on_tpu() or force_pallas):
        return ref.sqexp(x1, x2, lengthscale)
    n, m = x1.shape[0], x2.shape[0]
    npad, mpad = _round_up(n, block_n), _round_up(m, block_m)
    out = sqexp_kernel(
        _pad_rows(x1, npad), _pad_rows(x2, mpad),
        lengthscale=_static_float(lengthscale), block_n=block_n, block_m=block_m,
        interpret=not _on_tpu(),
    )
    return out[:n, :m]


def uncertainty_scores(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    *,
    lengthscale,
    prior,
    block_n: int | None = None,
    block_cap: int | None = None,
    force_pallas: bool = False,
) -> jax.Array:
    """Fused active-query uncertainty scores: (n,d) candidates -> (n,).

    ``binv`` is the masked Gram inverse and ``pmat = binv o XX^T``; see
    ref.uncertainty_scores for the algebra.  Padded candidate rows (zeros)
    produce junk scores that are sliced away before returning.  With
    ``block_cap < cap`` the cap-tiled kernel runs and the trajectory axis is
    zero-padded to a tile multiple (padded slots contribute exactly zero:
    the B/P padding rows+columns are zero); otherwise the whole (cap, cap)
    factor pair stays VMEM-resident.  Unset block sizes come from the
    deterministic autotuner.  On the kernel route ``lengthscale`` and
    ``prior`` must be static (``_static_float``).
    """
    if not (_on_tpu() or force_pallas):
        return ref.uncertainty_scores(cands, xs, binv, pmat, lengthscale, prior)
    ls, pr = _static_float(lengthscale), _static_float(prior)
    n, d = cands.shape
    cap = xs.shape[0]
    block_n, block_cap = _resolve_blocks(
        "score", n, cap, d, 1, block_n, block_cap, dtype=cands.dtype
    )
    npad = _round_up(n, block_n)
    interpret = not _on_tpu()
    if block_cap >= cap:
        out = uncertainty_scores_kernel(
            _pad_rows(cands, npad), xs, binv, pmat,
            lengthscale=ls, prior=pr, block_n=block_n, interpret=interpret,
        )
    else:
        cpad = _round_up(cap, block_cap)
        out = uncertainty_scores_tiled_kernel(
            _pad_rows(cands, npad), _pad_rows(xs, cpad),
            _pad_gram(binv, cpad), _pad_gram(pmat, cpad),
            lengthscale=ls, prior=pr, block_n=block_n, block_cap=block_cap,
            interpret=interpret,
        )
    return out[:n]


def uncertainty_scores_clients(
    cands: jax.Array,
    xs: jax.Array,
    binv: jax.Array,
    pmat: jax.Array,
    *,
    lengthscale,
    prior,
    block_n: int | None = None,
    block_cap: int | None = None,
    force_pallas: bool = False,
) -> jax.Array:
    """Client-batched fused uncertainty scores: (N, n, d) -> (N, n).

    One kernel launch with a client grid dimension for the whole batch;
    same padding/backend/static-scalar/tiling contract as
    ``uncertainty_scores`` (the candidate and trajectory axes are padded per
    client, the client axis never is).  The CPU execution path is the
    fused-epilogue contraction (``ref.uncertainty_scores_clients_fused``);
    the textbook oracle stays in ``ref.uncertainty_scores_clients``.
    """
    if not (_on_tpu() or force_pallas):
        return ref.uncertainty_scores_clients_fused(
            cands, xs, binv, pmat, lengthscale, prior
        )
    ls, pr = _static_float(lengthscale), _static_float(prior)
    nb, n, d = cands.shape
    cap = xs.shape[1]
    block_n, block_cap = _resolve_blocks(
        "score", n, cap, d, nb, block_n, block_cap, dtype=cands.dtype
    )
    npad = _round_up(n, block_n)
    interpret = not _on_tpu()
    if block_cap >= cap:
        out = uncertainty_scores_clients_kernel(
            _pad_axis1(cands, npad), xs, binv, pmat,
            lengthscale=ls, prior=pr, block_n=block_n, interpret=interpret,
        )
    else:
        cpad = _round_up(cap, block_cap)
        out = uncertainty_scores_tiled_clients_kernel(
            _pad_axis1(cands, npad), _pad_axis(xs, 1, cpad),
            _pad_gram(binv, cpad), _pad_gram(pmat, cpad),
            lengthscale=ls, prior=pr, block_n=block_n, block_cap=block_cap,
            interpret=interpret,
        )
    return out[:, :n]


def grad_mean_clients(
    cands: jax.Array,
    xs: jax.Array,
    alpha: jax.Array,
    *,
    lengthscale,
    block_n: int | None = None,
    block_cap: int | None = None,
    force_pallas: bool = False,
) -> jax.Array:
    """Client-batched fused gradient mean: (N, n, d) -> (N, n, d).

    ``alpha`` (N, cap) must already carry each client's validity mask.
    With ``block_cap < cap`` the cap-tiled accumulator kernel runs; padded
    trajectory slots carry alpha == 0 and contribute exactly zero.
    """
    if not (_on_tpu() or force_pallas):
        return ref.grad_mean_clients(cands, xs, alpha, lengthscale)
    ls = _static_float(lengthscale)
    nb, n, d = cands.shape
    cap = xs.shape[1]
    block_n, block_cap = _resolve_blocks(
        "grad", n, cap, d, nb, block_n, block_cap, dtype=cands.dtype
    )
    npad = _round_up(n, block_n)
    interpret = not _on_tpu()
    if block_cap >= cap:
        out = grad_mean_clients_kernel(
            _pad_axis1(cands, npad), xs, alpha[:, None, :],
            lengthscale=ls, block_n=block_n, interpret=interpret,
        )
    else:
        cpad = _round_up(cap, block_cap)
        out = grad_mean_tiled_clients_kernel(
            _pad_axis1(cands, npad), _pad_axis(xs, 1, cpad),
            _pad_axis(alpha, 1, cpad)[:, None, :],
            lengthscale=ls, block_n=block_n, block_cap=block_cap,
            interpret=interpret,
        )
    return out[:, :n, :]


def grad_mean_batch(
    cands: jax.Array,
    xs: jax.Array,
    alpha: jax.Array,
    *,
    lengthscale,
    block_n: int | None = None,
    block_cap: int | None = None,
    force_pallas: bool = False,
) -> jax.Array:
    """Fused batched derived-GP gradient mean: (n,d) queries -> (n,d).

    ``alpha`` (cap,) must already carry the validity mask (masked solves
    leave invalid slots exactly zero, so padded trajectory slots contribute
    nothing -- the same invariant makes cap-axis zero-padding exact on the
    tiled path).  Padded candidate rows are sliced away before returning.
    On the kernel route ``lengthscale`` must be static.
    """
    if not (_on_tpu() or force_pallas):
        return ref.grad_mean_batch(cands, xs, alpha, lengthscale)
    ls = _static_float(lengthscale)
    n, d = cands.shape
    cap = xs.shape[0]
    block_n, block_cap = _resolve_blocks(
        "grad", n, cap, d, 1, block_n, block_cap, dtype=cands.dtype
    )
    npad = _round_up(n, block_n)
    interpret = not _on_tpu()
    if block_cap >= cap:
        out = grad_mean_kernel(
            _pad_rows(cands, npad), xs, alpha[None, :],
            lengthscale=ls, block_n=block_n, interpret=interpret,
        )
    else:
        cpad = _round_up(cap, block_cap)
        out = grad_mean_tiled_kernel(
            _pad_rows(cands, npad), _pad_rows(xs, cpad),
            _pad_axis(alpha, 0, cpad)[None, :],
            lengthscale=ls, block_n=block_n, block_cap=block_cap,
            interpret=interpret,
        )
    return out[:n, :]
