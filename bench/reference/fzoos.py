"""Plain reference of FZooS rounds (arXiv:2308.04077, Algo. 2), float32.

Written from the paper's equations, per client, with no factor cache, no
kernels and no client batching; it imports nothing of the program.  It
draws its random numbers from the job key in the same order as the program
(the key schedule below), so both see the same candidates, directions and
query noise, and the comparison is of the arithmetic:

    job key -> (k_init, k_rff, -) ; client i's key = split(k_init, N)[i]
    local step t:  key, k_obs, k_act, - = split(key, 4)
                   y = query(x, k_obs); 100 candidates x + U(-r, r) from k_act,
                   the 5 of highest gradient uncertainty, queried with
                   split(fold_in(k_act, 1), 5)
    round end:     key, k_act = split(key, 2); candidates around the new
                   server iterate, queried with split(fold_in(k_act, 2), 5)

Surrogate (eq. 5): the SE-kernel GP over the last ``cap`` queries,
A = K + jitter I, alpha = A^-1 y, grad mu(x) = sum_t alpha_t d_x k(x, x_t);
uncertainty score tr(d/l^2 I - J^T A^-1 J) with J = d_c k(c, X).
Transfer (eqs. 6-7): phi(x) = sqrt(2/M) cos(V x + b),
w = Phi^T (Phi Phi^T + jitter I)^-1 y, averaged by the server; the local
estimate is grad mu(x) + (1/t) (grad phi(x)^T w_global - grad phi(x)^T w_local).
Local steps are Adam, reset every round, clipped to [lo, hi].

``products`` selects the arithmetic of every product: ``"highest"`` (f32)
or ``"bf16x3"`` (three bf16 passes, what a TPU's ``high`` precision
computes), the control.  ``fault`` plants a fault in the reference: it is
how the faults' readings are taken (see ``check.py``): ``"half_batch"``
(the server averages x and w over half the clients), ``"w_unaveraged"``
(the server's w is left at zero), ``"unchanged"``; and ``"exact_rank"``,
a witness rather than a fault: candidates ranked by the correction term
alone, which the constant d/l^2 of the score rounds away in float32.

``run`` starts at x_0 = 1/2 with empty trajectories, or, given ``start``,
from the program's own client states at the end of a round: the rounds
after it then see nonzero RFF weights, so the transfer term is exercised.
``w_fits`` fits eq. 6 on the program's own trajectories, and
``query_keys`` gives the key of every query in the trajectory's order.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def make_dot(products: str):
    """einsum with the given product arithmetic."""
    if products == "highest":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if products == "bf16x3":
        def dot(spec, a, b):
            ah, al = _split_bf16(a)
            bh, bl = _split_bf16(b)
            e = partial(jnp.einsum, spec, precision=HIGHEST)
            return e(ah, bh) + (e(ah, bl) + e(al, bh))
        return dot
    raise ValueError(f"unknown products {products!r}")


def _append(X, Y, count, xs, ys):
    cap = X.shape[0]
    idx = jnp.mod(count + jnp.arange(xs.shape[0]), cap)
    return X.at[idx].set(xs), Y.at[idx].set(ys), count + xs.shape[0]


def _gp(X, Y, count, s, dot):
    """Cholesky factor of A over the live slots (padding rows are identity)."""
    cap = X.shape[0]
    mask = (jnp.arange(cap) < jnp.minimum(count, cap)).astype(jnp.float32)
    Xc = X - X[0]  # distances are shift-invariant; this frame cancels nothing
    n = jnp.sum(Xc * Xc, axis=-1)
    d2 = jnp.maximum(n[:, None] + n[None, :] - 2.0 * dot("id,jd->ij", Xc, Xc), 0.0)
    K = jnp.exp(-0.5 * d2 / s["lengthscale"] ** 2)
    jitter = max(s["noise"], 1e-4)
    A = K * (mask[:, None] * mask[None, :]) + jnp.diag(jitter * mask + (1.0 - mask))
    L = jnp.linalg.cholesky(A)
    return L, mask


def _grad_mean(X, Y, L, mask, x, s, dot):
    alpha = jax.scipy.linalg.cho_solve((L, True), Y * mask)
    diff = x[None, :] - X
    k = jnp.exp(-0.5 * jnp.sum(diff * diff, axis=-1) / s["lengthscale"] ** 2) * mask
    return dot("t,td->d", alpha * k, -diff) / s["lengthscale"] ** 2


def _scores(X, L, mask, cands, s, dot, exact_rank=False):
    """tr Sigma_grad(c) = d/l^2 - sum_ij Binv_ij J_i.J_j for each candidate
    (``exact_rank``: -sum_ij ..., the same order without the rounding)."""
    l2 = s["lengthscale"] ** 2
    binv = jax.scipy.linalg.cho_solve((L, True), jnp.eye(X.shape[0])) * (
        mask[:, None] * mask[None, :])

    def one(c):
        delta = (c[None, :] - X) * mask[:, None]
        k = jnp.exp(-0.5 * jnp.sum(delta * delta, axis=-1) / l2) * mask
        gram = dot("id,jd->ij", delta, delta)
        corr = jnp.sum(binv * (k[:, None] * k[None, :]) * gram) / (l2 * l2)
        if exact_rank:
            return -corr
        return jnp.maximum(X.shape[1] / l2 - corr, 0.0)

    return jax.lax.map(one, cands)


def _select(key, X, L, mask, center, n_sel, s, dot, exact_rank=False):
    d = center.shape[-1]
    delta = jax.random.uniform(key, (s["active_candidates"], d),
                               minval=-s["active_radius"], maxval=s["active_radius"])
    cands = jnp.clip(center[None, :] + delta, s["lo"], s["hi"])
    _, top = jax.lax.top_k(_scores(X, L, mask, cands, s, dot, exact_rank), n_sel)
    return cands[top]


def _phi_grad_t_w(V, b, x, w, dot):
    m = V.shape[0]
    sn = jnp.sin(dot("md,d->m", V, x) + b)
    return -math.sqrt(2.0 / m) * dot("m,md->d", sn * w, V)


def _fit_w(V, b, X, Y, mask, s, dot):
    m = V.shape[0]
    phi = math.sqrt(2.0 / m) * jnp.cos(dot("td,md->tm", X, V) + b[None, :]) * mask[:, None]
    jitter = max(s["noise"], 1e-4)
    khat = dot("tm,um->tu", phi, phi) + jnp.diag(jitter * mask + (1.0 - mask))
    alpha = jax.scipy.linalg.cho_solve((jnp.linalg.cholesky(khat), True), Y * mask)
    return dot("tm,t->m", phi, alpha)


def _rff(key, s: dict):
    """The shared feature bank (V, b) of a job, from its key."""
    _, k_rff, _ = jax.random.split(key, 3)
    kv, kb = jax.random.split(k_rff)
    V = jax.random.normal(kv, (s["n_features"], s["dim"])) / s["lengthscale"]
    b = jax.random.uniform(kb, (s["n_features"],), minval=0.0, maxval=2.0 * math.pi)
    return V, b


def w_fits(s: dict, key, xs, ys, count, products: str = "highest"):
    """Eq. 6 fitted on each client's trajectory (xs (N, cap, d), ys (N, cap),
    count (N,) appends): the weights w_i (N, M)."""
    dot = make_dot(products)
    V, b = _rff(key, s)
    cap = xs.shape[1]

    @jax.jit
    def fit(V, b, xs, ys, count):
        def one(X, Y, cnt):
            mask = (jnp.arange(cap) < jnp.minimum(cnt, cap)).astype(jnp.float32)
            return _fit_w(V, b, X, Y, mask, s, dot)

        return jax.vmap(one)(xs, ys, count)

    ctx = "high" if products == "bf16x3" else "highest"
    with jax.default_matmul_precision(ctx):
        return fit(V, b, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(count))


def query_keys(key, s: dict, rounds: int):
    """The key of every query each client makes in ``rounds`` rounds, in the
    order its trajectory appends them: (N, rounds * (T (1 + n_act) + n_end), 2)."""
    T, n_act, n_end = s["local_steps"], s["active_per_iter"], s["active_round_end"]
    split, fold = jax.random.split, jax.random.fold_in

    def step(k, _):
        k, k_obs, k_act, _ = split(k, 4)
        return k, jnp.concatenate([k_obs[None], split(fold(k_act, 1), n_act)])

    def one_round(k, _):
        k, ks = jax.lax.scan(step, k, None, length=T)
        k, k_act = split(k)
        return k, jnp.concatenate([ks.reshape((-1,) + k.shape), split(fold(k_act, 2), n_end)])

    def client(k):
        _, ks = jax.lax.scan(one_round, k, None, length=rounds)
        return ks.reshape((-1,) + k.shape)

    k_init = split(key, 3)[0]
    return jax.jit(jax.vmap(client))(split(k_init, s["n_clients"]))


def run(obj, data: dict, s: dict, key, rounds: int, products: str = "highest",
        fault=None, start=None) -> dict:
    """Rounds 1..``rounds`` of one job from key ``key``, or the ``rounds``
    rounds after ``start``: a dict of the program's client states at the end
    of a round (``traj_x``, ``traj_y``, ``count``, ``key``, ``w_local``,
    ``w_global``, leading axis N) and the server iterate ``x`` there.

    Returns ``xs`` (rounds+1, d), the server iterates from the start point.
    ``obj`` is the objective module (its ``ref_model``)."""
    clients, params, query, _ = obj.ref_model(data)
    n, d = s["n_clients"], s["dim"]
    dot = make_dot(products)
    V, b = _rff(key, s)
    cap, T = s["traj_capacity"], s["local_steps"]
    n_act, n_end = s["active_per_iter"], s["active_round_end"]
    exact = fault == "exact_rank"
    half = n // 2 if fault == "half_batch" else n

    def local(params, ci, st, t):
        x, X, Y, cnt, key, m1, m2, wl, wg = st
        key, k_obs, k_act, _ = jax.random.split(key, 4)
        X, Y, cnt = _append(X, Y, cnt, x[None], query(params, ci, x, k_obs)[None])
        L, mask = _gp(X, Y, cnt, s, dot)
        sel = _select(k_act, X, L, mask, x, n_act, s, dot, exact)
        kq = jax.random.split(jax.random.fold_in(k_act, 1), n_act)
        X, Y, cnt = _append(X, Y, cnt, sel, jax.vmap(lambda c, k: query(params, ci, c, k))(sel, kq))
        L, mask = _gp(X, Y, cnt, s, dot)
        g = _grad_mean(X, Y, L, mask, x, s, dot) + (1.0 / t) * (
            _phi_grad_t_w(V, b, x, wg, dot) - _phi_grad_t_w(V, b, x, wl, dot))
        m1 = 0.9 * m1 + 0.1 * g
        m2 = 0.999 * m2 + 0.001 * g * g
        step = x - s["eta"] * (m1 / (1 - 0.9**t)) / (jnp.sqrt(m2 / (1 - 0.999**t)) + 1e-8)
        return (jnp.clip(step, s["lo"], s["hi"]), X, Y, cnt, key, m1, m2, wl, wg)

    def post(params, ci, st, sx):
        _, X, Y, cnt, key, m1, m2, wl, wg = st
        key, k_act = jax.random.split(key)
        L, mask = _gp(X, Y, cnt, s, dot)
        sel = _select(k_act, X, L, mask, sx, n_end, s, dot, exact)
        kq = jax.random.split(jax.random.fold_in(k_act, 2), n_end)
        X, Y, cnt = _append(X, Y, cnt, sel, jax.vmap(lambda c, k: query(params, ci, c, k))(sel, kq))
        mask = (jnp.arange(cap) < jnp.minimum(cnt, cap)).astype(jnp.float32)
        wl = _fit_w(V, b, X, Y, mask, s, dot)
        return (sx, X, Y, cnt, key, m1, m2, wl, wg)

    @jax.jit
    def one_round(params, clients, states, sx):
        def client(ci, st):
            st = (sx,) + st[1:5] + (jnp.zeros(d), jnp.zeros(d)) + st[7:]
            st, _ = jax.lax.scan(lambda st, t: (local(params, ci, st, t), None), st,
                                 jnp.arange(1, T + 1, dtype=jnp.float32))
            return st

        states = jax.vmap(client)(clients, states)
        new_sx = jnp.mean(states[0][:half], axis=0)
        states = jax.vmap(lambda ci, st: post(params, ci, st, new_sx))(clients, states)
        wg = jnp.mean(states[7][:half], axis=0)
        if fault == "w_unaveraged":
            wg = jnp.zeros_like(wg)
        states = states[:8] + (jnp.broadcast_to(wg, states[8].shape),)
        return states, new_sx

    z = lambda *shape: jnp.zeros((n,) + shape, jnp.float32)
    if start is None:
        x0 = jnp.full((d,), 0.5, jnp.float32)
        states = (jnp.broadcast_to(x0, (n, d)), z(cap, d), z(cap), jnp.zeros((n,), jnp.int32),
                  jax.random.split(jax.random.split(key, 3)[0], n), z(d), z(d),
                  z(s["n_features"]), z(s["n_features"]))
    else:
        x0 = jnp.asarray(start["x"], jnp.float32)
        wg = jnp.asarray(start["w_global"], jnp.float32)
        if fault == "w_unaveraged":
            wg = jnp.zeros_like(wg)
        states = (jnp.broadcast_to(x0, (n, d)), jnp.asarray(start["traj_x"], jnp.float32),
                  jnp.asarray(start["traj_y"], jnp.float32),
                  jnp.asarray(start["count"], jnp.int32), jnp.asarray(start["key"]), z(d), z(d),
                  jnp.asarray(start["w_local"], jnp.float32), wg)
    xs, sx = [x0], x0
    ctx = "high" if products == "bf16x3" else "highest"
    with jax.default_matmul_precision(ctx):
        for _ in range(rounds):
            if fault == "unchanged":
                xs.append(sx)
                continue
            states, sx = one_round(params, clients, states, sx)
            xs.append(sx)
    return {"xs": jnp.stack(xs)}
