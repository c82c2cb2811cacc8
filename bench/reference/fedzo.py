"""Plain reference of FedZO rounds (Fang et al., 2022), float32.

Each client takes T Adam steps (reset every round, clipped to [lo, hi]) on
the finite-difference estimate of eq. 3 of arXiv:2308.04077,

    g(x) = (1/Q) sum_q (y(x + lam u_q) - y(x)) / lam * u_q,   u_q ~ N(0, I),

and the server averages the clients' iterates.  It imports nothing of the
program and draws its random numbers from the job key in the program's
order: client i's key is split(k_init, N)[i] with (k_init, -, -) =
split(job key, 3); at each step key, -, -, k_est = split(key, 4),
(k, k_dirs) = split(k_est), the directions come from k_dirs and
(k_base, k_pert) = split(k) key the query at x and the Q perturbed ones.

``products`` selects the arithmetic of the estimate's contraction
sum_q c_q u_q: ``"highest"`` (f32) or ``"bf16x3"`` (three bf16 passes), the
control.  ``fault`` plants a fault (see ``check.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.fzoos import make_dot


def run(obj, data: dict, s: dict, key, rounds: int, products: str = "highest",
        fault=None) -> dict:
    clients, params, query, _ = obj.ref_model(data)
    n, d = s["n_clients"], s["dim"]
    q, lam, T = s["q"], s["fd_lambda"], s["local_steps"]
    dot = make_dot(products)

    def local(params, ci, st, t):
        x, key, m1, m2 = st
        key, _, _, k_est = jax.random.split(key, 4)
        k, k_dirs = jax.random.split(k_est)
        u = jax.random.normal(k_dirs, (q, d))
        k_base, k_pert = jax.random.split(k)
        y0 = query(params, ci, x, k_base)
        ys = jax.vmap(lambda ui, kk: query(params, ci, x + lam * ui, kk))(u, jax.random.split(k_pert, q))
        g = dot("q,qd->d", (ys - y0) / lam, u) / q
        m1 = 0.9 * m1 + 0.1 * g
        m2 = 0.999 * m2 + 0.001 * g * g
        step = x - s["eta"] * (m1 / (1 - 0.9**t)) / (jnp.sqrt(m2 / (1 - 0.999**t)) + 1e-8)
        return (jnp.clip(step, s["lo"], s["hi"]), key, m1, m2)

    @jax.jit
    def one_round(params, clients, keys, sx):
        def client(ci, key):
            st = (sx, key, jnp.zeros(d), jnp.zeros(d))
            st, _ = jax.lax.scan(lambda st, t: (local(params, ci, st, t), None), st,
                                 jnp.arange(1, T + 1, dtype=jnp.float32))
            return st[0], st[1]

        xs_c, keys = jax.vmap(client)(clients, keys)
        if fault == "half_batch":
            return keys, jnp.mean(xs_c[: n // 2], axis=0)
        return keys, jnp.mean(xs_c, axis=0)

    k_init, _, _ = jax.random.split(key, 3)
    keys = jax.random.split(k_init, n)
    x0 = jnp.full((d,), 0.5, jnp.float32)
    xs, sx = [x0], x0
    ctx = "high" if products == "bf16x3" else "highest"
    with jax.default_matmul_precision(ctx):
        for _ in range(rounds):
            if fault != "unchanged":
                keys, sx = one_round(params, clients, keys, sx)
            xs.append(sx)
    return {"xs": jnp.stack(xs)}
