"""Heterogeneous quadratics (FZooS, arXiv:2308.04077, App. E.1).

    f_i(x) = ( sum_j [(1 + C(a_ij - 1/N)) r_j^2 + (1 + C(b_ij - 1/N)) r_j] + 1 ) / (10 d),
    r = 20 x - 10,  a_.j, b_.j ~ Dir(1/N) across the N clients,

so F = mean_i f_i = (sum_j r_j^2 + r_j + 1) / (10 d), minimised at r_j = -1/2
with F* = (1 - d/4) / (10 d).  Queries add N(0, noise_std^2) noise.

``build`` makes the client data from the key in one jitted call on the
device and hands the program its own query and value functions.  The plain
reference below is written from the formula above and imports nothing of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import Problem


def make_data(key, n_clients: int, dim: int):
    """Dirichlet weights (N, d), each column summing to 1 across clients."""

    @jax.jit
    def make(key):
        ka, kb = jax.random.split(key)
        alpha = jnp.full((n_clients,), 1.0 / n_clients)
        a = jax.random.dirichlet(ka, alpha, shape=(dim,)).T.astype(jnp.float32)
        b = jax.random.dirichlet(kb, alpha, shape=(dim,)).T.astype(jnp.float32)
        return a, b

    a, b = make(key)
    return {"a": a, "b": b}


def query_flops(config: dict) -> float:
    import counts

    return counts.quadratic_query_flops(int(config["dim"]))


def fstar(dim: int) -> float:
    return (1.0 - dim / 4.0) / (10.0 * dim)


def build(config: dict, key) -> Problem:
    from repro.core import objectives as obj

    n, d = int(config["n_clients"]), int(config["dim"])
    data = make_data(key, n, d)
    data.update(c_het=float(config["c_het"]), noise_std=float(config["noise_std"]))
    rep = lambda v: jnp.full((n,), v, jnp.float32)
    cobjs = obj.QuadraticClient(a=data["a"], b=data["b"], c_het=rep(data["c_het"]),
                                n_clients=rep(float(n)), noise_std=rep(data["noise_std"]))
    return Problem(cobjs=cobjs, query=obj.quadratic_query,
                   global_value=obj.quadratic_global_value, dim=d, fstar=fstar(d),
                   data=data)


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def ref_global_value(data: dict, xs) -> np.ndarray:
    """F at each row of xs (n, d), in float64 on the host."""
    a = np.asarray(data["a"], np.float64)
    b = np.asarray(data["b"], np.float64)
    n, d = a.shape
    c = data["c_het"]
    wa = (1.0 + c * (a - 1.0 / n)).mean(axis=0)  # mean over clients, per coordinate
    wb = (1.0 + c * (b - 1.0 / n)).mean(axis=0)
    r = 20.0 * np.asarray(xs, np.float64) - 10.0
    return ((wa * r * r + wb * r).sum(axis=-1) + 1.0) / (10.0 * d)


def f_gaps(data: dict, jobs, problem: Problem) -> np.ndarray:
    """|F_program(x_r) - F(x_r)| at every round of every job, as a share of
    the gap F(x_0) - F* of the start point x_0 = 1/2."""
    xs = np.concatenate([np.asarray(j.result.xs) for j in jobs])
    f_prog = np.concatenate([np.asarray(j.result.f_values) for j in jobs])
    f_ref = ref_global_value(data, xs)
    d = xs.shape[-1]
    gap0 = float(ref_global_value(data, np.full((1, d), 0.5))[0]) - fstar(d)
    return np.abs(np.asarray(f_prog, np.float64) - f_ref) / gap0


def ref_clients(data: dict):
    """Per-client arrays the reference query reads, leading axis N."""
    n = data["a"].shape[0]
    return {"a": jnp.asarray(data["a"]), "b": jnp.asarray(data["b"]),
            "c": jnp.full((n,), data["c_het"], jnp.float32),
            "inv_n": jnp.full((n,), 1.0 / n, jnp.float32),
            "noise": jnp.full((n,), data["noise_std"], jnp.float32)}


def ref_value(params, ci: dict, x):
    """f_i(x) of one client, float32 (the quadratic has no shared parameters)."""
    r = 20.0 * x - 10.0
    wa = 1.0 + ci["c"] * (ci["a"] - ci["inv_n"])
    wb = 1.0 + ci["c"] * (ci["b"] - ci["inv_n"])
    return (jnp.sum(wa * r * r + wb * r) + 1.0) / (10.0 * x.shape[-1])


def ref_query(params, ci: dict, x, key):
    """A noisy query of one client: its value plus noise drawn from ``key``."""
    return ref_value(params, ci, x) + ci["noise"] * jax.random.normal(key, ())


def ref_model(data: dict):
    """(per-client arrays, shared parameters, query(params, ci, x, key),
    value(params, ci, x)) of the reference."""
    return ref_clients(data), {}, ref_query, ref_value
