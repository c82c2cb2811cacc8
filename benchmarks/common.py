"""Shared helpers for the paper-figure benchmarks.

Each benchmark module exposes ``run(quick: bool) -> list[Row]``;
``python -m benchmarks`` prints them as ``name,us_per_call,derived`` CSV.
Scale: CPU-sized reductions of the paper's settings (dims and rounds noted
per row so EXPERIMENTS.md can compare trends, not absolute numbers).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithms as alg


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float  # wall microseconds per communication round (or call)
    derived: str  # headline metric(s), ';'-separated k=v

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def algo_config(
    name: str, dim: int, n_clients: int, *, local_steps=10, eta=0.005,
    q=20, fd_lambda=5e-3, n_features=512, traj_capacity=160,
    active_per_iter=5, active_candidates=50, active_round_end=5,
) -> alg.AlgoConfig:
    """Paper Appx. E settings adapted to the CPU-scale reproductions."""
    return alg.AlgoConfig(
        name=name, dim=dim, n_clients=n_clients, local_steps=local_steps,
        eta=eta, q=q, fd_lambda=fd_lambda, n_features=n_features,
        traj_capacity=traj_capacity, active_per_iter=active_per_iter,
        active_candidates=active_candidates, active_round_end=active_round_end,
        lengthscale=0.5, noise=1e-5,
    )


def run_algo(cfg, key, cobjs, query, global_value, rounds, diag=None):
    t0 = time.time()
    res = alg.simulate(cfg, key, cobjs, query, global_value, rounds,
                       diag_global_grad=diag)
    dt = time.time() - t0
    return res, dt


def rounds_to_target(f_values: jax.Array, target: float) -> int:
    """First round index where F <= target (or -1)."""
    hit = np.where(np.asarray(f_values) <= target)[0]
    return int(hit[0]) if len(hit) else -1


def queries_at_round(res, r: int) -> int:
    if r <= 0:
        return 0
    return int(res.queries[min(r, len(res.queries)) - 1])


def best_f(res) -> float:
    return float(jnp.min(res.f_values))
