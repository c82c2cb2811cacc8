"""Partial-participation client pool tests (core/pool.py, DESIGN.md Sec. 9).

Covers the three layers of the pool engine:

  * the deterministic cohort sampler (pure in (seed, round, N, K), identity
    at K = N, loud validation);
  * the host-resident pool store (bitwise gather/scatter round trip,
    batched init == one-shot init == the dense engine's init);
  * the pooled round driver (K = N BITWISE equal to the dense engine on
    both front doors -- the equivalence oracle; resumed runs match
    uninterrupted ones; ONE cohort executable across sampled cohorts;
    fault rollback and quarantine re-admission before scatter-back).
"""

import dataclasses
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.core import algorithms as alg
from repro.core import objectives as obj
from repro.core import pool as pool_mod
from repro.core import rff as rfflib
from repro.core import rounds as rounds_mod
from repro.core.federated import run_distributed
from repro.faults import FaultConfig, corrupt
from repro.launch.mesh import make_mesh

ROUNDS = 8


@pytest.fixture(scope="module")
def quad():
    return obj.make_quadratic(jax.random.PRNGKey(0), 4, 8, 2.0, 0.001)


@pytest.fixture(scope="module")
def quad8():
    return obj.make_quadratic(jax.random.PRNGKey(0), 8, 8, 2.0, 0.001)


def _fzoos_cfg(**kw):
    base = dict(name="fzoos", dim=8, n_clients=4, local_steps=3,
                n_features=32, traj_capacity=32, active_per_iter=1,
                active_candidates=8, active_round_end=1, lengthscale=0.5)
    base.update(kw)
    return alg.AlgoConfig(**base)


def _sim(cfg, cobjs, rounds=ROUNDS, **kw):
    return alg.simulate(cfg, jax.random.PRNGKey(5), cobjs, obj.quadratic_query,
                        obj.quadratic_global_value, rounds, **kw)


def _dist(cfg, cobjs, rounds=ROUNDS, **kw):
    mesh = make_mesh((1,), ("data",))
    return run_distributed(cfg, mesh, jax.random.PRNGKey(5), cobjs,
                           obj.quadratic_query, obj.quadratic_global_value,
                           rounds, **kw)


def _assert_results_equal(r0, r1):
    for field in r0._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(r0, field)), np.asarray(getattr(r1, field)),
            err_msg=field,
        )


# ---------------------------------------------------------------------------
# Cohort sampler
# ---------------------------------------------------------------------------


def test_sample_cohort_deterministic_and_valid():
    a = pool_mod.sample_cohort(7, 3, 16, 5)
    b = pool_mod.sample_cohort(7, 3, 16, 5)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (5,)
    assert len(np.unique(a)) == 5  # without replacement
    assert a.min() >= 0 and a.max() < 16
    assert (np.diff(a) > 0).all()  # sorted: pool order == batch order
    # keyed on the absolute round: different rounds draw different cohorts
    c = pool_mod.sample_cohort(7, 4, 16, 5)
    assert not np.array_equal(a, c)
    # and on the seed
    d = pool_mod.sample_cohort(8, 3, 16, 5)
    assert not np.array_equal(a, d)


def test_sample_cohort_identity_at_full_participation():
    np.testing.assert_array_equal(pool_mod.sample_cohort(3, 9, 6, 6),
                                  np.arange(6))


def test_sample_cohort_validation():
    with pytest.raises(ValueError, match="cohort"):
        pool_mod.sample_cohort(0, 0, 8, 0)
    with pytest.raises(ValueError, match="cohort"):
        pool_mod.sample_cohort(0, 0, 8, 9)


# ---------------------------------------------------------------------------
# The pool store
# ---------------------------------------------------------------------------


def test_init_pool_matches_dense_init():
    """batch=None pool init is BITWISE the dense engine's init_states."""
    cfg = _fzoos_cfg(n_clients=8)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    key = jax.random.PRNGKey(2)
    pool = pool_mod.init_pool(cfg, key, x0)
    dense = alg.init_states(cfg, key, x0)
    for a, b in zip(pool.leaves, jax.tree_util.tree_leaves(dense)):
        np.testing.assert_array_equal(a, np.asarray(jax.device_get(b)))


def test_init_pool_batched_matches_oneshot():
    """Initializing 3 clients at a time never changes the pool contents:
    per-client RNG comes from one up-front split over all N."""
    cfg = _fzoos_cfg(n_clients=8)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    key = jax.random.PRNGKey(2)
    one = pool_mod.init_pool(cfg, key, x0)
    sliced = pool_mod.init_pool(cfg, key, x0, batch=3)
    for a, b in zip(one.leaves, sliced.leaves):
        np.testing.assert_array_equal(a, b)


def test_gather_scatter_roundtrip_bitwise():
    cfg = _fzoos_cfg(n_clients=8)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    pool = pool_mod.init_pool(cfg, jax.random.PRNGKey(2), x0)
    before = [a.copy() for a in pool.leaves]
    idx = pool_mod.sample_cohort(0, 0, 8, 3)
    pool.scatter(idx, pool.gather(idx))
    for a, b in zip(pool.leaves, before):
        np.testing.assert_array_equal(a, b)


def test_scatter_validates_structure():
    cfg = _fzoos_cfg(n_clients=8)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    pool = pool_mod.init_pool(cfg, jax.random.PRNGKey(2), x0)
    idx = np.arange(3)
    with pytest.raises(ValueError, match="structure"):
        pool.scatter(idx, {"not": "a client state"})


# ---------------------------------------------------------------------------
# K = N: the bitwise equivalence oracle
# ---------------------------------------------------------------------------


def test_full_participation_bitwise_sim(quad):
    """cohort == n_clients through the simulate front door is BITWISE the
    dense engine: identity sampling, same init, and the zero-rate masked
    aggregation the pooled body always runs reduces to the dense mean."""
    cfg = _fzoos_cfg()
    r_dense = _sim(cfg, quad, chunk=4)
    r_pool = _sim(cfg, quad, chunk=4, cohort=4)
    _assert_results_equal(r_dense, r_pool)


def test_full_participation_bitwise_distributed(quad):
    cfg = _fzoos_cfg()
    r_dense = _dist(cfg, quad, chunk=4)
    r_pool = _dist(cfg, quad, chunk=4, cohort=4)
    _assert_results_equal(r_dense, r_pool)


def test_cohort_requires_scan_driver(quad):
    cfg = _fzoos_cfg()
    with pytest.raises(ValueError, match="chunk"):
        _sim(cfg, quad, chunk=0, cohort=4)


# ---------------------------------------------------------------------------
# K < N: partial participation
# ---------------------------------------------------------------------------


def test_partial_participation_optimizes(quad8):
    """K=4 of N=8: the run stays finite and optimizes; only cohort-sized
    state ever exists on device (the dense K-client mesh footprint)."""
    cfg = _fzoos_cfg(n_clients=8)
    r = _sim(cfg, quad8, rounds=12, chunk=4, cohort=4)
    f = np.asarray(r.f_values)
    assert np.isfinite(f).all()
    assert f[-1] < f[0]


def test_cohort_schedule_topology_independent(quad8):
    """The sampler keys on (seed, round) only, so vmap and shard_map runs
    draw the SAME cohorts: identical query accounting, and iterates within
    the same bounded reduction-order divergence the dense engines show
    (vmap mean vs psum mean, cf. test_faults sim-vs-distributed)."""
    cfg = _fzoos_cfg(n_clients=8)
    r_sim = _sim(cfg, quad8, chunk=4, cohort=4)
    r_dist = _dist(cfg, quad8, chunk=4, cohort=4)
    np.testing.assert_array_equal(np.asarray(r_sim.queries),
                                  np.asarray(r_dist.queries))
    np.testing.assert_allclose(np.asarray(r_sim.xs), np.asarray(r_dist.xs),
                               atol=0.1)


def test_one_executable_serves_every_cohort(quad8):
    """The chunk step is keyed on K, not on the member ids: after the first
    cohort compiles it, every later cohort (different rows, same (K, ...)
    shapes) is a cache hit -- zero recompiles across the sweep."""
    from repro.analysis import no_recompiles

    cfg = _fzoos_cfg(n_clients=8)
    ccfg = dataclasses.replace(cfg, n_clients=4)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, cfg.dim,
                          cfg.lengthscale)
    pool = pool_mod.init_pool(cfg, jax.random.PRNGKey(2), x0)
    cobjs_host = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)), quad8)
    step = rounds_mod.make_chunk_step(rounds_mod.sim_chunk_fn(
        ccfg, obj.quadratic_query, obj.quadratic_global_value, None,
        2, 1, 6, faults=FaultConfig(),
    ))
    hist = rounds_mod.history_init(6, x0, obj.quadratic_global_value(quad8, x0))

    def boundary(off, hist, sx):
        idx = pool_mod.sample_cohort(0, off, 8, 4)
        cstates = pool.gather(idx)
        cco = jax.tree_util.tree_map(lambda a: jnp.asarray(a[idx]), cobjs_host)
        cstates, hist, sx = step(cstates, hist, cco, sx, jnp.int32(off), rff)
        pool.scatter(idx, cstates)
        return hist, sx

    hist, sx = boundary(0, hist, x0)  # warm the one executable
    with no_recompiles() as g:
        for off in (2, 4):
            hist, sx = boundary(off, hist, sx)
    assert g.compiles == 0


# ---------------------------------------------------------------------------
# Checkpoint / resume / rollback
# ---------------------------------------------------------------------------


def test_pooled_resume_bitwise(quad8, tmp_path):
    """A pooled run killed mid-way resumes from the newest checkpoint and
    finishes BITWISE identical to the uninterrupted run: the cohort
    schedule keys on the absolute round, so the replayed boundary re-draws
    the same cohorts."""
    cfg = _fzoos_cfg(n_clients=8, local_steps=2)
    d = str(tmp_path / "ck")
    r_full = _sim(cfg, quad8, chunk=2, cohort=4, checkpoint_dir=d)
    assert ckpt_io.list_steps(d) == [2, 4, 6, 8]
    for dname in os.listdir(d):
        if int(dname.split("_")[1]) > 4:
            shutil.rmtree(os.path.join(d, dname))
    r_res = _sim(cfg, quad8, chunk=2, cohort=4, checkpoint_dir=d)
    _assert_results_equal(r_full, r_res)


def test_pooled_resume_falls_back_past_corrupt_step(quad8, tmp_path):
    cfg = _fzoos_cfg(n_clients=8, local_steps=2)
    d = str(tmp_path / "ck")
    r_full = _sim(cfg, quad8, chunk=2, cohort=4, checkpoint_dir=d)
    corrupt.flip_bytes(d, ckpt_io.list_steps(d)[-1])
    r_res = _sim(cfg, quad8, chunk=2, cohort=4, checkpoint_dir=d)
    _assert_results_equal(r_full, r_res)


def test_pooled_resume_identity_includes_cohort(quad8, tmp_path):
    """A pool checkpoint dir refuses to resume under a different cohort
    size or sampler seed (the schedule is part of the run identity)."""
    cfg = _fzoos_cfg(n_clients=8, local_steps=2)
    d = str(tmp_path / "ck")
    _sim(cfg, quad8, rounds=4, chunk=2, cohort=4, checkpoint_dir=d)
    with pytest.raises(ValueError, match="cohort"):
        _sim(cfg, quad8, rounds=4, chunk=2, cohort=2, checkpoint_dir=d)
    with pytest.raises(ValueError, match="cohort_seed"):
        _sim(cfg, quad8, rounds=4, chunk=2, cohort=4, cohort_seed=1,
             checkpoint_dir=d)


def test_pooled_rollback_recovers_poisoned_run(quad8, tmp_path, capsys):
    """tolerate=False + NaN faults under partial participation: the
    boundary health check catches the poisoned iterate BEFORE it scatters
    into the pool, rolls {pool, history} back and re-runs with tolerance
    forced on."""
    cfg = _fzoos_cfg(n_clients=8)
    fcfg = FaultConfig(seed=3, nan_rate=0.3, tolerate=False)
    d = str(tmp_path / "ck")
    r = _sim(cfg, quad8, chunk=4, cohort=4, checkpoint_dir=d, faults=fcfg)
    assert np.isfinite(np.asarray(r.f_values)).all()
    assert np.isfinite(np.asarray(r.xs)).all()
    out = capsys.readouterr().out
    assert "ROLLBACK" in out and "FORCED ON" in out


def test_pooled_faults_quarantine_never_persists(quad8):
    """Quarantined cohort members are re-admitted at the boundary before
    scatter-back: no client ever sits in the pool quarantined."""
    cfg = _fzoos_cfg(n_clients=8)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, cfg.dim,
                          cfg.lengthscale)
    pool = pool_mod.init_pool(cfg, jax.random.PRNGKey(2), x0)
    fcfg = FaultConfig(seed=3, nan_rate=0.3)
    pool, hist = pool_mod.run_pooled_rounds(
        cfg, rff, obj.quadratic_query, quad8, pool, x0,
        obj.quadratic_global_value, ROUNDS, 4, cohort=4, faults=fcfg,
    )
    assert np.asarray(hist.quarantine_rate).max() > 0  # faults did fire
    states = pool.gather(np.arange(8))
    assert not np.asarray(states.quarantined).any()
    for leaf in pool.leaves:
        if np.issubdtype(leaf.dtype, np.floating):
            assert np.isfinite(leaf).all()


# ---------------------------------------------------------------------------
# Static contracts
# ---------------------------------------------------------------------------


def test_pool_contracts_clean():
    from repro.analysis import contracts

    for name in ("fzoos-pool/simulate", "fzoos-pool/distributed",
                 "fedzo-pool/simulate", "fedzo-pool/distributed"):
        violations = contracts.check_contract(name)
        assert violations == [], f"{name}: {violations}"


# ---------------------------------------------------------------------------
# Launcher flag surface
# ---------------------------------------------------------------------------


def test_pool_flags_validated():
    import argparse

    from repro.launch import common

    ap = argparse.ArgumentParser()
    common.add_pool_flags(ap)
    args = ap.parse_args(["--pool-size", "16"])
    with pytest.raises(SystemExit, match="cohort"):
        common.pool_from_args(args)
    args = ap.parse_args(["--pool-size", "16", "--cohort", "4"])
    assert common.pool_from_args(args) == (16, 4)
    args = ap.parse_args(["--cohort", "0"])
    with pytest.raises(SystemExit, match="cohort"):
        common.pool_from_args(args)
