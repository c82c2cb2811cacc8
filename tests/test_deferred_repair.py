"""Deferred-repair engine tests (DESIGN.md Sec. 2.6).

The contract under test: the branch-free scanned round body contains NO
eigh; an unhealthy factor update flags the client and FREEZES its factors
(solves stay finite through the last-good factors) until the chunk-boundary
repair pass runs one batched clamped-eigh over exactly the flagged clients;
and end-to-end the deferred engine tracks the inline-cond oracle
(``defer_repair=False``, i.e. the PR 2 engine) within the repo's
bounded-divergence equivalence contract -- the same scale as the
vmap/shard_map and scan/loop contracts, because the deferred engine lowers
the same math through batched kernels and a different (Cholesky) solver for
the round-end RFF fit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.core import algorithms as alg
from repro.core import gp_surrogate as gp
from repro.core import objectives as obj
from repro.core import rounds as rounds_mod
from repro.launch.mesh import make_mesh


def _fzoos_cfg(**kw):
    base = dict(name="fzoos", dim=8, n_clients=4, local_steps=3,
                n_features=32, traj_capacity=32, active_per_iter=1,
                active_candidates=8, active_round_end=1, lengthscale=0.5)
    base.update(kw)
    return alg.AlgoConfig(**base)


@pytest.fixture(scope="module")
def quad():
    return obj.make_quadratic(jax.random.PRNGKey(0), 4, 8, 2.0, 0.001)


# ---------------------------------------------------------------------------
# Factor-level: branch-free update vs the inline-cond oracle
# ---------------------------------------------------------------------------


def _drive(key, cap, d, n_events, batch, deferred, clustered=False):
    hyper = gp.default_hyper(0.7, 1e-4)
    traj = gp.traj_init(cap, d)
    factor = gp.factor_init(traj, hyper)
    for i in range(n_events):
        k = jax.random.fold_in(key, i)
        if clustered:
            xs = 0.4 + 0.005 * jax.random.uniform(k, (batch, d))
        else:
            xs = jax.random.uniform(k, (batch, d))
        traj, factor = gp.traj_extend(traj, factor, xs, jnp.sin(3.0 * xs.sum(-1)),
                                      hyper, deferred=deferred)
    return traj, factor, hyper


@pytest.mark.parametrize("clustered", [False, True],
                         ids=["well_posed", "clustered_near_singular"])
def test_deferred_update_matches_inline_while_healthy(clustered):
    """While every update is healthy (the measured-rate-~0 regime, incl. the
    clustered near-singular one from test_factor_cache) the deferred path
    adopts EXACTLY the factors the inline path adopts."""
    cap, d = 48, 5
    key = jax.random.PRNGKey(3)
    traj_i, fac_i, hyper = _drive(key, cap, d, 25, 3, deferred=False,
                                  clustered=clustered)
    traj_d, fac_d, _ = _drive(key, cap, d, 25, 3, deferred=True,
                              clustered=clustered)
    assert int(fac_i.n_refactors) == 0  # healthy: inline never fell back
    assert not bool(fac_d.needs_repair)
    np.testing.assert_array_equal(np.asarray(traj_i.xs), np.asarray(traj_d.xs))
    np.testing.assert_array_equal(np.asarray(fac_i.gram), np.asarray(fac_d.gram))
    np.testing.assert_array_equal(np.asarray(fac_i.chol), np.asarray(fac_d.chol))
    assert bool(fac_d.exact)


def test_poisoned_gram_flags_and_freezes():
    """The poisoned-Gram regime of test_factor_cache under the deferred
    path: no inline eigh -- the flag raises, the factors freeze, and every
    solve through the frozen factors stays finite."""
    cap, d = 12, 3
    key = jax.random.PRNGKey(5)
    traj, factor, hyper = _drive(key, cap, d, 4, 2, deferred=True)

    bad = factor._replace(gram=factor.gram.at[0, 1].set(5.0).at[1, 0].set(5.0),
                          exact=jnp.asarray(False))
    xs = jax.random.uniform(jax.random.fold_in(key, 99), (1, d))
    traj2 = gp.traj_append_batch(traj, xs, xs.sum(-1))
    fac2 = gp.factor_update_deferred(bad, traj2, hyper, 1, traj.count)

    assert bool(fac2.needs_repair)
    assert int(fac2.n_refactors) == int(bad.n_refactors)  # counted at repair
    np.testing.assert_array_equal(np.asarray(fac2.chol), np.asarray(bad.chol))
    np.testing.assert_array_equal(np.asarray(fac2.eigvecs), np.asarray(bad.eigvecs))
    assert bool(jnp.isfinite(gp.factor_solve(fac2, traj2.ys)).all())

    # flagged clients adopt NOTHING, even if a later candidate would be
    # healthy -- the freeze holds until the repair pass
    xs3 = jax.random.uniform(jax.random.fold_in(key, 100), (1, d))
    traj3 = gp.traj_append_batch(traj2, xs3, xs3.sum(-1))
    fac3 = gp.factor_update_deferred(fac2, traj3, hyper, 1, traj2.count)
    assert bool(fac3.needs_repair)
    np.testing.assert_array_equal(np.asarray(fac3.chol), np.asarray(fac2.chol))
    # ... but the cached Gram keeps its exact incremental updates
    gram_true, _ = gp._padded_gram(traj3, hyper)
    want = gram_true.at[0, 1].set(5.0).at[1, 0].set(5.0)
    np.testing.assert_allclose(np.asarray(fac3.gram), np.asarray(want), atol=1e-6)


def test_repair_matches_clamped_eigh_oracle():
    """The boundary repair == the inline fallback's clamped-eigh pseudo-solve
    (the NaN-robustness guarantee survives deferral)."""
    cap, d = 12, 3
    key = jax.random.PRNGKey(5)
    traj, factor, hyper = _drive(key, cap, d, 4, 2, deferred=True)
    jitter = gp._jitter_of(hyper)
    bad = factor._replace(gram=factor.gram.at[0, 1].set(5.0).at[1, 0].set(5.0),
                          needs_repair=jnp.asarray(True))

    healthy = factor  # second client: unflagged, must be untouched by repair
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), bad, healthy)
    rep = gp.factor_repair_masked(stacked, jitter)

    assert not bool(rep.needs_repair[0]) and not bool(rep.needs_repair[1])
    assert int(rep.n_refactors[0]) == int(bad.n_refactors) + 1
    assert int(rep.n_refactors[1]) == int(healthy.n_refactors)
    np.testing.assert_array_equal(np.asarray(rep.chol[1]), np.asarray(healthy.chol))
    assert bool(rep.exact[1]) == bool(healthy.exact)

    # flagged client: repaired solves equal the from-scratch clamped eigh
    rep0 = jax.tree_util.tree_map(lambda a: a[0], rep)
    assert not bool(rep0.exact)  # routes through the repaired eigh factors
    v, w = gp._clamped_eigh(bad.gram, jitter)
    b = traj.ys * traj.valid_mask()
    np.testing.assert_allclose(
        np.asarray(gp.factor_solve(rep0, b)),
        np.asarray(gp._gram_solve((v, w), b)),
        rtol=1e-4, atol=1e-5,
    )


def test_update_after_repair_refreshes_to_exact():
    """Inexact factors never compound: the first healthy update after a
    repair refactorizes the exact cached Gram and returns to the Cholesky
    route (same contract as the inline fallback)."""
    cap, d = 16, 3
    key = jax.random.PRNGKey(8)
    traj, factor, hyper = _drive(key, cap, d, 8, 3, deferred=True)
    flagged = factor._replace(needs_repair=jnp.asarray(True))
    stacked = jax.tree_util.tree_map(lambda a: a[None], flagged)
    rep = jax.tree_util.tree_map(
        lambda a: a[0], gp.factor_repair_masked(stacked, gp._jitter_of(hyper)))
    xs = jax.random.uniform(jax.random.fold_in(key, 77), (2, d))
    traj2, fac2 = gp.traj_extend(traj, rep, xs, xs.sum(-1), hyper, deferred=True)
    assert bool(fac2.exact) and not bool(fac2.needs_repair)
    gram, _ = gp._padded_gram(traj2, hyper)
    np.testing.assert_allclose(np.asarray(fac2.chol),
                               np.asarray(jnp.linalg.cholesky(gram)), atol=2e-5)


# ---------------------------------------------------------------------------
# Engine-level: deferred vs inline-cond oracle, HLO, history threading
# ---------------------------------------------------------------------------


def _assert_bounded(r_ref, r_new):
    np.testing.assert_allclose(np.asarray(r_ref.xs[1]), np.asarray(r_new.xs[1]),
                               atol=5e-2)
    np.testing.assert_allclose(np.asarray(r_ref.xs), np.asarray(r_new.xs), atol=0.1)
    np.testing.assert_allclose(np.asarray(r_ref.f_values),
                               np.asarray(r_new.f_values), atol=5e-2)
    np.testing.assert_array_equal(np.asarray(r_ref.queries),
                                  np.asarray(r_new.queries))
    assert np.isfinite(np.asarray(r_new.f_values)).all()


def test_deferred_engine_matches_inline_oracle(quad):
    """End-to-end: scanned deferred engine vs the PR 2 inline-cond engine,
    bounded divergence + exact integer query accounting."""
    k = jax.random.PRNGKey(5)
    args = (k, quad, obj.quadratic_query, obj.quadratic_global_value, 10)
    r_inline = alg.simulate(_fzoos_cfg(defer_repair=False), *args, chunk=4)
    r_defer = alg.simulate(_fzoos_cfg(defer_repair=True), *args, chunk=4)
    _assert_bounded(r_inline, r_defer)
    # healthy regime: nothing was ever flagged, nothing repaired
    assert float(np.abs(np.asarray(r_defer.repair_rate)).max()) == 0.0
    assert float(np.abs(np.asarray(r_defer.refactor_rate)).max()) == 0.0


def test_deferred_engine_matches_inline_oracle_distributed(quad):
    """Same oracle contract through shard_map (per-shard repair path)."""
    from repro.core.federated import run_distributed

    mesh = make_mesh((1,), ("data",))
    k = jax.random.PRNGKey(5)
    args = (k, quad, obj.quadratic_query, obj.quadratic_global_value, 6)
    r_inline = run_distributed(_fzoos_cfg(defer_repair=False), mesh, *args, chunk=3)
    r_defer = run_distributed(_fzoos_cfg(defer_repair=True), mesh, *args, chunk=3)
    _assert_bounded(r_inline, r_defer)


def test_deferred_engine_clustered_near_singular_regime():
    """The clustered active-query regime (radius-0.01 balls, cond ~ 1e6
    padded Gram): the engine must stay finite and track the inline oracle --
    this is the regime the inline eigh fallback existed for."""
    key = jax.random.PRNGKey(0)
    cobjs = obj.make_quadratic(key, 2, 6, 2.0, 0.001)
    cfg_kw = dict(dim=6, n_clients=2, local_steps=4, traj_capacity=16,
                  n_features=16, active_per_iter=3, active_candidates=16,
                  active_round_end=2, noise=1e-5)
    k = jax.random.PRNGKey(9)
    args = (k, cobjs, obj.quadratic_query, obj.quadratic_global_value, 8)
    r_inline = alg.simulate(_fzoos_cfg(defer_repair=False, **cfg_kw), *args, chunk=4)
    r_defer = alg.simulate(_fzoos_cfg(defer_repair=True, **cfg_kw), *args, chunk=4)
    _assert_bounded(r_inline, r_defer)


def test_hlo_of_scanned_round_body_contains_no_eigh(quad):
    """THE acceptance criterion: the deferred scanned round body lowers with
    no eigh anywhere; the inline-cond oracle body (both-branches under the
    client vmap) demonstrably does.  Fingerprints come from
    ``analysis.hlo_audit`` -- no inline custom_call_target regex here."""
    from repro.analysis import hlo_audit
    from repro.core import rff as rfflib

    x0 = jnp.full((8,), 0.5, jnp.float32)

    def lower_body(cfg):
        rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, cfg.dim,
                              cfg.lengthscale)
        states = alg.init_states(cfg, jax.random.PRNGKey(2), x0)
        cf = rounds_mod.sim_chunk_fn(cfg, rff, obj.quadratic_query,
                                     obj.quadratic_global_value, None, 2, 1, 4)
        return jax.jit(cf).lower(states, quad, x0, jnp.int32(0)).as_text()

    deferred = lower_body(_fzoos_cfg(defer_repair=True))
    inline = lower_body(_fzoos_cfg(defer_repair=False))
    assert hlo_audit.check_no_eigh(deferred, "deferred body") == []
    assert hlo_audit.contains_eigh(inline), hlo_audit.eigh_fingerprints()


def test_repair_rate_threaded_through_history(quad):
    cfg = _fzoos_cfg()
    res = alg.simulate(cfg, jax.random.PRNGKey(5), quad, obj.quadratic_query,
                       obj.quadratic_global_value, 5, chunk=2)
    assert res.repair_rate.shape == (5,)
    assert np.isfinite(np.asarray(res.repair_rate)).all()


def test_checkpoint_roundtrips_needs_repair_bitwise(quad, tmp_path):
    """The needs_repair flag rides in ClientState: a checkpoint taken with
    clients flagged must restore the flag (and the frozen factors) bitwise."""
    cfg = _fzoos_cfg()
    x0 = jnp.full((8,), 0.5, jnp.float32)
    states = alg.init_states(cfg, jax.random.PRNGKey(1), x0)
    flags = jnp.asarray([True, False, True, False])
    states = states._replace(factor=states.factor._replace(needs_repair=flags))
    hist = rounds_mod.history_init(4, x0, jnp.zeros((), jnp.float32))

    ckpt = str(tmp_path / "repair_ckpt")
    ckpt_io.save_round_state(ckpt, 2, states, hist)
    restored, _, step = ckpt_io.restore_round_state(ckpt, states, hist)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(restored.factor.needs_repair),
                                  np.asarray(flags))
    for got, want in zip(jax.tree_util.tree_leaves(restored),
                         jax.tree_util.tree_leaves(states)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_repair_pass_noop_when_unflagged(quad):
    cfg = _fzoos_cfg()
    states = alg.init_states(cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    repaired, n = rounds_mod.repair_flagged_clients(states, cfg)
    assert n == 0 and repaired is states


def test_repair_pass_repairs_only_flagged(quad):
    cfg = _fzoos_cfg()
    states = alg.init_states(cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    flags = jnp.asarray([False, True, False, False])
    states = states._replace(factor=states.factor._replace(needs_repair=flags))
    repaired, n = rounds_mod.repair_flagged_clients(states, cfg)
    assert n == 1
    assert not bool(repaired.factor.needs_repair.any())
    np.testing.assert_array_equal(np.asarray(repaired.factor.n_refactors),
                                  np.asarray(flags, np.int32))
    assert not bool(repaired.factor.exact[1])  # repaired -> eigh route
    assert bool(repaired.factor.exact[0])  # untouched


# ---------------------------------------------------------------------------
# Zero-sync boundary: device-side repair decision (DESIGN.md Sec. 3)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_mesh", [False, True], ids=["vmap", "shard_map"])
def test_device_repair_matches_host_oracle(quad, use_mesh):
    """The device-decided boundary (`boundary_repair_on_device`) == the
    host-read oracle (`repair_flagged_clients`), leaf for leaf, on both
    engines -- including n_refactors accounting and flag clearing."""
    mesh = make_mesh((1,), ("data",)) if use_mesh else None
    cfg = _fzoos_cfg()
    states = alg.init_states(cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    flags = jnp.asarray([True, False, False, True])
    states = states._replace(factor=states.factor._replace(needs_repair=flags))
    if mesh is not None:
        from repro.core.federated import shard_clients
        states = shard_clients(mesh, states)

    want, n = rounds_mod.repair_flagged_clients(states, cfg, mesh=mesh)
    assert n == 2
    got = rounds_mod.boundary_repair_on_device(states, cfg, mesh=mesh)
    for g, w in zip(jax.tree_util.tree_leaves(got.factor),
                    jax.tree_util.tree_leaves(want.factor)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not bool(got.factor.needs_repair.any())


def test_device_repair_noop_when_clear(quad):
    """All-healthy boundary: the gated branch is untaken and the factors come
    back bitwise unchanged (and non-deferred configs skip the pass whole)."""
    cfg = _fzoos_cfg()
    states = alg.init_states(cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    # snapshot first: the boundary donates the factor buffers (in-place)
    want = [np.asarray(a) for a in jax.tree_util.tree_leaves(states.factor)]
    got = rounds_mod.boundary_repair_on_device(states, cfg)
    for g, w in zip(jax.tree_util.tree_leaves(got.factor), want):
        np.testing.assert_array_equal(np.asarray(g), w)

    inline_cfg = _fzoos_cfg(defer_repair=False)
    st2 = alg.init_states(inline_cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    assert rounds_mod.boundary_repair_on_device(st2, inline_cfg) is st2


def test_boundary_executable_gates_eigh_behind_cond(quad):
    """The fused boundary executable carries the repair eigh BEHIND a
    conditional (so the all-healthy steady state never executes it), while
    the scanned chunk body stays eigh-free (asserted separately above).
    The jaxpr-level half of this lives in the ``boundary-repair`` contract;
    here the lowered text is checked through the shared auditor."""
    import re

    from repro.analysis import hlo_audit
    from repro.analysis.contracts import check_contract

    cfg = _fzoos_cfg()
    states = alg.init_states(cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    txt = jax.jit(gp.factor_repair_gated).lower(
        states.factor, jnp.float32(1e-4)).as_text()
    assert hlo_audit.contains_eigh(txt)  # the repair branch is there...
    assert re.search(r"\bcase\b|\bconditional\b", txt)  # ...but gated
    assert check_contract("boundary-repair") == []


def test_steady_state_boundary_issues_no_device_get(quad):
    """THE tentpole acceptance: a steady-state deferred distributed run
    performs ZERO host syncs at chunk boundaries -- no ``device_get`` of the
    flag vector (or anything else) between the initial eval and the final
    history return."""
    from repro.core import rff as rfflib

    mesh = make_mesh((1,), ("data",))
    cfg = _fzoos_cfg()
    x0 = jnp.full((8,), 0.5, jnp.float32)
    rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, cfg.dim,
                          cfg.lengthscale)
    from repro.analysis import steady_state_guard
    from repro.core.federated import shard_clients
    states = shard_clients(mesh, alg.init_states(cfg, jax.random.PRNGKey(2), x0))

    # allow_compiles=None: first-call compiles are expected here; the guard
    # raises SteadyStateViolation on any device_get between entry and exit.
    with steady_state_guard(allow_compiles=None, allow_device_gets=0):
        _, res = rounds_mod.run_rounds(
            cfg, rff, obj.quadratic_query, quad, states, x0,
            obj.quadratic_global_value, rounds=6, chunk=2, mesh=mesh,
        )
    assert np.isfinite(np.asarray(res.f_values)).all()


# ---------------------------------------------------------------------------
# Client-batched phase vs the per-client vmapped phase
# ---------------------------------------------------------------------------


def test_fit_w_chol_tracks_fit_w():
    """The eigh-free round-end fit == eq. 6 within solver roundoff of the
    same (cond-limited) RFF Gram system, in function space."""
    from repro.core import rff as rfflib

    cap, d, m = 32, 4, 128
    key = jax.random.PRNGKey(8)
    traj, factor, hyper = _drive(key, cap, d, 10, 3, deferred=True)
    params = rfflib.make_rff(jax.random.fold_in(key, 1), m, d, float(hyper.lengthscale))
    w_eigh = rfflib.fit_w(params, traj, hyper)
    w_chol = rfflib.fit_w_chol(params, traj, hyper, factor)
    xq = jax.random.uniform(jax.random.fold_in(key, 2), (16, d))
    g1 = rfflib.grad_features_t_w_batch(params, xq, w_eigh)
    g2 = rfflib.grad_features_t_w_batch(params, xq, w_chol)
    scale = max(float(jnp.abs(g1).max()), 1.0)
    assert float(jnp.abs(g1 - g2).max()) / scale < 5e-2


def test_client_batched_surrogate_matches_per_client():
    """The client-batched cached scoring/grad helpers == vmap of the
    per-client ones (identical math, batched contraction order)."""
    cap, d, n_clients, nc = 24, 5, 3, 12
    hyper = gp.default_hyper(0.7, 1e-4)
    key = jax.random.PRNGKey(4)

    trajs, factors = [], []
    for c in range(n_clients):
        tr, fa, _ = _drive(jax.random.fold_in(key, c), cap, d, 6, 3, deferred=True)
        trajs.append(tr)
        factors.append(fa)
    trajs = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trajs)
    factors = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *factors)
    xq = jax.random.uniform(jax.random.fold_in(key, 99), (n_clients, nc, d))

    got = gp.grad_uncertainty_batch_cached_clients(trajs, factors, hyper, xq)
    want = jax.vmap(
        lambda tr, fa, q: gp.grad_uncertainty_batch_cached(tr, fa, hyper, q)
    )(trajs, factors, xq)
    prior = d / float(hyper.lengthscale) ** 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * prior)

    x1 = jax.random.uniform(jax.random.fold_in(key, 98), (n_clients, d))
    g_got = gp.grad_mean_cached_clients(trajs, factors, hyper, x1)
    g_want = jax.vmap(
        lambda tr, fa, x: gp.grad_mean_cached(tr, fa, hyper, x)
    )(trajs, factors, x1)
    scale = max(float(jnp.abs(g_want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(g_got) / scale,
                               np.asarray(g_want) / scale, atol=1e-5)
