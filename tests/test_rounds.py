"""Scan-engine tests (core/rounds.py).

Equivalence vs the Python-loop oracle is ALGORITHMIC, not bitwise, for the
same reason as the vmap/shard_map contract in test_federated.py: the scanned
round body lowers differently from the per-round jit, and the near-singular
GP solves amplify single-ULP reassociation by the system conditioning.  The
FD baseline has no ill-conditioned solve, so it is held to a tight bound.
Checkpoint/resume, by contrast, replays the SAME executables on bitwise
restored state, so the round-trip is exact.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import objectives as obj
from repro.core import rounds as rounds_mod
from repro.core.federated import run_distributed
from repro.checkpoint import latest_step
from repro.launch.mesh import make_mesh

ROUNDS = 20


@pytest.fixture(scope="module")
def quad():
    key = jax.random.PRNGKey(0)
    return obj.make_quadratic(key, 4, 8, 2.0, 0.001)


def _fzoos_cfg(**kw):
    base = dict(name="fzoos", dim=8, n_clients=4, local_steps=3,
                n_features=32, traj_capacity=32, active_per_iter=1,
                active_candidates=8, active_round_end=1, lengthscale=0.5)
    base.update(kw)
    return alg.AlgoConfig(**base)


@pytest.fixture(scope="module")
def fzoos_oracle(quad):
    cfg = _fzoos_cfg()
    return alg.simulate(cfg, jax.random.PRNGKey(5), quad, obj.quadratic_query,
                        obj.quadratic_global_value, ROUNDS, chunk=0)


def _assert_bounded(r_ref, r_new):
    np.testing.assert_allclose(np.asarray(r_ref.xs[1]), np.asarray(r_new.xs[1]),
                               atol=5e-2)
    np.testing.assert_allclose(np.asarray(r_ref.xs), np.asarray(r_new.xs), atol=0.1)
    np.testing.assert_allclose(np.asarray(r_ref.f_values),
                               np.asarray(r_new.f_values), atol=5e-2)
    # query accounting is integer-deterministic: must agree exactly
    np.testing.assert_array_equal(np.asarray(r_ref.queries),
                                  np.asarray(r_new.queries))
    assert np.isfinite(np.asarray(r_new.f_values)).all()


def test_scan_matches_loop_fzoos_sim(quad, fzoos_oracle):
    """Chunked scan vs per-round loop, chunk not dividing rounds (8 | 20)."""
    cfg = _fzoos_cfg()
    r_new = alg.simulate(cfg, jax.random.PRNGKey(5), quad, obj.quadratic_query,
                         obj.quadratic_global_value, ROUNDS, chunk=8)
    _assert_bounded(fzoos_oracle, r_new)
    assert r_new.refactor_rate.shape == (ROUNDS,)


def test_scan_matches_loop_fzoos_distributed(quad, fzoos_oracle):
    """The shard_map engine scanning INSIDE shard_map vs the loop oracle."""
    mesh = make_mesh((1,), ("data",))
    r_new = run_distributed(_fzoos_cfg(), mesh, jax.random.PRNGKey(5), quad,
                            obj.quadratic_query, obj.quadratic_global_value,
                            ROUNDS, chunk=8)
    _assert_bounded(fzoos_oracle, r_new)


def test_scan_matches_loop_fedzo(quad):
    """FD baseline: no ill-conditioned solve, so the bound is tight."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=3, q=8)
    k = jax.random.PRNGKey(5)
    r_old = alg.simulate(cfg, k, quad, obj.quadratic_query,
                         obj.quadratic_global_value, ROUNDS, chunk=0)
    r_new = alg.simulate(cfg, k, quad, obj.quadratic_query,
                         obj.quadratic_global_value, ROUNDS, chunk=7)
    np.testing.assert_allclose(np.asarray(r_old.xs), np.asarray(r_new.xs),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_old.f_values),
                               np.asarray(r_new.f_values), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(r_old.queries),
                                  np.asarray(r_new.queries))


def test_checkpoint_resume_roundtrip(quad, tmp_path):
    """Chunk-boundary checkpoint -> preempt -> resume == uninterrupted run,
    EXACTLY (resume replays the same executables on bitwise-restored state)."""
    import shutil

    cfg = _fzoos_cfg(local_steps=2)
    k = jax.random.PRNGKey(5)
    args = (cfg, k, quad, obj.quadratic_query, obj.quadratic_global_value, 12)
    ckpt = str(tmp_path / "rounds_ckpt")

    r_full = alg.simulate(*args, chunk=4)
    alg.simulate(*args, chunk=4, checkpoint_dir=ckpt)
    assert latest_step(ckpt) == 12
    # fake preemption after round 8: drop the later checkpoints
    for d in os.listdir(ckpt):
        if int(d.split("_")[1]) > 8:
            shutil.rmtree(os.path.join(ckpt, d))
    assert latest_step(ckpt) == 8
    r_res = alg.simulate(*args, chunk=4, checkpoint_dir=ckpt)
    np.testing.assert_array_equal(np.asarray(r_full.xs), np.asarray(r_res.xs))
    np.testing.assert_array_equal(np.asarray(r_full.f_values),
                                  np.asarray(r_res.f_values))
    np.testing.assert_array_equal(np.asarray(r_full.queries),
                                  np.asarray(r_res.queries))


def test_run_rounds_rejects_bad_chunk(quad):
    cfg = _fzoos_cfg()
    states = alg.init_states(cfg, jax.random.PRNGKey(1), jnp.full((8,), 0.5))
    with pytest.raises(ValueError, match="chunk"):
        rounds_mod.run_rounds(cfg, None, obj.quadratic_query, quad, states,
                              jnp.full((8,), 0.5), obj.quadratic_global_value,
                              rounds=4, chunk=0)
    # negative chunk must not silently fall through to the loop oracle
    with pytest.raises(ValueError, match="chunk"):
        alg.simulate(cfg, jax.random.PRNGKey(1), quad, obj.quadratic_query,
                     obj.quadratic_global_value, 2, chunk=-8)


def test_resume_rejects_mismatched_rounds(quad, tmp_path):
    """A checkpoint dir from a run with different `rounds` must fail loudly,
    not resume the wrong run or die with an opaque shape error."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=1, q=2)
    k = jax.random.PRNGKey(5)
    ckpt = str(tmp_path / "mismatch_ckpt")
    alg.simulate(cfg, k, quad, obj.quadratic_query, obj.quadratic_global_value,
                 4, chunk=2, checkpoint_dir=ckpt)
    with pytest.raises(ValueError, match="rounds=4"):
        alg.simulate(cfg, k, quad, obj.quadratic_query,
                     obj.quadratic_global_value, 6, chunk=2, checkpoint_dir=ckpt)


def test_resume_rejects_mismatched_eval_every(quad, tmp_path):
    """Regression: `eval_every` is part of the resume identity.  Resuming
    with a different value used to splice two NaN patterns into one
    f_values history; now it fails loudly like rounds/cfg.  `chunk` stays
    excluded by design (dispatch granularity only), so a resume with a
    different chunk length succeeds."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=1, q=2)
    k = jax.random.PRNGKey(5)
    args = (cfg, k, quad, obj.quadratic_query, obj.quadratic_global_value, 6)
    ckpt = str(tmp_path / "ee_ckpt")
    alg.simulate(*args, chunk=2, eval_every=2, checkpoint_dir=ckpt)
    with pytest.raises(ValueError, match="eval_every=2"):
        alg.simulate(*args, chunk=2, eval_every=3, checkpoint_dir=ckpt)
    # different chunk is a legitimate resume (validated fields only)
    res = alg.simulate(*args, chunk=3, eval_every=2, checkpoint_dir=ckpt)
    assert np.isfinite(np.asarray(res.f_values)[[0, 2, 4, 6]]).all()


def test_checkpoint_sync_mode_roundtrip(quad, tmp_path):
    """`async_checkpoint=False` (the legacy blocking write) produces the
    same checkpoints and the same resume behavior as the async writer."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=1, q=2)
    k = jax.random.PRNGKey(5)
    args = (cfg, k, quad, obj.quadratic_query, obj.quadratic_global_value, 4)
    a_dir, s_dir = str(tmp_path / "a"), str(tmp_path / "s")
    r_a = alg.simulate(*args, chunk=2, checkpoint_dir=a_dir)
    r_s = alg.simulate(*args, chunk=2, checkpoint_dir=s_dir,
                       async_checkpoint=False)
    assert latest_step(a_dir) == latest_step(s_dir) == 4
    np.testing.assert_array_equal(np.asarray(r_a.xs), np.asarray(r_s.xs))
    from repro.checkpoint import io as ckpt_io
    ta = ckpt_io.load_meta(a_dir, 4)
    ts = ckpt_io.load_meta(s_dir, 4)
    assert ta["extra"] == ts["extra"]
    assert ta["dtypes"] == ts["dtypes"]


def test_eval_every_nan_contract(quad):
    """eval_every=k: F evaluated at rounds k, 2k, ... plus ALWAYS the final
    round; skipped rows hold NaN; everything else (xs, queries) unaffected."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=1, q=2)
    k = jax.random.PRNGKey(3)
    args = (cfg, k, quad, obj.quadratic_query, obj.quadratic_global_value, 7)
    r_all = alg.simulate(*args, chunk=3)
    r_skip = alg.simulate(*args, chunk=3, eval_every=3)

    f = np.asarray(r_skip.f_values)
    evaluated = {0, 3, 6, 7}  # round 0, multiples of 3, and the final round
    for r in range(8):
        if r in evaluated:
            assert np.isfinite(f[r]), r
            np.testing.assert_allclose(f[r], np.asarray(r_all.f_values)[r],
                                       atol=1e-6)
        else:
            assert np.isnan(f[r]), r
    # the trajectory itself must be untouched by skipping evals
    np.testing.assert_array_equal(np.asarray(r_all.xs), np.asarray(r_skip.xs))
    np.testing.assert_array_equal(np.asarray(r_all.queries),
                                  np.asarray(r_skip.queries))


def test_eval_every_matches_loop_oracle(quad):
    """Scan-engine eval_every == the Python-loop oracle's NaN pattern."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=1, q=2)
    k = jax.random.PRNGKey(3)
    args = (cfg, k, quad, obj.quadratic_query, obj.quadratic_global_value, 5)
    r_loop = alg.simulate(*args, chunk=0, eval_every=2)
    r_scan = alg.simulate(*args, chunk=2, eval_every=2)
    np.testing.assert_array_equal(np.isnan(np.asarray(r_loop.f_values)),
                                  np.isnan(np.asarray(r_scan.f_values)))
    np.testing.assert_allclose(np.asarray(r_loop.f_values),
                               np.asarray(r_scan.f_values), atol=1e-5)


def test_eval_every_distributed(quad):
    """eval_every through shard_map: the pmean inside the eval cond must
    lower and the NaN pattern must match the sim engine."""
    mesh = make_mesh((1,), ("data",))
    cfg = _fzoos_cfg(local_steps=2)
    k = jax.random.PRNGKey(5)
    r = run_distributed(cfg, mesh, k, quad, obj.quadratic_query,
                        obj.quadratic_global_value, 5, chunk=2, eval_every=2)
    f = np.asarray(r.f_values)
    assert np.isnan(f[[1, 3]]).all()
    assert np.isfinite(f[[0, 2, 4, 5]]).all()


def test_eval_every_rejected_when_invalid(quad):
    cfg = _fzoos_cfg()
    with pytest.raises(ValueError, match="eval_every"):
        alg.simulate(cfg, jax.random.PRNGKey(1), quad, obj.quadratic_query,
                     obj.quadratic_global_value, 2, eval_every=0)


def test_history_shapes_and_initial_row(quad):
    """xs[0]/f_values[0] hold the initial point; per-round rows line up."""
    cfg = alg.AlgoConfig(name="fedzo", dim=8, n_clients=4, local_steps=2, q=4)
    x0 = jnp.full((8,), 0.5)
    res = alg.simulate(cfg, jax.random.PRNGKey(3), quad, obj.quadratic_query,
                       obj.quadratic_global_value, 5, x0=x0, chunk=2)
    assert res.xs.shape == (6, 8) and res.f_values.shape == (6,)
    assert res.queries.shape == (5,) and res.refactor_rate.shape == (5,)
    np.testing.assert_array_equal(np.asarray(res.xs[0]), np.asarray(x0))
    f0 = float(obj.quadratic_global_value(quad, x0))
    assert float(res.f_values[0]) == pytest.approx(f0, abs=1e-6)
    # cumulative query counter is strictly increasing by the static rate
    per_round = cfg.queries_per_round()
    np.testing.assert_array_equal(
        np.asarray(res.queries), per_round * np.arange(1, 6, dtype=np.float32))


def test_engine_contracts_clean():
    """The scan engine's structural invariants -- eigh-free deferred body,
    the declared psum census, chunk-step donation -- are DECLARED in
    ``repro.analysis.contracts`` and linted there; the tier-1 suite routes
    the engine-level ones through that registry instead of keeping ad-hoc
    jaxpr/HLO assertions here."""
    import io

    from repro.analysis import check_all

    results = check_all(
        [
            "fzoos-deferred/simulate",
            "fzoos-deferred/distributed",
            "chunk-step-donation/simulate",
            "chunk-step-donation/distributed",
        ],
        out=io.StringIO(),
    )
    bad = {k: v for k, v in results.items() if v}
    assert not bad, bad
