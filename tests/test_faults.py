"""Fault-tolerant round engine tests (repro.faults + DESIGN.md Sec. 8).

Covers the three layers of the fault model:

  * the deterministic injector (reproducible, topology-independent,
    precedence- and window-correct draws);
  * the masked engine (every fault kind on both front doors; the faults-off
    BITWISE guarantee; quarantine reset == fresh-init oracle);
  * storage recovery (per-leaf checksums reject torn/bit-flipped
    checkpoints, resume falls back to the newest good step, chunk rollback
    re-runs a poisoned run to completion, writer retries transient I/O).

Scan-vs-oracle comparisons are bounded, not bitwise, for the same reason as
test_rounds.py: the quarantine-reset cadence differs (per round vs per
chunk boundary) inside the engine's bounded-divergence contract.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.core import algorithms as alg
from repro.core import objectives as obj
from repro.core import rounds as rounds_mod
from repro.core.federated import run_distributed
from repro.faults import FaultConfig, corrupt, draw_faults, schedule_table
from repro.launch.mesh import make_mesh

ROUNDS = 8


@pytest.fixture(scope="module")
def quad():
    return obj.make_quadratic(jax.random.PRNGKey(0), 4, 8, 2.0, 0.001)


def _fzoos_cfg(**kw):
    base = dict(name="fzoos", dim=8, n_clients=4, local_steps=3,
                n_features=32, traj_capacity=32, active_per_iter=1,
                active_candidates=8, active_round_end=1, lengthscale=0.5)
    base.update(kw)
    return alg.AlgoConfig(**base)


def _sim(cfg, quad, rounds=ROUNDS, **kw):
    return alg.simulate(cfg, jax.random.PRNGKey(5), quad, obj.quadratic_query,
                        obj.quadratic_global_value, rounds, **kw)


def _dist(cfg, quad, rounds=ROUNDS, **kw):
    mesh = make_mesh((1,), ("data",))
    return run_distributed(cfg, mesh, jax.random.PRNGKey(5), quad,
                           obj.quadratic_query, obj.quadratic_global_value,
                           rounds, **kw)


# ---------------------------------------------------------------------------
# Injector
# ---------------------------------------------------------------------------


def test_draws_deterministic_and_identity_keyed():
    fcfg = FaultConfig(seed=7, drop_rate=0.3, straggle_rate=0.2, nan_rate=0.2,
                       inf_rate=0.2)
    ids = jnp.arange(8, dtype=jnp.int32)
    d1 = draw_faults(fcfg, jnp.int32(3), ids)
    d2 = draw_faults(fcfg, jnp.int32(3), ids)
    for k in d1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(d1, k)),
                                      np.asarray(getattr(d2, k)))
    # draws key on CLIENT IDENTITY, not batch position: permuting the id
    # vector permutes the masks identically (topology independence)
    perm = np.array([5, 2, 7, 0, 1, 3, 4, 6])
    dp = draw_faults(fcfg, jnp.int32(3), jnp.asarray(perm, jnp.int32))
    for k in d1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(dp, k)),
                                      np.asarray(getattr(d1, k))[perm])


def test_schedule_precedence_and_window():
    fcfg = FaultConfig(seed=1, drop_rate=0.4, straggle_rate=0.4, nan_rate=0.4,
                       inf_rate=0.4)
    tab = schedule_table(fcfg, 20, 8)
    assert tab["drop"].any() and tab["nan"].any()
    # a dropped client sends nothing: it cannot also straggle or poison
    assert not (tab["drop"] & tab["straggle"]).any()
    assert not (tab["drop"] & tab["nan"]).any()
    assert not (tab["drop"] & tab["inf"]).any()
    # nan wins over inf when both fire
    assert not (tab["nan"] & tab["inf"]).any()
    # the injection window gates every kind on the absolute round index
    wcfg = dataclasses.replace(fcfg, first_round=5, last_round=12)
    wtab = schedule_table(wcfg, 20, 8)
    for k in wtab:
        assert not wtab[k][:5].any() and not wtab[k][12:].any()
        np.testing.assert_array_equal(wtab[k][5:12], tab[k][5:12])


def test_zero_rate_config_draws_nothing():
    tab = schedule_table(FaultConfig(), 5, 4)
    for k in tab:
        assert not tab[k].any()


def test_rate_validation():
    with pytest.raises(ValueError):
        FaultConfig(drop_rate=1.5)
    with pytest.raises(ValueError):
        FaultConfig(nan_rate=-0.1)


def test_schedule_table_matches_per_round_draws():
    """Regression: the vmapped one-dispatch schedule_table must be BITWISE
    the per-round draw_faults loop it replaced (same fold_in keying per
    row), window gating included."""
    fcfg = FaultConfig(seed=9, drop_rate=0.3, straggle_rate=0.2, nan_rate=0.2,
                       inf_rate=0.1, first_round=2, last_round=15)
    tab = schedule_table(fcfg, 20, 6)
    ids = jnp.arange(6, dtype=jnp.int32)
    for r in range(20):
        d = draw_faults(fcfg, jnp.int32(r), ids)
        for k in d._fields:
            np.testing.assert_array_equal(tab[k][r], np.asarray(getattr(d, k)),
                                          err_msg=f"round {r} kind {k}")


def test_statically_empty_window_never_injects():
    """A config whose [first_round, last_round) window is empty can never
    fire, whatever the rates: ``injects`` is False and the engine treats it
    as faults=None."""
    from repro.faults.injector import effective_config

    assert not FaultConfig(nan_rate=0.5, first_round=5, last_round=5).injects
    assert not FaultConfig(nan_rate=0.5, first_round=7, last_round=3).injects
    # a non-empty window starting past the horizon injects in principle but
    # is never ACTIVE inside this run: effective_config normalizes to None
    late = FaultConfig(nan_rate=0.5, first_round=100)
    assert late.injects and not late.active_in(8)
    assert effective_config(late, 8) is None
    assert effective_config(late, 200) is late
    # zero rates pass through unchanged: an explicit --fault-tolerance
    # masked-engine opt-in must keep selecting the masked engine
    z = FaultConfig()
    assert effective_config(z, 8) is z
    assert effective_config(None, 8) is None


# ---------------------------------------------------------------------------
# Masked engine
# ---------------------------------------------------------------------------


def test_faults_off_bitwise_sim(quad):
    """An all-zero-rate tolerant config must be BITWISE identical to
    faults=None: zero rates lower to static constants, and the masked
    aggregation (sum / live-count) reduces to the same mean."""
    cfg = _fzoos_cfg()
    r0 = _sim(cfg, quad, chunk=4)
    r1 = _sim(cfg, quad, chunk=4, faults=FaultConfig())
    np.testing.assert_array_equal(np.asarray(r0.xs), np.asarray(r1.xs))
    np.testing.assert_array_equal(np.asarray(r0.f_values),
                                  np.asarray(r1.f_values))
    np.testing.assert_array_equal(np.asarray(r0.queries),
                                  np.asarray(r1.queries))
    assert not np.asarray(r1.drop_rate).any()
    assert not np.asarray(r1.quarantine_rate).any()


def test_faults_off_bitwise_distributed(quad):
    cfg = _fzoos_cfg()
    r0 = _dist(cfg, quad, chunk=4)
    r1 = _dist(cfg, quad, chunk=4, faults=FaultConfig())
    np.testing.assert_array_equal(np.asarray(r0.xs), np.asarray(r1.xs))
    np.testing.assert_array_equal(np.asarray(r0.f_values),
                                  np.asarray(r1.f_values))


def test_out_of_window_faults_bitwise_identity_sim(quad, tmp_path):
    """Regression: a rates>0 config whose window never intersects the run
    used to select the FAULTED engine (different compile key, masked psum
    columns, insurance checkpoint, per-boundary finiteness sync) even
    though it could never fire.  It must be BITWISE the faults=None run --
    including writing NO step-0 insurance checkpoint."""
    cfg = _fzoos_cfg()
    wcfg = FaultConfig(seed=3, nan_rate=0.9, tolerate=False, first_round=100)
    d = str(tmp_path / "ck")
    r0 = _sim(cfg, quad, chunk=4)
    # tolerate=False + nan_rate>0 would need a checkpoint_dir to roll back
    # to if the faulted engine were selected -- running fine without one is
    # itself evidence the window was normalized away
    r1 = _sim(cfg, quad, chunk=4, faults=wcfg)
    np.testing.assert_array_equal(np.asarray(r0.xs), np.asarray(r1.xs))
    np.testing.assert_array_equal(np.asarray(r0.f_values),
                                  np.asarray(r1.f_values))
    np.testing.assert_array_equal(np.asarray(r0.queries),
                                  np.asarray(r1.queries))
    _sim(cfg, quad, chunk=4, faults=wcfg, checkpoint_dir=d)
    assert 0 not in ckpt_io.list_steps(d)  # no rollback-insurance write


def test_out_of_window_faults_bitwise_identity_distributed(quad):
    cfg = _fzoos_cfg()
    wcfg = FaultConfig(seed=3, nan_rate=0.9, tolerate=False, first_round=100)
    r0 = _dist(cfg, quad, chunk=4)
    r1 = _dist(cfg, quad, chunk=4, faults=wcfg)
    np.testing.assert_array_equal(np.asarray(r0.xs), np.asarray(r1.xs))
    np.testing.assert_array_equal(np.asarray(r0.f_values),
                                  np.asarray(r1.f_values))


_KIND_RATES = {
    "drop": dict(drop_rate=0.3),
    "straggle": dict(straggle_rate=0.3),
    "nan": dict(nan_rate=0.3),
    "inf": dict(inf_rate=0.3),
}


@pytest.mark.parametrize("kind", sorted(_KIND_RATES))
@pytest.mark.parametrize("driver", ["simulate", "distributed"])
def test_fault_kind_matrix(quad, kind, driver):
    """Each fault kind, on each front door: the tolerant engine absorbs the
    faults (finite history end to end) and reports them in the stats."""
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=3, **_KIND_RATES[kind])
    run = _sim if driver == "simulate" else _dist
    r = run(cfg, quad, chunk=4, faults=fcfg)
    assert np.isfinite(np.asarray(r.f_values)).all()
    assert np.isfinite(np.asarray(r.xs)).all()
    drop = np.asarray(r.drop_rate)
    quar = np.asarray(r.quarantine_rate)
    if kind == "drop":
        assert drop.max() > 0
    elif kind in ("nan", "inf"):
        # poisoned clients are detected on device and quarantined; their
        # payloads never reach the aggregate (x stays finite above)
        assert quar.max() > 0
    else:  # straggle: late updates are absorbed, nobody is dropped
        assert not quar.any()


def test_faulted_scan_matches_loop_oracle(quad):
    """chunk=4 scan vs chunk=0 loop under the same fault schedule: bounded
    divergence (reset cadence differs), exact query accounting."""
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=3, drop_rate=0.2, nan_rate=0.1)
    r_scan = _sim(cfg, quad, chunk=4, faults=fcfg)
    r_loop = _sim(cfg, quad, chunk=0, faults=fcfg)
    np.testing.assert_allclose(np.asarray(r_scan.xs), np.asarray(r_loop.xs),
                               atol=0.1)
    np.testing.assert_allclose(np.asarray(r_scan.f_values),
                               np.asarray(r_loop.f_values), atol=5e-2)


def test_faulted_sim_matches_distributed(quad):
    """The fault schedule is topology-independent: vmap and shard_map runs
    inject the SAME (round, client) faults (identical drop_rate history)."""
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=3, drop_rate=0.2, nan_rate=0.1)
    r_sim = _sim(cfg, quad, chunk=4, faults=fcfg)
    r_dist = _dist(cfg, quad, chunk=4, faults=fcfg)
    np.testing.assert_array_equal(np.asarray(r_sim.drop_rate),
                                  np.asarray(r_dist.drop_rate))
    np.testing.assert_array_equal(np.asarray(r_sim.quarantine_rate),
                                  np.asarray(r_dist.quarantine_rate))
    np.testing.assert_allclose(np.asarray(r_sim.xs), np.asarray(r_dist.xs),
                               atol=0.1)


def test_no_tolerance_poisons_dense_mean(quad):
    """Without masking, one NaN payload poisons the dense psum mean -- the
    failure mode the tolerant engine removes (loop driver: no rollback)."""
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=3, nan_rate=0.3, tolerate=False)
    r = _sim(cfg, quad, chunk=0, faults=fcfg)
    assert not np.isfinite(np.asarray(r.xs)).all()


def test_dropout_run_still_converges(quad):
    """20% dropout: the renormalized mean keeps the run on track."""
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=11, drop_rate=0.2)
    r = _sim(cfg, quad, rounds=20, chunk=8, faults=fcfg)
    f = np.asarray(r.f_values)
    assert np.isfinite(f).all()
    assert f[-1] < f[0]  # still optimizes through the faults


# ---------------------------------------------------------------------------
# Quarantine reset
# ---------------------------------------------------------------------------


def _flagged_states(cfg, flags):
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    states = alg.init_states(cfg, jax.random.PRNGKey(2), x0)
    # make the quarantined clients' mutable state visibly non-fresh
    states = states._replace(
        x=states.x + 1.0,
        queries=states.queries + jnp.arange(cfg.n_clients, dtype=states.queries.dtype),
        quarantined=jnp.asarray(flags),
    )
    return states


def test_quarantine_reset_matches_fresh_init_oracle():
    """Reset clients == a fresh client joining at server_x: template leaves
    adopted, identity/RNG/query-count/w_global preserved, flag cleared.
    Un-flagged clients are bitwise untouched."""
    cfg = _fzoos_cfg()
    flags = np.array([True, False, False, True])
    states = _flagged_states(cfg, flags)
    before = jax.tree_util.tree_map(jnp.copy, states)
    sx = jnp.linspace(0.2, 0.8, cfg.dim, dtype=jnp.float32)
    out = rounds_mod.boundary_quarantine_reset(states, cfg, sx)

    template = alg.init_client_state(cfg, jax.random.PRNGKey(0),
                                     jnp.zeros((cfg.dim,), jnp.float32))
    assert not np.asarray(out.quarantined).any()
    for i in range(cfg.n_clients):
        if flags[i]:
            np.testing.assert_array_equal(np.asarray(out.x[i]), np.asarray(sx))
            np.testing.assert_array_equal(np.asarray(out.traj.xs[i]),
                                          np.asarray(template.traj.xs))
            # preserved across the reset: identity, RNG stream, query count
            np.testing.assert_array_equal(np.asarray(out.key[i]),
                                          np.asarray(before.key[i]))
            assert int(out.client_id[i]) == i
            np.testing.assert_array_equal(np.asarray(out.queries[i]),
                                          np.asarray(before.queries[i]))
        else:
            for a, b in zip(jax.tree_util.tree_leaves(
                    jax.tree_util.tree_map(lambda l: l[i], out)),
                    jax.tree_util.tree_leaves(
                    jax.tree_util.tree_map(lambda l: l[i], before))):
                if a.dtype == bool and a.shape == ():  # the cleared flag
                    continue
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quarantine_host_oracle_matches_device_gate():
    cfg = _fzoos_cfg()
    flags = np.array([False, True, False, False])
    sx = jnp.linspace(0.2, 0.8, cfg.dim, dtype=jnp.float32)
    dev = rounds_mod.boundary_quarantine_reset(_flagged_states(cfg, flags), cfg, sx)
    host, n = rounds_mod.quarantine_reset_flagged(_flagged_states(cfg, flags),
                                                  cfg, sx)
    assert n == 1
    for a, b in zip(jax.tree_util.tree_leaves(dev),
                    jax.tree_util.tree_leaves(host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quarantine_reset_noop_without_flags():
    cfg = _fzoos_cfg()
    states = _flagged_states(cfg, np.zeros(4, bool))
    out, n = rounds_mod.quarantine_reset_flagged(
        states, cfg, jnp.zeros((cfg.dim,), jnp.float32))
    assert n == 0
    assert out is states  # host oracle short-circuits: zero dispatches


# ---------------------------------------------------------------------------
# Storage faults: checksums, restore fallback, rollback
# ---------------------------------------------------------------------------


def test_truncated_npz_rejected(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32), "b": jnp.ones((3, 2))}
    ckpt_io.save(str(tmp_path), tree, step=1)
    corrupt.truncate_npz(str(tmp_path), 1)
    with pytest.raises(ckpt_io.CorruptCheckpointError):
        ckpt_io.restore(str(tmp_path), tree, step=1)


def test_flipped_bytes_rejected(tmp_path):
    tree = {"a": jnp.arange(512, dtype=jnp.float32)}
    ckpt_io.save(str(tmp_path), tree, step=1)
    corrupt.flip_bytes(str(tmp_path), 1, n_bytes=16)
    with pytest.raises(ckpt_io.CorruptCheckpointError):
        ckpt_io.restore(str(tmp_path), tree, step=1)


def test_resume_falls_back_past_corrupt_steps(quad, tmp_path):
    """Torn newest step + bit-flipped second-newest: resume restores the
    newest GOOD step and completes bitwise-identically to the full run."""
    cfg = _fzoos_cfg(local_steps=2)
    d = str(tmp_path / "ck")
    r_full = _sim(cfg, quad, chunk=2, checkpoint_dir=d, checkpoint_every=1)
    steps = ckpt_io.list_steps(d)
    assert steps == [2, 4, 6, 8]
    corrupt.truncate_npz(d, steps[-1])
    corrupt.flip_bytes(d, steps[-2])
    r_res = _sim(cfg, quad, chunk=2, checkpoint_dir=d)
    np.testing.assert_array_equal(np.asarray(r_full.xs), np.asarray(r_res.xs))
    np.testing.assert_array_equal(np.asarray(r_full.f_values),
                                  np.asarray(r_res.f_values))


def test_rollback_recovers_poisoned_run(quad, tmp_path, capsys):
    """tolerate=False + NaN faults: the boundary health check detects the
    poisoned iterate, rolls back to the last good checkpoint and re-runs
    with tolerance forced on -- the run completes finite."""
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=3, nan_rate=0.3, tolerate=False)
    d = str(tmp_path / "ck")
    r = _sim(cfg, quad, chunk=4, checkpoint_dir=d, faults=fcfg)
    assert np.isfinite(np.asarray(r.f_values)).all()
    assert np.isfinite(np.asarray(r.xs)).all()
    out = capsys.readouterr().out
    assert "ROLLBACK" in out and "FORCED ON" in out


def test_rollback_without_checkpoint_dir_fails_loudly(quad):
    cfg = _fzoos_cfg()
    fcfg = FaultConfig(seed=3, nan_rate=0.3, tolerate=False)
    with pytest.raises(FloatingPointError, match="no checkpoint_dir"):
        _sim(cfg, quad, chunk=4, faults=fcfg)


def test_final_boundary_write_failure_rolls_back(quad, tmp_path, capsys,
                                                 monkeypatch):
    """Regression: a failed async write at the FINAL boundary used to
    surface from the post-loop ``finally: writer.wait()`` drain -- escaping
    the rollback machinery entirely and killing an otherwise-finished run.
    The final boundary now drains inside the rollback-capable block: the
    failure rolls back to the last good step and the replayed chunk
    completes bitwise identically."""
    cfg = _fzoos_cfg(local_steps=2)
    d_ref = str(tmp_path / "ref")
    r_ref = _sim(cfg, quad, chunk=4, checkpoint_dir=d_ref,
                 faults=FaultConfig())

    real = ckpt_io.write_round_state
    fails = []

    def flaky(root, round_idx, payload, extra_meta=None):
        # exhaust one full submit cycle (1 try + 2 writer retries) of the
        # LAST boundary's write, then heal for the post-rollback replay
        if round_idx == ROUNDS and len(fails) < 3:
            fails.append(1)
            raise OSError("injected: final write torn")
        return real(root, round_idx, payload, extra_meta=extra_meta)

    monkeypatch.setattr(ckpt_io, "write_round_state", flaky)
    d = str(tmp_path / "ck")
    r = _sim(cfg, quad, chunk=4, checkpoint_dir=d, faults=FaultConfig())
    assert len(fails) == 3  # the injected failure was actually exercised
    out = capsys.readouterr().out
    assert "ROLLBACK" in out
    assert ckpt_io.latest_step(d) == ROUNDS  # the replayed final write landed
    np.testing.assert_array_equal(np.asarray(r_ref.xs), np.asarray(r.xs))
    np.testing.assert_array_equal(np.asarray(r_ref.f_values),
                                  np.asarray(r.f_values))


def test_resume_identity_includes_faults(quad, tmp_path):
    """A checkpoint dir written under one fault schedule refuses to resume
    under a different one (the schedule is part of the run identity)."""
    cfg = _fzoos_cfg(local_steps=2)
    d = str(tmp_path / "ck")
    _sim(cfg, quad, rounds=4, chunk=2, checkpoint_dir=d,
         faults=FaultConfig(seed=1, drop_rate=0.2))
    with pytest.raises(ValueError, match="faults"):
        _sim(cfg, quad, rounds=4, chunk=2, checkpoint_dir=d,
             faults=FaultConfig(seed=2, drop_rate=0.2))


# ---------------------------------------------------------------------------
# Async writer retry
# ---------------------------------------------------------------------------


def test_writer_retries_transient_oserror():
    w = ckpt_io.AsyncCheckpointWriter(retries=2, backoff_s=0.01)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")

    w.submit(flaky)
    w.wait()  # retried to success: no raise
    assert len(calls) == 3


def test_writer_permanent_oserror_raises():
    w = ckpt_io.AsyncCheckpointWriter(retries=1, backoff_s=0.01)
    calls = []

    def bad():
        calls.append(1)
        raise OSError("disk on fire")

    w.submit(bad)
    with pytest.raises(OSError, match="disk on fire"):
        w.wait()
    assert len(calls) == 2  # 1 try + 1 retry


def test_writer_non_io_errors_not_retried():
    w = ckpt_io.AsyncCheckpointWriter(retries=5, backoff_s=0.01)
    calls = []

    def bug():
        calls.append(1)
        raise ValueError("logic bug")

    w.submit(bug)
    with pytest.raises(ValueError, match="logic bug"):
        w.wait()
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# Static contracts
# ---------------------------------------------------------------------------


def test_fault_contracts_clean():
    from repro.analysis import contracts

    for name in ("fzoos-faults/simulate", "fzoos-faults/distributed",
                 "fedzo-faults/simulate", "fedzo-faults/distributed",
                 "chunk-step-donation/faulted",
                 "chunk-step-donation/faulted-distributed",
                 "quarantine-reset"):
        violations = contracts.check_contract(name)
        assert violations == [], f"{name}: {violations}"
