"""Per-shard round-state checkpoint tests (checkpoint/io.py sharded layout)
plus the restore dtype-validation regression.

The sharded contract under test: saving with a mesh writes one
``shard_<p>/arrays.npz`` per process from process-local addressable data
(no full ClientState gather), the ``meta.json`` manifest pins
{n_shards, mesh} so mismatched topologies fail loudly, restore places each
block straight onto this process's devices, and the round-trip is BITWISE
against both the original state and the legacy gathered layout.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.core import algorithms as alg
from repro.core import rounds as rounds_mod
from repro.core.federated import shard_clients
from repro.launch.mesh import make_mesh

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def _fzoos_cfg(**kw):
    base = dict(name="fzoos", dim=8, n_clients=4, local_steps=2,
                n_features=32, traj_capacity=32, active_per_iter=1,
                active_candidates=8, active_round_end=1, lengthscale=0.5)
    base.update(kw)
    return alg.AlgoConfig(**base)


def _state_and_hist(mesh=None):
    cfg = _fzoos_cfg()
    x0 = jnp.full((8,), 0.5, jnp.float32)
    states = alg.init_states(cfg, jax.random.PRNGKey(1), x0)
    # make the state non-trivial: distinct flags, counters, keys per client
    states = states._replace(
        factor=states.factor._replace(
            needs_repair=jnp.asarray([True, False, False, True]),
            n_updates=jnp.arange(4, dtype=jnp.int32),
        ),
        queries=jnp.asarray([3, 1, 4, 1], jnp.int32),
    )
    if mesh is not None:
        states = shard_clients(mesh, states)
    hist = rounds_mod.history_init(6, x0, jnp.asarray(0.25, jnp.float32))
    return states, hist


def _assert_trees_equal(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert str(jnp.asarray(g).dtype) == str(jnp.asarray(w).dtype)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# restore() dtype validation (regression: docstring promised it, code didn't)
# ---------------------------------------------------------------------------


def test_restore_validates_dtype(tmp_path):
    """A leaf saved as bf16 must NOT silently restore into an f32 template
    (and vice versa) -- the docstring always promised dtype validation."""
    root = str(tmp_path / "dt")
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16), "b": jnp.zeros((2,), jnp.float32)}
    ckpt_io.save(root, tree, step=0)
    # matching template round-trips (bf16 through the uint16 view)
    got = ckpt_io.restore(root, tree, step=0)
    _assert_trees_equal(got, tree)
    # f32 template for the bf16 leaf: loud error, not a silent cast
    bad = {"a": jnp.zeros((6,), jnp.float32), "b": tree["b"]}
    with pytest.raises(ValueError, match="dtype mismatch"):
        ckpt_io.restore(root, bad, step=0)
    # and the transpose direction: bf16 template for an f32 leaf
    bad2 = {"a": tree["a"], "b": jnp.zeros((2,), jnp.bfloat16)}
    with pytest.raises(ValueError, match="dtype mismatch"):
        ckpt_io.restore(root, bad2, step=0)


# ---------------------------------------------------------------------------
# Sharded layout: round-trip, manifest validation, tmp recovery
# ---------------------------------------------------------------------------


def test_sharded_roundtrip_bitwise_vs_gathered(tmp_path):
    """mesh save/restore == the original state == the legacy gathered
    layout, leaf for leaf, bit for bit (incl. the bool repair flags and
    int32 counters)."""
    mesh = make_mesh((1,), ("data",))
    states, hist = _state_and_hist(mesh)

    shard_root = str(tmp_path / "sharded")
    legacy_root = str(tmp_path / "legacy")
    ckpt_io.save_round_state(shard_root, 4, states, hist, mesh=mesh,
                             extra_meta={"rounds": 6})
    ckpt_io.save_round_state(legacy_root, 4, states, hist)

    step_dir = os.path.join(shard_root, "step_00000004")
    assert os.path.isfile(os.path.join(step_dir, "meta.json"))
    assert os.path.isfile(os.path.join(step_dir, "shard_00000", "arrays.npz"))
    # the manifest is the step's meta.json: load_meta (resume identity) works
    meta = ckpt_io.load_meta(shard_root, 4)
    assert meta["layout"] == "sharded-v1"
    assert meta["n_shards"] == jax.process_count()
    assert meta["extra"] == {"rounds": 6}

    s_like, h_like = _state_and_hist(mesh)
    got_s, got_h, step = ckpt_io.restore_round_state(
        shard_root, s_like, h_like, mesh=mesh)
    assert step == 4
    _assert_trees_equal(got_s, states)
    _assert_trees_equal(got_h, hist)
    # restored leaves are already placed client-sharded on the mesh
    assert all(
        d in got_s.x.sharding.device_set for d in mesh.devices.flat
    )

    leg_s, leg_h, _ = ckpt_io.restore_round_state(legacy_root, s_like, h_like)
    _assert_trees_equal(got_s, leg_s)
    _assert_trees_equal(got_h, leg_h)


def test_sharded_manifest_rejects_mismatched_topology(tmp_path):
    """{n_shards, mesh} in the manifest are validated loudly; a sharded
    checkpoint also refuses to restore without a mesh at all."""
    mesh = make_mesh((1,), ("data",))
    states, hist = _state_and_hist(mesh)
    root = str(tmp_path / "m")
    ckpt_io.save_round_state(root, 2, states, hist, mesh=mesh)
    meta_path = os.path.join(root, "step_00000002", "meta.json")

    with pytest.raises(ValueError, match="requires the device mesh"):
        ckpt_io.restore_round_state(root, states, hist)

    with open(meta_path) as f:
        meta = json.load(f)
    meta["n_shards"] = 16
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="16 process"):
        ckpt_io.restore_round_state(root, states, hist, mesh=mesh)

    meta["n_shards"] = jax.process_count()
    meta["mesh"] = {"axis_names": ["data", "model"], "shape": [8, 2]}
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="mesh"):
        ckpt_io.restore_round_state(root, states, hist, mesh=mesh)


def test_sharded_dtype_and_shape_validated(tmp_path):
    """The per-leaf shape/dtype contract holds on the sharded path too."""
    mesh = make_mesh((1,), ("data",))
    states, hist = _state_and_hist(mesh)
    root = str(tmp_path / "v")
    ckpt_io.save_round_state(root, 2, states, hist, mesh=mesh)
    bad_states = states._replace(x=states.x.astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="dtype mismatch"):
        ckpt_io.restore_round_state(root, bad_states, hist, mesh=mesh)
    bad_hist = hist._replace(xs=jnp.zeros((99, 8), jnp.float32))
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt_io.restore_round_state(root, states, bad_hist, mesh=mesh)


def test_sharded_tmp_recovery(tmp_path):
    """A preemption mid-sharded-write leaves only ``step_*.tmp``; resume
    must fall back to the last COMPLETE checkpoint."""
    mesh = make_mesh((1,), ("data",))
    states, hist = _state_and_hist(mesh)
    root = str(tmp_path / "t")
    ckpt_io.save_round_state(root, 4, states, hist, mesh=mesh)
    # fake a crash mid-write of step 8: shard written, manifest missing
    tmp = os.path.join(root, "step_00000008.tmp")
    os.makedirs(os.path.join(tmp, "shard_00000"))
    with open(os.path.join(tmp, "shard_00000", "arrays.npz"), "wb") as f:
        f.write(b"truncated")
    assert ckpt_io.latest_step(root) == 4
    got_s, _, step = ckpt_io.restore_round_state(root, states, hist, mesh=mesh)
    assert step == 4
    _assert_trees_equal(got_s, states)
    # the next save of step 8 clears the stale tmp and completes
    ckpt_io.save_round_state(root, 8, states, hist, mesh=mesh)
    assert ckpt_io.latest_step(root) == 8


def test_async_writer_reraises_background_error(tmp_path):
    """A failing background write must fail the run on the next submit/wait,
    not vanish inside a daemon thread."""
    w = ckpt_io.AsyncCheckpointWriter()
    hits = []
    w.submit(lambda: hits.append(1))
    w.wait()
    assert hits == [1]

    def boom():
        raise OSError("disk full")

    w.submit(boom)
    with pytest.raises(OSError, match="disk full"):
        w.submit(lambda: hits.append(2))
    # the queue is usable again after the error surfaced
    w.submit(lambda: hits.append(3))
    w.wait()
    assert hits == [1, 3]


def test_async_writer_never_issues_collective_off_main_thread(monkeypatch):
    """REGRESSION: the sharded layout's _sync barrier is a collective; on a
    multi-process mesh it must never run on the async writer thread (it
    would race the main thread's round collectives and deadlock the pod).
    _sync itself enforces this with a RuntimeError, which the writer
    re-raises on wait()."""
    monkeypatch.setattr(ckpt_io.jax, "process_count", lambda: 2)
    # On the main thread the guard passes (and would proceed to the barrier,
    # which we stub out -- single-process CI has no multihost runtime).
    import repro.checkpoint.io as io_mod

    w = ckpt_io.AsyncCheckpointWriter()
    w.submit(lambda: io_mod._sync("round-1"))
    with pytest.raises(RuntimeError, match="off the main thread"):
        w.wait()


def test_run_rounds_forces_blocking_writes_multiprocess(monkeypatch, capsys):
    """async_checkpoint=True on a >1-process mesh must be downgraded to the
    blocking path with a loud log, not silently honored."""
    monkeypatch.setattr(rounds_mod.jax, "process_count", lambda: 2)
    seen = {}

    class NoWriter:
        def __init__(self):
            raise AssertionError("async writer must not be constructed on a pod")

    monkeypatch.setattr(rounds_mod.ckpt_io, "AsyncCheckpointWriter", NoWriter)

    # Drive just the writer-selection logic by running one chunk through
    # alg.simulate (scan driver): checkpoint_dir set, async requested, and a
    # faked 2-process count.  Checkpoints still land (blocking path).
    from repro.core import objectives as obj

    cfg = _fzoos_cfg()
    cobjs = obj.make_quadratic(jax.random.PRNGKey(0), 4, 8, 1.0, 0.0)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        res = alg.simulate(
            cfg, jax.random.PRNGKey(2), cobjs, obj.quadratic_query,
            obj.quadratic_global_value, rounds=1, chunk=1,
            checkpoint_dir=td, checkpoint_every=1, async_checkpoint=True,
        )
        assert res is not None
        seen["files"] = sorted(os.listdir(td))
    out = capsys.readouterr().out
    assert "FORCING blocking" in out
    assert any(f.startswith("step_") for f in seen["files"])


def test_run_rounds_sharded_resume_bitwise(tmp_path):
    """End-to-end through run_rounds on a mesh: per-shard checkpoints +
    preemption + resume == the uninterrupted run, exactly (same contract as
    the legacy layout's test in test_rounds.py)."""
    from repro.core import objectives as obj
    from repro.core.federated import run_distributed

    mesh = make_mesh((1,), ("data",))
    quad = obj.make_quadratic(jax.random.PRNGKey(0), 4, 8, 2.0, 0.001)
    cfg = _fzoos_cfg()
    k = jax.random.PRNGKey(5)
    args = (cfg, mesh, k, quad, obj.quadratic_query, obj.quadratic_global_value, 9)
    ckpt = str(tmp_path / "dist_ckpt")

    r_full = run_distributed(*args, chunk=3)
    run_distributed(*args, chunk=3, checkpoint_dir=ckpt)
    assert ckpt_io.latest_step(ckpt) == 9
    assert os.path.isdir(os.path.join(ckpt, "step_00000009", "shard_00000"))
    for d in os.listdir(ckpt):
        if int(d.split("_")[1]) > 6:
            shutil.rmtree(os.path.join(ckpt, d))
    r_res = run_distributed(*args, chunk=3, checkpoint_dir=ckpt)
    np.testing.assert_array_equal(np.asarray(r_full.xs), np.asarray(r_res.xs))
    np.testing.assert_array_equal(np.asarray(r_full.f_values),
                                  np.asarray(r_res.f_values))
    np.testing.assert_array_equal(np.asarray(r_full.queries),
                                  np.asarray(r_res.queries))


MULTIDEV_SHARD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"  # skip the (slow) accelerator probe
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.launch.mesh import make_mesh
    from repro.checkpoint import io as ckpt_io
    from repro.core.federated import shard_clients

    # A synthetic client-stacked pytree: the io layer only sees leaves with a
    # leading client axis, so a real ClientState (whose init compiles for
    # minutes on 4 host devices) adds nothing here.
    mesh = make_mesh((4,), ("data",))
    states = shard_clients(mesh, {
        "x": jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6),
        "flags": jnp.asarray([0, 1, 0, 0, 1, 0, 1, 0], bool),
        "count": jnp.arange(8, dtype=jnp.int32),
        "wide": jnp.ones((8, 3, 4), jnp.bfloat16) * 1.5,
    })
    hist = {"f": jnp.linspace(0.0, 1.0, 5), "q": jnp.arange(4, dtype=jnp.float32)}
    with tempfile.TemporaryDirectory() as td:
        ckpt_io.save_round_state(td, 2, states, hist, mesh=mesh)
        got_s, got_h, step = ckpt_io.restore_round_state(td, states, hist, mesh=mesh)
    assert step == 2
    for g, w in zip(jax.tree_util.tree_leaves(got_s), jax.tree_util.tree_leaves(states)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert str(g.dtype) == str(w.dtype)
    for g, w in zip(jax.tree_util.tree_leaves(got_h), jax.tree_util.tree_leaves(hist)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # restored leaves are placed with the client axis sharded over 4 devices
    assert len(got_s["x"].sharding.device_set) == 4
    print("SHARD_MULTIDEV_OK")
    """
)


@pytest.mark.slow
def test_sharded_roundtrip_four_devices_subprocess():
    """The block-extraction and direct-placement paths with REAL multi-device
    sharding (4 host devices, 2 clients per device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SHARD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD_MULTIDEV_OK" in out.stdout
