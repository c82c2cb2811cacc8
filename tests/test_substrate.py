"""Substrate tests: optimizers, schedules, data pipeline, partitioners,
checkpointing, sharding rules."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import latest_step, restore, save
from repro.data.partition import dirichlet_partition, label_subset_partition
from repro.data.pipeline import SyntheticTextConfig, synthetic_batch
from repro.optim import (
    adam_init,
    adam_update,
    adamw_init,
    adamw_update,
    warmup_cosine_schedule,
)


def test_adam_converges_on_quadratic():
    p = {"x": jnp.array([3.0, -2.0])}
    opt = adam_init(p)
    for _ in range(300):
        g = {"x": 2 * p["x"]}
        p, opt = adam_update(opt, g, p, 0.05)
    assert float(jnp.abs(p["x"]).max()) < 1e-2


def test_adamw_decays_unused_weights():
    p = {"x": jnp.array([1.0])}
    opt = adamw_init(p)
    for _ in range(50):
        p, opt = adamw_update(opt, {"x": jnp.array([0.0])}, p, 1e-2, weight_decay=0.5)
    assert float(p["x"][0]) < 1.0


def test_optimizer_updates_preserve_param_dtype():
    """Regression: ``p - lr * (...)`` with an f32 lr promoted bf16 params to
    f32 on the first step, so trained LM params drifted precision and
    checkpoints failed the restored-vs-init dtype validation."""
    from repro.optim import sgd_init, sgd_update

    p = {"w": jnp.ones((2, 3), jnp.bfloat16), "b": jnp.zeros((3,), jnp.float32)}
    g = {"w": jnp.full((2, 3), 0.1, jnp.bfloat16), "b": jnp.full((3,), 0.1)}
    lr = jnp.asarray(0.05, jnp.float32)  # large enough to move a bf16 ULP
    for init, update in ((adam_init, adam_update), (adamw_init, adamw_update),
                         (sgd_init, sgd_update)):
        opt = init(p)
        new_p, _ = update(opt, g, p, lr)
        assert new_p["w"].dtype == jnp.bfloat16, update.__name__
        assert new_p["b"].dtype == jnp.float32, update.__name__
        assert float(jnp.abs(new_p["w"].astype(jnp.float32) - 1.0).max()) > 0


def test_warmup_cosine_schedule_shape():
    s = warmup_cosine_schedule(1.0, 10, 110)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1.0, abs=1e-6)
    assert float(s(110)) == pytest.approx(0.0, abs=1e-2)
    assert float(s(5)) == pytest.approx(0.5, abs=1e-6)


def test_pipeline_deterministic_and_in_range():
    cfg = SyntheticTextConfig(vocab_size=97, seq_len=32, batch_size=4, seed=7)
    b1 = synthetic_batch(cfg, 3)
    b2 = synthetic_batch(cfg, 3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert int(b1["tokens"].max()) < 97 and int(b1["tokens"].min()) >= 0
    # labels are next tokens
    b_next = synthetic_batch(cfg, 4)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b_next["tokens"]))
    np.testing.assert_array_equal(np.asarray(b1["labels"][:, :-1]), np.asarray(b1["tokens"][:, 1:]))


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(40, 200),
    n_clients=st.integers(2, 8),
    p=st.floats(0.2, 1.0),
    seed=st.integers(0, 1000),
)
def test_label_subset_partition_properties(n, n_clients, p, seed):
    labels = np.random.default_rng(seed).integers(0, 10, size=n)
    parts = label_subset_partition(labels, n_clients, p, seed=seed)
    assert len(parts) == n_clients
    for idx in parts:
        assert len(idx) > 0
        assert len(np.unique(idx)) == len(idx)  # no duplicates within client
        assert idx.min() >= 0 and idx.max() < n
    if p == 1.0:
        for idx in parts:
            assert len(idx) == n  # everyone sees everything


def test_label_subset_degenerate_pad_no_duplicates():
    """Regression: the degenerate-draw pad used to sample from ALL points,
    so it could duplicate an index already in the client's set.  One point
    per class forces every client through the pad path (1 chosen point +
    min_per_client-1 padded); the pad must draw from the complement."""
    labels = np.arange(8)  # 8 classes x 1 point each
    for seed in range(16):
        parts = label_subset_partition(labels, n_clients=4, p_shared=0.1,
                                       seed=seed, min_per_client=8)
        for idx in parts:
            assert len(np.unique(idx)) == len(idx) == 8, (seed, idx)

    # the pad never over-asks when the complement is smaller than the deficit
    parts = label_subset_partition(np.arange(4), n_clients=2, p_shared=0.3,
                                   seed=0, min_per_client=10)
    for idx in parts:
        assert len(np.unique(idx)) == len(idx) == 4


@settings(max_examples=15, deadline=None)
@given(n_clients=st.integers(2, 6), alpha=st.floats(0.1, 10.0), seed=st.integers(0, 1000))
def test_dirichlet_partition_is_disjoint_and_exhaustive(n_clients, alpha, seed):
    labels = np.random.default_rng(seed).integers(0, 5, size=300)
    parts = dirichlet_partition(labels, n_clients, alpha, seed=seed)
    allidx = np.concatenate(parts)
    assert len(allidx) == 300
    assert len(np.unique(allidx)) == 300


def test_label_subset_partition_validates_hyperparameters():
    """Regression: p_shared > 1 used to crash deep inside rng.choice with an
    opaque 'cannot take a larger sample' error, and p_shared <= 0 silently
    degenerated to 1 class per client."""
    labels = np.arange(20) % 5
    for bad_p in (0.0, -0.3, 1.5, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="p_shared"):
            label_subset_partition(labels, 4, bad_p)
    for bad_n in (0, -2, 2.5):
        with pytest.raises(ValueError, match="n_clients"):
            label_subset_partition(labels, bad_n, 0.5)


def test_dirichlet_partition_validates_hyperparameters():
    """Regression: alpha <= 0 is outside the Dirichlet domain but numpy
    'accepts' it, returning NaN proportions that silently empty clients."""
    labels = np.arange(20) % 5
    for bad_a in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="alpha"):
            dirichlet_partition(labels, 4, bad_a)
    for bad_n in (0, -2, 2.5):
        with pytest.raises(ValueError, match="n_clients"):
            dirichlet_partition(labels, bad_n, 1.0)


def test_checkpoint_roundtrip_with_bf16(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.float32), "step": jnp.asarray(3, jnp.int32)},
    }
    save(str(tmp_path), tree, step=7)
    assert latest_step(str(tmp_path)) == 7
    back = restore(str(tmp_path), tree, step=7)
    for x, y in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save(str(tmp_path), {"a": jnp.zeros((2, 2))}, step=1)
    with pytest.raises(ValueError):
        restore(str(tmp_path), {"a": jnp.zeros((3,))}, step=1)


def test_spec_with_fallback_divisibility():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import spec_with_fallback, zero1_extend

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}
        axis_names = ("pod", "data", "model")

    m = FakeMesh()
    assert spec_with_fallback(m, (64, 160), (None, "model")) == P(None, "model")
    assert spec_with_fallback(m, (64, 100), (None, "model")) == P(None, None)  # 100 % 16 != 0
    assert spec_with_fallback(m, (32,), (("pod", "data"),)) == P(("pod", "data"))
    assert spec_with_fallback(m, (33,), (("pod", "data"),)) == P(None)

    # zero1 extends the largest replicated divisible dim with 'data'
    got = zero1_extend(m, (48, 6400, 160), P(None, None, "model"))
    assert got == P(None, "data", "model")
    # nothing divisible -> unchanged
    got = zero1_extend(m, (3, 5), P(None, None))
    assert got == P(None, None)


def test_spec_with_fallback_absent_axes_degrade_to_replicated():
    """Logical axes naming mesh-absent axes (e.g. 'model' on the data-only
    host mesh) replicate instead of KeyError / emitting invalid specs."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import spec_with_fallback

    class HostMesh:  # what make_host_mesh() builds on CPU
        shape = {"data": 8}
        axis_names = ("data",)

    m = HostMesh()
    assert spec_with_fallback(m, (64, 160), (None, "model")) == P(None, None)
    # tuple mixing absent+present axes keeps only the PRESENT name
    assert spec_with_fallback(m, (32, 64), (("model", "data"), None)) == P("data", None)
    assert spec_with_fallback(m, (33,), (("model", "data"),)) == P(None)  # 33 % 8
    assert spec_with_fallback(m, (32,), (("model", "pod"),)) == P(None)  # all absent


@pytest.mark.parametrize("explicit", [False, True], ids=["auto", "explicit"])
def test_constrain_valid_on_the_installed_mesh(explicit):
    """``constrain`` is valid under jit on Auto meshes (``launch.mesh.make_mesh``)
    and on Explicit ones (``jax.make_mesh``'s default), where it is a no-op."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import constrain, mesh_context

    mesh = (jax.make_mesh((1,), ("data",), axis_types=(AxisType.Explicit,))
            if explicit else make_mesh((1,), ("data",)))
    x = jnp.arange(12.0).reshape(4, 3)
    with mesh_context(mesh):
        y = jax.jit(lambda a: constrain(a * 2, "data", None))(x)
    np.testing.assert_array_equal(np.asarray(y), 2 * np.asarray(x))
