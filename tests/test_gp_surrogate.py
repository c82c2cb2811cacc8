"""Unit + property tests for the derived-GP gradient surrogate (paper eq. 4-5)."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import example, given, settings, strategies as st

from repro.core import gp_surrogate as gp


def _fit(key, f, n, d, cap, noise=0.0):
    xs = jax.random.uniform(key, (n, d))
    ys = jax.vmap(f)(xs)
    if noise:
        ys = ys + noise * jax.random.normal(jax.random.fold_in(key, 7), (n,))
    traj = gp.traj_init(cap, d)
    return gp.traj_append_batch(traj, xs, ys), xs, ys


def test_grad_mean_matches_autodiff_of_posterior():
    f = lambda x: jnp.sum(jnp.sin(2 * x)) + jnp.sum(x**2)
    traj, _, _ = _fit(jax.random.PRNGKey(0), f, 40, 6, 64)
    hyper = gp.default_hyper(0.5, 1e-5)
    xq = jnp.full((6,), 0.3)
    g_closed = gp.grad_mean(traj, hyper, xq)
    g_auto = jax.grad(lambda x: gp.mean_value(traj, hyper, x))(xq)
    np.testing.assert_allclose(np.asarray(g_closed), np.asarray(g_auto), atol=2e-4)


def test_grad_mean_approximates_true_gradient_with_dense_data():
    f = lambda x: jnp.sum(x**2)
    traj, _, _ = _fit(jax.random.PRNGKey(1), f, 200, 2, 256)
    hyper = gp.default_hyper(0.4, 1e-5)
    xq = jnp.array([0.5, 0.4])
    g = gp.grad_mean(traj, hyper, xq)
    np.testing.assert_allclose(np.asarray(g), np.asarray(2 * xq), atol=0.05)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(3, 20),
    extra_cap=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
@example(n=17, extra_cap=21, seed=17519)
def test_padding_invariance(n, extra_cap, seed):
    """The masked padded Gram solve must equal the exact-capacity solve."""
    d = 3
    key = jax.random.PRNGKey(seed)
    f = lambda x: jnp.sum(jnp.cos(3 * x))
    hyper = gp.default_hyper(0.7, 1e-4)
    xq = jax.random.uniform(jax.random.fold_in(key, 1), (d,))

    t_exact, xs, ys = _fit(key, f, n, d, n)
    t_padded = gp.traj_append_batch(gp.traj_init(n + extra_cap, d), xs, ys)
    g1 = gp.grad_mean(t_exact, hyper, xq)
    g2 = gp.grad_mean(t_padded, hyper, xq)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-4)

    u1 = gp.grad_uncertainty_trace(t_exact, hyper, xq)
    u2 = gp.grad_uncertainty_trace(t_padded, hyper, xq)
    np.testing.assert_allclose(float(u1), float(u2), rtol=1e-2, atol=1e-4)


def test_ring_buffer_overwrites_oldest():
    traj = gp.traj_init(4, 2)
    for i in range(6):
        traj = gp.traj_append(traj, jnp.full((2,), float(i)), jnp.asarray(float(i)))
    assert int(traj.count) == 6
    assert int(traj.n_valid()) == 4
    vals = sorted(np.asarray(traj.ys).tolist())
    assert vals == [2.0, 3.0, 4.0, 5.0]  # 0 and 1 evicted


def test_uncertainty_decreases_with_data():
    f = lambda x: jnp.sum(x)
    hyper = gp.default_hyper(0.5, 1e-4)
    xq = jnp.full((3,), 0.5)
    key = jax.random.PRNGKey(3)
    t_small, xs, ys = _fit(key, f, 5, 3, 64)
    t_big = gp.traj_append_batch(
        t_small, jax.random.uniform(jax.random.fold_in(key, 2), (40, 3)),
        jnp.zeros((40,)),
    )
    assert float(gp.grad_uncertainty_trace(t_big, hyper, xq)) <= float(
        gp.grad_uncertainty_trace(t_small, hyper, xq)
    ) + 1e-6


def test_empty_trajectory_gives_zero_gradient_and_prior_uncertainty():
    traj = gp.traj_init(16, 4)
    hyper = gp.default_hyper(1.0, 1e-4)
    xq = jnp.full((4,), 0.5)
    np.testing.assert_allclose(np.asarray(gp.grad_mean(traj, hyper, xq)), 0.0)
    np.testing.assert_allclose(float(gp.grad_uncertainty_trace(traj, hyper, xq)), 4.0, rtol=1e-5)


def test_active_query_selection_prefers_unseen_regions():
    f = lambda x: jnp.sum(x)
    key = jax.random.PRNGKey(4)
    # all data clustered at 0.2; candidates near 0.8 should score higher
    xs = 0.2 + 0.01 * jax.random.normal(key, (30, 2))
    traj = gp.traj_append_batch(gp.traj_init(64, 2), xs, jnp.zeros((30,)))
    hyper = gp.default_hyper(0.3, 1e-4)
    scores_near = gp.grad_uncertainty_batch(traj, hyper, jnp.full((1, 2), 0.2))
    scores_far = gp.grad_uncertainty_batch(traj, hyper, jnp.full((1, 2), 0.8))
    assert float(scores_far[0]) > float(scores_near[0])
    sel = gp.select_active_queries(key, traj, hyper, jnp.full((2,), 0.5), 20, 5, 0.05)
    assert sel.shape == (5, 2)
    assert bool(jnp.all((sel >= 0.0) & (sel <= 1.0)))
