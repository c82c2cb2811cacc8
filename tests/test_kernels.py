"""Pallas kernel validation: interpret-mode execution vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.launch.mesh import TPU_V5E

SHAPES = [
    (8, 16, 64),      # tiny, all-padding path
    (64, 64, 256),    # block-aligned-ish
    (128, 128, 512),  # exactly aligned
    (130, 300, 513),  # deliberately misaligned everything
    (1, 2189, 1000),  # the paper's Covertype dims (d=2189, M=1e3)
]
DTYPES = [jnp.float32]


def _data(n, d, m, dtype, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k1, (n, d), dtype)
    v = jax.random.normal(k2, (m, d), dtype)
    b = jax.random.uniform(k3, (m,), dtype, maxval=6.2831)
    w = jax.random.normal(k4, (m,), dtype)
    return x, v, b, w


@pytest.mark.parametrize("n,d,m", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_rff_features_kernel(n, d, m, dtype):
    x, v, b, _ = _data(n, d, m, dtype)
    got = ops.rff_features(x, v, b, force_pallas=True, block_n=64, block_m=128)
    want = ref.rff_features(x, v, b)
    assert got.shape == want.shape == (n, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n,d,m", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_rff_grad_kernel(n, d, m, dtype):
    x, v, b, w = _data(n, d, m, dtype)
    got = ops.rff_grad(x, v, b, w, force_pallas=True, block_n=64, block_m=128)
    want = ref.rff_grad(x, v, b, w)
    assert got.shape == want.shape == (n, d)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5
    )


@pytest.mark.parametrize("n,d,m", SHAPES)
def test_sqexp_kernel(n, d, m):
    x, v, _, _ = _data(n, d, m, jnp.float32)
    got = ops.sqexp(x, v, 1.3, force_pallas=True, block_n=64, block_m=64)
    want = ref.sqexp(x, v, 1.3)
    assert got.shape == want.shape == (n, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_kernels_match_core_math():
    """ops.* and the core GP/RFF modules must agree (single source of truth)."""
    from repro.core import gp_surrogate as gp
    from repro.core import rff as rfflib

    key = jax.random.PRNGKey(1)
    d, m = 7, 130
    params = rfflib.make_rff(key, m, d, 0.9)
    xs = jax.random.uniform(jax.random.fold_in(key, 1), (9, d))
    w = jax.random.normal(jax.random.fold_in(key, 2), (m,))

    np.testing.assert_allclose(
        np.asarray(ops.rff_features(xs, params.v, params.b, force_pallas=True, block_n=64, block_m=64)),
        np.asarray(rfflib.features(params, xs)),
        atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(ops.rff_grad(xs, params.v, params.b, w, force_pallas=True, block_n=64, block_m=64)),
        np.asarray(rfflib.grad_features_t_w_batch(params, xs, w)),
        atol=5e-5,
    )
    np.testing.assert_allclose(
        np.asarray(ops.sqexp(xs, xs, 0.9, force_pallas=True, block_n=64, block_m=64)),
        np.asarray(gp.sqexp(xs, xs, 0.9)),
        atol=2e-6,
    )


# ---------------------------------------------------------------------------
# Fused GP-surrogate kernels (gp_score / gp_grad)
# ---------------------------------------------------------------------------

GP_SHAPES = [
    (4, 3, 16),       # all-padding path (n < block)
    (64, 8, 64),      # block-aligned candidates
    (100, 20, 128),   # the paper's active-query shape (n_cand=100, cap=128)
    (130, 5, 96),     # misaligned candidate count
]


def _gp_data(n, d, cap, seed=0):
    key = jax.random.PRNGKey(seed)
    cands = jax.random.uniform(jax.random.fold_in(key, 0), (n, d))
    xs = jax.random.uniform(jax.random.fold_in(key, 1), (cap, d))
    a = jax.random.normal(jax.random.fold_in(key, 2), (cap, cap)) / np.sqrt(cap)
    binv = a @ a.T + 0.1 * jnp.eye(cap)  # any SPD stand-in for the Gram inverse
    pmat = binv * (xs @ xs.T)
    alpha = jax.random.normal(jax.random.fold_in(key, 3), (cap,))
    return cands, xs, binv, pmat, alpha


@pytest.mark.parametrize("n,d,cap", GP_SHAPES)
def test_uncertainty_scores_kernel(n, d, cap):
    cands, xs, binv, pmat, _ = _gp_data(n, d, cap)
    got = ops.uncertainty_scores(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_n=64, force_pallas=True,
    )
    want = ref.uncertainty_scores(cands, xs, binv, pmat, 0.8, d / 0.64)
    assert got.shape == want.shape == (n,)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5)


@pytest.mark.parametrize("n,d,cap", GP_SHAPES)
def test_grad_mean_kernel(n, d, cap):
    cands, xs, _, _, alpha = _gp_data(n, d, cap)
    got = ops.grad_mean_batch(
        cands, xs, alpha, lengthscale=0.8, block_n=64, force_pallas=True
    )
    want = ref.grad_mean_batch(cands, xs, alpha, 0.8)
    assert got.shape == want.shape == (n, d)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5)


def test_gp_kernels_match_surrogate_math():
    """ops fast paths == the first-principles gp_surrogate oracle."""
    from repro.core import gp_surrogate as gp

    cap, d = 48, 6
    key = jax.random.PRNGKey(4)
    hyper = gp.default_hyper(0.7, 1e-4)
    traj = gp.traj_init(cap, d)
    factor = gp.factor_init(traj, hyper)
    for i in range(10):
        xs = jax.random.uniform(jax.random.fold_in(key, i), (4, d))
        traj, factor = gp.traj_extend(traj, factor, xs, jnp.sin(xs.sum(-1)), hyper)
    xq = jax.random.uniform(jax.random.fold_in(key, 99), (9, d))

    u_direct = gp.grad_uncertainty_batch(traj, hyper, xq)
    u_fast = gp.grad_uncertainty_batch_cached(traj, factor, hyper, xq)
    np.testing.assert_allclose(np.asarray(u_fast), np.asarray(u_direct), atol=2e-3)

    alpha = gp.gp_alpha_cached(traj, factor, hyper)
    g_direct = jax.vmap(lambda x: gp.grad_mean_cached(traj, factor, hyper, x))(xq)
    g_fast = ops.grad_mean_batch(xq, traj.xs, alpha, lengthscale=0.7)
    np.testing.assert_allclose(np.asarray(g_fast), np.asarray(g_direct), atol=1e-5)


CLIENT_SHAPES = [
    (1, 4, 3, 16),     # single client (the per-device distributed shape)
    (3, 64, 8, 64),    # block-aligned candidates
    (8, 100, 20, 128), # the paper's active-query shape, 8 clients
    (5, 130, 5, 96),   # misaligned candidate count
]


def _gp_data_clients(nb, n, d, cap, seed=0):
    key = jax.random.PRNGKey(seed)
    cands = jax.random.uniform(jax.random.fold_in(key, 0), (nb, n, d))
    xs = jax.random.uniform(jax.random.fold_in(key, 1), (nb, cap, d))
    a = jax.random.normal(jax.random.fold_in(key, 2), (nb, cap, cap)) / np.sqrt(cap)
    binv = jnp.einsum("bij,bkj->bik", a, a) + 0.1 * jnp.eye(cap)
    pmat = binv * jnp.einsum("bcd,bkd->bck", xs, xs)
    alpha = jax.random.normal(jax.random.fold_in(key, 3), (nb, cap))
    return cands, xs, binv, pmat, alpha


@pytest.mark.parametrize("nb,n,d,cap", CLIENT_SHAPES)
def test_uncertainty_scores_clients_kernel(nb, n, d, cap):
    """Client-batched kernel == batched oracle == vmap of the single-client
    oracle (the client grid dimension is a pure layout change)."""
    cands, xs, binv, pmat, _ = _gp_data_clients(nb, n, d, cap)
    got = ops.uncertainty_scores_clients(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_n=64, force_pallas=True,
    )
    want = ref.uncertainty_scores_clients(cands, xs, binv, pmat, 0.8, d / 0.64)
    single = jax.vmap(lambda c, x, b, p: ref.uncertainty_scores(c, x, b, p, 0.8, d / 0.64))(
        cands, xs, binv, pmat)
    assert got.shape == want.shape == (nb, n)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5)
    np.testing.assert_allclose(np.asarray(want) / scale, np.asarray(single) / scale, atol=5e-5)


@pytest.mark.parametrize("nb,n,d,cap", CLIENT_SHAPES)
def test_grad_mean_clients_kernel(nb, n, d, cap):
    cands, xs, _, _, alpha = _gp_data_clients(nb, n, d, cap)
    got = ops.grad_mean_clients(
        cands, xs, alpha, lengthscale=0.8, block_n=64, force_pallas=True
    )
    want = ref.grad_mean_clients(cands, xs, alpha, 0.8)
    single = jax.vmap(lambda c, x, a: ref.grad_mean_batch(c, x, a, 0.8))(cands, xs, alpha)
    assert got.shape == want.shape == (nb, n, d)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5)
    np.testing.assert_allclose(np.asarray(want) / scale, np.asarray(single) / scale, atol=5e-5)


def test_clients_kernels_candidate_padding_invariance():
    """The per-client candidate axis is zero-padded to the block multiple;
    padded rows yield junk that must be sliced away, and the client axis is
    NEVER padded (it is a grid dimension, any N launches)."""
    nb, n, d, cap = 3, 37, 6, 32  # n far from the 64 block
    cands, xs, binv, pmat, alpha = _gp_data_clients(nb, n, d, cap, seed=7)
    got = ops.uncertainty_scores_clients(
        cands, xs, binv, pmat, lengthscale=0.9, prior=d / 0.81,
        block_n=64, force_pallas=True,
    )
    assert got.shape == (nb, n)
    assert bool(jnp.isfinite(got).all())
    want = ref.uncertainty_scores_clients(cands, xs, binv, pmat, 0.9, d / 0.81)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5)
    g_got = ops.grad_mean_clients(cands, xs, alpha, lengthscale=0.9,
                                  block_n=64, force_pallas=True)
    g_want = ref.grad_mean_clients(cands, xs, alpha, 0.9)
    gs = max(float(jnp.abs(g_want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(g_got) / gs, np.asarray(g_want) / gs, atol=5e-5)


def test_clients_kernels_traced_hyper_fall_back_to_oracle():
    """Only the oracle route takes a traced lengthscale.  On the kernel
    route a traced scalar is an error: it is never silently swapped for the
    oracle (which would make a kernel run look like one that never was)."""
    cands, xs, binv, pmat, alpha = _gp_data_clients(2, 16, 4, 32)

    def scores(ls, force):
        return ops.uncertainty_scores_clients(
            cands, xs, binv, pmat, lengthscale=ls, prior=4.0 / ls**2,
            force_pallas=force,
        )

    got = jax.jit(lambda ls: scores(ls, False))(jnp.asarray(0.8))
    want = ref.uncertainty_scores_clients(cands, xs, binv, pmat, 0.8, 4.0 / 0.64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    with pytest.raises(TypeError, match="traced kernel scalar"):
        jax.jit(lambda ls: scores(ls, True))(jnp.asarray(0.8))
    with pytest.raises(TypeError, match="traced kernel scalar"):
        jax.jit(lambda ls: ops.grad_mean_clients(
            cands, xs, alpha, lengthscale=ls, force_pallas=True))(jnp.asarray(0.8))


def test_gp_kernels_traced_hyper_fall_back_to_oracle():
    """Unbatched twins of the test above."""
    cands, xs, binv, pmat, alpha = _gp_data(16, 4, 32)

    def scores(ls, force):
        return ops.uncertainty_scores(
            cands, xs, binv, pmat, lengthscale=ls, prior=4.0 / ls**2,
            force_pallas=force,
        )

    got = jax.jit(lambda ls: scores(ls, False))(jnp.asarray(0.8))
    want = ref.uncertainty_scores(cands, xs, binv, pmat, 0.8, 4.0 / 0.64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    with pytest.raises(TypeError, match="traced kernel scalar"):
        jax.jit(lambda ls: scores(ls, True))(jnp.asarray(0.8))
    with pytest.raises(TypeError, match="traced kernel scalar"):
        jax.jit(lambda ls: ops.grad_mean_batch(
            cands, xs, alpha, lengthscale=ls, force_pallas=True))(jnp.asarray(0.8))


# ---------------------------------------------------------------------------
# ops padding paths: zero-row padding invariants (see ops.py docstrings)
# ---------------------------------------------------------------------------

ODD_SHAPES = [
    (1, 1, 1),        # degenerate: everything padded
    (5, 3, 17),       # tiny odd
    (129, 7, 257),    # one past a block boundary on both axes
    (63, 2189, 999),  # one short of a block boundary, paper-sized d
]


@pytest.mark.parametrize("n,d,m", ODD_SHAPES)
def test_padding_invariance_rff_features(n, d, m):
    x, v, b, _ = _data(n, d, m, jnp.float32, seed=3)
    got = ops.rff_features(x, v, b, force_pallas=True)  # default 128/256 blocks
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.rff_features(x, v, b)), atol=2e-5
    )


@pytest.mark.parametrize("n,d,m", ODD_SHAPES)
def test_padding_invariance_rff_grad(n, d, m):
    """Padded feature slots carry v == 0 AND w == 0: exactly zero
    contribution, so the sliced result equals the unpadded oracle."""
    x, v, b, w = _data(n, d, m, jnp.float32, seed=4)
    got = ops.rff_grad(x, v, b, w, force_pallas=True)
    want = ref.rff_grad(x, v, b, w)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-5
    )
    # The invariant itself, at the kernel level: zero-padded feature slots
    # (v == 0 AND w == 0) contribute nothing PROVIDED n_features still names
    # the live count -- the sqrt(2/M) normalization is part of phi's
    # definition, so padding without pinning n_features is NOT a no-op.
    from repro.kernels.rff_grad import rff_grad_kernel

    pad = 128 - (m % 128) if m % 128 else 128
    npad = 64 - (n % 64) if n % 64 else 0
    got_k = rff_grad_kernel(
        jnp.pad(x, ((0, npad), (0, 0))), jnp.pad(v, ((0, pad), (0, 0))),
        jnp.pad(b, (0, pad)), jnp.pad(w, (0, pad)),
        n_features=m, block_n=64, block_m=128, interpret=True,
    )[:n]
    np.testing.assert_allclose(
        np.asarray(got_k) / scale, np.asarray(want) / scale, atol=5e-5
    )


@pytest.mark.parametrize("n,d,m", ODD_SHAPES)
def test_padding_invariance_sqexp(n, d, m):
    """Padded rows produce exp(-||x||^2/2l^2) junk INSIDE the kernel; the
    wrapper must slice it away (padding is zeros, never NaN)."""
    x, v, _, _ = _data(n, d, m, jnp.float32, seed=5)
    got = ops.sqexp(x, v, 0.9, force_pallas=True)
    assert got.shape == (n, m)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.sqexp(x, v, 0.9)), atol=2e-6)


# ---------------------------------------------------------------------------
# Cap-axis tiling (gp_score / gp_grad tiled kernels) + block autotuner
# ---------------------------------------------------------------------------

def _norm_close(got, want, atol):
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=atol)


@pytest.mark.parametrize("cap,block_cap", [(256, 128), (512, 128), (1024, 256)])
def test_tiled_scores_match_oracle(cap, block_cap):
    """Cap-tiled scoring == oracle at caps the resident kernel cannot hold."""
    n, d = 32, 8
    cands, xs, binv, pmat, _ = _gp_data(n, d, cap)
    got = ops.uncertainty_scores(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_n=32, block_cap=block_cap, force_pallas=True,
    )
    want = ref.uncertainty_scores(cands, xs, binv, pmat, 0.8, d / 0.64)
    _norm_close(got, want, 5e-5)


@pytest.mark.parametrize("cap,block_cap", [(512, 128), (1024, 512)])
def test_tiled_grad_mean_match_oracle(cap, block_cap):
    n, d = 32, 8
    cands, xs, _, _, alpha = _gp_data(n, d, cap)
    got = ops.grad_mean_batch(
        cands, xs, alpha, lengthscale=0.8,
        block_n=32, block_cap=block_cap, force_pallas=True,
    )
    want = ref.grad_mean_batch(cands, xs, alpha, 0.8)
    _norm_close(got, want, 5e-5)


def test_tiled_clients_match_oracle_cap1024():
    """Interpret-mode parity at the scale-out target cap=1024, both families."""
    nb, n, d, cap = 2, 16, 6, 1024
    cands, xs, binv, pmat, alpha = _gp_data_clients(nb, n, d, cap)
    got_s = ops.uncertainty_scores_clients(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_n=16, block_cap=512, force_pallas=True,
    )
    _norm_close(got_s, ref.uncertainty_scores_clients(cands, xs, binv, pmat, 0.8, d / 0.64), 5e-5)
    got_g = ops.grad_mean_clients(
        cands, xs, alpha, lengthscale=0.8,
        block_n=16, block_cap=512, force_pallas=True,
    )
    _norm_close(got_g, ref.grad_mean_clients(cands, xs, alpha, 0.8), 5e-5)


def test_tiled_cap_padding_exact_zero_invariance():
    """cap NOT a multiple of block_cap: the wrapper zero-pads the trajectory
    axis.  Padded slots must contribute EXACTLY zero (zero B/P rows+columns
    for scores, zero alpha for the grad mean), so padding to 256 vs manually
    padding further to 384 is BITWISE identical -- extra zero tiles only add
    exact zeros to the f32 accumulators."""
    n, d, cap = 32, 8, 200  # 200 % 128 != 0 -> wrapper pads to 256
    cands, xs, binv, pmat, alpha = _gp_data(n, d, cap)

    s_auto = ops.uncertainty_scores(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_n=32, block_cap=128, force_pallas=True,
    )
    xs384 = jnp.pad(xs, ((0, 384 - cap), (0, 0)))
    b384 = jnp.pad(binv, ((0, 384 - cap), (0, 384 - cap)))
    p384 = jnp.pad(pmat, ((0, 384 - cap), (0, 384 - cap)))
    s_manual = ops.uncertainty_scores(
        cands, xs384, b384, p384, lengthscale=0.8, prior=d / 0.64,
        block_n=32, block_cap=128, force_pallas=True,
    )
    np.testing.assert_array_equal(np.asarray(s_auto), np.asarray(s_manual))
    _norm_close(s_auto, ref.uncertainty_scores(cands, xs, binv, pmat, 0.8, d / 0.64), 5e-5)

    g_auto = ops.grad_mean_batch(
        cands, xs, alpha, lengthscale=0.8, block_n=32, block_cap=128, force_pallas=True
    )
    g_manual = ops.grad_mean_batch(
        cands, xs384, jnp.pad(alpha, (0, 384 - cap)), lengthscale=0.8,
        block_n=32, block_cap=128, force_pallas=True,
    )
    np.testing.assert_array_equal(np.asarray(g_auto), np.asarray(g_manual))
    _norm_close(g_auto, ref.grad_mean_batch(cands, xs, alpha, 0.8), 5e-5)


def test_tiled_clients_vs_per_client_bit_parity():
    """The client grid dimension is a pure layout change: the batched tiled
    kernel must be BITWISE identical to running the single-client tiled
    kernel once per client with the same blocks."""
    nb, n, d, cap = 3, 32, 6, 256
    cands, xs, binv, pmat, alpha = _gp_data_clients(nb, n, d, cap, seed=11)
    kw = dict(block_n=32, block_cap=128, force_pallas=True)
    s_batched = ops.uncertainty_scores_clients(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64, **kw
    )
    g_batched = ops.grad_mean_clients(cands, xs, alpha, lengthscale=0.8, **kw)
    for b in range(nb):
        s_one = ops.uncertainty_scores(
            cands[b], xs[b], binv[b], pmat[b], lengthscale=0.8, prior=d / 0.64, **kw
        )
        np.testing.assert_array_equal(np.asarray(s_batched[b]), np.asarray(s_one))
        g_one = ops.grad_mean_batch(cands[b], xs[b], alpha[b], lengthscale=0.8, **kw)
        np.testing.assert_array_equal(np.asarray(g_batched[b]), np.asarray(g_one))


def test_fused_epilogue_ref_matches_textbook():
    """ref.uncertainty_scores_clients_fused (the CPU execution path and the
    Pallas epilogue) is algebraically identical to the textbook oracle."""
    for seed, (nb, n, d, cap) in enumerate([(2, 64, 8, 64), (4, 100, 20, 128), (1, 37, 5, 96)]):
        cands, xs, binv, pmat, _ = _gp_data_clients(nb, n, d, cap, seed=seed)
        want = ref.uncertainty_scores_clients(cands, xs, binv, pmat, 0.8, d / 0.64)
        got = ref.uncertainty_scores_clients_fused(cands, xs, binv, pmat, 0.8, d / 0.64)
        _norm_close(got, want, 2e-5)


def test_autotune_deterministic_and_feasible():
    from repro.kernels import autotune

    autotune.clear_cache()
    picks = [
        autotune.select_blocks("score", n=100, cap=1024, d=20, n_clients=64, device_kind=k)
        for k in (TPU_V5E, "cpu", TPU_V5E)
    ]
    assert picks[0] == picks[2]  # deterministic (and cached)
    for bn, bc in picks:
        assert bn in autotune._BLOCK_N_CANDIDATES
        assert bc in autotune._BLOCK_CAP_CANDIDATES
    # The scale-out premise: cap=1024 does NOT fit resident on tpu VMEM.
    assert picks[0][1] < 1024
    # Small shapes stay resident (no tiling overhead when everything fits).
    bn, bc = autotune.select_blocks("score", n=100, cap=128, d=20, n_clients=8, device_kind=TPU_V5E)
    assert bc >= 128


def test_autotune_unknown_device_kind_raises():
    """A device missing from the roofline table is an error, never a default
    VMEM budget."""
    from repro.kernels import autotune
    from repro.launch.mesh import UnknownDeviceError

    autotune.clear_cache()
    for call in (
        lambda: autotune.select_blocks("score", n=8, cap=128, d=16, device_kind="TPU v9"),
        lambda: autotune.validate_blocks("grad", block_n=8, block_cap=128, cap=128,
                                         d=16, device_kind="TPU v9"),
        lambda: autotune.measure_blocks("grad", lambda bn, bc: None, n=8, cap=128,
                                        d=16, device_kind="TPU v9"),
    ):
        with pytest.raises(UnknownDeviceError, match="TPU v9"):
            call()


def test_autotune_dtype_keys_cache_and_default_is_f32():
    """The tuner cache distinguishes dtypes; omitting dtype == explicit f32
    (bitwise-identical picks for every pre-dtype caller)."""
    from repro.kernels import autotune

    autotune.clear_cache()
    shape = dict(n=64, cap=2048, d=16, n_clients=4, device_kind=TPU_V5E)
    default = autotune.select_blocks("score", **shape)
    explicit = autotune.select_blocks("score", **shape, dtype=jnp.float32)
    assert default == explicit
    # Distinct key components per dtype, f32 key == no-dtype key.
    kf = autotune.cache_key("score", TPU_V5E, 4, 64, 2048, 16)
    assert kf == autotune.cache_key("score", TPU_V5E, 4, 64, 2048, 16, jnp.float32)
    kb = autotune.cache_key("score", TPU_V5E, 4, 64, 2048, 16, jnp.bfloat16)
    assert kf != kb
    # Both entries coexist in the cache; the bf16 feasibility set is at
    # least as large (halved working set), so its pick is independent.
    bf16 = autotune.select_blocks("score", **shape, dtype=jnp.bfloat16)
    assert kf in autotune._CACHE and kb in autotune._CACHE
    assert bf16[0] in autotune._BLOCK_N_CANDIDATES
    assert bf16[1] in autotune._BLOCK_CAP_CANDIDATES


def test_autotune_explicit_blocks_override():
    """AlgoConfig-pinned blocks must bypass the tuner entirely."""
    n, d, cap = 32, 8, 256
    cands, xs, binv, pmat, _ = _gp_data(n, d, cap)
    want = ops.uncertainty_scores(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_n=32, block_cap=128, force_pallas=True,
    )
    # Pin only one of the two: the other comes from the tuner.
    got = ops.uncertainty_scores(
        cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
        block_cap=128, force_pallas=True,
    )
    _norm_close(got, ref.uncertainty_scores(cands, xs, binv, pmat, 0.8, d / 0.64), 5e-5)
    assert want.shape == got.shape


def test_validate_blocks_rejects_over_budget_pins():
    """An AlgoConfig block pin the VMEM budget cannot hold must fail loudly
    (naming the blocks and the budget), not as an opaque Mosaic error."""
    from repro.kernels import autotune

    with pytest.raises(ValueError) as e:
        autotune.validate_blocks("score", block_n=256, block_cap=1024,
                                 cap=2048, d=256, device_kind=TPU_V5E)
    msg = str(e.value)
    assert "block_n=256" in msg and "block_cap=1024" in msg
    assert "budget" in msg and "bytes" in msg
    # an in-budget pin passes through untouched
    assert autotune.validate_blocks(
        "score", block_n=32, block_cap=128, cap=2048, d=256, device_kind=TPU_V5E
    ) == (32, 128)
    # block_cap >= cap routes resident: the pin is judged at the REAL
    # working set (lane-padded cap), so a nominal huge block_cap is fine
    # when the trajectory itself fits
    assert autotune.validate_blocks(
        "score", block_n=32, block_cap=1 << 20, cap=256, d=16, device_kind=TPU_V5E
    ) == (32, 1 << 20)


def test_ops_reject_over_budget_pins_before_launch():
    n, d, cap = 8, 256, 2048
    cands, xs, binv, pmat, _ = _gp_data(n, d, cap, seed=3)
    with pytest.raises(ValueError, match="VMEM"):
        ops.uncertainty_scores(
            cands, xs, binv, pmat, lengthscale=0.8, prior=d / 0.64,
            block_n=256, block_cap=1024, force_pallas=True,
        )
    # grad's working set is lighter (no (bc, bc) Gram tiles): it takes
    # d=2048 for the same pin to genuinely blow the budget
    with pytest.raises(ValueError, match="VMEM"):
        ops.grad_mean_batch(
            jnp.zeros((8, 2048)), jnp.zeros((2048, 2048)), jnp.zeros((2048,)),
            lengthscale=0.8, block_n=256, block_cap=1024, force_pallas=True,
        )


# ---------------------------------------------------------------------------
# bf16 inputs + f32 scratch: tiled-kernel interpret-mode parity
# ---------------------------------------------------------------------------


def _to_bf16(*arrays):
    return tuple(a.astype(jnp.bfloat16) for a in arrays)


def test_tiled_scores_bf16_inputs_f32_scratch_parity():
    """bf16 inputs through the cap-tiled scoring kernel: the f32 scratch
    accumulator keeps the error at input-quantization level (~bf16 eps),
    NOT at sum-length level -- compared against the f32 oracle."""
    from repro.kernels.gp_score import score_tiled_spec

    n, d, cap = 32, 8, 256
    spec = score_tiled_spec(n, cap, d, jnp.bfloat16, block_n=32, block_cap=128)
    assert all(jnp.dtype(s.dtype) == jnp.float32 for s in spec.scratch)
    cands, xs, binv, pmat, _ = _gp_data(n, d, cap)
    got = ops.uncertainty_scores(
        *_to_bf16(cands, xs, binv, pmat), lengthscale=0.8, prior=d / 0.64,
        block_n=32, block_cap=128, force_pallas=True,
    )
    assert got.dtype == jnp.bfloat16
    want = ref.uncertainty_scores(cands, xs, binv, pmat, 0.8, d / 0.64)
    _norm_close(got.astype(jnp.float32), want, 4e-2)


def test_tiled_grad_mean_bf16_inputs_f32_scratch_parity():
    from repro.kernels.gp_grad import grad_tiled_spec

    n, d, cap = 32, 8, 256
    spec = grad_tiled_spec(n, cap, d, jnp.bfloat16, block_n=32, block_cap=128)
    assert all(jnp.dtype(s.dtype) == jnp.float32 for s in spec.scratch)
    cands, xs, _, _, alpha = _gp_data(n, d, cap)
    got = ops.grad_mean_batch(
        *_to_bf16(cands, xs, alpha), lengthscale=0.8,
        block_n=32, block_cap=128, force_pallas=True,
    )
    assert got.dtype == jnp.bfloat16
    want = ref.grad_mean_batch(cands, xs, alpha, 0.8)
    _norm_close(got.astype(jnp.float32), want, 4e-2)


def test_algo_config_block_overrides_thread_through():
    """score_block_*/grad_block_* reach the kernels via gp_surrogate without
    changing results (tiling is value-preserving)."""
    from repro.core import gp_surrogate as gp

    nb, d, cap = 2, 4, 64
    key = jax.random.PRNGKey(3)
    hyper = gp.default_hyper(0.7, 1e-4)
    trajs = jax.vmap(lambda _: gp.traj_init(cap, d))(jnp.arange(nb))
    factors = jax.vmap(gp.factor_init, in_axes=(0, None))(trajs, hyper)
    xs = jax.random.uniform(jax.random.fold_in(key, 0), (nb, 6, d))
    ys = jnp.sin(xs.sum(-1))
    trajs, factors = gp.traj_extend_clients(trajs, factors, xs, ys, hyper)
    xq = jax.random.uniform(jax.random.fold_in(key, 1), (nb, 8, d))

    u_default = gp.grad_uncertainty_batch_cached_clients(trajs, factors, hyper, xq)
    u_pinned = gp.grad_uncertainty_batch_cached_clients(
        trajs, factors, hyper, xq, block_n=8, block_cap=128
    )
    np.testing.assert_allclose(np.asarray(u_pinned), np.asarray(u_default), atol=1e-5)

    g_default = gp.grad_mean_cached_clients(trajs, factors, hyper, xq[:, 0, :])
    g_pinned = gp.grad_mean_cached_clients(
        trajs, factors, hyper, xq[:, 0, :], block_n=8, block_cap=128
    )
    np.testing.assert_allclose(np.asarray(g_pinned), np.asarray(g_default), atol=1e-5)
