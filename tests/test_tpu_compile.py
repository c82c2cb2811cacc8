"""Compile the Pallas kernels and the fzoos chunk step for a TPU v5e.

Nothing runs here: each test compiles for one chip of a ``v5e:2x2`` topology
that the TPU compiler installed with jax describes without the chip, and
checks that the executable carries the Mosaic kernel (``tpu_custom_call``).
This catches what interpret mode cannot -- tiles the chip's layout refuses,
more VMEM than a kernel may use -- at no chip time.  The kernels compile at
the engine's real widths: N=64 clients, n=128 candidates, d=3072 (an
image-sized search space), M=512 random features, cap=128 (resident) and
cap=1024 (tiled).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.gp_grad import grad_mean_clients_kernel, grad_mean_tiled_clients_kernel
from repro.kernels.gp_score import (
    uncertainty_scores_clients_kernel,
    uncertainty_scores_tiled_clients_kernel,
)
from repro.kernels.rff_features import rff_features_kernel
from repro.kernels.rff_grad import rff_grad_kernel
from repro.kernels.sqexp import sqexp_kernel
from repro.launch.mesh import TPU_V5E, roofline

N, N_CAND, D, M = 64, 128, 3072, 512
LENGTHSCALE = 1.0  # the AlgoConfig default


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _blocks(kind, cap, *, tiled):
    """The tuner's v5e pick, checked to route to the kernel under test."""
    bn, bc = autotune.select_blocks(kind, n=N_CAND, cap=cap, d=D, n_clients=N,
                                    device_kind=TPU_V5E)
    assert (bc < cap) == tiled, (kind, cap, bn, bc)
    return bn, bc


def _lower_gp(kind, sh, *, n, cap, block_n, block_cap):
    """Lower the GP kernel that ``kernels/ops.py`` routes these blocks to:
    cap-tiled when ``block_cap < cap``, VMEM-resident otherwise."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)
    tiled = block_cap < cap
    if kind == "score":
        args = (f32(N, n, D), f32(N, cap, D), f32(N, cap, cap), f32(N, cap, cap))
        kw = dict(lengthscale=LENGTHSCALE, prior=D / LENGTHSCALE**2, block_n=block_n)
        if tiled:
            return uncertainty_scores_tiled_clients_kernel.lower(*args, **kw,
                                                                 block_cap=block_cap)
        return uncertainty_scores_clients_kernel.lower(*args, **kw)
    args = (f32(N, n, D), f32(N, cap, D), f32(N, 1, cap))
    kw = dict(lengthscale=LENGTHSCALE, block_n=block_n)
    if tiled:
        return grad_mean_tiled_clients_kernel.lower(*args, **kw, block_cap=block_cap)
    return grad_mean_clients_kernel.lower(*args, **kw)


def _lower_kernel(name, sh):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)
    if name in ("scores-clients", "scores-tiled-clients", "grad-clients", "grad-tiled-clients"):
        kind = "score" if name.startswith("scores") else "grad"
        tiled = "tiled" in name
        cap = 1024 if tiled else 128
        bn, bc = _blocks(kind, cap, tiled=tiled)
        return _lower_gp(kind, sh, n=N_CAND, cap=cap, block_n=bn, block_cap=bc)
    if name == "rff-features":
        return rff_features_kernel.lower(f32(N_CAND, D), f32(M, D), f32(M), n_features=M)
    if name == "rff-grad":
        return rff_grad_kernel.lower(f32(N_CAND, D), f32(M, D), f32(M), f32(M), n_features=M)
    if name == "sqexp":
        return sqexp_kernel.lower(f32(N_CAND, D), f32(N_CAND, D), lengthscale=LENGTHSCALE)
    raise ValueError(name)


@pytest.mark.parametrize("name", [
    "scores-clients", "scores-tiled-clients", "grad-clients", "grad-tiled-clients",
    "rff-features", "rff-grad", "sqexp",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    compiled = _lower_kernel(name, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind,cap", [("score", 128), ("score", 1024),
                                      ("grad", 128), ("grad", 1024)])
def test_every_feasible_block_pick_compiles_for_v5e(one_chip, kind, cap):
    """The tuner's VMEM model bounds what the compiler allocates: every
    ``(block_n, block_cap)`` it may pick at d=3072 compiles within the
    chip's default scoped VMEM.  (The engine's grad mean runs one point per
    client, block_n=8; a model without the f32 matmul's split operands let
    block_cap=256 through there, 18.5 MiB against a 16 MiB limit.)"""
    picks = list(autotune._feasible(kind, N_CAND, cap, D, roofline(TPU_V5E)))
    assert picks
    for bn, bc in picks:
        _lower_gp(kind, one_chip, n=bn, cap=cap, block_n=bn, block_cap=bc).compile()


def test_fzoos_chunk_step_takes_the_kernel_route_on_v5e(one_chip, monkeypatch):
    """The round body hands the kernel wrappers static scalars, so with the
    TPU route taken (forced here: the default backend is the CPU) the
    compiled chunk step calls the scoring and grad-mean kernels instead of
    their jnp references -- and a traced scalar would raise while tracing."""
    from repro.core import algorithms as alg
    from repro.core import objectives as obj
    from repro.core import rff as rfflib
    from repro.core import rounds as rounds_mod
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "local_device_kind", lambda: TPU_V5E)
    n, d, rounds = 8, 256, 1
    cfg = alg.AlgoConfig(name="fzoos", dim=d, n_clients=n, local_steps=1, traj_capacity=32,
                         n_features=32, active_candidates=16)
    key = jax.random.PRNGKey(0)
    cobjs = obj.make_quadratic(key, n, d, 5.0, 0.001)
    x0 = jnp.full((d,), 0.5, jnp.float32)
    rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, d, cfg.lengthscale)
    states = alg.init_states(cfg, jax.random.PRNGKey(2), x0)
    hist = rounds_mod.history_init(rounds, x0, jnp.zeros((), jnp.float32))
    step = rounds_mod.make_chunk_step(rounds_mod.sim_chunk_fn(
        cfg, rff, obj.quadratic_query, obj.quadratic_global_value, None, rounds))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (states, hist, cobjs, x0, jnp.int32(0)))
    text = step.lower(*args).compile().as_text()
    # local-step scoring, local-step grad mean, round-end scoring
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
