#!/usr/bin/env python3
"""Smoke run of the FZooS round engine on a TPU, through its front doors.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded engine on the host's 4 chips

One chip runs four phases:

* kernels  -- each Pallas kernel of the engine (client-batched scoring and
  grad mean, VMEM-resident at cap=128 and cap-tiled at cap=1024; RFF
  features, RFF gradient, SE Gram) against its ``kernels/ref.py`` oracle at
  d=3072, with the interpret-mode tests' tolerances;
* fzoos    -- ``algorithms.simulate`` with the ``AlgoConfig`` defaults
  (cap=128, M=512, T=10, 100 candidates, 5 active queries per step; Adam
  step ``ETA_D3072``) on the quadratic objective at N=64 clients, d=3072,
  32 rounds: two 16-round chunks and a chunk-boundary repair;
* fedzo    -- the finite-difference baseline on the same objective;
* lm       -- fzoos on the full-width qwen1.5-0.5b objective (24 layers,
  d_model 1024, vocab 151936; random weights from ``--seed``), with F(x_0)
  checked against a float32 ``lm_loss`` reference.

``--chips 4`` runs only ``federated.run_distributed`` of the fzoos phase on
a mesh of the four local chips (16 clients each) and ``simulate`` of the
same run on one chip, and checks their bounded divergence.

Every phase prints its wall time, compile count and the device's peak
bytes.  The script exits non-zero, and prints no result line, when JAX finds
no TPU or any phase fails; otherwise the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It runs in one process, which owns the chip(s), and starts no other.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Phase sizes.  ``FULL`` is what a chip run uses."""

    n_clients: int = 64
    dim: int = 3072  # 32x32x3 images: the cross-silo attack's search space
    rounds: int = 32  # two DEFAULT_CHUNK=16 chunks
    fedzo_rounds: int = 4
    n_cand: int = 100  # AlgoConfig.active_candidates
    cap_tiled: int = 1024
    n_features: int = 512  # AlgoConfig.n_features
    arch_variant: str = "full"
    lm_clients: int = 8
    lm_rounds: int = 2


FULL = Sizes()

#: Adam step of the d=3072 phases, the one departure from AlgoConfig's
#: defaults.  The default eta=0.01 moves the iterate eta*sqrt(d) = 0.55 per
#: local step at d=3072, and F rises under fzoos and fedzo alike; 0.001
#: keeps the step at 0.055 and both descend.
ETA_D3072 = 1e-3

#: Bars of tests/test_federated.py's four-device sim-vs-distributed test.
SIM_DIST_BARS = {"round1_x": 5e-2, "history_x": 0.1, "f": 5e-2}


class PhaseFailed(AssertionError):
    """A phase's result failed its check."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _peak_bytes():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def kernel_call_count(hlo_text: str) -> int:
    """Number of Mosaic kernel calls in a compiled program's HLO."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _norm_err(got, want) -> float:
    """Max error over max(|want|, 1): the tests' normalized comparison."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / max(float(np.abs(want).max()), 1.0))


def phase_kernels(sz: Sizes, key) -> None:
    from repro.core import rff as rfflib
    from repro.kernels import ops, ref

    n, d, nb, ls = sz.n_cand, sz.dim, sz.n_clients, 1.0
    prior = d / ls**2
    ks = jax.random.split(key, 8)

    def gp_data(cap, k):
        # shifted coordinates around the candidate centroid, as the engine
        # hands them over: points within the 0.01 active-query radius
        u = lambda kk, shape: 0.01 * jax.random.uniform(kk, shape, minval=-1.0, maxval=1.0)
        cands, xs = u(jax.random.fold_in(k, 0), (nb, n, d)), u(jax.random.fold_in(k, 1), (nb, cap, d))
        a = jax.random.normal(jax.random.fold_in(k, 2), (nb, cap, cap)) / np.sqrt(cap)
        with jax.default_matmul_precision("highest"):
            binv = jnp.einsum("bij,bkj->bik", a, a) + 0.1 * jnp.eye(cap)
            pmat = binv * jnp.einsum("bcd,bkd->bck", xs, xs)
        alpha = jax.random.normal(jax.random.fold_in(k, 3), (nb, cap))
        return cands, xs, binv, pmat, alpha

    # (name, kernel call, oracle call, tolerance of tests/test_kernels.py,
    #  normalized comparison?)
    cases = []
    for cap, k in ((128, ks[0]), (sz.cap_tiled, ks[1])):
        cands, xs, binv, pmat, alpha = gp_data(cap, k)
        tag = "resident" if cap <= 128 else "tiled"
        cases.append((
            f"scores-clients-{tag} cap={cap}",
            lambda c=cands, x=xs, b=binv, p=pmat: ops.uncertainty_scores_clients(
                c, x, b, p, lengthscale=ls, prior=prior),
            lambda c=cands, x=xs, b=binv, p=pmat: ref.uncertainty_scores_clients(
                c, x, b, p, ls, prior),
            5e-5, True, ("score", n, cap)))
        g_cands = cands[:, :1]  # the engine's grad mean: one point per client
        cases.append((
            f"grad-mean-clients-{tag} cap={cap}",
            lambda c=g_cands, x=xs, a=alpha: ops.grad_mean_clients(c, x, a, lengthscale=ls),
            lambda c=g_cands, x=xs, a=alpha: ref.grad_mean_clients(c, x, a, ls),
            5e-5, True, ("grad", 1, cap)))
    rff = rfflib.make_rff(ks[2], sz.n_features, d, ls)
    x = jax.random.uniform(ks[3], (n, d))
    w = jax.random.normal(ks[4], (sz.n_features,))
    cases.append(("rff-features", lambda: ops.rff_features(x, rff.v, rff.b),
                  lambda: ref.rff_features(x, rff.v, rff.b), 2e-5, False, None))
    cases.append(("rff-grad", lambda: ops.rff_grad(x, rff.v, rff.b, w),
                  lambda: ref.rff_grad(x, rff.v, rff.b, w), 5e-5, True, None))
    x1 = 0.01 * jax.random.uniform(ks[5], (n, d), minval=-1.0, maxval=1.0)
    x2 = 0.01 * jax.random.uniform(ks[6], (128, d), minval=-1.0, maxval=1.0)
    cases.append(("sqexp", lambda: ops.sqexp(x1, x2, ls), lambda: ref.sqexp(x1, x2, ls),
                  2e-6, False, None))

    failed = []
    for case in cases:
        try:  # every kernel is checked, so one run reports all of them
            _kernel_case(*case, d=d, nb=nb)
        except Exception:
            traceback.print_exc()
            failed.append(case[0])
    _check(not failed, f"kernels failed: {', '.join(failed)}")


def _kernel_case(name, kern, oracle, tol, normalized, tuned, *, d, nb) -> None:
    from repro.kernels import autotune

    # the kernels fix their own matmul precision (spec.MXU_PRECISION);
    # the oracle runs at the highest precision the chip has
    got = jax.block_until_ready(kern())
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(oracle())
    err = (_norm_err(got, want) if normalized
           else float(np.abs(np.asarray(got) - np.asarray(want)).max()))
    blocks = ""
    if tuned is not None:
        kind, nn, cap = tuned
        bn, bc = autotune.select_blocks(kind, n=nn, cap=cap, d=d, n_clients=nb)
        _check((bc < cap) == (cap > 128), f"{name}: tuner picked block_cap={bc}, "
               f"which does not route to the {'tiled' if cap > 128 else 'resident'} kernel")
        blocks = f" blocks=({bn},{bc})"
    print(f"  kernel {name}: shape={tuple(got.shape)}{blocks} err={err:.3e} tol={tol:.0e}",
          flush=True)
    _check(bool(np.isfinite(np.asarray(got)).all()), f"{name}: non-finite output")
    _check(err <= tol, f"{name}: error {err:.3e} exceeds {tol:.0e}")


# ---------------------------------------------------------------------------
# the round engine
# ---------------------------------------------------------------------------


class ChunkSpy:
    """Observes the chunk steps that a front door builds: compiles per
    chunk call, and the argument shapes of the first call (to recompile the
    step and read its HLO).  The second and later calls of one step run
    under ``no_recompiles``."""

    def __init__(self):
        self.compiles: list[int] = []
        self.steps: list = []

    def __enter__(self):
        from repro.analysis.contracts import no_recompiles, steady_state_guard
        from repro.core import rounds as rounds_mod

        self._rounds, self._orig = rounds_mod, rounds_mod.make_chunk_step
        spy = self

        def make_chunk_step(chunk_fn):
            step = spy._orig(chunk_fn)
            entry = {"step": step, "shapes": None}
            spy.steps.append(entry)

            def call(*args):
                if entry["shapes"] is None:
                    entry["shapes"] = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                        args)
                    guard = steady_state_guard(allow_compiles=None, allow_device_gets=None)
                else:
                    guard = no_recompiles()
                with guard as g:
                    out = jax.block_until_ready(step(*args))
                spy.compiles.append(g.compiles)
                return out

            return call

        rounds_mod.make_chunk_step = make_chunk_step
        return self

    def __exit__(self, *exc):
        self._rounds.make_chunk_step = self._orig
        return False

    def kernel_calls(self) -> int:
        """tpu_custom_call count of the first chunk step's executable."""
        e = self.steps[0]
        return kernel_call_count(e["step"].lower(*e["shapes"]).compile().as_text())


def _quadratic(sz: Sizes, key):
    """Heterogeneous quadratics (C=5) with observation noise 0.01/d: the
    tests' 1e-3 at d=10, scaled with the objective's own 1/(10 d) scale so
    the signal-to-noise ratio does not fall with d."""
    from repro.core import objectives as obj

    return obj.make_quadratic(key, sz.n_clients, sz.dim, 5.0, 0.01 / sz.dim)


def phase_fzoos(sz: Sizes, key) -> None:
    from repro.core import algorithms as alg
    from repro.core import objectives as obj

    kobj, krun = jax.random.split(key)
    cfg = alg.AlgoConfig(name="fzoos", dim=sz.dim, n_clients=sz.n_clients, eta=ETA_D3072)
    state_bytes = 4 * cfg.n_clients * (3 * cfg.traj_capacity**2
                                       + cfg.traj_capacity * (cfg.dim + 1))
    print(f"  fzoos N={cfg.n_clients} d={cfg.dim} cap={cfg.traj_capacity} "
          f"M={cfg.n_features} T={cfg.local_steps} eta={cfg.eta} rounds={sz.rounds}: "
          f"client factor+trajectory state {state_bytes / 1e6:.1f} MB")
    with ChunkSpy() as spy:
        res = alg.simulate(cfg, krun, _quadratic(sz, kobj), obj.quadratic_query,
                           obj.quadratic_global_value, sz.rounds)
        f = np.asarray(jax.block_until_ready(res.f_values))
    q = float(res.queries[-1])
    n_kernel = spy.kernel_calls()
    print(f"  F(x_0)={f[0]:+.6e} F(x_R)={f[-1]:+.6e} queries/client={q:.0f} "
          f"chunk compiles={spy.compiles} tpu_custom_calls={n_kernel} "
          f"repair_rate={float(np.mean(res.repair_rate)):.4f} "
          f"refactor_rate={float(np.mean(res.refactor_rate)):.4f}")
    _check(bool(np.isfinite(f).all()), "fzoos: non-finite F")
    _check(f[-1] < f[0], f"fzoos: F(x_R)={f[-1]} is not below F(x_0)={f[0]}")
    want_q = cfg.queries_per_round() * sz.rounds
    _check(q == want_q, f"fzoos: queries/client {q} != {want_q}")
    _check(len(spy.compiles) >= 2, f"fzoos: expected two chunk calls, got {spy.compiles}")
    _check(n_kernel >= 3, f"fzoos: compiled chunk step holds {n_kernel} tpu_custom_call(s); "
           "the scoring and grad-mean calls did not take the kernel route")


def phase_fedzo(sz: Sizes, key) -> None:
    from repro.core import algorithms as alg
    from repro.core import objectives as obj

    kobj, krun = jax.random.split(key)
    cfg = alg.AlgoConfig(name="fedzo", dim=sz.dim, n_clients=sz.n_clients, eta=ETA_D3072)
    res = alg.simulate(cfg, krun, _quadratic(sz, kobj), obj.quadratic_query,
                       obj.quadratic_global_value, sz.fedzo_rounds)
    f = np.asarray(jax.block_until_ready(res.f_values))
    q = float(res.queries[-1])
    print(f"  fedzo N={cfg.n_clients} d={cfg.dim} q={cfg.q} T={cfg.local_steps} eta={cfg.eta} "
          f"rounds={sz.fedzo_rounds}: F(x_0)={f[0]:+.6e} F(x_R)={f[-1]:+.6e} "
          f"queries/client={q:.0f}")
    _check(bool(np.isfinite(f).all()), "fedzo: non-finite F")
    want_q = cfg.queries_per_round() * sz.fedzo_rounds
    _check(q == want_q, f"fedzo: queries/client {q} != {want_q}")


def phase_lm(sz: Sizes, key) -> None:
    from repro.configs import get_config
    from repro.core import algorithms as alg
    from repro.core import model_objectives as mobj
    from repro.models.model import lm_loss
    from repro.models.params import init_params
    from repro.sharding.rules import ShardingPolicy

    kp, kobj, krun = jax.random.split(key, 3)
    mcfg = get_config("qwen1_5_0_5b", sz.arch_variant)
    params = init_params(kp, mcfg)
    n_params = sum(int(np.prod(a.shape)) for a in params.values())
    cobjs = mobj.make_lm_objective(kobj, mcfg, sz.lm_clients)
    query, global_value, dim, _ = mobj.make_lm_query(mcfg, params)
    cfg = alg.AlgoConfig(name="fzoos", dim=dim, n_clients=sz.lm_clients)
    print(f"  lm {mcfg.name} ({mcfg.source}) layers={mcfg.n_layers} d_model={mcfg.d_model} "
          f"vocab={mcfg.vocab_size} params={n_params / 1e6:.1f}M {mcfg.dtype}; "
          f"N={cfg.n_clients} tokens/client={cobjs.batches_tokens.shape[1:]} "
          f"rounds={sz.lm_rounds}")
    res = alg.simulate(cfg, krun, cobjs, query, global_value, sz.lm_rounds)
    f = np.asarray(jax.block_until_ready(res.f_values))

    # float32 reference on the unperturbed gains (x_0 = 1/2 shifts nothing)
    params32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    mcfg32 = dataclasses.replace(mcfg, dtype="float32")
    policy = ShardingPolicy(remat=False)
    loss = jax.jit(lambda p, tok, lab: lm_loss(p, mcfg32, {"tokens": tok, "labels": lab},
                                                policy)[0])
    with jax.default_matmul_precision("highest"):
        per_client = [float(loss(params32, cobjs.batches_tokens[i], cobjs.batches_labels[i]))
                      for i in range(sz.lm_clients)]
    f_ref = float(np.mean(per_client)) / 10.0
    rel = abs(f[0] - f_ref) / abs(f_ref)
    print(f"  F(x_0)={f[0]:+.6e} reference={f_ref:+.6e} rel_err={rel:.3e} "
          f"F(x_R)={f[-1]:+.6e} queries/client={float(res.queries[-1]):.0f}")
    _check(bool(np.isfinite(f).all()), "lm: non-finite F")
    _check(rel <= 1e-2, f"lm: F(x_0) differs from the float32 reference by {rel:.3e} > 1e-2")


def phase_sim_vs_distributed(sz: Sizes, key) -> None:
    from repro.core import algorithms as alg
    from repro.core import objectives as obj
    from repro.core.federated import run_distributed
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    mesh = make_mesh((len(devs),), ("data",))
    kobj, krun = jax.random.split(key)
    cobjs = _quadratic(sz, kobj)
    cfg = alg.AlgoConfig(name="fzoos", dim=sz.dim, n_clients=sz.n_clients, eta=ETA_D3072)
    print(f"  fzoos N={cfg.n_clients} d={cfg.dim} rounds={sz.rounds} on a "
          f"{len(devs)}-chip mesh ({cfg.n_clients // len(devs)} clients per chip)")

    def per_device(label):
        for dv in devs:
            st = dv.memory_stats() or {}
            print(f"    {label} {dv}: bytes_in_use={st.get('bytes_in_use')} "
                  f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")

    t0 = time.perf_counter()
    r_dist = jax.block_until_ready(run_distributed(cfg, mesh, krun, cobjs, obj.quadratic_query,
                                    obj.quadratic_global_value, sz.rounds))
    print(f"  run_distributed: {time.perf_counter() - t0:.3f} s")
    per_device("after run_distributed")
    t0 = time.perf_counter()
    r_sim = jax.block_until_ready(alg.simulate(cfg, krun, jax.device_put(cobjs, devs[0]),
                                obj.quadratic_query, obj.quadratic_global_value, sz.rounds))
    print(f"  simulate (one chip): {time.perf_counter() - t0:.3f} s")
    per_device("after simulate")
    xs_s, xs_d = np.asarray(r_sim.xs), np.asarray(r_dist.xs)
    f_s, f_d = np.asarray(r_sim.f_values), np.asarray(r_dist.f_values)
    errs = {"round1_x": float(np.abs(xs_s[1] - xs_d[1]).max()),
            "history_x": float(np.abs(xs_s - xs_d).max()),
            "f": float(np.abs(f_s - f_d).max())}
    print("  divergence " + " ".join(f"{k}={v:.3e} (bar {SIM_DIST_BARS[k]:.0e})"
                                    for k, v in errs.items()))
    print(f"  F(x_R): simulate={f_s[-1]:+.6e} distributed={f_d[-1]:+.6e}")
    _check(bool(np.isfinite(f_d).all()), "distributed: non-finite F")
    for k, v in errs.items():
        _check(v < SIM_DIST_BARS[k], f"sim vs distributed {k}: {v:.3e} >= {SIM_DIST_BARS[k]}")


# ---------------------------------------------------------------------------
# running the phases
# ---------------------------------------------------------------------------


def run_phases(phases, sz: Sizes, seed: int) -> list[str]:
    """Run every phase even when one fails; return the names that failed."""
    from repro.analysis.contracts import steady_state_guard

    failed = []
    key = jax.random.PRNGKey(seed)
    for i, (name, fn) in enumerate(phases):
        print(f"phase {name}")
        t0 = time.perf_counter()
        try:
            with steady_state_guard(allow_compiles=None, allow_device_gets=None) as g:
                fn(sz, jax.random.fold_in(key, i))
        except Exception:  # a failed phase must not hide the others
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        else:
            status = "ok"
        print(f"phase {name} {status}: wall_s={time.perf_counter() - t0:.3f} "
              f"compiles={g.compiles} peak_bytes_in_use={_peak_bytes()}", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine on the host's four chips "
                         "and its one-chip simulate comparison")
    ap.add_argument("--seed", type=int, default=0, help="seed of weights, data and runs")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device_kind={dev.device_kind} devices={len(jax.devices())} "
          f"jax={jax.__version__} compile_cache={enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phases = [("sim-vs-distributed", phase_sim_vs_distributed)]
    else:
        phases = [("kernels", phase_kernels), ("fzoos", phase_fzoos),
                  ("fedzo", phase_fedzo), ("lm", phase_lm)]
    failed = run_phases(phases, FULL, args.seed)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
