"""Paper-experiment launcher: run FZooS / baselines on any objective,
single-process (vmap) or distributed (shard_map over the device mesh).

    # paper Fig. 1 setting (synthetic quadratics, d=300, N=5)
    PYTHONPATH=src python -m repro.launch.fedzoo --objective quadratic \
        --algo fzoos --dim 300 --clients 5 --het 5.0 --rounds 50

    # federated black-box adversarial attack (Sec. 6.2)
    PYTHONPATH=src python -m repro.launch.fedzoo --objective attack --clients 10

    # non-differentiable metric optimization (Sec. 6.3)
    PYTHONPATH=src python -m repro.launch.fedzoo --objective metric --clients 7

    # FZooS over an architecture-zoo backbone (framework integration)
    PYTHONPATH=src python -m repro.launch.fedzoo --objective lm --arch mamba2-370m

    # distributed engine over the local device mesh
    PYTHONPATH=src python -m repro.launch.fedzoo --objective quadratic --distributed
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.core import algorithms as alg
from repro.core import model_objectives as mobj
from repro.core import objectives as obj
from repro.core.federated import run_distributed
from repro.launch import common
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def build_objective(args, key):
    if args.objective == "quadratic":
        cobjs = obj.make_quadratic(key, args.clients, args.dim, args.het, args.noise_std)
        return cobjs, obj.quadratic_query, obj.quadratic_global_value, args.dim
    if args.objective == "sinquad":
        cobjs = obj.make_sinquad(key, args.clients, args.dim, args.het, args.noise_std)
        return cobjs, obj.sinquad_query, obj.sinquad_global_value, args.dim
    if args.objective == "attack":
        cobjs, _ = mobj.make_attack_objective(key, args.clients, p_shared=args.p_shared)
        return cobjs, mobj.attack_query, mobj.attack_global_value, cobjs.z.shape[-1]
    if args.objective == "metric":
        cobjs, d = mobj.make_metric_objective(key, args.clients, p_shared=args.p_shared)
        return cobjs, mobj.metric_query, mobj.metric_global_value, d
    if args.objective == "lm":
        cfg = get_config(args.arch.replace("-", "_"), "smoke")
        from repro.models.model import init_train_state

        params, _ = init_train_state(key, cfg)
        cobjs = mobj.make_lm_objective(key, cfg, args.clients)
        query, global_value, d, _ = mobj.make_lm_query(cfg, params)
        return cobjs, query, global_value, d
    raise ValueError(args.objective)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--objective", default="quadratic",
                    choices=["quadratic", "sinquad", "attack", "metric", "lm"])
    ap.add_argument("--arch", default="qwen1_5_0_5b",
                    choices=[a.replace("_", "-") for a in ARCH_IDS] + list(ARCH_IDS))
    ap.add_argument("--dim", type=int, default=300)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--het", type=float, default=5.0, help="C for synthetic objectives")
    ap.add_argument("--p-shared", type=float, default=0.5, help="P for attack/metric")
    ap.add_argument("--noise-std", type=float, default=0.001)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true",
                    help="shard clients over the local device mesh via shard_map")
    common.add_algo_flags(ap)  # the shared AlgoConfig flag surface
    common.add_engine_flags(ap)  # --chunk / --ckpt-dir / --eval-every / pool
    args = ap.parse_args()
    enable_compile_cache()

    pool_size, cohort = common.pool_from_args(args)
    if pool_size is not None:
        # The pool IS the population: objectives and AlgoConfig are built
        # for N clients; only the K-client cohort ever touches the mesh.
        args.clients = pool_size

    key = jax.random.PRNGKey(args.seed)
    kobj, krun = jax.random.split(key)
    cobjs, query, global_value, dim = build_objective(args, kobj)
    print(f"objective={args.objective} dim={dim} clients={args.clients} algo={args.algo}"
          + (f" cohort={cohort}" if cohort is not None else ""))

    cfg = common.config_from_args(args, dim=dim, n_clients=args.clients)
    print(f"queries/round/client = {cfg.queries_per_round()}  "
          f"uplink floats/round/client = {cfg.comm_floats_per_round()}")
    faults = common.faults_from_args(args)
    if faults is not None:
        print(f"faults: {faults}")

    t0 = time.time()
    ckpt = args.ckpt_dir or None
    if args.distributed:
        mesh = make_host_mesh()
        res = run_distributed(cfg, mesh, krun, cobjs, query, global_value,
                              args.rounds, chunk=args.chunk, checkpoint_dir=ckpt,
                              checkpoint_every=args.ckpt_every,
                              eval_every=args.eval_every,
                              async_checkpoint=not args.sync_ckpt,
                              faults=faults, max_rollbacks=args.max_rollbacks,
                              cohort=cohort, cohort_seed=args.cohort_seed)
    else:
        res = alg.simulate(cfg, krun, cobjs, query, global_value, args.rounds,
                           chunk=args.chunk, checkpoint_dir=ckpt,
                           checkpoint_every=args.ckpt_every,
                           eval_every=args.eval_every,
                           async_checkpoint=not args.sync_ckpt,
                           faults=faults, max_rollbacks=args.max_rollbacks,
                           cohort=cohort, cohort_seed=args.cohort_seed)
    dt = time.time() - t0

    if jax.process_index() != 0:
        return  # one progress table per job, not one per host

    f = res.f_values
    best = float(jnp.nanmin(f))  # eval-every leaves NaN rows for skipped rounds
    print(f"F(x_0) = {float(f[0]):+.5f}   F(x_R) = {float(f[-1]):+.5f}   "
          f"best = {best:+.5f}   ({dt:.1f}s, "
          f"{args.rounds / max(dt, 1e-9):.1f} rounds/s)")
    if faults is not None:
        print(f"mean drop_rate = {float(jnp.mean(res.drop_rate)):.3f}   "
              f"mean quarantine_rate = {float(jnp.mean(res.quarantine_rate)):.3f}")
    stride = max(args.rounds // 10, 1)
    shown = sorted(set(range(0, args.rounds + 1, stride)) | {args.rounds})
    for r in shown:
        q = int(res.queries[r - 1]) if r > 0 else 0
        print(f"  round {r:4d}  F = {float(f[r]):+.5f}  queries/client = {q}")


if __name__ == "__main__":
    main()
