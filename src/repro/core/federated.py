"""Distributed federated-ZOO engine: clients sharded over a device mesh.

The paper runs N clients as separate processes with a central server.  On a
TPU pod we map clients onto the mesh's ``data`` axis (and the ``pod`` axis in
multi-pod mode) with ``shard_map``:

  * each device hosts ``N / n_devices`` clients (an inner vmap),
  * the T local updates are collective-free by construction,
  * the server aggregation of the iterate x and the RFF weight vector w is a
    single ``psum`` over the client axes -- exactly the paper's one (or two,
    with round-end active queries / SCAFFOLD-I) transmissions per round.

Because the aggregation is the ONLY cross-device communication, the HLO of
one round makes the paper's communication-efficiency claim *inspectable*:
the all-reduce payload is ``d + M`` floats per round for FZooS vs ``d`` (plus
control variates) for the baselines, and the dry-run (launch/dryrun.py)
accounts those bytes in the roofline's collective term.

The per-client Gram-factor cache (``gp_surrogate.GramFactor``, three
(cap, cap) buffers riding in ``ClientState``) is DEVICE-LOCAL state: it
shards over the client axes with the rest of the state pytree and never
enters a collective -- ``shard_clients``/``distributed_round_fn`` treat it
like the trajectory ring buffer it summarizes.  At the default cap=128 that
is ~0.2 MB per client, so thousands of clients per device fit in HBM before
the trajectory itself becomes the constraint.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import algorithms as alg
from repro.core import rff as rfflib

Pytree = Any


def client_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that enumerate clients (everything except 'model')."""
    return tuple(n for n in mesh.axis_names if n != "model")


def _psum_mean(tree: Pytree, axes: tuple[str, ...], n_clients: int) -> Pytree:
    """Global mean over all clients: local sum -> psum over client axes -> /N."""

    def one(a):
        s = jnp.sum(a, axis=0)
        s = jax.lax.psum(s, axes)
        return s / n_clients

    return jax.tree_util.tree_map(one, tree)


def client_mean_fn(cfg: alg.AlgoConfig, mesh: Mesh):
    """(client axes, psum-mean aggregation fn) with the shard contract
    enforced: N clients must divide the product of the client mesh axes
    (equal-size shards are what makes mean-of-shard-means the global mean).
    """
    axes = client_axes(mesh)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if cfg.n_clients % n_shards:
        raise ValueError(f"n_clients={cfg.n_clients} not divisible by client shards {n_shards}")
    return axes, partial(_psum_mean, axes=axes, n_clients=cfg.n_clients)


def client_sum_fn(mesh: Mesh):
    """Un-normalized global sum over all clients of ONE array: local axis-0
    sum -> psum over the client axes.  The aggregation primitive the
    fault-masked engine renormalizes by its own live count (the mask count
    rides inside the summed payload, so masking adds no extra psum)."""
    axes = client_axes(mesh)

    def one(a: jax.Array) -> jax.Array:
        return jax.lax.psum(jnp.sum(a, axis=0), axes)

    return one


def distributed_round_fn(
    cfg: alg.AlgoConfig,
    mesh: Mesh,
    rff: Optional[rfflib.RFFParams],
    query_fn: alg.QueryFn,
    faults=None,  # Optional[faults.FaultConfig]
):
    """Build a jitted one-round function with clients sharded over the mesh.

    Inputs (states, cobjs) are stacked over N clients; N must divide the
    product of the client mesh axes times 1-or-more clients per device.
    With ``faults`` the returned function takes an extra traced round-index
    argument: ``round_fn(states, cobjs, server_x, round_idx)``.
    """
    axes, mean_fn = client_mean_fn(cfg, mesh)
    sum_fn = client_sum_fn(mesh)

    cspec = P(axes)  # shard the client axis over all client mesh axes
    rspec = P()  # replicated

    if faults is None:
        def round_body(states, cobjs, server_x):
            new_states, stats = alg.run_round(
                cfg, rff, query_fn, cobjs, states, server_x, mean_fn, None
            )
            return new_states, stats

        in_specs = (cspec, cspec, rspec)
    else:
        def round_body(states, cobjs, server_x, round_idx):
            new_states, stats = alg.run_round(
                cfg, rff, query_fn, cobjs, states, server_x, mean_fn, None,
                sum_fn=sum_fn, faults=faults, round_idx=round_idx,
            )
            return new_states, stats

        in_specs = (cspec, cspec, rspec, rspec)

    shmapped = jax.shard_map(
        round_body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(cspec, rspec),
        check_vma=False,
    )
    return jax.jit(shmapped)


def shard_clients(mesh: Mesh, tree: Pytree) -> Pytree:
    """Place a client-stacked pytree with the client axis sharded on the mesh."""
    axes = client_axes(mesh)
    sh = NamedSharding(mesh, P(axes))

    def put(a):
        return jax.device_put(a, sh)

    return jax.tree_util.tree_map(put, tree)


def run_distributed(
    cfg: alg.AlgoConfig,
    mesh: Mesh,
    key: jax.Array,
    cobjs,
    query_fn: alg.QueryFn,
    global_value_fn: Callable[[Any, jax.Array], jax.Array],
    rounds: int,
    x0: Optional[jax.Array] = None,
    chunk: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    eval_every: int = 1,
    async_checkpoint: bool = True,
    faults=None,  # Optional[faults.FaultConfig]
    max_rollbacks: int = 3,
    cohort: Optional[int] = None,
    cohort_seed: int = 0,
) -> alg.SimResult:
    """Distributed analogue of algorithms.simulate (same history contract).

    ``chunk`` selects the round driver exactly as in ``simulate``: ``None``
    scans ``rounds.DEFAULT_CHUNK``-round chunks INSIDE shard_map (one
    dispatch per chunk, the per-round psum stays the only collective),
    ``chunk=k>0`` sets the chunk length, ``chunk=0`` keeps the seed
    one-dispatch-per-round Python loop as the equivalence oracle.
    ``eval_every`` follows the ``simulate`` contract (skipped ``f_values``
    rows hold NaN).  Checkpoints on this path use the PER-SHARD layout
    (checkpoint/io.py): each process writes only its addressable slice of
    the client-sharded state, the chunk-boundary repair decision stays on
    device, and with ``async_checkpoint`` the file write overlaps the next
    chunk -- the steady-state boundary performs zero host syncs.

    ``cohort=K`` selects PARTIAL PARTICIPATION (core/pool.py): the full
    N-client population lives in a host-resident pool -- never sharded onto
    the mesh -- and each chunk a deterministic cohort of K clients is
    gathered onto the mesh, scanned, and scattered back.  Only K must
    divide the client shard count; N is a host-memory number.
    """
    if chunk is not None and chunk < 0:
        raise ValueError(f"chunk must be None, 0 (loop oracle) or positive, got {chunk}")
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if x0 is None:
        x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    k_init, k_rff = jax.random.split(key)
    rff = None
    if cfg.is_fzoos:
        rff = rfflib.make_rff(k_rff, cfg.n_features, cfg.dim, cfg.lengthscale)

    if cohort is not None:
        if chunk == 0:
            raise ValueError("cohort (partial participation) requires the "
                             "scan driver (chunk != 0); the dense engine at "
                             "cohort == n_clients is the equivalence oracle")
        from repro.core import pool as pool_mod  # deferred: avoids cycle
        from repro.core import rounds as rounds_mod

        pool = pool_mod.init_pool(cfg, k_init, x0)
        _, res = pool_mod.run_pooled_rounds(
            cfg, rff, query_fn, cobjs, pool, x0, global_value_fn,
            rounds, chunk if chunk is not None else rounds_mod.DEFAULT_CHUNK,
            cohort=cohort, cohort_seed=cohort_seed, mesh=mesh,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            eval_every=eval_every, async_checkpoint=async_checkpoint,
            faults=faults, max_rollbacks=max_rollbacks,
        )
        return res

    states = alg.init_states(cfg, k_init, x0)
    states = shard_clients(mesh, states)
    cobjs = shard_clients(mesh, cobjs)

    if chunk is None or chunk > 0:
        from repro.core import rounds as rounds_mod  # deferred: avoids cycle

        if chunk is None:
            chunk = rounds_mod.DEFAULT_CHUNK
        _, res = rounds_mod.run_rounds(
            cfg, rff, query_fn, cobjs, states, x0, global_value_fn,
            rounds, chunk, mesh=mesh,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            eval_every=eval_every, async_checkpoint=async_checkpoint,
            faults=faults, max_rollbacks=max_rollbacks,
        )
        return res

    if checkpoint_dir:
        raise ValueError("checkpoint_dir requires the scan driver (chunk != 0)")
    from repro.core import rounds as rounds_mod  # deferred: avoids cycle

    if faults is not None:
        # Loop oracle matches the scan engine: a never-active window runs
        # the faults-free body (see rounds.run_rounds).
        from repro.faults.injector import effective_config
        faults = effective_config(faults, rounds)
    round_fn = distributed_round_fn(cfg, mesh, rff, query_fn, faults=faults)

    xs = [x0]
    fvals = [global_value_fn(cobjs, x0)]
    queries, coss, disps, rrs, reps = [], [], [], [], []
    drops, quars = [], []
    sx = x0
    for r in range(rounds):
        if faults is None:
            states, stats = round_fn(states, cobjs, sx)
        else:
            states, stats = round_fn(states, cobjs, sx, jnp.asarray(r, jnp.int32))
        if cfg.deferred:
            # Loop-oracle boundary: per-shard masked repair after every round
            # (the chunk=1 degenerate case of the deferred contract).
            states, _ = rounds_mod.repair_flagged_clients(states, cfg, mesh=mesh)
        sx = stats.server_x
        if faults is not None and faults.tolerate:
            states, _ = rounds_mod.quarantine_reset_flagged(
                states, cfg, sx, mesh=mesh
            )
        xs.append(sx)
        r1 = r + 1
        if r1 % eval_every == 0 or r1 == rounds:
            fvals.append(global_value_fn(cobjs, sx))
        else:
            fvals.append(jnp.full((), jnp.nan, jnp.float32))
        queries.append(stats.queries_per_client)
        coss.append(stats.mean_cos)
        disps.append(stats.mean_disparity)
        rrs.append(stats.refactor_rate)
        reps.append(stats.repair_rate)
        drops.append(stats.drop_rate)
        quars.append(stats.quarantine_rate)

    return alg.SimResult(
        xs=jnp.stack(xs),
        f_values=jnp.stack([jnp.asarray(f, jnp.float32) for f in fvals]),
        queries=jnp.stack(queries),
        mean_cos=jnp.stack(coss),
        mean_disparity=jnp.stack(disps),
        refactor_rate=jnp.stack(rrs),
        repair_rate=jnp.stack(reps),
        drop_rate=jnp.stack(drops),
        quarantine_rate=jnp.stack(quars),
    )
