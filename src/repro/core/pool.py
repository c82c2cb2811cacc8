"""Partial-participation client pool (DESIGN.md Sec. 9).

The paper's federated ZOO setting (and the client-sampling regime of
Fang et al., arXiv 2201.09531) assumes only a cohort of K << N clients
participates each round, but the scan engine runs a dense stacked
``ClientState`` of ALL clients: N is capped by mesh memory and the psum
mean divides by a static ``cfg.n_clients``, which is simply wrong under
partial participation.  This module supplies the population half:

  * ``ClientPool`` -- a HOST-resident store of the N pooled client states
    (stacked numpy leaves, leading axis N).  Only the active cohort ever
    touches the mesh, so the pool size is bounded by host memory, not HBM,
    and N need not divide the client shard count (only K must).
  * ``sample_cohort`` -- a deterministic PRNG cohort sampler keyed
    ``fold_in(PRNGKey(seed), round)`` (the same discipline as
    ``faults/injector.py``): pure in (seed, round, N, K), independent of
    topology, chunk length, and resume point.  ``K == N`` short-circuits to
    the identity so the pooled engine is BITWISE the dense engine (the
    equivalence oracle the tests pin).
  * ``run_pooled_rounds`` -- the pooled driver: at every chunk boundary it
    samples a cohort, gathers those K states (and their objectives) onto
    the mesh, runs the EXISTING scanned chunk engine over the cohort, and
    scatters the updated state back to the pool.  Aggregation inside the
    round body is participation-weighted: the cohort body always runs the
    fault engine's masked ``sum_fn`` path (a zero-rate ``FaultConfig`` when
    the caller injects no faults), so the denominator is the LIVE cohort
    count -- never the dense ``n_clients`` mean -- and dropped/quarantined
    cohort members are masked out of the aggregate exactly as in the dense
    faulted engine.  One chunk executable keyed on K serves every cohort
    (same shapes/dtypes/shardings each gather -- asserted recompile-free by
    the tests via ``analysis.no_recompiles``).

Checkpointing reuses the per-shard ``step_<N>/shard_<p>`` layout
(``checkpoint/io.prepare_pool_state``): each process persists its own row
range of the host pool plus the replicated history, with the same
atomic-rename, per-leaf checksum, and corrupt-step-fallback story as
round-state checkpoints.  Fault rollback restores {pool, history} from the
newest good step and replays the lost chunks; the cohort schedule is keyed
on the absolute round, so a rolled-back or resumed run re-draws the SAME
cohorts and matches an uninterrupted one bitwise (tested).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import io as ckpt_io
from repro.core import algorithms as alg
from repro.core import federated as fed
from repro.core import rff as rfflib
from repro.core import rounds as rounds_mod
from repro.faults.injector import FaultConfig, effective_config


# ---------------------------------------------------------------------------
# Cohort sampling
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(1,))
def _perm(key: jax.Array, n: int) -> jax.Array:
    return jax.random.permutation(key, n)


def sample_cohort(seed: int, round_idx: int, pool_size: int, cohort: int) -> np.ndarray:
    """Deterministic cohort for the gather at absolute round ``round_idx``.

    Keyed ``fold_in(PRNGKey(seed), round_idx)`` -- the injector's keying
    discipline -- so the schedule is a pure function of (seed, round, N, K):
    the same cohorts are drawn under vmap and shard_map, after a resume, and
    after a rollback replay.  Returns SORTED global indices (pool order ==
    batch order, so the gathered cohort aggregates in a stable order).
    ``cohort == pool_size`` returns the identity arrangement: the pooled
    engine then IS the dense engine (the bitwise equivalence oracle).
    """
    if not 1 <= cohort <= pool_size:
        raise ValueError(
            f"cohort={cohort} must be in [1, pool_size={pool_size}]"
        )
    if cohort == pool_size:
        return np.arange(pool_size, dtype=np.int64)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(round_idx))
    perm = np.asarray(jax.device_get(_perm(key, pool_size)))
    return np.sort(perm[:cohort]).astype(np.int64)


# ---------------------------------------------------------------------------
# The pool store
# ---------------------------------------------------------------------------


class ClientPool:
    """Host-resident store of N stacked client states.

    Leaves are writable numpy arrays with leading axis N (this process's
    rows); ``gather`` lifts a cohort's rows onto the device/mesh and
    ``scatter`` writes updated cohort state back.  The round trip is
    bitwise: numpy advanced indexing copies values unchanged, so a
    gather-scatter of untouched rows is a no-op.
    """

    def __init__(self, leaves: list[np.ndarray], treedef, row_start: int = 0,
                 global_rows: Optional[int] = None) -> None:
        if not leaves:
            raise ValueError("ClientPool requires at least one state leaf")
        self._leaves = leaves
        self._treedef = treedef
        self.row_start = int(row_start)
        self.global_rows = int(global_rows if global_rows is not None
                               else leaves[0].shape[0])

    @property
    def size(self) -> int:
        return self.global_rows

    @property
    def leaves(self) -> list[np.ndarray]:
        return self._leaves

    @property
    def treedef_str(self) -> str:
        return str(self._treedef)

    @classmethod
    def from_states(cls, states: alg.ClientState) -> "ClientPool":
        """Pool a stacked ``ClientState`` (device or host) by value."""
        leaves, treedef = jax.tree_util.tree_flatten(states)
        host = [np.array(jax.device_get(leaf)) for leaf in leaves]
        return cls(host, treedef)

    def load_leaves(self, leaves: list[np.ndarray]) -> None:
        """Replace the pool contents (checkpoint restore path)."""
        if len(leaves) != len(self._leaves):
            raise ValueError(
                f"pool has {len(self._leaves)} leaves, got {len(leaves)}"
            )
        for i, (old, new) in enumerate(zip(self._leaves, leaves)):
            if old.shape != new.shape or old.dtype != new.dtype:
                raise ValueError(
                    f"pool leaf {i}: cannot load {new.shape}/{new.dtype} over "
                    f"{old.shape}/{old.dtype}"
                )
        self._leaves = [np.array(leaf) for leaf in leaves]

    def gather(self, idx: np.ndarray, mesh: Optional[Mesh] = None) -> alg.ClientState:
        """Lift the cohort rows ``idx`` onto the device (sharded on a mesh).

        Every gather produces arrays of the same (K, ...) shapes, dtypes and
        shardings, so one compiled chunk executable serves every cohort."""
        idx = np.asarray(idx)
        cohort = [jnp.asarray(leaf[idx]) for leaf in self._leaves]
        states = jax.tree_util.tree_unflatten(self._treedef, cohort)
        if mesh is not None:
            states = fed.shard_clients(mesh, states)
        return states

    def scatter(self, idx: np.ndarray, states: alg.ClientState) -> None:
        """Write updated cohort state back into rows ``idx``."""
        idx = np.asarray(idx)
        leaves, treedef = jax.tree_util.tree_flatten(states)
        if str(treedef) != str(self._treedef):
            raise ValueError(
                "scatter: cohort state structure does not match the pool "
                f"({treedef} vs {self._treedef})"
            )
        for i, (dst, src) in enumerate(zip(self._leaves, leaves)):
            arr = np.asarray(jax.device_get(src))
            if arr.shape[1:] != dst.shape[1:] or arr.dtype != dst.dtype:
                raise ValueError(
                    f"scatter: leaf {i} is {arr.shape[1:]}/{arr.dtype}, pool "
                    f"holds {dst.shape[1:]}/{dst.dtype}"
                )
            dst[idx] = arr


def init_pool(cfg: alg.AlgoConfig, key: jax.Array, x0: jax.Array,
              batch: Optional[int] = None) -> ClientPool:
    """Initialize an N-client pool on the host.

    ``batch=None`` initializes all N clients in one vmap -- bitwise
    identical to ``alg.init_states`` (the dense engine's init).  A smaller
    ``batch`` bounds the device footprint of initialization to ``batch``
    clients at a time (the point of pooling: N never has to fit on the
    mesh), at the cost of per-slice vmap dispatches.
    """
    n = cfg.n_clients
    if batch is None:
        batch = n
    if batch < 1:
        raise ValueError(f"batch={batch} must be >= 1")
    keys = jax.random.split(key, n)
    leaves: Optional[list[np.ndarray]] = None
    treedef = None
    for lo in range(0, n, batch):
        hi = min(lo + batch, n)
        ids = jnp.arange(lo, hi, dtype=jnp.int32)
        block = jax.vmap(lambda k, i: alg.init_client_state(cfg, k, x0, i))(
            keys[lo:hi], ids
        )
        flat, treedef = jax.tree_util.tree_flatten(block)
        host = [np.asarray(jax.device_get(a)) for a in flat]
        if leaves is None:
            leaves = [np.empty((n,) + h.shape[1:], h.dtype) for h in host]
        for dst, h in zip(leaves, host):
            dst[lo:hi] = h
    return ClientPool(leaves, treedef)


# ---------------------------------------------------------------------------
# The pooled round driver
# ---------------------------------------------------------------------------


def _gather_cobjs(cobjs_host, idx: np.ndarray, n: int, mesh: Optional[Mesh]):
    """Cohort rows of the stacked per-client objectives."""
    idx = np.asarray(idx)

    def one(a: np.ndarray):
        if a.shape[0] != n:
            raise ValueError(
                f"cobjs leaf has leading axis {a.shape[0]}, expected the "
                f"pool size {n} (per-client objectives must stack over N)"
            )
        return jnp.asarray(a[idx])

    cohort = jax.tree_util.tree_map(one, cobjs_host)
    if mesh is not None:
        cohort = fed.shard_clients(mesh, cohort)
    return cohort


def _restore_newest_good_pool(checkpoint_dir: str, run_meta: dict, rounds: int,
                              x0: jax.Array, pool: ClientPool):
    """Pool analogue of ``rounds._restore_newest_good``: newest COMPLETE,
    uncorrupted pool checkpoint, falling back past corrupt steps; a step
    from a different run identity raises."""
    for step in sorted(ckpt_io.list_steps(checkpoint_dir), reverse=True):
        try:
            saved = (ckpt_io.load_meta(checkpoint_dir, step).get("extra") or {})
        except (OSError, ValueError) as e:
            print(f"[repro.pool] checkpoint step {step}: unreadable meta "
                  f"({e}); trying an older step")
            continue
        for field in ("rounds", "cfg", "eval_every", "faults",
                      "pool_size", "cohort", "cohort_seed"):
            if saved.get(field) not in (None, run_meta[field]):
                raise ValueError(
                    f"checkpoint_dir {checkpoint_dir!r} holds a run with "
                    f"{field}={saved[field]!r}, cannot resume it with "
                    f"{field}={run_meta[field]!r}; point at a fresh directory"
                )
        hist_like = rounds_mod.history_init(rounds, x0, jnp.zeros((), jnp.float32))
        try:
            leaves, hist, start = ckpt_io.restore_pool_state(
                checkpoint_dir, pool.leaves, hist_like, step=step
            )
        except (ckpt_io.CorruptCheckpointError, OSError) as e:
            print(f"[repro.pool] checkpoint step {step}: corrupt "
                  f"({e}); trying an older step")
            continue
        return leaves, hist, min(start, rounds)
    return None, None, 0


def run_pooled_rounds(
    cfg: alg.AlgoConfig,
    rff: Optional[rfflib.RFFParams],
    query_fn: alg.QueryFn,
    cobjs,
    pool: ClientPool,
    x0: jax.Array,
    global_value_fn: rounds_mod.GlobalValueFn,
    rounds: int,
    chunk: int,
    *,
    cohort: int,
    cohort_seed: int = 0,
    mesh: Optional[Mesh] = None,
    diag_global_grad=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = True,
    eval_every: int = 1,
    async_checkpoint: bool = True,
    faults=None,  # Optional[faults.FaultConfig]
    max_rollbacks: int = 3,
) -> tuple[ClientPool, alg.SimResult]:
    """Run ``rounds`` communication rounds with K-of-N partial participation.

    The driver is ``rounds.run_rounds`` with a gather/scatter boundary: at
    each chunk boundary a fresh cohort is sampled (``sample_cohort``, keyed
    on the absolute round of the gather), its K states and objectives are
    lifted onto the mesh, the scanned chunk engine runs over them, and the
    updated state is scattered back to the host pool.  Between boundaries
    the device never holds more than K client states -- the mesh footprint
    of a DENSE K-client run -- so the pool size N is a host-memory number.

    Aggregation is participation-weighted: the cohort round body always
    takes the fault engine's masked ``sum_fn`` path, renormalizing by the
    LIVE cohort count (``faults=None`` runs a zero-rate tolerant config, so
    all K members are live and the result is bitwise the dense mean -- the
    faults-off identity the fault suite pins).  With real ``faults``,
    dropped/poisoned cohort members are masked out of the aggregate and
    quarantined members are re-admitted at the boundary BEFORE their state
    scatters back, so a client never re-enters the pool quarantined.

    ``global_value_fn`` inside the scan sees the COHORT's objectives: under
    partial participation the reported F(x_r) curve is the standard cohort
    estimate of the global objective (exact when K = N; the initial f(x_0)
    entry is evaluated on the full pool).

    Checkpointing, resume, corrupt-step fallback and fault rollback follow
    the ``run_rounds`` contract, persisting {pool, history} in the pool
    per-shard layout.  Returns ``(pool, history)``.
    """
    if rounds < 0:
        raise ValueError(f"rounds must be >= 0, got {rounds}")
    if chunk < 1:
        raise ValueError("run_pooled_rounds requires chunk >= 1 (the pooled "
                         "engine has no Python-loop oracle; the dense engine "
                         "at K = N is the oracle)")
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if cfg.n_clients != pool.size:
        raise ValueError(
            f"cfg.n_clients={cfg.n_clients} must equal the pool size "
            f"{pool.size} (the pool IS the client population)"
        )
    if not 1 <= cohort <= pool.size:
        raise ValueError(
            f"cohort={cohort} must be in [1, pool_size={pool.size}]"
        )
    if mesh is not None and diag_global_grad is not None:
        raise ValueError("diag_global_grad is only supported on the vmap path "
                         "(mesh=None)")
    if jax.process_count() > 1:
        raise NotImplementedError(
            "run_pooled_rounds is single-process for now: multi-process pools "
            "need per-process row ownership for gather/scatter (see ROADMAP)"
        )
    chunk = min(chunk, max(rounds, 1))
    x0 = jnp.asarray(x0)

    # The config the COHORT engine compiles against: the round body sees K
    # clients, so the masked aggregation's rates and the shard-divisibility
    # contract (K % n_shards == 0) are all relative to the cohort.
    ccfg = dataclasses.replace(cfg, n_clients=cohort)
    ufcfg = effective_config(faults, rounds)  # user faults (None if never active)
    # The body ALWAYS runs the masked sum_fn path: zero-rate + tolerate when
    # the caller injects nothing, so the denominator is the live cohort
    # count, never the dense n_clients mean.
    bcfg = ufcfg if ufcfg is not None else FaultConfig()

    run_meta = {"rounds": rounds, "chunk": chunk, "cfg": repr(cfg),
                "eval_every": eval_every, "faults": repr(ufcfg),
                "pool_size": pool.size, "cohort": cohort,
                "cohort_seed": cohort_seed}
    # Objectives are gathered per cohort from host copies, like the states.
    cobjs_host = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)), cobjs
    )

    start, hist = 0, None
    if checkpoint_dir and resume and ckpt_io.latest_step(checkpoint_dir) is not None:
        r_leaves, r_hist, start = _restore_newest_good_pool(
            checkpoint_dir, run_meta, rounds, x0, pool
        )
        if r_hist is not None:
            pool.load_leaves(r_leaves)
            hist = r_hist
    if hist is None:
        hist = rounds_mod.history_init(rounds, x0, global_value_fn(cobjs, x0))

    sx = hist.xs[start]
    steps: dict[tuple, Any] = {}

    def step_for(k: int, body_cfg):
        skey = (k, body_cfg)
        if skey not in steps:
            if mesh is None:
                cf = rounds_mod.sim_chunk_fn(
                    ccfg, query_fn, global_value_fn, diag_global_grad,
                    k, eval_every, rounds, faults=body_cfg,
                )
            else:
                cf = rounds_mod.dist_chunk_fn(
                    ccfg, mesh, query_fn, global_value_fn,
                    k, eval_every, rounds, faults=body_cfg,
                )
            steps[skey] = rounds_mod.make_chunk_step(cf)
        return steps[skey]

    writer = (
        ckpt_io.AsyncCheckpointWriter()
        if (checkpoint_dir and async_checkpoint)
        else None
    )

    def snapshot():
        return ckpt_io.prepare_pool_state(
            pool.leaves, pool.treedef_str, pool.row_start, pool.size, hist
        )

    if ufcfg is not None and checkpoint_dir and ckpt_io.latest_step(checkpoint_dir) is None:
        # Rollback insurance: a restore target exists BEFORE the first
        # faulted chunk runs (one blocking write per fresh directory).
        ckpt_io.write_round_state(checkpoint_dir, start, snapshot(),
                                  extra_meta=run_meta)

    done, chunks_done, rollbacks = start, 0, 0
    try:
        while done < rounds:
            k = min(chunk, rounds - done)
            idx = sample_cohort(cohort_seed, done, pool.size, cohort)
            cstates = pool.gather(idx, mesh=mesh)
            c_cobjs = _gather_cobjs(cobjs_host, idx, pool.size, mesh)
            cstates, hist, sx = step_for(k, bcfg)(
                cstates, hist, c_cobjs, sx, jnp.asarray(done, jnp.int32), rff
            )
            done += k
            chunks_done += 1
            cstates = rounds_mod.boundary_repair_on_device(cstates, ccfg, mesh=mesh)
            if ufcfg is not None and bcfg.tolerate:
                # Re-admit quarantined cohort members BEFORE they scatter
                # back: a client never re-enters the pool quarantined.
                cstates = rounds_mod.boundary_quarantine_reset(
                    cstates, ccfg, sx, mesh=mesh
                )
            ok = True
            if ufcfg is not None:
                ok = bool(np.isfinite(np.asarray(jax.device_get(sx))).all())
            if ok:
                pool.scatter(idx, cstates)
            wrote_ok = True
            if ok and checkpoint_dir and (
                chunks_done % max(checkpoint_every, 1) == 0 or done == rounds
            ):
                payload = snapshot()
                try:
                    if writer is not None:
                        writer.submit(partial(
                            ckpt_io.write_round_state, checkpoint_dir, done,
                            payload, run_meta,
                        ))
                        if done >= rounds:
                            # Final boundary: drain now so a failed last
                            # write rolls back (see rounds.run_rounds).
                            writer.wait()
                    else:
                        ckpt_io.write_round_state(checkpoint_dir, done, payload,
                                                  extra_meta=run_meta)
                except OSError as e:
                    if ufcfg is None:
                        raise
                    print(f"[repro.pool] checkpoint write failed at round "
                          f"{done}: {e}")
                    wrote_ok = False
            if ufcfg is not None and (not ok or not wrote_ok):
                reason = ("non-finite server iterate" if not ok
                          else "checkpoint write failure")
                if not checkpoint_dir:
                    raise FloatingPointError(
                        f"{reason} at round {done} with no checkpoint_dir to "
                        "roll back to (chunk rollback needs checkpointing)"
                    )
                if rollbacks >= max_rollbacks:
                    raise FloatingPointError(
                        f"{reason} at round {done}: rollback budget "
                        f"max_rollbacks={max_rollbacks} exhausted"
                    )
                rollbacks += 1
                if writer is not None:
                    try:
                        writer.wait()
                    except OSError:
                        pass  # the failed write IS the fault being rolled back
                print(f"[repro.pool] ROLLBACK {rollbacks}/{max_rollbacks} at "
                      f"round {done} ({reason}): restoring last good checkpoint")
                r_leaves, r_hist, r_start = _restore_newest_good_pool(
                    checkpoint_dir, run_meta, rounds, x0, pool
                )
                if r_hist is None:
                    raise FloatingPointError(
                        f"rollback at round {done} failed: no restorable "
                        f"checkpoint under {checkpoint_dir!r}"
                    )
                pool.load_leaves(r_leaves)
                hist, done = r_hist, r_start
                sx = hist.xs[done]
                if not bcfg.tolerate:
                    print("[repro.pool] re-running with fault tolerance "
                          "FORCED ON")
                    bcfg = dataclasses.replace(bcfg, tolerate=True)
                chunks_done = 0
    finally:
        if writer is not None:
            writer.wait()

    return pool, hist
