"""On-device multi-round scan engine (DESIGN.md Sec. 3).

PR 1 made one local step ~6x cheaper, which moved the bottleneck up a level:
the seed drivers (`algorithms.simulate`, `federated.run_distributed`) ran a
Python `for` loop that re-dispatched one jitted round per iteration and
synced to host every round to evaluate an un-jitted ``global_value_fn``.
Query-parsimonious federated ZOO wants MANY cheap rounds (FedZeN; the
Hessian-informed FedZOO line), so the round loop itself must stop paying
per-round dispatch + host-roundtrip tax.

This module scans ``run_round`` over K-round *chunks*:

  * one ``lax.scan`` per chunk -> one compile (per chunk length), one
    dispatch per chunk, zero host syncs mid-chunk;
  * the traced and jitted chunk step outlives the front-door call
    (``make_chunk_step``): a later call of the same shape neither traces
    nor lowers it again;
  * ``global_value_fn`` is evaluated INSIDE the scanned body, so the
    F(x_r) curve is produced on device instead of round-tripping x_r;
  * per-round history (server iterates, F values, query counters,
    diagnostics) is written into preallocated on-device arrays with
    ``dynamic_update_slice`` at a traced round offset -- chunk length and
    history length are decoupled, so every full chunk reuses ONE executable;
  * the stacked ``ClientState`` and the history buffers are DONATED to the
    chunk executable, so the engine runs in place: no per-chunk copy of the
    (N, cap, d) trajectory/Gram buffers;
  * at chunk boundaries the engine can checkpoint {states, history} through
    ``checkpoint.io`` and resume from the latest checkpoint, so long
    federated runs survive preemption (the resume contract is
    round-granular: a checkpoint at round r restarts at round r).

Both front doors route here: ``algorithms.simulate`` (clients vmapped) and
``federated.run_distributed`` (clients sharded).  The distributed path scans
INSIDE ``shard_map`` so the per-round ``psum`` aggregation (plus one scalar
``pmean`` for the F curve) remains the only collective traffic; chunk
boundaries add no communication.

``chunk=0`` keeps the seed Python-loop driver in both front doors -- that
path is the equivalence oracle for the tests.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import weakref
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core
from jax.sharding import Mesh, PartitionSpec as P

from repro.checkpoint import io as ckpt_io
from repro.core import algorithms as alg
from repro.core import federated as fed
from repro.core import gp_surrogate as gp
from repro.core import rff as rfflib
from repro.core import telemetry

GlobalValueFn = Callable[[Any, jax.Array], jax.Array]

#: Auto chunk length used when a front door is called with ``chunk=None``.
#: Large enough to amortize dispatch, small enough that a preempted run
#: loses little work and the first result arrives quickly.
DEFAULT_CHUNK = 16


def history_init(rounds: int, x0: jax.Array, f0: jax.Array) -> alg.SimResult:
    """Preallocated on-device per-round history.  The buffers ARE the
    eventual SimResult (same NamedTuple), filled in place chunk by chunk."""
    return alg.SimResult(
        xs=jnp.zeros((rounds + 1, x0.shape[-1]), x0.dtype).at[0].set(x0),
        f_values=jnp.zeros((rounds + 1,), jnp.float32).at[0].set(
            jnp.asarray(f0, jnp.float32)
        ),
        queries=jnp.zeros((rounds,), jnp.float32),
        mean_cos=jnp.zeros((rounds,), jnp.float32),
        mean_disparity=jnp.zeros((rounds,), jnp.float32),
        refactor_rate=jnp.zeros((rounds,), jnp.float32),
        repair_rate=jnp.zeros((rounds,), jnp.float32),
        drop_rate=jnp.zeros((rounds,), jnp.float32),
        quarantine_rate=jnp.zeros((rounds,), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Chunk bodies
# ---------------------------------------------------------------------------


def _round_body(cfg, rff, query_fn, cobjs, mean_fn, eval_fn, diag_global_grad,
                eval_every: int, rounds_total: Optional[int],
                sum_fn=None, faults=None):
    """One scanned round: run_round + on-device F(x_{r+1}) evaluation.

    The scanned xs is the in-chunk round index; the carry holds the traced
    absolute offset so ``eval_every`` gates the (possibly expensive) global
    eval on the ABSOLUTE completed-round count: rows for skipped rounds hold
    NaN, round ``rounds_total`` is always evaluated.  ``lax.cond`` is safe
    here -- the scan carry is unbatched, so the untaken eval is skipped for
    real (that is the whole point for LM-backbone objectives).

    With ``faults`` the fault-masked ``run_round`` path runs instead: the
    traced absolute round index ``offset + i`` keys the deterministic fault
    draws, and ``sum_fn`` supplies the un-normalized payload aggregation the
    mask renormalizes.  ``faults=None`` traces the seed body UNCHANGED (the
    bitwise faults-off guarantee).
    """

    def body(carry, i):
        states, sx, offset = carry
        if faults is None:
            states, stats = alg.run_round(
                cfg, rff, query_fn, cobjs, states, sx, mean_fn, diag_global_grad
            )
        else:
            states, stats = alg.run_round(
                cfg, rff, query_fn, cobjs, states, sx, mean_fn, diag_global_grad,
                sum_fn=sum_fn, faults=faults, round_idx=offset + i,
            )

        def do_eval():
            with telemetry.scope("fzoo.eval"):
                return jnp.asarray(eval_fn(cobjs, stats.server_x), jnp.float32)

        if eval_every == 1:
            f = do_eval()
        else:
            r1 = offset + i + 1  # 1-based absolute completed-round index
            want = r1 % eval_every == 0
            if rounds_total is not None:
                want = want | (r1 == rounds_total)
            f = jax.lax.cond(want, do_eval, lambda: jnp.full((), jnp.nan, jnp.float32))
        ys = (
            stats.server_x,
            f,
            stats.queries_per_client,
            stats.mean_cos,
            stats.mean_disparity,
            stats.refactor_rate,
            stats.repair_rate,
            stats.drop_rate,
            stats.quarantine_rate,
        )
        return (states, stats.server_x, offset), ys

    return body


def sim_chunk_fn(
    cfg: alg.AlgoConfig,
    query_fn: alg.QueryFn,
    global_value_fn: GlobalValueFn,
    diag_global_grad,
    length: int,
    eval_every: int = 1,
    rounds_total: Optional[int] = None,
    faults=None,
):
    """K scanned rounds with clients vmapped (single-process simulation):
    ``chunk(states, cobjs, sx, offset, rff)``.  Carries its ``chunk_key``
    (see ``make_chunk_step``)."""
    mean_fn = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.mean(a, axis=0), tree
    )
    sum_fn = (lambda a: jnp.sum(a, axis=0)) if faults is not None else None

    def chunk(states, cobjs, sx, offset, rff):
        body = _round_body(
            cfg, rff, query_fn, cobjs, mean_fn, global_value_fn, diag_global_grad,
            eval_every, rounds_total, sum_fn=sum_fn, faults=faults,
        )
        (states, sx, _), ys = jax.lax.scan(
            body, (states, sx, offset), jnp.arange(length)
        )
        return states, sx, ys

    chunk.chunk_key = (None, cfg, query_fn, global_value_fn, diag_global_grad,
                       length, eval_every, rounds_total, faults)
    return chunk


def dist_chunk_fn(
    cfg: alg.AlgoConfig,
    mesh: Mesh,
    query_fn: alg.QueryFn,
    global_value_fn: GlobalValueFn,
    length: int,
    eval_every: int = 1,
    rounds_total: Optional[int] = None,
    faults=None,
):
    """K scanned rounds INSIDE shard_map: the per-round psum aggregation
    (plus one scalar pmean for F) stays the only collective.  The faulted
    body packs its live/quarantine counts INTO the psummed payload, so
    masking adds no collective either.  The RFF bank is replicated
    (``P()``); like ``sim_chunk_fn``, the result carries its ``chunk_key``."""
    axes, mean_fn = fed.client_mean_fn(cfg, mesh)
    sum_fn = fed.client_sum_fn(mesh) if faults is not None else None
    cspec, rspec = P(axes), P()

    # Each shard sees an equal-size slice of the stacked cobjs, so the mean
    # of per-shard means IS the global mean F(x).  (The eval-every cond
    # predicate is a pure function of the replicated round offset, so every
    # device takes the same branch and the pmean inside stays matched.)
    def eval_fn(cobjs, x):
        return jax.lax.pmean(global_value_fn(cobjs, x), axes)

    def local_chunk(states, cobjs, sx, offset, rff):
        body = _round_body(cfg, rff, query_fn, cobjs, mean_fn, eval_fn, None,
                           eval_every, rounds_total, sum_fn=sum_fn,
                           faults=faults)
        (states, sx, _), ys = jax.lax.scan(
            body, (states, sx, offset), jnp.arange(length)
        )
        return states, sx, ys

    chunk = jax.shard_map(
        local_chunk,
        mesh=mesh,
        in_specs=(cspec, cspec, rspec, rspec, rspec),
        out_specs=(cspec, rspec, rspec),
        check_vma=False,
    )
    chunk.chunk_key = (mesh, cfg, query_fn, global_value_fn, None, length,
                       eval_every, rounds_total, faults)
    return chunk


def _hist_write(hist: alg.SimResult, ys, offset: jax.Array) -> alg.SimResult:
    """Write a chunk's stacked per-round outputs at round ``offset``."""
    xs_k, f_k, q_k, cos_k, disp_k, rr_k, rep_k, dr_k, qr_k = ys
    dus = jax.lax.dynamic_update_slice
    return alg.SimResult(
        xs=dus(hist.xs, xs_k.astype(hist.xs.dtype), (offset + 1, 0)),
        f_values=dus(hist.f_values, f_k, (offset + 1,)),
        queries=dus(hist.queries, q_k, (offset,)),
        mean_cos=dus(hist.mean_cos, cos_k, (offset,)),
        mean_disparity=dus(hist.mean_disparity, disp_k, (offset,)),
        refactor_rate=dus(hist.refactor_rate, rr_k, (offset,)),
        repair_rate=dus(hist.repair_rate, rep_k, (offset,)),
        drop_rate=dus(hist.drop_rate, dr_k, (offset,)),
        quarantine_rate=dus(hist.quarantine_rate, qr_k, (offset,)),
    )


@dataclasses.dataclass
class _Program:
    """One traced chunk step: its jitted ``run``, the closure constants it
    takes as arguments, and whether it has run yet."""

    run: Callable
    consts: list
    called: bool = False


class ChunkStep:
    """One jitted chunk step: ``step(states, hist, cobjs, sx, offset, rff)``.

    The client states and the history buffers are donated: the engine
    mutates them in place across the whole run.  Everything that differs
    between two calls of one program -- the states, the objectives, the RFF
    bank each front-door call draws -- is an argument of ``chunk_fn``.  The
    arrays it closes over (the weights of a model-backed objective) reach
    the executable as ARGUMENTS too: left in the closure, jit would embed
    them in the program as literals, putting a model's weights into every
    compiled chunk step and compile-cache entry.

    A program is traced once per argument structure, shapes, dtypes and
    default matmul precision.  Its trace runs under the
    ``fzoo.chunk_step.trace`` span and counts in ``telemetry``'s
    ``chunk_traces``; the first call of each new jitted program (lowering,
    compiling or loading the executable from the persistent cache, the first
    enqueue) under ``fzoo.chunk_step.compile``.  ``programs`` shares the
    traced programs of an earlier step of the same chunk function key
    (``make_chunk_step``).
    """

    def __init__(self, chunk_fn, programs: Optional[dict] = None):
        self._chunk_fn = chunk_fn
        self._programs = {} if programs is None else programs

    def _get(self, states, cobjs, sx, offset, rff) -> _Program:
        args = (states, cobjs, sx, offset, rff)
        leaves, tree = jax.tree_util.tree_flatten(args)
        key = (tree, tuple((np.shape(a), str(jnp.result_type(a))) for a in leaves),
               jax.config.jax_default_matmul_precision)
        if key not in self._programs:
            with telemetry.span("fzoo.chunk_step.trace"):
                telemetry.count("chunk_traces")
                closed, out_shape = jax.make_jaxpr(self._chunk_fn, return_shape=True)(*args)
            body, out_tree = closed.jaxpr, jax.tree_util.tree_structure(out_shape)

            @partial(jax.jit, donate_argnums=(1, 2))
            def run(consts, states, hist, cobjs, sx, offset, rff):
                flat = jax.tree_util.tree_leaves((states, cobjs, sx, offset, rff))
                outs = jex_core.jaxpr_as_fun(jex_core.ClosedJaxpr(body, consts))(*flat)
                states, sx, ys = jax.tree_util.tree_unflatten(out_tree, outs)
                return states, _hist_write(hist, ys, offset), sx

            self._programs[key] = _Program(run, list(closed.consts))
        return self._programs[key]

    def __call__(self, states, hist, cobjs, sx, offset, rff):
        prog = self._get(states, cobjs, sx, offset, rff)
        if prog.called:
            return prog.run(prog.consts, states, hist, cobjs, sx, offset, rff)
        with telemetry.span("fzoo.chunk_step.compile"):
            out = prog.run(prog.consts, states, hist, cobjs, sx, offset, rff)
        prog.called = True
        return out

    def lower(self, states, hist, cobjs, sx, offset, rff):
        prog = self._get(states, cobjs, sx, offset, rff)
        return prog.run.lower(prog.consts, states, hist, cobjs, sx, offset, rff)


#: Most chunk function keys whose programs ``make_chunk_step`` keeps.
CHUNK_STEP_CACHE_SIZE = 16

#: The traced programs of each chunk function key, least recently used first.
_CHUNK_PROGRAMS: "collections.OrderedDict[tuple, dict]" = collections.OrderedDict()
_CHUNK_PROGRAMS_LOCK = threading.Lock()


def _weak_key(key: tuple, on_death=None) -> tuple:
    """``key`` with each function in it held by a weak reference, which
    compares by the function's identity while it lives.  A callable that
    cannot be weakly referenced is kept as it is."""
    out = []
    for x in key:
        if callable(x):
            try:
                x = weakref.ref(x, on_death)
            except TypeError:
                pass
        out.append(x)
    return tuple(out)


def make_chunk_step(chunk_fn) -> ChunkStep:
    """Jit one chunk step (see ``ChunkStep``), reusing the programs traced
    for an equal ``chunk_fn.chunk_key``.

    ``sim_chunk_fn`` and ``dist_chunk_fn`` key their chunk functions on
    ``(mesh, cfg, query_fn, global_value_fn, diag_global_grad, length,
    eval_every, rounds_total, faults)``.  The functions in it compare by
    identity, the contract ``jax.jit`` keeps for a function object: one
    function object stands for one computation, so whatever differs between
    two calls of one key is an argument of the chunk function, never a
    closure constant (DESIGN.md Sec. 3).  A hit
    returns a step that shares the earlier step's traced programs and
    counts in ``telemetry``'s ``chunk_step_reuses``; a chunk function
    without a key gets a step of its own.

    The cache holds at most ``CHUNK_STEP_CACHE_SIZE`` keys and only weak
    references to their functions: a key goes when one of its functions is
    garbage, with the programs and the closure constants they hold.
    """
    key = getattr(chunk_fn, "chunk_key", None)
    if key is None:
        return ChunkStep(chunk_fn)
    cache = _CHUNK_PROGRAMS  # bound here: the callback below may run at exit
    wkey = _weak_key(key)
    with _CHUNK_PROGRAMS_LOCK:
        programs = cache.get(wkey)
        if programs is not None:
            telemetry.count("chunk_step_reuses")
            cache.move_to_end(wkey)
        else:
            programs, held = {}, []
            held.append(_weak_key(key, lambda _: cache.pop(held[0], None)))
            cache[held[0]] = programs
            while len(cache) > CHUNK_STEP_CACHE_SIZE:
                cache.popitem(last=False)
    return ChunkStep(chunk_fn, programs)


# ---------------------------------------------------------------------------
# Deferred-repair pass (chunk boundaries; DESIGN.md Sec. 2.6 / 3)
# ---------------------------------------------------------------------------


#: jitted per-(mesh, capacity) shard_map repair executables (rare-event path).
_DIST_REPAIR_CACHE: dict = {}

#: jitted per-(mesh, capacity) DEVICE-decided boundary repair executables.
_DEVICE_REPAIR_CACHE: dict = {}


def boundary_repair_on_device(
    states: alg.ClientState,
    cfg: alg.AlgoConfig,
    mesh: Optional[Mesh] = None,
) -> alg.ClientState:
    """Zero-host-sync chunk boundary: the repair DECISION stays on device.

    One extra (async) dispatch per chunk running
    ``gp.factor_repair_gated`` -- a masked all-client repair under a
    ``lax.cond`` gated on the device-side flag-count scalar -- so the
    steady-state deferred boundary issues NO ``device_get`` of the flag
    vector and the Python driver never stalls the dispatch pipeline.  The
    common all-flags-clear case costs an O(N) reduction; when clients ARE
    flagged the taken branch is the same batched clamped-eigh
    ``factor_repair_masked`` the host-read path runs, so repaired state is
    identical to ``repair_flagged_clients`` (tested).  On a mesh the gate
    runs per shard inside ``shard_map`` (each shard conds on its LOCAL
    count; no collectives).  The factor buffers are donated: the boundary
    runs in place like the chunk step itself.
    """
    if not cfg.deferred:
        return states
    jitter = jnp.maximum(jnp.asarray(cfg.noise, jnp.float32), 1e-4)
    key = (mesh, states.factor.gram.shape)
    if key not in _DEVICE_REPAIR_CACHE:
        if mesh is None:
            fn = jax.jit(gp.factor_repair_gated, donate_argnums=0)
        else:
            axes = fed.client_axes(mesh)
            cspec = P(axes)
            fn = jax.jit(
                jax.shard_map(
                    gp.factor_repair_gated,
                    mesh=mesh,
                    in_specs=(cspec, P()),
                    out_specs=cspec,
                    check_vma=False,
                ),
                donate_argnums=0,
            )
        _DEVICE_REPAIR_CACHE[key] = fn
    return states._replace(factor=_DEVICE_REPAIR_CACHE[key](states.factor, jitter))


def repair_flagged_clients(
    states: alg.ClientState,
    cfg: alg.AlgoConfig,
    mesh: Optional[Mesh] = None,
) -> tuple[alg.ClientState, int]:
    """Repair every client flagged ``needs_repair`` by the deferred engine.

    HOST-read decision path: reads the (N,)-bool flag vector to host and
    returns unchanged states when nothing is flagged (the overwhelmingly
    common case: the flag fires only on genuine f32 indefiniteness, measured
    rate ~0).  Since the zero-sync boundary landed this is the ORACLE used by
    the ``chunk=0`` loop drivers and the tests; the scan driver's steady
    state uses ``boundary_repair_on_device`` instead, which makes the same
    decision on device and therefore costs no sync.  When clients ARE
    flagged:

      * vmap path (``mesh=None``): gather the flagged subset and run ONE
        batched clamped-eigh over exactly those Grams -- the eigh amortizes
        from per-step-per-client to per-chunk-per-flagged-client;
      * distributed path: a jitted ``shard_map`` masked repair over the
        local clients of each shard (flag counts are not static under jit,
        so every local client's Gram enters the batched eigh and only
        flagged ones adopt).  No collectives: the per-round psum stays the
        only communication.

    Returns (states, number of clients repaired).
    """
    if not cfg.deferred:
        return states, 0
    flags = np.asarray(jax.device_get(states.factor.needs_repair))
    n_flagged = int(flags.sum())
    if n_flagged == 0:
        return states, 0
    jitter = jnp.maximum(jnp.asarray(cfg.noise, jnp.float32), 1e-4)

    if mesh is None:
        # Gather the flagged subset, repair it (ONE batched eigh over exactly
        # those Grams -- the same masked primitive the shard path uses, so
        # the repair semantics live in one place), scatter it back.
        idx = jnp.asarray(np.nonzero(flags)[0])
        sub = jax.tree_util.tree_map(lambda a: a[idx], states.factor)
        rep = gp.factor_repair_masked(sub, jitter)
        factor = jax.tree_util.tree_map(
            lambda full, r: full.at[idx].set(r), states.factor, rep
        )
        return states._replace(factor=factor), n_flagged

    key = (mesh, states.factor.gram.shape)
    if key not in _DIST_REPAIR_CACHE:
        axes = fed.client_axes(mesh)
        cspec = P(axes)
        _DIST_REPAIR_CACHE[key] = jax.jit(
            jax.shard_map(
                lambda fac, jit_: gp.factor_repair_masked(fac, jit_),
                mesh=mesh,
                in_specs=(cspec, P()),
                out_specs=cspec,
                check_vma=False,
            )
        )
    factor = _DIST_REPAIR_CACHE[key](states.factor, jitter)
    return states._replace(factor=factor), n_flagged


# ---------------------------------------------------------------------------
# Quarantine reset (fault-tolerant chunk boundaries; DESIGN.md Sec. 8)
# ---------------------------------------------------------------------------


#: jitted per-(mesh, cfg, shape) DEVICE-decided quarantine-reset executables.
_QUARANTINE_RESET_CACHE: dict = {}


def _quarantine_reset_exec(cfg: alg.AlgoConfig, mesh: Optional[Mesh], shape):
    key = (mesh, repr(cfg), shape)
    if key not in _QUARANTINE_RESET_CACHE:
        reset = alg.make_quarantine_reset(cfg)

        def gated(sts, sx):
            n = jnp.sum(sts.quarantined.astype(jnp.int32))
            return jax.lax.cond(n > 0, lambda: reset(sts, sx), lambda: sts)

        if mesh is None:
            fn = jax.jit(gated, donate_argnums=0)
        else:
            axes = fed.client_axes(mesh)
            cspec = P(axes)
            fn = jax.jit(
                jax.shard_map(
                    gated,
                    mesh=mesh,
                    in_specs=(cspec, P()),
                    out_specs=cspec,
                    check_vma=False,
                ),
                donate_argnums=0,
            )
        _QUARANTINE_RESET_CACHE[key] = fn
    return _QUARANTINE_RESET_CACHE[key]


def boundary_quarantine_reset(
    states: alg.ClientState,
    cfg: alg.AlgoConfig,
    server_x: jax.Array,
    mesh: Optional[Mesh] = None,
) -> alg.ClientState:
    """Zero-host-sync chunk boundary: re-admit quarantined clients ON DEVICE.

    The fault-tolerant sibling of ``boundary_repair_on_device``: one extra
    (async) dispatch per chunk that ``lax.cond``s on the device-side
    quarantine count and, when any client is quarantined, rebuilds those
    clients from the current global iterate (``alg.make_quarantine_reset``;
    the reset template is computed eagerly at build time so no init-time
    linear algebra enters the compiled gate).  The common all-clear case
    costs an O(N) reduction; no flag vector is read to host, no collectives
    are issued (each shard conds on its LOCAL count), and the stacked state
    is donated so the boundary runs in place.
    """
    fn = _quarantine_reset_exec(cfg, mesh, states.x.shape)
    return fn(states, jnp.asarray(server_x))


def quarantine_reset_flagged(
    states: alg.ClientState,
    cfg: alg.AlgoConfig,
    server_x: jax.Array,
    mesh: Optional[Mesh] = None,
) -> tuple[alg.ClientState, int]:
    """Host-read quarantine reset: the ``chunk=0`` loop-driver ORACLE.

    Reads the (N,)-bool quarantine flags to host and returns unchanged
    states when nothing is flagged, exactly like ``repair_flagged_clients``;
    when clients ARE quarantined it runs the same gated executable as
    ``boundary_quarantine_reset``, so the reset semantics live in one place
    and the oracle/steady-state equivalence is tested.  Returns
    (states, number of clients re-admitted).
    """
    flags = np.asarray(jax.device_get(states.quarantined))
    n_flagged = int(flags.sum())
    if n_flagged == 0:
        return states, 0
    return boundary_quarantine_reset(states, cfg, server_x, mesh=mesh), n_flagged


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _restore_newest_good(
    checkpoint_dir: str,
    run_meta: dict,
    rounds: int,
    x0: jax.Array,
    states_like: alg.ClientState,
    mesh: Optional[Mesh],
):
    """Restore from the newest COMPLETE, uncorrupted checkpoint step.

    Steps whose meta is unreadable or whose arrays fail the integrity checks
    (truncated zip, checksum mismatch -- ``ckpt_io.CorruptCheckpointError``)
    are skipped with a warning and the next-older step is tried, so a torn
    or bit-flipped newest step degrades to losing one checkpoint interval
    instead of the whole run.  A step from a DIFFERENT run identity still
    raises: silently splicing two experiments is worse than failing.

    Returns ``(states, hist, start)``; ``hist is None`` means nothing
    restorable exists under ``checkpoint_dir``.
    """
    for step in sorted(ckpt_io.list_steps(checkpoint_dir), reverse=True):
        try:
            saved = (ckpt_io.load_meta(checkpoint_dir, step).get("extra") or {})
        except (OSError, ValueError) as e:
            print(f"[repro.rounds] checkpoint step {step}: unreadable meta "
                  f"({e}); trying an older step")
            continue
        for field in ("rounds", "cfg", "eval_every", "faults"):
            if saved.get(field) not in (None, run_meta[field]):
                raise ValueError(
                    f"checkpoint_dir {checkpoint_dir!r} holds a run with "
                    f"{field}={saved[field]!r}, cannot resume it with "
                    f"{field}={run_meta[field]!r}; point at a fresh directory"
                )
        hist_like = history_init(rounds, x0, jnp.zeros((), jnp.float32))
        try:
            states, hist, start = ckpt_io.restore_round_state(
                checkpoint_dir, states_like, hist_like, step=step, mesh=mesh
            )
        except (ckpt_io.CorruptCheckpointError, OSError) as e:
            print(f"[repro.rounds] checkpoint step {step}: corrupt "
                  f"({e}); trying an older step")
            continue
        return states, hist, min(start, rounds)
    return states_like, None, 0


def run_rounds(
    cfg: alg.AlgoConfig,
    rff: Optional[rfflib.RFFParams],
    query_fn: alg.QueryFn,
    cobjs,
    states: alg.ClientState,
    x0: jax.Array,
    global_value_fn: GlobalValueFn,
    rounds: int,
    chunk: int,
    *,
    mesh: Optional[Mesh] = None,
    diag_global_grad=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = True,
    eval_every: int = 1,
    async_checkpoint: bool = True,
    faults=None,  # Optional[faults.FaultConfig]
    max_rollbacks: int = 3,
) -> tuple[alg.ClientState, alg.SimResult]:
    """Run ``rounds`` communication rounds in chunks of ``chunk`` scanned
    iterations.  Returns (final stacked ClientState, SimResult history).

    With ``mesh=None`` clients run vmapped in-process; with a mesh they are
    sharded over the client axes and the scan runs inside shard_map.
    ``checkpoint_dir`` enables chunk-boundary checkpointing of
    {states, history} every ``checkpoint_every`` chunks (and at the end);
    when a checkpoint exists and ``resume`` is True the run restarts from
    the latest saved round.  On a mesh, checkpoints use the per-shard layout
    (one file per process from process-local data, no full ClientState
    gather; legacy single-file checkpoints still restore).  ``eval_every=k``
    evaluates ``global_value_fn`` inside the scan only every k-th round
    (plus the final one); skipped ``f_values`` rows hold NaN.

    The steady-state chunk boundary is HOST-SYNC-FREE: with ``cfg.deferred``
    the repair decision runs on device (``boundary_repair_on_device``, one
    extra async dispatch per chunk), and checkpoint writes are split into a
    synchronous host snapshot (required before the buffers are donated to
    the next chunk) plus a background file write overlapped with the next
    chunk's compute (``async_checkpoint=False`` forces the legacy blocking
    write).  Between boundaries the Python loop therefore runs ahead of the
    device, queueing chunk k+1 while chunk k executes.

    ``faults`` (a ``repro.faults.FaultConfig``) turns on the fault-tolerant
    engine (DESIGN.md Sec. 8): the scanned body masks dropped/poisoned
    clients out of the aggregation on device, quarantined clients are
    re-admitted from the global iterate at chunk boundaries by a
    device-decided gate, and the boundary gains ONE documented host sync --
    a finiteness check of the (d,)-vector server iterate -- that triggers
    chunk ROLLBACK: restore the newest good checkpoint (corrupt steps fall
    back to older ones) and re-run the lost rounds with tolerance forced
    on, at most ``max_rollbacks`` times.  A failed checkpoint write rolls
    back the same way.  ``faults=None`` leaves every code path above
    byte-identical to the faults-free engine.
    """
    if rounds < 0:
        raise ValueError(f"rounds must be >= 0, got {rounds}")
    if chunk < 1:
        raise ValueError("run_rounds requires chunk >= 1 (chunk=0 selects the "
                         "Python-loop oracle in the front doors)")
    if faults is not None:
        # A config whose window can never fire inside [0, rounds) must not
        # select the faulted engine (different compile key, extra psum
        # columns, insurance checkpoint, per-boundary finiteness sync): the
        # bitwise faults-off guarantee covers never-active windows too.
        from repro.faults.injector import effective_config  # deferred import
        faults = effective_config(faults, rounds)
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if mesh is not None and diag_global_grad is not None:
        raise ValueError("diag_global_grad is only supported on the vmap path "
                         "(mesh=None); the distributed round body runs without "
                         "diagnostics, so passing one would silently return zeros")
    chunk = min(chunk, max(rounds, 1))
    x0 = jnp.asarray(x0)

    # Resume identity: {rounds, AlgoConfig repr, eval_every} are recorded at
    # save time and must match at resume time, so a stale/reused checkpoint
    # dir fails loudly instead of splicing two different experiments -- or
    # two different f_values NaN patterns -- into one history.  ``chunk`` is
    # recorded but deliberately NOT validated: it only sets dispatch
    # granularity and boundary-repair cadence, both inside the
    # bounded-divergence equivalence contract, so resuming with a different
    # chunk length (e.g. shorter chunks on a slower machine) is legitimate.
    # (The initial iterate and RNG key live in the restored state itself and
    # so cannot drift; x0 passed here is ignored on resume.)
    run_meta = {"rounds": rounds, "chunk": chunk, "cfg": repr(cfg),
                "eval_every": eval_every, "faults": repr(faults)}
    start, hist = 0, None
    if checkpoint_dir and resume and ckpt_io.latest_step(checkpoint_dir) is not None:
        # Resume path: the checkpointed history already holds f(x_0), so the
        # (possibly expensive) initial eval is skipped.  Corrupt newest steps
        # fall back to older ones (the restore half of the fault model).
        r_states, r_hist, start = _restore_newest_good(
            checkpoint_dir, run_meta, rounds, x0, states, mesh
        )
        if r_hist is not None:
            states, hist = r_states, r_hist
            if mesh is not None:
                # No-op re-placement for shard-restored state; places legacy
                # single-file restores (host arrays) onto the mesh.
                states = fed.shard_clients(mesh, states)
    if hist is None:
        with telemetry.span("fzoo.eval_x0"):
            hist = history_init(rounds, x0, global_value_fn(cobjs, x0))

    sx = hist.xs[start]
    fcfg = faults
    steps: dict[tuple, Any] = {}

    def step_for(k: int):
        # Keyed on (length, fault config): a rollback flips ``tolerate`` and
        # must get a fresh executable, not the non-tolerant one.
        skey = (k, fcfg)
        if skey not in steps:
            if mesh is None:
                cf = sim_chunk_fn(cfg, query_fn, global_value_fn,
                                  diag_global_grad, k, eval_every, rounds,
                                  faults=fcfg)
            else:
                cf = dist_chunk_fn(cfg, mesh, query_fn, global_value_fn,
                                   k, eval_every, rounds, faults=fcfg)
            steps[skey] = make_chunk_step(cf)
        return steps[skey]

    # Multi-process pods force the blocking write: the sharded layout's
    # cross-process barrier (io._sync) is a collective, and issuing it from
    # the writer thread while the main thread dispatches the next chunk's
    # psum could interleave collectives in inconsistent cross-process order.
    # io._sync enforces the same invariant defensively (RuntimeError off the
    # main thread on a multi-process mesh).
    if checkpoint_dir and async_checkpoint and jax.process_count() > 1:
        if jax.process_index() == 0:
            print(
                "[repro.rounds] async_checkpoint requested on a "
                f"{jax.process_count()}-process mesh: FORCING blocking "
                "per-shard writes (async writer would issue the _sync "
                "collective off the main thread and deadlock the pod)."
            )
        async_checkpoint = False
    writer = (
        ckpt_io.AsyncCheckpointWriter()
        if (checkpoint_dir and async_checkpoint and jax.process_count() == 1)
        else None
    )
    if fcfg is not None and checkpoint_dir and ckpt_io.latest_step(checkpoint_dir) is None:
        # Rollback insurance: guarantee a restore target exists BEFORE the
        # first faulted chunk runs (one blocking write per fresh directory).
        payload = ckpt_io.prepare_round_state(states, hist, mesh=mesh)
        ckpt_io.write_round_state(checkpoint_dir, start, payload,
                                  extra_meta=run_meta)
    done, chunks_done, rollbacks = start, 0, 0
    try:
        while done < rounds:
            k = min(chunk, rounds - done)
            with telemetry.span("fzoo.chunk", offset=done, k=k):
                states, hist, sx = step_for(k)(
                    states, hist, cobjs, sx, jnp.asarray(done, jnp.int32), rff
                )
            done += k
            chunks_done += 1
            with telemetry.span("fzoo.boundary"):
                # Deferred-repair pass BETWEEN scan dispatches, decided ON
                # DEVICE: no flag read, no host sync -- the loop keeps running
                # ahead of the device (DESIGN.md Sec. 3).
                states = boundary_repair_on_device(states, cfg, mesh=mesh)
                if fcfg is not None and fcfg.tolerate:
                    # Re-admit quarantined clients from the global iterate;
                    # decided on device like the repair gate above.
                    states = boundary_quarantine_reset(states, cfg, sx, mesh=mesh)
                ok = True
                if fcfg is not None:
                    # THE one documented host sync of the faulted boundary: a
                    # finiteness check of the (d,) server iterate, gating the
                    # checkpoint write so a poisoned state is never persisted.
                    ok = bool(np.isfinite(np.asarray(jax.device_get(sx))).all())
                wrote_ok = True
                if ok and checkpoint_dir and (
                    chunks_done % max(checkpoint_every, 1) == 0 or done == rounds
                ):
                    # Snapshot to host BEFORE the next chunk donates these
                    # buffers; the file write itself overlaps the next chunk's
                    # compute on the writer thread.
                    payload = ckpt_io.prepare_round_state(states, hist, mesh=mesh)
                    try:
                        if writer is not None:
                            # A submit surfaces the PREVIOUS boundary's write
                            # error; rolling back to the last good step handles
                            # both boundaries identically.
                            writer.submit(partial(
                                ckpt_io.write_round_state, checkpoint_dir, done,
                                payload, run_meta,
                            ))
                            if done >= rounds:
                                # FINAL boundary: there is no next submit to
                                # surface this write's error, and raising it from
                                # the post-loop drain would escape the rollback
                                # machinery entirely.  Drain NOW so a failed last
                                # write rolls back like any other boundary.
                                writer.wait()
                        else:
                            ckpt_io.write_round_state(checkpoint_dir, done, payload,
                                                      extra_meta=run_meta)
                    except OSError as e:
                        if fcfg is None:
                            raise
                        print(f"[repro.rounds] checkpoint write failed at round "
                              f"{done}: {e}")
                        wrote_ok = False
                if fcfg is not None and (not ok or not wrote_ok):
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
                    print(f"[repro.rounds] ROLLBACK {rollbacks}/{max_rollbacks} at "
                          f"round {done} ({reason}): restoring last good checkpoint")
                    r_states, r_hist, r_start = _restore_newest_good(
                        checkpoint_dir, run_meta, rounds, x0, states, mesh
                    )
                    if r_hist is None:
                        raise FloatingPointError(
                            f"rollback at round {done} failed: no restorable "
                            f"checkpoint under {checkpoint_dir!r}"
                        )
                    states, hist, done = r_states, r_hist, r_start
                    if mesh is not None:
                        states = fed.shard_clients(mesh, states)
                    sx = hist.xs[done]
                    if not fcfg.tolerate:
                        print("[repro.rounds] re-running with fault tolerance "
                              "FORCED ON")
                        fcfg = dataclasses.replace(fcfg, tolerate=True)
                    chunks_done = 0
    finally:
        if writer is not None:
            writer.wait()

    return states, hist
