"""Federated ZOO algorithms under the paper's unified update (eq. 2):

    ghat^(i)_{r,t-1} = g^(i)_{r,t-1} + gamma * ( g_{r-1}(x') - g^(i)_{r-1}(x'') )

Instances (Sec. 3.1 + Appx. D):

  fzoos      g = derived-GP surrogate grad_mu at the CURRENT iterate,
             correction = grad_muhat_global(x) - grad_muhat_local(x) via RFF,
             gamma adaptive (1/t practical choice, Cor. C.1)        [Algo. 2]
  fedzo      g = finite difference, gamma = 0                        [2]
  fedprox    g = finite difference, correction = x - x_{r-1}, gamma=mu [4]
  scaffold1  g = FD, correction = mean_j FD_j(x_{r-1}) - FD_i(x_{r-1}), gamma=1
  scaffold2  g = FD, correction = round-averaged FD gradients, gamma=1

The round structure mirrors Algo. 1/2 exactly: T collective-free local steps
per client, one x-aggregation, then (FZooS) round-end active queries, the RFF
re-fit and one w-aggregation -- i.e. the paper's one-or-two transmissions per
round.  ``mean_fn`` abstracts the server aggregation so the same code runs
under single-process vmap simulation and under shard_map on a device mesh
(see repro.core.federated).
"""

from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import fd as fdlib
from repro.core import gp_surrogate as gp
from repro.core import rff as rfflib
from repro.optim import make_optimizer

Pytree = Any
QueryFn = Callable[..., jax.Array]
MeanFn = Callable[[Pytree], Pytree]

ALGORITHMS = ("fzoos", "fedzo", "fedprox", "scaffold1", "scaffold2")


@dataclasses.dataclass(frozen=True)
class AlgoConfig:
    """Static (hashable) algorithm configuration."""

    name: str
    dim: int
    n_clients: int
    eta: float = 0.01
    local_steps: int = 10  # T
    optimizer: str = "adam"  # paper Appx. E: Adam, lr 0.01
    # finite-difference baselines
    q: int = 20
    fd_lambda: float = 5e-3  # FD probe; must sit below curvature scale (see tests)
    # FedProx proximal coefficient (its gamma in eq. 2)
    prox_mu: float = 1.0
    # FZooS surrogate machinery
    n_features: int = 512  # M
    traj_capacity: int = 128
    lengthscale: float = 1.0
    noise: float = 1e-4
    gamma_mode: str = "inv_t"  # inv_t | const  (Cor. C.1 practical choice)
    gamma_const: float = 1.0
    active_per_iter: int = 5
    active_candidates: int = 100
    active_radius: float = 0.01
    active_round_end: int = 5
    # Per-step surrogate hot path: carry an incrementally maintained Gram
    # factorization in ClientState (DESIGN.md Sec. 2) instead of
    # refactorizing at every surrogate evaluation.  False = the seed's
    # eigh-from-scratch path, kept as the equivalence oracle for tests.
    use_factor_cache: bool = True
    # Deferred-repair vmapped engine (DESIGN.md Sec. 2.6): the scanned round
    # body is branch-free and eigh-free -- an unhealthy factor update flags
    # the client and freezes its factors until the chunk-boundary repair pass
    # -- and the local/post phases run client-BATCHED (one fused kernel
    # launch per step for the whole client batch).  False keeps PR 2's
    # inline-cond per-client path as the equivalence oracle, analogous to
    # use_factor_cache=False / chunk=0.  Only meaningful for fzoos with the
    # factor cache on (see ``deferred``).
    defer_repair: bool = True
    # Round-end RFF fit: solve through the exact-GP cached factor (one
    # O(cap^2) solve) instead of eigh-refactorizing the RFF Gram.  Off by
    # default: the RFF-Gram solve is the paper's eq. 6 and changing it
    # perturbs w by the O(1/sqrt(M)) feature-approximation error.
    rff_fit_exact: bool = False
    # Kernel tiling overrides for the client-batched scoring / grad-mean
    # Pallas kernels (kernels/ops.py).  None defers to the deterministic
    # per-(backend, shape) autotuner (kernels/autotune.py); pinning them
    # here makes a run's tiling reproducible independent of the autotuner's
    # model (the choice only affects scheduling, never results -- padded
    # trajectory slots contribute exactly zero on the tiled path).
    score_block_n: Optional[int] = None
    score_block_cap: Optional[int] = None
    grad_block_n: Optional[int] = None
    grad_block_cap: Optional[int] = None
    # domain
    lo: float = 0.0
    hi: float = 1.0

    def __post_init__(self):
        if self.name not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {self.name!r}; choose from {ALGORITHMS}")
        if self.rff_fit_exact and not self.use_factor_cache:
            raise ValueError("rff_fit_exact=True requires use_factor_cache=True "
                             "(the round-end fit consumes the cached Gram factor)")

    @property
    def is_fzoos(self) -> bool:
        return self.name == "fzoos"

    @property
    def deferred(self) -> bool:
        """True when the deferred-repair client-batched engine is active."""
        return self.is_fzoos and self.use_factor_cache and self.defer_repair

    @property
    def uses_fd(self) -> bool:
        return self.name in ("fedzo", "fedprox", "scaffold1", "scaffold2")

    def queries_per_round(self) -> int:
        """Static per-client query count per round (EXPERIMENTS.md bookkeeping)."""
        t = self.local_steps
        if self.is_fzoos:
            return t * (1 + self.active_per_iter) + self.active_round_end
        per_iter = fdlib.fd_queries(self.q)
        extra = fdlib.fd_queries(self.q) if self.name == "scaffold1" else 0
        return t * per_iter + extra

    def comm_floats_per_round(self) -> int:
        """Client->server payload floats per round (communication claim)."""
        base = self.dim  # the iterate
        if self.is_fzoos:
            return base + self.n_features  # + w^(i)  (Sec. 4.2.1)
        if self.name in ("scaffold1", "scaffold2"):
            return base + self.dim  # + control variate
        return base


class ClientState(NamedTuple):
    x: jax.Array  # (d,)
    traj: gp.Trajectory  # ring buffer (fzoos; 1-slot dummy otherwise)
    factor: gp.GramFactor  # cached Gram factorization of `traj` (DESIGN.md Sec. 2)
    w_local: jax.Array  # (M,) RFF weights of own surrogate at end of prev round
    w_global: jax.Array  # (M,) server-averaged weights
    c_local: jax.Array  # (d,) SCAFFOLD control variate
    c_global: jax.Array  # (d,)
    fd_bank: jax.Array  # (Q, d) shared direction bank (scaffold2, Prop. D.4)
    fd_accum: jax.Array  # (d,) running sum of FD grads this round (scaffold2)
    opt: Any  # local optimizer state
    queries: jax.Array  # () int32 cumulative per-client query counter
    key: jax.Array
    client_id: jax.Array  # () int32 global client identity (fault schedules)
    quarantined: jax.Array  # () bool -- excluded from aggregation until the
    #   chunk-boundary re-init (the fault-tolerance analogue of needs_repair)


class RoundStats(NamedTuple):
    server_x: jax.Array  # (d,) aggregated iterate after the round
    mean_cos: jax.Array  # () mean cos(ghat, grad F) over clients x iters (diag)
    mean_disparity: jax.Array  # () mean ||ghat - grad F||^2 (Thm. 1 Xi)
    queries_per_client: jax.Array  # () mean cumulative queries
    refactor_rate: jax.Array  # () mean clamped-eigh fallbacks / factor updates
    repair_rate: jax.Array  # () fraction of clients flagged needs_repair
    drop_rate: jax.Array  # () fraction of clients NOT contributing this round
    quarantine_rate: jax.Array  # () fraction of clients quarantined


def _hyper_of(cfg: AlgoConfig) -> gp.GPHyper:
    """The config's GP hyperparameters as static Python floats: inside the
    jitted round body they stay compile-time constants, so the Pallas kernel
    wrappers (which bake lengthscale and the derived prior d/l^2 into the
    program) take the kernel route on the TPU."""
    return gp.GPHyper(float(cfg.lengthscale), float(cfg.noise))


def init_client_state(cfg: AlgoConfig, key: jax.Array, x0: jax.Array,
                      client_id: int | jax.Array = 0) -> ClientState:
    cap = cfg.traj_capacity if cfg.is_fzoos else 1
    m = cfg.n_features if cfg.is_fzoos else 1
    qd = cfg.q if cfg.name == "scaffold2" else 1
    opt_init, _ = make_optimizer(cfg.optimizer)
    # The shared direction bank must be identical across clients (Prop. D.4):
    # derive it from a constant key, not the per-client key.
    # key-flow: ok (constant bank is intentional; collision with a user seed
    # requires a 2^-64 key-space coincidence)
    bank = fdlib.sample_directions(jax.random.PRNGKey(12345), qd, cfg.dim)
    traj0 = gp.traj_init(cap, cfg.dim)
    return ClientState(
        x=x0,
        traj=traj0,
        factor=gp.factor_init(traj0, _hyper_of(cfg)),
        w_local=jnp.zeros((m,), jnp.float32),
        w_global=jnp.zeros((m,), jnp.float32),
        c_local=jnp.zeros((cfg.dim,), jnp.float32),
        c_global=jnp.zeros((cfg.dim,), jnp.float32),
        fd_bank=bank,
        fd_accum=jnp.zeros((cfg.dim,), jnp.float32),
        opt=opt_init(x0),
        queries=jnp.zeros((), jnp.int32),
        key=key,
        client_id=jnp.asarray(client_id, jnp.int32),
        quarantined=jnp.zeros((), bool),
    )


def init_states(cfg: AlgoConfig, key: jax.Array, x0: jax.Array) -> ClientState:
    """Stacked states for all clients (leading axis N)."""
    keys = jax.random.split(key, cfg.n_clients)
    ids = jnp.arange(cfg.n_clients, dtype=jnp.int32)
    return jax.vmap(lambda k, i: init_client_state(cfg, k, x0, i))(keys, ids)


# ---------------------------------------------------------------------------
# Local phase: T collective-free steps on one client
# ---------------------------------------------------------------------------


def _estimate_gradient(
    cfg: AlgoConfig,
    rff: Optional[rfflib.RFFParams],
    query_fn: QueryFn,
    cobj,
    st: ClientState,
    server_x: jax.Array,
    t: jax.Array,
    key: jax.Array,
) -> tuple[jax.Array, ClientState]:
    """ghat^(i)_{r,t-1} per eq. (2)/(8).  Returns (ghat, state-with-queries)."""
    x = st.x
    if cfg.is_fzoos:
        hyper = _hyper_of(cfg)
        if cfg.use_factor_cache:
            g_loc = gp.grad_mean_cached(st.traj, st.factor, hyper, x)
        else:
            g_loc = gp.grad_mean(st.traj, hyper, x)
        corr = rfflib.grad_features_t_w(rff, x, st.w_global) - rfflib.grad_features_t_w(rff, x, st.w_local)
        if cfg.gamma_mode == "inv_t":
            gamma = 1.0 / t.astype(jnp.float32)  # Cor. C.1 practical choice
        else:
            gamma = jnp.asarray(cfg.gamma_const, jnp.float32)
        return g_loc + gamma * corr, st

    # FD family.  (Prop. D.4 analyzes SCAFFOLD-II under a shared direction
    # bank; with Q < d that traps the iterate in a Q-dim subspace forever,
    # so the executable algorithm samples fresh directions like the others.)
    key, kd = jax.random.split(key)
    dirs = fdlib.sample_directions(kd, cfg.q, cfg.dim)
    g_fd = fdlib.fd_grad(query_fn, cobj, x, key, dirs, cfg.fd_lambda)
    st = st._replace(queries=st.queries + fdlib.fd_queries(cfg.q))
    if cfg.name == "fedzo":
        return g_fd, st
    if cfg.name == "fedprox":
        return g_fd + cfg.prox_mu * (x - server_x), st
    # scaffold1 / scaffold2: gamma = 1 control-variate correction
    st = st._replace(fd_accum=st.fd_accum + g_fd)
    return g_fd + (st.c_global - st.c_local), st


def _local_phase(
    cfg: AlgoConfig,
    rff: Optional[rfflib.RFFParams],
    query_fn: QueryFn,
    cobj,
    st: ClientState,
    server_x: jax.Array,
    diag_global_grad: Optional[Callable[[jax.Array], jax.Array]],
) -> tuple[ClientState, jax.Array, jax.Array]:
    """Run T local steps.  Returns (state, sum_cos, sum_disparity)."""
    _, opt_update = make_optimizer(cfg.optimizer)

    def step(carry, t):
        st: ClientState = carry
        key, k_obs, k_act, k_est = jax.random.split(st.key, 4)
        st = st._replace(key=key)

        if cfg.is_fzoos:
            # Trajectory-informed: query the current iterate (+ active queries)
            # BEFORE estimating -- the estimate is conditioned on D_{r,t-1}.
            hyper = _hyper_of(cfg)
            y = query_fn(cobj, st.x, k_obs)
            if cfg.use_factor_cache:
                traj, factor = gp.traj_extend(
                    st.traj, st.factor, st.x[None, :], y[None], hyper
                )
            else:
                traj, factor = gp.traj_append(st.traj, st.x, y), st.factor
            n_q = 1
            if cfg.active_per_iter > 0:
                if cfg.use_factor_cache:
                    cands = gp.select_active_queries_cached(
                        k_act, traj, factor, hyper, st.x, cfg.active_candidates,
                        cfg.active_per_iter, cfg.active_radius, cfg.lo, cfg.hi,
                    )
                else:
                    cands = gp.select_active_queries(
                        k_act, traj, hyper, st.x, cfg.active_candidates, cfg.active_per_iter,
                        cfg.active_radius, cfg.lo, cfg.hi,
                    )
                # key-flow: ok (k_act sample/fold streams audited; kept for
                # bitwise seed-replay compatibility)
                kq = jax.random.split(jax.random.fold_in(k_act, 1), cfg.active_per_iter)
                ys = jax.vmap(lambda c, k: query_fn(cobj, c, k))(cands, kq)
                if cfg.use_factor_cache:
                    traj, factor = gp.traj_extend(traj, factor, cands, ys, hyper)
                else:
                    traj = gp.traj_append_batch(traj, cands, ys)
                n_q += cfg.active_per_iter
            st = st._replace(traj=traj, factor=factor, queries=st.queries + n_q)

        ghat, st = _estimate_gradient(cfg, rff, query_fn, cobj, st, server_x, t, k_est)
        new_x, new_opt = opt_update(st.opt, ghat, st.x, cfg.eta)
        new_x = jnp.clip(new_x, cfg.lo, cfg.hi)

        if diag_global_grad is not None:
            gf = diag_global_grad(st.x)
            cos = jnp.dot(ghat, gf) / (jnp.linalg.norm(ghat) * jnp.linalg.norm(gf) + 1e-12)
            disp = jnp.sum((ghat - gf) ** 2)
        else:
            cos = jnp.zeros(())
            disp = jnp.zeros(())

        st = st._replace(x=new_x, opt=new_opt)
        return st, (cos, disp)

    ts = jnp.arange(1, cfg.local_steps + 1)
    st, (coss, disps) = jax.lax.scan(step, st, ts)
    return st, jnp.sum(coss), jnp.sum(disps)


# ---------------------------------------------------------------------------
# Client-batched local/post phases (the deferred-repair engine).
#
# The per-client ``_local_phase`` is scanned over T INSIDE a client vmap, so
# every surrogate contraction launches once per client.  Local steps are
# collective-free and clients share all shapes, so scan-over-T with the
# client batch INSIDE each step is the same algorithm -- and lets the
# scoring / gradient-mean kernels take the whole client batch in ONE launch
# (the client grid dimension of kernels/gp_score.py, gp_grad.py).  RNG key
# derivations mirror the per-client path exactly, so the two engines follow
# the same query sequence up to f32 contraction ordering.
# ---------------------------------------------------------------------------


def _local_phase_clients(
    cfg: AlgoConfig,
    rff: rfflib.RFFParams,
    query_fn: QueryFn,
    cobjs,
    states: ClientState,  # stacked (N, ...)
    diag_global_grad: Optional[Callable[[jax.Array], jax.Array]],
) -> tuple[ClientState, jax.Array, jax.Array]:
    """T local FZooS steps for the whole client batch (deferred factors)."""
    _, opt_update = make_optimizer(cfg.optimizer)
    hyper = _hyper_of(cfg)

    def step(sts: ClientState, t):
        ks = jax.vmap(lambda k: jax.random.split(k, 4))(sts.key)  # (N, 4, 2)
        sts = sts._replace(key=ks[:, 0])
        k_act = ks[:, 2]

        y = jax.vmap(query_fn)(cobjs, sts.x, ks[:, 1])
        traj, factor = gp.traj_extend_clients(
            sts.traj, sts.factor, sts.x[:, None, :], y[:, None], hyper, deferred=True
        )
        n_q = 1
        if cfg.active_per_iter > 0:
            cands = gp.select_active_queries_cached_clients(
                k_act, traj, factor, hyper, sts.x, cfg.active_candidates,
                cfg.active_per_iter, cfg.active_radius, cfg.lo, cfg.hi,
                block_n=cfg.score_block_n, block_cap=cfg.score_block_cap,
            )  # (N, n_act, d)
            kq = jax.vmap(
                # key-flow: ok (k_act sample/fold streams audited; kept for
                # bitwise seed-replay compatibility)
                lambda k: jax.random.split(jax.random.fold_in(k, 1), cfg.active_per_iter)
            )(k_act)
            ys = jax.vmap(
                lambda cobj, cs, kk: jax.vmap(lambda c, k: query_fn(cobj, c, k))(cs, kk)
            )(cobjs, cands, kq)
            traj, factor = gp.traj_extend_clients(traj, factor, cands, ys, hyper, deferred=True)
            n_q += cfg.active_per_iter
        sts = sts._replace(traj=traj, factor=factor, queries=sts.queries + n_q)

        # eq. (2): batched surrogate mean + per-client RFF correction
        g_loc = gp.grad_mean_cached_clients(
            traj, factor, hyper, sts.x,
            block_n=cfg.grad_block_n, block_cap=cfg.grad_block_cap,
        )  # (N, d)
        corr = rfflib.grad_features_t_w_rows(rff, sts.x, sts.w_global) - \
            rfflib.grad_features_t_w_rows(rff, sts.x, sts.w_local)
        if cfg.gamma_mode == "inv_t":
            gamma = 1.0 / t.astype(jnp.float32)
        else:
            gamma = jnp.asarray(cfg.gamma_const, jnp.float32)
        ghat = g_loc + gamma * corr

        new_x, new_opt = jax.vmap(lambda o, g, x: opt_update(o, g, x, cfg.eta))(
            sts.opt, ghat, sts.x
        )
        new_x = jnp.clip(new_x, cfg.lo, cfg.hi)

        if diag_global_grad is not None:
            gf = jax.vmap(diag_global_grad)(sts.x)
            cos = jnp.sum(ghat * gf, -1) / (
                jnp.linalg.norm(ghat, axis=-1) * jnp.linalg.norm(gf, axis=-1) + 1e-12
            )
            disp = jnp.sum((ghat - gf) ** 2, -1)
        else:
            cos = jnp.zeros(sts.x.shape[:1])
            disp = jnp.zeros(sts.x.shape[:1])

        sts = sts._replace(x=new_x, opt=new_opt)
        return sts, (cos, disp)

    ts = jnp.arange(1, cfg.local_steps + 1)
    states, (coss, disps) = jax.lax.scan(step, states, ts)
    return states, jnp.sum(coss, axis=0), jnp.sum(disps, axis=0)


def _post_phase_clients(
    cfg: AlgoConfig,
    rff: rfflib.RFFParams,
    query_fn: QueryFn,
    cobjs,
    states: ClientState,
    new_server_x: jax.Array,
) -> ClientState:
    """Round-end active queries + eigh-free RFF fit for the client batch."""
    hyper = _hyper_of(cfg)
    states = states._replace(x=jnp.broadcast_to(new_server_x, states.x.shape))
    ks = jax.vmap(lambda k: jax.random.split(k, 2))(states.key)
    states = states._replace(key=ks[:, 0])
    k_act = ks[:, 1]
    traj, factor = states.traj, states.factor
    if cfg.active_round_end > 0:
        cands = gp.select_active_queries_cached_clients(
            k_act, traj, factor, hyper, states.x, cfg.active_candidates,
            cfg.active_round_end, cfg.active_radius, cfg.lo, cfg.hi,
            block_n=cfg.score_block_n, block_cap=cfg.score_block_cap,
        )
        kq = jax.vmap(
            # key-flow: ok (k_act sample/fold streams audited; kept for
            # bitwise seed-replay compatibility)
            lambda k: jax.random.split(jax.random.fold_in(k, 2), cfg.active_round_end)
        )(k_act)
        ys = jax.vmap(
            lambda cobj, cs, kk: jax.vmap(lambda c, k: query_fn(cobj, c, k))(cs, kk)
        )(cobjs, cands, kq)
        traj, factor = gp.traj_extend_clients(traj, factor, cands, ys, hyper, deferred=True)
        states = states._replace(
            traj=traj, factor=factor, queries=states.queries + cfg.active_round_end
        )
    if cfg.rff_fit_exact:
        w_i = jax.vmap(lambda tr, fa: rfflib.fit_w_from_factor(rff, tr, fa))(traj, factor)
    else:
        # eq. 6 via blocked Cholesky with a branch-free exact-factor fallback
        # -- the ONLY eigh of the seed round body that defer_repair does not
        # merely defer, it removes (fit_w's clamped eigh was robustness, not
        # math: see rff.fit_w_chol).
        w_i = jax.vmap(lambda tr, fa: rfflib.fit_w_chol(rff, tr, hyper, fa))(traj, factor)
    return states._replace(w_local=w_i)


# ---------------------------------------------------------------------------
# One full communication round (Algo. 1 / Algo. 2)
# ---------------------------------------------------------------------------


def _f32_matmuls(fn):
    """Trace ``fn`` with float32 matmuls.

    The surrogate's Gram algebra runs at condition numbers ~1e5 (DESIGN.md
    Sec. 2.4), and the pairwise distances behind it cancel |x|^2 ~ d/4
    against a cross term, so it needs f32 products.  On a TPU an f32 matmul
    defaults to one bfloat16 pass; this pins f32 for everything ``fn``
    traces, Pallas kernels included.  Other backends already compute f32
    dots in f32, so there it changes nothing.
    """

    @wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)

    return wrapped


@_f32_matmuls
def run_round(
    cfg: AlgoConfig,
    rff: Optional[rfflib.RFFParams],
    query_fn: QueryFn,
    cobjs,  # stacked per-client objective params (leading axis = local clients)
    states: ClientState,  # stacked states (leading axis = local clients)
    server_x: jax.Array,  # (d,)
    mean_fn: MeanFn,  # server aggregation over ALL clients
    diag_global_grad: Optional[Callable[[jax.Array], jax.Array]] = None,
    *,
    sum_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    faults=None,  # Optional[faults.FaultConfig]
    round_idx: Optional[jax.Array] = None,
) -> tuple[ClientState, RoundStats]:
    """One communication round.

    With ``faults=None`` (the default) this is structurally the fault-free
    engine: no mask ops are traced and the output is bitwise what it was
    before the fault layer existed.  With a ``faults.FaultConfig``, fault
    draws for ``round_idx`` are injected and (when ``faults.tolerate``) the
    aggregations switch to masked participation-weighted means renormalized
    by the live-client count: the live mask and the quarantine count ride
    INSIDE the existing payload arrays (one extra row of the concatenated
    psum operand), so masking adds ZERO collectives to the round
    (analysis/contracts.py pins the census).  ``sum_fn`` must then be the
    un-normalized global sum (``federated.client_sum_fn`` on a mesh; plain
    axis-0 sum under vmap simulation).
    """
    opt_init, _ = make_optimizer(cfg.optimizer)
    draws = None
    if faults is not None:
        if sum_fn is None or round_idx is None:
            raise ValueError("faults injection requires sum_fn and round_idx")
        from repro.faults import draw_faults  # deferred: keep import DAG slim

        draws = draw_faults(faults, round_idx, states.client_id)

    # ---- prologue: broadcast x_r, reset local optimizers ----
    def prologue(st: ClientState, cobj) -> ClientState:
        st = st._replace(x=server_x, opt=opt_init(server_x), fd_accum=jnp.zeros_like(server_x))
        if cfg.name == "scaffold1":
            # c_i <- FD estimate at x_{r-1}; requires one extra transmission
            # (SCAFFOLD Type I per Appx. D).
            key, kd, kf = jax.random.split(st.key, 3)
            dirs = fdlib.sample_directions(kd, cfg.q, cfg.dim)
            c_i = fdlib.fd_grad(query_fn, cobj, server_x, kf, dirs, cfg.fd_lambda)
            st = st._replace(key=key, c_local=c_i, queries=st.queries + fdlib.fd_queries(cfg.q))
        return st

    states = jax.vmap(prologue)(states, cobjs)
    if cfg.name == "scaffold1":
        c_glob = mean_fn(states.c_local)
        states = states._replace(c_global=jnp.broadcast_to(c_glob, states.c_global.shape))

    # Post-prologue snapshot: faulted clients (dropped / straggling /
    # quarantined) roll their local state back to this point at round end --
    # a client that did not deliver an update must not advance.
    states0 = states if faults is not None else None

    # ---- T local steps on every client in parallel ----
    if cfg.deferred:
        # Deferred-repair engine: branch-free factor updates, client-batched
        # surrogate kernels (one launch per step for the whole batch).
        states, sum_cos, sum_disp = _local_phase_clients(
            cfg, rff, query_fn, cobjs, states, diag_global_grad
        )
    else:
        local = partial(_local_phase, cfg, rff, query_fn)
        states, sum_cos, sum_disp = jax.vmap(
            lambda cobj, st: local(cobj, st, server_x, diag_global_grad)
        )(cobjs, states)

    # ---- server aggregation of the iterates (line 7/9 of Algo. 1/2) ----
    zero = jnp.zeros((), jnp.float32)
    live = quar = n_live = n_quar = None
    if faults is None:
        new_server_x = mean_fn(states.x)
    else:
        # Inject the payload faults on the UPDATE, never on the state: the
        # client's own state stays finite and is rolled back below.
        x_up = states.x
        if faults.nan_rate > 0:
            x_up = jnp.where(draws.nan[:, None], jnp.float32(jnp.nan), x_up)
        if faults.inf_rate > 0:
            x_up = jnp.where(draws.inf[:, None], jnp.float32(jnp.inf), x_up)
        # straggler: the server sees its STALE iterate (this round's broadcast)
        x_up = jnp.where(draws.straggle[:, None], server_x, x_up)
        if faults.tolerate:
            # On-device liveness + health mask.  NOTE: jnp.where, never
            # multiply-by-mask -- NaN * 0 is NaN and would defeat the mask.
            finite = jnp.all(jnp.isfinite(x_up), axis=-1)
            quar = states.quarantined | (~finite & ~draws.drop)
            live = ~draws.drop & ~states.quarantined & finite
            # The live count and quarantine census ride as two extra rows of
            # the SAME psum operand: masking adds zero collectives.
            payload = jnp.concatenate(
                [jnp.where(live[:, None], x_up, 0.0),
                 live.astype(jnp.float32)[:, None],
                 quar.astype(jnp.float32)[:, None]], axis=1)
            tot = sum_fn(payload)
            n_live, n_quar = tot[cfg.dim], tot[cfg.dim + 1]
            new_server_x = jnp.where(
                n_live > 0, tot[: cfg.dim] / jnp.maximum(n_live, 1.0), server_x)
        else:
            # No tolerance: a dropped client is simply never heard from, and
            # the dense mean treats silence as NaN -- the poisoning failure
            # mode the masked path removes (and the rollback demo trigger).
            x_up = jnp.where(draws.drop[:, None], jnp.float32(jnp.nan), x_up)
            new_server_x = sum_fn(x_up) / cfg.n_clients

    # ---- post phase ----
    def post(st: ClientState, cobj) -> ClientState:
        st = st._replace(x=new_server_x)
        if cfg.is_fzoos:
            key, k_act = jax.random.split(st.key)
            st = st._replace(key=key)
            traj, factor = st.traj, st.factor
            hyper = _hyper_of(cfg)
            if cfg.active_round_end > 0:
                # Active queries around x_r (line 7 of Algo. 2) sharpen the
                # correction term (2) in Thm. 1 before w is fitted & shipped.
                if cfg.use_factor_cache:
                    cands = gp.select_active_queries_cached(
                        k_act, traj, factor, hyper, new_server_x, cfg.active_candidates,
                        cfg.active_round_end, cfg.active_radius, cfg.lo, cfg.hi,
                    )
                else:
                    cands = gp.select_active_queries(
                        k_act, traj, hyper, new_server_x, cfg.active_candidates,
                        cfg.active_round_end, cfg.active_radius, cfg.lo, cfg.hi,
                    )
                # key-flow: ok (k_act sample/fold streams audited; kept for
                # bitwise seed-replay compatibility)
                kq = jax.random.split(jax.random.fold_in(k_act, 2), cfg.active_round_end)
                ys = jax.vmap(lambda c, k: query_fn(cobj, c, k))(cands, kq)
                if cfg.use_factor_cache:
                    traj, factor = gp.traj_extend(traj, factor, cands, ys, hyper)
                else:
                    traj = gp.traj_append_batch(traj, cands, ys)
                st = st._replace(
                    traj=traj, factor=factor, queries=st.queries + cfg.active_round_end
                )
            if cfg.rff_fit_exact and cfg.use_factor_cache:
                w_i = rfflib.fit_w_from_factor(rff, traj, factor)
            else:
                w_i = rfflib.fit_w(rff, traj, hyper)
            st = st._replace(w_local=w_i)
        elif cfg.name == "scaffold2":
            st = st._replace(c_local=st.fd_accum / cfg.local_steps)
        return st

    if cfg.deferred:
        states = _post_phase_clients(cfg, rff, query_fn, cobjs, states, new_server_x)
    else:
        states = jax.vmap(post)(states, cobjs)

    # ---- fault response: roll faulted clients back to the round prologue ----
    if faults is not None and faults.tolerate:
        # A client that did not deliver (drop/straggle) or is quarantined
        # keeps its pre-round state -- its trajectory, factors, w and RNG
        # stream advance only on rounds it actually completes.
        frozen = draws.drop | draws.straggle | quar

        def _freeze(old, new):
            f = frozen.reshape(frozen.shape + (1,) * (new.ndim - 1))
            return jnp.where(f, old, new)

        states = jax.tree_util.tree_map(_freeze, states0, states)
        states = states._replace(quarantined=quar)

    # ---- second aggregation: w (FZooS) / control variates (scaffold2) ----
    if cfg.is_fzoos:
        if faults is not None and faults.tolerate:
            # stragglers contribute their (stale) w; quarantined and dropped
            # clients are masked out, count packed into the same psum operand
            m_w = ~draws.drop & ~quar
            w_pay = jnp.concatenate(
                [jnp.where(m_w[:, None], states.w_local, 0.0),
                 m_w.astype(jnp.float32)[:, None]], axis=1)
            w_tot = sum_fn(w_pay)
            w_glob = jnp.where(
                w_tot[-1] > 0, w_tot[:-1] / jnp.maximum(w_tot[-1], 1.0),
                # all clients dead: keep the previous global w (replicated
                # rows, so the LOCAL mean is the global value -- no psum)
                jnp.mean(states.w_global, axis=0))
        else:
            w_glob = mean_fn(states.w_local)
        states = states._replace(w_global=jnp.broadcast_to(w_glob, states.w_global.shape))
    elif cfg.name == "scaffold2":
        c_glob = mean_fn(states.c_local)
        states = states._replace(c_global=jnp.broadcast_to(c_glob, states.c_global.shape))

    # ---- round stats (masked means over live clients under faults) ----
    if faults is None:
        agg = mean_fn
        drop_rate, quarantine_rate = zero, zero
    elif faults.tolerate:
        denom = jnp.maximum(n_live, 1.0)
        agg = lambda v: sum_fn(jnp.where(live, v, 0.0)) / denom
        drop_rate = 1.0 - n_live / cfg.n_clients
        quarantine_rate = n_quar / cfg.n_clients
    else:
        agg = mean_fn
        drop_rate = sum_fn(draws.drop.astype(jnp.float32)) / cfg.n_clients
        quarantine_rate = zero

    stats = RoundStats(
        server_x=new_server_x,
        mean_cos=agg(sum_cos) / cfg.local_steps,
        mean_disparity=agg(sum_disp) / cfg.local_steps,
        queries_per_client=agg(states.queries.astype(jnp.float32)),
        refactor_rate=agg(
            states.factor.n_refactors.astype(jnp.float32)
            / jnp.maximum(states.factor.n_updates.astype(jnp.float32), 1.0)
        ),
        repair_rate=agg(states.factor.needs_repair.astype(jnp.float32)),
        drop_rate=drop_rate,
        quarantine_rate=quarantine_rate,
    )
    return states, stats


def make_quarantine_reset(cfg: AlgoConfig):
    """Build ``reset(states, server_x)``: re-initialize quarantined clients
    from the global iterate (chunk-boundary recovery, DESIGN.md Sec. 8).

    The fresh-client template (empty trajectory, its Gram factorization, the
    shared FD bank) is computed EAGERLY here -- it does not depend on the
    traced ``server_x`` -- so the compiled reset contains no cholesky/eigh at
    all (contract-checked).  A quarantined client keeps its identity, RNG
    stream, cumulative query count and the replicated ``w_global``;
    everything else (iterate, trajectory, factor, local weights, optimizer)
    restarts as a fresh client joining at ``server_x``.
    """
    template = init_client_state(cfg, jax.random.PRNGKey(0),
                                 jnp.zeros((cfg.dim,), jnp.float32))
    opt_init, _ = make_optimizer(cfg.optimizer)

    def reset(states: ClientState, server_x: jax.Array) -> ClientState:
        flag = states.quarantined
        fresh = template._replace(x=server_x, opt=opt_init(server_x))

        def sel(old, new):
            f = flag.reshape(flag.shape + (1,) * (old.ndim - 1))
            return jnp.where(f, jnp.broadcast_to(new, old.shape), old)

        merged = jax.tree_util.tree_map(sel, states, fresh)
        return merged._replace(
            key=states.key, client_id=states.client_id, queries=states.queries,
            w_global=states.w_global,
            quarantined=jnp.zeros_like(states.quarantined),
        )

    return reset


# ---------------------------------------------------------------------------
# Single-process simulation driver
# ---------------------------------------------------------------------------


class SimResult(NamedTuple):
    """Per-round history of a run.

    ``f_values[r]`` is F(x_r); with ``eval_every=k > 1`` only every k-th
    round (plus round 0 and the final round) is evaluated and the skipped
    rows hold NaN -- the objective curve degrades gracefully instead of
    paying an expensive global eval every round.
    """

    xs: jax.Array  # (R+1, d) server iterates
    f_values: jax.Array  # (R+1,) F(x_r); NaN rows = skipped by eval_every
    queries: jax.Array  # (R,) cumulative mean queries per client
    mean_cos: jax.Array  # (R,)
    mean_disparity: jax.Array  # (R,)
    refactor_rate: jax.Array  # (R,) factor-cache clamped-eigh fallback rate
    repair_rate: jax.Array  # (R,) fraction of clients flagged needs_repair
    drop_rate: jax.Array  # (R,) fraction of clients not contributing (faults)
    quarantine_rate: jax.Array  # (R,) fraction of clients quarantined (faults)


def simulate(
    cfg: AlgoConfig,
    key: jax.Array,
    cobjs,
    query_fn: QueryFn,
    global_value_fn: Callable[[Any, jax.Array], jax.Array],
    rounds: int,
    x0: Optional[jax.Array] = None,
    diag_global_grad: Optional[Callable[[jax.Array], jax.Array]] = None,
    rff_key: Optional[jax.Array] = None,
    chunk: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    eval_every: int = 1,
    async_checkpoint: bool = True,
    faults=None,  # Optional[faults.FaultConfig]
    max_rollbacks: int = 3,
    cohort: Optional[int] = None,
    cohort_seed: int = 0,
) -> SimResult:
    """Run R communication rounds in a single process (clients via vmap).

    ``chunk`` selects the round driver: ``None`` (default) scans rounds in
    chunks of ``rounds.DEFAULT_CHUNK`` on device (core/rounds.py -- one
    dispatch per chunk, ``global_value_fn`` evaluated inside the scan);
    ``chunk=k>0`` sets the chunk length; ``chunk=0`` keeps the seed
    one-dispatch-per-round Python loop as the equivalence oracle.
    ``checkpoint_dir`` (scan driver only) enables chunk-boundary
    checkpoint/resume of the run; ``async_checkpoint`` overlaps the file
    write with the next chunk (core/rounds.py).  ``eval_every=k`` evaluates
    the (possibly expensive) ``global_value_fn`` only every k-th round plus
    the final one; skipped ``f_values`` rows hold NaN (see SimResult).

    ``cohort=K`` selects PARTIAL PARTICIPATION (core/pool.py): the N =
    ``cfg.n_clients`` states live in a host-resident pool and each chunk a
    deterministic cohort of K clients (keyed ``cohort_seed``) is gathered,
    run through the scan engine, and scattered back, with the aggregation
    renormalized by the live cohort count.  ``cohort=None`` (default) keeps
    the dense all-clients engine; K = N is bitwise the dense engine.
    """
    if chunk is not None and chunk < 0:
        raise ValueError(f"chunk must be None, 0 (loop oracle) or positive, got {chunk}")
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if x0 is None:
        x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    k_init, k_rff, k_rounds = jax.random.split(key, 3)
    rff = None
    if cfg.is_fzoos:
        rff = rfflib.make_rff(rff_key if rff_key is not None else k_rff, cfg.n_features, cfg.dim, cfg.lengthscale)

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
            cohort=cohort, cohort_seed=cohort_seed,
            diag_global_grad=diag_global_grad,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            eval_every=eval_every, async_checkpoint=async_checkpoint,
            faults=faults, max_rollbacks=max_rollbacks,
        )
        return res

    states = init_states(cfg, k_init, x0)

    if chunk is None or chunk > 0:
        from repro.core import rounds as rounds_mod  # deferred: avoids cycle

        if chunk is None:
            chunk = rounds_mod.DEFAULT_CHUNK
        _, res = rounds_mod.run_rounds(
            cfg, rff, query_fn, cobjs, states, x0, global_value_fn,
            rounds, chunk, diag_global_grad=diag_global_grad,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            eval_every=eval_every, async_checkpoint=async_checkpoint,
            faults=faults, max_rollbacks=max_rollbacks,
        )
        return res

    if checkpoint_dir:
        raise ValueError("checkpoint_dir requires the scan driver (chunk != 0)")
    if faults is not None:
        # Loop oracle matches the scan engine: a never-active window runs
        # the faults-free body (see rounds.run_rounds).
        from repro.faults.injector import effective_config
        faults = effective_config(faults, rounds)
    mean_fn = lambda tree: jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), tree)

    if faults is None:
        round_jit = jax.jit(
            lambda states, sx: run_round(cfg, rff, query_fn, cobjs, states, sx, mean_fn, diag_global_grad)
        )
    else:
        sum_fn = lambda a: jnp.sum(a, axis=0)
        round_jit = jax.jit(
            lambda states, sx, r: run_round(
                cfg, rff, query_fn, cobjs, states, sx, mean_fn, diag_global_grad,
                sum_fn=sum_fn, faults=faults, round_idx=r,
            )
        )

    if cfg.deferred or faults is not None:
        from repro.core import rounds as rounds_mod  # deferred: avoids cycle

    xs = [x0]
    fvals = [global_value_fn(cobjs, x0)]
    queries, coss, disps, rrs, reps = [], [], [], [], []
    drops, quars = [], []
    sx = x0
    for r in range(rounds):
        if faults is None:
            states, stats = round_jit(states, sx)
        else:
            states, stats = round_jit(states, sx, jnp.asarray(r, jnp.int32))
        if cfg.deferred:
            # Loop oracle for the scan engine's chunk boundary: repair after
            # every round (the chunk=1 degenerate case of the deferred
            # contract -- flags never persist across rounds here).
            states, _ = rounds_mod.repair_flagged_clients(states, cfg)
        sx = stats.server_x
        if faults is not None and faults.tolerate:
            # Loop oracle for the boundary quarantine reset (host-read flag,
            # chunk=1 degenerate cadence -- see rounds.quarantine_reset_flagged)
            states, _ = rounds_mod.quarantine_reset_flagged(states, cfg, sx)
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

    return SimResult(
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


def optimal_gamma_star(
    grad_f_global: jax.Array, g_local: jax.Array, correction: jax.Array
) -> jax.Array:
    """Prop. 1 closed-form optimal correction length gamma*."""
    drift = grad_f_global - g_local
    denom = jnp.sum(correction * correction)
    return jnp.dot(drift, correction) / jnp.maximum(denom, 1e-30)


def disparity(ghat: jax.Array, grad_f_global: jax.Array) -> jax.Array:
    """Xi = ||ghat - grad F||^2 (Sec. 3.2)."""
    return jnp.sum((ghat - grad_f_global) ** 2)
