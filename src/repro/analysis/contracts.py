"""Per-engine compiled-program contracts (DESIGN.md Sec. 7).

Each engine configuration DECLARES its invariants here; ``runner.py`` (and
``python -m repro.analysis``) lowers every registered (algorithm,
engine-flag) combination from ``AlgoConfig`` -- via the same
``launch.common.make_config`` surface the launchers use -- and lints the
jaxpr + lowered HLO against the declaration, without executing anything.

Registered contracts (one line each; detection mechanism in parens):

  * fzoos deferred body, sim + dist: NO eigh (jaxpr primitive + HLO
    fingerprint), no host callbacks/transfers, no carry-dtype promotion;
    dist adds the collective census;
  * fzoos inline oracle body: eigh MUST be present (the oracle exists to
    demonstrate the contrast) but everything else holds;
  * fedzo / fedprox (FD family) bodies: eigh-free by construction, census
    pins 1 array psum (the iterate payload) on the dist path;
  * fzoos/fedzo FAULT-MASKED bodies, sim + dist: same rules AND the same
    collective census as the unmasked engines -- the live/quarantine counts
    ride inside the existing payload psums, so fault masking adds zero
    collectives and zero host ops to the round;
  * chunk step: every donated {ClientState, history} leaf is actually
    aliased input->output in the lowering (``tf.aliasing_output``), with
    and without the fault mask;
  * boundary repair: the repair eigh exists but ONLY behind a cond, and
    the donated factor buffers alias;
  * quarantine reset: the device-decided re-admission gate traces NO
    init-time linear algebra (the fresh-client template is eager) and
    donates the stacked state;
  * optimizers: sgd/adam/adamw updates preserve bf16 param dtype (the
    PR 4 drift class, checked on invar/outvar avals).

The census numbers are DECLARED from the communication claim, not
re-measured: 2 array-payload psums for fzoos (iterate x + RFF weights w =
the paper's ``d + M`` floats/round), 1 for the FD family (x only), plus 6
scalar psums (5 RoundStats reductions + the eval pmean, which lowers to a
psum).  Adding a collective to the round body is a PROTOCOL change and
must show up here as a deliberate diff.

``steady_state_guard`` / ``no_recompiles`` are the runtime complement: a
context manager that fails on unexpected executable compiles (cache
misses) and host ``device_get`` syncs inside a steady-state window --
subsuming the PR 4 zero-device_get assertion.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.analysis import hlo_audit, jaxpr_lint
from repro.analysis.jaxpr_lint import Violation

# ---------------------------------------------------------------------------
# Steady-state guard (recompiles + host syncs)
# ---------------------------------------------------------------------------

#: Monitoring event jax records once per backend executable compile.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_guard_lock = threading.Lock()
_active_guards: list["GuardState"] = []
_listener_installed = False


class SteadyStateViolation(AssertionError):
    """A steady-state window compiled or synced more than its contract allows."""


@dataclasses.dataclass
class GuardState:
    """Counters exposed to the ``with steady_state_guard() as g`` body."""

    compiles: int = 0
    device_gets: int = 0


def _on_event_duration(event: str, duration: float, **kw) -> None:
    del duration, kw
    if event == _COMPILE_EVENT:
        with _guard_lock:
            for g in _active_guards:
                g.compiles += 1


def _ensure_listener() -> None:
    global _listener_installed
    if not _listener_installed:
        jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
        _listener_installed = True


@contextlib.contextmanager
def steady_state_guard(
    *,
    allow_compiles: Optional[int] = None,
    allow_device_gets: Optional[int] = 0,
):
    """Fail if the enclosed code compiles / host-syncs beyond its budget.

    ``allow_compiles``: max executable compiles (compilation-cache misses)
    tolerated; ``None`` counts but does not enforce.  ``allow_device_gets``
    likewise for ``jax.device_get`` calls (the chunk-boundary host-sync
    class PR 4 eliminated).  Yields a ``GuardState`` whose counters are
    live, so callers can also assert richer conditions themselves.
    """
    _ensure_listener()
    st = GuardState()
    real_get = jax.device_get

    def spy(x):
        st.device_gets += 1
        return real_get(x)

    with _guard_lock:
        _active_guards.append(st)
    jax.device_get = spy
    try:
        yield st
    finally:
        jax.device_get = real_get
        with _guard_lock:
            _active_guards.remove(st)
    if allow_compiles is not None and st.compiles > allow_compiles:
        raise SteadyStateViolation(
            f"steady-state window compiled {st.compiles} executable(s) "
            f"(allowed {allow_compiles}): an executable cache miss is "
            "re-tracing inside the steady state"
        )
    if allow_device_gets is not None and st.device_gets > allow_device_gets:
        raise SteadyStateViolation(
            f"steady-state window issued {st.device_gets} jax.device_get "
            f"sync(s) (allowed {allow_device_gets}): the zero-sync boundary "
            "contract is broken"
        )


def no_recompiles(allow: int = 0):
    """Recompile guard only: fail on executable cache misses, ignore syncs."""
    return steady_state_guard(allow_compiles=allow, allow_device_gets=None)


# ---------------------------------------------------------------------------
# Contract registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Contract:
    """One declared invariant set over one lowered entry point."""

    name: str
    description: str
    check: Callable[[], list[Violation]]


CONTRACTS: dict[str, Contract] = {}


def register(name: str, description: str):
    def deco(fn: Callable[[], list[Violation]]):
        CONTRACTS[name] = Contract(name=name, description=description, check=fn)
        return fn

    return deco


def check_contract(name: str) -> list[Violation]:
    return CONTRACTS[name].check()


# -- shared fixtures (small shapes: lint cost, not run cost) ----------------


def _make_cfg(algo: str, **overrides):
    from repro.launch.common import make_config

    base = dict(dim=8, n_clients=4, local_steps=2, lengthscale=0.5)
    if algo == "fzoos":
        base.update(n_features=32, traj_capacity=32, active_per_iter=1,
                    active_candidates=8, active_round_end=1)
    else:
        base.update(q=4)
    base.update(overrides)
    return make_config(algo, **base)


@lru_cache(maxsize=None)
def _fixture(algo: str, defer_repair: bool):
    from repro.core import algorithms as alg
    from repro.core import objectives as obj
    from repro.core import rff as rfflib

    cfg = _make_cfg(algo, defer_repair=defer_repair)
    quad = obj.make_quadratic(jax.random.PRNGKey(0), cfg.n_clients, cfg.dim,
                              2.0, 0.001)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    rff = None
    if cfg.is_fzoos:
        rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, cfg.dim,
                              cfg.lengthscale)
    states = alg.init_states(cfg, jax.random.PRNGKey(2), x0)
    return cfg, rff, quad, states, x0


@lru_cache(maxsize=None)
def _mesh():
    from repro.launch.mesh import make_mesh

    return make_mesh((1,), ("data",))


def _fault_fixture():
    """The tolerant fault schedule every faulted contract lowers with:
    nonzero drop + poison rates so the mask, the packed-count payload and
    the quarantine logic are all live in the traced program."""
    from repro.faults import FaultConfig

    return FaultConfig(seed=0, drop_rate=0.25, nan_rate=0.25, tolerate=True)


def _chunk_fn(algo: str, defer_repair: bool, distributed: bool, length: int = 2,
              faulted: bool = False):
    from repro.core import objectives as obj
    from repro.core import rounds as rounds_mod

    cfg, rff, quad, states, x0 = _fixture(algo, defer_repair)
    faults = _fault_fixture() if faulted else None
    if distributed:
        cf = rounds_mod.dist_chunk_fn(cfg, _mesh(), rff, obj.quadratic_query,
                                      obj.quadratic_global_value, length, 1, 4,
                                      faults=faults)
    else:
        cf = rounds_mod.sim_chunk_fn(cfg, rff, obj.quadratic_query,
                                     obj.quadratic_global_value, None, length,
                                     1, 4, faults=faults)
    args = (states, quad, x0, jnp.int32(0))
    return cf, args


@lru_cache(maxsize=None)
def _body_artifacts(algo: str, defer_repair: bool, distributed: bool,
                    faulted: bool = False):
    """(closed jaxpr, lowered stablehlo text) of one scanned chunk body."""
    cf, args = _chunk_fn(algo, defer_repair, distributed, faulted=faulted)
    closed = jax.make_jaxpr(cf)(*args)
    text = jax.jit(cf).lower(*args).as_text()
    return closed, text


#: Scalar psums every distributed round body carries: the five RoundStats
#: reductions (cos, disparity, queries, refactor, repair) + the eval pmean.
_SCALAR_PSUMS = 6


def _body_rules(
    closed,
    text,
    *,
    expect_eigh: bool,
    census: Optional[dict[str, int]],
) -> list[Violation]:
    out: list[Violation] = []
    if expect_eigh:
        # the oracle body must DEMONSTRABLY carry the inline eigh, or the
        # no-eigh assertions elsewhere are vacuous
        if not jaxpr_lint.count_primitives(closed, jaxpr_lint.EIGH_PRIMITIVES):
            out.append(Violation(
                rule="oracle-eigh-missing",
                message="inline-cond oracle body lowered WITHOUT eigh; the "
                        "deferred/inline contrast is no longer being tested",
            ))
        if not hlo_audit.contains_eigh(text):
            out.append(Violation(
                rule="oracle-eigh-missing",
                message="inline-cond oracle HLO carries no eigh custom call",
            ))
    else:
        out += jaxpr_lint.find_forbidden(closed, jaxpr_lint.EIGH_PRIMITIVES,
                                         rule="no-eigh")
        out += hlo_audit.check_no_eigh(text, where="scanned round body")
    out += jaxpr_lint.find_host_ops(closed)
    out += jaxpr_lint.find_carry_promotions(closed)
    if census is not None:
        out += jaxpr_lint.check_psum_census(closed, census)
    else:
        # the vmapped sim body must stay collective-free outright
        out += jaxpr_lint.check_psum_census(closed, {})
    return out


def _register_engine(key: str, algo: str, defer_repair: bool,
                     expect_eigh: bool, n_array_psums: int,
                     faulted: bool = False) -> None:
    for dist in (False, True):
        mode = "distributed" if dist else "simulate"
        census = (
            {"psum_array": n_array_psums, "psum_scalar": _SCALAR_PSUMS}
            if dist else None
        )

        def chk(d=dist, c=census):
            closed, text = _body_artifacts(algo, defer_repair, d, faulted)
            return _body_rules(closed, text, expect_eigh=expect_eigh, census=c)

        register(
            f"{key}/{mode}",
            f"{key} scanned round body ({mode}): "
            + ("eigh present (oracle)" if expect_eigh else "eigh-free")
            + ", no host ops, no carry promotion"
            + (f", census {census}" if census else ", collective-free"),
        )(chk)


# FZooS deferred engine (the default): the tentpole no-eigh contract.
_register_engine("fzoos-deferred", "fzoos", defer_repair=True,
                 expect_eigh=False, n_array_psums=2)
# FZooS inline-cond oracle: eigh must remain visible (contrast witness).
_register_engine("fzoos-inline", "fzoos", defer_repair=False,
                 expect_eigh=True, n_array_psums=2)
# FD family: eigh-free by construction, iterate-only array payload.
_register_engine("fedzo", "fedzo", defer_repair=True,
                 expect_eigh=False, n_array_psums=1)
_register_engine("fd-fedprox", "fedprox", defer_repair=True,
                 expect_eigh=False, n_array_psums=1)
# Fault-masked engines: the census is UNCHANGED vs the unmasked bodies --
# the live/quarantine counts ride inside the existing payload psums, so
# masking adds zero collectives (and zero host ops) to the round.
_register_engine("fzoos-faults", "fzoos", defer_repair=True,
                 expect_eigh=False, n_array_psums=2, faulted=True)
_register_engine("fedzo-faults", "fedzo", defer_repair=True,
                 expect_eigh=False, n_array_psums=1, faulted=True)


# -- partial-participation cohort engine (core/pool.py) ---------------------


def _pool_chunk_fn(algo: str, distributed: bool, length: int = 2):
    """The cohort chunk body EXACTLY as run_pooled_rounds builds it: the
    round body compiles against the K-client cohort config and the masked
    zero-rate sum_fn path (participation-weighted aggregation)."""
    import dataclasses as _dc

    from repro.core import objectives as obj
    from repro.core import pool as pool_mod
    from repro.core import rounds as rounds_mod
    from repro.faults import FaultConfig

    cfg, rff, quad, states, x0 = _fixture(algo, True)
    cohort = cfg.n_clients // 2
    ccfg = _dc.replace(cfg, n_clients=cohort)
    bcfg = FaultConfig()  # zero rates: the pooled faults=None body
    pool = pool_mod.ClientPool.from_states(states)
    idx = pool_mod.sample_cohort(0, 0, cfg.n_clients, cohort)
    mesh = _mesh() if distributed else None
    cstates = pool.gather(idx, mesh=mesh)
    c_quad = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[jnp.asarray(idx)], quad)
    if distributed:
        cf = rounds_mod.dist_chunk_fn(ccfg, mesh, rff, obj.quadratic_query,
                                      obj.quadratic_global_value, length, 1, 4,
                                      faults=bcfg)
    else:
        cf = rounds_mod.sim_chunk_fn(ccfg, rff, obj.quadratic_query,
                                     obj.quadratic_global_value, None, length,
                                     1, 4, faults=bcfg)
    return cf, (cstates, c_quad, x0, jnp.int32(0))


def _register_pool_engine(key: str, algo: str, n_array_psums: int) -> None:
    """Cohort-engine census contract: the K-client cohort body must carry
    EXACTLY the dense engine's collective count -- the participation
    weighting rides inside the existing payload psums, so partial
    participation changes the denominator, never the protocol."""
    for dist in (False, True):
        mode = "distributed" if dist else "simulate"
        census = (
            {"psum_array": n_array_psums, "psum_scalar": _SCALAR_PSUMS}
            if dist else None
        )

        def chk(d=dist, c=census):
            cf, args = _pool_chunk_fn(algo, d)
            closed = jax.make_jaxpr(cf)(*args)
            text = jax.jit(cf).lower(*args).as_text()
            return _body_rules(closed, text, expect_eigh=False, census=c)

        register(
            f"{key}/{mode}",
            f"{key} cohort round body ({mode}): eigh-free, no host ops, "
            + (f"census {census} == dense engine" if census
               else "collective-free"),
        )(chk)


_register_pool_engine("fzoos-pool", "fzoos", n_array_psums=2)
_register_pool_engine("fedzo-pool", "fedzo", n_array_psums=1)


def _chunk_step_donation(distributed: bool, faulted: bool = False) -> list[Violation]:
    from repro.core import rounds as rounds_mod

    cf, (states, quad, x0, off) = _chunk_fn("fzoos", True, distributed,
                                            faulted=faulted)
    hist = rounds_mod.history_init(4, x0, jnp.zeros((), jnp.float32))
    step = rounds_mod.make_chunk_step(cf)
    text = step.lower(states, hist, quad, x0, off).as_text()
    n_leaves = len(jax.tree_util.tree_leaves((states, hist)))
    where = "distributed" if distributed else "simulate"
    if faulted:
        where += ", faulted"
    return hlo_audit.check_donation(text, n_leaves, where=f"chunk step ({where})")


register(
    "chunk-step-donation/simulate",
    "every donated {ClientState, history} leaf aliases input->output",
)(lambda: _chunk_step_donation(False))
register(
    "chunk-step-donation/distributed",
    "donation survives the shard_map lowering of the chunk step",
)(lambda: _chunk_step_donation(True))
register(
    "chunk-step-donation/faulted",
    "the fault-masked chunk step (incl. mask/quarantine leaves) still "
    "donates every {ClientState, history} leaf",
)(lambda: _chunk_step_donation(False, faulted=True))
register(
    "chunk-step-donation/faulted-distributed",
    "faulted chunk-step donation survives the shard_map lowering",
)(lambda: _chunk_step_donation(True, faulted=True))


@register(
    "quarantine-reset",
    "device-decided quarantine reset: NO init-time linear algebra traced "
    "(eager template), no host ops; donated state leaves alias",
)
def _quarantine_reset_contract() -> list[Violation]:
    from repro.core import rounds as rounds_mod

    cfg, _, _, states, x0 = _fixture("fzoos", True)
    fn = rounds_mod._quarantine_reset_exec(cfg, None, states.x.shape)
    closed = jax.make_jaxpr(fn)(states, x0)
    out = jaxpr_lint.find_forbidden(closed, jaxpr_lint.EIGH_PRIMITIVES,
                                    rule="no-eigh")
    out += jaxpr_lint.find_host_ops(closed)
    text = fn.lower(states, x0).as_text()
    out += hlo_audit.check_no_eigh(text, where="quarantine reset")
    n_leaves = len(jax.tree_util.tree_leaves(states))
    out += hlo_audit.check_donation(text, n_leaves, where="quarantine reset")
    return out


@register(
    "boundary-repair",
    "repair eigh exists ONLY behind cond; donated factor buffers alias",
)
def _boundary_repair_contract() -> list[Violation]:
    from repro.core import gp_surrogate as gp

    _, _, _, states, _ = _fixture("fzoos", True)
    closed = jax.make_jaxpr(gp.factor_repair_gated)(states.factor,
                                                    jnp.float32(1e-4))
    out = jaxpr_lint.eigh_only_behind_cond(closed)
    if not jaxpr_lint.count_primitives(closed, jaxpr_lint.EIGH_PRIMITIVES):
        out.append(Violation(
            rule="oracle-eigh-missing",
            message="boundary repair lost its eigh: flagged Grams would "
                    "never be refactorized",
        ))
    jitted = jax.jit(gp.factor_repair_gated, donate_argnums=0)
    text = jitted.lower(states.factor, jnp.float32(1e-4)).as_text()
    n_leaves = len(jax.tree_util.tree_leaves(states.factor))
    out += hlo_audit.check_donation(text, n_leaves, where="boundary repair")
    return out


@register(
    "optimizer-dtype",
    "sgd/adam/adamw updates preserve bf16 param dtype (PR 4 drift class)",
)
def _optimizer_dtype_contract() -> list[Violation]:
    from repro.optim import make_optimizer

    out: list[Violation] = []
    for name in ("sgd", "adam", "adamw"):
        opt_init, opt_update = make_optimizer(name)
        p = jnp.zeros((4,), jnp.bfloat16)
        state = opt_init(p)
        g = jnp.zeros((4,), jnp.float32)
        closed = jax.make_jaxpr(
            lambda s, gg, pp: opt_update(s, gg, pp, 0.01)
        )(state, g, p)
        # flat leaf indices: params are the LAST input leaf; the updated
        # params are the FIRST output leaf ((new_params, new_state) order)
        n_in = len(jax.tree_util.tree_leaves((state, g, p)))
        for v in jaxpr_lint.check_io_dtypes(closed, [(n_in - 1, 0)]):
            out.append(dataclasses.replace(
                v, message=f"{name}: {v.message}"))
    return out


# ---------------------------------------------------------------------------
# Static kernel-launch contracts (analysis/kernel_audit.py over KernelSpec)
# ---------------------------------------------------------------------------

#: Audit shapes: small enough to enumerate the grid instantly, large enough
#: that every kernel is genuinely tiled (several blocks per axis).
_KAUDIT_N, _KAUDIT_CAP, _KAUDIT_D = 64, 512, 32
_KAUDIT_BN, _KAUDIT_BC = 16, 128
_KAUDIT_NB = 4  # client batch of the *_clients variants


def _register_kernel_contract(key: str, make_specs, description: str) -> None:
    def chk():
        from repro.analysis import kernel_audit

        out: list[Violation] = []
        for spec in make_specs():
            out += kernel_audit.audit_spec(spec)
        return out

    register(f"kernel/{key}", description)(chk)


def _gp_specs(builder, *, tiled: bool, clients: bool):
    """The f32 spec plus -- for the tiled accumulator kernels -- the bf16
    variant, which must keep its scratch accumulators in f32."""

    def make():
        shape = (_KAUDIT_N, _KAUDIT_CAP if tiled else _KAUDIT_BC, _KAUDIT_D)
        blocks = {"block_n": _KAUDIT_BN}
        if tiled:
            blocks["block_cap"] = _KAUDIT_BC
        dtypes = (jnp.float32, jnp.bfloat16) if tiled else (jnp.float32,)
        for dt in dtypes:
            if clients:
                yield builder(_KAUDIT_NB, *shape, dt, **blocks)
            else:
                yield builder(*shape, dt, **blocks)

    return make


def _register_gp_kernel_contracts() -> None:
    from repro.kernels import gp_grad, gp_score

    for mod, stem in ((gp_score, "gp-score"), (gp_grad, "gp-grad")):
        pre = "score" if stem == "gp-score" else "grad"
        for variant, tiled, clients in (
            ("resident", False, False),
            ("clients", False, True),
            ("tiled", True, False),
            ("tiled-clients", True, True),
        ):
            builder = getattr(mod, f"{pre}_{variant.replace('-', '_')}_spec")
            _register_kernel_contract(
                f"{stem}-{variant}",
                _gp_specs(builder, tiled=tiled, clients=clients),
                f"{stem}.{variant} launch geometry: race-free, covered, "
                "in-bounds, accumulator-disciplined, in VMEM budget"
                + (" (f32 + bf16-in/f32-scratch)" if tiled else ""),
            )


_register_gp_kernel_contracts()

def _rff_features_specs():
    from repro.kernels.rff_features import features_spec

    return [features_spec(128, 256, _KAUDIT_D, jnp.float32,
                          block_n=64, block_m=128)]


def _rff_grad_specs():
    from repro.kernels.rff_grad import grad_spec

    return [grad_spec(128, 256, _KAUDIT_D, jnp.float32,
                      block_n=64, block_m=128)]


def _sqexp_specs():
    from repro.kernels.sqexp import sqexp_spec

    return [sqexp_spec(128, 256, _KAUDIT_D, jnp.float32,
                       block_n=64, block_m=128)]


_register_kernel_contract(
    "rff-features", _rff_features_specs,
    "rff_features launch geometry: one writer per output tile, in budget",
)
_register_kernel_contract(
    "rff-grad", _rff_grad_specs,
    "rff_grad launch geometry: M-axis reduction accumulates in the output "
    "ref (f32 only: the output IS the accumulator, so bf16 would trip "
    "kernel-accum-dtype -- see tests)",
)
_register_kernel_contract(
    "sqexp", _sqexp_specs,
    "sqexp launch geometry: one writer per output tile, in budget",
)


@register(
    "kernel/autotune-candidates",
    "every block pair the tuner's feasibility filter can emit (score + "
    "grad, f32 + bf16, cap=1024) fits the TPU VMEM budget as a real "
    "KernelSpec launch",
)
def _autotune_candidates_contract() -> list[Violation]:
    import numpy as np

    from repro.analysis import kernel_audit
    from repro.kernels import autotune
    from repro.kernels.gp_grad import grad_tiled_spec
    from repro.kernels.gp_score import score_tiled_spec
    from repro.launch.mesh import TPU_V5E, roofline

    hw = roofline(TPU_V5E)
    n, cap, d = 256, 1024, 64
    out: list[Violation] = []
    for kind, builder in (("score", score_tiled_spec),
                          ("grad", grad_tiled_spec)):
        for dt in (jnp.float32, jnp.bfloat16):
            itemsize = np.dtype(dt).itemsize
            for bn, bc in autotune._feasible(kind, n, cap, d, hw, itemsize):
                if bc > cap:
                    continue  # routes to the resident kernel, not this spec
                spec = builder(n, cap, d, dt, block_n=bn, block_cap=bc)
                out += kernel_audit.check_vmem(spec, device_kind=TPU_V5E)
    return out


# ---------------------------------------------------------------------------
# PRNG key-flow contracts (analysis/key_flow.py over engine entry points)
# ---------------------------------------------------------------------------


def _register_key_flow(key: str, algo: str, defer_repair: bool) -> None:
    @register(
        f"key-flow/{key}",
        f"{key} round body: no PRNG key consumed twice, no key threaded "
        "unsplit through a scan carry, no unsuppressed hard-coded seed",
    )
    def _chk() -> list[Violation]:
        from repro.analysis import key_flow

        closed, _ = _body_artifacts(algo, defer_repair, False)
        return key_flow.check_key_flow(closed)


_register_key_flow("fzoos-deferred", "fzoos", True)
_register_key_flow("fzoos-inline", "fzoos", False)
_register_key_flow("fedzo", "fedzo", True)
_register_key_flow("fd-fedprox", "fedprox", True)


@register(
    "key-flow/init-states",
    "init_states: the constant direction-bank key (Prop. D.4) is the ONLY "
    "hard-coded seed, and it is explicitly suppressed in source",
)
def _init_states_key_flow() -> list[Violation]:
    from repro.analysis import key_flow
    from repro.core import algorithms as alg

    cfg, _, _, _, x0 = _fixture("fzoos", True)
    closed = jax.make_jaxpr(
        lambda key, x: alg.init_states(cfg, key, x)
    )(jax.random.PRNGKey(2), x0)
    report = key_flow.analyze_key_flow(closed)
    out = list(report.violations)
    if not report.suppressed:
        out.append(Violation(
            rule="key-flow-suppression-missing",
            message="init_states no longer carries the suppressed "
                    "constant-bank finding; if the bank key became "
                    "caller-derived, Prop. D.4 (identical banks across "
                    "clients) needs a new witness",
        ))
    return out
