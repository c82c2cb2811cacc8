"""Recursive jaxpr linter (DESIGN.md Sec. 7).

Walks a (closed) jaxpr through every sub-jaxpr -- scan/cond/while bodies,
pjit calls, shard_map bodies, custom_jvp/vjp call jaxprs -- and checks
structural contracts that executing the program cannot reveal cheaply:

  * **forbidden primitives** (``find_forbidden``): e.g. no ``eigh`` in the
    scanned deferred-repair round body (the PR 3 acceptance criterion);
  * **host ops** (``find_host_ops``): callbacks and host transfers have no
    business inside a scanned round body -- any of them turns the
    zero-sync chunk into a per-iteration host round-trip;
  * **carry promotions** (``find_carry_promotions``): a
    ``convert_element_type`` that WIDENS a scan carry leaf is the
    structural signature of the PR 4 bf16->f32 optimizer bug class (the
    promoted value flows back into the carry, so the param's precision
    silently changes after step 1);
  * **i/o dtype preservation** (``check_io_dtypes``): paired input/output
    leaves (param in, updated param out) must keep their dtype;
  * **collective census** (``psum_census``): count ``psum`` equations by
    payload shape.  The paper's communication claim is per-round payload
    ``d + M`` floats; the census pins the number of array-payload psums
    (iterate + RFF weights) and scalar-payload psums (stats + eval pmean;
    ``lax.pmean`` lowers to a psum at jaxpr level) so a new collective
    cannot slip into the round body unnoticed.

Every violation carries the jaxpr source location of the offending
equation (``jax``'s own traceback summary), so ``python -m repro.analysis``
reports point at repo code, not at lowered soup.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Iterator, Optional, Sequence

import jax.numpy as jnp
from jax._src import source_info_util  # jaxpr source locations (jax internal)
from jax.extend import core as jcore

#: Callback primitives: every one of these re-enters Python from inside the
#: compiled program (and serializes the dispatch pipeline).
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "outside_call",  # legacy host_callback
})

#: Placement/transfer primitives that pin or move buffers mid-program.
TRANSFER_PRIMITIVES = frozenset({"device_put", "copy"})

#: Names the eigendecomposition lowers to at jaxpr level.
EIGH_PRIMITIVES = frozenset({"eigh"})


@dataclasses.dataclass(frozen=True)
class Violation:
    """One contract violation, locatable in repo source."""

    rule: str  # e.g. "no-eigh", "carry-promotion"
    message: str
    source: str = "<unknown>"  # jaxpr source location of the equation
    path: tuple[str, ...] = ()  # primitive path, e.g. ("scan", "cond")

    def __str__(self) -> str:
        ctx = "/".join(self.path) or "<top>"
        return f"[{self.rule}] {self.message}  (in {ctx}; at {self.source})"


def source_of(eqn) -> str:
    """Source location of a jaxpr equation (``<unknown>`` when it has none)."""
    if eqn.source_info is None:
        return "<unknown>"
    return source_info_util.summarize(eqn.source_info)


def _as_jaxpr(obj) -> Optional[jcore.Jaxpr]:
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jcore.Jaxpr):
        return obj
    return None


def subjaxprs(eqn) -> Iterator[jcore.Jaxpr]:
    """All sub-jaxprs reachable from one equation's params."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            j = _as_jaxpr(v)
            if j is not None:
                yield j


def iter_eqns(jaxpr, path: tuple[str, ...] = ()):
    """Yield ``(eqn, path)`` for every equation, recursing into sub-jaxprs.

    ``path`` is the chain of enclosing primitives, e.g. ``("scan", "cond")``
    for an equation inside a cond branch inside a scanned body.
    """
    j = _as_jaxpr(jaxpr)
    if j is None:
        raise TypeError(f"expected a (Closed)Jaxpr, got {type(jaxpr)!r}")
    for eqn in j.eqns:
        yield eqn, path
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub, path + (eqn.primitive.name,))


def count_primitives(jaxpr, names: Sequence[str]) -> Counter:
    """Occurrence count of each primitive name, recursively."""
    wanted = frozenset(names)
    c: Counter = Counter()
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name in wanted:
            c[eqn.primitive.name] += 1
    return c


def find_forbidden(jaxpr, forbidden: Sequence[str], rule: str = "forbidden-primitive") -> list[Violation]:
    """Every occurrence of a forbidden primitive, with source + context."""
    bad = frozenset(forbidden)
    out = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name in bad:
            out.append(Violation(
                rule=rule,
                message=f"primitive '{eqn.primitive.name}' is forbidden here",
                source=source_of(eqn),
                path=path,
            ))
    return out


def find_host_ops(jaxpr, *, include_transfers: bool = True) -> list[Violation]:
    """Host callbacks (and optionally placement/transfer ops) anywhere in
    the program.  ``include_transfers=False`` permits ``device_put`` for
    programs that legitimately re-place buffers (top-level drivers)."""
    names = set(HOST_CALLBACK_PRIMITIVES)
    if include_transfers:
        names |= TRANSFER_PRIMITIVES
    return find_forbidden(jaxpr, sorted(names), rule="host-op")


def _is_widening_float_convert(in_aval, out_aval) -> bool:
    din, dout = jnp.dtype(in_aval.dtype), jnp.dtype(out_aval.dtype)
    if not (jnp.issubdtype(din, jnp.floating) and jnp.issubdtype(dout, jnp.floating)):
        return False
    return dout.itemsize > din.itemsize


def find_carry_promotions(jaxpr) -> list[Violation]:
    """Widening ``convert_element_type`` applied DIRECTLY to a scan carry
    leaf, in any scan body at any depth.

    This is the structural signature of the PR 4 bug class: ``p - lr * g``
    with an f32 ``lr`` emits ``convert_element_type(p: bf16) -> f32`` on
    the carried param before the arithmetic, and the promoted result flows
    back into the carry -- training silently switches precision after the
    first step.  jax itself enforces carry-in == carry-out dtype, so the
    promotion always appears as this in-body convert, never as a carry
    dtype mismatch.
    """
    out = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params["jaxpr"].jaxpr
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        carry_vars = set(body.invars[nc:nc + ncar])
        for beqn, bpath in iter_eqns(body, path + ("scan",)):
            if beqn.primitive.name != "convert_element_type":
                continue
            (src_var,) = beqn.invars
            if isinstance(src_var, jcore.Var) and src_var in carry_vars \
                    and _is_widening_float_convert(src_var.aval, beqn.outvars[0].aval):
                out.append(Violation(
                    rule="carry-promotion",
                    message=(
                        f"scan carry leaf {src_var.aval.str_short()} widened to "
                        f"{beqn.outvars[0].aval.str_short()} inside the body "
                        "(param-precision drift: the promoted value flows back "
                        "into the carry)"
                    ),
                    source=source_of(beqn),
                    path=bpath,
                ))
    return out


def check_io_dtypes(closed: jcore.ClosedJaxpr, pairs: Sequence[tuple[int, int]]) -> list[Violation]:
    """Paired (input leaf index, output leaf index) must share a dtype.

    Use for param-like leaves of non-scan functions (e.g. optimizer
    updates: params in -> new params out), where there is no scan carry
    for jax to enforce the invariant on.
    """
    j = closed.jaxpr
    out = []
    for i, o in pairs:
        din = jnp.dtype(j.invars[i].aval.dtype)
        dout = jnp.dtype(j.outvars[o].aval.dtype)
        if din != dout:
            out.append(Violation(
                rule="dtype-drift",
                message=(
                    f"input leaf {i} ({din.name}) returns as output leaf {o} "
                    f"({dout.name}); param-like leaves must preserve dtype"
                ),
            ))
    return out


def psum_census(jaxpr) -> dict[str, int]:
    """Collective census at jaxpr level.

    Returns ``{"psum_array": ..., "psum_scalar": ..., <other collectives>}``.
    ``lax.pmean`` is psum + a static divide, so it contributes one psum;
    scalar vs array payload is what the communication claim cares about
    (the array psums ARE the per-round ``d + M``-float payload).
    """
    census = {"psum_array": 0, "psum_scalar": 0}
    others = ("ppermute", "all_gather", "all_to_all", "reduce_scatter", "pgather")
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "psum":
            for v in eqn.invars:
                if getattr(v.aval, "shape", ()) == ():
                    census["psum_scalar"] += 1
                else:
                    census["psum_array"] += 1
        elif name in others:
            census[name] = census.get(name, 0) + 1
    return census


def check_psum_census(jaxpr, expected: dict[str, int]) -> list[Violation]:
    """Census must match EXACTLY (missing expected keys count as 0)."""
    got = psum_census(jaxpr)
    out = []
    keys = set(got) | set(expected)
    for k in sorted(keys):
        g, e = got.get(k, 0), expected.get(k, 0)
        if g != e:
            out.append(Violation(
                rule="collective-census",
                message=f"{k}: expected {e} but the body lowers {g} "
                        "(a collective was added or removed from the round body)",
            ))
    return out


def eigh_only_behind_cond(jaxpr) -> list[Violation]:
    """Every ``eigh`` must sit behind a ``cond`` (rare-event gating): the
    boundary-repair executable may CARRY the repair eigh, but the
    all-healthy steady state must never execute it."""
    out = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name in EIGH_PRIMITIVES and "cond" not in path:
            out.append(Violation(
                rule="eigh-not-gated",
                message="eigh outside any cond branch: the steady state would "
                        "pay the factorization unconditionally",
                source=source_of(eqn),
                path=path,
            ))
    return out
