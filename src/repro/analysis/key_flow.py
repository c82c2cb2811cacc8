"""PRNG key-flow lint: jaxpr dataflow over key values (DESIGN.md Sec. 7).

Under jax's counter-mode PRNG, *deriving* from a key (``split`` /
``fold_in``) and *sampling* from it (``random_bits``, the primitive every
``jax.random`` sampler bottoms out in) walk the same counter stream: the
keys ``split(k)`` returns are literally the first blocks ``uniform(k, ...)``
would also draw.  A key consumed by two primitives therefore correlates
streams that the algorithm treats as independent -- the bug class that
silently breaks the sim == distributed identity and any seed-replay
protocol built on fold_in discipline.

This module walks a (closed) jaxpr as an abstract interpreter over key
identities:

* producers -- ``random_seed`` (``PRNGKey``), ``random_wrap``,
  ``random_split``, ``random_fold_in`` -- create identity nodes; two
  derivations with the SAME parent and the SAME static parameters (e.g.
  ``fold_in(k, 1)`` twice) collapse to one node, so their consumers are
  correctly seen as consuming one key;
* views -- ``random_unwrap`` / re-``wrap``, ``reshape``, ``squeeze``,
  ``transpose``, ``broadcast_in_dim`` -- alias the node; static ``slice``
  selects a per-parameter child (``ks[:, 0]`` vs ``ks[:, 1]`` are distinct
  keys; the same slice twice is the same key);
* consumers -- ``random_*`` samplers record a *sample* use,
  ``split``/``fold_in`` record a *derive* use;
* control flow -- the walker recurses through ``pjit``/custom-call
  sub-jaxprs with argument binding, through ``cond`` branches and
  ``while`` bodies, and gives ``scan`` special treatment: a carried key
  that is sampled in the body and returned to the carry UNCHANGED is the
  ``key-carry-unsplit`` rule (every iteration re-draws the same stream);
  jax forwards such a carry into the scan's consts, so a const key the
  body samples directly is the same finding.

Findings:

* ``key-reuse``         -- a key with >= 2 sample uses, or a sample use
                           plus a later derivation (or vice versa);
* ``key-carry-unsplit`` -- a scan carry key sampled in the body and
                           threaded through unchanged;
* ``key-constant``      -- a sampler whose key has no dataflow from the
                           entry point's inputs (a hard-coded seed baked
                           into the traced program).

Suppression: a finding whose reported source line (or the line above it,
for wrapped statements) carries a ``# key-flow: ok (reason)`` comment is
moved to the report's ``suppressed`` list -- the mechanism the repo uses
to document the audited, intentional exceptions in ``core/algorithms.py``.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import re
from typing import Any, Optional

from jax.extend import core as jcore

from repro.analysis.jaxpr_lint import Violation, source_of

#: Primitives that create or transform key identities.
_SEED = "random_seed"
_WRAP = "random_wrap"
_UNWRAP = "random_unwrap"
_CLONE = "random_clone"
_SPLIT = "random_split"
_FOLD = "random_fold_in"
_SAMPLER_EXEMPT = frozenset({_SEED, _WRAP, _UNWRAP, _CLONE, _SPLIT, _FOLD})

#: Pure element-preserving views: the out value IS the in key (set).
_ALIAS_VIEWS = frozenset({_UNWRAP, _CLONE, "reshape", "squeeze", "transpose",
                          "broadcast_in_dim", "copy", "rev"})

#: Call-like primitives whose single sub-jaxpr binds 1:1 to the eqn invars.
_CALL_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

_SUPPRESS_RE = re.compile(r"#\s*key-flow:\s*ok\b")
_SRC_RE = re.compile(r"(/?[\w./-]+\.py):(\d+)")


@dataclasses.dataclass
class _Use:
    kind: str  # "sample" | "derive"
    source: str
    path: tuple[str, ...]
    order: int


@dataclasses.dataclass
class _KeyNode:
    nid: int
    origin: str  # source location of the creating equation
    tainted: bool  # has dataflow from the entry point's inputs
    uses: list[_Use] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class KeyFlowReport:
    """Full key-flow analysis result for one entry point."""

    violations: list[Violation]
    suppressed: list[Violation]
    n_keys: int
    n_samples: int


class _Analysis:
    def __init__(self):
        self.nodes: dict[int, _KeyNode] = {}
        self.children: dict[tuple[int, Any], int] = {}
        self.order = itertools.count()
        self.carry_unsplit: list[Violation] = []
        # (call-site source, is_jax_internal) per entered call-like eqn.
        # jax.random samplers trace their `_uniform`-style inner fn ONCE and
        # cache it, so eqn source info inside the sub-jaxpr points at the
        # FIRST trace site ever -- attribute uses inside an internal pjit to
        # the pjit's own call site instead.
        self.call_stack: list[tuple[str, bool]] = []

    def new_node(self, origin: str, tainted: bool) -> int:
        nid = len(self.nodes)
        self.nodes[nid] = _KeyNode(nid, origin, tainted)
        return nid

    def child(self, parent: int, sig: Any, origin: str) -> int:
        key = (parent, sig)
        nid = self.children.get(key)
        if nid is None:
            nid = self.new_node(origin, self.nodes[parent].tainted)
            self.children[key] = nid
        return nid

    def src(self, eqn) -> str:
        """Attribution source: the user-visible call site.  If the walker is
        inside a chain of jax-internal pjits, the site where user code
        entered that chain; otherwise the equation's own source."""
        site = None
        for s, internal in reversed(self.call_stack):
            if not internal:
                break
            site = s
        return site if site is not None else source_of(eqn)

    def use(self, nid: int, kind: str, eqn, path) -> None:
        self.nodes[nid].uses.append(
            _Use(kind, self.src(eqn), path, next(self.order)))

    # -- the walker --------------------------------------------------------

    def walk(self, jaxpr: jcore.Jaxpr, env: dict, taint: dict,
             path: tuple[str, ...]) -> None:
        """``env``: Var -> node id for key-typed values; ``taint``: Var ->
        bool dataflow-from-inputs.  Both are per-jaxpr scopes seeded by the
        caller."""

        def node_of(v) -> Optional[int]:
            return env.get(v) if isinstance(v, jcore.Var) else None

        def taint_of(v) -> bool:
            return bool(taint.get(v)) if isinstance(v, jcore.Var) else False

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_taint = any(taint_of(v) for v in eqn.invars)
            for ov in eqn.outvars:
                taint[ov] = in_taint
            src = self.src(eqn)

            if prim == _SEED:
                env[eqn.outvars[0]] = self.new_node(src, in_taint)
            elif prim == _WRAP:
                raw = eqn.invars[0]
                nid = node_of(raw)
                if nid is None:
                    nid = self.new_node(src, in_taint)
                    if isinstance(raw, jcore.Var):
                        env[raw] = nid  # pass-through detection (scan carry)
                env[eqn.outvars[0]] = nid
            elif prim == _SPLIT:
                nid = node_of(eqn.invars[0])
                if nid is not None:
                    self.use(nid, "derive", eqn, path)
                    sig = ("split", repr(sorted(eqn.params.items())))
                    env[eqn.outvars[0]] = self.child(nid, sig, src)
            elif prim == _FOLD:
                nid = node_of(eqn.invars[0])
                if nid is not None:
                    self.use(nid, "derive", eqn, path)
                    data = eqn.invars[1]
                    if isinstance(data, jcore.Literal):
                        sig = ("fold_in", repr(data.val))
                    else:
                        sig = ("fold_in_dyn", id(eqn))  # traced data: unique
                    child = self.child(nid, sig, src)
                    if taint_of(data):
                        self.nodes[child].tainted = True
                    env[eqn.outvars[0]] = child
            elif prim in _ALIAS_VIEWS:
                nid = node_of(eqn.invars[0])
                if nid is not None:
                    env[eqn.outvars[0]] = nid
            elif prim == "slice":
                nid = node_of(eqn.invars[0])
                if nid is not None:
                    sig = ("slice", repr(sorted(eqn.params.items())))
                    env[eqn.outvars[0]] = self.child(nid, sig, src)
            elif prim in ("dynamic_slice", "gather"):
                nid = node_of(eqn.invars[0])
                if nid is not None:
                    # data-dependent selection: a fresh key per equation
                    env[eqn.outvars[0]] = self.child(nid, (prim, id(eqn)), src)
            elif prim.startswith("random_") and prim not in _SAMPLER_EXEMPT:
                nid = node_of(eqn.invars[0])
                if nid is not None:
                    self.use(nid, "sample", eqn, path)
            elif prim == "scan":
                self._walk_scan(eqn, env, taint, path)
            elif prim == "while":
                self._walk_while(eqn, env, taint, path)
            elif prim == "cond":
                for br in eqn.params["branches"]:
                    self._walk_sub(br.jaxpr, eqn.invars[1:], eqn, env, taint,
                                   path + ("cond",), bind_out=False)
            else:
                sub = next(
                    (eqn.params[k] for k in _CALL_JAXPR_PARAMS
                     if k in eqn.params
                     and isinstance(eqn.params[k],
                                    (jcore.Jaxpr, jcore.ClosedJaxpr))),
                    None,
                )
                if sub is not None:
                    j = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) else sub
                    if len(j.invars) == len(eqn.invars):
                        self._walk_sub(j, eqn.invars, eqn, env, taint,
                                       path + (prim,), bind_out=True)

    def _walk_sub(self, body: jcore.Jaxpr, args, eqn, env, taint,
                  path, *, bind_out: bool) -> dict:
        sub_env = {bv: env[av] for bv, av in zip(body.invars, args)
                   if isinstance(av, jcore.Var) and av in env}
        sub_taint = {bv: taint.get(av, False)
                     for bv, av in zip(body.invars, args)
                     if isinstance(av, jcore.Var)}
        internal = (eqn.primitive.name == "pjit"
                    and str(eqn.params.get("name", "")).startswith("_"))
        self.call_stack.append((source_of(eqn), internal))
        try:
            self.walk(body, sub_env, sub_taint, path)
        finally:
            self.call_stack.pop()
        if bind_out:
            for ov, bv in zip(eqn.outvars, body.outvars):
                if isinstance(bv, jcore.Var) and bv in sub_env:
                    env[ov] = sub_env[bv]
                taint[ov] = taint.get(ov, False) or (
                    isinstance(bv, jcore.Var) and sub_taint.get(bv, False))
        return sub_env

    def _walk_while(self, eqn, env, taint, path) -> None:
        ncc = eqn.params["cond_nconsts"]
        nbc = eqn.params["body_nconsts"]
        cond = eqn.params["cond_jaxpr"].jaxpr
        body = eqn.params["body_jaxpr"].jaxpr
        carry = eqn.invars[ncc + nbc:]
        self._walk_sub(cond, eqn.invars[:ncc] + carry, eqn, env, taint,
                       path + ("while.cond",), bind_out=False)
        self._walk_sub(body, eqn.invars[ncc:ncc + nbc] + carry, eqn, env,
                       taint, path + ("while.body",), bind_out=False)

    def _walk_scan(self, eqn, env, taint, path) -> None:
        body = eqn.params["jaxpr"].jaxpr
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        sub_env: dict = {}
        sub_taint: dict = {}
        for bv, av in zip(body.invars[:nc], eqn.invars[:nc]):
            if isinstance(av, jcore.Var) and av in env:
                sub_env[bv] = env[av]
            sub_taint[bv] = taint.get(av, False) \
                if isinstance(av, jcore.Var) else False
        # uses each const key node had before the body ran (outer keys)
        uses_before = {bv: len(self.nodes[sub_env[bv]].uses)
                       for bv in body.invars[:nc] if bv in sub_env}
        # carry and per-iteration xs slots get fresh identities: each
        # iteration sees a different concrete value under one abstract var
        for bv, av in zip(body.invars[nc:], eqn.invars[nc:]):
            sub_taint[bv] = taint.get(av, False) \
                if isinstance(av, jcore.Var) else False
        self.call_stack.append((source_of(eqn), False))
        try:
            self.walk(body, sub_env, sub_taint, path + ("scan",))
        finally:
            self.call_stack.pop()
        # key-carry-unsplit, loop-invariant form: jax forwards a carry the
        # body returns unchanged into the scan's CONSTS, so an unsplit
        # carried key arrives as a const that the body samples directly --
        # the same draw at every iteration
        for bv in body.invars[:nc]:
            nid = sub_env.get(bv)
            if nid is None:
                continue
            fresh = self.nodes[nid].uses[uses_before.get(bv, 0):]
            samples = [u for u in fresh if u.kind == "sample"]
            if samples:
                u = samples[0]
                self.carry_unsplit.append(Violation(
                    rule="key-carry-unsplit",
                    message=(
                        "PRNG key sampled inside a scan body without a "
                        f"per-iteration split or fold_in (at {u.source}): "
                        "the key is loop-invariant, so every iteration "
                        "re-draws the same stream"
                    ),
                    source=u.source,
                    path=u.path,
                ))
        # key-carry-unsplit: the body wrapped a carried raw key (binding the
        # carry invar to its node), sampled it, and returned the SAME node
        # as the carry output
        for i in range(ncar):
            inv = body.invars[nc + i]
            outv = body.outvars[i]
            nid = sub_env.get(inv)
            if nid is None or not isinstance(outv, jcore.Var):
                continue
            if sub_env.get(outv) != nid:
                continue
            samples = [u for u in self.nodes[nid].uses if u.kind == "sample"]
            if samples:
                u = samples[0]
                self.carry_unsplit.append(Violation(
                    rule="key-carry-unsplit",
                    message=(
                        "PRNG key threaded UNSPLIT through a scan carry: "
                        f"sampled in the body (at {u.source}) and returned "
                        "to the carry unchanged, so every iteration "
                        "re-draws the same stream"
                    ),
                    source=u.source,
                    path=u.path,
                ))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _repo_roots() -> list[str]:
    here = os.path.dirname(os.path.abspath(__file__))  # .../src/repro/analysis
    src = os.path.dirname(os.path.dirname(here))
    return [os.getcwd(), src, os.path.dirname(src)]


def _suppressed_at(source: str) -> bool:
    """True if the reported source line (or the line above, for wrapped
    statements) carries a ``# key-flow: ok`` comment."""
    m = _SRC_RE.search(source)
    if not m:
        return False
    rel, lineno = m.group(1), int(m.group(2))
    candidates = [rel] if os.path.isabs(rel) else [
        os.path.join(root, rel) for root in _repo_roots()
    ]
    for cand in candidates:
        if not os.path.isfile(cand):
            continue
        try:
            with open(cand, encoding="utf-8") as f:
                lines = f.readlines()
        except OSError:  # pragma: no cover
            continue
        if 1 <= lineno <= len(lines) and _SUPPRESS_RE.search(lines[lineno - 1]):
            return True
        # walk upward through the contiguous comment block above the line
        ln = lineno - 1
        while 1 <= ln <= len(lines) and lines[ln - 1].lstrip().startswith("#"):
            if _SUPPRESS_RE.search(lines[ln - 1]):
                return True
            ln -= 1
    return False


def analyze_key_flow(closed: jcore.ClosedJaxpr) -> KeyFlowReport:
    """Run the key-flow lint over one closed jaxpr (an entry point traced
    with ``jax.make_jaxpr``).  Entry-point invars are the taint sources for
    the constant-key rule."""
    ana = _Analysis()
    jaxpr = closed.jaxpr
    taint = {v: True for v in jaxpr.invars}
    for v in jaxpr.constvars:
        taint[v] = False
    ana.walk(jaxpr, {}, taint, ())

    findings: list[Violation] = []
    n_samples = 0
    constant_origins: set[str] = set()
    for node in ana.nodes.values():
        uses = sorted(node.uses, key=lambda u: u.order)
        samples = [u for u in uses if u.kind == "sample"]
        n_samples += len(samples)
        if len(uses) >= 2 and samples:
            # multiple samples, or sample + derivation, of ONE key.  Two
            # derivations with distinct parameters are fine (distinct
            # streams); any pair involving a sample is a conflict.  Flag at
            # the LATER consumer of each conflicting pair.
            for i, u in enumerate(uses[1:], start=1):
                earlier = uses[:i]
                if not (u.kind == "sample"
                        or any(e.kind == "sample" for e in earlier)):
                    continue
                first = next(e for e in earlier
                             if u.kind == "sample" or e.kind == "sample")
                findings.append(Violation(
                    rule="key-reuse",
                    message=(
                        f"PRNG key consumed more than once: first use is a "
                        f"{first.kind} at {first.source}; this {u.kind} "
                        "re-consumes the same key (derivations and samples "
                        "of one key walk the same counter stream)"
                    ),
                    source=u.source,
                    path=u.path,
                ))
        if samples and not node.tainted and node.origin not in constant_origins:
            constant_origins.add(node.origin)
            findings.append(Violation(
                rule="key-constant",
                message=(
                    "sampler consumes a key with NO dataflow from the entry "
                    f"point's inputs (hard-coded seed created at "
                    f"{node.origin}; sampled at {samples[0].source}) -- the "
                    "drawn values are identical for every caller seed"
                ),
                source=node.origin,
                path=samples[0].path,
            ))
    findings.extend(ana.carry_unsplit)

    violations, suppressed = [], []
    for v in findings:
        (suppressed if _suppressed_at(v.source) else violations).append(v)
    return KeyFlowReport(violations=violations, suppressed=suppressed,
                         n_keys=len(ana.nodes), n_samples=n_samples)


def check_key_flow(closed: jcore.ClosedJaxpr) -> list[Violation]:
    """Contract-style entry: unsuppressed key-flow violations only."""
    return analyze_key_flow(closed).violations
