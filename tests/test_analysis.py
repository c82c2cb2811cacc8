"""Static-analysis subsystem tests (repro.analysis, DESIGN.md Sec. 7).

Two halves:

  * **negative suite** -- one deliberately-violating program per rule
    (inline eigh in a scan body, bf16 carry promoted, un-donated buffer,
    extra psum vs the declared census, host callback in a scanned body;
    plus one violating ``KernelSpec`` per kernel-audit rule and one seeded
    PRNG misuse per key-flow rule), each caught WITH a source location --
    this file for jaxpr rules, kernel name + grid cell for launch rules;
  * **positive gate** -- every shipping contract in the registry lints
    clean, and the ``python -m repro.analysis`` CLI round-trips.
"""

import io
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    SteadyStateViolation,
    check_all,
    no_recompiles,
    steady_state_guard,
)
from repro.analysis import hlo_audit, jaxpr_lint, kernel_audit, key_flow
from repro.kernels.spec import ArraySpec, BlockDecl, KernelSpec, ScratchDecl
from repro.launch.mesh import TPU_V5E


# ---------------------------------------------------------------------------
# Negative suite: each rule catches its seeded violation
# ---------------------------------------------------------------------------


def test_inline_eigh_in_scan_body_caught():
    def body(c, _):
        w, _v = jnp.linalg.eigh(c)  # the violation under test
        return c + jnp.diag(w), None

    closed = jax.make_jaxpr(
        lambda c: jax.lax.scan(body, c, None, length=2)
    )(jnp.eye(3, dtype=jnp.float32))
    vs = jaxpr_lint.find_forbidden(closed, jaxpr_lint.EIGH_PRIMITIVES,
                                   rule="no-eigh")
    assert len(vs) == 1
    assert vs[0].rule == "no-eigh"
    assert "scan" in vs[0].path  # located inside the scanned body
    assert "test_analysis" in vs[0].source  # points at repo source, not soup


def test_bf16_carry_promotion_caught():
    def body(p, g):
        p32 = p.astype(jnp.float32)  # the PR 4 drift signature
        return (p32 - 0.1 * g).astype(jnp.bfloat16), None

    gs = jnp.zeros((3, 4), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p: jax.lax.scan(body, p, gs)
    )(jnp.zeros((4,), jnp.bfloat16))
    vs = jaxpr_lint.find_carry_promotions(closed)
    assert len(vs) == 1
    assert vs[0].rule == "carry-promotion"
    assert "bfloat16" in vs[0].message and "float32" in vs[0].message
    assert "test_analysis" in vs[0].source
    # the clean version of the same update lints clean
    def ok_body(p, g):
        return p - (0.1 * g).astype(p.dtype), None
    clean = jax.make_jaxpr(
        lambda p: jax.lax.scan(ok_body, p, gs)
    )(jnp.zeros((4,), jnp.bfloat16))
    assert jaxpr_lint.find_carry_promotions(clean) == []


def test_dropped_donation_caught():
    """XLA silently drops a donation whose output has no shape/dtype-matched
    buffer; the audit turns the silence into a violation."""
    def f(a):
        return a.astype(jnp.float32) + 1.0

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax warns about the unused donation
        txt = jax.jit(f, donate_argnums=0).lower(
            jnp.zeros((4,), jnp.bfloat16)).as_text()
    assert hlo_audit.aliased_inputs(txt) == {}
    vs = hlo_audit.check_donation(txt, expected_aliased=1, where="seeded")
    assert len(vs) == 1 and vs[0].rule == "donation-dropped"

    # control: a dtype-preserving donated update aliases and lints clean
    txt_ok = jax.jit(lambda a: a + 1, donate_argnums=0).lower(
        jnp.zeros((4,), jnp.float32)).as_text()
    assert hlo_audit.check_donation(txt_ok, expected_aliased=1) == []


def test_extra_psum_vs_census_caught():
    def f(x):
        return jax.lax.psum(x, "i") + jax.lax.psum(x.sum(), "i")

    closed = jax.make_jaxpr(f, axis_env=[("i", 2)])(jnp.zeros((4,), jnp.float32))
    assert jaxpr_lint.psum_census(closed) == {"psum_array": 1, "psum_scalar": 1}
    assert jaxpr_lint.check_psum_census(
        closed, {"psum_array": 1, "psum_scalar": 1}) == []
    # declaring only the array psum makes the scalar one a violation...
    vs = jaxpr_lint.check_psum_census(closed, {"psum_array": 1})
    assert [v.rule for v in vs] == ["collective-census"]
    assert "psum_scalar" in vs[0].message
    # ...and a MISSING declared collective is equally a violation
    vs2 = jaxpr_lint.check_psum_census(
        closed, {"psum_array": 2, "psum_scalar": 1})
    assert len(vs2) == 1 and "psum_array" in vs2[0].message


def test_host_callback_in_scan_body_caught():
    def body(c, _):
        y = jax.pure_callback(
            lambda v: np.sin(v), jax.ShapeDtypeStruct((), jnp.float32), c)
        return c + y, None

    closed = jax.make_jaxpr(
        lambda c: jax.lax.scan(body, c, None, length=3)
    )(jnp.float32(0.0))
    vs = jaxpr_lint.find_host_ops(closed)
    assert any(v.rule == "host-op" and "pure_callback" in v.message
               and "scan" in v.path for v in vs)


def test_io_dtype_drift_caught():
    closed = jax.make_jaxpr(
        lambda p, g: (p.astype(jnp.float32) - g, None)
    )(jnp.zeros((4,), jnp.bfloat16), jnp.zeros((4,), jnp.float32))
    vs = jaxpr_lint.check_io_dtypes(closed, [(0, 0)])
    assert len(vs) == 1 and vs[0].rule == "dtype-drift"
    assert jaxpr_lint.check_io_dtypes(closed, [(1, 0)]) == []  # f32 -> f32


def test_ungated_eigh_caught():
    """eigh outside any cond: the steady state would pay it unconditionally."""
    closed = jax.make_jaxpr(lambda a: jnp.linalg.eigh(a)[0])(jnp.eye(3))
    vs = jaxpr_lint.eigh_only_behind_cond(closed)
    assert len(vs) == 1 and vs[0].rule == "eigh-not-gated"

    gated = jax.make_jaxpr(
        lambda a, flag: jax.lax.cond(
            flag, lambda m: jnp.linalg.eigh(m)[0], lambda m: m[:, 0], a)
    )(jnp.eye(3), jnp.asarray(True))
    assert jaxpr_lint.eigh_only_behind_cond(gated) == []


def test_fingerprints_are_shared_and_nonempty():
    """The probe-derived fingerprints back every eigh assertion in the repo;
    they must resolve on this backend and match a live eigh lowering."""
    markers = hlo_audit.eigh_fingerprints()
    assert markers and all(isinstance(m, str) for m in markers)
    txt = jax.jit(lambda a: jnp.linalg.eigh(a)[0]).lower(jnp.eye(4)).as_text()
    assert hlo_audit.contains_eigh(txt)
    assert hlo_audit.found_markers(txt, markers)
    assert not hlo_audit.contains_eigh("stablehlo.add only")
    assert hlo_audit.cholesky_fingerprints()


# ---------------------------------------------------------------------------
# Kernel-launch audit: one violating KernelSpec per rule
# ---------------------------------------------------------------------------


def _spec(**over):
    """A clean 2x2-grid fixture spec; each test perturbs ONE declaration."""
    base = dict(
        name="test.fixture",
        grid=(2, 2),
        in_shapes=(ArraySpec((32, 16), jnp.float32),),
        in_specs=(BlockDecl((16, 16), lambda i, j: (i, 0)),),
        out_shapes=(ArraySpec((32, 32), jnp.float32),),
        out_specs=(BlockDecl((16, 16), lambda i, j: (i, j)),),
    )
    base.update(over)
    return KernelSpec(**base)


def test_clean_spec_fixture_audits_clean():
    assert kernel_audit.audit_spec(_spec()) == []


def test_seeded_write_race_caught_with_cell():
    """Two grid cells differing OUTSIDE the revisit axes write one block."""
    spec = _spec(out_shapes=(ArraySpec((32, 16), jnp.float32),),
                 out_specs=(BlockDecl((16, 16), lambda i, j: (i, 0)),))
    vs = kernel_audit.check_geometry(spec)
    assert {v.rule for v in vs} == {"kernel-write-race"}
    assert "test.fixture" in vs[0].message  # kernel name...
    assert "(0, 0)" in vs[0].message and "(0, 1)" in vs[0].message  # ...cells
    assert vs[0].source == "test.fixture"
    # the SAME mapping is legal once the second axis is a declared reduction
    ok = _spec(out_shapes=(ArraySpec((32, 16), jnp.float32),),
               out_specs=(BlockDecl((16, 16), lambda i, j: (i, 0)),),
               scratch=(ScratchDecl((16, 16), jnp.float32),),
               revisit_axes=(1,), init_axes=(1,))
    assert kernel_audit.check_geometry(ok) == []


def test_unwritten_output_block_caught():
    spec = _spec(grid=(2,),
                 in_specs=(BlockDecl((16, 16), lambda i: (i, 0)),),
                 out_specs=(BlockDecl((16, 16), lambda i: (i, 0)),))
    vs = kernel_audit.check_geometry(spec)
    assert {v.rule for v in vs} == {"kernel-unwritten-block"}
    assert "(0, 1)" in vs[0].message  # the stranded block


def test_oob_index_map_caught_with_cell():
    spec = _spec(grid=(2,),
                 in_specs=(BlockDecl((16, 16), lambda i: (i + 1, 0)),),
                 out_shapes=(ArraySpec((32, 16), jnp.float32),),
                 out_specs=(BlockDecl((16, 16), lambda i: (i, 0)),))
    vs = kernel_audit.check_geometry(spec)
    assert [v.rule for v in vs] == ["kernel-oob-index"]
    assert "grid cell (1)" in vs[0].message  # offending grid cell
    assert "beyond padded bound 32" in vs[0].message


def test_leading_revisit_axis_caught():
    spec = _spec(out_shapes=(ArraySpec((16, 32), jnp.float32),),
                 out_specs=(BlockDecl((16, 16), lambda i, j: (0, j)),),
                 scratch=(ScratchDecl((16, 16), jnp.float32),),
                 revisit_axes=(0,), init_axes=(0,))
    vs = kernel_audit.check_geometry(spec)
    assert "kernel-revisit-order" in {v.rule for v in vs}


def test_misaligned_block_caught():
    spec = _spec(in_shapes=(ArraySpec((30, 16), jnp.float32),))
    vs = kernel_audit.check_geometry(spec)
    assert any(v.rule == "kernel-block-misaligned"
               and "axes [0]" in v.message for v in vs)


def test_missing_accumulator_caught():
    spec = _spec(out_shapes=(ArraySpec((32, 16), jnp.float32),),
                 out_specs=(BlockDecl((16, 16), lambda i, j: (i, 0)),),
                 revisit_axes=(1,), init_axes=(1,))
    vs = kernel_audit.check_geometry(spec)
    assert "kernel-accum-missing" in {v.rule for v in vs}


def test_accumulator_init_mismatch_caught():
    spec = _spec(out_shapes=(ArraySpec((32, 16), jnp.float32),),
                 out_specs=(BlockDecl((16, 16), lambda i, j: (i, 0)),),
                 scratch=(ScratchDecl((16, 16), jnp.float32),),
                 revisit_axes=(1,), init_axes=())
    vs = kernel_audit.check_geometry(spec)
    assert any(v.rule == "kernel-accum-init"
               and "(0, 1)" in v.message for v in vs)  # first revisiting cell


def test_bf16_accumulator_caught_on_real_rff_grad_spec():
    """rff_grad accumulates IN its output ref, so a bf16 launch would sum
    partials in bf16 -- the audit must reject the REAL spec at bf16 (the
    shipping contract pins it to f32)."""
    from repro.kernels.rff_grad import grad_spec

    vs = kernel_audit.check_geometry(
        grad_spec(128, 256, 32, jnp.bfloat16, block_n=64, block_m=128))
    assert [v.rule for v in vs] == ["kernel-accum-dtype"]
    assert "rff_grad" in vs[0].message and "bfloat16" in vs[0].message


def test_over_budget_block_pick_caught():
    """A block pair the tuner would never emit -- but a user CAN pin --
    blows the per-cell VMEM budget and is caught statically."""
    from repro.kernels.gp_score import score_tiled_spec

    spec = score_tiled_spec(256, 2048, 256, jnp.float32,
                            block_n=256, block_cap=1024)
    vs = kernel_audit.check_vmem(spec, device_kind=TPU_V5E)
    assert [v.rule for v in vs] == ["kernel-vmem-budget"]
    assert "gp_score.tiled" in vs[0].message
    assert "budget" in vs[0].message and "(0, 0, 0)" in vs[0].message
    # the tuner's own pick for the same shape fits
    from repro.kernels import autotune

    bn, bc = autotune.select_blocks("score", n=256, cap=2048, d=256,
                                    device_kind=TPU_V5E)
    ok = score_tiled_spec(256, 2048, 256, jnp.float32, block_n=bn,
                          block_cap=min(bc, 2048))
    assert kernel_audit.check_vmem(ok, device_kind=TPU_V5E) == []


# ---------------------------------------------------------------------------
# PRNG key-flow lint: one seeded misuse per rule
# ---------------------------------------------------------------------------


def test_reused_key_caught_with_location():
    def f(key):
        a = jax.random.uniform(key, (3,))
        b = jax.random.normal(key, (3,))  # seeded reuse of `key`
        return a + b

    vs = key_flow.check_key_flow(jax.make_jaxpr(f)(jax.random.PRNGKey(0)))
    assert [v.rule for v in vs] == ["key-reuse"]
    assert "test_analysis" in vs[0].source


def test_sample_then_derive_caught():
    """split/fold of an already-sampled key walks the same counter stream."""
    def f(key):
        a = jax.random.uniform(key, (3,))
        kb = jax.random.fold_in(key, 7)  # seeded derive-after-sample
        return a + jax.random.normal(kb, (3,))

    vs = key_flow.check_key_flow(jax.make_jaxpr(f)(jax.random.PRNGKey(0)))
    assert [v.rule for v in vs] == ["key-reuse"]
    # distinct-parameter derivations of an UNSAMPLED key stay clean
    def ok(key):
        a = jax.random.uniform(jax.random.fold_in(key, 1), (3,))
        return a + jax.random.normal(jax.random.fold_in(key, 2), (3,))

    assert key_flow.check_key_flow(
        jax.make_jaxpr(ok)(jax.random.PRNGKey(0))) == []


def test_same_fold_constant_twice_caught():
    def f(key):
        a = jax.random.uniform(jax.random.fold_in(key, 3), (3,))
        b = jax.random.normal(jax.random.fold_in(key, 3), (3,))  # collision
        return a + b

    vs = key_flow.check_key_flow(jax.make_jaxpr(f)(jax.random.PRNGKey(0)))
    assert [v.rule for v in vs] == ["key-reuse"]


def test_scan_carry_unsplit_caught():
    def f(key):
        def body(c, _):
            return c, jax.random.uniform(c, ())  # carry never split

        _, ys = jax.lax.scan(body, key, None, length=4)
        return ys

    vs = key_flow.check_key_flow(jax.make_jaxpr(f)(jax.random.PRNGKey(0)))
    assert [v.rule for v in vs] == ["key-carry-unsplit"]
    assert "test_analysis" in vs[0].source
    # the split-every-iteration version is clean
    def ok(key):
        def body(c, _):
            c, sub = jax.random.split(c)
            return c, jax.random.uniform(sub, ())

        return jax.lax.scan(body, key, None, length=4)[1]

    assert key_flow.check_key_flow(
        jax.make_jaxpr(ok)(jax.random.PRNGKey(0))) == []


def test_constant_key_caught_at_creation_site():
    def f(x):
        kk = jax.random.PRNGKey(777)
        return x + jax.random.normal(kk, (3,))

    vs = key_flow.check_key_flow(jax.make_jaxpr(f)(jnp.ones(3)))
    assert [v.rule for v in vs] == ["key-constant"]
    assert "test_analysis" in vs[0].source


def test_suppression_comment_honored():
    def f(x):
        kk = jax.random.PRNGKey(777)  # key-flow: ok (negative-test fixture)
        return x + jax.random.normal(kk, (3,))

    report = key_flow.analyze_key_flow(jax.make_jaxpr(f)(jnp.ones(3)))
    assert report.violations == []
    assert [v.rule for v in report.suppressed] == ["key-constant"]


def test_split_family_element_reuse_caught():
    def f(key):
        ks = jax.random.split(key, 3)
        return jax.random.uniform(ks[0], ()) + jax.random.normal(ks[0], ())

    vs = key_flow.check_key_flow(jax.make_jaxpr(f)(jax.random.PRNGKey(0)))
    assert [v.rule for v in vs] == ["key-reuse"]
    # distinct elements of the family are distinct keys
    def ok(key):
        ks = jax.random.split(key, 3)
        return jax.random.uniform(ks[0], ()) + jax.random.normal(ks[1], ())

    assert key_flow.check_key_flow(
        jax.make_jaxpr(ok)(jax.random.PRNGKey(0))) == []


# ---------------------------------------------------------------------------
# Steady-state guard
# ---------------------------------------------------------------------------


def test_guard_catches_device_get():
    x = jnp.zeros(())
    with pytest.raises(SteadyStateViolation, match="device_get"):
        with steady_state_guard(allow_compiles=None, allow_device_gets=0):
            jax.device_get(x)


def test_guard_counts_within_budget():
    x = jnp.zeros(())
    with steady_state_guard(allow_compiles=None, allow_device_gets=2) as g:
        jax.device_get(x)
    assert g.device_gets == 1


def test_no_recompiles_guard():
    f = jax.jit(lambda x: x * 2 + 1)
    a, b = jnp.zeros((3,)), jnp.zeros((5,))
    f(a).block_until_ready()  # warm the (3,) executable outside the guard
    with no_recompiles() as g:
        f(a).block_until_ready()  # cache hit: no fresh compile
    assert g.compiles == 0
    with pytest.raises(SteadyStateViolation, match="compiled"):
        with no_recompiles():
            f(b).block_until_ready()  # new shape: fresh executable


def test_guard_restores_device_get_on_error():
    real = jax.device_get
    with pytest.raises(RuntimeError, match="boom"):
        with steady_state_guard(allow_device_gets=0):
            raise RuntimeError("boom")
    assert jax.device_get is real


# ---------------------------------------------------------------------------
# Positive gate: the shipping contracts + the CLI
# ---------------------------------------------------------------------------


def test_all_shipping_contracts_clean():
    """Every registered contract lints clean -- the same gate
    ``python -m repro.analysis`` applies in verify.sh/CI."""
    results = check_all(out=io.StringIO())
    bad = {k: [str(v) for v in vs] for k, vs in results.items() if vs}
    assert not bad, bad


def test_check_all_rejects_unknown_contract():
    with pytest.raises(KeyError, match="unknown contract"):
        check_all(["no-such-contract"], out=io.StringIO())


def test_runner_exits_nonzero_on_violation(capsys):
    """A violating contract turns into exit code 1 with a source-located
    report (registered transiently; the shipping registry stays clean)."""
    from repro.analysis.contracts import CONTRACTS, register
    from repro.analysis.runner import main

    def seeded():
        def body(c, _):
            return c + jnp.diag(jnp.linalg.eigh(c)[0]), None
        closed = jax.make_jaxpr(
            lambda c: jax.lax.scan(body, c, None, length=2))(jnp.eye(3))
        return jaxpr_lint.find_forbidden(closed, jaxpr_lint.EIGH_PRIMITIVES,
                                         rule="no-eigh")

    name = "test-seeded-violation"
    register(name, "transient negative fixture")(seeded)
    try:
        rc = main(["--only", name])
    finally:
        del CONTRACTS[name]
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL test-seeded-violation" in out
    assert "no-eigh" in out and "test_analysis" in out  # source-located
    assert "1/1 contract(s) violated" in out


def test_runner_wraps_lowering_errors(capsys):
    from repro.analysis.contracts import CONTRACTS, register
    from repro.analysis.runner import main

    name = "test-broken-contract"
    register(name, "raises instead of lowering")(
        lambda: (_ for _ in ()).throw(RuntimeError("broken fixture")))
    try:
        rc = main(["--only", name])
    finally:
        del CONTRACTS[name]
    assert rc == 1
    assert "lowering-error" in capsys.readouterr().out


def test_contract_registry_floor():
    """The registry must carry the full contract population: the engine
    contracts plus the kernel-audit and key-flow families (the verify.sh
    --static floor guards the same count in CI)."""
    from repro.analysis.contracts import CONTRACTS

    assert len(CONTRACTS) >= 27, sorted(CONTRACTS)
    assert sum(n.startswith("kernel/") for n in CONTRACTS) >= 11
    assert sum(n.startswith("key-flow/") for n in CONTRACTS) >= 5


def test_cli_json_report(tmp_path):
    """--json writes the machine-readable report CI uploads as an artifact."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"))
    path = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--only", "optimizer-dtype",
         "--json", str(path)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(path.read_text())
    assert report["clean"] is True
    assert report["n_contracts"] == 1 and report["n_violations"] == 0
    entry = report["contracts"]["optimizer-dtype"]
    assert entry["violations"] == [] and "bf16" in entry["description"]


def test_cli_smoke():
    """`python -m repro.analysis --list` and a single cheap contract run in a
    fresh interpreter (forced onto CPU so the probe never touches a TPU)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"))
    listing = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert listing.returncode == 0, listing.stderr
    assert "fzoos-deferred/simulate" in listing.stdout
    single = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--only", "optimizer-dtype"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert single.returncode == 0, single.stdout + single.stderr
    assert "1 contract(s) clean" in single.stdout
