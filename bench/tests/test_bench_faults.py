"""A run with the timed path broken underneath comes out not correct.

Each tiny cell is driven through the harness's own functions (the look for a
chip skipped) with one fault planted in the program, and the number that
should catch it has to pass its limit, so ``correct`` is false:

* a round that returns its state unchanged -> ``x_gap`` or ``step_gap`` (both
  read 1) and ``queries_off``;
* half of the clients left out of the server's mean -> ``x_gap`` or ``step_gap``
  (whichever the cell compares);
* the answer altered where it is produced (F(x_r) off by 1%) -> ``f_gap``;
* the server's average of the RFF weights left out (FZooS: w_global stays
  zero) -> ``w_avg_gap`` or ``x_gap_resume``.

The exchange between chips does not exist in these one-chip cells.

The control (the reference in the precision below the configuration's, in
the program's place) fails the check at the cell's own size on the chip,
where ``readings.py`` reads it; at a size a CPU test can hold its error
stays under the limits (a TPU's ``high`` precision also lowers the
Cholesky and solves, which the CPU does not emulate).  Here the control's
arithmetic is checked, and every limit against the chip readings it was set
from."""

from __future__ import annotations

import os
import time

import pytest

import tinycells

import check  # noqa: E402
import harness  # noqa: E402


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tinycells.make_root(str(tmp_path_factory.mktemp("tinyf")))


def _unchanged(monkeypatch, cell):
    import jax.numpy as jnp

    from repro.core import algorithms as alg

    def run_round(cfg, rff, query_fn, cobjs, states, server_x, mean_fn, *a, **k):
        z = jnp.zeros((), jnp.float32)
        stats = alg.RoundStats(server_x=server_x, mean_cos=z, mean_disparity=z,
                               queries_per_client=mean_fn(states.queries.astype(jnp.float32)),
                               refactor_rate=z, repair_rate=z, drop_rate=z, quarantine_rate=z)
        return states, stats

    monkeypatch.setattr(alg, "run_round", run_round)
    return ("x_gap", "step_gap", "queries_off")


def _half_batch(monkeypatch, cell):
    import jax
    import jax.numpy as jnp

    from repro.core import algorithms as alg

    orig = alg.run_round

    def half_mean(tree):
        return jax.tree_util.tree_map(lambda a: jnp.mean(a[: a.shape[0] // 2], axis=0), tree)

    def run_round(cfg, rff, query_fn, cobjs, states, server_x, mean_fn, *a, **k):
        return orig(cfg, rff, query_fn, cobjs, states, server_x, half_mean, *a, **k)

    monkeypatch.setattr(alg, "run_round", run_round)
    return ("x_gap", "step_gap")


def _answer_altered(monkeypatch, cell):
    if cell.config["objective"] == "quadratic":
        from repro.core import objectives as obj

        orig = obj.quadratic_global_value
        monkeypatch.setattr(obj, "quadratic_global_value", lambda c, x: 1.01 * orig(c, x))
    else:
        from repro.core import model_objectives as mobj

        orig = mobj.make_lm_query

        def make_lm_query(*a, **k):
            q, gv, dim, value = orig(*a, **k)
            return q, (lambda c, x: 1.01 * gv(c, x)), dim, value

        monkeypatch.setattr(mobj, "make_lm_query", make_lm_query)
    return ("f_gap",)


def _w_unaveraged(monkeypatch, cell):
    import jax.numpy as jnp

    from repro.core import algorithms as alg

    orig = alg.run_round

    def run_round(*a, **k):
        states, stats = orig(*a, **k)
        return states._replace(w_global=jnp.zeros_like(states.w_global)), stats

    monkeypatch.setattr(alg, "run_round", run_round)
    return ("w_avg_gap", "x_gap_resume")


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered, "w_unaveraged": _w_unaveraged}

#: Faults that only a cell of the given algorithm can have.
ONLY = {"w_unaveraged": "fzoos"}


def _cases():
    algo = {n: harness.load_cell(n).traffic["algo"] for n in tinycells.cell_names()}
    return [(n, f) for n in algo for f in sorted(FAULTS) if ONLY.get(f) in (None, algo[n])]


@pytest.mark.parametrize("name,fault", _cases())
def test_fault_makes_the_run_not_correct(tiny_root, monkeypatch, name, fault):
    import jax

    cell = harness.load_cell(name, tiny_root)
    catchers = [n for n in FAULTS[fault](monkeypatch, cell) if n in cell.limits["limits"]]
    out = harness.run(cell, 2**32 + 99, 0.05, False, time.perf_counter(), jax.devices()[:1],
                      log=lambda m: None)
    assert out["correct"] is False
    assert any(out["checks"][n]["value"] > out["checks"][n]["limit"] for n in catchers), (
        catchers, out["checks"])


def test_control_products_are_three_bf16_passes():
    """The control's products: hi*hi + hi*lo + lo*hi of the bf16 split, in
    f32, what a TPU computes at ``high``; it differs from f32 products at
    about bf16's precision squared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference.fzoos import make_dot

    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    hi = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    ah, bh = hi(a), hi(b)
    al, bl = hi(a - ah), hi(b - bh)
    manual = np.asarray(ah @ bh + (ah @ bl + al @ bh), np.float64)
    got = np.asarray(make_dot("bf16x3")("ij,jk->ik", a, b), np.float64)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert np.allclose(got, manual, rtol=1e-6, atol=1e-5)
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    assert 1e-7 < rel < 1e-4


def test_control_weights_are_fp8():
    """The LM control's weights: float8 e4m3 (3 mantissa bits) under one
    scale per tensor."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lm = harness.load_module(os.path.join(tinycells.BENCH, "objectives", "lm.py"))
    w = jax.random.normal(jax.random.PRNGKey(2), (128, 64)) * 0.03
    q = np.asarray(lm.fp8(w))
    w = np.asarray(w)
    rel = np.abs(q - w) / np.maximum(np.abs(w), 1e-3)
    assert rel.max() <= 2.0**-4 + 1e-6 and rel.max() > 2.0**-6
    assert np.isclose(np.abs(q).max(), np.abs(w).max(), rtol=2.0**-4)
    assert jnp.float8_e4m3fn is not None


@pytest.mark.parametrize("name", tinycells.cell_names())
def test_limits_lie_between_their_readings(name):
    """Every limit lies above the largest sound reading of its number and
    below the smallest reading of whatever it is there to fail (the control
    or a fault), as read on the chip at the cell's own size
    (``readings.py``; ``limits/<cell>.json``)."""
    cell = harness.load_cell(name)
    readings = cell.limits["readings"]
    assert isinstance(readings, dict), "limits set without readings"
    for number, r in readings.items():
        if number == "origin":
            continue
        limit = cell.limits["limits"][r.get("number", number)]
        assert r["lower"] < limit < r["upper"], (number, r["lower"], limit, r["upper"])
