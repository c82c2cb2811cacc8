"""The program's spans, scopes and counters (core/telemetry.py) on the CPU.

A tiny two-chunk ``simulate`` runs under the profiler; the trace read back
with ``ProfileData`` must hold every host span of the program, nested where
it is called, and the compiled chunk step's HLO must name each device phase.
"""

import collections
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import algorithms as alg
from repro.core import objectives as obj
from repro.core import rff as rfflib
from repro.core import rounds as rounds_mod
from repro.core import telemetry

ROUNDS, CHUNK = 4, 2

#: Device scopes each algorithm's chunk step must carry.
SCOPES = {
    "fzoos": ("fzoo.local.update", "fzoo.local.score", "fzoo.local.grad",
              "fzoo.aggregate", "fzoo.round_end", "fzoo.eval"),
    "fedzo": ("fzoo.local.grad", "fzoo.aggregate", "fzoo.eval"),
}


def _cfg(algo: str) -> alg.AlgoConfig:
    kw = dict(name=algo, dim=4, n_clients=2, local_steps=2, lengthscale=0.5)
    if algo == "fzoos":
        kw.update(n_features=16, traj_capacity=16, active_per_iter=1,
                  active_candidates=4, active_round_end=1)
    else:
        kw.update(q=2)
    return alg.AlgoConfig(**kw)


@pytest.fixture(scope="module")
def quad():
    return obj.make_quadratic(jax.random.PRNGKey(0), 2, 4, 2.0, 0.001)


def _query(cp, x, key):
    with jax.named_scope("test.query"):
        return obj.quadratic_query(cp, x, key)


def _own_query():
    """``_query`` as a new function object: no earlier call in this process
    can have warmed a chunk step keyed on it (``rounds.make_chunk_step``)."""

    def query(cp, x, key):
        return _query(cp, x, key)

    return query


def _fzoo_spans(tdir: str) -> list:
    """The trace's ``fzoo.*`` host events as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("fzoo.")]
    return sorted(out, key=lambda e: e[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _traced_simulate(cfg, quad, query, seed: int, tdir: str) -> list:
    """The ``fzoo.*`` host spans of one ``simulate`` call under the profiler."""
    with jax.profiler.trace(tdir):
        res = alg.simulate(cfg, jax.random.PRNGKey(seed), quad, query,
                           obj.quadratic_global_value, ROUNDS, chunk=CHUNK)
        jax.block_until_ready(res)
    return _fzoo_spans(tdir)


@pytest.mark.parametrize("algo", ["fzoos", "fedzo"])
def test_host_spans_nest_and_count_traces(algo, quad, tmp_path):
    cfg = _cfg(algo)
    query = _own_query()
    before = telemetry.counters()
    spans = _traced_simulate(cfg, quad, query, 3, str(tmp_path / "first"))
    traces = telemetry.counters()["chunk_traces"] - before["chunk_traces"]

    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    n_chunks = ROUNDS // CHUNK
    assert {k: len(v) for k, v in by.items()} == {
        "fzoo.simulate": 1, "fzoo.init": 1, "fzoo.eval_x0": 1,
        "fzoo.chunk": n_chunks, "fzoo.boundary": n_chunks,
        "fzoo.chunk_step.trace": 1, "fzoo.chunk_step.compile": 1,
    }
    (sim,) = by["fzoo.simulate"]
    for name in ("fzoo.init", "fzoo.eval_x0", "fzoo.chunk", "fzoo.boundary"):
        assert all(_inside(s, sim) for s in by[name]), name
    # in call order: init, F(x_0), then chunk and boundary in turn
    order = [s[0] for s in spans if s[0] in ("fzoo.init", "fzoo.eval_x0", "fzoo.chunk",
                                             "fzoo.boundary")]
    assert order == ["fzoo.init", "fzoo.eval_x0"] + ["fzoo.chunk", "fzoo.boundary"] * n_chunks
    # the first chunk traces and compiles the step; the second reuses it
    first = by["fzoo.chunk"][0]
    assert _inside(by["fzoo.chunk_step.trace"][0], first)
    assert _inside(by["fzoo.chunk_step.compile"][0], first)
    assert by["fzoo.chunk_step.trace"][0][2] <= by["fzoo.chunk_step.compile"][0][1]
    assert traces == len(by["fzoo.chunk_step.trace"]) == 1

    # a second call of one shape reuses the step: no trace, no compile
    again = _traced_simulate(cfg, quad, query, 4, str(tmp_path / "again"))
    assert collections.Counter(s[0] for s in again) == {
        "fzoo.simulate": 1, "fzoo.init": 1, "fzoo.eval_x0": 1,
        "fzoo.chunk": n_chunks, "fzoo.boundary": n_chunks,
    }
    after = telemetry.counters()
    assert after["chunk_traces"] - before["chunk_traces"] == 1
    assert after["chunk_step_reuses"] - before["chunk_step_reuses"] == 1


@pytest.mark.parametrize("algo", ["fzoos", "fedzo"])
def test_chunk_step_hlo_names_each_phase(algo, quad):
    cfg = _cfg(algo)
    x0 = jnp.full((cfg.dim,), 0.5, jnp.float32)
    rff = None
    if cfg.is_fzoos:
        rff = rfflib.make_rff(jax.random.PRNGKey(1), cfg.n_features, cfg.dim,
                              cfg.lengthscale)
    states = alg.init_states(cfg, jax.random.PRNGKey(2), x0)
    step = rounds_mod.make_chunk_step(rounds_mod.sim_chunk_fn(
        cfg, _query, obj.quadratic_global_value, None, CHUNK))
    hist = rounds_mod.history_init(ROUNDS, x0, jnp.zeros((), jnp.float32))
    hlo = step.lower(states, hist, quad, x0, jnp.int32(0), rff).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for sc in SCOPES[algo]:
        assert any(sc in n for n in names), sc
    # the objective's own scope stays nested inside the program's phases
    queries = [n for n in names if "test.query" in n]
    assert any(re.search(r"fzoo\.local.*test\.query", n) for n in queries)
    assert all(re.search(r"fzoo\.\w+.*test\.query", n) for n in queries)


def test_span_and_scope_are_jax_annotations():
    assert telemetry.span is jax.profiler.TraceAnnotation
    assert telemetry.scope is jax.named_scope


def test_device_get_counting_nests_and_restores():
    real = jax.device_get
    x = jnp.zeros(())
    before = telemetry.counters()["device_gets"]
    with telemetry.counting_device_gets():
        jax.device_get(x)
        with telemetry.counting_device_gets():
            jax.device_get(x)
        jax.device_get(x)
    jax.device_get(x)  # not counted: no counting block is active
    assert telemetry.counters()["device_gets"] - before == 3
    assert jax.device_get is real


def test_compile_events_are_counted():
    f = jax.jit(lambda x: x * 3 - 1)
    x = jnp.arange(7.0).block_until_ready()
    before = telemetry.counters()["compiles"]
    f(x).block_until_ready()
    assert telemetry.counters()["compiles"] - before == 1
    f(x).block_until_ready()  # the same executable
    assert telemetry.counters()["compiles"] - before == 1
