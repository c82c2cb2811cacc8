"""One chunk step per program across front-door calls (``rounds.make_chunk_step``).

Two calls of one shape -- the same cfg, query and value functions, another
key -- trace the chunk function once.  The second call shares the first
call's traced programs: it counts in ``chunk_step_reuses``, compiles no
``jit(run)``, and returns bitwise what a fresh step returns for its key
(the RFF bank each call draws reaches the program as an argument, never as
a stale closure constant).  A change of any part of the key gets a step of
its own, and the cache keeps no function alive.
"""

import dataclasses
import gc
import logging
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import federated as fed
from repro.core import objectives as obj
from repro.core import rounds as rounds_mod
from repro.core import telemetry
from repro.faults import FaultConfig
from repro.launch.mesh import make_mesh

ROUNDS, CHUNK, N_CLIENTS = 4, 2, 4


def _cfg(algo: str) -> alg.AlgoConfig:
    kw = dict(name=algo, dim=4, n_clients=N_CLIENTS, local_steps=2, lengthscale=0.5)
    if algo == "fzoos":
        kw.update(n_features=16, traj_capacity=16, active_per_iter=1,
                  active_candidates=4, active_round_end=1)
    else:
        kw.update(q=2)
    return alg.AlgoConfig(**kw)


@pytest.fixture(scope="module")
def quad():
    return obj.make_quadratic(jax.random.PRNGKey(0), N_CLIENTS, 4, 2.0, 0.001)


def _query():
    """A new query function: no earlier call can have warmed a step keyed on it."""

    def query(cp, x, key):
        return obj.quadratic_query(cp, x, key)

    return query


def _front_door(door: str, cfg, quad, query, seed: int):
    key = jax.random.PRNGKey(seed)
    args = (quad, query, obj.quadratic_global_value, ROUNDS)
    if door == "distributed":
        res = fed.run_distributed(cfg, make_mesh((1,), ("data",)), key, *args, chunk=CHUNK)
    else:
        res = alg.simulate(cfg, key, *args, chunk=CHUNK,
                           cohort=N_CLIENTS // 2 if door == "pooled" else None)
    return jax.tree_util.tree_map(np.asarray, jax.device_get(res))


def _run_compiles(records) -> int:
    """Compiles of the chunk step's jitted ``run`` that ``jax.log_compiles`` logged."""
    return sum("jit(run)" in r.getMessage() for r in records)


@pytest.mark.parametrize("door", ["simulate", "pooled", "distributed"])
@pytest.mark.parametrize("algo", ["fzoos", "fedzo"])
def test_second_call_reuses_the_step(algo, door, quad, caplog):
    cfg = _cfg(algo)
    query = _query()
    with caplog.at_level(logging.WARNING), jax.log_compiles(True):
        first = _front_door(door, cfg, quad, query, 1)
        first_compiles = _run_compiles(caplog.records)
        caplog.clear()
        before = telemetry.counters()
        second = _front_door(door, cfg, quad, query, 2)
        after = telemetry.counters()
        second_compiles = _run_compiles(caplog.records)
    assert first_compiles >= 1  # the log does name the step's program
    assert second_compiles == 0
    assert after["chunk_traces"] == before["chunk_traces"]
    assert after["chunk_step_reuses"] - before["chunk_step_reuses"] == 1

    # bitwise what a step of its own computes for the second key
    fresh = _front_door(door, cfg, quad, _query(), 2)
    for name, got, want in zip(second._fields, second, fresh):
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert not np.array_equal(first.xs, second.xs)

    # the cache keeps neither the function nor what its programs hold
    ref = weakref.ref(query)
    del query
    gc.collect()
    assert ref() is None


def _chunk_fn(query, **change):
    args = dict(cfg=_cfg("fzoos"), query_fn=query,
                global_value_fn=obj.quadratic_global_value, diag_global_grad=None,
                length=CHUNK, eval_every=1, rounds_total=ROUNDS,
                faults=FaultConfig(drop_rate=0.25, tolerate=False))
    args.update(change)
    return rounds_mod.sim_chunk_fn(**args)


@pytest.mark.parametrize("change", [
    {},
    {"query_fn": _query()},
    {"rounds_total": ROUNDS + 2},
    {"faults": FaultConfig(drop_rate=0.25, tolerate=True)},
    {"length": CHUNK + 1},
    {"eval_every": 2},
    {"cfg": dataclasses.replace(_cfg("fzoos"), local_steps=3)},
], ids=["same", "query", "rounds", "tolerate", "length", "eval_every", "cfg"])
def test_each_key_gets_its_own_step(change):
    query = _query()
    rounds_mod.make_chunk_step(_chunk_fn(query))
    before = telemetry.counters()["chunk_step_reuses"]
    rounds_mod.make_chunk_step(_chunk_fn(query, **change))
    assert telemetry.counters()["chunk_step_reuses"] - before == (0 if change else 1)


def test_chunk_fn_without_a_key_gets_a_step_of_its_own():
    def chunk(states, cobjs, sx, offset, rff):
        return states, sx, ()

    before = telemetry.counters()["chunk_step_reuses"]
    rounds_mod.make_chunk_step(chunk)
    rounds_mod.make_chunk_step(chunk)
    assert telemetry.counters()["chunk_step_reuses"] == before


def test_cache_is_bounded_and_drops_dead_functions():
    size = rounds_mod.CHUNK_STEP_CACHE_SIZE
    query = _query()
    for length in range(1, size + 2):
        rounds_mod.make_chunk_step(_chunk_fn(query, length=length))
    assert len(rounds_mod._CHUNK_PROGRAMS) == size
    before = telemetry.counters()["chunk_step_reuses"]
    rounds_mod.make_chunk_step(_chunk_fn(query, length=size + 1))  # the newest stays
    rounds_mod.make_chunk_step(_chunk_fn(query, length=1))  # the oldest went first
    assert telemetry.counters()["chunk_step_reuses"] - before == 1
    del query
    gc.collect()
    assert len(rounds_mod._CHUNK_PROGRAMS) == 0


class _SlottedQuery:
    """A callable that cannot be weakly referenced."""

    __slots__ = ()

    def __call__(self, cp, x, key):
        return obj.quadratic_query(cp, x, key)


def test_callable_without_weakref_is_held_and_reused():
    query = _SlottedQuery()
    rounds_mod.make_chunk_step(_chunk_fn(query, length=CHUNK + 5))
    before = telemetry.counters()["chunk_step_reuses"]
    rounds_mod.make_chunk_step(_chunk_fn(query, length=CHUNK + 5))
    assert telemetry.counters()["chunk_step_reuses"] - before == 1


def _matmul_chunk(states, cobjs, sx, offset, rff):
    x = (cobjs @ sx)[None]
    z = jnp.zeros((1,), jnp.float32)
    return states, x[0], (x, z, z, z, z, z, z, z, z)


def test_default_matmul_precision_gets_its_own_program():
    """A product's precision is fixed when it is traced, so a call under
    another ``jax.default_matmul_precision`` must not reuse the program."""
    x0 = jnp.ones((3,), jnp.float32)
    args = (jnp.zeros(()), rounds_mod.history_init(1, x0, jnp.zeros(())),
            jnp.eye(3), x0, jnp.int32(0), None)
    step = rounds_mod.ChunkStep(_matmul_chunk)
    before = telemetry.counters()["chunk_traces"]
    step.lower(*args)
    with jax.default_matmul_precision("highest"):
        text = step.lower(*args).as_text()
    step.lower(*args)
    assert telemetry.counters()["chunk_traces"] - before == 2
    assert "HIGHEST" in text
