"""The reduction of the program's spans and scopes (``phases.py``) and the
per-layer metrics that read them, on synthetic planes (CPU only)."""

from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import phases  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
ev = tr.Event


def _planes():
    """Two jobs.  The first: init, F(x_0), a chunk that traces and compiles
    its step, a boundary, a second chunk and boundary; its two chunk-step
    executions run [5.5, 8] and [8, 9.5].  The second job is a front door
    with nothing inside and no device work."""
    host = tr.Plane("/host:CPU", {"python": [
        ev("bench.job", 0.0, 10.0), ev("bench.job", 11.0, 12.0),
        ev("fzoo.simulate", 1.0, 9.0),
        ev("fzoo.init", 1.0, 2.0), ev("fzoo.eval_x0", 2.0, 3.0),
        ev("fzoo.chunk", 3.0, 6.0),
        ev("fzoo.chunk_step.trace", 3.0, 4.0), ev("fzoo.chunk_step.compile", 4.0, 5.5),
        ev("lower_sharding_computation", 4.1, 5.4),  # JAX's own: not a phase
        ev("fzoo.boundary", 6.0, 6.5), ev("fzoo.chunk", 6.5, 7.0),
        ev("fzoo.boundary", 7.0, 7.5),
        ev("fzoo.simulate", 11.0, 12.0)]})
    dev = tr.Plane("/device:TPU:0", {
        "XLA Modules": [ev("jit_run(3)", 5.5, 8.0), ev("jit_run(3)", 8.0, 9.5)],
        "XLA Ops": [
            ev("%fusion.3 = f32[] fusion(%x)", 2.5, 3.0),  # F(x_0): not the chunk step
            ev("%fusion.1 = f32[8] fusion(%a)", 5.5, 6.5),
            ev("%fusion.2 = f32[8] fusion(%b)", 6.5, 7.5),
            ev("%fusion.3 = f32[] fusion(%c)", 7.5, 8.0),
            ev("%fusion.1 = f32[8] fusion(%a)", 8.0, 9.0),
            ev("%fusion.4 = f32[8] fusion(%d)", 9.0, 9.5),
            ev("%while.7 = (s32[]) while(%t), body=%b", 5.5, 9.5),
        ]})
    return [host, dev]


OP_NAMES = {
    "fusion.1": "jit(run)/while/body/fzoo.local/while/body/fzoo.local.update/"
                "bench.objective/add",
    "fusion.2": "jit(run)/while/body/fzoo.round_end/mul",
    "fusion.3": "jit(run)/while/body/fzoo.eval/bench.objective/reduce_sum",
    "fusion.4": "jit(run)/while/body/fzoo.local/while/body/fzoo.local.score/dot_general",
}


def test_span_seconds_and_counts_per_job():
    p = phases.reduce_phases(_planes(), OP_NAMES)
    assert p["jobs"] == 2
    assert p["span_s"] == pytest.approx({
        "fzoo.simulate": (8.0 + 1.0) / 2, "fzoo.init": 0.5, "fzoo.eval_x0": 0.5,
        "fzoo.chunk": (3.0 + 0.5) / 2, "fzoo.chunk_step.trace": 0.5,
        "fzoo.chunk_step.compile": 0.75, "fzoo.boundary": (0.5 + 0.5) / 2})
    assert p["span_n"] == pytest.approx({
        "fzoo.simulate": 1.0, "fzoo.init": 0.5, "fzoo.eval_x0": 0.5, "fzoo.chunk": 1.0,
        "fzoo.chunk_step.trace": 0.5, "fzoo.chunk_step.compile": 0.5, "fzoo.boundary": 1.0})


def test_idle_goes_to_the_innermost_program_span():
    idle = phases.reduce_phases(_planes(), OP_NAMES)["idle_by_phase"]
    # job 1: [0, 1] and [9, 10] outside the front door, [9.5, 10] of it idle;
    # F(x_0)'s op covers half its span; the compile span holds JAX's lowering
    # span, which is not a program phase; the chunk and boundary spans and
    # fzoo.simulate's own [7.5, 9] are covered by the chunk step.  Job 2:
    # its front door is idle throughout.
    assert idle == pytest.approx({
        phases.OUTSIDE: 1.0 + 0.5, "fzoo.init": 1.0, "fzoo.eval_x0": 0.5,
        "fzoo.chunk_step.trace": 1.0, "fzoo.chunk_step.compile": 1.5,
        "fzoo.chunk": 0.0, "fzoo.boundary": 0.0, "fzoo.simulate": 1.0})
    # every idle second inside the jobs is put down exactly once
    busy = 0.5 + 4.0
    assert sum(idle.values()) == pytest.approx(10.0 + 1.0 - busy)


def test_scope_seconds_nest_and_keep_to_the_chunk_step():
    s = phases.reduce_phases(_planes(), OP_NAMES)["scopes"]
    assert s == pytest.approx({
        "fzoo.local": 2.0 + 0.5, "fzoo.local.update": 2.0, "fzoo.local.score": 0.5,
        "fzoo.local.grad": 0.0, "fzoo.aggregate": 0.0, "fzoo.round_end": 1.0,
        "fzoo.eval": 0.5})  # F(x_0)'s fusion.3 ran outside the chunk step


def test_unscoped_chunk_step_time():
    names = dict(OP_NAMES, **{"fusion.2": "jit(run)/while/body/mul"})
    p = phases.reduce_phases(_planes(), names)
    assert p["unscoped_s"] == pytest.approx(1.0)
    assert p["unscoped_top"] == [["fusion.2 jit(run)/while/body/mul", pytest.approx(1.0)]]


def test_chunk_leads_pair_spans_with_executions():
    assert phases.reduce_phases(_planes(), OP_NAMES)["chunk_leads_s"] == \
        pytest.approx([3.0 - 5.5, 6.5 - 8.0])
    planes = _planes()
    planes[1].lines["XLA Modules"] = planes[1].lines["XLA Modules"][:1]
    assert phases.reduce_phases(planes, OP_NAMES)["chunk_leads_s"] == []


def test_a_trace_without_program_spans_reads_as_outside():
    """The recorded TPU trace predates the program's spans: every idle
    second is outside the front door, no scope has time, nothing raises."""
    planes = tr.load_planes(os.path.join(DATA, "tiny.xplane.pb"))
    p = phases.reduce_phases(planes)
    t = tr.reduce(planes)
    assert set(p["idle_by_phase"]) == {phases.OUTSIDE}
    # its device operations fall between the jobs on the trace's clock
    # (test_reduce_recorded_tpu_trace), so both jobs are idle throughout
    assert p["idle_by_phase"][phases.OUTSIDE] == pytest.approx(
        sum(e - s for s, e in t.jobs), abs=3e-9)
    assert p["span_s"] == {} and not any(p["scopes"].values())


# ---------------------------------------------------------------------------
# the metric readers
# ---------------------------------------------------------------------------


def _reader(name: str):
    return harness.load_module(os.path.join(BENCH, "metrics", f"{name}.py"))


def _planes_apart():
    """``_planes`` with F(x_0)'s instruction named apart from the chunk
    step's."""
    planes = _planes()
    planes[1].lines["XLA Ops"][0] = ev("%fusion.9 = f32[] fusion(%x)", 2.5, 3.0)
    return planes


@pytest.mark.parametrize("name, seconds", [
    ("local_phase_share", 2.0 + 0.5),
    ("round_end_share", 1.0),
    ("eval_share", 0.5),
])
def test_scope_share_readers(name, seconds):
    t = tr.reduce(_planes_apart(), op_names=OP_NAMES)
    ctx = types.SimpleNamespace(reduced=t)
    assert t.busy_s == pytest.approx(4.5)
    assert _reader(name).read(ctx) == pytest.approx(100.0 * seconds / 4.5)


@pytest.mark.parametrize("name, scope", [
    ("local_phase_share", "fzoo.local"),
    ("round_end_share", "fzoo.round_end"),
    ("eval_share", "fzoo.eval"),
])
def test_scope_share_readers_bound_the_chunk_step_share(name, scope):
    """Where another executable runs an instruction of the chunk step's name
    (F(x_0)'s fusion.3 in ``_planes``), the reader counts it too: it reads at
    least the chunk step's own share, never less."""
    planes = _planes()
    t = tr.reduce(planes, op_names=OP_NAMES)
    own = phases.reduce_phases(planes, OP_NAMES)["scopes"][scope]
    assert _reader(name).read(types.SimpleNamespace(reduced=t)) >= \
        100.0 * own / t.busy_s - 1e-9


@pytest.mark.parametrize("name", ["local_phase_share", "round_end_share", "eval_share"])
def test_scope_share_readers_find_nothing_without_scopes(name):
    untraced = types.SimpleNamespace(reduced=None)
    assert _reader(name).read(untraced) is None
    names = {k: v.replace("fzoo.", "other.") for k, v in OP_NAMES.items()}
    ctx = types.SimpleNamespace(reduced=tr.reduce(_planes(), op_names=names))
    assert _reader(name).read(ctx) is None


def test_chunk_traces_reader(monkeypatch):
    read = _reader("chunk_traces_per_job").read
    ctx = types.SimpleNamespace(jobs=[object(), object()])  # and the warm-up job
    fake = types.SimpleNamespace(counters=lambda: {"chunk_traces": 6, "compiles": 9})
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", fake)
    assert read(ctx) == pytest.approx(2.0)
    monkeypatch.setitem(sys.modules, "repro.core.telemetry",
                        types.SimpleNamespace(counters=lambda: {"compiles": 9}))
    assert read(ctx) is None
    monkeypatch.delitem(sys.modules, "repro.core.telemetry")
    assert read(ctx) is None  # a program without the counter
