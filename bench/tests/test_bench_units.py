"""Peaks table, operation counts and the trace reduction (CPU only)."""

from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import counts  # noqa: E402
import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")


# ---------------------------------------------------------------------------
# peaks
# ---------------------------------------------------------------------------


def test_peaks_known_device_and_source():
    p = peaks.for_device("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks.table()["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_peaks_unknown_device_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_device(kind)


def test_peaks_table_has_no_cpu_entry():
    assert not any("cpu" in k.lower() for k in peaks.table()["devices"])


# ---------------------------------------------------------------------------
# counts: hand counts at tiny shapes
# ---------------------------------------------------------------------------


def test_gp_score_call_hand_count():
    # N=2 clients, n=3 candidates, cap=4, d=5
    # per client: 2*3*4*5 + 2*3*5 + 2*4*5 + 4*3*16 + 10*3*4 = 120+30+40+192+120 = 502
    w = counts.gp_score_call(2, 3, 4, 5)
    assert w["flops"] == 2 * 502
    # bytes per client: 4 * (3*5 + 4*5 + 2*16 + 3) = 4 * 70 = 280
    assert w["bytes"] == 2 * 280


def test_gp_grad_call_hand_count():
    # N=3, cap=4, d=5: per client 6*4*5 + 2*5 + 4*4 = 120 + 10 + 16 = 146
    w = counts.gp_grad_call(3, 4, 5)
    assert w["flops"] == 3 * 146
    # bytes per client 4 * (20 + 10 + 4) = 136
    assert w["bytes"] == 3 * 136


def test_lm_counts_hand_count():
    cfg = {"hidden_size": 4, "vocab_size": 10}
    # a query: 3 tokens x (2*4*10 + 3*10 + 5*4) = 3 * 130
    assert counts.lm_query_flops(cfg, 3, 1) == 3 * 130


def test_quadratic_and_round_counts():
    assert counts.quadratic_query_flops(10) == 80
    s = {"n_clients": 2, "dim": 3, "local_steps": 2, "q": 4}
    # fedzo: per step 5 queries * 24 + 2*4*3 + 2*4*3 = 120 + 48 = 168
    assert counts.round_flops("fedzo", s, 24.0) == 2 * 2 * 168 + 2 * 24


def _fzoos_settings(**over):
    s = {"n_clients": 64, "dim": 3072, "traj_capacity": 128, "n_features": 512,
         "local_steps": 10, "active_candidates": 100, "active_per_iter": 5,
         "active_round_end": 5}
    s.update(over)
    return s


@pytest.mark.parametrize("blocks", [
    {}, {"score_block_n": 8, "score_block_cap": 128},
    {"score_block_n": 128, "score_block_cap": 64, "grad_block_n": 8, "grad_block_cap": 32}])
def test_counts_do_not_change_with_block_sizes(blocks):
    base = _fzoos_settings()
    tiled = _fzoos_settings(**blocks)
    for k in ("gp_score", "gp_grad"):
        assert counts.kernel_call(k, tiled) == counts.kernel_call(k, base)
    assert counts.round_flops("fzoos", tiled, 8.0 * 3072) == \
        counts.round_flops("fzoos", base, 8.0 * 3072)


def test_fzoos_round_count_at_cell_size():
    # the shape count of ISSUE-sized rounds: ~171 GFLOP at N=64, d=3072
    f = counts.round_flops("fzoos", _fzoos_settings(), 8.0 * 3072)
    assert 1.5e11 < f < 2.0e11


def test_roofline_share_names_its_bound():
    p = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = counts.roofline_share({"flops": 100.0, "bytes": 50.0}, 2, 20.0, p)
    assert bound == "memory" and share == pytest.approx(0.5)  # 2*50/10 = 10 s of 20
    share, bound = counts.roofline_share({"flops": 1000.0, "bytes": 5.0}, 1, 20.0, p)
    assert bound == "compute" and share == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _ev(name, s, e):
    return tr.Event(name, s, e)


def _synthetic():
    host = tr.Plane("/host:CPU", {"python": [
        _ev("bench.job", 1.0, 3.0), _ev("bench.job", 4.0, 6.0),
        _ev("PjitFunction(simulate)", 1.0, 1.5), _ev("trace", 2.6, 3.0)]})
    dev = tr.Plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_run(12)", 1.5, 2.5), _ev("jit_run(12)", 4.5, 5.0),
                        _ev("jit_other(3)", 5.2, 5.4)],
        "XLA Ops": [
            _ev("%fusion.1 = f32[8] fusion(%p), kind=kLoop", 1.5, 1.8),
            _ev("%uncertainty_scores_clients_kernel.5 = f32[8] custom-call(%a)", 1.8, 2.2),
            _ev("%fusion.2 = f32[8] fusion(%q)", 2.1, 2.5),  # overlaps the kernel
            _ev("%grad_mean_clients_kernel.3 = f32[8] custom-call(%b)", 4.5, 5.0),
            _ev("%fusion.1 = f32[8] fusion(%p), kind=kLoop", 5.2, 5.4),  # not the chunk step
            _ev("%fusion.9 = f32[8] fusion(%r)", 0.2, 0.9),  # before the window
            _ev("%while.4 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b", 1.5, 2.5),
        ]})
    return [host, dev]


OP_NAMES = {"fusion.1": "jit(run)/while/body/vmap(bench.objective)/add",
            "fusion.2": "jit(run)/while/body/mul",
            "uncertainty_scores_clients_kernel.5": "jit(run)/pallas_call"}


def test_reduce_synthetic_hand_sums():
    t = tr.reduce(_synthetic(), op_names=OP_NAMES)
    assert t.window == (1.0, 6.0) and t.window_s == pytest.approx(5.0)
    # busy: [1.5, 2.5] + [4.5, 5.0] + [5.2, 5.4] = 1.0 + 0.5 + 0.2
    assert t.busy_s == pytest.approx(1.7)
    assert t.idle_share == pytest.approx(1 - 1.7 / 5.0)
    assert t.chunk_busy_s == pytest.approx(1.5)
    assert t.chunk_busy_in_jobs == pytest.approx([1.0, 0.5])
    assert t.seconds_of(r"uncertainty_scores\w*_kernel(\.\d+)?") == pytest.approx(0.4)
    assert t.seconds_of(r"grad_mean\w*_kernel(\.\d+)?") == pytest.approx(0.5)
    # fusion.1 inside a jit_run execution counts, the one in jit_other does not
    assert t.scope_s["bench.objective"] == pytest.approx(0.3)
    assert t.op_s["fusion.1"] == pytest.approx(0.3 + 0.2)
    assert "fusion.9" not in t.op_s  # ran before the window
    assert "while.4" not in t.op_s  # a loop: its body's ops are counted
    # the idle gaps [1, 1.5], [2.5, 4.5], [5.0, 5.2], [5.4, 6.0], labelled by
    # the host span at their middle
    gaps = dict(t.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(5.0 - 1.7)
    assert gaps["PjitFunction(simulate)"] == pytest.approx(0.5)
    assert gaps["between jobs"] == pytest.approx(2.0)
    assert gaps["no host span"] == pytest.approx(0.8)
    b = t.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"} and len(b["device_ops"]) <= 10


def test_op_names_from_hlo():
    hlo = """
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(run)/vmap(bench.objective)/add" source_file="x.py"}
  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.3)
  %k.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/pallas_call"}
"""
    assert tr.op_names_from_hlo(hlo) == {"fusion.3": "jit(run)/vmap(bench.objective)/add",
                                         "k.2": "jit(run)/pallas_call"}


def test_busy_and_gaps_streaming():
    from array import array

    b, g = tr.busy_and_gaps(array("d", [1, 3, 2.5]), array("d", [2, 4, 3.5]), 0, 5)
    assert b == pytest.approx(2.5)  # [1,2] + [2.5,4]
    assert sorted(g) == [(0, 1), (2, 2.5), (4, 5)]


def test_union_and_gaps():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == pytest.approx(4.0)
    assert tr.union_length([(0, 2), (1, 3), (5, 6)], 1.5, 5.5) == pytest.approx(2.0)


def test_reduce_needs_job_spans_and_a_device():
    with pytest.raises(ValueError):
        tr.reduce([tr.Plane("/device:TPU:0", {"XLA Ops": []})])
    with pytest.raises(ValueError):
        tr.reduce([tr.Plane("/host:CPU", {"python": [_ev("bench.job", 0, 1)]})])


def test_reduce_recorded_tpu_trace():
    """The small trace ``record_trace.py`` recorded on a TPU v5e, against sums
    taken by hand from its event listing (``data/tiny_events.txt``)."""
    path = os.path.join(DATA, "tiny.xplane.pb")
    expect = json.load(open(os.path.join(DATA, "tiny_expected.json")))
    t = tr.reduce(tr.load_planes(path))
    # the listing rounds each time to the nanosecond: 3 ns of slack
    for key in ("window_s", "busy_s", "chunk_busy_s"):
        assert getattr(t, key) == pytest.approx(expect[key], abs=3e-9), key
    for pattern, v in expect["kernels"].items():
        assert t.seconds_of(pattern) == pytest.approx(v, abs=3e-9), pattern
    assert t.chunk_busy_in_jobs == [0.0, 0.0]  # device clock behind the host's
