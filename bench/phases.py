#!/usr/bin/env python3
"""The program's own spans and scopes in a profiler trace.

The program names its host phases with ``fzoo.*`` spans and its device
phases with ``fzoo.*`` scopes (``src/repro/core/telemetry.py``).  From the
planes of a trace (``trace_reduce.load_planes``), ``reduce_phases`` reads:

* ``span_s``, ``span_n``: the seconds and the count of each ``fzoo.*`` span
  that starts inside one of the window's ``bench.job`` spans, per job;
* ``idle_by_phase``: each idle second of the device inside the window's
  jobs, put down to the innermost ``fzoo.*`` span around it
  (``fzoo.simulate`` where none of its children covers it; ``outside
  fzoo.simulate`` inside a job but outside the program's front door),
  summed over the jobs;
* ``scopes``: device seconds of the chunk step's operations (those
  that start inside a ``jit_run`` execution) under each program scope, by
  substring of their ``op_name``, so ``fzoo.local`` holds its sub-scopes;
* ``chunk_leads_s``: for each ``fzoo.chunk`` span, its start minus the start
  of the chunk-step execution it dispatched: the host and device clocks of
  the trace agree if none is above a millisecond.

``scope_share`` is the per-layer metrics' reading of a scope from a
``trace_reduce.TraceSummary``: the device seconds of the traced window's
operations whose name the chunk step's HLO gives that scope, over busy time.
It is an upper bound: the summary sums operations by instruction name over
every executable, so an instruction of another executable that shares a
name with one of the chunk step's counts under that one's scope.

This module is the part of a trace's reduction that ``trace_reduce.py``
does not hold yet; it goes when ``trace_reduce`` takes in these readings.

    python3 bench/phases.py --workload <cell> --seed <n>

runs ``bench/run.py --workload <cell> --seed <n> --seconds <run_seconds>
--trace 1`` and prints its result line with the readings above added as
``phases``.  It needs a TPU.
"""

from __future__ import annotations

import bisect
import json
import os
import sys
import time
from array import array
from collections import defaultdict
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import trace_reduce as tr  # noqa: E402

PREFIX = "fzoo."
OUTSIDE = "outside fzoo.simulate"
#: The program's device scopes (``fzoo.local`` includes its three sub-scopes).
SCOPES = ("fzoo.local", "fzoo.local.update", "fzoo.local.score", "fzoo.local.grad",
          "fzoo.aggregate", "fzoo.round_end", "fzoo.eval")


def _job_of(t: float, jobs: list) -> Optional[int]:
    for i, (s, e) in enumerate(jobs):
        if s <= t <= e:
            return i
    return None


def _segments(spans: list, lo: float, hi: float) -> list:
    """[lo, hi] cut where a span starts or ends, each piece labelled by the
    shortest span that covers it (``OUTSIDE`` where none does)."""
    cuts = sorted({lo, hi} | {t for e in spans for t in (e.start, e.end) if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        m = 0.5 * (a + b)
        cover = [e for e in spans if e.start <= m <= e.end]
        out.append((a, b, min(cover, key=lambda e: e.end - e.start).name if cover else OUTSIDE))
    return out


def _idle_within(gaps: list, gap_starts: list, lo: float, hi: float) -> float:
    """Idle seconds in [lo, hi]: its overlap with the sorted, disjoint gaps."""
    total = 0.0
    for a, b in gaps[max(bisect.bisect_right(gap_starts, lo) - 1, 0):]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


def _device_pass(dev, lo: float, hi: float):
    """One pass over a device's operations inside [lo, hi]: every stretch
    that no operation covers (``trace_reduce.busy_and_gaps``), the chunk
    step's device seconds by instruction name, and the chunk step's
    executions."""
    mods = sorted((e.start, e.end) for e in dev.lines.get("XLA Modules", ())
                  if e.name.startswith(tr.CHUNK_MODULE))
    mod_starts = [s for s, _ in mods]
    starts, ends = array("d"), array("d")
    chunk_op_s: dict = defaultdict(float)
    for ev in dev.lines.get("XLA Ops", ()):
        if ev.end <= lo or ev.start >= hi:
            continue
        s, e = max(ev.start, lo), min(ev.end, hi)
        starts.append(s)
        ends.append(e)
        if tr._CONTAINER.search(ev.name):
            continue
        k = bisect.bisect_right(mod_starts, ev.start) - 1
        if k >= 0 and ev.start <= mods[k][1]:
            chunk_op_s[tr.instr_name(ev.name)] += e - s
    _, gaps = tr.busy_and_gaps(starts, ends, lo, hi, keep=len(starts) + 1)
    return sorted(gaps), chunk_op_s, mods


def reduce_phases(planes, op_names: Optional[dict] = None,
                  n_devices: Optional[int] = None) -> dict:
    """Every reading of this module from one trace, as a JSON-able dict:
    ``span_s`` and ``span_n`` per job, ``idle_by_phase`` summed over the
    jobs, ``scopes`` (device seconds, per device), ``unscoped_s`` and
    ``unscoped_top`` (the chunk step's device seconds under no program scope,
    and its ten largest such instructions), ``chunk_leads_s`` (from the first
    device; empty when the counts of spans and executions differ)."""
    op_names = op_names or {}
    host = sorted((e for p in planes if p.name.startswith("/host:")
                   for evs in p.lines.values() for e in evs), key=lambda e: e.start)
    jobs = [(e.start, e.end) for e in host if e.name == tr.JOB_SPAN]
    if not jobs:
        raise ValueError(f"the trace holds no {tr.JOB_SPAN!r} span")
    devs = sorted((p for p in planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)[:n_devices]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")

    by_job = [[] for _ in jobs]  # each job's fzoo.* spans
    for e in host:
        if e.name.startswith(PREFIX):
            i = _job_of(e.start, jobs)
            if i is not None:
                by_job[i].append(e)
    span_s, span_n = defaultdict(float), defaultdict(float)
    for (_, end), spans in zip(jobs, by_job):
        for e in spans:
            span_s[e.name] += (min(e.end, end) - e.start) / len(jobs)
            span_n[e.name] += 1.0 / len(jobs)

    idle: dict = defaultdict(float)
    scopes: dict = defaultdict(float)
    unscoped: dict = defaultdict(float)
    leads = []
    for k, dev in enumerate(devs):
        gaps, chunk_op_s, mods = _device_pass(dev, jobs[0][0], jobs[-1][1])
        gap_starts = [a for a, _ in gaps]
        for (s, end), spans in zip(jobs, by_job):
            for a, b, name in _segments(spans, s, end):
                idle[name] += _idle_within(gaps, gap_starts, a, b) / len(devs)
        for sc in SCOPES:
            scopes[sc] += sum(v for n, v in chunk_op_s.items()
                              if sc in op_names.get(n, "")) / len(devs)
        for n, v in chunk_op_s.items():
            if PREFIX not in op_names.get(n, ""):
                unscoped[n] += v / len(devs)
        if k == 0:
            chunks = [e.start for spans in by_job for e in spans if e.name == "fzoo.chunk"]
            runs = [s for s, _ in mods if _job_of(s, jobs) is not None]
            leads = [h - d for h, d in zip(chunks, runs)] if len(chunks) == len(runs) else []
    top = sorted(unscoped.items(), key=lambda x: -x[1])[:10]
    return {"jobs": len(jobs), "span_s": dict(span_s), "span_n": dict(span_n),
            "idle_by_phase": dict(sorted(idle.items(), key=lambda x: -x[1])),
            "scopes": dict(scopes), "unscoped_s": sum(unscoped.values()),
            "unscoped_top": [[f"{n} {op_names.get(n, '')[-120:]}".strip(), v] for n, v in top],
            "chunk_leads_s": leads}


def scope_share(t, scope: str) -> Optional[float]:
    """Share of busy time (%) in operations under ``scope``, or None when no
    operation carries it.  An upper bound: ``TraceSummary.op_s`` sums
    operations by instruction name over every executable of the window, so
    an instruction of another executable (F(x_0), the initial states) that
    shares a name with one of the chunk step's counts under that one's
    scope.  ``reduce_phases``'s ``scopes`` keep to the chunk step."""
    if t is None or t.busy_s <= 0:
        return None
    seconds = sum(v for n, v in t.op_s.items() if scope in t.op_names.get(n, ""))
    return 100.0 * seconds / t.busy_s if seconds > 0 else None


def main(argv=None) -> int:
    """``bench/run.py --trace 1`` (``harness.run``), with this module's
    readings of the trace added to the result line as ``phases``."""
    import argparse

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(1, os.path.join(os.path.dirname(BENCH_DIR), "src"))

    import harness

    harness.keep_logs_in_checkout()
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.require_tpu(cell.chips)
    except (harness.NoAccelerator, KeyError, FileNotFoundError) as e:
        print(f"phases: {e}", file=sys.stderr)
        return 2
    got = {}
    traced_window = harness.traced_window

    def keep_phases(runner, seconds, tdir, log):
        jobs, window_s, reduced = traced_window(runner, seconds, tdir, log)
        got["phases"] = reduce_phases(tr.load_planes(tr.find_trace(tdir)), reduced.op_names,
                                      n_devices=len(devices))
        return jobs, window_s, reduced

    harness.traced_window = keep_phases
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    out = harness.run(cell, args.seed, float(cell.spec["run_seconds"]), True, t_start,
                      devices, log=log)
    print(json.dumps({**out, "phases": got["phases"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
