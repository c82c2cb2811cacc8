"""Reduction of a profiler trace to device busy, idle, kernel and scope times.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  A window of a cell holds millions of device
operations, so they are streamed into flat arrays, never kept as objects.

* The window is the span of the benchmark's ``bench.job`` host annotations:
  from the first job's start to the last job's end.
* A device is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line, its executables those of ``XLA Modules``.
  An operation's event is named by its HLO instruction (``%name = ...``).
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices; idle share is 1 - busy / window.
* ``op_s`` sums each instruction's device time, leaving out the loops,
  branches and calls whose events span their bodies' operations; a
  kernel's time is the sum over the instructions named after it (its
  Pallas call or the jitted wrapper that holds it).
* A scope's time (``bench.objective``) sums the operations inside the chunk
  step whose HLO metadata (``op_name``, from the compiled chunk step's text)
  holds the scope's name.  A fusion carries the metadata of one of the
  operations it fused, so the split of a mixed fusion is approximate.
* The chunk step's time is the union of the ``XLA Modules`` events of the
  executable named ``jit_run`` (the program's ``rounds.ChunkStep``).
* Device and host clocks of one trace agree to about a millisecond.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re
from array import array
from collections import defaultdict
from typing import Optional

JOB_SPAN = "bench.job"
CHUNK_MODULE = "jit_run"
SCOPES = ("bench.objective",)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds
    end: float


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict  # line name -> iterable of Event (or anything with name/start/end)


class _Events:
    """The events of one ProfileData line, as ``Event`` on iteration."""

    def __init__(self, line):
        self._line = line

    def __iter__(self):
        for e in self._line.events:
            s = e.start_ns * 1e-9
            yield Event(e.name, s, s + e.duration_ns * 1e-9)


def load_planes(path: str) -> list[Plane]:
    """The planes of one ``.xplane.pb``; events are read when iterated."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [Plane(p.name, {line.name: _Events(line) for line in p.lines}) for p in pd.planes]


def find_trace(tdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return paths[-1]


#: Operations that run other computations (a loop, a branch, a call): their
#: events span their bodies' operations, which are counted on their own.
_CONTAINER = re.compile(r" (while|conditional|call)\(")


def instr_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{[^}]*op_name="([^"]*)"')


def op_names_from_hlo(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name`` metadata, from an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of intervals, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_and_gaps(starts, ends, lo: float, hi: float, keep: int = 500):
    """Union length of the intervals (starts[i], ends[i]) inside [lo, hi] and
    the ``keep`` longest stretches that none covers, in one pass over the
    intervals sorted by start."""
    order = range(len(starts))
    if any(starts[i] > starts[i + 1] for i in range(len(starts) - 1)):
        order = sorted(order, key=starts.__getitem__)
    busy, t, top = 0.0, lo, []
    for i in order:
        s, e = max(starts[i], lo), min(ends[i], hi)
        if e <= s:
            continue
        if s > t:
            heapq.heappush(top, (s - t, t, s))
            if len(top) > keep:
                heapq.heappop(top)
        if e > t:
            busy += e - max(s, t)
            t = e
    if t < hi:
        heapq.heappush(top, (hi - t, t, hi))
        if len(top) > keep:
            heapq.heappop(top)
    return busy, [(a, b) for _, a, b in top]


@dataclasses.dataclass
class TraceSummary:
    window: tuple  # (start, end) of the traced window, seconds
    n_devices: int
    busy_s: float  # per device, averaged
    jobs: list  # [(start, end)] host spans of the jobs
    chunk_busy_s: float  # per device, averaged: union of chunk-step executions
    chunk_busy_in_jobs: list  # per job: chunk-step busy seconds inside its span
    op_s: dict  # instruction name -> summed device seconds (per device)
    scope_s: dict  # scope -> summed seconds of chunk-step ops under it (per device)
    idle_gaps: list  # [(label, seconds)] idle time by the host span around it
    op_names: dict = dataclasses.field(default_factory=dict)  # instruction -> op_name

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, pattern: str) -> float:
        """Summed device seconds of the instructions whose name matches."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.fullmatch(k))

    def breakdown(self) -> dict:
        """The ten device operations that took most time, each named by its
        instruction and the tail of its ``op_name``, and the longest idle
        time by the host span around it."""
        top = sorted(self.op_s.items(), key=lambda x: -x[1])[:10]
        label = lambda n: f"{n} {self.op_names.get(n, '')[-120:]}".strip()
        return {"device_ops": [[label(n), s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def _label(host: list, starts: list, jobs: list, t: float) -> str:
    """The innermost host span at time t; ``between jobs`` outside every job."""
    if not any(s <= t <= e for s, e in jobs):
        return "between jobs"
    best = None
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        e = host[i]
        if e.end >= t and e.name != JOB_SPAN and (
                best is None or e.end - e.start < best.end - best.start):
            best = e
        if t - e.start > 60.0:  # no host span of the benchmark is that long
            break
    return best.name if best is not None else "no host span"


def reduce(planes: list[Plane], op_names: Optional[dict] = None,
           n_devices: Optional[int] = None) -> TraceSummary:
    op_names = op_names or {}
    host = sorted((e for p in planes if p.name.startswith("/host:")
                   for evs in p.lines.values() for e in evs), key=lambda e: e.start)
    jobs = [(e.start, e.end) for e in host if e.name == JOB_SPAN]
    if not jobs:
        raise ValueError(f"the trace holds no {JOB_SPAN!r} span")
    lo, hi = jobs[0][0], jobs[-1][1]
    devs = sorted((p for p in planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    if n_devices is not None:
        devs = devs[:n_devices]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")

    busy = chunk = 0.0
    chunk_in_jobs = [0.0] * len(jobs)
    op_s: dict = defaultdict(float)
    chunk_op_s: dict = defaultdict(float)
    gap_list = []
    for p in devs:
        mods = sorted((e.start, e.end) for e in p.lines.get("XLA Modules", ())
                      if e.name.startswith(CHUNK_MODULE))
        chunk += union_length(mods, lo, hi)
        for i, (s, e) in enumerate(jobs):
            chunk_in_jobs[i] += union_length(mods, s, e)
        mod_starts = [s for s, _ in mods]
        starts, ends = array("d"), array("d")
        for ev in p.lines.get("XLA Ops", ()):
            if ev.end <= lo or ev.start >= hi:
                continue
            s, e = max(ev.start, lo), min(ev.end, hi)
            starts.append(s)
            ends.append(e)
            if _CONTAINER.search(ev.name):
                continue  # its body's operations have events of their own
            name = instr_name(ev.name)
            op_s[name] += e - s
            k = bisect.bisect_right(mod_starts, ev.start) - 1
            if k >= 0 and ev.start <= mods[k][1]:
                chunk_op_s[name] += e - s
        b, g = busy_and_gaps(starts, ends, lo, hi)
        busy += b
        gap_list += g
    k = len(devs)
    scope_s = {sc: sum(v for n, v in chunk_op_s.items() if sc in op_names.get(n, "")) / k
               for sc in SCOPES}
    host_starts = [e.start for e in host]
    labelled: dict = defaultdict(float)
    for s, e in gap_list:
        labelled[_label(host, host_starts, jobs, 0.5 * (s + e))] += (e - s) / k
    return TraceSummary(
        window=(lo, hi), n_devices=k, busy_s=busy / k, jobs=jobs, chunk_busy_s=chunk / k,
        chunk_busy_in_jobs=[c / k for c in chunk_in_jobs],
        op_s={n: v / k for n, v in op_s.items()}, scope_s=scope_s,
        idle_gaps=sorted(labelled.items(), key=lambda x: -x[1]),
        op_names={n: op_names[n] for n in op_s if n in op_names},
    )


def reduce_dir(tdir: str, op_names: Optional[dict] = None,
               n_devices: Optional[int] = None) -> TraceSummary:
    return reduce(load_planes(find_trace(tdir)), op_names=op_names, n_devices=n_devices)

