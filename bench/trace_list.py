#!/usr/bin/env python3
"""List what a profiler trace holds, to read one by hand before reducing it.

    python3 bench/trace_list.py <trace dir or .xplane.pb> [events per line]

Prints each plane and line with its event count and the first events with
their times and text metadata."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402


def main(argv) -> int:
    path = argv[1]
    per_line = int(argv[2]) if len(argv) > 2 else 8
    if os.path.isdir(path):
        path = trace_reduce.find_trace(path)
    for p in trace_reduce.load_planes(path):
        print(f"PLANE {p.name}")
        for name, evs in p.lines.items():
            print(f"  LINE {name}: {len(evs)} events")
            for e in evs[:per_line]:
                print(f"    {e.name!r} start={e.start:.9f} dur={e.end - e.start:.9f} "
                      f"text={e.text[:300]!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
