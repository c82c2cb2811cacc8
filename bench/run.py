#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip(s) this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, device start, the objective's data or weights from the
seed, one warm-up job of the cell's own shape) is timed as ``setup_s``; then
jobs run back to back for ``--seconds``.  ``--trace 1`` runs the window
under the profiler and reports the per-layer metrics instead of the
end-to-end ones.  The run then checks the window's results against the
plain reference (``check.py``) and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), and last the
numbers compared, each beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    harness.keep_logs_in_checkout()
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.require_tpu(cell.chips)
    except (harness.NoAccelerator, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                          devices, log=log)
    except Exception:
        traceback.print_exc()
        return 1
    for name, n in out["checks"].items():
        log(f"check {name} = {n['value']!r} (limit {n['limit']!r})")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
