"""Reproduce the paper's figures and Theorem 1's rates.

    PYTHONPATH=src python -m benchmarks             # every module, CPU-sized
    PYTHONPATH=src python -m benchmarks fig1 thm1   # a subset
    REPRO_BENCH_FULL=1 PYTHONPATH=src python -m benchmarks   # the paper's sizes

Prints one ``name,us_per_call,derived`` CSV row per result (``common.Row``).
These modules reproduce results, not speed; the chip benchmark is ``bench/``.
"""

from __future__ import annotations

import importlib
import os
import sys

from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "fig1": "fig1_synthetic",
    "fig2": "fig2_attack",
    "fig3": "fig3_metric",
    "fig4": "fig4_disparity",
    "fig5": "fig5_localsteps",
    "fig6": "fig6_features",
    "thm1": "thm1_rates",
}


def main(argv=None) -> int:
    names = list(sys.argv[1:] if argv is None else argv) or list(MODULES)
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        print(f"unknown module(s) {unknown}; choose from {list(MODULES)}", file=sys.stderr)
        return 2
    enable_compile_cache()
    quick = not os.environ.get("REPRO_BENCH_FULL")
    print("name,us_per_call,derived")
    for name in names:
        for row in importlib.import_module(f"benchmarks.{MODULES[name]}").run(quick=quick):
            print(row.csv(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
