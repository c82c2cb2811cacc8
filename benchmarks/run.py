"""Benchmark harness entry point (deliverable d).

One module per paper table/figure + the roofline table + kernel microbench.
Prints ``name,us_per_call,derived`` CSV per row.  Modules that expose a
``json_payload()`` hook additionally get their metrics serialized to
``BENCH_<name>.json`` next to this file, so the perf trajectory (e.g. the
surrogate-step speedup and factor_refactor_rate from the kernels module) is
machine-readable across PRs.

    PYTHONPATH=src python -m benchmarks.run            # quick (CPU-sized)
    REPRO_BENCH_FULL=1 PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --only fig1,roofline

``--compare`` diffs the fresh run against the COMMITTED ``BENCH_*.json``
files instead of overwriting them, and exits nonzero on any perf metric
regressing by more than ``--compare-tol`` (default 20%) -- so perf claims
are checkable in CI without a dashboard.  Metric direction is inferred
from the key name: ``*_us`` / ``*ms_per_round`` are lower-is-better,
``*per_sec`` / ``*speedup`` are higher-is-better; everything else (shape
descriptors, rates, flags) is informational and ignored.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "fig1": "benchmarks.fig1_synthetic",
    "fig2": "benchmarks.fig2_attack",
    "fig3": "benchmarks.fig3_metric",
    "fig4": "benchmarks.fig4_disparity",
    "fig5": "benchmarks.fig5_localsteps",
    "fig6": "benchmarks.fig6_features",
    "thm1": "benchmarks.thm1_rates",
    "kernels": "benchmarks.kernels_bench",
    "rounds": "benchmarks.rounds_bench",
    "roofline": "benchmarks.roofline",
    "faults": "benchmarks.faults_bench",
}


#: key-name suffix/substring -> metric direction for --compare.
_LOWER_BETTER = ("_us", "_ms", "ms_per_round", "ms_per_boundary")
_HIGHER_BETTER = ("per_sec", "speedup")

#: Keys that are DELIBERATELY informational: meaningful numbers we record
#: but refuse to gate on.  Rates move with workload shape, not perf; the
#: `_usec`/`_msec` spellings are machine-dependent wall-I/O timings; the
#: overhead ratio is already gated through its two ms_per_round parents.
#: Any direction-less key NOT matched here shows up in the ``ungated:``
#: summary that --compare prints per BENCH file, so silently-untracked
#: metrics are visible instead of vanishing from the regression gate.
_INFORMATIONAL = ("repair_rate", "refactor_rate", "drop_rate",
                  "quarantine_rate", "mask_overhead_ratio",
                  "pool_overhead_ratio", "_usec", "_msec")


def _metric_direction(key: str) -> str | None:
    """'lower' / 'higher' for perf metrics, None for informational values."""
    if any(key.endswith(s) for s in _LOWER_BETTER):
        return "lower"
    if any(s in key for s in _HIGHER_BETTER):
        return "higher"
    return None


def _is_informational(key: str) -> bool:
    return any(key.endswith(s) for s in _INFORMATIONAL)


def ungated_keys(payload: dict) -> list[str]:
    """Dotted keys of numeric leaves the regression gate ignores, split out
    from the explicit allowlist: ``['cap (!)', 'repair_rate']`` style, with
    ``(!)`` marking keys that are neither gated nor allowlisted."""
    out = []
    for key, _ in _walk_metrics(payload):
        leaf = key.rsplit(".", 1)[-1]
        if _metric_direction(leaf) is not None:
            continue
        out.append(key if _is_informational(leaf) else f"{key} (!)")
    return sorted(out)


def _walk_metrics(payload, prefix=""):
    """Yield (dotted_key, value) for every numeric leaf of a payload.

    The ``backend`` identity subtree holds only strings, so it never
    contributes metrics."""
    if isinstance(payload, dict):
        for k, v in payload.items():
            yield from _walk_metrics(v, f"{prefix}{k}.")
    elif isinstance(payload, (int, float)) and not isinstance(payload, bool):
        yield prefix.rstrip("."), float(payload)


def backend_identity() -> dict:
    """Stamp recorded into every BENCH_*.json: numbers from a CPU run and a
    TPU run are not comparable, so --compare refuses cross-backend diffs."""
    import jax

    return {
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }


def compare_payload(name: str, fresh: dict, committed_path: str, tol: float) -> list[str]:
    """Regressions (> tol relative) of fresh vs the committed BENCH json."""
    if not os.path.exists(committed_path):
        return [f"{name}: no committed baseline at {committed_path}"]
    with open(committed_path) as f:
        committed = json.load(f)
    base_backend = (committed.get("backend") or {}).get("platform")
    fresh_backend = (fresh.get("backend") or {}).get("platform")
    if base_backend and fresh_backend and base_backend != fresh_backend:
        return [
            f"{name}: REFUSING cross-backend comparison -- committed baseline "
            f"is {base_backend} ({(committed['backend']).get('device_kind')}), "
            f"this run is {fresh_backend}; re-baseline on the matching backend"
        ]
    base = dict(_walk_metrics(committed))
    regressions = []
    for key, val in _walk_metrics(fresh):
        direction = _metric_direction(key.rsplit(".", 1)[-1])
        if direction is None or key not in base or base[key] <= 0:
            continue
        rel = val / base[key] - 1.0
        if (direction == "lower" and rel > tol) or (direction == "higher" and rel < -tol):
            regressions.append(
                f"{name}.{key}: {base[key]:.3g} -> {val:.3g} "
                f"({rel:+.1%}, {direction}-is-better)"
            )
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset of " + ",".join(MODULES))
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--compare", action="store_true",
                    help="diff against committed BENCH_*.json (no overwrite); "
                         "exit nonzero on >tol perf regression")
    ap.add_argument("--compare-tol", type=float, default=0.2,
                    help="relative regression tolerance for --compare")
    args = ap.parse_args()
    enable_compile_cache()
    quick = not (args.full or os.environ.get("REPRO_BENCH_FULL"))

    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(MODULES)
    print("name,us_per_call,derived")
    failures = 0
    regressions: list[str] = []
    for name in names:
        import importlib

        t0 = time.time()
        try:
            mod = importlib.import_module(MODULES[name])
            rows = mod.run(quick=quick)
            for row in rows:
                print(row.csv(), flush=True)
            payload = getattr(mod, "json_payload", lambda: None)()
            if payload:
                payload["backend"] = backend_identity()
                path = os.path.join(os.path.dirname(__file__), f"BENCH_{name}.json")
                if args.compare:
                    regs = compare_payload(name, payload, path, args.compare_tol)
                    regressions.extend(regs)
                    status = f"{len(regs)} regressions vs {path}" if regs else f"no regressions vs {path}"
                    print(f"# {name}: {status}", flush=True)
                    ungated = ungated_keys(payload)
                    if ungated:
                        print(f"# {name}: ungated: " + ", ".join(ungated), flush=True)
                else:
                    with open(path, "w") as f:
                        json.dump(payload, f, indent=2, sort_keys=True)
                    print(f"# {name}: wrote {path}", flush=True)
            print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name}: FAILED\n# " + traceback.format_exc().replace("\n", "\n# "),
                  flush=True)
    for r in regressions:
        print(f"# REGRESSION {r}", flush=True)
    if failures or regressions:
        sys.exit(1)


if __name__ == "__main__":
    main()
