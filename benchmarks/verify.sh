#!/usr/bin/env bash
# CI smoke gate: tier-1 tests, and the static contract gate.
#
#   bash benchmarks/verify.sh            # tier-1 tests
#   bash benchmarks/verify.sh --static   # static gate only: contract
#                                        # analyzer + ruff (no execution)
#   bash benchmarks/verify.sh --faults   # fault-tolerance test suite
#   bash benchmarks/verify.sh --pool     # partial-participation test suite
#
# Speed is measured on the chip (bench/, BENCHMARK.json), never here: a CPU
# run gates correctness and counts only.
#
# --static runs the compiled-program contract analyzer (DESIGN.md Sec. 7:
# python -m repro.analysis lowers every registered engine entry point and
# lints jaxpr + HLO, no execution) plus `ruff check` at the version pinned
# in pyproject.toml.  ruff is not baked into every image, so its absence is
# a LOUD skip, not a failure -- CI installs it and gets the full gate.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--static" ]]; then
    echo "== static gate: compiled-program contracts =="
    # Engine contracts + the Pallas kernel-launch audit + the PRNG key-flow
    # lint, with the machine-readable report CI uploads as an artifact.
    # CONTRACT_FLOOR guards against registrations silently vanishing (e.g.
    # an import-time exception swallowing half the registry).
    CONTRACT_FLOOR="${CONTRACT_FLOOR:-27}"
    REPORT="${ANALYSIS_REPORT:-analysis_report.json}"
    python -m repro.analysis --json "${REPORT}"
    N_CONTRACTS=$(python -c "import json; print(json.load(open('${REPORT}'))['n_contracts'])")
    echo "static gate: ${N_CONTRACTS} contract(s) ran (floor ${CONTRACT_FLOOR}), report: ${REPORT}"
    if [[ "${N_CONTRACTS}" -lt "${CONTRACT_FLOOR}" ]]; then
        echo "ERROR: only ${N_CONTRACTS} contracts ran, below the floor of ${CONTRACT_FLOOR}" >&2
        exit 1
    fi

    echo "== static gate: ruff =="
    if command -v ruff >/dev/null 2>&1; then
        ruff check .
    else
        echo "WARNING: ruff not installed -- SKIPPING the lint half of the" >&2
        echo "WARNING: static gate (pip install ruff to match CI)" >&2
    fi
    echo "verify --static: OK"
    exit 0
fi

if [[ "${1:-}" == "--faults" ]]; then
    # Robustness gate: the fault-injection suite end to end -- deterministic
    # schedules, faults-off bitwise identity, quarantine reset vs the
    # fresh-init oracle, corrupt-checkpoint fallback and chunk rollback.
    echo "== fault-tolerance gate: test suite =="
    python -m pytest -x -q tests/test_faults.py
    echo "verify --faults: OK"
    exit 0
fi

if [[ "${1:-}" == "--pool" ]]; then
    # Partial-participation gate: the client-pool suite end to end --
    # deterministic cohort sampling, K=N bitwise identity against the dense
    # engine (sim + distributed), pooled checkpoint resume, one cohort
    # executable across rounds.
    echo "== partial-participation gate: test suite =="
    python -m pytest -x -q tests/test_pool.py
    echo "verify --pool: OK"
    exit 0
fi

echo "== tier-1 pytest =="
python -m pytest -x -q

echo "verify: OK"
