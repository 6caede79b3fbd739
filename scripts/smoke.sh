#!/usr/bin/env bash
# Repo smoke: every module selftest CLI, end to end. Each one exits
# nonzero on failure, so `set -e` makes this script a single go/no-go
# gate — CI or a dev box runs it before trusting a change.
#
#   bash scripts/smoke.sh          # from the repo root
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="${PYTHONPATH:+$PYTHONPATH:}$PWD/src"

# Lint first when ruff is available (the container may not ship it —
# the tier-1 pre-step runs it where it exists; skipping is not a pass
# of lint, just absence of the tool).
if command -v ruff >/dev/null 2>&1; then
    echo "== ruff check =="
    ruff check src tests benchmarks
elif python -c "import ruff" >/dev/null 2>&1; then
    echo "== ruff check (python -m) =="
    python -m ruff check src tests benchmarks
else
    echo "== ruff not installed; skipping lint =="
fi

echo "== repro.chip --selftest =="
python -m repro.chip --selftest

echo "== repro.fleet --distributed-selftest =="
python -m repro.fleet --distributed-selftest

echo "== repro.fleet --chaos-selftest =="
python -m repro.fleet --chaos-selftest

echo "== repro.deploy --selftest =="
python -m repro.deploy --selftest

echo "== repro.tune --selftest =="
python -m repro.tune --selftest

echo "== repro.variability --selftest =="
python -m repro.variability --selftest

echo "== repro.obs --selftest =="
python -m repro.obs --selftest

echo "== repro.lm --selftest =="
python -m repro.lm --selftest

echo "smoke: ALL PASS"
