#!/usr/bin/env bash
# Lint / format gate (reference format.sh: yapf + flake8; here ruff, which
# subsumes both). Usage:
#   ./format.sh          # fix in place
#   ./format.sh --check  # CI mode: fail on violations, change nothing
set -euo pipefail
cd "$(dirname "$0")"

TARGETS=(ray_shuffling_data_loader_tpu tests benchmarks examples __graft_entry__.py)

if ! command -v ruff >/dev/null 2>&1; then
    echo "ruff not installed; running syntax check only" >&2
    python -m compileall -q "${TARGETS[@]}"
    if [[ "${1:-}" == "--check" ]]; then
        # Invariant lint rides the check gate even without ruff
        # (ISSUE 14; pure stdlib/AST).
        python tools/rsdl_lint.py
    fi
    exit 0
fi

if [[ "${1:-}" == "--check" ]]; then
    ruff check "${TARGETS[@]}"
    # Style clean isn't invariant clean: chain the repo's own
    # static-analysis suite (gate/knob/vocab/determinism/lock/barrier
    # checkers — see docs/static-analysis.md) into the same gate.
    python tools/rsdl_lint.py
else
    ruff check --fix "${TARGETS[@]}"
fi
