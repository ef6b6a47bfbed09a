#!/usr/bin/env bash
# Idle-host race hunt: widened-seed stress soaks + deep hypothesis runs.
# Usage: tools/run_soak.sh [iterations]
set -u
cd "$(dirname "$0")/.."
ITER=${1:-5}
for i in $(seq 1 "$ITER"); do
  echo "[soak] iteration $i/$ITER ($(date -u +%FT%TZ))"
  RSDL_STRESS_SEEDS=$((3 + i * 3)) python -m pytest tests/test_stress.py -q \
    2>&1 | tail -1
  HYPOTHESIS_PROFILE=deep python -m pytest tests/test_rebatch_property.py \
    -q -p no:cacheprovider 2>&1 | tail -1
done
echo "[soak] complete"
