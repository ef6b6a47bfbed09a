#!/usr/bin/env bash
# Multi-trainer benchmark sweep (VERDICT r3 item 2): the reference's
# official workload shape at {4,8,16} trainers x {2,4} reducers/trainer
# (reference benchmarks/benchmark_batch.sh:9-24), on a >=5 GB DATA_SPEC
# dataset. One trial x 10 epochs per config, results + CSVs under
# tools/sweep_results/; the JSON summary line of each config is saved as
# <tag>.json for the BENCHLOG table.
set -euo pipefail
cd "$(dirname "$0")/.."
OUT=tools/sweep_results
mkdir -p "$OUT"
ROWS=${RSDL_SWEEP_ROWS:-29761904}     # ~5 GB at 168 B/row
FILES=${RSDL_SWEEP_FILES:-25}         # reference's smallest official file count
EPOCHS=${RSDL_SWEEP_EPOCHS:-10}
DATA_DIR=${RSDL_SWEEP_DATA:-.bench_cache/sweep5g}
# Reuse only a COMPLETE dataset: a capture-preempted trial can die
# mid-generation, and benchmarking a fragment while recording it as the
# full workload would silently corrupt the rows/s comparison. Re-counted
# before every trial so the first successful generation flips later
# trials to reuse (and a fragment left by a preempted trial is wiped).
count_files() {
  find "$DATA_DIR" -name '*.parquet.snappy' 2>/dev/null | wc -l
}
gen_args() {
  if [ "$(count_files)" -ge "$FILES" ]; then echo "--use-old-data"; fi
}
nfiles=$(count_files)
if [ "$nfiles" -gt 0 ] && [ "$nfiles" -lt "$FILES" ]; then
  echo "[sweep] partial dataset ($nfiles of >=$FILES files); regenerating"
  rm -rf "$DATA_DIR"
fi
for T in 4 8 16; do
  for RPT in 2 4; do
    R=$((T * RPT))
    TAG="t${T}_r${R}"
    if [ -s "$OUT/$TAG.json" ]; then
      echo "[sweep] $TAG already recorded; skipping"
      continue
    fi
    echo "[sweep] trainers=$T reducers=$R ($(date -u +%FT%TZ))"
    python benchmarks/benchmark.py \
      --num-rows "$ROWS" --num-files "$FILES" \
      --num-row-groups-per-file 5 --batch-size 250000 \
      --num-epochs "$EPOCHS" --num-trials 1 \
      --num-trainers "$T" --num-reducers "$R" \
      --max-concurrent-epochs 2 \
      --data-dir "$DATA_DIR" $(gen_args) \
      --stats-dir "$OUT/stats_$TAG" \
      > "$OUT/$TAG.log" 2>&1 || {
        echo "[sweep] $TAG FAILED (see $OUT/$TAG.log)"; continue; }
    grep -E '^\{' "$OUT/$TAG.log" | tail -1 > "$OUT/$TAG.json"
    echo "[sweep] $TAG done: $(cat "$OUT/$TAG.json")"
  done
done
echo "[sweep] complete"
