#!/usr/bin/env bash
# CI test entry (reference run_ci_tests.sh:8-11 wraps pytest likewise),
# two-tiered (VERDICT r3 item 7):
#   fast tier — in-process tests, fail-fast (-x), target <8 min;
#   slow tier — multi-process/subprocess tests (@pytest.mark.slow), run
#   WITHOUT -x so one flaky subprocess test cannot kill the whole lane.
# Tests force the CPU backend with 8 virtual devices via tests/conftest.py.
# RSDL_CI_TIER=fast|slow runs a single tier (CI matrix lanes); default both.
set -euo pipefail
cd "$(dirname "$0")"
tier="${RSDL_CI_TIER:-all}"
rc=0
if [ "$tier" != "slow" ]; then
  # Static-analysis lane (ISSUE 14), exit-code gated and FIRST: the
  # invariant suite (gate-integrity lazy-import graph, knob registry vs
  # TUNING.md, metric/event vocabulary vs observability.md, determinism
  # hygiene, lock discipline, flush-before-done barriers) is pure AST —
  # seconds, no runtime — so a structural violation fails the lane
  # before any test minute is spent. docs/static-analysis.md has the
  # checker catalog and the suppression policy.
  python tools/rsdl_lint.py
  # Telemetry is env-gated and DEFAULT OFF: this pass asserts tier-1 is
  # clean with it disabled (the zero-overhead path).
  python -m pytest tests/ -m "not slow" -v --durations=10 -x
  # ... and must not perturb the data plane when ENABLED: re-run the
  # core data-path tests with tracing + metrics on, spooling to a throwaway
  # dir (every spawned worker/actor inherits the env and spools spans).
  RSDL_TRACE=1 RSDL_METRICS=1 RSDL_TRACE_DIR="$(mktemp -d)" \
    python -m pytest tests/test_telemetry.py tests/test_shuffle.py \
      tests/test_batch_queue.py tests/test_dataset.py \
      tests/test_jax_dataset.py tests/test_stats.py \
      -m "not slow" -q -x
  # Audit lane (ISSUE 2): the data-correctness digests on — the shuffle,
  # queue, dataset, and device-staging suites must pass with every stage
  # folding exactly-once digests, and the audit suite itself verifies the
  # verdicts (incl. the injected-fault and determinism checks).
  RSDL_AUDIT=1 RSDL_AUDIT_DIR="$(mktemp -d)" RSDL_METRICS=1 \
    python -m pytest tests/test_audit.py tests/test_shuffle.py \
      tests/test_batch_queue.py tests/test_dataset.py \
      tests/test_jax_dataset.py tests/test_audit_report.py \
      -m "not slow" -q -x
  # Chaos lane (ISSUE 3): the fault-injection plane armed with a fixed-
  # seed low-probability schedule across the core data-path suites —
  # recovery (bounded stage re-execution + transport retry) must make
  # the injected crashes/resets INVISIBLE to every existing test, and
  # the dedicated chaos harness proves each failure class reconciles
  # exactly-once under RSDL_AUDIT (docs/robustness.md). The xN caps
  # keep the lane deterministic-by-construction: at most 1 crash per
  # worker (2 workers) and 2 resets per driver process can never
  # exhaust a 3-attempt retry budget, so no probabilistic flake mode
  # exists regardless of task placement.
  # RSDL_TCP_ZEROCOPY rides along so recovery is proven over the
  # vectored-framing transport path too (ISSUE 5), not just the legacy
  # pickle frames; RSDL_TCP_STREAMS=2 keeps striping on so transport
  # fault sites exercise per-stream connections (ISSUE 6).
  # tests/test_slo.py rides the chaos lane for its wedge-alert proof
  # (ISSUE 9): an injected wedge fault must fire — and later resolve —
  # the default wedged-worker alert with audit ok=true (the test arms
  # its own deterministic RSDL_FAULTS schedule, overriding the lane's).
  RSDL_AUDIT=1 RSDL_AUDIT_DIR="$(mktemp -d)" RSDL_METRICS=1 \
    RSDL_TCP_ZEROCOPY=1 RSDL_TCP_STREAMS=2 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1,transport.send/driver:reset:0.02x2" \
    RSDL_FAULTS_SEED=1234 \
    python -m pytest tests/test_chaos.py tests/test_shuffle.py \
      tests/test_batch_queue.py tests/test_dataset.py \
      tests/test_slo.py \
      -m "not slow" -q -x
  # Observability lane (ISSUE 4): the live obs plane on — metrics
  # spool/aggregation + the RSDL_OBS_PORT scrape endpoint enabled for
  # the telemetry/obs suites (core data-path suites ride along so the
  # endpoint demonstrably doesn't perturb them; the smoke test binds
  # its own free port, so a taken lane port only warns).
  # The decision plane (ISSUE 9) rides the obs lane: capacity-ledger
  # accounting + zero-overhead proof, online-vs-post-hoc critical-path
  # parity, and SLO rule-engine semantics.
  RSDL_METRICS=1 RSDL_OBS_PORT=18431 \
    python -m pytest tests/test_obs.py tests/test_telemetry.py \
      tests/test_epoch_report.py tests/test_shuffle.py \
      tests/test_capacity.py tests/test_critical.py \
      -m "not slow" -q -x
  # Epoch critical-path report, gated BOTH ways against the committed
  # fixture pair: a clean run must exit 0 (and name the dominant
  # stage), an injected regression must exit non-zero.
  python tools/epoch_report.py \
    --trace tests/fixtures/epoch_report/trace.json \
    --epoch-csv tests/fixtures/epoch_report/epoch_stats.csv \
    --bench tests/fixtures/epoch_report/bench_clean.json \
    --baseline tests/fixtures/epoch_report/baseline.json
  if python tools/epoch_report.py \
    --trace tests/fixtures/epoch_report/trace.json \
    --bench tests/fixtures/epoch_report/bench_regressed.json \
    --baseline tests/fixtures/epoch_report/baseline.json > /dev/null; then
    echo "epoch_report failed to flag the injected regression" >&2
    exit 1
  fi
  # Device-direct lane (ISSUE 8): reducer outputs in staging layout
  # forced ON across the core data-path suites — batch-aligned packed
  # bodies + boundary remainders must be invisible to every existing
  # consumer (bit-identical streams), reconcile exactly-once under
  # RSDL_AUDIT (packed segments digest through their logical column
  # views), and survive the chaos schedule (a retried reduce re-packs
  # against the same rank-stream offsets). Exit-code gated like every
  # other lane.
  RSDL_DEVICE_DIRECT=on \
    RSDL_AUDIT=1 RSDL_AUDIT_DIR="$(mktemp -d)" RSDL_METRICS=1 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=4321 \
    python -m pytest tests/test_device_direct.py \
      tests/test_device_direct_audit.py tests/test_jax_dataset.py \
      tests/test_dataset.py tests/test_shuffle.py \
      -m "not slow" -q -x
  # Elastic lane (ISSUE 10): autoscaler + tiered store eviction +
  # graceful drain, chaos-proven. The membership/drain/evict tests run
  # under a low-prob ambient fault schedule (same xN-capped convention
  # as the chaos lane) with audit strict + metrics on; the acceptance
  # test — scale-up, a crash mid-drain degrading into the failover
  # backstop, a shm→spill→drop eviction re-materialized from lineage,
  # audit ok=true and ledger residency zero at cleanup — arms its own
  # deterministic schedule on top. Exit-code gated.
  RSDL_AUDIT=1 RSDL_AUDIT_DIR="$(mktemp -d)" RSDL_METRICS=1 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=555 \
    python -m pytest tests/test_elastic.py -m "not slow" -q -x
  # Decode-plane lane (ISSUE 11): row-group parallelism FORCED (2
  # threads on any host), column pushdown derived from staging layouts,
  # and the cross-epoch shared decode cache — all under the audit-STRICT
  # chaos schedule, so bit-identity of the parallel/selective/pushdown
  # decode paths is proven by exactly-once digests, not just unit
  # asserts. The dedicated suite owns the shared-cache assertions.
  RSDL_DECODE_ROWGROUPS=2 RSDL_DECODE_PUSHDOWN=on \
    RSDL_DECODE_CACHE_SHARED=on \
    RSDL_AUDIT=1 RSDL_AUDIT_STRICT=1 RSDL_AUDIT_DIR="$(mktemp -d)" \
    RSDL_METRICS=1 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=777 \
    python -m pytest tests/test_decode_plane.py -m "not slow" -q -x
  # Block-plan leg (ISSUE 12): the plan family switched to block:1 with
  # the selective schedule FORCED ON, under the same audit-STRICT chaos
  # schedule — exactly-once coverage must hold when the plan family
  # changes mid-fleet-of-faults, per-reducer row-group selections are
  # disjoint by construction (each group decoded once per epoch), and
  # the stream-equality tests prove selective==materialized under the
  # BLOCK plan too. The shared-cache tests are excluded: a forced
  # selective schedule never publishes decode-cache segments, so their
  # epoch-0 index-schedule assertions cannot hold by design.
  RSDL_SHUFFLE_PLAN=block RSDL_SELECTIVE_READS=on \
    RSDL_DECODE_ROWGROUPS=2 \
    RSDL_AUDIT=1 RSDL_AUDIT_STRICT=1 RSDL_AUDIT_DIR="$(mktemp -d)" \
    RSDL_METRICS=1 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=888 \
    python -m pytest tests/test_decode_plane.py -m "not slow" \
      -k "not shared_cache" -q -x
  # ... and the decode knobs must be invisible to the core data-path
  # suites: forced row-group parallelism + pushdown ride along (shared
  # cache deliberately NOT set here — cross-run cache hits legitimately
  # change epoch-0 schedules, which test_shuffle asserts).
  RSDL_DECODE_ROWGROUPS=2 RSDL_DECODE_PUSHDOWN=on \
    RSDL_AUDIT=1 RSDL_AUDIT_DIR="$(mktemp -d)" RSDL_METRICS=1 \
    python -m pytest tests/test_shuffle.py tests/test_dataset.py \
      tests/test_jax_dataset.py -m "not slow" -q -x
  # Planner lane (ISSUE 20): the cost-based plan compiler FORCED ON over
  # the shuffle/decode/device-direct suites under strict audit + the
  # same low-prob xN-capped fault schedule — planned runs must stay
  # exactly-once and bit-identical for fixed seed + fixed plan, with
  # every planner-chosen knob (plan family, selective engagement,
  # decode threads, window depth, native threads) riding the stage-task
  # knob channel instead of the workers' stale env snapshots. The
  # planner suite itself owns the cost-model units, override precedence,
  # replan recording, and the zero-overhead-off fresh-interpreter proof.
  RSDL_PLAN=auto \
    RSDL_AUDIT=1 RSDL_AUDIT_STRICT=1 RSDL_AUDIT_DIR="$(mktemp -d)" \
    RSDL_METRICS=1 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=2020 \
    python -m pytest tests/test_planner.py tests/test_shuffle.py \
      tests/test_decode_plane.py tests/test_device_direct.py \
      -m "not slow" -k "not shared_cache" -q -x
  # Resume lane (ISSUE 13): the durable epoch-state plane under chaos.
  # Journal fold/identity units, graceful suspend (programmatic +
  # SIGTERM), the SIGKILL-the-driver kill-and-resume legs (per-rank
  # delivered_seq digests bit-identical to an uninterrupted same-seed
  # control, journaled-complete epochs re-execute zero stage tasks,
  # capacity residency folds to zero), the degraded resume with the
  # store segments dropped, the zero-overhead-off fresh-interpreter
  # proof, and tools/replay.py's divergence gate — all with strict
  # audit on and the fixed-seed xN-capped fault schedule riding into
  # every child driver (recovery is exactly-once, so injected crashes
  # must be invisible to digest equality across the preemption). The
  # checkpoint suite rides along: torn-publish debris pruning and the
  # cursor's plan-family stream identity share this failure model.
  # Chaos tests stay function-scoped-runtime per the established
  # recipe; the kill legs own no pytest-process runtime at all.
  RSDL_AUDIT=1 RSDL_AUDIT_STRICT=1 RSDL_AUDIT_DIR="$(mktemp -d)" \
    RSDL_METRICS=1 \
    RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=1313 \
    python -m pytest tests/test_resume.py tests/test_checkpoint.py \
      -m "not slow" -q -x
  # Service lane (ISSUE 15): the multi-tenant shuffle service — two
  # concurrent jobs under a low-prob xN-capped fault schedule with
  # STRICT per-job audit (the two-job concurrency test proves per-job
  # ok=true AND delivered_seq digests bit-identical to solo same-seed
  # runs; the chaos leg proves one job's crashed reducer never touches
  # the neighbor's epochs), plus the name-collision regression,
  # fair-share/admission units, cross-job cache-hot, and the
  # zero-overhead-off fresh-interpreter proof. The suite arms
  # RSDL_SERVICE itself per test (function-scoped runtimes); the
  # lane-level schedule rides into every spawned worker.
  RSDL_FAULTS="task.map/task:crash-entry:0.03x1,task.reduce/task:crash-exit:0.03x1" \
    RSDL_FAULTS_SEED=1515 \
    python -m pytest tests/test_service.py -m "not slow" -q -x
  # Temporal + decision obs smoke (ISSUES 7/9), exit-code gated:
  # against a MID-FLIGHT shuffle with the obs endpoint up, /timeseries
  # must serve a non-empty rate series, `rsdl_top --once --json` must
  # render a frame, /capacity must show live per-epoch residency,
  # /critical must name a critical-path stage, a deliberately-tripped
  # SLO rule must FIRE and RESOLVE on /alerts (both transitions event-
  # logged), and /events must carry the full epoch lifecycle afterwards
  # (tools/obs_smoke.py asserts all of it; its exit code is the gate).
  # The fleet plane rides along (ISSUE 16): the smoke arms the service
  # plane, so /jobs must list the running tenant mid-flight and the
  # job=-filtered /events must return the tenant's stamped events (and
  # nothing for a bogus id).
  RSDL_METRICS=1 python tools/obs_smoke.py
  # Relay lane (ISSUE 19): cross-host telemetry federation. The unit
  # suite proves the protocol (receiver restamping for clock-skew
  # safety, CRC/gap/overlap idempotency, shared-filesystem skip,
  # bounded drop-ahead, sink-death degradation) and the federation
  # smoke is the live gate: a second host process joins over TCP with
  # NO shared spool tree and the driver's /metrics must show >= 2
  # distinct host= labels MID-FLIGHT with a fresh relay source on
  # /healthz (exit-code gated; the two-host no-shared-spool chaos
  # acceptance test runs in the slow tier).
  RSDL_METRICS=1 python -m pytest tests/test_relay.py -m "not slow" -q -x
  RSDL_METRICS=1 python tools/obs_smoke.py --federation > /dev/null
  # Profile lane (ISSUE 17): the continuous sampling profiler armed
  # across the core data-path + profiler suites — every process (driver,
  # task workers, actor hosts) runs the sampler daemon and spools, and
  # none of it may perturb the data plane (bit-identical streams, same
  # green tests). The profiler suite itself proves folding, tagging,
  # merge, diff math, and the zero-overhead-off fresh-interpreter
  # contract.
  RSDL_PROFILE=1 RSDL_METRICS=1 \
    python -m pytest tests/test_profiler.py tests/test_shuffle.py \
      tests/test_batch_queue.py tests/test_dataset.py \
      tests/test_jax_dataset.py -m "not slow" -q -x
  # Run-ledger regression gate (ISSUE 16), gated BOTH ways against the
  # committed fixture pair: the clean base..head must exit 0, the
  # fixture with an injected throughput drop + stall rise must exit
  # non-zero — and (ISSUE 17) its verdict must NAME the frame the
  # regression's time moved into, from the records' profile digests.
  python tools/run_ledger.py \
    --ledger tests/fixtures/run_ledger/clean.ndjson --regress 0..1
  if regress_out=$(python tools/run_ledger.py \
    --ledger tests/fixtures/run_ledger/regressed.ndjson \
    --regress 0..1); then
    echo "run_ledger --regress failed to flag the regressed fixture" >&2
    exit 1
  fi
  if ! grep -q "runtime.store:_spill_segment" <<<"$regress_out"; then
    echo "run_ledger --regress did not name the regressed frame" >&2
    exit 1
  fi
fi
if [ "$tier" != "fast" ]; then
  python -m pytest tests/ -m slow -v --durations=10 || rc=$?
fi
exit $rc
