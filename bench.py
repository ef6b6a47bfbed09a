"""Benchmark: per-epoch shuffle -> HBM-staged batches -> real train step.

Measures the north-star metric (BASELINE.json): shuffle+delivery throughput
per chip and trainer stall fraction on the synthetic DATA_SPEC workload,
with the flagship DLRM train step consuming mesh-sharded HBM batches on the
real chip. Prints ONE JSON line:

    {"metric": ..., "value": <GB/s/chip>, "unit": ..., "vs_baseline": ...}

``vs_baseline`` is the achieved fraction of the driver target (0.8 x the
measured peak host->HBM ``device_put`` bandwidth on this chip — BASELINE.md
">=80% of host->HBM staging bandwidth"); >=1.0 means target met. Extra keys
carry stall%, peak bandwidth, phase timings, and peak /dev/shm + HBM
occupancy.

The bench measures the chip and nothing else: JAX is initialized once, in
this process, and a platform other than ``tpu`` is a non-zero exit before
any work. A kernel that does not compile, a failed fused epoch, a failed
resident loader or any other failed phase raises — the error goes to
stderr, the exit code is non-zero and no result line is printed.
``chip_smoke.py`` is the quick proof that the path runs at all.

Workload (reference sweep: 4e8 rows ~64 GB, ``benchmark_batch.sh:9``): a
>=10 GB DATA_SPEC dataset by default (``RSDL_BENCH_GB``), auto-shrunk only
if /dev/shm headroom demands it. Generated Parquet is cached under
``.bench_cache/`` keyed by the workload knobs.

Quick mode (``RSDL_BENCH_QUICK=1``): ~2 GB dataset, 2 epochs. Same one-line
JSON contract with ``"quick": true``.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import tempfile
import threading
import time

# -- workload knobs (fixed so values are comparable across rounds) -----------

# Quick mode: small-but-real workload for short accelerator windows. The
# 2 GB / 2-epoch shape still exercises the full pipeline (resident staging
# amortized over >1 epoch, fused scan, real train steps) in a few minutes.
QUICK = os.environ.get("RSDL_BENCH_QUICK", "") == "1"

BYTES_PER_ROW = 168  # 21 int64/float64 columns (DATA_SPEC)
TARGET_GB = float(os.environ.get("RSDL_BENCH_GB", "2" if QUICK else "10"))
NUM_FILES = int(os.environ.get("RSDL_BENCH_FILES", "16"))
ROW_GROUPS_PER_FILE = 2
BATCH_SIZE = 250_000  # reference benchmark_batch.sh:11
# 10 epochs — the reference sweep's own count (benchmark_batch.sh:12-13).
# Epoch 1 pays cold decode (+ cache publish / resident staging); the rest
# are the steady state the per-epoch metric is meant to capture, and the
# resident loader's one-time staging amortizes exactly as it would in a
# real multi-epoch job.
NUM_EPOCHS = int(os.environ.get("RSDL_BENCH_EPOCHS", "2" if QUICK else "10"))
NUM_REDUCERS = int(os.environ.get("RSDL_BENCH_REDUCERS", "8"))
EMBED_DIM = 32
SEED = 0

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache")


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _mean(xs) -> float:
    return float(sum(xs) / len(xs)) if xs else 0.0


METRIC = "Shuffle GB/s/chip + trainer stall % on synthetic Parquet"


def _attach_obs_summaries(result: dict) -> None:
    """End-of-run straggler/skew summary + structured-event counts
    (ISSUE 7), embedded on success AND watchdog/error paths (the PR-4
    telemetry_final convention). Publishes the rsdl_straggler_* gauges
    into the registry FIRST, so the subsequent aggregate() (the
    telemetry_final embed) carries them; the compact dicts ride
    alongside for humans. Pure file reads — safe on error paths."""
    from ray_shuffling_data_loader_tpu.telemetry import metrics as _m

    if not _m.enabled():
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import stragglers

        analysis = stragglers.analyze()
        stragglers.publish_metrics(analysis)
        if analysis.get("tasks_total"):
            result["stragglers"] = {
                "tasks_total": analysis["tasks_total"],
                "wedged": len(analysis.get("wedged", [])),
                "flagged": analysis.get("flagged_total", 0),
                "stages": {
                    stage: {
                        "count": st.get("count"),
                        "median_s": st.get("median_s"),
                        "p99_s": st.get("p99_s"),
                        "skew_ratio": st.get("skew_ratio"),
                        "slowest_host": st.get("slowest_host"),
                    }
                    for stage, st in analysis.get("stages", {}).items()
                },
            }
    except Exception:
        pass
    try:
        from ray_shuffling_data_loader_tpu.telemetry import events

        by_kind = events.counts()
        if by_kind:
            result["events"] = by_kind
            for kind, count in by_kind.items():
                # Gauges (recomputed totals), so telemetry_final and a
                # final scrape show rsdl_events_total{kind=...} too.
                _m.registry.gauge("events.total", kind=kind).set(count)
    except Exception:
        pass
    # The decision plane (ISSUE 9): capacity watermarks + fired-alert
    # counts, published as gauges FIRST (same ordering contract as the
    # straggler block) so the aggregate() embed carries rsdl_capacity_*
    # and rsdl_alert_* alongside the compact human dicts.
    try:
        from ray_shuffling_data_loader_tpu.telemetry import capacity

        cap = capacity.view()
        capacity.publish_metrics(cap)
        if cap.get("ops"):
            result["capacity"] = {
                "totals": cap.get("totals"),
                "shm_used_frac": cap.get("shm_used_frac"),
                "hwm_by_epoch": {
                    epoch: {
                        tier: cell.get("hwm_bytes", 0)
                        for tier, cell in tiers.items()
                    }
                    for epoch, tiers in cap.get("epochs", {}).items()
                },
            }
    except Exception:
        pass
    try:
        from ray_shuffling_data_loader_tpu.telemetry import slo

        fired = slo.fired_counts()
        if fired:
            result["alerts_fired"] = fired
    except Exception:
        pass
    # The decode plane (ISSUE 11/12): row-group + pushdown counters
    # from the cluster-wide aggregate (worker decode tasks spool them
    # at task-done), compacted for humans next to telemetry_final. The
    # counters carry {schedule, plan} labels since ISSUE 12, so the
    # summary keeps the totals AND the per-(schedule, plan) breakdown —
    # decode amplification is attributable per run, and an audit-key
    # side sweep never masquerades as data-path decode work.
    try:
        from ray_shuffling_data_loader_tpu.telemetry import (
            export as _export,
        )

        flat = _export.aggregate()

        def _labeled_sum(name):
            total, by_label = _export.labeled_sum(flat, name)
            return int(total), {k: int(v) for k, v in by_label.items()}

        rowgroups, rowgroups_by = _labeled_sum("shuffle.decode_rowgroups")
        rows_pruned, _ = _labeled_sum("shuffle.decode_rows_pruned")
        bytes_pruned, _ = _labeled_sum("shuffle.decode_bytes_pruned")
        decode = {
            "rowgroups": rowgroups,
            # Data-path decode only: the selective plan's audit-key
            # side read is real decode work but not stream decode —
            # the acceptance comparison against the dataset's physical
            # row-group count keys on this figure.
            "rowgroups_data": rowgroups
            - sum(
                v
                for k, v in rowgroups_by.items()
                if "schedule=audit-key" in k
            ),
            "rows_pruned": rows_pruned,
            "bytes_pruned": bytes_pruned,
        }
        if rowgroups_by:
            decode["rowgroups_by"] = rowgroups_by
        if any(
            decode[k] for k in ("rowgroups", "rows_pruned", "bytes_pruned")
        ):
            try:
                import importlib

                _sh = importlib.import_module(
                    "ray_shuffling_data_loader_tpu.shuffle"
                )
                from ray_shuffling_data_loader_tpu.utils import (
                    shuffle_plan_label,
                )

                engaged, reason = _sh.selective_reads_decision()
                decode["plan"] = shuffle_plan_label()
                # The decline is documented, not silent (ISSUE 12):
                # under RSDL_SELECTIVE_READS=auto with a rowwise plan
                # the reason string says the schedule fell back to the
                # materialized path and why.
                decode["selective"] = {
                    "engaged": engaged,
                    "reason": reason,
                }
            except Exception:
                pass
            result["decode"] = decode
    except Exception:
        pass
    # The elastic control plane (ISSUE 10): scale/evict/drain lifetime
    # totals. sys.modules lookup, never an import — the plane only
    # exists when RSDL_ELASTIC brought it up; its elastic.* counters/
    # gauges already ride the registry into telemetry_final, the
    # compact fields land here for humans (success AND error paths).
    try:
        import sys as _sys

        elastic = _sys.modules.get(
            "ray_shuffling_data_loader_tpu.runtime.elastic"
        )
        if elastic is not None:
            summary = elastic.summary()
            if summary:
                result["scale_events"] = summary.get("scale_events", 0)
                result["evicted_gb"] = summary.get("evicted_gb", 0.0)
                result["drains"] = summary.get("drains", 0)
    except Exception:
        pass


def _ledger_append(result: dict) -> None:
    """Append this bench invocation to the durable run ledger (ISSUE
    16, telemetry/runledger.py) and embed the record id in the bench
    JSON (``ledger_record``) so an artifact line and its ledger row
    cross-reference each other. Called on success AND the watchdog/
    error paths — a failed capture is exactly what the next run's
    ``--regress`` comparison needs to see. Check-then-import keeps the
    plane zero-overhead with RSDL_RUN_LEDGER unset; never raises."""
    if not os.environ.get("RSDL_RUN_LEDGER"):
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import runledger

        if not runledger.enabled():
            return
        extra = {
            "bench": {
                k: result.get(k)
                for k in ("metric", "value", "unit", "plane",
                          "vs_baseline", "backend")
                if result.get(k) is not None
            }
        }
        value = result.get("value")
        unit = str(result.get("unit") or "")
        if isinstance(value, (int, float)) and value and "GB/s" in unit:
            extra["throughput"] = {"bytes_per_s": float(value) * 1e9}
        rec_id = runledger.record_run(
            "failed" if result.get("error") else "done",
            kind="bench",
            error=result.get("error"),
            extra=extra,
        )
        if rec_id:
            result["ledger_record"] = rec_id
    except Exception:
        pass


def _error_result(platform, msg: str) -> dict:
    """The failure shape of the one-JSON-line contract, for the two
    places that still print one: the stall watchdog (a hang has no
    exception to raise) and a bad command line. When telemetry/audit are
    on, the artifact carries their last-known state: the final LOCAL
    metrics snapshot (no cross-process sources — a wedged actor must not
    hang the error path) and the audit verdicts folded from whatever
    records reached the spool, so a wedged run still reports its counters
    and digests."""
    result = {
        "metric": METRIC,
        "value": 0.0,
        "unit": "GB/s/chip",
        "vs_baseline": 0.0,
        "backend": platform,
        "error": msg[:300],
    }
    if QUICK:
        result["quick"] = True
    try:
        from ray_shuffling_data_loader_tpu.telemetry import export as _e
        from ray_shuffling_data_loader_tpu.telemetry import metrics as _m

        if _m.enabled():
            # Straggler/event summaries FIRST so their gauges land in
            # the aggregate below (success path mirrors this ordering).
            _attach_obs_summaries(result)
            # The CLUSTER view, not the driver-local one: worker/actor
            # registries already spooled at task-done/quiescence, and
            # aggregate() is a pure file read plus the local registry —
            # no RPCs, so a wedged actor cannot hang this error path.
            try:
                result["telemetry_final"] = _e.aggregate()
            except Exception:
                result["telemetry_final"] = _m.registry.snapshot()
    except Exception:
        pass
    try:
        from ray_shuffling_data_loader_tpu.telemetry import audit as _a

        if _a.enabled():
            result["audit"] = _a.summary()
    except Exception:
        pass
    _attach_profile(result)
    return result


def _attach_profile(result: dict) -> None:
    """Embed the cluster-merged sampling-profile digest (ISSUE 17) in
    the bench JSON — success AND error paths, like telemetry_final: the
    profile of a wedged run is the artifact that names where the time
    went. The env check precedes the import so RSDL_PROFILE unset
    stays exactly zero-cost; never raises (one-JSON-line contract)."""
    if not os.environ.get("RSDL_PROFILE"):
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import profiler

        digest = profiler.digest()
        if digest:
            result["profile"] = digest
    except Exception:
        pass


# -- backend ------------------------------------------------------------------


def require_tpu():
    """Initialize JAX in this process — the only one that may touch the
    chip — and return ``(platform, device_count)``. Anything but a TPU is
    an error: a number taken elsewhere is not this bench's metric."""
    import jax

    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(
            f"bench.py measures the chip: JAX found platform {platform!r} "
            f"({devices[0].device_kind} x{len(devices)}), no TPU"
        )
    _log(f"backend: {platform} {devices[0].device_kind} x{len(devices)}")
    return platform, len(devices)


# -- workload ----------------------------------------------------------------


def _shm_free_bytes() -> int:
    try:
        st = os.statvfs("/dev/shm")
        return st.f_bavail * st.f_frsize
    except OSError:
        return 1 << 62


def _sized_workload():
    """Pick (num_rows, scaled_down): TARGET_GB unless /dev/shm headroom
    forces smaller. Peak store residency is ~2x dataset (one epoch's map
    partitions + reducer outputs) x up to 2 epochs in flight; require 5x
    so the bench never ENOSPCs mid-epoch."""
    target_bytes = int(TARGET_GB * 1e9)
    headroom = _shm_free_bytes()
    budget = int(headroom / 5)
    scaled = min(target_bytes, budget)
    if scaled < target_bytes:
        _log(
            f"shrinking workload {target_bytes/1e9:.1f} -> {scaled/1e9:.1f} GB"
            f" (/dev/shm free {headroom/1e9:.1f} GB / 5)"
        )
    num_rows = max(BATCH_SIZE, scaled // BYTES_PER_ROW)
    return int(num_rows), scaled < target_bytes


def _get_data(num_rows: int):
    from ray_shuffling_data_loader_tpu.data_generation import (
        cached_generate_data,
    )

    data_dir = os.path.join(
        CACHE_DIR, f"r{num_rows}_f{NUM_FILES}_g{ROW_GROUPS_PER_FILE}_s{SEED}"
    )
    os.makedirs(data_dir, exist_ok=True)
    t0 = time.perf_counter()
    filenames, num_bytes = cached_generate_data(
        num_rows, NUM_FILES, ROW_GROUPS_PER_FILE, data_dir, seed=SEED
    )
    if time.perf_counter() - t0 > 1.0:
        _log(
            f"generated {num_bytes/1e9:.2f} GB in "
            f"{time.perf_counter()-t0:.1f}s"
        )
    return list(filenames), num_bytes


def _measure_peak_h2d_gbps() -> float:
    """Peak blocking host->HBM bandwidth via a large device_put."""
    import jax
    import numpy as np

    arr = np.ones((256, 1024, 1024), dtype=np.uint8)  # 256 MB
    jax.block_until_ready(jax.device_put(arr))  # warm up
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(arr))
        best = max(best, arr.nbytes / (time.perf_counter() - t0))
    return best / 1e9


# Stop callables for the sampler threads run_bench starts. run_bench pops
# them on its straight-line teardown; main()'s error path pops whatever is
# left BEFORE exporting the trace/metrics artifacts, so an orphaned 1 Hz
# sampler cannot race the export of exactly the failed run whose artifacts
# matter most.
_LIVE_SAMPLERS: list = []


def _stop_live_samplers() -> None:
    # pop-until-empty, not check-then-pop: main's error path and a
    # watchdog thread can drain this list concurrently (both react to the
    # same wedge), and the loser of a check/pop race must exit the loop,
    # not die on IndexError before its export/JSON contract work.
    while True:
        try:
            stop = _LIVE_SAMPLERS.pop()
        except IndexError:
            return
        try:
            stop()
        except Exception:
            pass


# Artifact paths for the watchdogs' hard-exit path, set by main() when
# --trace-out is given: os._exit skips atexit and main()'s export block,
# and the trace of a wedged run is the one artifact that shows WHERE it
# wedged. [trace_out, metrics_out].
_TELEMETRY_EXIT_PATHS: list = [None, None]


def _export_telemetry_for_exit() -> None:
    """Best-effort trace/metrics export before a watchdog os._exit. Never
    touches cross-process metrics sources (the wedged actor could hang
    this very exit) — the trace spool and sampled timeline are local."""
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

    # Uphold stop-before-export on the watchdog paths too (sampler stops
    # join with a timeout, so this cannot wedge the exit). The two
    # artifacts are guarded independently, like main()'s export block —
    # a full/read-only trace volume must not also cost the metrics dump.
    _stop_live_samplers()
    try:
        if telemetry.enabled():
            telemetry.flush()
            if _TELEMETRY_EXIT_PATHS[0]:
                telemetry.trace_export(_TELEMETRY_EXIT_PATHS[0])
    except Exception:
        pass
    try:
        if _metrics.enabled() and _TELEMETRY_EXIT_PATHS[1]:
            _metrics.dump_json(
                _TELEMETRY_EXIT_PATHS[1], include_sources=False
            )
    except Exception:
        pass


class _ShmSampler(threading.Thread):
    """Samples this session's /dev/shm occupancy; reports the peak
    (the reference samples its object store every 5 s via raylet gRPC,
    reference ``stats.py:686-699``)."""

    def __init__(self, store, period_s: float = 0.5):
        super().__init__(name="shm-sampler", daemon=True)
        self._store = store
        self._period = period_s
        # NB: not "_stop" — threading.Thread uses that name internally.
        self._halt = threading.Event()
        self.peak_bytes = 0
        self.peak_spill_bytes = 0

    def run(self):
        while not self._halt.wait(self._period):
            try:
                s = self._store.store_stats()
                # shm residency only — spilled bytes live on disk and are
                # tracked separately (capacity-budget evidence).
                self.peak_bytes = max(
                    self.peak_bytes, s.total_bytes - s.spill_bytes
                )
                self.peak_spill_bytes = max(
                    self.peak_spill_bytes, s.spill_bytes
                )
            except OSError:
                pass

    def stop(self):
        self._halt.set()
        self.join(timeout=2)


# -- main --------------------------------------------------------------------


def run_bench(platform: str, num_chips: int):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.data_generation import (
        DATA_SPEC,
        LABEL_COLUMN,
    )
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import TabularDLRM
    from ray_shuffling_data_loader_tpu.parallel import (
        batch_sharding,
        init_state,
        make_mesh,
        make_step_body,
        make_train_step,
    )

    num_chips = max(1, num_chips)
    # Pool sizing: one worker per core, floor 2 so shuffle stages overlap
    # the TPU-side train steps even on a 1-core host. Wider pools on small
    # hosts only add spawn latency and context-switch thrash (measured:
    # same steady-state GB/s at 1/2/4 workers on 1 core, but +5s cold
    # start at 4).
    ctx = runtime.init(num_workers=max(2, os.cpu_count() or 1))
    # The train step is real unless RSDL_BENCH_MOCK_STEP_S asks for the
    # reference harness's loader-isolation mode (a fixed sleep per batch,
    # --mock-train-step-time, ray_torch_shuffle.py:214).
    mock_step_s = None
    env_mock = os.environ.get("RSDL_BENCH_MOCK_STEP_S")
    # Calibrated-step config (VERDICT r5 item 5): measure ONE real
    # compiled step on this backend, then pin the mock step to that
    # duration (x RSDL_BENCH_CALIBRATED_SCALE) — so the stall claim
    # rests on a realistic consumer cadence over many steps instead of
    # 4 real steps at 0.1 GB. Calibration runs after the model is built
    # (below); sizing treats it as loader-isolation (full workload).
    calibrate = os.environ.get("RSDL_BENCH_CALIBRATED") == "1"
    calibrated_from_s = None
    if calibrate and env_mock is not None:
        # An explicit RSDL_BENCH_MOCK_STEP_S (value OR the empty-string
        # real-step opt-out) outranks a lingering calibrate flag — the
        # per-run knob must never be silently overridden.
        _log(
            "RSDL_BENCH_MOCK_STEP_S is set explicitly; ignoring "
            "RSDL_BENCH_CALIBRATED"
        )
        calibrate = False
    if env_mock:
        mock_step_s = float(env_mock)
    num_rows, scaled_down = _sized_workload()
    filenames, dataset_bytes = _get_data(num_rows)

    peak_gbps = _measure_peak_h2d_gbps()
    _log(f"peak H2D: {peak_gbps:.2f} GB/s on {platform}")

    feature_columns = [c for c in DATA_SPEC if c != LABEL_COLUMN]
    mesh = make_mesh(model_parallelism=1)
    optimizer = optax.adam(1e-3)
    example = {c: jnp.zeros((BATCH_SIZE,), jnp.int32) for c in feature_columns}
    bsh = batch_sharding(mesh, 1)
    example_dev = {k: jax.device_put(v, bsh) for k, v in example.items()}
    labels0 = jax.device_put(jnp.zeros((BATCH_SIZE,), jnp.float32), bsh)

    def build_and_warm(use_pallas):
        """Init state, jit the step, and execute one warm-up step — with
        the warm-up batch placed exactly as real batches arrive
        (committed, mesh-sharded): input sharding is part of the jit
        cache key, so an uncommitted warm-up would leave the first timed
        step to recompile. Returns the post-warm-up (state, step_fn)."""
        model = TabularDLRM(
            vocab_sizes={c: DATA_SPEC[c][1] for c in feature_columns},
            embed_dim=EMBED_DIM,
            use_pallas_interaction=use_pallas,
        )
        state, shardings = init_state(model, optimizer, mesh, example)
        step_fn = make_train_step(model, optimizer, mesh, shardings)
        state, _ = step_fn(state, example_dev, labels0)
        jax.block_until_ready(state.params)
        return state, step_fn, make_step_body(model, optimizer)

    if calibrate:
        # Measure the real compiled step, pin the mock to it, drop the
        # model. min-of-3 (not mean): post-warm-up step time is stable
        # and the minimum rejects scheduler noise on a loaded host.
        scale = float(os.environ.get("RSDL_BENCH_CALIBRATED_SCALE", "1"))
        cal_state, cal_step, _ = build_and_warm(False)
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            cal_state, _cal_metrics = cal_step(
                cal_state, example_dev, labels0
            )
            jax.block_until_ready(cal_state.step)
            samples.append(time.perf_counter() - t0)
        calibrated_from_s = min(samples)
        mock_step_s = max(1e-4, calibrated_from_s * scale)
        del cal_state, cal_step
        _log(
            f"calibrated step: measured {calibrated_from_s:.3f}s real "
            f"x scale {scale} -> mock {mock_step_s:.3f}s"
        )

    # The interaction runs on its Pallas kernel; RSDL_BENCH_PALLAS=off asks
    # for the XLA reference instead. A step that does not compile raises.
    # In loader-isolation mode the train step is a fixed sleep, so model
    # build + compile + warm-up are skipped entirely.
    state = step_fn = step_body = None
    if mock_step_s is not None:
        pallas_mode = "mocked-step"
    elif os.environ.get("RSDL_BENCH_PALLAS", "on") == "off":
        pallas_mode = "off"
        state, step_fn, step_body = build_and_warm(False)
    else:
        pallas_mode = "on"
        state, step_fn, step_body = build_and_warm(True)

    # Loader choice: the device-resident shuffle (epoch permutation +
    # gather in HBM, one staging pass total — resident.py) when the packed
    # dataset fits the device budget, else the general host map/reduce
    # pipeline. RSDL_BENCH_RESIDENT=on|off|auto overrides.
    from ray_shuffling_data_loader_tpu import resident as resident_mod

    resident_env = os.environ.get("RSDL_BENCH_RESIDENT", "auto")
    if resident_env == "on":
        use_resident = True
    elif resident_env == "off":
        use_resident = False
    else:
        # The bench is SPMD on pods (every process runs this same line),
        # so pod-consistent auto-selection is safe: resident engages on
        # the target topology when every host's budget agrees.
        use_resident = resident_mod.fits_device(
            filenames,
            len(feature_columns),
            mesh=mesh,
            num_rows=num_rows,
            pod_consistent=True,
        )
    _log(f"loader: {'device-resident' if use_resident else 'map/reduce'}")

    from ray_shuffling_data_loader_tpu.stats import TrialStatsCollector

    # Only the map/reduce loader reports to the collector; the resident
    # loader runs no stage tasks per epoch and takes no collector (its
    # hand-over is the ``resident:handover`` span and the trace's
    # programs), so its trial rows hold the trainer-side columns alone.
    collector = runtime.spawn_actor(
        TrialStatsCollector,
        NUM_EPOCHS,
        len(filenames) if not use_resident else 1,
        NUM_REDUCERS if not use_resident else 1,
        num_rows,
        BATCH_SIZE,
        1,
        name="bench-stats",
    )

    def make_dataset():
        if use_resident:
            return resident_mod.DeviceResidentShufflingDataset(
                filenames,
                num_epochs=NUM_EPOCHS,
                batch_size=BATCH_SIZE,
                feature_columns=feature_columns,
                label_column=LABEL_COLUMN,
                mesh=mesh,
                seed=SEED,
                num_rows=num_rows,
                # The one-time staging pass can exceed the per-batch
                # stall timeout on a slow host; every staged piece is
                # liveness progress for the watchdog.
                progress_cb=lambda: last_progress.__setitem__(
                    0, time.monotonic()
                ),
            )
        return JaxShufflingDataset(
            filenames,
            num_epochs=NUM_EPOCHS,
            num_trainers=1,
            batch_size=BATCH_SIZE,
            rank=0,
            feature_columns=feature_columns,
            label_column=LABEL_COLUMN,
            num_reducers=NUM_REDUCERS,
            mesh=mesh,
            seed=SEED,
            queue_name=f"bench-queue-{int(time.time() * 1000) % 10 ** 9}",
            stats_collector=collector,
        )

    sampler = _ShmSampler(ctx.store)
    sampler.start()
    _LIVE_SAMPLERS.append(sampler.stop)

    # Live-metrics sampler (telemetry): only when the metrics half is on
    # (bench --trace-out / RSDL_METRICS=1). Feeds the batch-queue depth
    # source + store gauges into the sampled timeline that
    # telemetry.metrics.dump_json() writes next to the trace artifact.
    from ray_shuffling_data_loader_tpu.stats import ObjectStoreStatsCollector
    from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

    metrics_sampler = None
    if _metrics.enabled():
        metrics_sampler = ObjectStoreStatsCollector(
            collector, sample_period_s=1.0
        )
        metrics_sampler.__enter__()
        _LIVE_SAMPLERS.append(
            lambda: metrics_sampler.__exit__(None, None, None)
        )

    # Optional trace (SURVEY §5 tracing): RSDL_BENCH_XPROF_DIR=/tmp/trace
    # wraps the measured region in a jax.profiler trace for xprof.
    # (RSDL_PROFILE_DIR now names the sampling-profiler spool override —
    # ISSUE 17 — a different artifact entirely.)
    profile_dir = os.environ.get("RSDL_BENCH_XPROF_DIR")
    if profile_dir:
        jax.profiler.start_trace(profile_dir)

    # Mid-run stall watchdog: a run that stops making per-batch progress
    # prints a machine-readable error JSON and exits non-zero instead of
    # hanging. The timeout is sized to survive a full cold epoch gap on a
    # slow host.
    stall_timeout_s = float(
        os.environ.get("RSDL_BENCH_STALL_TIMEOUT_S", "900")
    )
    # <= 0 disables the watchdog (the conventional env-knob off switch).
    watchdog_enabled = math.isfinite(stall_timeout_s) and stall_timeout_s > 0
    last_progress = [time.monotonic()]

    check_s = min(30.0, max(1.0, stall_timeout_s / 4))

    def _stall_watchdog():
        while True:
            time.sleep(check_s)
            idle = time.monotonic() - last_progress[0]
            if idle > stall_timeout_s:
                result = _error_result(
                    platform,
                    f"no batch progress for {idle:.0f}s "
                    "(accelerator wedged mid-run?); watchdog exit",
                )
                _ledger_append(result)
                print(json.dumps(result), flush=True)
                if profile_dir:
                    # The trace of the wedged run is the one artifact
                    # that shows WHERE it wedged; flush it if possible.
                    try:
                        jax.profiler.stop_trace()
                    except Exception:
                        pass
                _export_telemetry_for_exit()
                # Nonzero rc: rc-keyed tooling must record the failed
                # capture truthfully.
                os._exit(1)

    if watchdog_enabled:
        threading.Thread(
            target=_stall_watchdog, name="stall-watchdog", daemon=True
        ).start()

    def timed_run():
        nonlocal state, metrics, step_time, num_steps
        t0_run = time.perf_counter()
        # Constructed INSIDE the timed window: the resident loader's
        # one-time decode+stage pass is part of the pipeline cost the
        # metric reports (the map/reduce loader's constructor is cheap —
        # its shuffle work already overlaps the timed loop).
        ds = make_dataset()
        step_time = 0.0
        num_steps = 0
        fused = (
            use_resident
            and mock_step_s is None
            and os.environ.get("RSDL_BENCH_FUSED", "on") != "off"
        )
        if fused:
            # Epoch fusion: the dataset is HBM-resident, so the entire
            # epoch (batch slice + unpack + train step) runs as ONE
            # jitted lax.scan — one dispatch per epoch instead of one+
            # host round-trips per batch (resident.make_fused_epoch).
            run_epoch = resident_mod.make_fused_epoch(
                ds, step_body, donate_state=False
            )
            per_epoch = ds._rank_rows // BATCH_SIZE
            epoch_bytes = (
                (len(feature_columns) + 1) * 4 * per_epoch * BATCH_SIZE
            )
            for epoch in range(NUM_EPOCHS):
                t0 = time.perf_counter()
                if epoch == 0:
                    # The first fused call compiles the whole scanned
                    # step; grant the stall watchdog one compile's
                    # worth of extra budget (a future "last progress"
                    # = more headroom) without disarming wedge
                    # detection.
                    last_progress[0] = time.monotonic() + 900
                collector.call_oneway("epoch_start", epoch)
                collector.call_oneway("map_start", epoch)
                collector.call_oneway("map_done", epoch, 0.0, 0.0)
                collector.call_oneway("reduce_start", epoch)
                state, losses = run_epoch(state, epoch)
                jax.block_until_ready(losses)
                dur = time.perf_counter() - t0
                collector.call_oneway("reduce_done", epoch, dur)
                collector.call_oneway("consume", 0, epoch, epoch_bytes)
                metrics = {"loss": losses[-1]}
                step_time += dur
                num_steps += per_epoch
                last_progress[0] = time.monotonic()
            return time.perf_counter() - t0_run, ds
        for epoch in range(NUM_EPOCHS):
            ds.set_epoch(epoch)
            for features, label in ds:
                t0 = time.perf_counter()
                if mock_step_s is not None:
                    time.sleep(mock_step_s)
                else:
                    state, metrics = step_fn(state, features, label)
                    jax.block_until_ready(state.step)
                step_time += time.perf_counter() - t0
                num_steps += 1
                last_progress[0] = time.monotonic()
        return time.perf_counter() - t0_run, ds

    step_time = 0.0
    num_steps = 0
    metrics = {"loss": float("nan")}
    total_s, ds = timed_run()
    # Finalization below (device sync, profiler stop, stats snapshot) can
    # wedge exactly like the loop can, so the watchdog stays armed; it
    # cannot double-print because it os._exit()s right after its line.
    last_progress[0] = time.monotonic()
    if state is not None:
        jax.block_until_ready(state.params)
    if profile_dir:
        jax.profiler.stop_trace()
    _stop_live_samplers()

    stats = ds.stats.as_dict()
    staged_gb = stats["bytes_staged"] / 1e9
    staged_direct_gb = stats.get("bytes_staged_direct", 0) / 1e9
    # Per-stage shuffle timings (diagnosability of the headline number):
    # wall-clock stage windows and mean task durations per epoch.
    phase = {}
    try:
        # The map/reduce loader's stage events; the resident loader runs
        # no stage tasks per epoch (its hand-over is the
        # ``resident:handover`` span and the trace's programs).
        epochs = collector.call("snapshot").epochs
        if epochs:
            phase = {
                "map_stage_s": round(
                    sum(e.map_stage_duration or 0.0 for e in epochs), 2
                ),
                "reduce_stage_s": round(
                    sum(e.reduce_stage_duration or 0.0 for e in epochs), 2
                ),
                "map_task_avg_s": round(_mean(
                    [d for e in epochs for d in e.map_durations]
                ), 3),
                "reduce_task_avg_s": round(_mean(
                    [d for e in epochs for d in e.reduce_durations]
                ), 3),
                "throttle_s": round(
                    sum(e.throttle_duration or 0.0 for e in epochs), 2
                ),
            }
    except Exception as exc:  # diagnostics must never sink the number
        _log(f"stage-stats snapshot failed: {exc!r:.200}")
    # Pipeline throughput: logical dataset bytes moved per epoch, per chip.
    pipeline_gbps = dataset_bytes * NUM_EPOCHS / 1e9 / total_s / num_chips
    stall_pct = 100.0 * stats["stall_s"] / total_s
    target = 0.8 * peak_gbps

    result = {
        "metric": METRIC,
        "value": round(pipeline_gbps, 4),
        "unit": "GB/s/chip",
        "vs_baseline": round(pipeline_gbps / target, 4) if target else 0.0,
        "stall_pct": round(stall_pct, 2),
        # Attribution (VERDICT r4 item 2): upstream = consumer waited while
        # the loader had no host batch (epoch window closed / shuffle still
        # producing); staging = host batch existed, H2D pipeline was behind.
        # Cross-check against throttle_s (driver-side window-gating time).
        "stall_upstream_pct": round(
            100.0 * stats.get("stall_upstream_s", 0.0) / total_s, 2
        ),
        "stall_staging_pct": round(
            100.0 * stats.get("stall_staging_s", 0.0) / total_s, 2
        ),
        "peak_h2d_gbps": round(peak_gbps, 2),
        "dataset_gb": round(dataset_bytes / 1e9, 3),
        "scaled_down": scaled_down,
        # staged_gb counts HOST-COPIED staging bytes (the rebatch+pack
        # amplification ISSUE 8 kills); staged_direct_gb counts bytes
        # device_put shipped straight off mmapped packed segments with
        # no host copy. Their sum is total H2D traffic. device_direct
        # records whether the path actually ENGAGED (at least one batch
        # shipped direct), not merely whether the env requested it — a
        # non-engaging run must not read as "optimization was on".
        "staged_gb": round(staged_gb, 3),
        "staged_direct_gb": round(staged_direct_gb, 3),
        "batches_staged_direct": int(
            stats.get("batches_staged_direct", 0)
        ),
        "device_direct": stats.get("batches_staged_direct", 0) > 0,
        "steps": num_steps,
        "step_time_s": round(step_time, 2),
        "total_s": round(total_s, 2),
        # None (-> JSON null) when no real step ran: json.dumps would
        # otherwise emit the literal NaN, which strict parsers reject.
        "loss": (
            round(float(metrics["loss"]), 4)
            if math.isfinite(float(metrics["loss"]))
            else None
        ),
        "num_chips": num_chips,
        "host_cpus": os.cpu_count(),
        "backend": platform,
        "step": (
            f"calibrated-{mock_step_s:.3f}s"
            if calibrated_from_s is not None
            else f"mock-{mock_step_s}s"
            if mock_step_s is not None
            else "real"
        ),
        **(
            {"calibrated_from_s": round(calibrated_from_s, 4)}
            if calibrated_from_s is not None
            else {}
        ),
        "loader": "resident" if use_resident else "mapreduce",
        "pallas": pallas_mode,
        # Resident loader: the one-time decode+pack+H2D staging pass;
        # map/reduce loader: time to the first delivered batch.
        "first_batch_s": round(stats.get("first_batch_s", 0.0), 2),
        "peak_hbm_gb": round(
            max(
                (
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices()
                ),
                default=0,
            ) / 1e9, 3
        ),
        "peak_shm_gb": round(sampler.peak_bytes / 1e9, 3),
        "peak_spill_gb": round(sampler.peak_spill_bytes / 1e9, 3),
        **phase,
    }
    if QUICK:
        result["quick"] = True
    # Disarm only now: everything after this is pure host-side printing.
    last_progress[0] = float("inf")
    return result


# -- TCP-plane bench (two-process loopback "two hosts") ----------------------
#
# The DCN stand-in measurement the r5 VERDICT flagged as missing (#2): the
# reference's cross-host plane (plasma + gRPC) ran on 4-node deployments;
# this repo's StoreServer windowed fetch had no GB/s, latency, or
# protocol-overhead number at all. `bench.py --plane tcp` starts a cluster
# head on 127.0.0.1, joins ONE worker host in a subprocess with its own
# shm dir (so nothing short-circuits through a shared /dev/shm), and then:
#
#   (a) windowed-fetch microbench — a publisher actor ON THE WORKER HOST
#       publishes hardlinked row-window segments; the driver pulls every
#       window over TCP through the real remote-fetch path, once with the
#       legacy pickle framing and once with the zero-copy vectored plane
#       (RSDL_TCP_ZEROCOPY), against a local-shm read of the same shape
#       and a raw loopback-socket ceiling;
#   (b) a mini end-to-end shuffle with locality DISABLED, so map/reduce
#       tasks scatter across both hosts and reducers/trainers pull their
#       inputs over TCP — with the audit plane on, proving exactly-once
#       delivery over the new transport path (`audit.ok`).


class _TcpPublisher:
    """Actor placed on the WORKER host: publishes window segments into
    that host's store so the driver's fetches must cross TCP."""

    def publish(self, num_windows: int, window_bytes: int):
        import numpy as np

        from ray_shuffling_data_loader_tpu import runtime

        ctx = runtime.ensure_initialized()
        rows_per = max(1, window_bytes // 16)  # two 8-byte columns
        total = rows_per * num_windows
        pending = ctx.store.create_columns(
            {
                "a": ((total,), np.dtype(np.int64)),
                "b": ((total,), np.dtype(np.float64)),
            }
        )
        try:
            pending.columns["a"][:] = np.arange(total, dtype=np.int64)
            pending.columns["b"][:] = 0.5
            refs = pending.publish_slices(
                [
                    (i * rows_per, (i + 1) * rows_per)
                    for i in range(num_windows)
                ]
            )
        finally:
            pending.abort()
        return refs

    def free(self, refs):
        from ray_shuffling_data_loader_tpu import runtime

        runtime.ensure_initialized().store.free(list(refs))


def _publisher_cls():
    """The publisher class via the importable `bench` module (pickle by
    reference must resolve on the worker host's agent, where __main__ is
    the actor bootstrap, not this script)."""
    try:
        import bench as _self  # noqa: PLW0406 — self-import on purpose

        return _self._TcpPublisher
    except ImportError:
        return _TcpPublisher


_TCP_WORKER_SRC = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.runtime import cluster
ctx = runtime.init(address={address!r}, num_workers=2)
print("[tcp-bench-worker] joined", ctx.cluster.host_id, flush=True)
cluster.serve_forever()
runtime.shutdown()
"""


def _raw_loopback_gbps(nbytes: int = 256 << 20) -> float:
    """Throughput of a plain sendall/recv_into stream over one loopback
    TCP connection — the kernel-path ceiling any framing overhead is
    measured against."""
    import socket

    server = socket.create_server(("127.0.0.1", 0))
    port = server.getsockname()[1]
    chunk = bytearray(4 << 20)

    def _sink():
        conn, _ = server.accept()
        with conn:
            buf = memoryview(bytearray(8 << 20))
            got = 0
            while got < nbytes:
                n = conn.recv_into(buf)
                if not n:
                    break
                got += n

    t = threading.Thread(target=_sink, daemon=True)
    t.start()
    out = socket.create_connection(("127.0.0.1", port))
    out.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    t0 = time.perf_counter()
    sent = 0
    while sent < nbytes:
        out.sendall(chunk)
        sent += len(chunk)
    out.close()
    t.join(30)
    server.close()
    return sent / 1e9 / max(1e-9, time.perf_counter() - t0)


def _lat_stats(lat_s) -> dict:
    lat_ms = sorted(1e3 * x for x in lat_s)
    n = len(lat_ms)
    return {
        "mean": round(sum(lat_ms) / n, 3),
        "p50": round(lat_ms[n // 2], 3),
        "min": round(lat_ms[0], 3),
        "max": round(lat_ms[-1], 3),
    }


def run_tcp_plane_bench() -> dict:
    import tempfile as _tempfile

    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.runtime import transport
    from ray_shuffling_data_loader_tpu.telemetry import audit as _audit
    from ray_shuffling_data_loader_tpu.telemetry import metrics as _m

    windows = int(os.environ.get("RSDL_BENCH_TCP_WINDOWS", "64"))
    window_mb = float(os.environ.get("RSDL_BENCH_TCP_WINDOW_MB", "4"))
    window_bytes = int(window_mb * 1e6)
    shuffle_gb = float(os.environ.get("RSDL_BENCH_TCP_SHUFFLE_GB", "0.2"))

    # Arm metrics + audit BEFORE the cluster comes up: worker-host agents
    # fix their env at spawn, and the mini shuffle's exactly-once verdict
    # needs every remote task folding digests.
    _m.enable()
    audit_dir = _tempfile.mkdtemp(prefix="rsdl-tcpbench-audit-")
    _audit.enable(spool_dir=audit_dir)
    # The mini shuffle must SCATTER (locality would keep reduces next to
    # their inputs and off the wire — the opposite of what this bench
    # exists to measure).
    os.environ["RSDL_DISABLE_LOCALITY"] = "1"
    # Telemetry federation (ISSUE 19) rides this leg by default: the
    # worker host joins with its OWN runtime dir, so without the relay
    # the driver-side telemetry_final/audit would silently lose every
    # remote record. setdefault so RSDL_RELAY=off A/Bs the overhead.
    os.environ.setdefault("RSDL_RELAY", "auto")
    # Worker-host processes fix their env at spawn: arm the zero-copy
    # plane cluster-wide NOW so the shuffle leg's remote reducers ride
    # it; the windowed-fetch microbench below toggles the DRIVER's gate
    # per plane (the client side chooses the framing). Striping
    # (RSDL_TCP_STREAMS) rides the same spawn-time env so the shuffle
    # leg's worker-side fetches stripe too.
    os.environ["RSDL_TCP_ZEROCOPY"] = "1"
    # Default 2: stream count should track cores devoted to recv — on
    # this 2-core host more streams just oversubscribe (BENCHLOG r7).
    # Clamped to the transport's own [1, 16] range so the JSON records
    # the stream count that actually ran (an uncapped env value would be
    # silently re-clamped inside transport.tcp_streams()).
    streams = min(
        16, max(1, int(os.environ.get("RSDL_BENCH_TCP_STREAMS", "2")))
    )
    os.environ["RSDL_TCP_STREAMS"] = str(streams)

    worker_shm = _tempfile.mkdtemp(prefix="rsdl-tcpbench-shm-")
    worker_spill = _tempfile.mkdtemp(prefix="rsdl-tcpbench-spill-")
    ctx = runtime.init_cluster(
        listen_host="127.0.0.1",
        advertise_host="127.0.0.1",
        num_workers=2,
    )
    worker_env = dict(
        os.environ,
        RSDL_SHM_DIR=worker_shm,
        RSDL_SPILL_DIR=worker_spill,
        RSDL_ADVERTISE_HOST="127.0.0.1",
        JAX_PLATFORMS="cpu",
    )
    worker = subprocess.Popen(
        [
            sys.executable,
            "-c",
            _TCP_WORKER_SRC.format(
                repo=os.path.dirname(os.path.abspath(__file__)),
                address=ctx.cluster.address,
            ),
        ],
        env=worker_env,
    )
    result = {
        "metric": (
            "Cross-host TCP plane: StoreServer windowed fetch GB/s + "
            "two-host shuffle (loopback stand-in for DCN)"
        ),
        "plane": "tcp",
        "unit": "GB/s",
        "backend": "cpu",
        "host_cpus": os.cpu_count(),
        "windows": windows,
        "window_mb": window_mb,
    }

    def _embed_final(res: dict) -> None:
        """Federated final counters + relay status — success AND error
        paths, and BEFORE the finally below tears the session down
        (shutdown removes the spool tree the relayed records live in).
        Never raises (one-JSON-line contract)."""
        if _m.enabled():
            try:
                from ray_shuffling_data_loader_tpu.telemetry import (
                    export as _export,
                )

                res["telemetry_final"] = _export.aggregate()
                res["telemetry_source_hosts"] = sorted(
                    {
                        str((rec.get("source") or {}).get("host"))
                        for rec in _export.load_records()
                    }
                )
            except Exception:
                pass
        _relay = sys.modules.get(
            "ray_shuffling_data_loader_tpu.telemetry.relay"
        )
        if _relay is not None:
            try:
                res["relay"] = {
                    "mode": os.environ.get("RSDL_RELAY", ""),
                    "status": _relay.status_section(),
                }
            except Exception:
                pass

    try:
        deadline = time.monotonic() + 120
        while len(ctx.cluster.registry.call("hosts")) < 2:
            if worker.poll() is not None:
                raise RuntimeError(
                    f"worker host exited rc={worker.returncode}"
                )
            if time.monotonic() > deadline:
                raise RuntimeError("worker host never joined")
            time.sleep(0.2)
        worker_host_id = next(
            hid
            for hid in ctx.cluster.registry.call("hosts")
            if hid != ctx.cluster.host_id
        )
        pub = runtime.spawn_actor(
            _publisher_cls(), host_id=worker_host_id
        )
        refs = pub.call("publish", windows, window_bytes)
        store = ctx.store
        total_bytes = sum(
            16 * (r.rows[1] - r.rows[0]) for r in refs
        )

        def _timed_tcp_fetch():
            lat = []
            t0 = time.perf_counter()
            for ref in refs:
                s = time.perf_counter()
                cb = store.get_columns(ref)
                assert cb.num_rows > 0
                del cb
                lat.append(time.perf_counter() - s)
            dt = time.perf_counter() - t0
            # Drop the fetched caches OUTSIDE the timed window so the
            # next plane re-fetches over the wire.
            store.drop_cache(refs)
            return total_bytes / 1e9 / dt, lat

        # Plane 1: legacy pickle framing.
        os.environ.pop("RSDL_TCP_ZEROCOPY", None)
        transport.refresh_zerocopy_from_env()
        pickle_gbps, pickle_lat = _timed_tcp_fetch()
        # Plane 2: zero-copy vectored framing, single stream.
        os.environ["RSDL_TCP_ZEROCOPY"] = "1"
        os.environ["RSDL_TCP_STREAMS"] = "1"
        transport.refresh_zerocopy_from_env()
        transport.refresh_tcp_streams_from_env()
        zc_gbps, zc_lat = _timed_tcp_fetch()
        # Plane 3: zero-copy striped over RSDL_TCP_STREAMS persistent
        # connections — each window's payload split by byte range with
        # parallel recv_into disjoint regions of one mmapped cache file
        # (the single-stream framing + single-core recv gap, ROADMAP 2).
        os.environ["RSDL_TCP_STREAMS"] = str(streams)
        transport.refresh_tcp_streams_from_env()
        striped_gbps, striped_lat = _timed_tcp_fetch()

        def _timed_pipelined_fetch(depth: int = 8):
            """Windowed fetch the way the reduce plane actually runs it:
            ``store.prefetch`` keeps ``depth`` windows in flight, so
            per-window costs (cache-file lifecycle, recv, server send)
            overlap across windows instead of serializing — the
            DELIVERED fetch bandwidth, vs the serial loop's per-window
            latency view."""
            t0 = time.perf_counter()
            futs = store.prefetch(refs, max_parallel=depth)
            if not futs:  # nothing was foreign/uncached: no real measure
                return None
            for f in futs:
                f.result()
            dt = time.perf_counter() - t0
            missing = [r for r in refs if store._find_cache(r) is None]
            store.drop_cache(refs)
            if missing:  # a swallowed prefetch failure: don't fake a number
                return None
            return total_bytes / 1e9 / dt

        # Pipelined rows, both framings (same windows, prefetch depth 8).
        os.environ["RSDL_TCP_STREAMS"] = "1"
        transport.refresh_tcp_streams_from_env()
        zc_pipe_gbps = _timed_pipelined_fetch()
        os.environ["RSDL_TCP_STREAMS"] = str(streams)
        transport.refresh_tcp_streams_from_env()
        striped_pipe_gbps = _timed_pipelined_fetch()

        # Baseline: the same windows living in LOCAL shm, reading every
        # byte (the mmap is lazy; the sum forces the pages).
        import numpy as np

        rows_per = max(1, window_bytes // 16)
        local_pending = store.create_columns(
            {
                "a": ((rows_per * windows,), np.dtype(np.int64)),
                "b": ((rows_per * windows,), np.dtype(np.float64)),
            }
        )
        local_pending.columns["a"][:] = 1
        local_pending.columns["b"][:] = 0.5
        local_refs = local_pending.publish_slices(
            [(i * rows_per, (i + 1) * rows_per) for i in range(windows)]
        )
        local_pending.abort()
        del local_pending
        shm_lat = []
        t0 = time.perf_counter()
        for ref in local_refs:
            s = time.perf_counter()
            cb = store.get_columns(ref)
            for col in cb.columns.values():
                col.sum()
            del cb
            shm_lat.append(time.perf_counter() - s)
        shm_gbps = total_bytes / 1e9 / (time.perf_counter() - t0)
        store.free(local_refs)
        pub.call("free", refs)

        raw_gbps = _raw_loopback_gbps()
        # HMAC challenge-response cost: full authed TCP connection setup
        # to the worker's store server, amortized per connection.
        store_addr = tuple(
            ctx.cluster.registry.call("hosts")[worker_host_id]["store"]
        )
        t0 = time.perf_counter()
        n_conn = 20
        for _ in range(n_conn):
            conn = transport.Connection(store_addr, timeout=10.0)
            conn.close()
        hmac_ms = 1e3 * (time.perf_counter() - t0) / n_conn

        result["fetch"] = {
            "total_gb": round(total_bytes / 1e9, 3),
            "shm_gbps": round(shm_gbps, 3),
            "tcp_pickle_gbps": round(pickle_gbps, 3),
            "tcp_zerocopy_gbps": round(zc_gbps, 3),
            "tcp_zerocopy_striped_gbps": round(striped_gbps, 3),
            "tcp_zerocopy_pipelined_gbps": (
                round(zc_pipe_gbps, 3) if zc_pipe_gbps else None
            ),
            "tcp_zerocopy_striped_pipelined_gbps": (
                round(striped_pipe_gbps, 3) if striped_pipe_gbps else None
            ),
            "tcp_streams": streams,
            "raw_loopback_gbps": round(raw_gbps, 3),
            "window_ms": {
                "shm": _lat_stats(shm_lat),
                "tcp_pickle": _lat_stats(pickle_lat),
                "tcp_zerocopy": _lat_stats(zc_lat),
                "tcp_zerocopy_striped": _lat_stats(striped_lat),
            },
            "hmac_handshake_ms": round(hmac_ms, 3),
            # Framing+pickle+copy overhead vs the raw socket ceiling,
            # per plane (what fraction of achievable loopback bandwidth
            # the protocol costs).
            "overhead_vs_raw_pct": {
                "tcp_pickle": round(100 * (1 - pickle_gbps / raw_gbps), 1),
                "tcp_zerocopy": round(100 * (1 - zc_gbps / raw_gbps), 1),
                "tcp_zerocopy_striped": round(
                    100 * (1 - striped_gbps / raw_gbps), 1
                ),
            },
        }

        # -- (b) two-host end-to-end shuffle over TCP ---------------------
        import importlib

        from ray_shuffling_data_loader_tpu.data_generation import (
            cached_generate_data,
        )

        # The package re-exports shuffle() the FUNCTION under the module
        # name; resolve the module explicitly.
        shuffle_mod = importlib.import_module(
            "ray_shuffling_data_loader_tpu.shuffle"
        )

        num_rows = max(4000, int(shuffle_gb * 1e9) // BYTES_PER_ROW)
        data_dir = os.path.join(CACHE_DIR, f"tcp_r{num_rows}_f8")
        os.makedirs(data_dir, exist_ok=True)
        filenames, dataset_bytes = cached_generate_data(
            num_rows, 8, 1, data_dir, seed=SEED
        )

        class _Drain(shuffle_mod.BatchConsumer):
            def __init__(self):
                self.nbytes = 0
                self.rows = 0

            def consume(self, rank, epoch, batches):
                for ref in batches:
                    cb = store.get_columns(ref)
                    self.rows += cb.num_rows
                    self.nbytes += cb.nbytes
                    del cb
                    store.free(ref)

            def producer_done(self, rank, epoch):
                pass

            def wait_until_ready(self, epoch):
                pass

            def wait_until_all_epochs_done(self):
                pass

        consumer = _Drain()
        schedule_log = []
        t0 = time.perf_counter()
        shuffle_mod.shuffle(
            list(filenames),
            consumer,
            num_epochs=2,
            num_reducers=8,
            num_trainers=1,
            seed=SEED,
            schedule_log=schedule_log,
        )
        shuffle_s = time.perf_counter() - t0
        served = {}
        for hid, info in ctx.cluster.registry.call("hosts").items():
            from ray_shuffling_data_loader_tpu.runtime.actor import (
                ActorHandle,
            )

            role = "head" if hid == ctx.cluster.host_id else "worker"
            served[role] = ActorHandle(tuple(info["store"])).call(
                "fetch_stats"
            )
        audit_summary = _audit.summary()
        # summary().ok is None when zero epochs actually reconciled —
        # that must read as NOT verified, never as a pass.
        audit_ok = audit_summary.get("ok") is True
        shuffle_gbps = consumer.nbytes / 1e9 / shuffle_s
        result["value"] = round(shuffle_gbps, 4)
        result["shuffle"] = {
            "dataset_gb": round(dataset_bytes / 1e9, 3),
            "delivered_gb": round(consumer.nbytes / 1e9, 3),
            "seconds": round(shuffle_s, 2),
            "gbps": round(shuffle_gbps, 4),
            "audit_ok": audit_ok,
            "zerocopy": True,
            "tcp_streams": streams,
            "served_cross_host": served,
            "schedules": [s for _, s in schedule_log],
        }
        if not audit_ok:
            result["error"] = "audit mismatch over the TCP plane"
        if _m.enabled():
            try:
                from ray_shuffling_data_loader_tpu.telemetry import (
                    export as _export,
                )

                flat = _export.aggregate()
                result["fetch_window_metrics"] = {
                    k: v
                    for k, v in flat.items()
                    if k.startswith("store.fetch_window")
                }
            except Exception:
                pass
        _embed_final(result)
        return result
    except Exception as exc:
        # Error path: same federated embed — the remote counters of a
        # failed run are the artifact that shows what the worker host
        # was doing when it died. Embed BEFORE the finally's shutdown
        # removes the spool tree, then return the error result (main
        # exits non-zero on any "error" key).
        import traceback

        traceback.print_exc(file=sys.stderr)
        result.setdefault("error", f"{type(exc).__name__}: {exc}"[:300])
        _embed_final(result)
        return result
    finally:
        try:
            runtime.shutdown()
        except Exception:
            pass
        if worker.poll() is None:
            worker.terminate()
            try:
                worker.wait(10)
            except subprocess.TimeoutExpired:
                worker.kill()
        import shutil as _shutil

        for d in (worker_shm, worker_spill):
            _shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# Suspend/resume leg (ISSUE 13): SIGKILL the driver mid-window, resume
# from the write-ahead journal, and price the recovery.
# ---------------------------------------------------------------------------

_RESUME_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["RSDL_BENCH_RESUME_REPO"])
from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.shuffle import BatchConsumer, shuffle
from ray_shuffling_data_loader_tpu.telemetry import audit as _audit
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

mode = os.environ["RSDL_BENCH_RESUME_MODE"]
files = json.loads(os.environ["RSDL_BENCH_RESUME_FILES"])
epochs = int(os.environ["RSDL_BENCH_RESUME_EPOCHS"])
reducers = int(os.environ["RSDL_BENCH_RESUME_REDUCERS"])
seed = int(os.environ["RSDL_BENCH_RESUME_SEED"])

runtime.init(num_workers=2)
t0 = time.perf_counter()
first = []


class Drain(BatchConsumer):
    def consume(self, rank, epoch, batches, seq=None):
        if not first:
            first.append(time.perf_counter() - t0)
            print("FIRST_BATCH %.4f" % first[0], flush=True)
        store = runtime.get_context().store
        for ref in batches:
            store.free(ref)
        print("DELIVERED %d %s" % (epoch, seq), flush=True)
        if mode == "victim":
            time.sleep(0.15)  # widen the kill window

    def producer_done(self, rank, epoch):
        pass

    def wait_until_ready(self, epoch):
        pass

    def wait_until_all_epochs_done(self):
        pass


shuffle(files, Drain(), num_epochs=epochs, num_reducers=reducers,
        num_trainers=1, seed=seed)
verdicts = _audit.reconcile(range(epochs)) if _audit.enabled() else []
snap = _metrics.registry.snapshot() if _metrics.enabled() else {}
print("RESULT " + json.dumps({
    "first_batch_s": first[0] if first else None,
    "verdicts": [
        {"epoch": v["epoch"], "ok": v["ok"],
         "delivered_seq": v.get("delivered_seq")} for v in verdicts
    ],
    "recovery": {k: v for k, v in snap.items()
                 if k.startswith("recovery.")},
}), flush=True)
runtime.shutdown()
"""


def run_resume_bench() -> dict:
    """The ``--resume`` leg: a journal-armed driver is SIGKILLed
    mid-epoch-window, a fresh driver resumes from the write-ahead
    journal (``RSDL_RESUME=auto``), and the JSON records resume-to-
    first-batch latency against a cold epoch start plus the resume
    counters — with per-epoch ``delivered_seq`` digests proven
    bit-identical to an uninterrupted same-seed control run."""
    import shutil
    import signal as _signal

    from ray_shuffling_data_loader_tpu.data_generation import (
        cached_generate_data,
    )

    epochs, reducers, seed = 3, 4, SEED
    num_rows = max(20_000, int(0.05e9) // BYTES_PER_ROW)
    data_dir = os.path.join(CACHE_DIR, f"resume_r{num_rows}_f4")
    os.makedirs(data_dir, exist_ok=True)
    filenames, dataset_bytes = cached_generate_data(
        num_rows, 4, 1, data_dir, seed=seed
    )
    # Data generation brought up a pool in THIS process; the leg's
    # drivers are child processes with their own runtimes — drop ours
    # so the kill/resume measurements run against an idle parent.
    from ray_shuffling_data_loader_tpu import runtime as _runtime

    _runtime.shutdown()
    work = tempfile.mkdtemp(prefix="rsdl-resume-bench-")
    journal_dir = os.path.join(work, "journal")
    spool_ctrl = os.path.join(work, "audit-control")
    spool_run = os.path.join(work, "audit-run")
    shm_dir = os.path.join(work, "shm")
    for d in (journal_dir, spool_ctrl, spool_run, shm_dir):
        os.makedirs(d, exist_ok=True)

    base_env = dict(
        os.environ,
        RSDL_BENCH_RESUME_REPO=os.path.dirname(os.path.abspath(__file__)),
        RSDL_BENCH_RESUME_FILES=json.dumps(list(filenames)),
        RSDL_BENCH_RESUME_EPOCHS=str(epochs),
        RSDL_BENCH_RESUME_REDUCERS=str(reducers),
        RSDL_BENCH_RESUME_SEED=str(seed),
        RSDL_SHM_DIR=shm_dir,
        RSDL_AUDIT="1",
        RSDL_METRICS="1",
        JAX_PLATFORMS="cpu",
    )
    base_env.pop("RSDL_JOURNAL", None)
    base_env.pop("RSDL_RESUME", None)

    def _child(mode, extra, kill_after=None):
        env = dict(base_env, RSDL_BENCH_RESUME_MODE=mode, **extra)
        proc = subprocess.Popen(
            [sys.executable, "-c", _RESUME_CHILD],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True,
        )
        first_batch, result, delivered = None, None, 0
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("FIRST_BATCH "):
                first_batch = float(line.split()[1])
            elif line.startswith("DELIVERED "):
                delivered += 1
                if kill_after is not None and delivered >= kill_after:
                    os.kill(proc.pid, _signal.SIGKILL)
                    break
            elif line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
        proc.wait()
        return first_batch, result, delivered

    result = {
        "metric": "Suspend/resume (driver SIGKILLed mid-window)",
        "plane": "resume",
        "unit": "s",
        "dataset_gb": round(dataset_bytes / 1e9, 3),
        "epochs": epochs,
    }
    try:
        # Control: uninterrupted same-seed run — the digest truth and
        # the cold first-batch latency.
        cold_first, ctrl, _ = _child(
            "control", {"RSDL_AUDIT_DIR": spool_ctrl}
        )
        if ctrl is None:
            result["error"] = "control run died"
            return result
        # Victim: journal armed, SIGKILLed after epoch 0's window plus
        # a couple of epoch-1 deliveries (mid-epoch-window).
        _child(
            "victim",
            {"RSDL_AUDIT_DIR": spool_run, "RSDL_JOURNAL": journal_dir},
            kill_after=reducers + 2,
        )
        # Resume: fresh driver, RSDL_RESUME=auto, strict audit.
        resume_first, res, _ = _child(
            "resume",
            {"RSDL_AUDIT_DIR": spool_run, "RSDL_JOURNAL": journal_dir,
             "RSDL_RESUME": "auto", "RSDL_AUDIT_STRICT": "1"},
        )
        if res is None:
            result["error"] = "resumed run died"
            return result
        ctrl_seq = {v["epoch"]: v["delivered_seq"]
                    for v in ctrl["verdicts"]}
        res_seq = {v["epoch"]: v["delivered_seq"]
                   for v in res["verdicts"]}
        recovery = res.get("recovery", {})

        def _sum(prefix):
            return int(sum(v for k, v in recovery.items()
                           if k.startswith(prefix)))

        result.update({
            "value": round(resume_first, 4) if resume_first else None,
            "cold_first_batch_s": (
                round(cold_first, 4) if cold_first else None
            ),
            "resume_to_first_batch_s": (
                round(resume_first, 4) if resume_first else None
            ),
            "resumed_epochs": _sum("recovery.resumed_epochs"),
            "resumed_epochs_skipped": _sum(
                "recovery.resume_epochs_skipped"
            ),
            "replayed_stages": _sum("recovery.resume_reexecuted"),
            "reattached_map_stages": _sum("recovery.resume_map_skipped"),
            "reattached_reduce_stages": _sum(
                "recovery.resume_reduce_skipped"
            ),
            "digest_match": ctrl_seq == res_seq and len(ctrl_seq) == epochs,
            "audit_ok": all(v["ok"] for v in res["verdicts"]),
        })
        if not result["digest_match"]:
            result["error"] = (
                f"delivered_seq diverged: control={ctrl_seq} "
                f"resumed={res_seq}"
            )
        elif not result["audit_ok"]:
            result["error"] = "resumed run audit mismatch"
        elif not (result["resumed_epochs"]
                  or result["resumed_epochs_skipped"]):
            result["error"] = "resume found no journaled progress"
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def run_service_bench() -> dict:
    """The ``--plane service`` leg (ISSUE 15): two concurrent shuffle
    jobs against one service session — a same-dataset leg (job 2 rides
    job 1's decoded segments: cache-hot first epoch) and a
    disjoint-dataset leg (pure capacity sharing) — reporting aggregate
    wall vs the serial sum of the cold solo runs, job 2's first-batch
    latency vs its cold solo first batch, and per-job delivered-rows
    fairness over the overlap window. Each leg owns a fresh runtime
    session so every "cold" is honestly cold."""
    import threading as _threading

    from ray_shuffling_data_loader_tpu.data_generation import (
        cached_generate_data,
    )
    from ray_shuffling_data_loader_tpu import runtime as _runtime
    from ray_shuffling_data_loader_tpu.shuffle import (
        BatchConsumer as _BC,
        shuffle as _shuffle,
    )
    from ray_shuffling_data_loader_tpu.telemetry import (
        metrics as _metrics_mod,
    )

    os.environ["RSDL_SERVICE"] = "auto"
    os.environ["RSDL_METRICS"] = "1"
    _metrics_mod.refresh_from_env()
    from ray_shuffling_data_loader_tpu.runtime import service as _service

    epochs, reducers, seed = 2, 4, SEED
    num_rows = max(20_000, int(0.05e9) // BYTES_PER_ROW)
    dirs = [
        os.path.join(CACHE_DIR, f"service_r{num_rows}_f4_d{i}")
        for i in (0, 1)
    ]
    for d in dirs:
        os.makedirs(d, exist_ok=True)
    files1, bytes1 = cached_generate_data(
        num_rows, 4, 1, dirs[0], seed=seed
    )
    files2, bytes2 = cached_generate_data(
        num_rows, 4, 1, dirs[1], seed=seed + 1
    )
    _runtime.shutdown()  # data gen's pool; each leg owns its session

    class TimingConsumer(_BC):
        def __init__(self):
            self.t0 = time.perf_counter()
            self.first_batch = None
            self.deliveries = []  # (monotonic ts, rows)
            self.epoch_done = {}

        def consume(self, rank, epoch, batches):
            now = time.perf_counter()
            if self.first_batch is None:
                self.first_batch = now - self.t0
            nbytes = sum(int(ref.nbytes) for ref in batches)
            self.deliveries.append((now, nbytes))
            _runtime.get_context().store.free(list(batches))

        def producer_done(self, rank, epoch):
            self.epoch_done[epoch] = time.perf_counter()

        def wait_until_ready(self, epoch):
            pass

        def wait_until_all_epochs_done(self):
            pass

    def run_job(name, files, job_seed, out, schedule_log=None):
        job = _service.register_job(name=name)
        try:
            with _service.job_context(job):
                consumer = TimingConsumer()
                out[name] = consumer
                _shuffle(
                    files, consumer, num_epochs=epochs,
                    num_reducers=reducers, num_trainers=1,
                    seed=job_seed, cache_decoded=True,
                    schedule_log=schedule_log,
                )
        finally:
            _service.end_job(job)

    def solo(files, job_seed):
        _runtime.init()
        _service.cache_registry_clear()
        out = {}
        t0 = time.perf_counter()
        run_job("solo", files, job_seed, out)
        wall = time.perf_counter() - t0
        consumer = out["solo"]
        _runtime.shutdown()
        _service.reset_state()
        return wall, consumer.first_batch

    def _cache_hits_job2() -> int:
        snap = _metrics_mod.registry.snapshot()
        return int(
            sum(
                v
                for k, v in snap.items()
                if k.startswith("service.cache_hits") and "job2" in k
            )
        )

    def concurrent(files_a, files_b, stagger_on_epoch0):
        """Job A starts; job B starts either after A's epoch-0 window
        (same-dataset: A's decode segments are published then) or
        immediately (disjoint). Returns walls + consumers + fairness."""
        _runtime.init()
        _service.cache_registry_clear()
        # Per-LEG counter baseline: the registry is process-global and
        # both legs' job ids start with "job2" — without the delta the
        # disjoint leg would inherit the same-dataset leg's hits.
        hits2_before = _cache_hits_job2()
        out = {}
        log_b = []
        t0 = time.perf_counter()
        ta = _threading.Thread(
            target=run_job, args=("job1", files_a, seed, out)
        )
        ta.start()
        if stagger_on_epoch0:
            # Same-dataset leg: start job 2 once job 1's epoch-0 decode
            # segments are PUBLISHED in the content registry (promoted
            # as each publishing map resolves) — the "second job joins
            # a warm service" shape; most of job 1's run still
            # overlaps.
            deadline = time.perf_counter() + 120
            while time.perf_counter() < deadline:
                published = (
                    _service.status_section().get("cache_entries") or 0
                )
                if published >= len(files_a):
                    break
                time.sleep(0.02)
        t_b0 = time.perf_counter()
        tb = _threading.Thread(
            target=run_job,
            args=("job2", files_b, seed + 7, out),
            kwargs={"schedule_log": log_b},
        )
        tb.start()
        ta.join(timeout=600)
        tb.join(timeout=600)
        t_end = time.perf_counter()
        c1, c2 = out["job1"], out["job2"]
        # Cross-job cache proof: every lookup hit job 2 scored against
        # the content registry THIS leg (>= one per file when it rode
        # job 1's segments — its own decode would score zero).
        hits2 = _cache_hits_job2() - hits2_before
        # Fairness over the window where BOTH jobs are delivering
        # (first common delivery to last common delivery): delivered-
        # BYTES rate per job, min/max ratio. A window under 0.3 s (the
        # staggered same-dataset leg can leave almost none) reports
        # null rather than a noise ratio.
        fair = None
        overlap = 0.0
        if c1.deliveries and c2.deliveries:
            lo = max(c1.deliveries[0][0], c2.deliveries[0][0])
            hi = min(c1.deliveries[-1][0], c2.deliveries[-1][0])
            overlap = max(hi - lo, 0.0)
            if overlap > 0.3:
                rates = []
                for c in (c1, c2):
                    nbytes = sum(
                        b for ts, b in c.deliveries if lo <= ts <= hi
                    )
                    rates.append(nbytes / overlap)
                if max(rates) > 0:
                    fair = round(min(rates) / max(rates), 4)
        _runtime.shutdown()
        _service.reset_state()
        return {
            "wall_s": round(t_end - t0, 3),
            "job2_first_batch_s": (
                round(c2.first_batch, 3)
                if c2.first_batch is not None
                else None
            ),
            "job2_epoch0_schedule": dict(log_b).get(0),
            "job2_cache_hits": hits2,
            "fairness_min_over_max": fair,
            "overlap_s": round(overlap, 3),
            "job1_gb": round(
                sum(b for _, b in c1.deliveries) / 1e9, 4
            ),
            "job2_gb": round(
                sum(b for _, b in c2.deliveries) / 1e9, 4
            ),
        }

    result = {
        "metric": "Disaggregated shuffle service (two concurrent jobs)",
        "plane": "service",
        "unit": "s",
        "dataset_gb": round((bytes1 + bytes2) / 1e9, 3),
        "epochs": epochs,
        "reducers": reducers,
    }
    wall_a, first_a = solo(files1, seed)
    wall_b, _first_b = solo(files2, seed + 7)
    same = concurrent(files1, files1, stagger_on_epoch0=True)
    disjoint = concurrent(files1, files2, stagger_on_epoch0=False)
    serial_sum_same = wall_a + wall_a  # two cold solos over D1
    serial_sum_disjoint = wall_a + wall_b
    result.update({
        "solo_cold_wall_s": round(wall_a, 3),
        "solo_cold_first_batch_s": (
            round(first_a, 3) if first_a is not None else None
        ),
        "solo_cold_wall_b_s": round(wall_b, 3),
        "same_dataset": dict(
            same, serial_sum_s=round(serial_sum_same, 3),
            speedup_vs_serial=round(serial_sum_same / same["wall_s"], 3),
        ),
        "disjoint_dataset": dict(
            disjoint, serial_sum_s=round(serial_sum_disjoint, 3),
            speedup_vs_serial=round(
                serial_sum_disjoint / disjoint["wall_s"], 3
            ),
        ),
        "value": same["wall_s"],
    })
    checks = []
    if same.get("job2_cache_hits", 0) < len(files1):
        checks.append(
            "job2 epoch-0 did not ride job1's decode cache "
            f"(cache_hits={same.get('job2_cache_hits')}, "
            f"schedule={same.get('job2_epoch0_schedule')!r})"
        )
    if first_a and same.get("job2_first_batch_s"):
        result["job2_first_batch_speedup_vs_cold"] = round(
            first_a / same["job2_first_batch_s"], 2
        )
        if same["job2_first_batch_s"] > first_a / 2:
            checks.append(
                "job2 first batch not >=2x faster than cold solo"
            )
    if same["wall_s"] >= serial_sum_same:
        checks.append("same-dataset concurrent wall >= serial sum")
    if disjoint["wall_s"] >= serial_sum_disjoint:
        checks.append("disjoint concurrent wall >= serial sum")
    for leg in (same, disjoint):
        fair = leg.get("fairness_min_over_max")
        if fair is not None and fair < (1.0 / 3.0):
            checks.append(
                f"fairness ratio {fair} below 1/3 at equal weights"
            )
    if checks:
        result["error"] = "; ".join(checks)[:400]
    return result


# Every knob the plan compiler owns (planner TERM_KNOBS) plus the gate
# itself: each planner-bench leg starts from a clean slate of these so a
# stray shell export can't contaminate a "stock defaults" leg.
_PLANNER_KNOBS = (
    "RSDL_PLAN",
    "RSDL_SHUFFLE_PLAN",
    "RSDL_SELECTIVE_READS",
    "RSDL_DECODE_PUSHDOWN",
    "RSDL_DECODE_ROWGROUPS",
    "RSDL_FETCH_WINDOW_DEPTH",
    "RSDL_NATIVE_THREADS",
)


def run_planner_bench() -> dict:
    """The ``--plane planner`` leg (ISSUE 20): A/B the cost-based plan
    compiler against a hand-tuned knob set and stock defaults at two
    shapes — the r12 decode-bound shape (0.4 GB decoded x 4 files x 9
    skewed row groups, R=4, cache off, 2 epochs: block+selective is the
    documented win) and a mock-step delivery-bound shape (few blocks per
    file, so rowwise/stock is already right and the planner must not
    lose). Each leg owns a fresh runtime session so the workers' env
    snapshots honestly reflect the leg's knobs; the planner leg embeds
    the chosen plan terms (snapshotted from ``runtime.plan`` at first
    delivery) in the JSON."""
    from ray_shuffling_data_loader_tpu.data_generation import generate_data
    from ray_shuffling_data_loader_tpu import runtime as _runtime
    from ray_shuffling_data_loader_tpu.shuffle import (
        BatchConsumer as _BC,
        shuffle as _shuffle,
    )

    trials = int(os.environ.get("RSDL_BENCH_PLANNER_TRIALS", "3"))
    decode_gb = float(os.environ.get("RSDL_BENCH_PLANNER_GB", "0.4"))
    # Sized so the mock step dominates the delivery-bound wall (~8
    # deliveries x step >> pipeline noise on a loaded 2-core host):
    # the shape's claim is "the planner must not LOSE when the loader
    # is not the bottleneck", which a noise-dominated wall can't test.
    step_s = float(os.environ.get("RSDL_BENCH_PLANNER_STEP_S", "0.15"))

    def _dataset(tag, num_rows, files, groups, skew):
        """generate_data with a manifest cache keyed on the full shape
        (cached_generate_data can't: it pins skew to 0)."""
        data_dir = os.path.join(
            CACHE_DIR, f"planner_{tag}_r{num_rows}_f{files}_g{groups}"
        )
        os.makedirs(data_dir, exist_ok=True)
        key = {
            "num_rows": num_rows, "files": files, "groups": groups,
            "skew": skew, "seed": SEED,
        }
        manifest = os.path.join(data_dir, "planner_manifest.json")
        if os.path.exists(manifest):
            try:
                with open(manifest) as f:
                    m = json.load(f)
                if m.get("key") == key and all(
                    os.path.exists(p) for p in m["filenames"]
                ):
                    return m["filenames"], m["num_bytes"]
            except (json.JSONDecodeError, OSError, KeyError):
                pass
        filenames, num_bytes = generate_data(
            num_rows, files, groups, skew, data_dir, seed=SEED
        )
        tmp = f"{manifest}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(
                {"key": key, "filenames": filenames, "num_bytes": num_bytes},
                f,
            )
        os.replace(tmp, manifest)
        return filenames, num_bytes

    reducers = 4
    shapes = {
        # r12 shape: 9 skewed groups/file >= 2R -> planner should choose
        # block:1 + selective; stock rowwise pays the materialized path.
        "decode_bound": {
            "rows": max(BATCH_SIZE, int(decode_gb * 1e9) // BYTES_PER_ROW),
            "files": 4, "groups": 9, "skew": 0.5, "epochs": 2,
            "step_s": 0.0,
            "hand": {
                "RSDL_SHUFFLE_PLAN": "block:1",
                "RSDL_SELECTIVE_READS": "auto",
                "RSDL_DECODE_ROWGROUPS": "auto",
            },
        },
        # 2 groups/file < 2R: the quality bound forbids block, stock
        # rowwise is already optimal, and a mock train step dominates the
        # wall — the planner's job here is to decline cleverness.
        "delivery_bound": {
            "rows": max(BATCH_SIZE // 4, int(0.05e9) // BYTES_PER_ROW),
            "files": 4, "groups": 2, "skew": 0.0, "epochs": 2,
            "step_s": step_s,
            "hand": {
                "RSDL_SHUFFLE_PLAN": "rowwise",
                "RSDL_FETCH_WINDOW_DEPTH": "4",
            },
        },
    }
    configs = ("stock", "hand", "planner")

    class StepConsumer(_BC):
        """Frees refs on delivery; optionally burns a mock train step per
        delivered batch (the delivery-bound regime); snapshots the
        resolved plan terms the first time a batch lands (the run is
        still live, so ``runtime.plan`` holds the current plan)."""

        def __init__(self, step_s):
            self.t0 = time.perf_counter()
            self.step_s = step_s
            self.first_batch = None
            self.nbytes = 0
            self.plan_terms = None

        def consume(self, rank, epoch, batches):
            now = time.perf_counter()
            if self.first_batch is None:
                self.first_batch = now - self.t0
                planmod = sys.modules.get(
                    "ray_shuffling_data_loader_tpu.runtime.plan"
                )
                if planmod is not None:
                    try:
                        self.plan_terms = planmod.current_terms()
                    except Exception:
                        pass
            self.nbytes += sum(int(ref.nbytes) for ref in batches)
            _runtime.get_context().store.free(list(batches))
            if self.step_s > 0:
                time.sleep(self.step_s)

        def producer_done(self, rank, epoch):
            pass

        def wait_until_ready(self, epoch):
            pass

        def wait_until_all_epochs_done(self):
            pass

    def run_once(files, shape, env):
        """One measured run under the leg's knobs (every planner knob
        cleared first so a stray shell export can't contaminate a
        'stock defaults' leg; restored after)."""
        saved = {k: os.environ.pop(k, None) for k in _PLANNER_KNOBS}
        try:
            os.environ.update(env)
            _runtime.init()
            try:
                consumer = StepConsumer(shape["step_s"])
                t0 = time.perf_counter()
                _shuffle(
                    files, consumer, num_epochs=shape["epochs"],
                    num_reducers=reducers, num_trainers=1,
                    seed=SEED, cache_decoded=False,
                )
                wall = time.perf_counter() - t0
            finally:
                _runtime.shutdown()
            # Delivered-volume sanity (ref.nbytes includes column
            # padding, so bytes-exact is the wrong assert): every
            # leg must deliver the full dataset each epoch +-2%.
            expected = shape["rows"] * BYTES_PER_ROW * shape["epochs"]
            if not (0.98 * expected <= consumer.nbytes <= 1.02 * expected):
                raise RuntimeError(
                    f"delivered {consumer.nbytes} bytes, expected "
                    f"~{expected}"
                )
            return wall, consumer.first_batch, consumer.plan_terms
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    result = {
        "metric": "Self-tuning plan compiler A/B (planner vs hand vs stock)",
        "plane": "planner",
        "unit": "s",
        "reducers": reducers,
        "trials": trials,
        "shapes": {},
    }
    checks = []
    beats_stock = []
    for shape_name, shape in shapes.items():
        files, num_bytes = _dataset(
            shape_name, shape["rows"], shape["files"], shape["groups"],
            shape["skew"],
        )
        _runtime.shutdown()  # data gen's pool; each leg owns its session
        envs = {
            "stock": {},
            "hand": dict(shape["hand"]),
            "planner": {"RSDL_PLAN": "auto"},
        }
        # Trials are INTERLEAVED round-robin across configs: background
        # load drifts on shared hosts at the tens-of-seconds scale, and
        # back-to-back per-config trials would hand whichever config ran
        # in the quiet window an unearned win. Per-config best-of-N.
        walls = {c: [] for c in configs}
        firsts = {c: [] for c in configs}
        terms_by = {c: None for c in configs}
        for trial in range(max(1, trials)):
            for config in configs:
                _log(
                    f"planner bench: {shape_name}/{config} trial {trial}"
                )
                wall, first, terms = run_once(files, shape, envs[config])
                walls[config].append(wall)
                if first is not None:
                    firsts[config].append(first)
                if terms:
                    terms_by[config] = terms
        legs = {}
        for config in configs:
            legs[config] = {
                "wall_s": round(min(walls[config]), 3),
                "wall_trials_s": [round(w, 3) for w in walls[config]],
                "first_batch_s": (
                    round(min(firsts[config]), 3)
                    if firsts[config]
                    else None
                ),
                "env": dict(envs[config]),
            }
            if terms_by[config] is not None:
                legs[config]["plan_terms"] = {
                    name: {"value": t.get("value"), "source": t.get("source")}
                    for name, t in terms_by[config].items()
                }
        legs["dataset_gb"] = round(num_bytes / 1e9, 3)
        legs["epochs"] = shape["epochs"]
        legs["mock_step_s"] = shape["step_s"]
        result["shapes"][shape_name] = legs
        planner_w = legs["planner"]["wall_s"]
        hand_w = legs["hand"]["wall_s"]
        stock_w = legs["stock"]["wall_s"]
        legs["planner_vs_hand"] = round(hand_w / planner_w, 3)
        legs["planner_vs_stock"] = round(stock_w / planner_w, 3)
        if legs["planner"].get("plan_terms") is None:
            checks.append(f"{shape_name}: planner leg recorded no plan terms")
        # >= 0.95x hand-tuned on BOTH shapes (issue acceptance bound).
        if planner_w > hand_w / 0.95:
            checks.append(
                f"{shape_name}: planner wall {planner_w:.2f}s worse than "
                f"0.95x hand-tuned {hand_w:.2f}s"
            )
        fb_p = legs["planner"]["first_batch_s"]
        fb_s = legs["stock"]["first_batch_s"]
        beats_stock.append(
            planner_w < stock_w
            or (fb_p is not None and fb_s is not None and fb_p < 0.8 * fb_s)
        )
    if not any(beats_stock):
        checks.append("planner beat stock defaults on neither shape")
    result["value"] = result["shapes"]["decode_bound"]["planner"]["wall_s"]
    if checks:
        result["error"] = "; ".join(checks)[:400]
    return result


def _parse_args(argv=None):
    import argparse

    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument(
        "--trace-out",
        default=os.environ.get("RSDL_TRACE_OUT") or None,
        help="write a merged Chrome-trace/Perfetto JSON of the whole run "
        "here (enables tracing + live metrics; see docs/observability.md)",
    )
    parser.add_argument(
        "--metrics-out",
        default=os.environ.get("RSDL_METRICS_OUT") or None,
        help="write the sampled metrics timeline + final snapshot JSON "
        "here (default: <trace-out>.metrics.json when --trace-out is set)",
    )
    parser.add_argument(
        "--plane",
        choices=("local", "tcp", "service", "planner"),
        default="local",
        help="'tcp' runs the two-process loopback cross-host plane bench "
        "instead of the training bench: a worker host joins over TCP "
        "(own shm dir), reducers/trainers fetch inputs through the "
        "StoreServer windowed-fetch path, and the JSON records GB/s, "
        "per-window latency, and HMAC/framing/pickle overhead vs the "
        "same shape on local shm (plane: \"tcp\" artifact; see "
        "docs/observability.md); 'service' runs two concurrent shuffle "
        "jobs against one RSDL_SERVICE session (same-dataset and "
        "disjoint-dataset legs) and records aggregate wall vs the "
        "serial solo sum, job 2's cache-hot first batch, and the "
        "delivered-rows fairness ratio (plane: \"service\" artifact; "
        "see docs/service.md); 'planner' A/Bs the RSDL_PLAN cost-based "
        "plan compiler against hand-tuned knobs and stock defaults at a "
        "decode-bound and a mock-step delivery-bound shape, with the "
        "chosen plan terms embedded (plane: \"planner\" artifact; see "
        "docs/TUNING.md planner section)",
    )
    parser.add_argument(
        "--resume",
        action="store_true",
        help="run the suspend/resume leg instead of the training bench: "
        "a journal-armed driver child (RSDL_JOURNAL) is SIGKILLed "
        "mid-epoch-window, a fresh child resumes with RSDL_RESUME=auto "
        "under strict audit, and the JSON records resume-to-first-batch "
        "latency vs the cold start, resumed_epochs/replayed_stages "
        "counters, and per-epoch delivered_seq digest equality against "
        "an uninterrupted same-seed control run (plane: \"resume\" "
        "artifact; see docs/robustness.md)",
    )
    parser.add_argument(
        "--audit",
        action="store_true",
        default=os.environ.get("RSDL_BENCH_AUDIT", "") == "1",
        help="run with the data-correctness audit layer on (RSDL_AUDIT): "
        "per-epoch exactly-once digest verdicts are embedded under "
        "\"audit\" in the result JSON (including on watchdog/error "
        "exits); forces the map/reduce loader unless RSDL_BENCH_RESIDENT "
        "is set explicitly (the resident loader bypasses the audited "
        "host pipeline)",
    )
    try:
        return parser.parse_args(argv)
    except SystemExit as exc:
        # argparse already printed usage to stderr; keep the one-JSON-line
        # stdout contract for genuine errors (--help exits 0, no JSON).
        if exc.code not in (0, None):
            print(
                json.dumps(
                    _error_result(
                        "unknown",
                        "bad command line: "
                        + " ".join(sys.argv[1:])[:200],
                    )
                ),
                flush=True,
            )
        raise


def main() -> None:
    args = _parse_args()
    if args.resume:
        # The suspend/resume leg: self-contained child drivers (own
        # runtimes, journals, audit spools), same one-JSON-line
        # contract; a non-zero exit marks a failed capture.
        try:
            result = run_resume_bench()
        except BaseException as exc:  # noqa: BLE001 — the JSON line matters
            import traceback

            traceback.print_exc(file=sys.stderr)
            result = {
                "metric": "Suspend/resume (driver SIGKILLed mid-window)",
                "plane": "resume",
                "unit": "s",
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }
        _ledger_append(result)
        print(json.dumps(result), flush=True)
        sys.exit(1 if "error" in result else 0)

    if args.plane == "service":
        # The two-concurrent-jobs service bench: self-contained (owns
        # its sessions, service registry, metrics) and the same
        # one-JSON-line contract; a non-zero exit marks a failed
        # capture for the CI lane's check.
        try:
            result = run_service_bench()
        except BaseException as exc:  # noqa: BLE001 — the JSON line matters
            import traceback

            traceback.print_exc(file=sys.stderr)
            result = {
                "metric": (
                    "Disaggregated shuffle service (two concurrent jobs)"
                ),
                "plane": "service",
                "unit": "s",
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }
        _ledger_append(result)
        print(json.dumps(result), flush=True)
        sys.exit(1 if "error" in result else 0)

    if args.plane == "planner":
        # The plan-compiler A/B bench: self-contained (owns its
        # sessions and the planner env knobs, restored on exit) and the
        # same one-JSON-line contract; a non-zero exit marks a failed
        # capture OR a planner that lost to hand-tuned/stock beyond the
        # acceptance bounds.
        try:
            result = run_planner_bench()
        except BaseException as exc:  # noqa: BLE001 — the JSON line matters
            import traceback

            traceback.print_exc(file=sys.stderr)
            result = {
                "metric": (
                    "Self-tuning plan compiler A/B "
                    "(planner vs hand vs stock)"
                ),
                "plane": "planner",
                "unit": "s",
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }
        _ledger_append(result)
        print(json.dumps(result), flush=True)
        sys.exit(1 if "error" in result else 0)

    if args.plane == "tcp":
        # The loopback two-host plane bench: self-contained (owns its
        # cluster, metrics, audit) and same one-JSON-line contract; a
        # non-zero exit marks a failed capture for the CI lane's check.
        try:
            result = run_tcp_plane_bench()
        except BaseException as exc:  # noqa: BLE001 — the JSON line matters
            import traceback

            traceback.print_exc(file=sys.stderr)
            result = {
                "metric": "Cross-host TCP plane (two-process loopback)",
                "plane": "tcp",
                "value": 0.0,
                "unit": "GB/s",
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }
        _ledger_append(result)
        print(json.dumps(result), flush=True)
        sys.exit(1 if "error" in result else 0)

    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

    metrics_out = args.metrics_out
    if args.trace_out:
        # Enable BEFORE any runtime bring-up so every spawned worker and
        # actor inherits the spool dir through the environment.
        spool = args.trace_out + ".spool"
        # Drop spool files left by a previous run with the same
        # --trace-out: flush appends and trace_export merges every
        # trace-*.jsonl it finds, so stale files would splice the old
        # run's spans (possibly under reused pids) into the new artifact.
        if os.path.isdir(spool):
            for fname in os.listdir(spool):
                if fname.startswith("trace-") and fname.endswith(".jsonl"):
                    try:
                        os.unlink(os.path.join(spool, fname))
                    except OSError:
                        pass
        telemetry.enable(spool_dir=spool)
        _metrics.enable()
        telemetry.set_process_name("bench-driver")
        telemetry.set_context(trial=0)
        if metrics_out is None:
            metrics_out = args.trace_out + ".metrics.json"
        _TELEMETRY_EXIT_PATHS[0] = args.trace_out
        _TELEMETRY_EXIT_PATHS[1] = metrics_out
    elif metrics_out:
        # --metrics-out alone is an explicit opt-in to the metrics half;
        # without this the guard below would silently skip the requested
        # artifact.
        _metrics.enable()
        _TELEMETRY_EXIT_PATHS[1] = metrics_out

    from ray_shuffling_data_loader_tpu.telemetry import audit as _audit

    if args.audit:
        # Enable BEFORE runtime bring-up so pool workers inherit the
        # audit env and spool their map/reduce digest records where the
        # driver's reconciler can fold them.
        spool = (
            args.trace_out + ".auditspool"
            if args.trace_out
            else tempfile.mkdtemp(prefix="rsdl-audit-")
        )
        _audit.enable(spool_dir=spool)
        # Metrics carry the audit.* counters; keep them on so the
        # verdict counters land in the snapshot artifacts too.
        _metrics.enable()
        if "RSDL_BENCH_RESIDENT" not in os.environ:
            _log(
                "audit mode: forcing the map/reduce loader "
                "(RSDL_BENCH_RESIDENT=off) — the device-resident loader "
                "bypasses the audited host shuffle pipeline"
            )
            os.environ["RSDL_BENCH_RESIDENT"] = "off"

    platform, num_chips = require_tpu()
    try:
        result = run_bench(platform, num_chips)
    finally:
        # Success or not: stop the sampler threads (run_bench only reaches
        # its own teardown on the straight-line path) and export the trace
        # — the trace of a failed run is the artifact that shows where it
        # died. A failure itself propagates: traceback on stderr, non-zero
        # exit, no result line.
        _stop_live_samplers()
        trace_path = trace_error = None
        if args.trace_out:
            try:
                trace_path = telemetry.trace_export(args.trace_out)
            except Exception as exc:
                trace_error = f"{type(exc).__name__}: {exc}"[:200]
                _log(f"trace export failed: {trace_error}")
    if trace_path:
        result["trace_out"] = trace_path
    if trace_error:
        result["trace_error"] = trace_error
    if args.audit:
        # The shuffle driver already reconciled at epoch end; embed the
        # per-epoch verdicts. Guarded like the other artifact exports.
        try:
            result["audit"] = _audit.summary()
        except Exception as exc:
            result["audit_error"] = f"{type(exc).__name__}: {exc}"[:200]
    if metrics_out and _metrics.enabled():
        try:
            result["metrics_out"] = _metrics.dump_json(metrics_out)
        except Exception as exc:
            result["metrics_error"] = f"{type(exc).__name__}: {exc}"[:200]
    if _metrics.enabled():
        # Embed the CLUSTER-aggregated final counters — worker map/reduce
        # counters spooled at task-done fold in here; the driver-local
        # snapshot alone would silently drop everything worker-side.
        # Straggler/event summaries first, so their gauges fold in too.
        try:
            from ray_shuffling_data_loader_tpu.telemetry import (
                export as _metrics_export,
            )

            _attach_obs_summaries(result)
            result["telemetry_final"] = _metrics_export.aggregate()
        except Exception as exc:
            result["telemetry_error"] = f"{type(exc).__name__}: {exc}"[:200]
    _attach_profile(result)
    _ledger_append(result)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
