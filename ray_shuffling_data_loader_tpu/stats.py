"""Shuffle/delivery statistics: model, collectors, and report writers.

Capability parity with the reference stats subsystem (``stats.py:24-699``):
a dataclass tree of per-trial/epoch/stage stats, an async collector actor
that shuffle tasks report timings to, a store-utilization sampler thread,
and ``process_stats`` writing trial-, epoch-, and consumer-timeline CSVs.

TPU-first differences:

* Store utilization comes from this runtime's session-scoped shared-memory
  store (:func:`~.runtime.store_stats`) instead of a raw gRPC probe into the
  raylet (reference ``stats.py:653-683``).
* Timings use ``timeit.default_timer`` wall-clock deltas reported by the
  tasks themselves, exactly like the reference (``shuffle.py:149-167``).
"""

from __future__ import annotations

import asyncio
import csv
import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Sequence

import numpy as np

from ray_shuffling_data_loader_tpu.telemetry import export as _export
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Stats model (reference stats.py:24-64)
# ---------------------------------------------------------------------------


def _agg(values: Sequence[float]) -> Dict[str, float]:
    if not values:
        return {"avg": 0.0, "std": 0.0, "max": 0.0, "min": 0.0}
    arr = np.asarray(values, dtype=np.float64)
    return {
        "avg": float(arr.mean()),
        "std": float(arr.std()),
        "max": float(arr.max()),
        "min": float(arr.min()),
    }


@dataclass
class ConsumeRecord:
    """One reducer-batch delivery (the consumer-timeline row, reference
    ``stats.py:591-602``)."""

    rank: int
    epoch: int
    time_since_epoch_start: float
    nbytes: int


@dataclass
class EpochStats:
    """Per-epoch stage timings (reference ``stats.py:33-52``)."""

    epoch: int
    start_time: float = 0.0
    duration: float = 0.0
    throttle_duration: float = 0.0  # epoch-window admission wait
    map_durations: List[float] = field(default_factory=list)
    map_read_durations: List[float] = field(default_factory=list)
    reduce_durations: List[float] = field(default_factory=list)
    consume_records: List[ConsumeRecord] = field(default_factory=list)
    # Stage windows: first task start -> last task done.
    map_stage_duration: float = 0.0
    reduce_stage_duration: float = 0.0

    def row(self, trial: int) -> Dict[str, float]:
        out = {
            "trial": trial,
            "epoch": self.epoch,
            "duration": self.duration,
            "throttle_duration": self.throttle_duration,
            "map_stage_duration": self.map_stage_duration,
            "reduce_stage_duration": self.reduce_stage_duration,
            "num_map_tasks": len(self.map_durations),
            "num_reduce_tasks": len(self.reduce_durations),
        }
        for k, v in _agg(self.map_durations).items():
            out[f"map_task_{k}"] = v
        for k, v in _agg(self.map_read_durations).items():
            out[f"map_read_{k}"] = v
        for k, v in _agg(self.reduce_durations).items():
            out[f"reduce_task_{k}"] = v
        for k, v in _agg(
            [c.time_since_epoch_start for c in self.consume_records]
        ).items():
            out[f"consume_time_{k}"] = v
        return out


@dataclass
class StoreSample:
    timestamp: float
    num_objects: int
    total_bytes: int
    # Portion of total_bytes living in the disk spill tier (0 when the
    # capacity budget was never exceeded).
    spill_bytes: int = 0


@dataclass
class TrialStats:
    """Whole-trial stats (reference ``stats.py:55-64``)."""

    trial: int = 0
    duration: float = 0.0
    num_rows: int = 0
    num_epochs: int = 0
    batch_size: int = 0
    num_trainers: int = 1
    # Workload configuration (leading reference trial-CSV columns,
    # reference ``stats.py:336-344``).
    num_files: int = 0
    num_row_groups_per_file: int = 0
    num_reducers: int = 0
    max_concurrent_epochs: int = 0
    epochs: List[EpochStats] = field(default_factory=list)
    # Sampled series are rings (only max/mean reductions read them): a
    # 1 Hz sampler on a long run must not grow the actor — and every
    # snapshot round-trip — without bound.
    store_samples: Deque[StoreSample] = field(
        default_factory=lambda: deque(maxlen=_metrics.MAX_TIMELINE_SAMPLES)
    )
    # Live-metrics snapshots ({"ts", "values"}) forwarded by the store
    # sampler when the telemetry metrics half is on — the same series
    # telemetry.metrics.dump_json() writes, so CSV stats and live metrics
    # share one source of truth.
    metrics_samples: Deque[Dict[str, Any]] = field(
        default_factory=lambda: deque(maxlen=_metrics.MAX_TIMELINE_SAMPLES)
    )
    # Per-epoch audit verdicts (telemetry.audit.reconcile forwards them
    # when RSDL_AUDIT is on): digest equality + shuffle-quality metrics.
    audit_epochs: List[Dict[str, Any]] = field(default_factory=list)

    # -- derived metrics (reference stats.py:396-401) -----------------------

    @property
    def row_throughput(self) -> float:
        return (
            self.num_epochs * self.num_rows / self.duration
            if self.duration
            else 0.0
        )

    @property
    def batch_throughput(self) -> float:
        return self.row_throughput / self.batch_size if self.batch_size else 0.0

    @property
    def per_trainer_batch_throughput(self) -> float:
        return self.batch_throughput / max(1, self.num_trainers)

    @property
    def max_store_bytes(self) -> int:
        return max((s.total_bytes for s in self.store_samples), default=0)

    @property
    def avg_store_bytes(self) -> float:
        if not self.store_samples:
            return 0.0
        return float(np.mean([s.total_bytes for s in self.store_samples]))

    @property
    def max_spill_bytes(self) -> int:
        return max((s.spill_bytes for s in self.store_samples), default=0)

    @property
    def max_shm_bytes(self) -> int:
        """Peak SHARED-MEMORY residency: total minus whatever had spilled
        at that sample — the number the capacity budget pins."""
        return max(
            (s.total_bytes - s.spill_bytes for s in self.store_samples),
            default=0,
        )

    def row(self) -> Dict[str, float]:
        """The trial-CSV row: the reference's exact fieldname set
        (reference ``stats.py:335-381``) followed by the spill-tier and
        audit columns."""
        out = {
            "num_files": self.num_files,
            "num_row_groups_per_file": self.num_row_groups_per_file,
            "num_reducers": self.num_reducers,
            "num_trainers": self.num_trainers,
            "num_epochs": self.num_epochs,
            "max_concurrent_epochs": self.max_concurrent_epochs,
            "trial": self.trial,
            "duration": self.duration,
            "num_rows": self.num_rows,
            "batch_size": self.batch_size,
            "row_throughput": self.row_throughput,
            "batch_throughput": self.batch_throughput,
            "batch_throughput_per_trainer": self.per_trainer_batch_throughput,
            "avg_object_store_utilization": self.avg_store_bytes,
            "max_object_store_utilization": self.max_store_bytes,
            # Spill-tier evidence (no reference analog — Ray OOMs where
            # this spills): peak shm residency vs peak bytes on disk.
            "max_store_shm_bytes": self.max_shm_bytes,
            "max_store_spill_bytes": self.max_spill_bytes,
        }

        def put_agg(name: str, values: Sequence[float]) -> None:
            for k, v in _agg(values).items():
                out[f"{k}_{name}"] = v

        put_agg("epoch_duration", [e.duration for e in self.epochs])
        put_agg(
            "map_stage_duration",
            [e.map_stage_duration for e in self.epochs],
        )
        put_agg(
            "reduce_stage_duration",
            [e.reduce_stage_duration for e in self.epochs],
        )
        put_agg(
            "consume_stage_duration",
            [
                max(
                    (c.time_since_epoch_start for c in e.consume_records),
                    default=0.0,
                )
                for e in self.epochs
            ],
        )
        put_agg(
            "map_task_duration",
            [d for e in self.epochs for d in e.map_durations],
        )
        put_agg(
            "read_duration",
            [d for e in self.epochs for d in e.map_read_durations],
        )
        put_agg(
            "reduce_task_duration",
            [d for e in self.epochs for d in e.reduce_durations],
        )
        put_agg(
            "time_to_consume",
            [
                c.time_since_epoch_start
                for e in self.epochs
                for c in e.consume_records
            ],
        )

        # Audit columns (empty-string/zero when auditing was off so the
        # trial CSV schema is stable either way): epochs whose digest
        # reconciliation passed, and the ones that failed, by id.
        out["audit_epochs_ok"] = sum(
            1 for v in self.audit_epochs if v.get("ok")
        )
        out["audit_mismatch_epochs"] = ";".join(
            str(v.get("epoch")) for v in self.audit_epochs
            if v.get("ok") is False
        )
        out["audit_rows_delivered"] = sum(
            int(v.get("rows_delivered") or 0) for v in self.audit_epochs
        )
        return out


# ---------------------------------------------------------------------------
# Collector actor (reference stats.py:72-255)
# ---------------------------------------------------------------------------


class TrialStatsCollector:
    """Collects per-stage timing reports from shuffle tasks.

    Run as a named runtime actor (``runtime.spawn_actor(TrialStatsCollector,
    ...)``); shuffle tasks hold a picklable handle and report via
    fire-and-forget ``call_oneway`` — the analog of the reference's
    zero-CPU async stats actor (``stats.py:209-255``).

    Stage windows are computed server-side from first-start / last-done
    wall-clock, using the collector's own clock so tasks on different
    workers need no clock agreement beyond this one process.
    """

    def __init__(
        self,
        num_epochs: int,
        num_maps_per_epoch: int,
        num_reduces_per_epoch: int,
        num_rows: int = 0,
        batch_size: int = 0,
        num_trainers: int = 1,
        trial: int = 0,
        num_row_groups_per_file: int = 0,
        max_concurrent_epochs: int = 0,
    ):
        self._num_maps = num_maps_per_epoch
        self._num_reduces = num_reduces_per_epoch
        self.stats = TrialStats(
            trial=trial,
            num_rows=num_rows,
            num_epochs=num_epochs,
            batch_size=batch_size,
            num_trainers=num_trainers,
            num_files=num_maps_per_epoch,
            num_row_groups_per_file=num_row_groups_per_file,
            num_reducers=num_reduces_per_epoch,
            max_concurrent_epochs=max_concurrent_epochs,
        )
        self._epochs: Dict[int, EpochStats] = {}
        self._map_started: Dict[int, int] = {}
        self._map_first_start: Dict[int, float] = {}
        self._reduce_first_start: Dict[int, float] = {}
        self._done = asyncio.Event()

    def _epoch(self, epoch: int) -> EpochStats:
        if epoch not in self._epochs:
            self._epochs[epoch] = EpochStats(epoch=epoch)
        return self._epochs[epoch]

    # -- producer-side hooks (called from shuffle tasks/driver) -------------

    def epoch_start(self, epoch: int) -> None:
        self._epoch(epoch).start_time = time.time()

    def epoch_throttle(self, epoch: int, duration: float) -> None:
        self._epoch(epoch).throttle_duration = duration

    def map_start(self, epoch: int) -> None:
        self._map_first_start.setdefault(epoch, time.time())

    def map_done(self, epoch: int, duration: float, read_duration: float) -> None:
        e = self._epoch(epoch)
        e.map_durations.append(duration)
        e.map_read_durations.append(read_duration)
        if len(e.map_durations) == self._num_maps:
            e.map_stage_duration = time.time() - self._map_first_start.get(
                epoch, e.start_time or time.time()
            )

    def reduce_start(self, epoch: int) -> None:
        self._reduce_first_start.setdefault(epoch, time.time())

    def reduce_done(self, epoch: int, duration: float) -> None:
        e = self._epoch(epoch)
        e.reduce_durations.append(duration)
        if len(e.reduce_durations) == self._num_reduces:
            e.reduce_stage_duration = time.time() - self._reduce_first_start.get(
                epoch, e.start_time or time.time()
            )
            if e.start_time:
                e.duration = time.time() - e.start_time

    def consume(self, rank: int, epoch: int, nbytes: int = 0) -> None:
        e = self._epoch(epoch)
        e.consume_records.append(
            ConsumeRecord(
                rank=rank,
                epoch=epoch,
                time_since_epoch_start=(
                    time.time() - e.start_time if e.start_time else 0.0
                ),
                nbytes=nbytes,
            )
        )

    def audit_epoch(self, epoch: int, verdict: Dict[str, Any]) -> None:
        """One epoch's audit verdict (fire-and-forget from the shuffle
        driver's reconciler) — joins the trial CSV via the audit_*
        columns and rides the stats snapshot for tools/audit_report.py."""
        self.stats.audit_epochs.append(dict(verdict))

    def metrics_sample(self, ts: float, values: Dict[str, float]) -> None:
        """One sampled live-metrics snapshot from the store sampler
        (fire-and-forget, like every other report; the deque's maxlen
        bounds the series)."""
        self.stats.metrics_samples.append({"ts": ts, "values": values})

    def store_sample(
        self, num_objects: int, total_bytes: int, spill_bytes: int = 0
    ) -> None:
        self.stats.store_samples.append(
            StoreSample(
                timestamp=time.time(),
                num_objects=num_objects,
                total_bytes=total_bytes,
                spill_bytes=spill_bytes,
            )
        )

    # -- completion ----------------------------------------------------------

    def trial_done(self, duration: float) -> None:
        self.stats.duration = duration
        self._done.set()

    def _counts_complete(self) -> bool:
        """All expected fire-and-forget reports have landed. trial_done and
        task reports arrive on different connections, so completion must be
        judged by count, not by trial_done ordering."""
        if len(self._epochs) < self.stats.num_epochs:
            return False
        for e in self._epochs.values():
            if (
                len(e.map_durations) < self._num_maps
                or len(e.reduce_durations) < self._num_reduces
                or len(e.consume_records) < self._num_reduces
            ):
                return False
        return True

    def snapshot(self) -> TrialStats:
        """Current stats without awaiting completion — for callers that
        drive consumption themselves and never send ``consume`` records,
        which ``get_stats`` would wait for."""
        self.stats.epochs = [self._epochs[e] for e in sorted(self._epochs)]
        return self.stats

    async def get_stats(self, timeout: Optional[float] = None) -> TrialStats:
        """Await trial completion — the done signal AND every per-task report
        (oneway frames from worker connections may trail ``trial_done``) —
        then return the full stats tree (the reference instead awaits its
        consume futures, ``stats.py:251-255``)."""

        async def _wait():
            await self._done.wait()
            while not self._counts_complete():
                await asyncio.sleep(0.02)

        await asyncio.wait_for(_wait(), timeout)
        self.stats.epochs = [self._epochs[e] for e in sorted(self._epochs)]
        return self.stats


# ---------------------------------------------------------------------------
# Store utilization sampler (reference stats.py:258-279, 686-699)
# ---------------------------------------------------------------------------


class ObjectStoreStatsCollector:
    """Context manager sampling shared-memory store utilization on a daemon
    thread every ``sample_period_s`` and reporting to the collector actor
    (or accumulating locally when ``collector`` is None).

    When the telemetry metrics half is on (``RSDL_METRICS=1``), this
    thread doubles as the live-metrics sampler: every period it sets the
    store gauges, takes a :func:`telemetry.metrics.global_snapshot`
    (local instruments + cross-process sources like the batch-queue
    actor's depths), appends it to the in-memory timeline that
    ``metrics.dump_json`` writes, forwards it to the collector actor
    (``metrics_sample``), and logs a human-readable progress line."""

    def __init__(self, collector=None, sample_period_s: float = 5.0):
        self._collector = collector
        self._period = sample_period_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.samples: List[StoreSample] = []

    def _sample_metrics(self, sample: StoreSample) -> None:
        reg = _metrics.registry
        reg.gauge("store.shm_bytes").set(
            sample.total_bytes - sample.spill_bytes
        )
        reg.gauge("store.spill_bytes").set(sample.spill_bytes)
        reg.gauge("store.objects").set(sample.num_objects)
        snap = _metrics.global_snapshot()
        _metrics.record_sample(snap, ts=sample.timestamp)
        if self._collector is not None:
            try:
                self._collector.call_oneway(
                    "metrics_sample", sample.timestamp, snap
                )
            except Exception:
                pass
        # Spool the driver's own registry each period so cross-process
        # aggregators (another process's /metrics endpoint, a post-crash
        # report) see a fresh driver source without asking it anything.
        _export.maybe_flush()
        logger.info(_metrics.progress_line(snap))

    def _loop(self):
        from ray_shuffling_data_loader_tpu import runtime

        while not self._stop.wait(self._period):
            try:
                s = runtime.store_stats()
            except Exception:
                continue
            sample = StoreSample(
                timestamp=time.time(),
                num_objects=s.num_objects,
                total_bytes=s.total_bytes,
                spill_bytes=getattr(s, "spill_bytes", 0),
            )
            self.samples.append(sample)
            if self._collector is not None:
                try:
                    self._collector.call_oneway(
                        "store_sample",
                        sample.num_objects,
                        sample.total_bytes,
                        sample.spill_bytes,
                    )
                except Exception:
                    pass
            if _metrics.enabled():
                try:
                    self._sample_metrics(sample)
                except Exception:
                    # Telemetry must never sink the sampler thread.
                    pass

    def __enter__(self):
        self._thread = threading.Thread(
            target=self._loop, name="store-stats", daemon=True
        )
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=2 * self._period)
        return False


# ---------------------------------------------------------------------------
# Report writers (reference stats.py:287-625)
# ---------------------------------------------------------------------------


from ray_shuffling_data_loader_tpu.utils import is_remote_path as _is_remote  # noqa: E402


def _write_rows(f, rows: List[Dict], write_header: bool) -> None:
    writer = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
    if write_header:
        writer.writeheader()
    writer.writerows(rows)


def _check_append_schema(header_line: str, rows: List[Dict], path: str) -> None:
    """Appending headerless rows under an OLD header silently shifts every
    value after a schema change — corrupted CSVs with no error. Refuse
    instead: the operator overwrites or picks a fresh stats dir."""
    existing = next(csv.reader([header_line])) if header_line.strip() else []
    current = list(rows[0].keys())
    if existing != current:
        diff = "existing header is empty"
        for i in range(max(len(existing), len(current))):
            a = existing[i] if i < len(existing) else "<missing>"
            b = current[i] if i < len(current) else "<missing>"
            if a != b:
                diff = f"first difference at column {i}: {a!r} vs {b!r}"
                break
        raise ValueError(
            f"cannot append to {path}: its header ({len(existing)} cols) "
            f"does not match the current stats schema ({len(current)} "
            f"cols; {diff}). The file predates a schema change — use "
            "overwrite_stats=True or a new stats dir."
        )


def _write_csv(path: str, rows: List[Dict], overwrite: bool) -> None:
    if not rows:
        return
    if _is_remote(path):
        # Remote artifact store (s3://, gs://, ...) via fsspec — parity
        # with the reference's s3 stats upload (``stats.py:316-334``).
        # Object stores have no append: emulate it by read-modify-write
        # (stats files are small; one rewrite per trial is fine).
        import fsspec

        fs, _ = fsspec.core.url_to_fs(path)
        exists = fs.exists(path)
        if overwrite or not exists:
            with fsspec.open(path, "w", newline="") as f:
                _write_rows(f, rows, write_header=True)
        else:
            with fsspec.open(path, "r", newline="") as f:
                existing = f.read()
            lines = existing.splitlines()
            _check_append_schema(lines[0] if lines else "", rows, path)
            with fsspec.open(path, "w", newline="") as f:
                f.write(existing)
                _write_rows(f, rows, write_header=False)
        return
    write_header = overwrite or not os.path.exists(path)
    if not write_header:
        with open(path, newline="") as f:
            _check_append_schema(f.readline(), rows, path)
    with open(path, "w" if overwrite else "a", newline="") as f:
        _write_rows(f, rows, write_header)


def process_stats(
    all_trial_stats: Sequence[TrialStats],
    stats_dir: str = ".",
    overwrite_stats: bool = True,
    trial_csv: str = "trial_stats.csv",
    epoch_csv: str = "epoch_stats.csv",
    consume_csv: str = "consume_timeline.csv",
) -> Dict[str, float]:
    """Aggregate trials into three CSV artifacts + a summary dict.

    The reference writes trial-level (~40 cols), epoch-level, and
    consumer-timeline CSVs locally or to s3 via fsspec
    (``stats.py:287-625``); here local filesystem (or any mounted path).
    Returns the cross-trial summary (mean/std duration + throughputs).
    """
    if not _is_remote(stats_dir):
        os.makedirs(stats_dir, exist_ok=True)
    trial_rows = [t.row() for t in all_trial_stats]
    epoch_rows = [
        e.row(t.trial) for t in all_trial_stats for e in t.epochs
    ]
    consume_rows = [
        {
            "trial": t.trial,
            "epoch": c.epoch,
            "rank": c.rank,
            "time_since_epoch_start": c.time_since_epoch_start,
            "nbytes": c.nbytes,
        }
        for t in all_trial_stats
        for e in t.epochs
        for c in e.consume_records
    ]
    _write_csv(os.path.join(stats_dir, trial_csv), trial_rows, overwrite_stats)
    _write_csv(os.path.join(stats_dir, epoch_csv), epoch_rows, overwrite_stats)
    _write_csv(
        os.path.join(stats_dir, consume_csv), consume_rows, overwrite_stats
    )

    durations = [t.duration for t in all_trial_stats]
    summary = {
        "num_trials": len(all_trial_stats),
        "duration_mean": float(np.mean(durations)) if durations else 0.0,
        "duration_std": float(np.std(durations)) if durations else 0.0,
        "row_throughput_mean": float(
            np.mean([t.row_throughput for t in all_trial_stats])
        )
        if all_trial_stats
        else 0.0,
        "batch_throughput_mean": float(
            np.mean([t.batch_throughput for t in all_trial_stats])
        )
        if all_trial_stats
        else 0.0,
    }
    return summary


# ---------------------------------------------------------------------------
# Human-readable helpers (reference stats.py:628-646)
# ---------------------------------------------------------------------------


def human_readable_big_num(num: float) -> str:
    for magnitude, suffix in ((12, "T"), (9, "B"), (6, "M"), (3, "K")):
        if abs(num) >= 10 ** magnitude:
            value = num / 10 ** magnitude
            return (
                f"{value:.0f}{suffix}"
                if value == int(value)
                else f"{value:.1f}{suffix}"
            )
    return f"{num:.0f}" if num == int(num) else f"{num:.1f}"


def human_readable_size(num: float, precision: int = 1) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(num) < 1024.0:
            return f"{num:.{precision}f} {unit}"
        num /= 1024.0
    return f"{num:.{precision}f} EiB"
