"""Per-epoch distributed map/reduce shuffle over Parquet.

Capability parity with the reference shuffle engine (``shuffle.py:51-219``),
re-designed columnar/TPU-first instead of DataFrame-at-a-time:

* **map** (one task per input file): decode Parquet straight to contiguous
  numpy columns via Arrow, draw a seeded random reducer assignment, and
  partition rows with a *single stable argsort + one gather per column*
  (the reference builds ``num_reducers`` boolean masks over a DataFrame —
  O(R·N) row scans, ``shuffle.py:156-161``). Partitions are published to the
  shared-memory store; only refs travel.
* **reduce** (one task per reducer): concatenate its partition from every
  mapper and apply a seeded full permutation — again one gather per column
  (the reference pays ``pd.concat`` + ``DataFrame.sample(frac=1)``,
  ``shuffle.py:192-194``). The output segment is column-contiguous and
  64-byte aligned: exactly the layout ``jax.device_put`` stages from, so
  the delivery layer never re-packs rows.
* **delivery**: reducer outputs are assigned to trainer ranks by contiguous
  split (reference ``np.array_split``, ``shuffle.py:125``) and pushed to the
  consumer *as each reducer finishes* (the reference enqueues Ray futures
  upfront and lets ``ray.wait`` block; here completed refs stream out, which
  is strictly earlier availability).
* **epoch pipelining**: ``shuffle`` admits epoch ``e`` only when the
  consumer's epoch window allows (``wait_until_ready``), then kicks off the
  epoch's tasks and moves on — up to ``max_concurrent_epochs`` epochs of
  shuffle work overlap training, throttled by consumer ``task_done`` acks
  (reference ``shuffle.py:72-79`` + ``batch_queue.py:395-418``).

Determinism: all randomness derives from ``np.random.SeedSequence(seed,
epoch, stage, index)``, so a given ``(seed, epoch)`` yields a reproducible
global permutation — a property the reference lacks (it uses the global
numpy RNG, ``shuffle.py:156,194``) and which the exactly-once tests rely on.
"""

from __future__ import annotations

import os
import threading
import time
import timeit
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_shuffling_data_loader_tpu import runtime, telemetry
from ray_shuffling_data_loader_tpu._lazy import lazy_module
from ray_shuffling_data_loader_tpu.runtime import ColumnBatch, ObjectRef
from ray_shuffling_data_loader_tpu.runtime import store as _store
from ray_shuffling_data_loader_tpu.runtime.retry import stage_policy
from ray_shuffling_data_loader_tpu.runtime.tasks import (
    TaskError,
    TaskFuture,
    wait,
)
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

# Gated planes (ISSUE 14 gate-integrity): resolved on first attribute
# access, never at import time — importing the shuffle engine must not
# execute a telemetry-plane or fault-plane module body.
_audit = lazy_module("ray_shuffling_data_loader_tpu.telemetry.audit")
_phases = lazy_module("ray_shuffling_data_loader_tpu.telemetry.phases")
_faults = lazy_module("ray_shuffling_data_loader_tpu.runtime.faults")
from ray_shuffling_data_loader_tpu.utils import (
    arrow_decode_threads,
    decode_rowgroup_threads,
    shuffle_plan_label,
    shuffle_plan_spec,
)


class StageFailedError(TaskError):
    """A shuffle stage task exhausted its bounded re-execution budget
    (``RSDL_STAGE_MAX_ATTEMPTS``, default 3) — the structured terminal
    error a poison task produces instead of retrying forever across
    hosts. Subclasses :class:`TaskError` so pre-existing ``except
    TaskError`` callers (and tests) keep working; the stage, epoch and
    attempts fields are for the caller (tests read them, nothing in the
    program does)."""

    def __init__(self, stage: str, epoch: int, attempts: int, message: str):
        super().__init__(message, error_type="StageFailedError")
        self.stage = stage
        self.epoch = epoch
        self.attempts = attempts

    def __reduce__(self):
        return (
            StageFailedError,
            (self.stage, self.epoch, self.attempts,
             self.args[0] if self.args else ""),
        )


def _count_recovery(name: str, **labels) -> None:
    """``recovery.*`` counter increment, metrics-gated and never raising
    into the data path. Each increment also lands in the structured
    event log (with the ambient epoch context) so the obs plane can
    answer *when* recovery work happened, not just how much."""
    _metrics.safe_inc(name, **labels)
    telemetry.emit_event("recovery", counter=name, **labels)


# ---------------------------------------------------------------------------
# Live trial status (the obs plane's shuffle provider)
# ---------------------------------------------------------------------------
# A driver-side view of the running trial(s) — which epochs are in
# flight, what schedule each runs, how far delivery has progressed —
# published to telemetry.obs_server's /status endpoint. The tracker is
# keyed per service-plane job (ISSUE 15): concurrent ``shuffle()``
# calls each own an entry instead of clobbering one global dict (the
# latent multi-job collision), and the eviction fence unions every
# running job's window. Single-job runs use one "_default" entry and
# see the exact historical shape. Updates are a handful per epoch
# (admission, schedule pick, one increment per delivered reducer,
# completion): noise next to the per-reducer RPC + store traffic, so
# the tracker stays on unconditionally; the obs_server registration
# (the only part with an import cost) happens only when RSDL_OBS_PORT
# is set.

_live_lock = threading.Lock()
_DEFAULT_JOB_KEY = "_default"
_live_jobs: Dict[str, Dict[str, object]] = {}
_MAX_ENDED_JOBS = 8  # ended entries kept for /status history


def _in_flight_of(status: Dict[str, object]) -> List[int]:
    return sorted(
        int(e)
        for e, st in (status.get("epochs") or {}).items()
        if st.get("state") not in ("done", "failed")
    )


def live_status() -> dict:
    """JSON-safe snapshot of the current (or last) trial's live state —
    the status provider ``shuffle()`` registers with
    :mod:`~.telemetry.obs_server` when the obs endpoint is on. With the
    service plane on and several jobs live, the top-level fields mirror
    the most recently started RUNNING job (compatibility with every
    single-job consumer), ``running`` is true while ANY job runs,
    ``in_flight_epochs`` is the union over running jobs (the eviction
    fence), and a ``jobs`` section carries every tracked job's view."""
    with _live_lock:
        jobs: Dict[str, Dict[str, object]] = {}
        for key, st in _live_jobs.items():
            top = {k: v for k, v in st.items() if k != "epochs"}
            top["epochs"] = {
                str(e): dict(es)
                for e, es in (st.get("epochs") or {}).items()
            }
            jobs[key] = top
    if not jobs:
        return {"epochs": {}, "in_flight_epochs": []}
    running = [k for k, st in jobs.items() if st.get("running")]

    def _started(key: str) -> float:
        return float(jobs[key].get("started_ts") or 0.0)

    primary = max(running or jobs, key=_started)
    out = dict(jobs[primary])
    for key in jobs:
        jobs[key]["in_flight_epochs"] = _in_flight_of(jobs[key])
    out["running"] = bool(running)
    out["in_flight_epochs"] = sorted(
        {
            e
            for key in (running or [primary])
            for e in jobs[key]["in_flight_epochs"]
        }
    )
    if len(jobs) > 1 or primary != _DEFAULT_JOB_KEY:
        out["jobs"] = jobs
    return out


def protected_epochs() -> set:
    """The eviction fence (ISSUE 10): epochs still inside the in-flight
    window — admitted but not yet fully delivered/consumed — whose
    segments the tiered evictor must not demote or drop. Derived from
    the same live tracker ``/status`` serves, so "in flight" here and
    on the obs plane can never disagree; with several service jobs live
    the fence is the UNION of their windows (two jobs both at epoch 0
    keep it fenced until both finish it). Between trials (or before the
    first) the set is empty: everything still resident is cold by
    definition and lineage-recoverable — an ended trial's epochs must
    not stay fenced forever just because delivery never marked them
    done (a failed run's epochs park in "running" otherwise)."""
    status = live_status()
    if not status.get("running"):
        return set()
    return set(status.get("in_flight_epochs") or [])


def _status_begin_trial(
    num_epochs: int,
    num_files: int,
    num_reducers: int,
    num_trainers: int,
    start_epoch: int,
    job: Optional[str] = None,
) -> None:
    key = job or _DEFAULT_JOB_KEY
    with _live_lock:
        if job is None:
            # Historical single-job semantics: a fresh trial owns the
            # whole tracker.
            _live_jobs.clear()
        else:
            ended = sorted(
                (k for k, st in _live_jobs.items() if not st.get("running")),
                key=lambda k: float(_live_jobs[k].get("ended_ts") or 0.0),
            )
            while len(ended) > _MAX_ENDED_JOBS:
                _live_jobs.pop(ended.pop(0), None)
        _live_jobs[key] = {
            "running": True,
            "job": key,
            "started_ts": time.time(),
            "num_epochs": num_epochs,
            "num_files": num_files,
            "num_reducers": num_reducers,
            "num_trainers": num_trainers,
            "start_epoch": start_epoch,
            "epochs": {},
        }


def _status_epoch(
    epoch: int,
    delivered_inc: int = 0,
    job: Optional[str] = None,
    **kv,
) -> None:
    key = job or _DEFAULT_JOB_KEY
    with _live_lock:
        status = _live_jobs.setdefault(key, {"epochs": {}})
        epochs = status.setdefault("epochs", {})
        st = epochs.setdefault(
            int(epoch), {"state": "pending", "delivered_reducers": 0}
        )
        if delivered_inc:
            st["delivered_reducers"] = (
                st.get("delivered_reducers", 0) + delivered_inc
            )
        st.update(kv)


def _status_end_trial(
    error: Optional[str] = None, job: Optional[str] = None
) -> None:
    key = job or _DEFAULT_JOB_KEY
    with _live_lock:
        status = _live_jobs.setdefault(key, {"epochs": {}})
        status["running"] = False
        status["ended_ts"] = time.time()
        if error is not None:
            status["error"] = error[:300]


def _ledger_record(
    status: str,
    duration_s: Optional[float] = None,
    error: Optional[str] = None,
    plan=None,
    job_id: Optional[str] = None,
    audit_verdicts=None,
) -> None:
    """Append this run's record to the durable run ledger
    (telemetry/runledger.py). Check-then-import keeps the plane
    zero-overhead with RSDL_RUN_LEDGER unset; a ledger failure never
    changes the run's outcome (this sits on the failure paths too)."""
    if not os.environ.get("RSDL_RUN_LEDGER"):
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import runledger

        runledger.record_run(
            status,
            duration_s=duration_s,
            error=error,
            plan_label=_label_of_plan(plan) if plan is not None else None,
            job_id=job_id,
            audit_verdicts=audit_verdicts,
        )
    except Exception:
        pass


class BatchConsumer:
    """Interface for consumers of shuffle outputs (reference
    ``shuffle.py:11-43``)."""

    def consume(self, rank: int, epoch: int, batches: List[ObjectRef]):
        """Consume the provided batches for the given trainer and epoch.

        Implementations MAY accept an optional ``seq`` keyword (the
        producing reducer's index): a journal-armed shuffle
        (``RSDL_JOURNAL``, runtime/journal.py) then tags each delivery
        so queue-backed consumers can drop an idempotent re-publish
        after a driver preemption. Consumers with the plain 3-arg
        signature keep working — they just keep the one-reducer
        re-delivery window on resume.
        """
        raise NotImplementedError

    def producer_done(self, rank: int, epoch: int):
        """All batches for (epoch, rank) have been produced."""
        raise NotImplementedError

    def wait_until_ready(self, epoch: int):
        """Block until the consumer can admit this epoch."""
        raise NotImplementedError

    def wait_until_all_epochs_done(self):
        """Block until every batch of every epoch has been consumed."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Map / reduce tasks (run in spawned pool workers; no JAX, no TPU)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Parquet decode plane (ISSUE 11): row-group execution plans, column
# pushdown, selective row-group reads
# ---------------------------------------------------------------------------
# Since PR 6 the decode stage is the single-core laggard: its
# parallelism was file-level only (one pq.read_table per mapper, all
# columns). The decode plan here splits a file into contiguous
# row-group ranges decoded concurrently (RSDL_DECODE_ROWGROUPS, fair-
# share threaded via utils.decode_rowgroup_threads) and assembled into
# ONE set of contiguous columns bit-identical to the single-shot read;
# a projection decodes only the columns the run can ever touch
# (pushdown), and a row-group selection decodes only the groups a
# reducer's rows live in (the RINAS-style selective schedule). Pruned
# rows/bytes are counted so the win is visible in /metrics.

_RG_META_LOCK = threading.Lock()
_RG_META_CACHE: Dict[str, Tuple[int, ...]] = {}


def _open_parquet_file(filename: str):
    """``(ParquetFile, fs, rel)`` for any local/URI dataset path."""
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu.utils import parquet_filesystem

    fs, rel = parquet_filesystem(filename)
    return pq.ParquetFile(rel, filesystem=fs, memory_map=fs is None), fs, rel


def file_row_group_sizes(filename: str) -> List[int]:
    """Per-row-group row counts from the Parquet footer, cached per
    process — the selective schedule plans against every file's footer
    each epoch, and dataset files are immutable for a run (the decode
    cache already depends on that)."""
    with _RG_META_LOCK:
        hit = _RG_META_CACHE.get(filename)
    if hit is not None:
        return list(hit)
    pf, _, _ = _open_parquet_file(filename)
    meta = pf.metadata
    sizes = tuple(
        int(meta.row_group(g).num_rows)
        for g in range(meta.num_row_groups)
    )
    with _RG_META_LOCK:
        _RG_META_CACHE[filename] = sizes
    return list(sizes)


def _np_dtype_of(field) -> Optional[np.dtype]:
    """The numpy dtype an Arrow schema field decodes to, or None when it
    has no fixed-width numeric equivalent (the parallel assembly path
    then declines to preallocate and falls back to single-shot). A
    ``fixed_size_list`` of a numeric type decodes to its element type,
    ``list_size`` numbers a row."""
    import pyarrow as pa

    typ = field.type
    if pa.types.is_fixed_size_list(typ):
        typ = typ.value_type
    try:
        dt = np.dtype(typ.to_pandas_dtype())
    except (TypeError, NotImplementedError):
        return None
    return dt if dt.kind in "fiub" else None


def _row_shape_of(field) -> Tuple[int, ...]:
    """``(width,)`` of a ``fixed_size_list`` field, ``()`` of any other."""
    import pyarrow as pa

    if pa.types.is_fixed_size_list(field.type):
        return (int(field.type.list_size),)
    return ()


def _column_to_numpy(col) -> np.ndarray:
    """One Arrow column as a contiguous array: ``[rows]``, or ``[rows,
    width]`` of a ``fixed_size_list`` column of a numeric type (a sample
    of more than one number a row: a token sequence). Every stage below
    moves a column by whole rows of ``arr[i]``, whatever their width."""
    import pyarrow as pa

    typ = col.type
    if pa.types.is_fixed_size_list(typ):
        if col.null_count:
            raise ValueError(
                f"a fixed_size_list column with null rows cannot be "
                f"decoded to [rows, {typ.list_size}]"
            )
        chunks = col.chunks if isinstance(col, pa.ChunkedArray) else [col]
        flat = [
            c.flatten().to_numpy(zero_copy_only=False) for c in chunks
        ] or [np.empty(0, typ.value_type.to_pandas_dtype())]
        arr = flat[0] if len(flat) == 1 else np.concatenate(flat)
        return np.ascontiguousarray(arr).reshape(-1, typ.list_size)
    return np.ascontiguousarray(col.to_numpy(zero_copy_only=False))


def _table_to_columns(table) -> Dict[str, np.ndarray]:
    return {
        name: _column_to_numpy(col)
        for name, col in zip(table.column_names, table.columns)
    }


def _note_pruned(schema, group_rows, sel_rows, proj, labels=None) -> None:
    """Pushdown/selection observability: rows skipped by the row-group
    selection and decoded-bytes avoided by both prunes (column widths at
    pre-narrowing decode width). ``labels`` is the caller's
    ``{schedule, plan}`` attribution (ISSUE 12) — without it a selective
    re-read and a materialized decode are indistinguishable in the
    aggregate. One cached boolean when metrics are off; never raises."""
    if not _metrics.enabled():
        return
    labels = labels or {}
    try:
        total_rows = int(sum(group_rows))
        proj_bytes = 0
        pruned_col_bytes = 0
        for i in range(len(schema.names)):
            field = schema.field(i)
            dt = _np_dtype_of(field)
            width = dt.itemsize if dt is not None else 8
            for w in _row_shape_of(field):
                width *= w
            if proj is not None and field.name not in proj:
                pruned_col_bytes += width
            else:
                proj_bytes += width
        rows_pruned = total_rows - int(sel_rows)
        bytes_pruned = (
            total_rows * pruned_col_bytes + rows_pruned * proj_bytes
        )
        if rows_pruned > 0:
            _metrics.safe_inc(
                "shuffle.decode_rows_pruned", float(rows_pruned), **labels
            )
        if bytes_pruned > 0:
            _metrics.safe_inc(
                "shuffle.decode_bytes_pruned", float(bytes_pruned),
                **labels,
            )
    except Exception:
        pass


def _decode_rowgroups_parallel(
    fs, rel, schema, sel, proj, threads
) -> Optional[Dict[str, np.ndarray]]:
    """Decode the ``sel`` row groups with the plan's threads striped
    across COLUMNS: each worker bulk-reads the whole selection for its
    column subset on its own ParquetFile (Arrow readers are not shared
    across threads; Arrow releases the GIL during decode) and converts
    with exactly the calls the single-shot path uses — bit-identity by
    construction, nulls and logical types included.

    Why columns and not row-group ranges: a range split must assemble
    each column contiguously across workers, and that copy is GIL-held
    and bandwidth-bound, serializing behind the decode; finer per-group
    reads that interleave copy with decode pay a scanner setup PER
    read_row_groups call. Column striping needs ONE read per worker
    and no cross-worker assembly at all. Row groups remain the plan's SELECTION axis (the selective
    schedule prunes them); columns are its parallel axis. Returns None
    for single-column files (nothing to stripe; the caller falls back
    to the bit-identical single-shot read)."""
    import pyarrow.parquet as pq

    names = list(proj) if proj is not None else list(schema.names)
    if len(names) < 2:
        return None
    threads = min(threads, len(names))
    parts = [names[k::threads] for k in range(threads)]
    results: Dict[str, np.ndarray] = {}
    errors: List[BaseException] = []

    def _work(cols: List[str]) -> None:
        try:
            pf = pq.ParquetFile(rel, filesystem=fs, memory_map=fs is None)
            table = pf.read_row_groups(
                list(sel), columns=cols, use_threads=False
            )
            # THE single-shot conversion (shared helper, so the
            # bit-identity-by-construction argument survives future
            # conversion changes); one dict op per worker: GIL-atomic.
            results.update(_table_to_columns(table))
        except BaseException as exc:  # surfaced to the caller below
            errors.append(exc)

    workers = [
        threading.Thread(target=_work, args=(p,), name="rsdl-decode-rg")
        for p in parts[1:]
    ]
    for w in workers:
        w.start()
    _work(parts[0])  # the caller's thread takes the first stripe
    for w in workers:
        w.join()
    if errors or set(results) != set(names):
        return None
    return {name: results[name] for name in names}


def read_parquet_columns(
    filename: str,
    columns: Optional[Sequence[str]] = None,
    use_threads: bool = False,
    row_groups: Optional[Sequence[int]] = None,
    rowgroup_threads: int = 1,
    prof=None,
    count_pruned: bool = True,
    metric_labels: Optional[Dict[str, str]] = None,
) -> ColumnBatch:
    """Decode a Parquet file to contiguous numpy columns (Arrow C++ decode
    stays on host CPUs, per SURVEY §2b). ``columns`` restricts the decode
    to a projection (None = all columns; pruned-bytes counters record
    what the projection avoided unless ``count_pruned=False`` marks an
    internal side read; a projected name the schema lacks raises,
    EXCEPT the audit key — auto-appended by :func:`_pushdown_columns`,
    and a keyless dataset must warn-and-skip, not fail the map).
    ``row_groups`` restricts it to a row-group selection in ascending
    order (the selective schedule's intra-file read); the result is
    bit-identical to decoding the whole file and slicing those groups
    out — for datasets whose decoded dtypes are selection-independent
    (Arrow promotes a null-bearing int64 group to float64, so a
    selection that skips every null group decodes a different dtype
    than the whole file; the selective schedule guards this loudly).

    ``rowgroup_threads > 1`` decodes the selected groups as a parallel
    execution plan (column-striped — see
    :func:`_decode_rowgroups_parallel` for why that beats range
    striping; each worker on its own reader, Arrow releasing the GIL),
    producing the same contiguous columns the single-shot read does —
    bit-identical, and any shortfall falls back to single-shot. Size it
    with :func:`~.utils.decode_rowgroup_threads` (the
    ``RSDL_DECODE_ROWGROUPS`` gate + fair-share logic).

    ``use_threads`` defaults OFF: parallelism here normally comes from
    the worker POOL (one mapper process per file), so Arrow's per-read
    thread pool only adds oversubscription — measured 5x slower with the
    default ``use_threads=True`` on a saturated host. Decode tasks that
    know their stage's concurrency pass
    :func:`~.utils.arrow_decode_threads`'s worker-local decision (which
    also caps Arrow's pool to the task's fair share of the host); it is
    ignored when a row-group plan runs (the plan owns its threads).
    ``memory_map`` only applies to local paths; URI inputs (gs://,
    s3://, memory://, ...) resolve through
    :func:`~.utils.parquet_filesystem` so pods can shuffle straight from
    object storage.

    ``prof``: a :func:`~.telemetry.phases.stage_profiler` — decode cost
    lands as the ``decode:io`` (open + footer) and ``decode:arrow``
    (decompress + decode + assembly) sub-phases.

    ``metric_labels``: the caller's ``{schedule, plan}`` attribution on
    ``shuffle.decode_rowgroups`` and the pruned counters (ISSUE 12) —
    decode amplification is per-(schedule, plan) in /metrics, so a
    selective re-read, a materialized decode, and an audit-key side
    read are distinguishable; None = unlabeled (direct/tool calls)."""
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu.utils import parquet_filesystem

    if prof is None:
        prof = _phases.stage_profiler("decode")
    simple = (
        columns is None and row_groups is None and rowgroup_threads <= 1
    )
    if simple:
        # The legacy single-shot whole-file read, untouched.
        with prof.phase("decode:arrow") as ph:
            fs, rel = parquet_filesystem(filename)
            table = pq.read_table(
                rel,
                columns=None,
                use_threads=use_threads,
                memory_map=fs is None,
                filesystem=fs,
            )
            cols = _table_to_columns(table)
            ph.add_bytes(sum(v.nbytes for v in cols.values()))
        return ColumnBatch(cols)
    with prof.phase("decode:io"):
        pf, fs, rel = _open_parquet_file(filename)
        meta = pf.metadata
        group_rows = [
            int(meta.row_group(g).num_rows)
            for g in range(meta.num_row_groups)
        ]
        schema = pf.schema_arrow
    proj = list(columns) if columns is not None else None
    if proj is not None:
        # Projected names the file's schema lacks: ONLY the audit key
        # is tolerated-and-skipped (it is auto-appended by
        # _pushdown_columns, and audit's contract on a keyless dataset
        # is warn-and-skip, not a map failure). Any other missing name
        # is a caller bug — a typo'd explicit projection must raise at
        # the decode site, exactly as pq.read_table always did, not
        # deliver a stream silently missing a feature.
        have = set(schema.names)
        missing = [c for c in proj if c not in have]
        if missing:
            tolerated = (
                {_audit.key_column_name()} if _audit.enabled() else set()
            )
            hard = [c for c in missing if c not in tolerated]
            if hard:
                raise ValueError(
                    f"projected columns not in {filename!r} schema: "
                    f"{hard}"
                )
            proj = [c for c in proj if c in have]
        if not proj:
            raise ValueError(
                f"projection selects no columns of {filename!r} "
                f"(requested {list(columns)!r})"
            )
    sel = (
        list(range(len(group_rows)))
        if row_groups is None
        else sorted(int(g) for g in row_groups)
    )
    sel_rows = sum(group_rows[g] for g in sel)
    if count_pruned:
        # ``count_pruned=False`` marks internal side reads (the
        # selective plan's audit-key-only decode) whose "pruned"
        # columns the run decodes elsewhere anyway — crediting them
        # would fabricate avoided work in the headline counter.
        _note_pruned(schema, group_rows, sel_rows, proj, metric_labels)
    _metrics.safe_inc(
        "shuffle.decode_rowgroups", float(len(sel)),
        **(metric_labels or {}),
    )
    with prof.phase("decode:arrow") as ph:
        cols = None
        if rowgroup_threads > 1 and sel:
            cols = _decode_rowgroups_parallel(
                fs, rel, schema, sel, proj, rowgroup_threads
            )
        if cols is None:
            if sel:
                table = pf.read_row_groups(
                    sel, columns=proj, use_threads=use_threads
                )
                cols = _table_to_columns(table)
            else:
                # Empty selection: schema-typed empty columns, so the
                # caller's concat/dtype logic never special-cases it.
                names = proj if proj is not None else list(schema.names)
                cols = {}
                for name in names:
                    field = schema.field(name)
                    dt = _np_dtype_of(field)
                    cols[name] = np.empty(
                        (0, *_row_shape_of(field)),
                        dt if dt is not None else np.int64,
                    )
        ph.add_bytes(sum(v.nbytes for v in cols.values()))
    return ColumnBatch(cols)


def narrowed_dtype(dtype) -> np.dtype:
    """The 32-bit dtype a column has after decode narrowing — the ONE
    definition of the narrowing policy (``_narrow_column`` applies it;
    ``resident._load_multiprocess`` predicts it from the schema)."""
    dtype = np.dtype(dtype)
    if dtype == np.int64:
        return np.dtype(np.int32)
    if dtype == np.float64:
        return np.dtype(np.float32)
    return dtype


def _narrow_column(name: str, v: np.ndarray) -> np.ndarray:
    """Cast a 64-bit column to 32 bits, REFUSING silent wraparound: an id
    outside int32 range would corrupt training data undetectably (floats
    merely lose precision, which the device path accepts by design).
    The C++ kernel fuses the range check into the cast (one pass instead
    of numpy's max + min + astype three)."""
    if v.dtype == np.int64:
        from ray_shuffling_data_loader_tpu import native

        out = native.narrow_i64_checked(v)
        if out is None:
            raise ValueError(
                f"narrow_to_32: column {name!r} has values outside int32 "
                "range; disable narrowing for this dataset"
            )
        return out
    if v.dtype == np.float64:
        return v.astype(np.float32)
    return v


def _map_seed(seed: int, epoch: int, file_index: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=(0, epoch, file_index))
    )

def _reduce_seed(seed: int, epoch: int, reducer: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=(1, epoch, reducer))
    )


def _group_owners(
    seed: int,
    epoch: int,
    file_index: int,
    group_sizes: Sequence[int],
    num_reducers: int,
    granularity: int,
) -> np.ndarray:
    """Per-row-group reducer owners under the BLOCK plan family
    (ISSUE 12): consecutive runs of ``granularity`` row groups form
    blocks, and blocks are dealt to reducers by a seeded permutation of
    a balanced round-robin multiset — per-file block counts differ by
    at most one across reducers, and the seeded start offset keeps the
    "one extra block" from always landing on the same low reducer
    indices across files. Every row of a group travels to the group's
    owner, which is what makes per-reducer row-group selections
    disjoint (each group decoded exactly once per epoch)."""
    rng = _map_seed(seed, epoch, file_index)
    n_groups = len(group_sizes)
    n_blocks = -(-n_groups // granularity) if n_groups else 0
    if n_blocks == 0:
        return np.empty(0, dtype=np.int64)
    owners = (
        np.arange(n_blocks, dtype=np.int64)
        + int(rng.integers(num_reducers))
    ) % num_reducers
    rng.shuffle(owners)
    return np.repeat(owners, granularity)[:n_groups]


def _label_of_plan(plan: Tuple[str, int]) -> str:
    """Metric-label value of a resolved plan spec (``rowwise`` /
    ``block:G``) — the worker-side twin of
    :func:`~.utils.shuffle_plan_label`, fed from the plan the DRIVER
    resolved rather than this process's env."""
    family, granularity = plan
    return family if family == "rowwise" else f"block:{granularity}"


def _file_assignment(
    seed: int,
    epoch: int,
    file_index: int,
    n: int,
    num_reducers: int,
    filename: Optional[str] = None,
    plan: Optional[Tuple[str, int]] = None,
) -> np.ndarray:
    """The seeded per-row reducer assignment for one file — THE plan,
    and its ONLY definition: :func:`shuffle_map`, :func:`shuffle_plan`,
    and the selective schedule all call it, so every schedule
    partitions the same rows to the same reducers by construction.

    The plan FAMILY is ``RSDL_SHUFFLE_PLAN`` (:func:`shuffle_plan_spec`
    — the one parser): rowwise draws each row's reducer independently;
    block expands :func:`_group_owners` over the file's footer
    row-group sizes (``filename`` required — the block plan is
    footer-metadata-driven, no data read), so a whole row group lands
    on one reducer and the selective schedule can prune for real.

    ``plan``: the resolved ``(family, granularity)`` spec. The DRIVER
    parses the env once per run and threads it through every stage
    task's arguments — pool workers inherit their env at spawn, so an
    env-only plan would silently split driver and worker onto different
    plan families whenever the env changed after ``runtime.init``
    (schedules would still agree with each other, but auto-selective
    would prune nothing and every label would lie). None = parse this
    process's env (direct callers/tools)."""
    family, granularity = plan if plan is not None else shuffle_plan_spec()
    if family == "rowwise":
        rng = _map_seed(seed, epoch, file_index)
        return rng.integers(num_reducers, size=n)
    if filename is None:
        raise ValueError(
            "block shuffle plan needs the source filename to read "
            "row-group sizes from the footer (caller bug: a schedule "
            "did not thread it through)"
        )
    sizes = np.asarray(file_row_group_sizes(filename), dtype=np.int64)
    if int(sizes.sum()) != int(n):
        raise ValueError(
            f"block shuffle plan: footer row count {int(sizes.sum())} "
            f"!= caller row count {n} for {filename!r} (stale decode "
            "cache or mutated dataset)"
        )
    owners = _group_owners(
        seed, epoch, file_index, sizes, num_reducers, granularity
    )
    return np.repeat(owners, sizes)


def plan_is_prunable(plan: Optional[Tuple[str, int]] = None) -> bool:
    """Can the plan family ever skip a row group for a reducer?
    Rowwise cannot (every group holds rows for every reducer whp);
    block plans can by construction. The
    ``RSDL_SELECTIVE_READS=auto`` gate keys on this. ``plan``: the
    resolved spec (None = parse this process's env — driver/tool
    callers only, same rule as :func:`_file_assignment`)."""
    family, _ = plan if plan is not None else shuffle_plan_spec()
    return family == "block"


def _plan_enabled() -> bool:
    """Is the self-tuning plan compiler on? Env checked *before* any
    import of the planner plane (``analysis.planner`` /
    ``runtime.plan`` stay dark — GATED_PLANES — when off)."""
    mode = (os.environ.get("RSDL_PLAN") or "").strip().lower()
    return mode in ("auto", "on", "1", "true")


def _clear_plan_state() -> None:
    """Drop the driver's current-plan registry entry at run end (after
    the ledger record that harvests it) so a later planner-off run in
    this process cannot inherit stale terms. sys.modules only — never
    the reason the plane loads."""
    import sys

    mod = sys.modules.get("ray_shuffling_data_loader_tpu.runtime.plan")
    if mod is not None:
        mod.set_current(None)


def _apply_task_knobs(knobs: Optional[dict]) -> None:
    """Apply driver-planned per-task knobs on stage-task entry.

    Only ``native_threads`` needs process-level application (the
    kernel wrappers read the process default); decode threads and
    window depth are consumed at their call sites from the same dict.
    Plain dict, not a ResolvedPlan — workers never import the planner
    plane."""
    if not knobs:
        return
    n = knobs.get("native_threads")
    if n is not None:
        from ray_shuffling_data_loader_tpu import native as _native

        _native.set_num_threads(int(n))


def _knob_decode_threads(knobs: Optional[dict], stage_tasks: int) -> int:
    """Decode row-group threads for this task: the driver-planned
    value when present, else the env fair-share rule
    (``decode_rowgroup_threads``). Planned values are threaded as
    arguments because worker env snapshots date from pool spawn."""
    if knobs and knobs.get("decode_rowgroup_threads") is not None:
        return max(1, int(knobs["decode_rowgroup_threads"]))
    return decode_rowgroup_threads(stage_tasks)


def shuffle_map(
    filename: str,
    file_index: int,
    num_reducers: int,
    epoch: int,
    seed: int,
    stats_collector=None,
    narrow_to_32: bool = False,
    cache_ref: Optional[ObjectRef] = None,
    publish_cache: bool = False,
    stage_tasks: int = 0,
    columns: Optional[Sequence[str]] = None,
    plan: Optional[Tuple[str, int]] = None,
    knobs: Optional[dict] = None,
):
    """Map stage: load one file, randomly partition its rows across reducers.

    ``plan``: the driver-resolved ``RSDL_SHUFFLE_PLAN`` spec (see
    :func:`_file_assignment` — threading it as an argument is what
    keeps every worker on the driver's plan family).

    Returns ``num_reducers`` store refs (reference ``shuffle_map`` returns
    ``num_returns=num_reducers`` object refs, ``shuffle.py:129-168``) —
    or, with ``publish_cache``, the tuple ``(refs, decoded_cache_ref)``.

    ``narrow_to_32`` casts 64-bit columns to 32-bit right after decode —
    one extra cheap pass here so the partition scatter, reduce
    concat+permute, store residency, and DCN fetches all move half the
    bytes. Integer columns are range-checked (a ValueError beats silent
    wraparound); float columns narrow lossily by design.

    ``columns``: the decode projection (column pushdown, ISSUE 11) —
    only these columns are ever decoded, partitioned, and delivered.
    The driver passes it only when the run's full touchable set is
    provably known (:func:`_pushdown_columns`); None = full decode.

    Decode caching (no reference analog — the reference re-decodes every
    file every epoch): with ``publish_cache`` the decoded (and narrowed)
    columns are also written once to the store and the ref returned;
    later epochs pass it back as ``cache_ref`` and partition straight
    from the mmapped segment, skipping Parquet decode entirely.
    """
    if _faults.enabled():
        _faults.fire("task.map", epoch=epoch, point="entry")
    if stats_collector is not None:
        stats_collector.call_oneway("map_start", epoch)
    start = timeit.default_timer()
    wall0 = time.time()
    ctx = runtime.ensure_initialized()
    _apply_task_knobs(knobs)
    prof = _phases.stage_profiler("map", epoch=epoch, file=file_index)
    if plan is None:
        plan = shuffle_plan_spec()
    new_cache_ref = None
    if cache_ref is not None:
        with prof.phase("window-fetch") as ph:
            batch = ctx.store.get_columns(cache_ref)
            ph.add_bytes(batch.nbytes)
    else:
        # Worker-side decode plan: row-group parallelism when the fair-
        # share gate grants this task threads (RSDL_DECODE_ROWGROUPS);
        # otherwise Arrow's per-read pool under the same fair-share rule
        # (utils.arrow_decode_threads; stage_tasks == files this epoch).
        # The two never stack — a row-group plan reads each range with
        # use_threads=False. The planner's value arrives via ``knobs``
        # (worker env snapshots date from pool spawn).
        rg_threads = _knob_decode_threads(knobs, stage_tasks or 1)
        use_threads = (
            rg_threads <= 1
            and stage_tasks > 0
            and arrow_decode_threads(stage_tasks)
        )
        batch = read_parquet_columns(
            filename,
            columns=columns,
            use_threads=use_threads,
            rowgroup_threads=rg_threads,
            prof=prof,
            metric_labels={
                "schedule": "mapreduce",
                "plan": _label_of_plan(plan),
            },
        )
        if narrow_to_32:
            with prof.phase("decode:narrow", nbytes=batch.nbytes):
                batch = ColumnBatch(
                    {
                        k: _narrow_column(k, v)
                        for k, v in batch.columns.items()
                    }
                )
        if publish_cache:
            # The cache is purely an optimization: a failed publish
            # (ENOSPC etc.) degrades to plain per-epoch decode — it must
            # never sink the run (claim_or_wait treats a None ref as
            # "decode yourself").
            with prof.phase("cache-publish", nbytes=batch.nbytes):
                try:
                    cache_pending = ctx.store.create_columns(
                        {
                            k: (v.shape, v.dtype)
                            for k, v in batch.columns.items()
                        },
                        # Cross-epoch shared tier (ISSUE 11): cache
                        # segments account under the ledger's "cache"
                        # tier so the evictor can see (and shed) them
                        # separately from epoch state.
                        ledger_tier=(
                            "cache"
                            if shared_decode_cache_enabled()
                            else None
                        ),
                    )
                    try:
                        for k, v in batch.columns.items():
                            np.copyto(cache_pending.columns[k], v)
                        new_cache_ref = cache_pending.seal()
                    finally:
                        cache_pending.abort()
                    del cache_pending
                except Exception:
                    new_cache_ref = None
    end_read = timeit.default_timer()

    # Any file size is legal, including n < num_reducers (some reducers
    # then get an empty partition) and n == 0 — the reference tolerates
    # every size too (reference ``shuffle.py:151-163``).
    n = batch.num_rows
    assignment = _file_assignment(
        seed, epoch, file_index, n, num_reducers, filename, plan
    )
    # Stable group-by-reducer: single-pass counting scatter per column via
    # the C++ kernel (one-argsort-then-gather fallback otherwise), written
    # DIRECTLY into one shared-memory segment; per-reducer partitions are
    # published as hardlinked row-window refs — this stage's only full data
    # pass (put_columns copy-out eliminated).
    from ray_shuffling_data_loader_tpu import native

    pending = ctx.store.create_columns(
        {k: (v.shape, v.dtype) for k, v in batch.columns.items()}
    )
    try:
        with prof.phase("partition-scatter", nbytes=batch.nbytes):
            _, offsets = native.group_rows_multi(
                batch.columns, assignment, num_reducers, out=pending.columns
            )
        with prof.phase("publish"):
            refs = pending.publish_slices(
                [
                    (int(offsets[i]), int(offsets[i + 1]))
                    for i in range(num_reducers)
                ]
            )
    finally:
        # Reclaims the tmpfs segment if anything above raised; no-op after
        # a successful publish.
        pending.abort()
    del pending  # drop writable views before readers map the segment
    if _audit.enabled():
        # Map-side coverage digest + per-reducer partition counts (the
        # source-file-entropy input) — counts come from the scatter's own
        # offsets, so the audit pays one key-column pass and nothing
        # else; nothing at all when RSDL_AUDIT is unset.
        _audit.record_map(
            epoch, file_index, batch.columns,
            per_reducer=np.diff(offsets),
        )
    del batch  # drop (possibly mmapped-cache) views before returning
    # Worker-sourced counters (obs plane): spooled at task-done by the
    # pool worker, summed across processes by the driver's aggregation —
    # one cached boolean each when metrics are off.
    _metrics.safe_inc("shuffle.map_tasks")
    _metrics.safe_inc("shuffle.map_rows", float(n))
    duration = timeit.default_timer() - start
    # Retroactive spans (record_span no-ops when tracing is off): the
    # whole map plus its decode sub-interval, on the worker's timeline.
    telemetry.record_span(
        "map:read", wall0, end_read - start, cat="shuffle",
        epoch=epoch, file=file_index, cached=cache_ref is not None,
    )
    telemetry.record_span(
        "map", wall0, duration, cat="shuffle",
        epoch=epoch, file=file_index, rows=n,
    )
    if stats_collector is not None:
        stats_collector.call_oneway(
            "map_done", epoch, duration, end_read - start
        )
    if _faults.enabled():
        # Exit-point crash: the partitions are already published (and the
        # audit digest recorded) — the retry's duplicate records are the
        # case the audit reconciler's dedup exists for.
        _faults.fire("task.map", epoch=epoch, point="exit")
    if publish_cache:
        return refs, new_cache_ref
    return refs


def shuffle_plan(
    file_index: int,
    num_reducers: int,
    epoch: int,
    seed: int,
    cache_ref: ObjectRef,
    stats_collector=None,
    filename: Optional[str] = None,
    plan: Optional[Tuple[str, int]] = None,
) -> List[ObjectRef]:
    """Index-only map stage for steady-state epochs (no reference analog —
    the reference re-partitions the full data every epoch,
    ``shuffle.py:151-163``).

    Draws the SAME seeded reducer assignment as :func:`shuffle_map` and
    stably groups row *indices* by reducer — column data is never touched.
    Returns ``num_reducers`` store refs over one ``{"idx"}`` segment whose
    windows are each reducer's within-file row indices in file order,
    exactly the rows (and order) the materialized map's partitions hold.

    ``filename``: the file's source path — required under a block plan
    (:func:`_file_assignment` reads row-group sizes from the footer;
    the cached segment alone cannot say where group boundaries fall).
    """
    if _faults.enabled():
        _faults.fire("task.map", epoch=epoch, point="entry")
    if stats_collector is not None:
        stats_collector.call_oneway("map_start", epoch)
    start = timeit.default_timer()
    wall0 = time.time()
    ctx = runtime.ensure_initialized()
    prof = _phases.stage_profiler("plan", epoch=epoch, file=file_index)
    cached = ctx.store.get_columns(cache_ref)
    n = cached.num_rows
    del cached  # header read only; drop the mmap view immediately
    end_read = timeit.default_timer()
    with prof.phase("plan", nbytes=8 * n):
        assignment = _file_assignment(
            seed, epoch, file_index, n, num_reducers, filename, plan
        )
        # Stable argsort groups indices by reducer preserving file order —
        # the same stable grouping native.group_rows_multi applies to data.
        order = np.argsort(assignment, kind="stable")
        counts = np.bincount(assignment, minlength=num_reducers)
    if _audit.enabled():
        # The index schedule never touches column data; the audit pays
        # one key-column read from the cached segment so the map side of
        # the digest equality exists for this schedule too (counts are
        # the plan's own bincount, not a recomputation).
        cached = ctx.store.get_columns(cache_ref)
        _audit.record_map(
            epoch, file_index, cached.columns, per_reducer=counts
        )
        del cached
    offsets = np.zeros(num_reducers + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    idx_dtype = np.int32 if n <= np.iinfo(np.int32).max else np.int64
    pending = ctx.store.create_columns({"idx": ((n,), np.dtype(idx_dtype))})
    try:
        with prof.phase("publish", nbytes=n * np.dtype(idx_dtype).itemsize):
            # One fused cast-copy straight into the segment view: the old
            # ``astype(...)`` built a full int32 intermediate that copyto
            # then copied AGAIN — a second full pass over the index data
            # (values fit idx_dtype by construction, so the narrowing
            # cast is exact).
            np.copyto(
                pending.columns["idx"], order, casting="same_kind"
            )
            refs = pending.publish_slices(
                [
                    (int(offsets[r]), int(offsets[r + 1]))
                    for r in range(num_reducers)
                ]
            )
    finally:
        pending.abort()
    del pending
    _metrics.safe_inc("shuffle.map_tasks")
    _metrics.safe_inc("shuffle.map_rows", float(n))
    duration = timeit.default_timer() - start
    telemetry.record_span(
        "map", wall0, duration, cat="shuffle",
        epoch=epoch, file=file_index, rows=n, schedule="index",
    )
    if stats_collector is not None:
        stats_collector.call_oneway(
            "map_done", epoch, duration, end_read - start
        )
    if _faults.enabled():
        _faults.fire("task.map", epoch=epoch, point="exit")
    return refs


def selective_reads_decision(
    plan: Optional[Tuple[str, int]] = None,
    planned: Optional[bool] = None,
) -> Tuple[bool, str]:
    """The ONE parser of ``RSDL_SELECTIVE_READS`` (default off):
    ``(engage, reason)`` for the RINAS-style selective schedule —
    per-reducer intra-file row-group selections derived from the seeded
    plan, no map materialization in the store at all.

    ``auto`` (ISSUE 12) engages only when the plan family is prunable
    (:func:`plan_is_prunable` — block plans): under a rowwise plan
    every reducer's selection covers every row group, so selective
    would silently re-read+decode each file ~R times; ``auto`` declines
    to the materialized path instead and says why — the reason string
    goes into the plan compiler's ``selective`` term
    (``analysis/planner.py``) and nowhere else. ``on`` is the operator
    forcing it
    regardless (the amplification is their call); anything else is
    off.

    ``plan``: the resolved spec. :func:`shuffle_epoch` passes the one
    the driver threads through the stage tasks, so the engage decision
    can never key on a different plan family than the assignment and
    the metric labels; None = parse this process's env (driver-side
    summaries/tools).

    ``planned``: the plan compiler's decision (ISSUE 20). Honored only
    when the env knob is *unset* — a set ``RSDL_SELECTIVE_READS`` is
    an operator pin that outranks the planner — and an engage still
    requires a prunable plan (the planner cannot force the ~R×
    amplification ``on`` accepts)."""
    plan = plan if plan is not None else shuffle_plan_spec()
    label = _label_of_plan(plan)
    mode = os.environ.get(
        "RSDL_SELECTIVE_READS", ""
    ).strip().lower()
    if mode == "" and planned is not None:
        if planned and plan_is_prunable(plan):
            return True, f"planned: engaged (plan={label})"
        if planned:
            return False, (
                "planned engage declined: plan "
                f"{label} is not prunable"
            )
        return False, "planned: off"
    if mode in ("1", "on", "true"):
        return True, f"forced on (plan={label})"
    if mode == "auto":
        if plan_is_prunable(plan):
            return True, (
                f"auto: plan {label} is prunable "
                "(disjoint per-reducer row-group selections)"
            )
        return False, (
            "auto declined: rowwise plan is not prunable — selective "
            "would re-read every row group ~R times; running the "
            "materialized schedule (set RSDL_SHUFFLE_PLAN=block to "
            "engage)"
        )
    return False, "off"


def shuffle_selective_plan(
    filename: str,
    file_index: int,
    num_reducers: int,
    epoch: int,
    seed: int,
    columns: Optional[Sequence[str]] = None,
    narrow_to_32: bool = False,
    stats_collector=None,
    plan: Optional[Tuple[str, int]] = None,
) -> List[int]:
    """Index-only map stage for the SELECTIVE schedule (RINAS,
    PAPERS.md): draws the seeded assignment over the file's footer row
    count — no data read, no store write — and returns the per-reducer
    row counts the driver needs for delivery offsets and device-direct
    packing. With audit on it additionally decodes JUST the audit key
    column (column pushdown at its most extreme) so the map side of the
    exactly-once digest exists for this schedule too."""
    if _faults.enabled():
        _faults.fire("task.map", epoch=epoch, point="entry")
    if stats_collector is not None:
        stats_collector.call_oneway("map_start", epoch)
    start = timeit.default_timer()
    wall0 = time.time()
    runtime.ensure_initialized()
    prof = _phases.stage_profiler("plan", epoch=epoch, file=file_index)
    if plan is None:
        plan = shuffle_plan_spec()
    with prof.phase("decode:io"):
        n = sum(file_row_group_sizes(filename))
    end_read = timeit.default_timer()
    with prof.phase("plan", nbytes=8 * n):
        assignment = _file_assignment(
            seed, epoch, file_index, n, num_reducers, filename, plan
        )
        counts = np.bincount(assignment, minlength=num_reducers)
    if _audit.enabled():
        key = _audit.key_column_name()
        try:
            # The key-only side read is labeled schedule=audit-key so
            # the data path's decode amplification stays attributable:
            # an audit sweep over every group is audit cost, not a
            # selective re-read.
            kb = read_parquet_columns(
                filename, columns=[key], prof=prof, count_pruned=False,
                metric_labels={
                    "schedule": "audit-key",
                    "plan": _label_of_plan(plan),
                },
            )
            # Digest what the data path DELIVERS: the reduce side
            # narrows before digesting, and float narrowing changes
            # the IEEE bits — an un-narrowed map digest would make
            # strict audit fail a correct run with a float key.
            cols = {
                k: (_narrow_column(k, v) if narrow_to_32 else v)
                for k, v in kb.columns.items()
            }
        except Exception:
            cols = {}  # no key column: audit warns once and skips
        _audit.record_map(epoch, file_index, cols, per_reducer=counts)
    _metrics.safe_inc("shuffle.map_tasks")
    _metrics.safe_inc("shuffle.map_rows", float(n))
    duration = timeit.default_timer() - start
    telemetry.record_span(
        "map", wall0, duration, cat="shuffle",
        epoch=epoch, file=file_index, rows=n, schedule="selective",
    )
    if stats_collector is not None:
        stats_collector.call_oneway(
            "map_done", epoch, duration, end_read - start
        )
    if _faults.enabled():
        _faults.fire("task.map", epoch=epoch, point="exit")
    return [int(c) for c in counts]


def selective_file_selection(
    filename: str,
    file_index: int,
    reduce_index: int,
    num_reducers: int,
    epoch: int,
    seed: int,
    plan: Optional[Tuple[str, int]] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """One file's selective-read plan for one reducer:
    ``(row_groups, positions)`` — which row groups hold this reducer's
    rows under the seeded plan, and where each row lands within the
    compact decode of just those groups (skipped groups collapse out).

    Derived from THE :func:`_file_assignment` seam, so the selection
    covers exactly the rows the materialized map would partition to
    this reducer; under a block plan the selections are additionally
    DISJOINT across reducers by construction — each group decodes
    exactly once per epoch instead of ~R times."""
    sizes = np.asarray(file_row_group_sizes(filename), dtype=np.int64)
    n = int(sizes.sum())
    assignment = _file_assignment(
        seed, epoch, file_index, n, num_reducers, filename, plan
    )
    # File-order positions of my rows — identical to the stable
    # grouping's reducer window (stable argsort preserves within-group
    # source order).
    mine = np.flatnonzero(assignment == reduce_index)
    offs = np.zeros(len(sizes) + 1, dtype=np.int64)
    np.cumsum(sizes, out=offs[1:])
    g_idx = np.searchsorted(offs, mine, side="right") - 1
    gsel = np.unique(g_idx)
    # Destination base of each SELECTED group in the compact decode
    # (skipped groups collapse out).
    base_of = np.zeros(len(sizes), dtype=np.int64)
    acc = 0
    for g in gsel:
        base_of[g] = acc
        acc += int(sizes[g])
    pos = base_of[g_idx] + (mine - offs[g_idx])
    return gsel, pos


def shuffle_selective_reduce(
    reduce_index: int,
    epoch: int,
    seed: int,
    filenames: List[str],
    num_reducers: int,
    narrow_to_32: bool = False,
    columns: Optional[Sequence[str]] = None,
    stats_collector=None,
    pack=None,
    plan: Optional[Tuple[str, int]] = None,
    knobs: Optional[dict] = None,
):
    """Reduce stage for the selective schedule: decode ONLY the row
    groups holding this reducer's rows (per-file selections derived
    from the seeded plan), gather them in file order, and apply the
    same seeded permutation as :func:`shuffle_reduce` — the output is
    bit-identical to the materialized reducer's, with no shuffle state
    in the store beyond the reducer outputs themselves (the RINAS
    property: an epoch is never fully materialized).

    Pruning by plan family (ISSUE 12): a row group is skipped only when
    this reducer drew NONE of its rows. Under the rowwise plan that
    almost never happens — every group holds rows for every reducer, so
    selections degrade to whole-file decode and the epoch re-reads each
    file ~R times. Under a BLOCK plan
    (``RSDL_SHUFFLE_PLAN=block[:G]``) whole row groups belong to one
    reducer, selections are disjoint by construction, and each group
    decodes exactly once per epoch — ``decode_rows_pruned`` engages for
    real. Each file decodes under the row-group plan
    (``RSDL_DECODE_ROWGROUPS``) and the column projection, so the three
    decode levers compose."""
    if _faults.enabled():
        _faults.fire("task.reduce", epoch=epoch, point="entry")
    if stats_collector is not None:
        stats_collector.call_oneway("reduce_start", epoch)
    start = timeit.default_timer()
    wall0 = time.time()
    ctx = runtime.ensure_initialized()
    _apply_task_knobs(knobs)
    prof = _phases.stage_profiler(
        "selective-reduce", epoch=epoch, reducer=reduce_index
    )
    if plan is None:
        plan = shuffle_plan_spec()
    from ray_shuffling_data_loader_tpu import native

    # Plan every file first (footers are process-cached): which row
    # groups hold my rows, and where each row lands within the compact
    # decoded selection (selective_file_selection — the same seeded
    # seam every schedule partitions with).
    sel_per_file: List[np.ndarray] = []
    pos_per_file: List[np.ndarray] = []
    counts: List[int] = []
    with prof.phase("plan"):
        for i, fname in enumerate(filenames):
            gsel, pos = selective_file_selection(
                fname, i, reduce_index, num_reducers, epoch, seed, plan
            )
            sel_per_file.append(gsel)
            pos_per_file.append(pos)
            counts.append(len(pos))
    dst_off = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=dst_off[1:])
    total = int(dst_off[-1])
    with prof.phase("permute", nbytes=8 * total):
        rng = _reduce_seed(seed, epoch, reduce_index)
        perm = rng.permutation(total)
    # Pass 1: per-file selective decode + near-sequential take into the
    # compact buffer (the same locality two-pass as the index schedule's
    # gather-reduce; pass 2 below permutes the dense result).
    rg_threads = _knob_decode_threads(knobs, num_reducers)
    compact: Optional[Dict[str, np.ndarray]] = None
    for i, fname in enumerate(filenames):
        batch = read_parquet_columns(
            fname,
            columns=columns,
            row_groups=[int(g) for g in sel_per_file[i]],
            rowgroup_threads=rg_threads,
            prof=prof,
            metric_labels={
                "schedule": "selective",
                "plan": _label_of_plan(plan),
            },
        )
        if narrow_to_32:
            with prof.phase("decode:narrow", nbytes=batch.nbytes):
                batch = ColumnBatch(
                    {
                        k: _narrow_column(k, v)
                        for k, v in batch.columns.items()
                    }
                )
        if compact is None:
            compact = {
                k: np.empty((total, *v.shape[1:]), v.dtype)
                for k, v in batch.columns.items()
            }
        else:
            # Selection-dependent dtype promotion (Arrow decodes a
            # null-bearing int64 group as float64; a selection that
            # skips the null groups doesn't) would silently corrupt
            # the gather below AND break stream identity with the
            # materialized path — refuse loudly. First-cut limitation,
            # documented in TUNING.md: the selective schedule needs
            # selection-independent decoded dtypes (null-free columns).
            for k, v in batch.columns.items():
                if k not in compact or v.dtype != compact[k].dtype:
                    earlier = (
                        str(compact[k].dtype) if k in compact else "absent"
                    )
                    raise ValueError(
                        "selective schedule: file "
                        f"{filenames[i]!r} decoded column {k!r} as "
                        f"{v.dtype} where an earlier file decoded "
                        f"{earlier} — selection-dependent dtype "
                        "promotion (nullable columns) is not supported; "
                        "run with RSDL_SELECTIVE_READS=off for this "
                        "dataset"
                    )
        lo, hi = int(dst_off[i]), int(dst_off[i + 1])
        if hi > lo:
            with prof.phase("gather") as ph:
                pos = pos_per_file[i]
                for k, v in batch.columns.items():
                    native.take(v, pos, out=compact[k][lo:hi])
                ph.add_bytes(
                    2 * sum(compact[k][lo:hi].nbytes for k in compact)
                )
        del batch
    if compact is None:
        compact = {}
    template = compact if compact else None
    packed_out = _packed_output(ctx.store, pack, total, template)
    pending = (
        ctx.store.create_columns(
            {
                k: ((total, *v.shape[1:]), v.dtype)
                for k, v in compact.items()
            }
        )
        if packed_out is None
        else None
    )
    try:
        with prof.phase("gather") as ph:
            if packed_out is not None:
                # Pass 2 writes straight into the batch-aligned device
                # layout — the permute IS the pack (ISSUE 8).
                for lo, hi, views in packed_out.chunks():
                    for k, dst in views.items():
                        native.take(compact[k], perm[lo:hi], out=dst)
            else:
                for k, dst in pending.columns.items():
                    native.take(compact[k], perm, out=dst)
            ph.add_bytes(2 * sum(v.nbytes for v in compact.values()))
        if _audit.enabled():
            if packed_out is not None:
                packed_out.record_audit(epoch, reduce_index)
            else:
                _audit.record_reduce(epoch, reduce_index, pending.columns)
        with prof.phase("publish"):
            out_ref = (
                packed_out.seal() if packed_out is not None
                else pending.seal()
            )
    finally:
        if pending is not None:
            pending.abort()
        if packed_out is not None:
            packed_out.abort()
    _metrics.safe_inc("shuffle.reduce_tasks")
    _metrics.safe_inc("shuffle.reduce_rows", float(total))
    duration = timeit.default_timer() - start
    telemetry.record_span(
        "reduce", wall0, duration, cat="shuffle",
        epoch=epoch, reducer=reduce_index, schedule="selective",
    )
    if stats_collector is not None:
        stats_collector.call_oneway("reduce_done", epoch, duration)
    if _faults.enabled():
        _faults.fire("task.reduce", epoch=epoch, point="exit")
    return out_ref


# ---------------------------------------------------------------------------
# Device-direct reducer output (ROADMAP 3 / ISSUE 8)
# ---------------------------------------------------------------------------
# When the consumer is the JAX staging path, it tells the shuffle its
# staging layout — the ordered 4-byte columns (features then label) and
# the training batch size B. The reduce stage then writes its permuted
# rows DIRECTLY into that layout: the rank stream's batch grid is fixed
# (batch k covers rank-stream rows [kB, (k+1)B)), so a reducer whose
# rows occupy rank-stream interval [start, start+total) splits into
#
#   head  — rows [start, ceil(start/B)·B): the tail of a batch the
#           previous reducer began (plain columnar remainder);
#   body  — the m whole batches inside the interval, emitted as ONE
#           packed segment of shape [m, n_cols, B] int32 (each batch a
#           contiguous [n_cols, B] block, float columns as bit
#           patterns) — exactly what one ``jax.device_put`` stages with
#           no host-side rebatch/pack copy;
#   tail  — the leftover rows carried into the next reducer's batch.
#
# The delivered row stream is bit-identical to the legacy columnar path
# (the grid is where the consumer's carry rebatcher cut anyway); only
# the straddling boundary batches (~1 per reducer) still take the
# host-copy path. One layout pass replaces reduce-then-rebatch-then-
# pack — the staged_gb ≈ 4.8x dataset_gb amplification every BENCH
# point showed.


class _PackedOutput:
    """Batch-aligned device-layout destination for one reduce task.

    Packs EVERY column of the reducer output — the consumer's requested
    staging columns first (the contiguous prefix one ``device_put``
    ships), any remaining dataset columns after — so the delivered
    stream keeps the same column set as the legacy path: boundary
    remainders concat cleanly with legacy segments in the consumer's
    carry buffer, and audit digests can fold any key column. A column of
    ``width`` numbers a row takes ``width`` slots of each batch's block
    (``runtime/store.py``: the packed layout)."""

    def __init__(self, store, layout: dict, start: int, total: int,
                 names: List[str], col_dtypes: Dict[str, "np.dtype"],
                 col_widths: Optional[Dict[str, int]] = None):
        self.B = B = int(layout["batch"])
        self.names = names = list(names)
        self.dtypes = [np.dtype(col_dtypes[n]) for n in names]
        self.widths = [int((col_widths or {}).get(n, 1)) for n in names]
        self.ncols = len(names)
        self.nslots = sum(self.widths)
        self.total = int(total)
        self.h = h = min(total, (-int(start)) % B)
        self.m = m = (total - h) // B
        self.t = total - h - m * B
        self._store = store
        self._pendings: list = []
        # Three sequential segment allocations: a failure on a later one
        # (ENOSPC, injected store.put fault) must reclaim the earlier
        # unpublished tmp files — no caller holds a reference to abort
        # until __init__ returns.
        try:
            self.head = self._remainder(h)
            if m:
                descriptor = {
                    "kind": _store.DEVICE_BATCH_KIND,
                    "batch": B,
                    "columns": names,
                    "dtypes": [d.str for d in self.dtypes],
                }
                if self.nslots != self.ncols:
                    # Only a stream with a wide column carries the key:
                    # a scalar stream's descriptor is what it was.
                    descriptor["widths"] = self.widths
                self.slots = _store.packed_slots(descriptor)
                self.body = store.create_columns(
                    {
                        _store.PACKED_COLUMN: (
                            (m, self.nslots, B), np.dtype(np.int32)
                        )
                    },
                    layout=descriptor,
                )
                self._pendings.append(self.body)
                self.mat = self.body.columns[_store.PACKED_COLUMN]
            else:  # pragma: no cover - engagement requires m >= 1
                self.body = None
                self.mat = None
            self.tail = self._remainder(self.t)
        except BaseException:
            self.abort()
            raise

    def _row_shape(self, i: int) -> Tuple[int, ...]:
        return () if self.widths[i] == 1 else (self.widths[i],)

    def _remainder(self, rows: int):
        if rows <= 0:
            return None
        p = self._store.create_columns(
            {
                n: ((rows, *self._row_shape(i)), d)
                for i, (n, d) in enumerate(zip(self.names, self.dtypes))
            }
        )
        self._pendings.append(p)
        return p

    def chunks(self):
        """``(lo, hi, {name: writable view})`` destinations in output-row
        order. Body views are a column's slots of the packed block
        bit-viewed back to the column dtype — a take/gather into them
        lands bytes already in staging layout."""
        if self.head is not None:
            yield 0, self.h, self.head.columns
        for b in range(self.m):
            lo = self.h + b * self.B
            views = {
                n: _store.packed_column_view(self.mat[b], at, w, dt)
                for n, dt, (at, w) in zip(self.names, self.dtypes, self.slots)
            }
            yield lo, lo + self.B, views
        if self.tail is not None:
            lo = self.h + self.m * self.B
            yield lo, self.total, self.tail.columns

    def scatter(self, dest: np.ndarray, cols) -> None:
        """Scatter rows whose reducer-output positions are ``dest`` (a
        slice of the inverted epoch permutation — unique indices by
        construction) from ``cols`` into head/body/tail. The overlapped
        reduce's placement op: the threaded scatter kernel releases the
        GIL, so window N packs on every core while windows N+1..N+depth
        are still in flight over DCN."""
        from ray_shuffling_data_loader_tpu import native

        B = self.B
        body_lo, body_hi = self.h, self.h + self.m * B

        def _sub(name, sel):
            src = cols[name]
            return src if sel is None else src[sel]

        if self.head is not None:
            mask = dest < body_lo
            if mask.any():
                sel = None if mask.all() else mask
                idx = dest if sel is None else dest[sel]
                for n in self.names:
                    native.scatter(_sub(n, sel), idx, self.head.columns[n])
        if self.m:
            mask = (dest >= body_lo) & (dest < body_hi)
            if mask.any():
                sel = None if mask.all() else mask
                rel = (dest if sel is None else dest[sel]) - body_lo
                # Flat packed position of logical row r for a one-slot
                # column at slot s:
                # (r // B) * (n_slots * B) + s * B + (r % B); the constant
                # s*B term rides as a base-offset view so ONE position
                # array serves every column through the same threaded
                # scatter kernel.
                pos = (rel // B) * (self.nslots * B) + rel % B
                flat = self.mat.reshape(-1)
                for n, (at, w) in zip(self.names, self.slots):
                    src = _sub(n, sel)
                    if src.dtype != np.int32:
                        src = src.view(np.int32)
                    if w == 1:
                        native.scatter(src, pos, flat[at * B:])
                        continue
                    # A wide column's rows lie whole inside their batch's
                    # slab: one scatter a batch this window reaches.
                    of_batch = rel // B
                    for b in np.unique(of_batch):
                        here = of_batch == b
                        native.scatter(
                            src[here], rel[here] % B,
                            _store.packed_column_view(
                                self.mat[b], at, w, np.int32
                            ),
                        )
        if self.tail is not None:
            mask = dest >= body_hi
            if mask.any():
                sel = None if mask.all() else mask
                idx = (dest if sel is None else dest[sel]) - body_hi
                for n in self.names:
                    native.scatter(_sub(n, sel), idx, self.tail.columns[n])

    def key_column(self, name: str) -> np.ndarray:
        """The logical values of one column across head+body+tail (the
        audit digest input; body planes flatten through one contiguous
        copy of just that column)."""
        i = self.names.index(name)
        dt = self.dtypes[i]
        pieces = []
        if self.head is not None:
            pieces.append(self.head.columns[name])
        if self.m:
            pieces.append(
                _store.packed_logical_column(self.mat, *self.slots[i], dt)
            )
        if self.tail is not None:
            pieces.append(self.tail.columns[name])
        if not pieces:
            return np.empty((0, *self._row_shape(i)), dt)
        return pieces[0] if len(pieces) == 1 else np.concatenate(pieces)

    def record_audit(self, epoch: int, reduce_index: int) -> None:
        key = _audit.key_column_name()
        cols = {key: self.key_column(key)} if key in self.names else {}
        _audit.record_reduce(epoch, reduce_index, cols)

    def seal(self) -> List[ObjectRef]:
        """Publish head/body/tail (skipping absent pieces) in delivery
        order."""
        refs = []
        for p in (self.head, self.body, self.tail):
            if p is not None:
                refs.append(p.seal())
        return refs

    def abort(self) -> None:
        for p in self._pendings:
            p.abort()


def _packed_output(store, pack, total: int, template) -> Optional[_PackedOutput]:
    """A :class:`_PackedOutput` when device-direct packing can engage for
    this reducer — the task got a layout, every reducer column holds
    4-byte numbers, one a row or a fixed ``width`` a row, with the
    requested columns present, and the interval
    holds at least one whole aligned batch — else None (the legacy
    columnar segment is emitted; refs are self-describing, so consumers
    handle a mixed stream)."""
    if pack is None or total <= 0 or template is None:
        return None
    start, layout = pack
    try:
        B = int(layout["batch"])
        req = list(layout["columns"])
    except (KeyError, TypeError, ValueError):
        return None
    if B <= 0 or not req:
        return None
    try:
        all_names = list(template)
    except TypeError:
        return None
    if any(n not in all_names for n in req):
        return None
    # Requested staging columns first (the device_put prefix), every
    # other reducer column after — the stream's column set matches the
    # legacy path exactly.
    names = req + [n for n in all_names if n not in req]
    col_dtypes: Dict[str, np.dtype] = {}
    col_widths: Dict[str, int] = {}
    for n in names:
        v = template[n]
        # One slot means one number a row: a list of one stays columnar.
        if v.dtype.itemsize != 4 or v.ndim > 2 or (
            v.ndim == 2 and v.shape[1] < 2
        ):
            return None
        col_dtypes[n] = v.dtype
        col_widths[n] = int(v.shape[1]) if v.ndim == 2 else 1
    h = min(total, (-int(start)) % B)
    if (total - h) // B < 1:
        return None
    return _PackedOutput(
        store, layout, start, total, names, col_dtypes, col_widths
    )


def shuffle_gather_reduce(
    reduce_index: int,
    epoch: int,
    seed: int,
    idx_refs: Sequence[ObjectRef],
    cache_refs: Sequence[ObjectRef],
    stats_collector=None,
    pack=None,
    knobs: Optional[dict] = None,
) -> ObjectRef:
    """Reduce stage for the index schedule: ONE sparse gather straight out
    of the cached decoded file segments, replacing the materialized path's
    two full data passes (map partition scatter + reduce concat-permute).

    Applies the SAME seeded permutation as :func:`shuffle_reduce` to the
    concatenated index windows, then gathers the permuted rows from the
    file caches in a single fused multi-source take — output is
    bit-identical to the materialized reducer's segment.
    """
    if _faults.enabled():
        _faults.fire("task.reduce", epoch=epoch, point="entry")
    if stats_collector is not None:
        stats_collector.call_oneway("reduce_start", epoch)
    start = timeit.default_timer()
    wall0 = time.time()
    ctx = runtime.ensure_initialized()
    _apply_task_knobs(knobs)
    prof = _phases.stage_profiler(
        "gather-reduce", epoch=epoch, reducer=reduce_index
    )
    caches: List[ColumnBatch] = []
    idx_parts: List[ColumnBatch] = []
    try:
        with prof.phase("window-fetch") as ph:
            caches = [ctx.store.get_columns(r) for r in cache_refs]
            idx_parts = [ctx.store.get_columns(r)["idx"] for r in idx_refs]
            ph.add_bytes(sum(ip.nbytes for ip in idx_parts))
        counts = [len(ip) for ip in idx_parts]
        dst_off = np.zeros(len(counts) + 1, dtype=np.int64)
        np.cumsum(counts, out=dst_off[1:])
        total = int(dst_off[-1])
        with prof.phase("permute", nbytes=8 * total):
            rng = _reduce_seed(seed, epoch, reduce_index)
            perm = rng.permutation(total)
        template = caches[0] if caches else None
        packed_out = _packed_output(ctx.store, pack, total, template)
        pending = (
            ctx.store.create_columns(
                {
                    k: ((total, *template[k].shape[1:]), template[k].dtype)
                    for k in (template or {})
                }
            )
            if packed_out is None
            else None
        )
        try:
            # Two locality-friendly passes instead of one fully-random
            # gather over the whole cached dataset: each plan window is
            # ASCENDING within its file (the plan's stable grouping), so
            # pass 1 is a per-file vectorized take with near-sequential,
            # prefetchable reads; pass 2 permutes the compact result — a
            # dense take over ~1/R of the data, which fits cache tiers a
            # full-cache random gather blows through (measured 2.2x).
            from ray_shuffling_data_loader_tpu import native

            gather_keys = (
                packed_out.names if packed_out is not None
                else list(template or {})
            )
            with prof.phase("gather") as ph:
                compact = {
                    k: np.empty(
                        (total, *template[k].shape[1:]), template[k].dtype
                    )
                    for k in gather_keys
                }
                for i, (idx_i, cache) in enumerate(zip(idx_parts, caches)):
                    lo, hi = int(dst_off[i]), int(dst_off[i + 1])
                    if hi > lo:
                        for k in gather_keys:
                            native.take(
                                cache[k], idx_i, out=compact[k][lo:hi]
                            )
                if packed_out is not None:
                    # Pass 2 writes straight into the batch-aligned
                    # device layout — the permute IS the pack.
                    for lo, hi, views in packed_out.chunks():
                        for k, dst in views.items():
                            native.take(compact[k], perm[lo:hi], out=dst)
                else:
                    for k, dst in pending.columns.items():
                        native.take(compact[k], perm, out=dst)
                ph.add_bytes(2 * sum(v.nbytes for v in compact.values()))
            if _audit.enabled():
                if packed_out is not None:
                    packed_out.record_audit(epoch, reduce_index)
                else:
                    _audit.record_reduce(
                        epoch, reduce_index, pending.columns
                    )
            with prof.phase("publish"):
                out_ref = (
                    packed_out.seal() if packed_out is not None
                    else pending.seal()
                )
        finally:
            if pending is not None:
                pending.abort()
            if packed_out is not None:
                packed_out.abort()
        del pending
    finally:
        # Drop mmap views before the driver can free/unlink; only the idx
        # windows' fetched copies are droppable — the file caches are
        # shared across epochs and must survive.
        del caches, idx_parts
        ctx.store.drop_cache(list(idx_refs))
    _metrics.safe_inc("shuffle.reduce_tasks")
    _metrics.safe_inc("shuffle.reduce_rows", float(total))
    duration = timeit.default_timer() - start
    telemetry.record_span(
        "reduce", wall0, duration, cat="shuffle",
        epoch=epoch, reducer=reduce_index, schedule="index",
    )
    if stats_collector is not None:
        stats_collector.call_oneway("reduce_done", epoch, duration)
    if _faults.enabled():
        _faults.fire("task.reduce", epoch=epoch, point="exit")
    return out_ref


def _ref_window_rows(ref) -> Optional[int]:
    """Row count of a window ref, or None when the ref covers a whole
    segment (row count unknowable without opening it)."""
    rows = getattr(ref, "rows", None)
    if rows is None:
        return None
    return int(rows[1]) - int(rows[0])


def _fetch_window_depth(knobs: Optional[dict] = None) -> int:
    """How many mapper-partition windows the overlapped reduce keeps in
    flight ahead of the gather (``RSDL_FETCH_WINDOW_DEPTH``, default 4 —
    measured flat from 2..8 on loopback, so the default leans small to
    bound peak cache residency at ``depth`` windows). A driver-planned
    depth arrives via ``knobs`` and wins (the env read would see the
    pool-spawn snapshot, not the plan)."""
    if knobs and knobs.get("fetch_window_depth") is not None:
        return max(1, int(knobs["fetch_window_depth"]))
    from ray_shuffling_data_loader_tpu.runtime.store import (
        fetch_window_depth,
    )

    return fetch_window_depth(default=4)


def _overlapped_reduce(
    store, part_refs, counts, reduce_index, epoch, seed, prof, pack=None,
    knobs=None,
):
    """Reduce-side fetch/gather overlap: prefetch mapper-partition
    windows N+1..N+depth over DCN while scattering window N into the
    output segment.

    The fused ``concat_take`` needs every partition materialized before
    the first gathered byte, so on a cluster the reduce used to sit idle
    for the whole serial window-fetch tail. Here the permutation is
    inverted once (``inv[perm] = arange``) so each window's destination
    rows are a contiguous slice of ``inv`` — window ``i``'s rows land at
    ``out[inv[off_i:off_i+1]]`` — and windows are consumed in arrival
    order of the pipeline while later fetches proceed on the store's
    prefetch threads. Output is bit-identical to the fused path
    (``out[j] = concat[perm[j]]`` both ways; tested). Read-ahead is a
    true sliding window: window ``i + depth`` is submitted only when
    window ``i`` is consumed (and each consumed window's cache dropped
    immediately), so peak fetched residency stays O(depth) windows — a
    bulk prefetch of the whole ref list would only cap fetch
    CONCURRENCY, and completed fetches would pile up to the full
    reducer input whenever DCN outpaces the gather.
    """
    from ray_shuffling_data_loader_tpu import native

    depth = _fetch_window_depth(knobs)
    store.prefetch(part_refs[:depth], max_parallel=depth)
    dst_off = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=dst_off[1:])
    total = int(dst_off[-1])
    with prof.phase("permute", nbytes=8 * total):
        rng = _reduce_seed(seed, epoch, reduce_index)
        perm = rng.permutation(total)
        inv = np.empty(total, dtype=np.int64)
        # Permutation inversion is itself a scatter; the threaded kernel
        # splits it by row range (numpy fallback: inv[perm] = arange).
        native.scatter(
            np.arange(total, dtype=np.int64), perm, inv
        )
    pending = None
    packed_out = None
    allocated = False
    try:
        for i, ref in enumerate(part_refs):
            if i + depth < len(part_refs):
                # Slide the read-ahead window: one new fetch per
                # consumed window keeps in-flight + landed-unconsumed
                # bounded by ``depth``.
                store.prefetch([part_refs[i + depth]])
            with prof.phase("window-fetch") as ph:
                part = store.get_columns(ref)
                ph.add_bytes(part.nbytes)
            if not allocated:
                allocated = True
                packed_out = _packed_output(store, pack, total, part)
                if packed_out is None:
                    pending = store.create_columns(
                        {
                            k: ((total, *part[k].shape[1:]), part[k].dtype)
                            for k in part
                        }
                    )
            lo, hi = int(dst_off[i]), int(dst_off[i + 1])
            if hi > lo:
                with prof.phase("gather", nbytes=2 * part.nbytes):
                    # Per-core ownership of the window's output rows: the
                    # threaded scatter kernel splits dest by row range, so
                    # window N's placement uses every core while windows
                    # N+1..N+depth are still in flight on the prefetch
                    # threads (the C call releases the GIL). dest is a
                    # permutation slice — unique indices by construction.
                    dest = inv[lo:hi]
                    if packed_out is not None:
                        # Device-direct: the window lands straight in the
                        # batch-aligned staging layout (head/body/tail).
                        packed_out.scatter(dest, part)
                    else:
                        for k, dst in pending.columns.items():
                            native.scatter(part[k], dest, dst)
            del part
            # This window is consumed; dropping its fetched copy now
            # bounds peak local residency at ~depth windows (drop_cache
            # no-ops for local refs; the authoritative copy survives, so
            # the task stays retryable).
            store.drop_cache([ref])
        if pending is None and packed_out is None:
            pending = store.create_columns({})
        if _audit.enabled():
            if packed_out is not None:
                packed_out.record_audit(epoch, reduce_index)
            else:
                _audit.record_reduce(epoch, reduce_index, pending.columns)
        with prof.phase("publish"):
            out_ref = (
                packed_out.seal() if packed_out is not None
                else pending.seal()
            )
    finally:
        if pending is not None:
            pending.abort()  # reclaims on failure; no-op after seal
        if packed_out is not None:
            packed_out.abort()
    return out_ref, total


def shuffle_reduce(
    reduce_index: int,
    epoch: int,
    seed: int,
    part_refs: Sequence[ObjectRef],
    stats_collector=None,
    pack=None,
    knobs: Optional[dict] = None,
) -> ObjectRef:
    """Reduce stage: concat this reducer's partition from every mapper and
    fully permute it (reference ``shuffle_reduce``, ``shuffle.py:171-200``).

    Frees the consumed mapper partitions (the Ray build gets this from
    distributed ref-counting GC).

    Cluster mode: when any input window lives on a remote host, the
    fetch/gather pipeline overlaps — see :func:`_overlapped_reduce`
    (``RSDL_REDUCE_FETCH_OVERLAP=auto|on|off``; ``auto`` engages only
    when a DCN fetch actually exists, so the single-host path keeps the
    fused native concat-take untouched).

    ``pack``: device-direct delivery — ``(rank_stream_start, layout)``
    from the driver makes the permute write straight into batch-aligned
    staging layout (see :class:`_PackedOutput`); the task then returns a
    short LIST of refs (head/body/tail) instead of one columnar ref.
    """
    if _faults.enabled():
        _faults.fire("task.reduce", epoch=epoch, point="entry")
    if stats_collector is not None:
        stats_collector.call_oneway("reduce_start", epoch)
    start = timeit.default_timer()
    wall0 = time.time()
    ctx = runtime.ensure_initialized()
    _apply_task_knobs(knobs)
    prof = _phases.stage_profiler(
        "reduce", epoch=epoch, reducer=reduce_index
    )
    parts: List[ColumnBatch] = []
    try:
        store = ctx.store
        counts = [_ref_window_rows(r) for r in part_refs]
        mode = os.environ.get(
            "RSDL_REDUCE_FETCH_OVERLAP", "auto"
        ).strip().lower()
        overlap = (
            mode not in ("off", "0", "false")
            and all(c is not None for c in counts)
            and (
                mode in ("on", "1", "true")
                # Auto engages only when a window would ACTUALLY ride
                # DCN right now — already-cached windows (a retried
                # reduce's first-attempt fetches) have no latency to
                # hide, and the fused native gather is faster.
                or any(store.needs_fetch(r) for r in part_refs)
            )
        )
        if overlap:
            out_ref, total_rows = _overlapped_reduce(
                store, part_refs, counts, reduce_index, epoch, seed, prof,
                pack=pack, knobs=knobs,
            )
        else:
            with prof.phase("window-fetch") as ph:
                parts = [store.get_columns(r) for r in part_refs]
                ph.add_bytes(sum(p.nbytes for p in parts))
            total_rows = sum(p.num_rows for p in parts)
            with prof.phase("permute", nbytes=8 * total_rows):
                rng = _reduce_seed(seed, epoch, reduce_index)
                perm = rng.permutation(total_rows)
            # Fused concat+permute straight out of the mmapped partitions
            # INTO the output segment — this stage's only full data pass
            # (put_columns copy-out eliminated).
            template = parts[0] if parts else None
            packed_out = _packed_output(ctx.store, pack, total_rows, template)
            pending = (
                ctx.store.create_columns(
                    {
                        k: (
                            (total_rows, *template[k].shape[1:]),
                            template[k].dtype,
                        )
                        for k in (template or {})
                    }
                )
                if packed_out is None
                else None
            )
            try:
                with prof.phase("gather") as ph:
                    if packed_out is not None:
                        # Device-direct: the SAME fused concat-take, cut
                        # at the rank stream's batch grid so each chunk
                        # gathers straight into its staging-layout
                        # destination — the permute IS the pack.
                        from ray_shuffling_data_loader_tpu import native

                        live = [p for p in parts if p.num_rows > 0]
                        col_parts = {
                            n: [p[n] for p in live]
                            for n in packed_out.names
                        }
                        moved = 0
                        for lo, hi, views in packed_out.chunks():
                            for n, dst in views.items():
                                native.take_multi(
                                    col_parts[n], perm[lo:hi], out=dst
                                )
                                moved += dst.nbytes
                        ph.add_bytes(2 * moved)
                    else:
                        ColumnBatch.concat_take(
                            parts, perm, out=pending.columns
                        )
                        ph.add_bytes(
                            2
                            * sum(
                                v.nbytes
                                for v in pending.columns.values()
                            )
                        )
                if _audit.enabled():
                    # Reduce-side digest of the permuted output, while the
                    # writable views are still alive.
                    if packed_out is not None:
                        packed_out.record_audit(epoch, reduce_index)
                    else:
                        _audit.record_reduce(
                            epoch, reduce_index, pending.columns
                        )
                with prof.phase("publish"):
                    out_ref = (
                        packed_out.seal() if packed_out is not None
                        else pending.seal()
                    )
            finally:
                if pending is not None:
                    pending.abort()  # reclaims on failure; no-op on seal
                if packed_out is not None:
                    packed_out.abort()
            del pending
    finally:
        # Input partitions are NOT freed here — the driver frees them after
        # the result lands (shuffle_epoch), which keeps this task retryable
        # on another host after an agent death. Only this host's DCN window
        # caches are dropped (authoritative copies survive) — in a finally
        # so a failed reduce does not leak its fetched windows in /dev/shm.
        del parts  # drop mmap views before unlinking
        ctx.store.drop_cache(list(part_refs))
    _metrics.safe_inc("shuffle.reduce_tasks")
    _metrics.safe_inc("shuffle.reduce_rows", float(total_rows))
    duration = timeit.default_timer() - start
    telemetry.record_span(
        "reduce", wall0, duration, cat="shuffle",
        epoch=epoch, reducer=reduce_index, schedule="mapreduce",
    )
    if stats_collector is not None:
        stats_collector.call_oneway("reduce_done", epoch, duration)
    if _faults.enabled():
        _faults.fire("task.reduce", epoch=epoch, point="exit")
    return out_ref


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


class _ResolvedMapResult:
    """A pre-resolved stand-in for a stage task's TaskFuture: lineage
    recovery registers one into :class:`_DecodeCache` when it
    regenerates a decode-cache segment synchronously, and the journal
    resume path (ISSUE 13) uses them to re-attach journaled map/reduce
    results to surviving store segments without re-executing the
    task."""

    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        return self._value


# ---------------------------------------------------------------------------
# Durable epoch-state plane (ISSUE 13): journal re-attach helpers
# ---------------------------------------------------------------------------
# Everything here is called only when a journal is armed (RSDL_JOURNAL /
# an explicit resume_from), so the lazy journal import inside never
# loads on a plain run — the zero-overhead contract.


def _journaled_refs(ref_dicts) -> Optional[list]:
    """Reconstructed store refs for one journaled stage result, when
    EVERY ref still resolves in the store (``store.exists``) — else
    None, and the caller re-executes the stage (lineage/full seeded
    re-execution; the delivered stream is identical either way)."""
    from ray_shuffling_data_loader_tpu.runtime import journal as _journal

    try:
        store = runtime.get_context().store
        refs = [_journal.ref_from_json(d) for d in ref_dicts]
        if refs and all(store.exists(r) for r in refs):
            return refs
    except Exception:
        pass
    return None


def _seed_decode_cache_from_journal(decode_cache, resume_state) -> None:
    """Re-attach journaled decode-cache segments on resume: the newest
    surviving cache ref per file is registered so resumed epochs skip
    Parquet decode (and the index schedule can re-engage). A dead
    segment is simply not seeded — the claim path re-decodes."""
    from ray_shuffling_data_loader_tpu.runtime import journal as _journal

    store = runtime.get_context().store
    best: Dict[int, ObjectRef] = {}
    for e in sorted(resume_state.epochs):
        for i, m in resume_state.epochs[e].maps.items():
            d = m.get("cache_ref")
            if not d:
                continue
            try:
                ref = _journal.ref_from_json(d)
                if store.exists(ref):
                    best[int(i)] = ref
            except Exception:
                continue
    for i, ref in best.items():
        decode_cache.register(i, _ResolvedMapResult((None, ref)))
        _metrics.safe_inc(
            "recovery.resume_refs_reattached", stage="decode-cache"
        )


def _iter_journaled_ref_dicts(resume_state):
    """Every journaled ref dict in a folded run state — map partition
    refs, decode-cache refs, and reduce outputs. The ONE traversal both
    sweep helpers below share, so a journal-record shape change cannot
    silently desynchronize them."""
    for st in resume_state.epochs.values():
        for m in st.maps.values():
            for d in m.get("refs") or []:
                yield d
            if m.get("cache_ref"):
                yield m["cache_ref"]
        for refs in st.reduces.values():
            for d in refs:
                yield d


def _free_superseded_refs(resume_state) -> None:
    """Reclaim a same-session (in-process) superseded attempt's
    leftovers at the end of the resumed run: journaled refs the resume
    did NOT re-attach (re-executed stages publish fresh objects, so the
    old segments have no other owner) would otherwise linger until
    session cleanup. Refs the run DID re-attach are already freed
    through the normal delivery / decode-cache paths by now
    (``store.free`` is a no-op on missing segments) — except those
    promoted into the shared decode-cache registry, which must outlive
    this run and are spared."""
    from ray_shuffling_data_loader_tpu.runtime import journal as _journal

    with _SHARED_CACHE_LOCK:
        keep = {ref.object_id for ref in _SHARED_CACHE.values()}
    ref_dicts: Dict[str, dict] = {
        d["id"]: d for d in _iter_journaled_ref_dicts(resume_state)
    }
    stale = [
        _journal.ref_from_json(d)
        for oid, d in ref_dicts.items()
        if oid not in keep
    ]
    if stale:
        try:
            runtime.get_context().store.free(stale)
            _metrics.safe_inc(
                "recovery.superseded_refs_freed", len(stale)
            )
        except Exception:
            pass


def _sweep_superseded(resume_state) -> None:
    """End-of-run reclamation of everything the preempted attempt(s)
    left behind. Dead sessions — the predecessor's, and any older ones
    whose refs were carried through a chain of preemptions — are swept
    whole by prefix (their creating drivers are gone, so reclamation
    falls to us and the capacity ledger's residency folds to zero),
    sparing segments promoted into the shared decode-cache tier, which
    must outlive the session that created them. A same-session
    (in-process) predecessor has no prefix of its own to sweep; its
    un-reattached journaled refs are freed individually instead."""
    store = runtime.get_context().store
    cur = store.session
    with _SHARED_CACHE_LOCK:
        spare = {ref.object_id for ref in _SHARED_CACHE.values()}
    sessions = {resume_state.identity.get("session")}
    sessions.update(
        d.get("session") for d in _iter_journaled_ref_dicts(resume_state)
    )
    for s in sessions:
        if s and s != cur:
            try:
                store.cleanup(session=s, keep=spare)
            except Exception:
                pass
    if resume_state.identity.get("session") == cur:
        _free_superseded_refs(resume_state)


# -- cross-epoch shared decode-cache tier (ISSUE 11) ------------------------
# The per-run _DecodeCache's segments used to die with the shuffle()
# call; with RSDL_DECODE_CACHE_SHARED on, resolved cache refs are
# promoted into this process-level registry keyed by CONTENT identity
# (file, projection, narrowing) so the next run over the same dataset
# starts cache-hot — epoch 0 goes straight to the index schedule, and
# two co-resident jobs on one driver share one decode (ISSUE 1's
# hot-dataset sharing foundation). Entries are validated against the
# store on every claim: a segment the evictor shed (ledger tier
# "cache") or a session cleanup reclaimed simply re-decodes — the
# registry can never hand out a dangling ref without the lineage
# machinery noticing (ObjectLostError → _recover_lost_cache).

_SHARED_CACHE_LOCK = threading.Lock()
_SHARED_CACHE: Dict[tuple, ObjectRef] = {}


def shared_decode_cache_enabled() -> bool:
    """The ONE parser of ``RSDL_DECODE_CACHE_SHARED`` (default off —
    the zero-overhead contract: unset means no registry entry, no
    ledger ``cache`` tier, per-run cache semantics untouched). Under
    the multi-job service plane (``RSDL_SERVICE``, ISSUE 15) the
    default flips ON — cross-job hot-dataset sharing is half the point
    of the service — while an explicit ``off`` still wins."""
    raw = os.environ.get("RSDL_DECODE_CACHE_SHARED", "").strip().lower()
    if raw in ("1", "on", "true", "auto"):
        return True
    if raw in ("0", "off", "false", "no"):
        return False
    if os.environ.get("RSDL_SERVICE"):
        try:
            from ray_shuffling_data_loader_tpu.runtime import (
                service as _service,
            )

            return _service.enabled()
        except Exception:
            return False
    return False


def _shared_cache_key(
    session: str,
    filename: str,
    columns: Optional[Sequence[str]],
    narrow: bool,
) -> tuple:
    """Content identity of one file's decoded columns: the store
    session (refs are session-scoped), the file, the projection, and
    the narrowing flag — a run with a different projection or
    narrowing must never read another run's cache."""
    path = filename if "://" in filename else os.path.abspath(filename)
    proj = None if columns is None else tuple(columns)
    return (session, path, proj, bool(narrow))


def shared_decode_cache_clear(free: bool = False) -> None:
    """Drop every shared-registry entry (tests / operators);
    ``free=True`` also frees the underlying segments."""
    with _SHARED_CACHE_LOCK:
        refs = list(_SHARED_CACHE.values())
        _SHARED_CACHE.clear()
    if free and refs:
        try:
            runtime.get_context().store.free(refs)
        except Exception:
            pass


class _DecodeCache:
    """Driver-side registry of per-file decoded-column cache refs.

    The FIRST epoch to submit a map for file ``i`` claims publishing; a
    later epoch's submission blocks on that map's future (same-file
    chaining only — its data cannot exist earlier anyway) and partitions
    from the cached segment instead of re-decoding Parquet.

    ``shared_keys`` (one content key per file, from
    :func:`_shared_cache_key`) arms the cross-epoch shared tier: claims
    consult the process-level registry before decoding, and resolved
    refs are promoted into it at run end instead of being freed.

    ``service_job`` (ISSUE 15) re-homes the shared tier onto the
    service plane's CONTENT-identity registry (``shared_keys`` are then
    :func:`~.runtime.service.cache_key` strings): lookups add a
    refcounted claim for the job (fencing the segment against the
    evictor while the job lives) and publishes land in the
    cross-process registry, so a second job over the same Parquet set
    is cache-hot from its first epoch.
    """

    def __init__(
        self,
        enabled: bool,
        shared_keys: Optional[list] = None,
        service_job=None,
    ):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._futs: dict = {}  # file index -> publishing map TaskFuture
        self._shared_keys = shared_keys
        self._service_job = service_job

    def _shared_get(self, index: int) -> Optional[ObjectRef]:
        """A still-live shared-tier ref for file ``index``, else None
        (stale entries — evicted or cleaned-up segments — are dropped
        so the caller re-decodes instead of chasing a dead ref)."""
        if self._shared_keys is None:
            return None
        key = self._shared_keys[index]
        if self._service_job is not None:
            from ray_shuffling_data_loader_tpu.runtime import (
                service as _service,
            )

            return _service.cache_lookup(key, job=self._service_job)
        with _SHARED_CACHE_LOCK:
            ref = _SHARED_CACHE.get(key)
        if ref is None:
            return None
        try:
            if runtime.get_context().store.exists(ref):
                return ref
        except Exception:
            pass
        with _SHARED_CACHE_LOCK:
            if _SHARED_CACHE.get(key) is ref:
                del _SHARED_CACHE[key]
        return None

    def _share(self, index: int, ref: ObjectRef) -> None:
        if self._shared_keys is None or ref is None:
            return
        if self._service_job is not None:
            from ray_shuffling_data_loader_tpu.runtime import (
                service as _service,
            )

            _service.cache_publish(
                self._shared_keys[index], ref, job=self._service_job
            )
            return
        with _SHARED_CACHE_LOCK:
            _SHARED_CACHE[self._shared_keys[index]] = ref

    def claim_or_wait(self, index: int):
        """Returns ``(cache_ref, publish)`` for file ``index``: a
        shared-tier hit short-circuits (cross-run cache-hot); else the
        first caller gets ``(None, True)`` and later callers block
        until the publisher's map resolves and get ``(ref, False)``. A
        publisher failure (its retry will have published nothing)
        degrades to plain decode."""
        if not self.enabled:
            return None, False
        ref = self._shared_get(index)
        if ref is not None:
            return ref, False
        with self._lock:
            fut = self._futs.get(index)
            if fut is None:
                return None, True
        try:
            _, ref = fut.result()
            return ref, False
        except Exception:
            return None, False

    def register(self, index: int, fut) -> None:
        with self._lock:
            self._futs[index] = fut

    def hot_refs(self, num_files: int) -> Optional[List[ObjectRef]]:
        """Every file's cache ref once all publishers have resolved (or
        the shared tier already holds them), else None. Blocks on
        in-flight publishing maps (an earlier epoch's — the data cannot
        exist sooner anyway); any missing/failed publish disqualifies
        the whole epoch from the index schedule, degrading to the
        materialized path."""
        if not self.enabled:
            return None
        refs = []
        for i in range(num_files):
            ref = self._shared_get(i)
            if ref is None:
                with self._lock:
                    fut = self._futs.get(i)
                if fut is None:
                    return None
                try:
                    _, ref = fut.result()
                except Exception:
                    return None
                if ref is None:
                    return None
                self._share(i, ref)
            refs.append(ref)
        return refs

    def free_all(self) -> None:
        """Run-end reclamation — or, with the shared tier armed,
        promotion: resolved cache refs outlive the run in the shared
        registry (the evictor and session cleanup own their
        lifetime)."""
        refs = []
        with self._lock:
            futs, self._futs = dict(self._futs), {}
        for index, fut in futs.items():
            try:
                _, ref = fut.result()
            except Exception:
                continue
            if ref is None:
                continue
            if self._shared_keys is not None:
                self._share(index, ref)
            else:
                refs.append(ref)
        if refs:
            try:
                runtime.get_context().store.free(refs)
            except Exception:
                pass


# Once-per-process microprobe results (a runtime measurement adapts to
# the host's shape where a baked constant cannot).
_PROBE_CACHE: Dict[str, object] = {}
_PROBE_LOCK = threading.Lock()


_PROBE_SMALL = 2 << 20  # cache-resident gather regime
_PROBE_LARGE = 64 << 20  # DRAM gather regime (exceeds any L2/L3)


def _probed_host_costs() -> Dict[str, float]:
    """Measured once per process (~200 ms): the host costs the schedule
    policy models with —

    * ``gather_small`` / ``gather_large`` — the index schedule's hot op
      (a random-permutation row gather via the same threaded
      :func:`native.take` the schedule executes, numpy fallback
      included) at a cache-resident and a DRAM-resident buffer size.
      Gather bandwidth is strongly size-dependent because a small
      cache gathers out of L2/L3; the policy
      interpolates by the dataset's actual cached size.
    * ``copy`` — the materialized path's hot op: a sequential pass
      through the SAME threaded kernel (``take`` with sorted indices),
      so both figures scale with however well this host actually
      threads, instead of guessing from a core count.
    * ``roundtrip`` — publish+fetch+free seconds for one tiny object
      through the shared-memory store: the per-object control cost the
      materialized path pays ``num_files x num_reducers`` times per
      epoch (its partition matrix) and the index schedule pays only
      ``O(num_files + num_reducers)`` times.

    ``np.arange`` (not zeros) defeats COW zero-pages, which would let
    "reads" hit one physical page. ``RSDL_HOST_PROBE=off`` skips
    measurement and returns conservative 1-vCPU-shaped figures."""
    with _PROBE_LOCK:
        hit = _PROBE_CACHE.get("costs")
        if hit is not None:
            return hit
        if os.environ.get("RSDL_HOST_PROBE", "").lower() in ("off", "0"):
            costs = {
                "gather_small": 2.4e9,
                "gather_large": 0.5e9,
                "copy": 3.5e9,
                "roundtrip": 1e-3,
            }
            _PROBE_CACHE["costs"] = costs
            return costs
        from ray_shuffling_data_loader_tpu import native

        rng = np.random.default_rng(0)

        def gather_bps(nbytes: int) -> float:
            rows = nbytes // 8
            buf = np.arange(rows, dtype=np.int64)
            idx = rng.permutation(rows).astype(np.int64)
            native.take(buf, idx[: 1 << 14])  # warm the lib/threads
            t0 = time.perf_counter()
            native.take(buf, idx)
            return buf.nbytes / max(1e-9, time.perf_counter() - t0)

        g_small = gather_bps(_PROBE_SMALL)
        g_large = gather_bps(_PROBE_LARGE)
        rows = _PROBE_LARGE // 8
        buf = np.arange(rows, dtype=np.int64)
        seq = np.arange(rows, dtype=np.int64)
        t0 = time.perf_counter()
        native.take(buf, seq)
        copy = (2 * buf.nbytes) / max(1e-9, time.perf_counter() - t0)
        roundtrip = 1e-3
        try:
            store = runtime.get_context().store
            tiny = {"x": np.zeros(16, np.int64)}
            store.free([store.put_columns(tiny)])  # warm
            t0 = time.perf_counter()
            ref = store.put_columns(tiny)
            store.get_columns(ref)
            store.free([ref])
            roundtrip = max(1e-5, time.perf_counter() - t0)
        except Exception:
            pass  # no runtime yet: keep the conservative default
        costs = {
            "gather_small": float(g_small),
            "gather_large": float(g_large),
            "copy": float(copy),
            "roundtrip": float(roundtrip),
        }
        _PROBE_CACHE["costs"] = costs
        return costs


def _gather_bw_for(cache_bytes: float) -> float:
    """Gather bandwidth at the dataset's cached size: the small probe
    figure below the small probe size, the large figure above the large
    one, log-linear in between (locality decays smoothly with working
    set)."""
    c = _probed_host_costs()
    lo, hi = float(_PROBE_SMALL), float(_PROBE_LARGE)
    if cache_bytes <= lo:
        return c["gather_small"]
    if cache_bytes >= hi:
        return c["gather_large"]
    frac = (np.log(cache_bytes) - np.log(lo)) / (np.log(hi) - np.log(lo))
    return float(
        np.exp(
            (1 - frac) * np.log(c["gather_small"])
            + frac * np.log(c["gather_large"])
        )
    )


def _dataset_stats_task(
    filenames: List[str],
    narrow_to_32: bool,
    columns: Optional[Sequence[str]] = None,
) -> Tuple[float, int]:
    """Runs IN A POOL WORKER: ``(decoded_bytes_per_row, total_rows)``
    for a dataset — bytes/row from a <=65k-row decoded sample of the
    first file (the schema is uniform across a dataset; narrowing
    applies :func:`narrowed_dtype` per column), total rows from every
    file's footer. ``columns`` restricts the bytes/row sum to the
    active decode projection — under pushdown the decoded footprint is
    only the projected columns, and estimating the full schema would
    mis-size the store budget (decline the cache / index schedule for
    data that will never be decoded). Worker placement is deliberate:
    pyarrow opens on the shuffle DRIVER thread segfaulted (pyarrow 25,
    observed r4 in-process after unrelated earlier runs), while worker
    processes decode Parquet all day — this rides the battle-tested
    path."""
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu.utils import parquet_filesystem

    def _pf(path):
        fs, rel = parquet_filesystem(path)
        return pq.ParquetFile(rel, filesystem=fs)

    pf = _pf(filenames[0])
    per_row = 0.0
    wanted = None if columns is None else set(columns)
    for batch in pf.iter_batches(batch_size=1 << 16):
        if batch.num_rows == 0:
            continue
        for col in batch.schema:
            if wanted is not None and col.name not in wanted:
                continue
            dt = _np_dtype_of(col) or np.dtype(col.type.to_pandas_dtype())
            if narrow_to_32:
                dt = narrowed_dtype(dt)
            per_row += float(
                dt.itemsize * int(np.prod(_row_shape_of(col), dtype=np.int64))
            )
        break  # one bounded sample batch: fixed-width schema
    if per_row == 0.0:
        raise OSError(f"empty sample from {filenames[0]}")
    total_rows = pf.metadata.num_rows
    total_rows += sum(_pf(f).metadata.num_rows for f in filenames[1:])
    return per_row, int(total_rows)


def _est_decoded_bytes(
    filenames: List[str],
    narrow_to_32: bool,
    columns: Optional[Sequence[str]] = None,
) -> float:
    """Estimated decoded-columns footprint of the dataset: measured
    bytes/row (decode microprobe on the first file — the schema is
    uniform across a dataset) x total rows from Parquet footers, plus
    15% planning headroom. Falls back to fitted on-disk expansion
    factors (1.3x un-narrowed / 0.7x narrowed, headroom included) if the
    footer sweep fails where plain getsize would work. Raises OSError
    (callers treat that as "unknown: decline")."""
    if not filenames:
        return 0.0
    key = (
        "est", tuple(filenames), narrow_to_32,
        None if columns is None else tuple(columns),
    )
    with _PROBE_LOCK:
        if key in _PROBE_CACHE:
            return _PROBE_CACHE[key]
    try:
        per_row, total_rows = runtime.get_context().scheduler.submit(
            _dataset_stats_task, list(filenames), narrow_to_32,
            list(columns) if columns is not None else None,
        ).result()
        est = per_row * total_rows * 1.15
    except Exception:
        # Any probe/footer failure falls back to the fitted on-disk
        # expansion factors; only getsize itself failing raises
        # OSError (the pre-probe "unknown: decline" contract).
        factor = 0.7 if narrow_to_32 else 1.3
        est = sum(os.path.getsize(f) for f in filenames) * factor
    with _PROBE_LOCK:
        _PROBE_CACHE[key] = est
    return est


def _decode_cache_auto(
    filenames: List[str],
    num_epochs: int,
    narrow_to_32: bool = False,
    columns: Optional[Sequence[str]] = None,
) -> bool:
    """Auto policy: cache when more than one epoch will read the files AND
    the (estimated) decoded size fits comfortably inside the store's
    capacity budget alongside ~2 epochs of in-flight shuffle state.

    Sizing comes from :func:`_est_decoded_bytes`; a wrong guess only
    shifts segments into the spill tier rather than breaking anything.
    When the budget is unknowable (``capacity_bytes`` None —
    budgeting disabled, statvfs failure, or spill dir on the same
    tmpfs), there IS no spill tier to absorb a wrong guess, so auto
    stays off."""
    if num_epochs < 2:
        return False
    try:
        est = _est_decoded_bytes(filenames, narrow_to_32, columns)
    except OSError:
        return False
    cap = runtime.get_context().store.capacity_bytes
    if cap is None:
        return False
    return est < 0.35 * cap


def _index_schedule_allowed(
    filenames: List[str],
    num_reducers: int,
    narrow_to_32: bool,
    columns: Optional[Sequence[str]] = None,
) -> bool:
    """Policy for the index-only steady-state schedule. ``auto`` (default)
    weighs its read amplification: every gather reads ~the ENTIRE cached
    dataset (a 1/R row subset still touches every cache line), so one
    epoch's gathers read ``R x cache_bytes`` where the materialized path
    reads ~3x cache_bytes total. Which side wins therefore depends on
    size and host (neither has a chip reading) — so auto engages only
    when the per-epoch read traffic is modest relative to the host's
    parallelism (threaded gathers amortize it on many-core hosts), and
    only single-host (cross-host the reads would ride DCN).
    ``RSDL_INDEX_SHUFFLE=on|off`` overrides.

    The auto gate is a measured time model (a runtime measurement
    adapts to any host shape; a fitted budget says nothing about WHY).
    Per-epoch cost of each schedule, from what the code actually does:

    * index:  ``min(8, R) x cache / gather_bw`` — R reducer gathers;
      each reads its 1/R row subset at random, touching a full 64 B
      cache line per 8 B element, so per-reducer traffic is
      ``min(8 x cache/R, cache)`` and the total caps at ``8 x cache``.
    * materialized: ``3 x cache / copy_bw`` of sequential traffic (map
      partition gather over sorted runs + reduce concat-permute + cache
      read) **plus** ``F x R`` store round-trips
      for its partition-object matrix, which is what the index schedule
      structurally eliminates and why it can win on small datasets
      despite slower gathers.

    Engage iff the modeled index epoch is no slower. All three costs
    come from :func:`_probed_host_costs` on THIS host.
    """
    mode = os.environ.get("RSDL_INDEX_SHUFFLE", "auto").strip().lower()
    if mode in ("on", "1", "true"):
        return True
    if mode in ("off", "0", "false"):
        return False
    if runtime.get_context().cluster is not None:
        return False
    try:
        est_cache = _est_decoded_bytes(filenames, narrow_to_32, columns)
    except OSError:
        return False
    costs = _probed_host_costs()
    gather_bw = _gather_bw_for(est_cache)
    if gather_bw <= 0 or costs["copy"] <= 0:
        return False
    t_index = min(8, num_reducers) * est_cache / gather_bw
    t_mat = (
        3.0 * est_cache / costs["copy"]
        + len(filenames) * num_reducers * costs["roundtrip"]
    )
    return t_index <= t_mat


def _audit_deliver(store, out_refs, epoch, reducer, rank, offsets):
    """Delivery-side audit hook (audit-on only): digest the reducer
    output (one or more refs — device-direct delivery splits a reducer
    into head/body/tail) exactly as it is about to be handed to the
    consumer, tracking each rank's running row offset for the
    order-sensitive determinism digest. Also the injection point for the
    test-only ``drop-row`` fault: the returned ref list (with one row
    silently removed from the final piece) REPLACES the real output, so
    a delivery-path defect is reproducible on demand and must surface as
    a digest mismatch at reconcile."""
    from ray_shuffling_data_loader_tpu.runtime.store import (
        device_batch_rows,
        is_device_batch,
        logical_columns,
    )

    def _rows(cb):
        return device_batch_rows(cb) if is_device_batch(cb) else cb.num_rows

    out_refs = list(out_refs)
    try:
        if out_refs and _audit.take_fault("drop-row", epoch):
            cb = store.get_columns(out_refs[-1])
            nrows = _rows(cb)
            if nrows > 0:
                # Republished as plain columnar (a packed piece re-packs
                # logically) minus its last row — the consumer's mixed-
                # stream handling delivers it unchanged otherwise.
                cols = logical_columns(cb)
                dropped = store.put_columns(
                    {k: np.asarray(cols[k])[: nrows - 1] for k in cols}
                )
                del cb
                store.free(out_refs[-1])
                out_refs[-1] = dropped
            else:
                del cb
        for ref in out_refs:
            cb = store.get_columns(ref)
            offset = offsets.get(rank, 0)
            _audit.record_deliver(
                epoch, reducer, rank, logical_columns(cb), offset
            )
            offsets[rank] = offset + _rows(cb)
            del cb
    except Exception:
        import logging

        logging.getLogger(__name__).warning(
            "audit: delivery digest failed", exc_info=True
        )
    return out_refs


def shuffle_epoch(
    epoch: int,
    filenames: List[str],
    batch_consumer: BatchConsumer,
    num_reducers: int,
    num_trainers: int,
    seed: int = 0,
    stats_collector=None,
    narrow_to_32: bool = False,
    decode_cache: Optional[_DecodeCache] = None,
    schedule_log: Optional[list] = None,
    device_layout: Optional[dict] = None,
    columns: Optional[Sequence[str]] = None,
    plan: Optional[Tuple[str, int]] = None,
    journal=None,
    est=None,
    job=None,
    knobs: Optional[dict] = None,
) -> threading.Thread:
    """Kick off one epoch's shuffle; returns the delivery thread.

    ``knobs`` (ISSUE 20): the plan compiler's effective task knobs
    (``ResolvedPlan.task_knobs()`` — decode threads, fetch-window
    depth, native threads, selective engagement), threaded into every
    stage task as a plain dict for the same reason as ``plan``.

    ``job`` (ISSUE 15): the service-plane tenant this epoch belongs to.
    Its id rides the telemetry context into every stage task (so
    worker-side audit digests, events, and ledger ops attribute to the
    job) and keys the live-status entry this epoch updates.

    ``journal``/``est`` (ISSUE 13): the run's
    :class:`~.runtime.journal.RunJournal` and this epoch's journaled
    :class:`~.runtime.journal.EpochState` from a resumed run. With a
    journal, stage completions and delivery cursors are appended at
    the existing barriers; with an ``est``, journaled stage results
    re-attach to surviving store segments (``store.exists``-validated,
    re-executing on a miss) and delivery skips the journaled cursor
    prefix so the per-rank ``delivered_seq`` digest over the whole run
    matches an uninterrupted same-seed run bit-for-bit.

    ``plan``: the resolved ``(family, granularity)`` shuffle-plan spec
    (``RSDL_SHUFFLE_PLAN``), threaded into every stage task so workers
    can never drift onto a different plan family than the driver (their
    env snapshot dates from pool spawn). None = parse here.

    ``device_layout``: device-direct delivery (ROADMAP 3) — a
    ``{"batch": B, "columns": [...]}`` staging layout from the consumer.
    Once every map resolves (so per-reducer row counts are known), each
    reduce task learns its rank-stream start offset and emits
    batch-aligned packed bodies plus boundary remainders instead of one
    columnar segment; the delivered row stream is bit-identical.

    Submits all map tasks, then all reduce tasks (each gated on its mapper
    inputs), and streams completed reducer outputs to the consumer in
    reducer order. Calls ``producer_done`` per rank once that rank's last
    reducer output is delivered (reference ``shuffle_epoch`` +
    ``consume``, ``shuffle.py:89-126,203-219``).

    Steady-state fast path: once every file's decoded columns are cached
    (and the policy allows — :func:`_index_schedule_allowed`), the epoch
    switches to the **index schedule**: per-file :func:`shuffle_plan`
    tasks draw the assignment over row indices only, and per-reducer
    :func:`shuffle_gather_reduce` tasks cut their output with ONE sparse
    gather from the cached segments — the epoch's only full data pass,
    replacing the materialized map scatter + reduce concat-permute while
    producing a bit-identical batch stream (tested).

    With ``RSDL_SELECTIVE_READS=on`` and no hot cache, the epoch runs
    the **selective schedule** instead (RINAS, ISSUE 11): per-file
    :func:`shuffle_selective_plan` tasks return counts only and each
    :func:`shuffle_selective_reduce` decodes just the row groups its
    seeded window needs — no map materialization in the store at all,
    same bit-identical stream (tested).
    """
    if stats_collector is not None:
        stats_collector.call_oneway("epoch_start", epoch)
    jid = job.job_id if job is not None else None
    # Job identity for every context (re-)entry below: thread-new
    # threads and task submissions must all carry it (contextvars do
    # not cross threads).
    jkv = {"job": jid} if jid is not None else {}
    # Cluster mode scatters stages across every host's workers; single-host
    # falls back to the local pool (same submit surface).
    pool = runtime.get_context().scheduler
    if plan is None:
        plan = shuffle_plan_spec()
    if decode_cache is None:
        decode_cache = _DecodeCache(enabled=False)
    cache_refs = (
        decode_cache.hot_refs(len(filenames))
        if _index_schedule_allowed(
            filenames, num_reducers, narrow_to_32, columns
        )
        else None
    )
    if cache_refs is not None:
        schedule = "index"
    elif selective_reads_decision(
        plan, planned=(knobs or {}).get("selective")
    )[0]:
        # RINAS-style selective schedule (ISSUE 11): no map
        # materialization at all — per-file plans return counts only,
        # reducers decode just the row groups their windows need.
        # Under auto (ISSUE 12) this arm engages only for prunable
        # (block) plans; rowwise declines to the materialized path.
        schedule = "selective"
    else:
        schedule = "mapreduce"
    if schedule_log is not None:
        schedule_log.append((epoch, schedule))
    jmod = None
    consume_seq = False
    if journal is not None:
        # Already imported by shuffle()'s journal bring-up; this only
        # binds the module object for the deliver thread below.
        from ray_shuffling_data_loader_tpu.runtime import journal as jmod

        # Seq-tagged delivery is opt-in per consumer (the queue-backed
        # one supports it); a consumer with the plain 3-arg signature
        # still works under a journal — it just keeps the one-reducer
        # re-delivery window on resume.
        try:
            import inspect

            consume_seq = (
                "seq"
                in inspect.signature(batch_consumer.consume).parameters
            )
        except (TypeError, ValueError):
            consume_seq = False
    if est is not None and est.schedule is not None and est.schedule != schedule:
        # The resumed epoch chose a different schedule than the
        # journaled attempt (env/policy drift between runs): the
        # journaled stage results belong to the other schedule's task
        # shapes and are unusable, but the delivery CURSOR stays valid
        # — the delivered stream is schedule-independent (bit-identical
        # across all three schedules, tested).
        pruned = type(est)(est.epoch)
        pruned.schedule = schedule
        pruned.delivered = est.delivered
        pruned.rank_rows = dict(est.rank_rows)
        pruned.sampled = est.sampled
        est = pruned
    cursor = est.delivered if est is not None else 0
    _status_epoch(
        epoch, state="running", schedule=schedule,
        delivered_reducers=cursor, job=jid,
    )
    if journal is not None:
        journal.append("epoch", epoch=epoch, schedule=schedule)
    telemetry.emit_event(
        "epoch.start", epoch=epoch, schedule=schedule,
        files=len(filenames), reducers=num_reducers,
    )

    if est is not None and cursor >= num_reducers:
        # The journal records every reducer of this epoch as delivered
        # before the preemption: skip the whole window — zero map, zero
        # reduce tasks — and only re-run the rank-boundary bookkeeping.
        # The epoch's audit partials were carried in the spool, so the
        # whole-run digests still fold to the uninterrupted values.
        _metrics.safe_inc("recovery.resume_epochs_skipped")

        def skip_done():
            done_ranks = set()
            try:
                for rank in range(num_trainers):
                    batch_consumer.producer_done(rank, epoch)
                    done_ranks.add(rank)
                if journal is not None:
                    journal.append("epoch-done", epoch=epoch)
                _status_epoch(epoch, state="done", job=jid)
                telemetry.emit_event(
                    "epoch.done", epoch=epoch, _flush=True
                )
            except BaseException as exc:
                thread.error = exc
                _status_epoch(epoch, state="failed", job=jid)
                telemetry.emit_event(
                    "epoch.failed", _flush=True, epoch=epoch,
                    error=f"{type(exc).__name__}: {exc}"[:200],
                )
            finally:
                # Same guarantee as deliver(): every rank gets its done
                # sentinel even on failure — consumers must unblock; the
                # driver re-raises the stored error after joining.
                for rank in range(num_trainers):
                    if rank not in done_ranks:
                        try:
                            batch_consumer.producer_done(rank, epoch)
                        except Exception:
                            pass

        thread = threading.Thread(
            target=skip_done, name=f"shuffle-deliver-e{epoch}",
            daemon=True,
        )
        thread.error = None
        thread.suspended = False
        thread.start()
        return thread

    skipped_maps: set = set()

    def _attach_map(i: int):
        """The journaled map result for file ``i`` when it re-attaches
        cleanly (selective counts always do; ref results need every
        segment alive), else None — and the stage re-executes."""
        if est is None:
            return None
        m = est.maps.get(i)
        if m is None:
            return None
        if schedule == "selective":
            counts = m.get("counts")
            if counts is None or len(counts) != num_reducers:
                return None
            _metrics.safe_inc("recovery.resume_map_skipped")
            return _ResolvedMapResult([int(c) for c in counts])
        refs_json = m.get("refs")
        if not refs_json:
            return None
        refs = _journaled_refs(refs_json)
        if refs is None:
            _metrics.safe_inc("recovery.resume_reexecuted", stage="map")
            return None
        if len(refs) != num_reducers:
            return None  # journaled under a different reducer count
        _metrics.safe_inc("recovery.resume_map_skipped")
        return _ResolvedMapResult(refs)

    map_futs: List[TaskFuture] = []
    map_published: List[bool] = []
    # ``shuffle:epoch`` runs from here, the first map's submission, to
    # the last reducer's output handed to the queue (the deliver thread
    # records it): what the host needs to make one epoch, hidden or not.
    epoch_t0 = time.time() if telemetry.active() else None
    # Trace context for everything this epoch submits from THIS thread:
    # the task layer pickles the submitter's context next to each task, so
    # worker-side map spans inherit the epoch id (the deliver thread below
    # re-enters it separately — thread-local context does not cross
    # threads).
    with telemetry.context(epoch=epoch, schedule=schedule, **jkv):
        if schedule == "index":
            for i in range(len(filenames)):
                attached = _attach_map(i)
                if attached is not None:
                    map_futs.append(attached)
                    map_published.append(False)
                    skipped_maps.add(i)
                    continue
                map_futs.append(
                    pool.submit_local_to(
                        [cache_refs[i]],
                        shuffle_plan,
                        i,
                        num_reducers,
                        epoch,
                        seed,
                        cache_refs[i],
                        stats_collector,
                        filenames[i],
                        plan,
                    )
                )
                map_published.append(False)
        elif schedule == "selective":
            for i, fname in enumerate(filenames):
                attached = _attach_map(i)
                if attached is not None:
                    map_futs.append(attached)
                    map_published.append(False)
                    skipped_maps.add(i)
                    continue
                map_futs.append(
                    pool.submit(
                        shuffle_selective_plan,
                        fname,
                        i,
                        num_reducers,
                        epoch,
                        seed,
                        columns,
                        narrow_to_32,
                        stats_collector,
                        plan,
                    )
                )
                map_published.append(False)
        else:
            for i, fname in enumerate(filenames):
                attached = _attach_map(i)
                if attached is not None:
                    map_futs.append(attached)
                    map_published.append(False)
                    skipped_maps.add(i)
                    continue
                cache_ref, publish = decode_cache.claim_or_wait(i)
                args = (
                    fname,
                    i,
                    num_reducers,
                    epoch,
                    seed,
                    stats_collector,
                    narrow_to_32,
                    cache_ref,
                    publish,
                    len(filenames),
                    columns,
                    plan,
                    knobs,
                )
                if cache_ref is not None:
                    # Locality: run the map on the host that owns the
                    # cached decode (cluster mode; the local pool ignores
                    # the hint).
                    fut = pool.submit_local_to(
                        [cache_ref], shuffle_map, *args
                    )
                else:
                    fut = pool.submit(shuffle_map, *args)
                if publish:
                    decode_cache.register(i, fut)
                map_futs.append(fut)
                map_published.append(publish)

    # Rank assignment: contiguous split of reducer indices across trainers
    # (reference np.array_split, shuffle.py:125).
    rank_of = np.concatenate(
        [
            np.full(len(chunk), rank, dtype=np.int64)
            for rank, chunk in enumerate(
                np.array_split(np.arange(num_reducers), num_trainers)
            )
        ]
    )

    # -- stage recovery (PR 3) ----------------------------------------------
    # Every stage task gets a bounded re-execution budget; a lost input
    # object is re-materialized from lineage (the driver knows which map
    # produced every partition ref) instead of failing the epoch. A task
    # that keeps failing — a poison task — exhausts the budget and
    # surfaces StageFailedError through shuffle().
    policy = stage_policy()

    def _resubmit_map(i, publish=False):
        """A fresh map attempt for file ``i``, always decoding from the
        Parquet source rather than a decode-cache ref (the cache segment
        may itself be the lost/corrupt object). ``publish`` re-publishes
        a fresh cache segment when the failed attempt was the file's
        cache publisher — a recovered crash must not silently disable
        the cross-epoch cache."""
        if schedule == "index":
            return pool.submit_local_to(
                [cache_refs[i]],
                shuffle_plan,
                i,
                num_reducers,
                epoch,
                seed,
                cache_refs[i],
                stats_collector,
                filenames[i],
                plan,
            )
        if schedule == "selective":
            return pool.submit(
                shuffle_selective_plan,
                filenames[i],
                i,
                num_reducers,
                epoch,
                seed,
                columns,
                narrow_to_32,
                stats_collector,
                plan,
            )
        return pool.submit(
            shuffle_map,
            filenames[i],
            i,
            num_reducers,
            epoch,
            seed,
            stats_collector,
            narrow_to_32,
            None,
            publish,
            len(filenames),
            columns,
            plan,
            knobs,
        )

    def _regenerate_cache(j):
        """Index schedule: the decoded-columns cache segment for file
        ``j`` is lost — re-decode from Parquet and republish, swapping
        the new ref into this epoch's cache list (shared with every
        pending resubmission closure) and the cross-epoch registry, so
        both this epoch's retries and later epochs read the regenerated
        segment."""
        _count_recovery("recovery.rematerialized", stage="decode-cache")
        telemetry.instant(
            "recovery:rematerialize", cat="recovery", file=j, cache=True
        )
        fut = pool.submit(
            shuffle_map,
            filenames[j],
            j,
            num_reducers,
            epoch,
            seed,
            stats_collector,
            narrow_to_32,
            None,
            True,
            len(filenames),
            columns,
            plan,
            knobs,
        )
        try:
            part_refs, new_cache = fut.result()
        except TaskError as exc:
            raise StageFailedError(
                "map-rematerialize", epoch, 1,
                f"decode-cache regeneration for file {j} failed:\n{exc}",
            ) from exc
        if new_cache is None:
            raise StageFailedError(
                "map-rematerialize", epoch, 1,
                f"decode-cache regeneration for file {j} republished "
                "nothing (store full?)",
            )
        store = runtime.get_context().store
        try:
            # The fresh partitions are unused by the index schedule, and
            # free() no-ops on whatever is left of the lost segment.
            store.free(list(part_refs) + [cache_refs[j]])
        except Exception:
            pass
        cache_refs[j] = new_cache
        decode_cache.register(j, _ResolvedMapResult((None, new_cache)))

    def _recover_lost_cache(lost):
        """If ``lost`` names one of this epoch's decode-cache segments
        (index schedule), regenerate it and return True."""
        if lost is None or schedule != "index" or not cache_refs:
            return False
        for cj, cache_ref in enumerate(cache_refs):
            if cache_ref.object_id == lost:
                _regenerate_cache(cj)
                return True
        return False

    def _await_map(i, fut, published):
        """Resolve one map future, re-executing on failure up to the
        stage budget. Returns ``(partition_refs, cache_ref_or_None)``
        — publish tuples unwrapped, the cache ref kept for the journal
        barrier. A lost decode-cache segment (index schedule) is
        regenerated before the plan resubmits against it."""
        for attempt, backoff in policy.attempts(site="stage.map"):
            try:
                res = fut.result()
                if published and res[1] is not None:
                    # Promote the fresh cache segment into the shared
                    # tier NOW, not at run end: under the service plane
                    # (ISSUE 15) a CONCURRENT job over the same files
                    # should ride these segments mid-flight, not only
                    # after this run finishes. No-op without shared
                    # keys; idempotent (first publisher wins).
                    decode_cache._share(i, res[1])
                return (res[0], res[1]) if published else (res, None)
            except TaskError as exc:
                if attempt >= policy.max_attempts:
                    raise StageFailedError(
                        "map", epoch, attempt,
                        f"map task for file {i} failed after "
                        f"{attempt} attempts:\n{exc}",
                    ) from exc
                _count_recovery("recovery.stage_retries", stage="map")
                telemetry.emit_event(
                    "stage.retry", stage="map", epoch=epoch,
                    attempt=attempt, file=i,
                    error=f"{exc.error_type or type(exc).__name__}",
                )
                backoff.backoff(str(exc))
                _recover_lost_cache(exc.lost_object_id)
                # ``retry`` rides the context into the resubmitted
                # task's ``pool:`` span: the runtime layer's retry count.
                with telemetry.context(retry=attempt):
                    fut = _resubmit_map(i, publish=published)
                if published:
                    # Later epochs block on the NEW publishing attempt
                    # instead of degrading to per-epoch decode for the
                    # rest of the run.
                    decode_cache.register(i, fut)
        raise AssertionError("unreachable: retry budget mis-sized")

    def deliver():
        done_ranks = set()
        # rank -> delivered-row offset. On resume the journaled per-rank
        # row counts seed the offsets so the continuation's seq digests
        # keep folding from the exact position the preempted run reached
        # — the whole-run delivered_seq is then bit-identical.
        audit_offsets: Dict[int, int] = (
            dict(est.rank_rows) if est is not None else {}
        )
        if job is not None:
            # Fresh thread: make the job ambient for the fair-share
            # scheduler's reduce submissions (service TLS does not
            # cross threads; the trace context below carries the id
            # for telemetry, this carries the Job for scheduling).
            from ray_shuffling_data_loader_tpu.runtime import (
                service as _service,
            )

            _service.set_current_job(job)
        try:
            # Re-enter the epoch's trace context on this (fresh) thread
            # so the reduce submissions and delivery spans below carry
            # the epoch id — INSIDE the try, so the finally's sentinel
            # delivery can never depend on telemetry.
            with telemetry.context(epoch=epoch, schedule=schedule, **jkv):
                # Wait for all maps (reduce needs one partition per mapper).
                # Publishing maps return (refs, cache_ref); unwrap those.
                with telemetry.trace_span("deliver:wait-maps", cat="shuffle"):
                    resolved_maps = [
                        _await_map(i, f, pub)
                        for i, (f, pub) in enumerate(
                            zip(map_futs, map_published)
                        )
                    ]
                per_file_refs = [refs for refs, _ in resolved_maps]
                if journal is not None:
                    # Task-done journal barrier: each map's result is
                    # durable the moment the driver observes it (the
                    # worker's audit/metrics spools flushed before the
                    # future resolved — runtime/tasks.py). Re-attached
                    # results were carried forward at begin_run.
                    for i, (refs, cache_ref) in enumerate(resolved_maps):
                        if i in skipped_maps:
                            continue
                        rec: Dict[str, object] = {}
                        if schedule == "selective":
                            rec["counts"] = [int(c) for c in refs]
                        else:
                            rec["refs"] = [
                                jmod.ref_to_json(x) for x in refs
                            ]
                        if cache_ref is not None:
                            rec["cache_ref"] = jmod.ref_to_json(cache_ref)
                        journal.append("map", epoch=epoch, file=i, **rec)
                # Lineage: which map produced every partition ref. When a
                # reduce dies on ObjectLostError, the driver re-executes
                # exactly that producing map (bounded by the stage budget)
                # instead of failing the epoch — the Ray-lineage analog
                # the runtime lost when it replaced Ray. The selective
                # schedule has no partition refs (its "maps" return
                # per-reducer counts) and so no lineage to track: a
                # selective reduce's only input is the immutable Parquet
                # source, and a plain resubmit IS its re-materialization.
                lineage: Dict[str, int] = {}
                if schedule != "selective":
                    for i, refs in enumerate(per_file_refs):
                        for ref in refs:
                            lineage[ref.object_id] = i
                # Locality: each reduce runs on the host already holding the
                # most of its input-partition rows (cluster mode; the local
                # pool ignores the hint). Ray gets this from its scheduler;
                # round-robin alone would cross DCN with ~(N-1)/N of all
                # partition bytes.
                reduce_fn, extra = (
                    (shuffle_gather_reduce, (cache_refs,))
                    if schedule == "index"
                    else (shuffle_reduce, ())
                )

                # Device-direct delivery: per-reducer rank-stream start
                # offsets, derivable the moment every map resolved (the
                # partition/plan window refs carry row counts). Both
                # schedules' per-file refs are row windows, so the counts
                # exist without opening a single segment; any unknown
                # count (whole-segment ref) disables packing for the
                # epoch — columnar refs are always legal.
                pack_for: List[Optional[tuple]] = [None] * num_reducers
                if device_layout is not None:
                    counts_r: List[Optional[int]] = []
                    for r in range(num_reducers):
                        if schedule == "selective":
                            # The plans returned per-reducer counts
                            # directly — no refs to interrogate.
                            counts_r.append(
                                int(
                                    sum(
                                        int(counts[r])
                                        for counts in per_file_refs
                                    )
                                )
                            )
                            continue
                        rows = [
                            _ref_window_rows(refs[r])
                            for refs in per_file_refs
                        ]
                        counts_r.append(
                            None
                            if any(c is None for c in rows)
                            else int(sum(rows))
                        )
                    if all(c is not None for c in counts_r):
                        acc: Dict[int, int] = {}
                        for r in range(num_reducers):
                            rnk = int(rank_of[r])
                            pack_for[r] = (
                                acc.get(rnk, 0), device_layout
                            )
                            acc[rnk] = acc.get(rnk, 0) + counts_r[r]

                def _submit_reduce(r, refs_r):
                    if schedule == "selective":
                        return pool.submit(
                            shuffle_selective_reduce,
                            r,
                            epoch,
                            seed,
                            filenames,
                            num_reducers,
                            narrow_to_32,
                            columns,
                            stats_collector,
                            pack_for[r],
                            plan,
                            knobs,
                        )
                    return pool.submit_local_to(
                        refs_r,
                        reduce_fn,
                        r,
                        epoch,
                        seed,
                        refs_r,
                        *extra,
                        stats_collector,
                        pack_for[r],
                        knobs,
                    )

                def _refs_for(r):
                    if schedule == "selective":
                        return []
                    return [refs[r] for refs in per_file_refs]

                def _attach_reduce(r):
                    """The journaled reduce output for ``r``, when every
                    published ref (one columnar, or device-direct
                    head/body/tail) still resolves — else None and the
                    reduce re-executes (bit-identical by seed)."""
                    if est is None:
                        return None
                    refs_json = est.reduces.get(r)
                    if not refs_json:
                        return None
                    refs = _journaled_refs(refs_json)
                    if refs is None:
                        _metrics.safe_inc(
                            "recovery.resume_reexecuted", stage="reduce"
                        )
                        return None
                    _metrics.safe_inc("recovery.resume_reduce_skipped")
                    return _ResolvedMapResult(refs)

                # Delivery-cursor prefix (ISSUE 13): reducers the
                # journaled run already handed to the consumer get no
                # future at all — their audit partials are durable in
                # the spool, so skipping keeps the whole-run
                # delivered_seq digest bit-identical.
                reduce_futs = []
                attached_reduces: set = set()
                for r in range(num_reducers):
                    if r < cursor:
                        reduce_futs.append(None)
                        continue
                    attached = _attach_reduce(r)
                    if attached is not None:
                        attached_reduces.add(r)
                        reduce_futs.append(attached)
                    else:
                        reduce_futs.append(_submit_reduce(r, _refs_for(r)))

                def _failed(f):
                    try:
                        f.result(timeout=0)
                        return False
                    except Exception:
                        return True

                # Free each reducer's input partitions from the driver — not
                # inside the task (keeps reduce retryable for cluster
                # failover) — and in COMPLETION order on a side thread, not
                # delivery order: the delivery loop below can block on
                # consumer backpressure while later reducers finished long
                # ago, and holding their inputs would double peak /dev/shm.
                # FAILED futures are skipped: the delivery retry path owns
                # (and frees) a retried reducer's inputs.
                def free_inputs():
                    store = runtime.get_context().store
                    # Resume (ISSUE 13): cursor-skipped reducers (None)
                    # and journal-re-attached ones (_ResolvedMapResult)
                    # never consume their input partitions — free those
                    # windows up front (no-op on refs that were already
                    # freed before the preemption), and only real task
                    # futures enter the completion-order wait below.
                    # Classified positively: a real future may be a
                    # TaskFuture OR a ClusterTaskFuture, so "not a
                    # TaskFuture" would misread every cluster-mode
                    # reduce as skipped and free its inputs mid-fetch.
                    def _skipped(f):
                        return f is None or isinstance(
                            f, _ResolvedMapResult
                        )

                    skipped_rs = [
                        r
                        for r, f in enumerate(reduce_futs)
                        if _skipped(f)
                    ]
                    if skipped_rs:
                        try:
                            store.free(
                                [
                                    refs[r]
                                    for refs in per_file_refs
                                    for r in skipped_rs
                                ]
                            )
                        except Exception:
                            pass
                    index_of = {
                        id(f): r
                        for r, f in enumerate(reduce_futs)
                        if not _skipped(f)
                    }
                    remaining = [
                        f for f in reduce_futs if not _skipped(f)
                    ]
                    while remaining:
                        finished, remaining = wait(remaining, num_returns=1)
                        for f in finished:
                            if _failed(f):
                                continue
                            try:
                                store.free(
                                    [
                                        refs[index_of[id(f)]]
                                        for refs in per_file_refs
                                    ]
                                )
                            except Exception:
                                pass

                if schedule != "selective":
                    # Selective reducers consumed nothing from the
                    # store; there are no inputs to free.
                    threading.Thread(
                        target=free_inputs,
                        name=f"free-inputs-e{epoch}",
                        daemon=True,
                    ).start()

                def _rematerialize(j, r, old_ref):
                    """Lineage re-execution: re-run map ``j``, keep its
                    window for reducer ``r``, free the rest (they pin the
                    regenerated segment; the surviving reducers still hold
                    the original, intact partitions)."""
                    _count_recovery("recovery.rematerialized", stage="map")
                    telemetry.instant(
                        "recovery:rematerialize", cat="recovery",
                        file=j, reducer=r,
                    )
                    try:
                        newrefs = _resubmit_map(j).result()
                    except TaskError as exc:
                        raise StageFailedError(
                            "map-rematerialize", epoch, 1,
                            f"lineage re-execution of file {j} failed:\n"
                            f"{exc}",
                        ) from exc
                    store = runtime.get_context().store
                    try:
                        # The unused regenerated windows, plus whatever is
                        # left of the lost original (free is a no-op on a
                        # truly missing segment).
                        store.free(
                            [nr for k, nr in enumerate(newrefs) if k != r]
                            + [old_ref]
                        )
                    except Exception:
                        pass
                    lineage[newrefs[r].object_id] = j
                    return newrefs[r]

                def _await_reduce(r, fut):
                    """Resolve one reduce future with re-execution: lost
                    inputs are re-materialized from lineage before the
                    resubmit; anything else is retried as-is (transient),
                    all bounded by the stage budget."""
                    refs_r = _refs_for(r)
                    retried = False
                    for attempt, backoff in policy.attempts(
                        site="stage.reduce"
                    ):
                        try:
                            out = fut.result()
                            if retried:
                                # First-attempt successes are freed by the
                                # completion-order thread; a retried
                                # reducer's (possibly regenerated) inputs
                                # are freed here.
                                try:
                                    runtime.get_context().store.free(refs_r)
                                except Exception:
                                    pass
                            return out
                        except TaskError as exc:
                            if attempt >= policy.max_attempts:
                                raise StageFailedError(
                                    "reduce", epoch, attempt,
                                    f"reduce task {r} failed after "
                                    f"{attempt} attempts:\n{exc}",
                                ) from exc
                            _count_recovery(
                                "recovery.stage_retries", stage="reduce"
                            )
                            telemetry.emit_event(
                                "stage.retry", stage="reduce", epoch=epoch,
                                attempt=attempt, reducer=r,
                                error=(
                                    f"{exc.error_type or type(exc).__name__}"
                                ),
                            )
                            backoff.backoff(str(exc))
                            lost = exc.lost_object_id
                            if lost is not None and lost in lineage:
                                j = lineage[lost]
                                refs_r[j] = _rematerialize(
                                    j, r, refs_r[j]
                                )
                            else:
                                # Index schedule: the lost object may be
                                # a decode-cache segment (never in the
                                # partition lineage) — regenerate it so
                                # the resubmitted gather reads a live
                                # segment instead of burning its budget
                                # on identical doomed attempts.
                                _recover_lost_cache(lost)
                            retried = True
                            with telemetry.context(retry=attempt):
                                fut = _submit_reduce(r, refs_r)
                    raise AssertionError("unreachable: retry budget mis-sized")

                # Stream each reducer's output to its rank as soon as it
                # completes, preserving reducer order within a rank for
                # determinism.
                for r, fut in enumerate(reduce_futs):
                    rank = int(rank_of[r])
                    if fut is None:
                        # Journaled delivery cursor (ISSUE 13): this
                        # reducer reached the consumer before the
                        # preemption and its audit partials are durable
                        # in the spool — only the rank-boundary sentinel
                        # bookkeeping happens again.
                        if r + 1 == num_reducers or rank_of[r + 1] != rank:
                            batch_consumer.producer_done(rank, epoch)
                            done_ranks.add(rank)
                        continue
                    if jmod is not None and jmod.suspend_requested():
                        # Preemption notice: the current reducer was the
                        # quiesce window; stop at this barrier with the
                        # journal cursor exactly describing what the
                        # consumer got. The remaining reducers are
                        # already executing — drain them and journal
                        # their published outputs so the work is
                        # durable and re-attachable (the resume
                        # delivers them without re-execution; abandoned
                        # they would leak until session cleanup). A
                        # reducer that fails or outlives the quiesce
                        # budget is simply not journaled — the resume
                        # re-executes it, bit-identical by seed. The
                        # budget is ONE deadline across the whole drain,
                        # not per-future: a preemption notice is
                        # typically 30-120 s, and a wedged fleet must
                        # not stack 60 s waits serially past it.
                        if journal is not None:
                            drain_deadline = timeit.default_timer() + 60
                            for r2 in range(r, num_reducers):
                                f2 = reduce_futs[r2]
                                if f2 is None or r2 in attached_reduces:
                                    continue
                                try:
                                    out2 = f2.result(
                                        timeout=max(
                                            0.0,
                                            drain_deadline
                                            - timeit.default_timer(),
                                        )
                                    )
                                except Exception:
                                    continue
                                refs2 = (
                                    list(out2)
                                    if isinstance(out2, (list, tuple))
                                    else [out2]
                                )
                                journal.append(
                                    "reduce", epoch=epoch, reducer=r2,
                                    refs=[
                                        jmod.ref_to_json(x)
                                        for x in refs2
                                    ],
                                )
                        thread.suspended = True
                        break
                    out = _await_reduce(r, fut)
                    # Device-direct reducers return a short LIST of refs
                    # (head/body/tail); legacy reducers one columnar ref.
                    out_refs = (
                        list(out)
                        if isinstance(out, (list, tuple))
                        else [out]
                    )
                    if journal is not None and r not in attached_reduces:
                        # Task-done journal barrier for the reduce: its
                        # published output can re-attach on resume even
                        # when the preemption lands before delivery.
                        # (Before the audit drop-row hook, which swaps
                        # in a deliberately corrupted ref.)
                        journal.append(
                            "reduce", epoch=epoch, reducer=r,
                            refs=[jmod.ref_to_json(x) for x in out_refs],
                        )
                    if _faults.enabled():
                        # The scripted producer-stall (or kill: a dead
                        # delivery thread is what ProducerDiedError
                        # supervision detects on the consumer side).
                        _faults.fire("queue.producer", epoch=epoch)
                    offset_before = audit_offsets.get(rank, 0)
                    if _audit.enabled():
                        out_refs = _audit_deliver(
                            runtime.get_context().store,
                            out_refs, epoch, r, rank, audit_offsets,
                        )
                    # The span covers the consumer handoff INCLUDING any
                    # blocking inside it (queue put_batch backpressure) — on
                    # the timeline this is where delivery waits on the
                    # trainer.
                    with telemetry.trace_span(
                        "deliver", cat="queue", rank=rank, reducer=r
                    ):
                        if consume_seq:
                            # Idempotent re-publish (ISSUE 13): tag the
                            # publication with its reducer index so a
                            # queue actor that outlived a preempted
                            # driver drops the one-reducer overlap
                            # between "published" and "journaled".
                            batch_consumer.consume(
                                rank, epoch, out_refs, seq=r
                            )
                        else:
                            batch_consumer.consume(rank, epoch, out_refs)
                    _status_epoch(epoch, delivered_inc=1, job=jid)
                    if jid is not None:
                        # Per-job delivered-volume rate: the fairness
                        # signal the service SLOs key on. Bytes,
                        # not rows — a whole-segment reducer output
                        # carries no row window, and opening it just to
                        # count would cost a read on the hot path.
                        _metrics.safe_inc(
                            "service.delivered_bytes",
                            float(sum(ref.nbytes for ref in out_refs)),
                            job=jid,
                        )
                    if journal is not None:
                        # Deliver-thread journal barrier. Write-ahead
                        # ordering with the audit spool: the delivery
                        # digest is flushed BEFORE the cursor record, so
                        # a journaled "delivered" always implies the
                        # digest is on disk — a crash between the two
                        # merely re-delivers this one reducer, which the
                        # reconciler's (rank, reducer, offset) dedup
                        # absorbs.
                        if _audit.enabled():
                            _audit.safe_flush()
                            rows = audit_offsets.get(rank, 0) - offset_before
                            sampled = _audit.sample_count(epoch)
                        else:
                            rows = sum(
                                _ref_window_rows(ref) or 0
                                for ref in out_refs
                            )
                            # Keep the per-rank row offsets folding even
                            # with audit off — a later audited resume
                            # must not inherit zeroed offsets.
                            audit_offsets[rank] = offset_before + rows
                            sampled = 0
                        journal.append(
                            "deliver", epoch=epoch, reducer=r, rank=rank,
                            rows=int(rows), sampled=int(sampled),
                        )
                        if getattr(journal, "resume_pending", False):
                            # First delivery of the resumed run: the
                            # resume_stalled SLO rule stands down.
                            journal.resume_pending = False
                            jmod.set_resume_in_progress(False)
                    if stats_collector is not None:
                        stats_collector.call_oneway(
                            "consume", rank, epoch,
                            sum(ref.nbytes for ref in out_refs),
                        )
                    if r + 1 == num_reducers or rank_of[r + 1] != rank:
                        batch_consumer.producer_done(rank, epoch)
                        done_ranks.add(rank)
                if epoch_t0 is not None and not getattr(
                    thread, "suspended", False
                ):
                    telemetry.record_span(
                        "shuffle:epoch", epoch_t0, time.time() - epoch_t0,
                        cat="shuffle", reducers=num_reducers,
                        maps=len(filenames), schedule=schedule,
                    )
                if journal is not None and not getattr(
                    thread, "suspended", False
                ):
                    # Epoch barrier: every reducer delivered — a resume
                    # skips this epoch's window outright.
                    journal.append("epoch-done", epoch=epoch)
        except BaseException as exc:
            thread.error = exc
        finally:
            failed = thread.error is not None
            suspended = not failed and getattr(thread, "suspended", False)
            _status_epoch(
                epoch,
                state=(
                    "failed"
                    if failed
                    else ("suspended" if suspended else "done")
                ),
                job=jid,
            )
            if failed:
                telemetry.emit_event(
                    "epoch.failed", _flush=True, epoch=epoch,
                    error=(
                        f"{type(thread.error).__name__}: {thread.error}"
                    )[:200],
                )
            elif not suspended:
                telemetry.emit_event("epoch.done", epoch=epoch, _flush=True)
            # Every rank gets its done sentinel even on failure (or when it
            # was assigned zero reducers): consumers must unblock; the
            # driver re-raises the stored error after joining.
            for rank in range(num_trainers):
                if rank not in done_ranks:
                    try:
                        batch_consumer.producer_done(rank, epoch)
                    except Exception:
                        pass

    thread = threading.Thread(
        target=deliver, name=f"shuffle-deliver-e{epoch}", daemon=True
    )
    thread.error = None
    thread.suspended = False
    thread.start()
    return thread


def device_direct_enabled() -> bool:
    """The ONE parser of the ``RSDL_DEVICE_DIRECT`` kill switch (default
    ``auto`` = honor consumer layout requests). Shared by the shuffle
    gate and the stager's request builder so the disable spellings
    can never drift apart."""
    return os.environ.get(
        "RSDL_DEVICE_DIRECT", "auto"
    ).strip().lower() not in ("off", "0", "false")


def _device_layout_allowed(device_layout: Optional[dict]) -> Optional[dict]:
    """The authoritative device-direct gate: honor the consumer's layout
    request unless ``RSDL_DEVICE_DIRECT=off`` (the kill switch). Audit
    needs no special-casing — packed segments carry every reducer column
    (requested prefix first), so any key column the legacy path could
    digest, the packed path digests too."""
    if device_layout is None or not device_direct_enabled():
        return None
    return device_layout


def _pushdown_columns(
    device_layout: Optional[dict],
    columns: Optional[Sequence[str]],
) -> Optional[List[str]]:
    """The decode projection for a run, or None (full decode).

    Column pushdown (ISSUE 11) engages only when the set of columns the
    run can ever touch is PROVABLY known — an explicit ``columns=``
    request from the caller (honored under the default ``auto``), or,
    under ``RSDL_DECODE_PUSHDOWN=on``, the staging layout's column set
    (the packed prefix is all the consumer ships; ``on`` is the
    operator asserting nothing else reads the stream). The audit key
    column is always appended when audit is armed — digests must keep
    folding. Unknown spec → decline to full decode; ``off`` → never
    prune (the bit-identity control)."""
    mode = os.environ.get(
        "RSDL_DECODE_PUSHDOWN", "auto"
    ).strip().lower()
    if mode in ("off", "0", "false"):
        return None
    need: Optional[List[str]] = None
    if columns is not None:
        need = [str(c) for c in columns]
    elif mode in ("on", "1", "true") and device_layout is not None:
        try:
            need = [str(c) for c in device_layout["columns"]]
        except (KeyError, TypeError):
            return None
    if not need:
        return None
    if _audit.enabled():
        key = _audit.key_column_name()
        if key not in need:
            need = need + [key]
    seen: set = set()
    return [c for c in need if not (c in seen or seen.add(c))]


def shuffle(
    filenames: List[str],
    batch_consumer: BatchConsumer,
    num_epochs: int,
    num_reducers: int,
    num_trainers: int,
    seed: int = 0,
    stats_collector=None,
    start_epoch: int = 0,
    narrow_to_32: bool = False,
    cache_decoded: Optional[bool] = None,
    schedule_log: Optional[list] = None,
    device_layout: Optional[dict] = None,
    columns: Optional[Sequence[str]] = None,
    resume_from: Optional[str] = None,
) -> float:
    """Shuffle the dataset every epoch; returns total wall-clock duration.

    The top-level driver (reference ``shuffle``, ``shuffle.py:51-86``): for
    each epoch, block until the consumer's epoch window admits it, then
    launch that epoch's map/reduce/delivery pipeline. ``start_epoch`` skips
    fully-consumed epochs when resuming from a checkpoint (epoch indices
    stay absolute so per-epoch permutations match the original run).

    ``cache_decoded``: keep each file's decoded columns in the store after
    the first epoch so later epochs skip Parquet decode (None = auto:
    on when multiple epochs run and the estimate fits the store budget).
    With the cache hot, later epochs also switch to the index-only
    steady-state schedule (see :func:`shuffle_epoch`) when policy allows.

    ``schedule_log``: optional list; each epoch appends
    ``(epoch, "index" | "mapreduce")`` — observability for tests.

    ``device_layout``: device-direct delivery (ROADMAP 3, see
    :func:`shuffle_epoch`) — ``{"batch": B, "columns": [...]}`` from a
    staging consumer; honored unless the ``RSDL_DEVICE_DIRECT`` kill
    switch is off (:func:`_device_layout_allowed`).

    ``columns``: an explicit decode projection (column pushdown,
    ISSUE 11) — the delivered stream then contains exactly this set
    (plus the audit key when audit is armed) and nothing else is ever
    decoded off Parquet; ``shuffle.decode_bytes_pruned`` counts the
    avoided work. See :func:`_pushdown_columns` for the
    ``RSDL_DECODE_PUSHDOWN`` gate semantics.

    ``resume_from`` (ISSUE 13): resume a preempted run from its
    write-ahead journal — ``"auto"`` (or ``RSDL_RESUME=auto``) discovers
    the newest resumable journal under ``RSDL_JOURNAL`` whose run
    identity matches this call; a path names a journal file/dir
    explicitly (an identity mismatch then refuses loudly);
    ``"redeliver"`` resumes the stages but re-delivers the in-flight
    epochs' full streams (a consumer that restarted from scratch).
    With ``RSDL_JOURNAL`` set, every run journals its epoch-window
    state at the task-done / deliver / epoch barriers and installs a
    SIGTERM graceful-suspend handler. See
    :mod:`~.runtime.journal` and docs/robustness.md ("Preemption,
    suspend/resume, and replay").

    Under the multi-tenant service plane (``RSDL_SERVICE``, ISSUE 15)
    every call runs as a *job*: the ambient
    :func:`~.runtime.service.job_context` job if the caller entered
    one, else a freshly auto-registered job ended when this call
    returns. Job identity then scopes the live status, audit digests,
    journal identity, and capacity-ledger attribution, stage tasks are
    fair-share scheduled against concurrent jobs, epoch admission keys
    on the shared shm budget, and the decode cache is shared by content
    identity across jobs. With ``RSDL_SERVICE`` unset none of this
    executes — the single-job path is byte-for-byte unchanged.
    """
    service_mod = None
    job = None
    own_job = False
    if os.environ.get("RSDL_SERVICE"):
        # Lazy, env-guarded: the plane's module body never runs on a
        # service-off driver (gate-integrity).
        from ray_shuffling_data_loader_tpu.runtime import (
            service as service_mod,
        )

        if service_mod.enabled():
            job = service_mod.current_job()
            if job is None:
                job = service_mod.register_job()
                own_job = True
        else:
            service_mod = None
    if job is None:
        return _shuffle_impl(
            filenames, batch_consumer, num_epochs, num_reducers,
            num_trainers, seed=seed, stats_collector=stats_collector,
            start_epoch=start_epoch, narrow_to_32=narrow_to_32,
            cache_decoded=cache_decoded, schedule_log=schedule_log,
            device_layout=device_layout, columns=columns,
            resume_from=resume_from,
        )
    try:
        with service_mod.job_context(job):
            return _shuffle_impl(
                filenames, batch_consumer, num_epochs, num_reducers,
                num_trainers, seed=seed, stats_collector=stats_collector,
                start_epoch=start_epoch, narrow_to_32=narrow_to_32,
                cache_decoded=cache_decoded, schedule_log=schedule_log,
                device_layout=device_layout, columns=columns,
                resume_from=resume_from, job=job,
            )
    finally:
        if own_job:
            service_mod.end_job(job)


def _shuffle_impl(
    filenames: List[str],
    batch_consumer: BatchConsumer,
    num_epochs: int,
    num_reducers: int,
    num_trainers: int,
    seed: int = 0,
    stats_collector=None,
    start_epoch: int = 0,
    narrow_to_32: bool = False,
    cache_decoded: Optional[bool] = None,
    schedule_log: Optional[list] = None,
    device_layout: Optional[dict] = None,
    columns: Optional[Sequence[str]] = None,
    resume_from: Optional[str] = None,
    job=None,
) -> float:
    """The driver body behind :func:`shuffle`; ``job`` is the resolved
    service-plane tenant (already ambient via job_context) or None."""
    jid = job.job_id if job is not None else None
    # What the audit layer reconciles as "this run": normally the job
    # id; widened to the whole resume chain's ids under a journaled
    # service resume (set below — records stamped by a preempted
    # attempt carry ITS id).
    audit_scope = jid
    if not filenames:
        # A typo'd glob would otherwise "shuffle" zero rows successfully.
        raise ValueError("no input files to shuffle")
    # Resolve RSDL_SHUFFLE_PLAN once, driver-side (ISSUE 12): a
    # malformed value fails fast before any task runs, and the resolved
    # spec is threaded through every stage task's arguments — workers'
    # env snapshots date from pool spawn, so an env-only plan could
    # split driver and workers onto different plan families.
    plan = shuffle_plan_spec()
    runtime.ensure_initialized()
    _status_begin_trial(
        num_epochs, len(filenames), num_reducers, num_trainers,
        start_epoch, job=jid,
    )
    telemetry.emit_event(
        "trial.start", epochs=num_epochs, files=len(filenames),
        reducers=num_reducers, trainers=num_trainers,
        start_epoch=start_epoch,
    )
    if os.environ.get("RSDL_OBS_PORT"):
        # Publish the live trial view to the obs endpoint. Registration
        # is one dict set; the import is the only cost and is gated on
        # the endpoint actually being configured.
        try:
            from ray_shuffling_data_loader_tpu.telemetry import obs_server

            obs_server.register_status_provider("shuffle", live_status)
        except Exception:
            pass
    device_layout = _device_layout_allowed(device_layout)
    # -- self-tuning plan compiler (ISSUE 20) -------------------------------
    # Gate checked before any planner import (zero-overhead off). The
    # compiler resolves every planner-owned knob once, driver-side; an
    # env-set knob pins its term (env beats planned — see
    # analysis/planner.py). Effective task knobs then ride stage-task
    # ARGUMENTS (the PR 12 lesson: worker env snapshots date from pool
    # spawn), and the resolved plan replaces the env-parsed one.
    rplan = None
    task_knobs: Optional[dict] = None
    _planner = None
    if _plan_enabled():
        from ray_shuffling_data_loader_tpu.analysis import planner as _planner
        from ray_shuffling_data_loader_tpu.runtime import plan as _plan_state

        rplan = _planner.compile_plan(
            filenames,
            num_reducers=num_reducers,
            num_trainers=num_trainers,
            num_epochs=num_epochs,
            start_epoch=start_epoch,
            columns=columns,
            device_layout=device_layout,
            narrow_to_32=narrow_to_32,
            cache_decoded=cache_decoded,
        )
        plan = rplan.plan
        if columns is None and rplan.projection is not None:
            # The planned projection enters the SAME seam caller
            # columns do, upstream of _pushdown_columns (audit-key
            # append and dedup stay in one place).
            columns = list(rplan.projection)
        task_knobs = rplan.task_knobs()
        _plan_state.set_current(rplan)
        telemetry.emit_event(
            "plan.chosen", plan=_label_of_plan(plan),
            terms=rplan.terms_dict(),
        )
        _metrics.safe_inc("plan.compiled", plan=_label_of_plan(plan))
    columns = _pushdown_columns(device_layout, columns)
    # -- durable epoch-state plane (ISSUE 13) -------------------------------
    # Lazy import: with RSDL_JOURNAL unset and no explicit resume the
    # journal module never loads, no file is created, and no signal
    # handler is installed (the zero-overhead contract, proven by a
    # fresh-interpreter test).
    jmod = None
    journal = None
    resume_state = None
    resume_mode = "cursor"
    if resume_from is not None or os.environ.get("RSDL_JOURNAL"):
        from ray_shuffling_data_loader_tpu.runtime import journal as jmod

        if job is not None and job.name == "job":
            # The journal identity distinguishes tenants by job NAME
            # (stable across restarts — the per-registration id would
            # refuse every legitimate resume). With the implicit
            # default name, two same-shaped tenants sharing one
            # journal dir would collide and RSDL_RESUME=auto could
            # cross them — warn loudly; distinct names (RSDL_JOB_NAME
            # or register_job(name=)) are the documented contract for
            # journaled multi-tenant runs (docs/service.md).
            import logging

            logging.getLogger(__name__).warning(
                "journaled service run with the default job name "
                "'job': concurrent same-shaped tenants in this journal "
                "dir would share a run identity — set RSDL_JOB_NAME "
                "(or register_job(name=...)) per tenant"
            )
        identity = jmod.run_identity(
            filenames, num_epochs, num_reducers, num_trainers, seed,
            start_epoch, narrow_to_32, _label_of_plan(plan), columns,
            device_layout,
            job=job.name if job is not None else None,
        )
        resume_state, resume_mode = jmod.resolve_resume(
            resume_from, identity
        )
        if jid is not None:
            # Audit lineage across the resume chain (ISSUE 15): digest
            # records are stamped with the per-registration job id,
            # which CHANGES across restarts — a resumed attempt must
            # fold every ancestor attempt's records or the carried
            # spool would reconcile as a false mismatch. The chain
            # rides the journal identity (informational, not
            # validated), so a twice-preempted run still reaches its
            # grandparent's records.
            prev_jobs = []
            if resume_state is not None:
                prev_jobs = [
                    str(j)
                    for j in (
                        resume_state.identity.get("audit_jobs") or []
                    )
                ]
            identity["audit_jobs"] = prev_jobs + [jid]
            if prev_jobs:
                audit_scope = identity["audit_jobs"]
        if not jmod.enabled() and resume_state is None:
            # resume_from="auto"/"off" with RSDL_JOURNAL unset: nothing
            # to resume and nowhere to journal — the plane stays off
            # (an explicit resume_from path journals next to the old
            # run's file instead).
            jmod = None
    if jmod is not None:
        jmod.clear_suspend()
        journal = jmod.begin_run(
            identity, resume=resume_state, mode=resume_mode
        )
        jmod.install_sigterm_handler()
        if resume_state is not None:
            journal.resume_pending = True
            jmod.set_resume_in_progress(True)
            _metrics.safe_inc("recovery.resume_runs")
            telemetry.emit_event(
                "run.resumed", _flush=True,
                run_id=journal.run_id,
                from_run=resume_state.run_id,
                mode=resume_mode,
                epochs_with_progress=len(resume_state.epochs),
            )
            restore_cursors = getattr(
                batch_consumer, "restore_delivery_cursors", None
            )
            if restore_cursors is not None and resume_mode == "cursor":
                # Seed the queue actor's idempotency cursors so a
                # reducer that reached the queue in the crash window
                # between its publish and its journal append is dropped
                # whole on re-publish — never duplicated to the trainer.
                cursors = {
                    f"{e}/{rank}": st.delivered
                    for e, st in resume_state.epochs.items()
                    if st.delivered > 0
                    for rank in range(num_trainers)
                }
                if cursors:
                    try:
                        restore_cursors(cursors)
                    except Exception:
                        import logging

                        logging.getLogger(__name__).warning(
                            "could not seed queue delivery cursors",
                            exc_info=True,
                        )
    if _audit.enabled():
        # Scope the digest records to THIS run: stale records (a previous
        # shuffle in the same process / spool dir) would fold into this
        # run's digests and poison the verdicts. On resume the superseded
        # attempt's spooled partials are the first half of THIS run's
        # digests — carried, not cleared (the reconciler's per-side dedup
        # absorbs any re-executed stage's duplicate records). Job-scoped
        # runs must not clear a concurrent tenant's records (ISSUE 15).
        _audit.begin_run(carry=resume_state is not None, job=jid)
        if resume_state is not None:
            for e, st in resume_state.epochs.items():
                if st.sampled:
                    _audit.seed_sample_count(e, st.sampled)
    if cache_decoded is None:
        cache_decoded = _decode_cache_auto(
            filenames, num_epochs - start_epoch, narrow_to_32, columns
        )
    shared_keys = None
    if cache_decoded and shared_decode_cache_enabled():
        if job is not None:
            # Service plane (ISSUE 15): content-identity keys in the
            # cross-process registry — a concurrent or later job over
            # the same files (any session process) rides these
            # segments, and its claims fence them from the evictor.
            from ray_shuffling_data_loader_tpu.runtime import (
                service as _service,
            )

            shared_keys = [
                _service.cache_key(f, columns, narrow_to_32)
                for f in filenames
            ]
        else:
            # The cross-epoch shared tier: claims hit the process-level
            # registry (cache-hot across shuffle() calls) and resolved
            # refs are promoted into it at run end instead of freed.
            session = runtime.get_context().store.session
            with _SHARED_CACHE_LOCK:
                # Entries keyed by a dead session are unreachable (their
                # segments died with the session's cleanup) — sweep them
                # so a driver cycling runtime sessions can't grow the
                # registry forever.
                for key in [k for k in _SHARED_CACHE if k[0] != session]:
                    del _SHARED_CACHE[key]
            shared_keys = [
                _shared_cache_key(session, f, columns, narrow_to_32)
                for f in filenames
            ]
    decode_cache = _DecodeCache(
        enabled=cache_decoded,
        shared_keys=shared_keys,
        service_job=job if shared_keys is not None else None,
    )
    if resume_state is not None and cache_decoded:
        # Re-attach the preempted run's surviving decode-cache segments
        # so resumed epochs skip Parquet decode (a dead segment simply
        # is not seeded — the claim path re-decodes).
        _seed_decode_cache_from_journal(decode_cache, resume_state)
    start = timeit.default_timer()
    threads = []
    audit_verdicts = None
    try:
        for epoch in range(start_epoch, num_epochs):
            if jmod is not None and jmod.suspend_requested():
                # Preemption notice: stop admitting epochs; the already
                # in-flight windows quiesce at their reducer barriers.
                break
            throttle_start = timeit.default_timer()
            _status_epoch(epoch, state="waiting-admission", job=jid)
            if job is not None:
                # Service-plane admission (ISSUE 15): hold a NEW window
                # back while the shared shm budget is over the
                # admission watermark and other jobs are in flight —
                # concurrent windows must shape to the ledger, not
                # thrash the evictor. Bounded wait, and a job with no
                # window in flight is always admitted (progress).
                from ray_shuffling_data_loader_tpu.runtime import (
                    service as _service,
                )

                _service.admit_epoch(
                    job, epoch, sum(1 for t in threads if t.is_alive())
                )
            # The admission span IS the window throttle: its duration is
            # how long this epoch waited for the oldest in-flight epoch to
            # drain (max_concurrent_epochs backpressure) — on the trace
            # timeline it sits between consecutive epochs' map stages. The
            # context block (not just a span arg) ships the epoch id with
            # the queue-actor call, so the actor-side new_epoch span
            # carries it too.
            with telemetry.context(epoch=epoch):
                with telemetry.trace_span("epoch:admission", cat="queue"):
                    batch_consumer.wait_until_ready(epoch)
            _status_epoch(epoch, state="admitted", job=jid)
            if stats_collector is not None:
                stats_collector.call_oneway(
                    "epoch_throttle",
                    epoch,
                    timeit.default_timer() - throttle_start,
                )
            est = (
                resume_state.epochs.get(epoch)
                if resume_state is not None
                else None
            )
            if est is not None:
                _metrics.safe_inc("recovery.resumed_epochs")
            if rplan is not None and epoch > start_epoch:
                # Epoch-boundary re-plan (ISSUE 20): live /critical +
                # /capacity signals adjust the mutable-mid-run terms
                # before this epoch's tasks are submitted. Best-effort
                # — a telemetry hiccup must never fail the run.
                try:
                    if _planner.replan(rplan, epoch=epoch):
                        task_knobs = rplan.task_knobs()
                except Exception:
                    pass
            threads.append(
                shuffle_epoch(
                    epoch,
                    filenames,
                    batch_consumer,
                    num_reducers,
                    num_trainers,
                    seed=seed,
                    stats_collector=stats_collector,
                    narrow_to_32=narrow_to_32,
                    decode_cache=decode_cache,
                    schedule_log=schedule_log,
                    device_layout=device_layout,
                    columns=columns,
                    plan=plan,
                    journal=journal,
                    est=est,
                    job=job,
                    knobs=task_knobs,
                )
            )
        for t in threads:
            t.join()
        if jmod is not None and jmod.suspend_requested():
            # Every in-flight window quiesced at a reducer barrier and
            # its cursor is journaled: record the suspension, leave the
            # store segments alive (they ARE the suspended window), and
            # either leave with exit code 0 (the SIGTERM path) or raise
            # RunSuspended for embedding drivers/tests.
            for t in threads:
                if t.error is not None:
                    raise t.error
            journal.append("suspended")
            telemetry.emit_event(
                "run.suspended", _flush=True, run_id=journal.run_id,
                journal=journal.path,
            )
            _metrics.safe_inc("recovery.suspended_runs")
            _status_end_trial(error="suspended", job=jid)
            # Ledger record BEFORE the possible os._exit(0) below —
            # a preempted run's partial-epoch telemetry is exactly
            # what the post-hoc regression question needs.
            _ledger_record(
                "suspended",
                duration_s=timeit.default_timer() - start,
                plan=plan, job_id=jid,
            )
            _clear_plan_state()
            # No resume is in progress once the run is suspended: a
            # stuck gauge would page resume_stalled forever in an
            # embedding driver that catches RunSuspended and lives on.
            jmod.set_resume_in_progress(False)
            if jmod.suspend_should_exit():
                jmod.suspend_and_exit(journal)  # os._exit(0)
            jmod.end_run(journal, status="suspended")
            raise jmod.RunSuspended(journal.path)
        decode_cache.free_all()
        batch_consumer.wait_until_all_epochs_done()
        for t in threads:
            if t.error is not None:
                raise t.error
        if _audit.enabled():
            # Epoch-end reconciliation: every map/reduce task has
            # completed and flushed its digest records (flush-before-done
            # ordering in runtime/tasks.py), and consumers have acked
            # every batch — fold all sides, emit per-epoch verdicts +
            # audit.* metrics, and (in RSDL_AUDIT_STRICT mode) raise on
            # any mismatch.
            audit_verdicts = _audit.reconcile(
                range(start_epoch, num_epochs),
                stats_collector=stats_collector,
                plan_label=_label_of_plan(plan),
                job=audit_scope,
            )
            if journal is not None:
                # Epoch-reconcile journal barrier: the per-epoch digest
                # verdicts (incl. the order-sensitive delivered_seq) are
                # what tools/replay.py checks a re-execution against.
                for v in audit_verdicts:
                    journal.append("verdict", **v)
        if journal is not None:
            if resume_state is not None:
                try:
                    _sweep_superseded(resume_state)
                except Exception:
                    pass
            jmod.set_resume_in_progress(False)
            jmod.end_run(journal)
    except BaseException as exc:
        if jmod is not None and isinstance(exc, jmod.RunSuspended):
            raise  # already journaled + reported as suspended
        if journal is not None:
            # Close (but do not complete) the journal: a failed run
            # stays resumable — its completed stages re-attach once the
            # failure cause is fixed. The in-progress gauge clears too:
            # an abandoned resume must not page resume_stalled forever.
            try:
                jmod.set_resume_in_progress(False)
                jmod.end_run(journal, status="failed")
            except Exception:
                pass
        _status_end_trial(error=f"{type(exc).__name__}: {exc}", job=jid)
        telemetry.emit_event(
            "trial.failed", _flush=True,
            error=f"{type(exc).__name__}: {exc}"[:200],
        )
        _ledger_record(
            "failed",
            duration_s=timeit.default_timer() - start,
            error=f"{type(exc).__name__}: {exc}",
            plan=plan, job_id=jid,
            audit_verdicts=audit_verdicts,
        )
        _clear_plan_state()
        raise
    _status_end_trial(job=jid)
    duration = timeit.default_timer() - start
    telemetry.emit_event(
        "trial.done", duration_s=round(duration, 3), _flush=True
    )
    _ledger_record(
        "done", duration_s=duration, plan=plan, job_id=jid,
        audit_verdicts=audit_verdicts,
    )
    _clear_plan_state()
    if stats_collector is not None:
        stats_collector.call_oneway("trial_done", duration)
    return duration
