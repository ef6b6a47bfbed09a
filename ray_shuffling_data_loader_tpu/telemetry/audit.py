"""Data-correctness audit: exactly-once digests + shuffle-quality metrics.

The third telemetry half (ISSUE 2; tracing is :mod:`.trace`, live metrics
:mod:`.metrics`): where those two make the pipeline's *time* visible, this
one proves the *data* is right. Off unless ``RSDL_AUDIT`` is truthy —
every instrumentation site checks :func:`enabled` (one cached boolean)
first, so the disabled pipeline does no digest work at all.

Three mechanisms:

* **Exactly-once coverage digests.** Each stage folds an order-invariant
  streaming digest over the audit key column (``RSDL_AUDIT_KEY``, default
  ``"key"``): per-row splitmix64 hashes combined by XOR and wrapping sum,
  plus a row count. Mappers digest each file's rows (``shuffle_map`` /
  ``shuffle_plan``), reducers digest their permuted output
  (``shuffle_reduce`` / ``shuffle_gather_reduce``), the delivery thread
  digests what it actually hands the consumer, and the trainer-side
  dataset digests what it reads back from the queue+store. Because the
  digest is associative and order-invariant, *map == reduce == delivered*
  holds iff every row survived exactly once — a drop, duplicate, or
  corruption anywhere in between breaks the equality and
  :func:`reconcile` names the failing epoch.

* **Determinism digests.** Delivery and consumption additionally fold an
  order-*sensitive* sequence digest (position-mixed hashes): with a fixed
  seed the per-epoch delivered stream is reproducible, so comparing
  ``delivered_seq`` across two runs is a one-line reproducibility check.

* **Shuffle-quality metrics.** Per epoch, from a sampled prefix of the
  rank-0 delivered stream (``RSDL_AUDIT_SAMPLE`` keys): adjacent-pair
  retention vs. the previous epoch (a broken reshuffle repeats pairs),
  mean normalized displacement (a lazy permutation moves rows barely),
  and per-reducer source-file entropy from the map-side partition counts
  (a degenerate assignment starves reducers of file diversity).

Cross-process transport mirrors the trace spool: worker processes append
records to ``audit-<pid>.jsonl`` under ``RSDL_AUDIT_DIR`` (flushed after
every task, before its result is observable); the driver's
:func:`reconcile` merges every spool plus its own buffer, emits per-epoch
verdicts, and feeds the ``audit.*`` counters/gauges into the
:mod:`.metrics` registry. Verdicts never raise by default (an audit layer
must not sink the run); ``RSDL_AUDIT_STRICT=1`` upgrades a mismatch to
:class:`AuditError`.
"""

from __future__ import annotations

import atexit
import json
import logging
import math
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_shuffling_data_loader_tpu.telemetry import _env

logger = logging.getLogger(__name__)

ENV_AUDIT = "RSDL_AUDIT"
ENV_AUDIT_DIR = "RSDL_AUDIT_DIR"
ENV_AUDIT_KEY = "RSDL_AUDIT_KEY"
ENV_AUDIT_SAMPLE = "RSDL_AUDIT_SAMPLE"
ENV_AUDIT_STRICT = "RSDL_AUDIT_STRICT"

DEFAULT_KEY_COLUMN = "key"
DEFAULT_SAMPLE_KEYS = 4096

_enabled: Optional[bool] = None  # tri-state: None = not yet read from env

_lock = threading.Lock()
_records: List[dict] = []
_verdicts: List[dict] = []
_emitted_epochs: set = set()  # (job, epoch) pairs with metrics emitted
_sample_counts: Dict[Tuple, int] = {}  # (job, epoch) -> keys sampled
_faults: Dict[Tuple[str, int], int] = {}
_atexit_registered = False
_warned_no_key = False


def _ambient_job() -> Optional[str]:
    """The ambient service-plane job id (ISSUE 15), read from the trace
    context via ``sys.modules`` — a single-job process that never
    entered a job context gets None and records stay exactly as before
    (no import, no field)."""
    import sys as _sys

    tr = _sys.modules.get("ray_shuffling_data_loader_tpu.telemetry.trace")
    if tr is None:
        return None
    try:
        job = tr.current_context().get("job")
    except Exception:
        return None
    return None if job is None else str(job)


class AuditError(AssertionError):
    """A digest reconciliation failed under ``RSDL_AUDIT_STRICT``."""


def enabled() -> bool:
    """Is auditing on in this process? Cached after the first env read —
    the audit-off hot path pays one boolean check, no digest work."""
    global _enabled
    if _enabled is None:
        _enabled = _env.read_flag(ENV_AUDIT)
    return _enabled


def enable(spool_dir: Optional[str] = None) -> None:
    """Turn auditing on for this process AND (via the environment) every
    process spawned after this call — like :func:`telemetry.enable`, call
    before ``runtime.init()`` so pool workers inherit it. ``spool_dir``
    is where each process drains its digest records; without one, records
    stay in this process's memory and reconcile covers only this
    process (fine for single-process consumers)."""
    global _enabled
    os.environ[ENV_AUDIT] = "1"
    if spool_dir:
        os.makedirs(spool_dir, exist_ok=True)
        os.environ[ENV_AUDIT_DIR] = spool_dir
    _enabled = True
    _register_atexit()


def disable() -> None:
    global _enabled
    os.environ.pop(ENV_AUDIT, None)
    _enabled = False


def refresh_from_env() -> None:
    """Forget the cached enabled state; the next check re-reads the env
    (test harness hook)."""
    global _enabled
    _enabled = None


def spool_dir() -> Optional[str]:
    return os.environ.get(ENV_AUDIT_DIR) or None


def key_column_name() -> str:
    return os.environ.get(ENV_AUDIT_KEY, DEFAULT_KEY_COLUMN)


def _sample_cap() -> int:
    try:
        return int(os.environ.get(ENV_AUDIT_SAMPLE, str(DEFAULT_SAMPLE_KEYS)))
    except ValueError:
        return DEFAULT_SAMPLE_KEYS


def strict() -> bool:
    return _env.read_flag(ENV_AUDIT_STRICT)


# ---------------------------------------------------------------------------
# Digest math (vectorized, uint64 wrapping)
# ---------------------------------------------------------------------------

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_U64 = np.uint64(0xFFFFFFFFFFFFFFFF)
# Distinct domain for POSITION hashing in the seq digest. Positions must
# not hash like keys: with the common row-id key scheme (key == 0..N-1)
# a shared domain makes a row at its own key index contribute
# mix(h ^ h) = mix(0), and a key<->position crossed swap contribute the
# same value twice — cancelling under XOR, so a sorted stream and its
# reversal would digest to the same seq.
_POS_SALT = np.uint64(0xD1B54A32D192ED03)


def _mix(z: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — a cheap, well-mixed 64-bit permutation."""
    with np.errstate(over="ignore"):
        z = (z ^ (z >> np.uint64(30))) * _MIX1
        z = (z ^ (z >> np.uint64(27))) * _MIX2
        return z ^ (z >> np.uint64(31))


def hash_keys(arr: np.ndarray) -> np.ndarray:
    """Per-row uint64 hashes of a key column. Integers hash their 64-bit
    two's-complement bits; floats their IEEE-754 bits — so equal key
    VALUES hash equally regardless of 32/64-bit narrowing for ints."""
    a = np.asarray(arr)
    if a.dtype.kind == "f":
        bits = np.ascontiguousarray(a, dtype=np.float64).view(np.uint64)
    elif a.dtype.kind in "iub":
        bits = np.ascontiguousarray(a.astype(np.int64, copy=False)).view(
            np.uint64
        )
    else:
        raise TypeError(f"unsupported audit key dtype {a.dtype}")
    with np.errstate(over="ignore"):
        return _mix(bits + _GOLDEN)


class StreamDigest:
    """Order-invariant (count/xor/sum) + order-sensitive (seq) streaming
    digest over key batches. Associative in the invariant parts, so
    map-side digests folded across files equal reduce-side digests folded
    across reducers when and only when coverage is exactly-once. ``seq``
    mixes each hash with its GLOBAL stream position, so two streams with
    the same rows in a different order get different ``seq``."""

    __slots__ = ("count", "xor", "sum", "seq")

    def __init__(self, count: int = 0, xor: int = 0, sum: int = 0,
                 seq: int = 0):
        self.count = int(count)
        self.xor = int(xor)
        self.sum = int(sum)
        self.seq = int(seq)

    def update(self, keys: np.ndarray, offset: Optional[int] = None) -> None:
        """Fold one batch of keys. ``offset`` is the batch's starting
        position in its stream (None skips the seq component)."""
        h = hash_keys(keys)
        n = len(h)
        if n == 0:
            return
        self.count += n
        self.xor ^= int(np.bitwise_xor.reduce(h))
        with np.errstate(over="ignore"):
            self.sum = int(
                (np.uint64(self.sum) + np.add.reduce(h, dtype=np.uint64))
                & _U64
            )
            if offset is not None:
                pos = np.arange(offset, offset + n, dtype=np.uint64)
                g = _mix(h ^ _mix(pos ^ _POS_SALT))
                self.seq ^= int(np.bitwise_xor.reduce(g))

    def merge(self, other: "StreamDigest") -> None:
        self.count += other.count
        self.xor ^= other.xor
        self.sum = (self.sum + other.sum) & int(_U64)
        self.seq ^= other.seq

    def coverage(self) -> Tuple[int, int, int]:
        """The order-invariant identity: equal coverage tuples mean the
        same multiset of rows."""
        return (self.count, self.xor, self.sum)

    def hex(self) -> str:
        return f"{self.xor:016x}:{self.sum:016x}"


# ---------------------------------------------------------------------------
# Record capture (called from instrumentation sites; audit-on only)
# ---------------------------------------------------------------------------


def _keys_of(columns) -> Optional[np.ndarray]:
    """The audit key column of a batch, or None (warned once) when the
    dataset has no such column OR its dtype is unhashable — audit then
    skips that batch rather than guessing a key, producing meaningless
    digests, or spamming a per-batch traceback."""
    global _warned_no_key
    name = key_column_name()
    try:
        keys = columns[name]
    except (KeyError, IndexError, TypeError):
        keys = None
    if keys is not None and np.asarray(keys).dtype.kind not in "fiub":
        keys = None  # string/object keys: hash_keys cannot digest them
    if keys is None:
        if not _warned_no_key:
            _warned_no_key = True
            logger.warning(
                "audit: key column %r not present (or not a numeric "
                "dtype); digests skipped for batches without it (set "
                "%s)", name, ENV_AUDIT_KEY,
            )
        return None
    return keys


def _append(record: dict) -> None:
    _register_atexit()
    with _lock:
        _records.append(record)


def _digest_record(
    side: str, epoch: int, columns, offset: Optional[int] = None,
    **extra: Any,
) -> Optional[dict]:
    """The shared digest-build-append body behind every record_* site:
    resolve keys, fold one StreamDigest, append the flat record. Returns
    the record (for callers that attach more fields) or None when the
    batch had no usable key column."""
    keys = _keys_of(columns)
    if keys is None:
        return None
    d = StreamDigest()
    d.update(keys, offset=offset)
    rec: Dict[str, Any] = {
        "side": side,
        "epoch": int(epoch),
        "count": d.count,
        "xor": d.xor,
        "sum": d.sum,
        **extra,
    }
    job = _ambient_job()
    if job is not None:
        # Multi-job service (ISSUE 15): scope the digest to its tenant
        # so concurrent jobs' same-numbered epochs never fold together.
        rec["job"] = job
    if offset is not None:
        rec["offset"] = int(offset)
        rec["seq"] = d.seq
    _append(rec)
    return rec


def record_map(
    epoch: int,
    file_index: int,
    columns,
    per_reducer=None,
) -> None:
    """Map-side digest of one input file's rows, plus the per-reducer
    partition counts (source-file entropy input) — pass the counts the
    map stage already computed (scatter offsets / plan bincount) rather
    than re-deriving them. Runs in the map task's worker process; never
    raises into the data path."""
    try:
        extra: Dict[str, Any] = {"file": int(file_index)}
        if per_reducer is not None:
            extra["per_reducer"] = [int(c) for c in per_reducer]
        _digest_record("map", epoch, columns, **extra)
    except Exception:
        logger.warning("audit: map digest failed", exc_info=True)


def record_reduce(epoch: int, reducer: int, columns) -> None:
    """Reduce-side digest of one reducer's permuted output segment."""
    try:
        _digest_record("reduce", epoch, columns, reducer=int(reducer))
    except Exception:
        logger.warning("audit: reduce digest failed", exc_info=True)


def record_deliver(
    epoch: int, reducer: int, rank: int, columns, offset: int
) -> None:
    """Delivery-side digest of one reducer output exactly as handed to the
    consumer (driver deliver thread). ``offset`` is the batch's starting
    row position in the rank's delivered stream (seq determinism). Also
    collects the rank-0 sampled key prefix the quality metrics use."""
    try:
        extra: Dict[str, Any] = {"reducer": int(reducer), "rank": int(rank)}
        keys = _keys_of(columns) if rank == 0 else None
        if keys is not None:
            # Sample extras are attached BEFORE the append: a record must
            # never mutate after it becomes visible to a concurrent flush.
            # The sample cap is per (job, epoch): two concurrent jobs'
            # rank-0 streams must each keep a full quality sample.
            skey = (_ambient_job(), int(epoch))
            with _lock:
                taken = _sample_counts.get(skey, 0)
                want = _sample_cap() - taken
            if want > 0:
                sample = np.asarray(keys)[:want]
                extra["keys"] = [
                    float(k) if isinstance(k, float) else int(k)
                    for k in sample.tolist()
                ]
                with _lock:
                    _sample_counts[skey] = taken + len(sample)
        _digest_record("deliver", epoch, columns, offset=offset, **extra)
    except Exception:
        logger.warning("audit: deliver digest failed", exc_info=True)


def record_consume(epoch: int, rank: int, columns, offset: int) -> None:
    """Consumption-side digest of one queue batch as read back from the
    store by the trainer-side dataset."""
    try:
        _digest_record(
            "consume", epoch, columns, offset=offset, rank=int(rank)
        )
    except Exception:
        logger.warning("audit: consume digest failed", exc_info=True)


def record_staged(epoch: int, rank: int, columns, offset: int) -> None:
    """Device-staging digest of ONE post-rebatch batch (JAX stager).
    Recorded per batch, before the stager pulls the next item — so every
    staged record is appended before the underlying dataset's final acks
    let the driver reconcile (an epoch-end aggregate would race the
    reconciler and silently skip the staged==delivered check). With
    ``drop_last`` the tail rows legitimately differ from the delivered
    count — reconcile compares digests only when the counts match."""
    try:
        _digest_record(
            "staged", epoch, columns, offset=offset, rank=int(rank)
        )
    except Exception:
        logger.warning("audit: staged digest failed", exc_info=True)


# ---------------------------------------------------------------------------
# Fault injection (tests only)
# ---------------------------------------------------------------------------


def inject_fault(kind: str, epoch: int, count: int = 1) -> None:
    """Arm a test-only fault. ``kind="drop-row"`` makes the delivery path
    silently drop the last row of ``count`` reducer outputs in ``epoch``
    — the injected defect the reconciler must catch."""
    with _lock:
        _faults[(kind, int(epoch))] = count


def take_fault(kind: str, epoch: int) -> bool:
    """Consume one armed fault occurrence; False when none is armed."""
    with _lock:
        left = _faults.get((kind, int(epoch)), 0)
        if left <= 0:
            return False
        _faults[(kind, int(epoch))] = left - 1
        return True


def clear_faults() -> None:
    with _lock:
        _faults.clear()


# ---------------------------------------------------------------------------
# Spool + lifecycle
# ---------------------------------------------------------------------------


def _register_atexit() -> None:
    global _atexit_registered
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(flush)


def flush() -> None:
    """Drain this process's record buffer to its spool file. No-op
    without a spool directory (records then stay in memory for a local
    reconcile)."""
    directory = spool_dir()
    if not directory:
        return
    with _lock:
        if not _records:
            return
        drained = list(_records)
        _records.clear()
    try:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"audit-{os.getpid()}.jsonl")
        with open(path, "a") as f:
            for rec in drained:
                f.write(json.dumps(rec) + "\n")
    except OSError:
        # The audit layer must never sink the run; the records are lost.
        pass


def safe_flush() -> None:
    """Guarded flush for process-teardown paths (task done): no-op when
    auditing is off, never raises."""
    if not enabled():
        return
    try:
        flush()
    except Exception:
        pass


def reset(clear_spool: bool = False) -> None:
    """Drop buffered records, verdicts, and samples (tests and run
    boundaries). Armed faults survive — they are injected BEFORE the run
    whose :func:`begin_run` calls this; use :func:`clear_faults`.
    ``clear_spool`` also unlinks every spool file."""
    with _lock:
        _records.clear()
        _verdicts.clear()
        _emitted_epochs.clear()
        _sample_counts.clear()
    if clear_spool:
        directory = spool_dir()
        if directory and os.path.isdir(directory):
            for fname in os.listdir(directory):
                if fname.startswith("audit-") and fname.endswith(".jsonl"):
                    try:
                        os.unlink(os.path.join(directory, fname))
                    except OSError:
                        pass


def begin_run(carry: bool = False, job: Optional[str] = None) -> None:
    """Mark the start of one audited shuffle run: previous records (local
    and spooled) would otherwise fold into this run's digests. Called by
    ``shuffle()`` when auditing is on — one audited run per spool dir at
    a time.

    ``carry=True`` (a journal resume, runtime/journal.py): the spool is
    the ONE thing kept — the preempted attempt's digest records are the
    first half of this run's digests, and clearing them would make
    every partially-delivered epoch reconcile as a false mismatch. The
    local buffer/verdict state still resets (this is a fresh process's
    run boundary).

    ``job`` (the multi-job service, ISSUE 15): a job-scoped run must
    NOT clear shared state while a CONCURRENT tenant's in-flight
    records live in the same buffer and spool — its records are
    job-stamped and its reconcile is job-filtered, and job ids are
    never reused. But a resident service driver running tenants
    sequentially would otherwise grow the spool without bound (every
    finished job's records are provably dead), so when this job is the
    SOLE live tenant session-wide the classic full reset runs —
    bounded state, identical semantics."""
    if job is not None:
        if not carry:
            try:
                from ray_shuffling_data_loader_tpu.runtime import (
                    service as _service,
                )

                # <= 1: this job itself registered before begin_run.
                if _service.live_jobs_count() <= 1:
                    reset(clear_spool=True)
                    return
            except Exception:
                pass  # can't prove sole tenancy: keep everything
        with _lock:
            _emitted_epochs.difference_update(
                {k for k in _emitted_epochs if k[0] == job}
            )
            for k in [k for k in _sample_counts if k[0] == job]:
                del _sample_counts[k]
        return
    reset(clear_spool=not carry)


def seed_sample_count(epoch: int, taken: int) -> None:
    """Resume carry-forward for the rank-0 quality sample: the journaled
    run already took ``taken`` sample keys for ``epoch`` (they ride its
    spooled deliver records), so this process's cap accounting must
    start there, not at zero — the combined sample stays one capped
    prefix of the rank-0 stream. Keyed by the ambient job like the
    records themselves."""
    skey = (_ambient_job(), int(epoch))
    with _lock:
        _sample_counts[skey] = max(_sample_counts.get(skey, 0), int(taken))


def sample_count(epoch: int) -> int:
    """Sample keys taken so far for ``epoch`` (journal barrier reads
    this so a resumed run can seed it back)."""
    with _lock:
        return _sample_counts.get((_ambient_job(), int(epoch)), 0)


def _load_records() -> List[dict]:
    """This process's buffer plus every spool file's records."""
    with _lock:
        out = list(_records)
    directory = spool_dir()
    if directory and os.path.isdir(directory):
        for fname in sorted(os.listdir(directory)):
            if not (fname.startswith("audit-") and fname.endswith(".jsonl")):
                continue
            try:
                with open(os.path.join(directory, fname)) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            continue  # torn concurrent append; skip
            except OSError:
                continue
    return out


# ---------------------------------------------------------------------------
# Reconciliation
# ---------------------------------------------------------------------------


# One record per logical unit of work per side: the cluster scheduler
# retries a map/reduce task on another agent when its first agent dies,
# and the first attempt may already have flushed its digest record —
# folding both would inflate one side and report a false mismatch on a
# run whose data was delivered exactly once.
_DEDUP_KEYS = {
    "map": ("file",),
    "reduce": ("reducer",),
    "deliver": ("rank", "reducer", "offset"),
    "consume": ("rank", "offset"),
    "staged": ("rank", "offset"),
}


def _dedup(side: str, recs: Sequence[dict]) -> List[dict]:
    fields = _DEDUP_KEYS[side]
    seen: Dict[tuple, dict] = {}
    for r in recs:
        seen.setdefault(tuple(r.get(f) for f in fields), r)
    return list(seen.values())


def _fold(recs: Sequence[dict]) -> StreamDigest:
    d = StreamDigest()
    for r in recs:
        d.merge(
            StreamDigest(
                r.get("count", 0), r.get("xor", 0), r.get("sum", 0),
                r.get("seq", 0),
            )
        )
    return d


def _rank_mixed_seq(recs: Sequence[dict]) -> int:
    """Combine per-batch seq digests across ranks: each batch's seq is
    already position-mixed within its rank's stream; mixing in the rank
    id keeps distinct ranks' streams from cancelling."""
    out = np.uint64(0)
    for r in recs:
        with np.errstate(over="ignore"):
            out ^= _mix(
                np.uint64(r.get("seq", 0))
                ^ _mix(np.uint64(r.get("rank", 0)) + _GOLDEN)
            )
    return int(out)


def _adjacent_pairs(seq: Sequence) -> set:
    return {(seq[i], seq[i + 1]) for i in range(len(seq) - 1)}


def _quality(
    cur_sample: List, prev_sample: Optional[List]
) -> Dict[str, Optional[float]]:
    out: Dict[str, Optional[float]] = {
        "adjacent_pair_retention": None,
        "mean_normalized_displacement": None,
    }
    if prev_sample and len(cur_sample) > 1 and len(prev_sample) > 1:
        cur_pairs = _adjacent_pairs(cur_sample)
        prev_pairs = _adjacent_pairs(prev_sample)
        out["adjacent_pair_retention"] = len(cur_pairs & prev_pairs) / max(
            1, len(cur_pairs)
        )
        pos_prev = {k: i for i, k in enumerate(prev_sample)}
        disp = [
            abs(i - pos_prev[k])
            for i, k in enumerate(cur_sample)
            if k in pos_prev
        ]
        if disp:
            out["mean_normalized_displacement"] = float(
                np.mean(disp) / max(1, len(prev_sample))
            )
    return out


def _entropy(map_recs: Sequence[dict]) -> Dict[str, Optional[float]]:
    """Per-reducer source-file entropy, normalized to [0, 1] by log(F):
    1.0 = every reducer draws evenly from every file; 0.0 = some reducer
    is fed by a single file (a degenerate partition)."""
    rows = [r["per_reducer"] for r in map_recs if r.get("per_reducer")]
    if not rows or len({len(r) for r in rows}) != 1:
        return {"source_entropy_mean": None, "source_entropy_min": None}
    mat = np.asarray(rows, dtype=np.float64)  # files x reducers
    num_files = mat.shape[0]
    if num_files < 2:
        return {"source_entropy_mean": 1.0, "source_entropy_min": 1.0}
    totals = mat.sum(axis=0)
    ents = []
    for r in range(mat.shape[1]):
        if totals[r] <= 0:
            continue
        p = mat[:, r] / totals[r]
        p = p[p > 0]
        ents.append(float(-(p * np.log(p)).sum() / math.log(num_files)))
    if not ents:
        return {"source_entropy_mean": None, "source_entropy_min": None}
    return {
        "source_entropy_mean": float(np.mean(ents)),
        "source_entropy_min": float(np.min(ents)),
    }


def _emit_metrics(verdict: dict) -> None:
    """Fold one epoch's verdict into the live-metrics registry (PR-1
    vocabulary) — once per epoch, only when the metrics half is on."""
    from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

    if not _metrics.enabled():
        return
    epoch = verdict["epoch"]
    job = verdict.get("job")
    with _lock:
        if (job, epoch) in _emitted_epochs:
            return
        _emitted_epochs.add((job, epoch))
    # Per-job label only on job-scoped runs: single-job series keep
    # their exact historical shape (the zero-overhead-off contract).
    jl: Dict[str, Any] = {"job": job} if job is not None else {}
    reg = _metrics.registry
    reg.counter("audit.rows_mapped", **jl).inc(verdict["rows_mapped"])
    reg.counter("audit.rows_reduced", **jl).inc(verdict["rows_reduced"])
    reg.counter("audit.rows_delivered", **jl).inc(
        verdict["rows_delivered"]
    )
    # Resolve up front so a clean run reports 0.0, not a missing key.
    mism = reg.counter("audit.digest_mismatch", **jl)
    if verdict["ok"] is False:
        mism.inc()
    reg.gauge("audit.epoch_ok", epoch=epoch, **jl).set(
        1.0 if verdict["ok"] else 0.0
    )
    # Shuffle-quality gauges carry the run's plan family (ISSUE 12):
    # block plans trade dispersion for prunability, and the tradeoff
    # must be measurable per run — a quality regression after a plan
    # switch should name the plan, not hide in an unlabeled gauge. The
    # label rides the verdict (threaded by the reconcile caller from
    # the driver-resolved spec); "unknown" when no caller recorded it —
    # never a silently-wrong "rowwise".
    plan = verdict.get("plan") or "unknown"
    for name in (
        "adjacent_pair_retention",
        "mean_normalized_displacement",
        "source_entropy_mean",
        "source_entropy_min",
    ):
        value = verdict.get(name)
        if value is not None:
            reg.gauge(f"audit.{name}", epoch=epoch, plan=plan, **jl).set(
                value
            )


def reconcile(
    epochs: Optional[Sequence[int]] = None,
    stats_collector=None,
    plan_label: Optional[str] = None,
    job=None,
) -> List[dict]:
    """Fold every visible record into per-epoch verdicts: map-side ==
    reduce-side == delivered-side coverage (and consumed-side when every
    delivering rank also reported consumption), plus the quality metrics.
    Emits ``audit.*`` counters/gauges, forwards each verdict to the stats
    collector (``audit_epoch``), logs mismatches, and — under
    ``RSDL_AUDIT_STRICT`` — raises :class:`AuditError` naming the failing
    epochs. Idempotent per epoch for the metric side-effects.

    ``plan_label``: the run's resolved shuffle-plan family
    (``rowwise`` / ``block:G``, ISSUE 12) — the driver threads the spec
    it resolved rather than this process's env, so an offline or
    env-divergent reconcile cannot mislabel the quality gauges; None
    falls back to this process's env, and on any parse failure the
    verdicts carry ``unknown`` (never a silently-wrong default).

    ``job`` (the multi-job service, ISSUE 15): reconcile exactly ONE
    tenant's records — a concurrent job's same-numbered epochs are a
    different stream, and folding them together would report a false
    mismatch on two correct runs. A sequence of ids is one tenant's
    RESUME CHAIN (job ids change across restarts; the preempted
    attempts' carried records stamp the old ids) — the verdicts carry
    the newest id. ``None`` keeps the historical behavior (every
    record folds), which is correct exactly when the process runs one
    job at a time."""
    if plan_label is None:
        try:
            from ray_shuffling_data_loader_tpu.utils import (
                shuffle_plan_label,
            )

            plan_label = shuffle_plan_label()
        except Exception:
            plan_label = "unknown"
    flush()  # our own records join the spool view
    recs = _load_records()
    if job is not None:
        if isinstance(job, str):
            wanted = {job}
        else:
            chain = [str(j) for j in job]
            wanted = set(chain)
            job = chain[-1]  # verdicts/gauges carry the newest attempt
        recs = [r for r in recs if r.get("job") in wanted]
    by_epoch: Dict[int, List[dict]] = {}
    for r in recs:
        by_epoch.setdefault(int(r.get("epoch", -1)), []).append(r)
    if epochs is None:
        epoch_list = sorted(e for e in by_epoch if e >= 0)
    else:
        epoch_list = sorted(set(int(e) for e in epochs))
    verdicts: List[dict] = []
    prev_sample: Optional[List] = None
    for epoch in epoch_list:
        erecs = by_epoch.get(epoch, [])
        sides = {
            side: _dedup(
                side, [r for r in erecs if r.get("side") == side]
            )
            for side in ("map", "reduce", "deliver", "consume", "staged")
        }
        mapped = _fold(sides["map"])
        reduced = _fold(sides["reduce"])
        delivered = _fold(sides["deliver"])
        consumed = _fold(sides["consume"])
        staged = _fold(sides["staged"])
        mismatch: List[str] = []
        if not sides["map"] and not sides["reduce"] and not sides["deliver"]:
            verdict_nr: Dict[str, Any] = {
                "epoch": epoch,
                "ok": None,
                "detail": "no records",
                "rows_mapped": 0,
                "rows_reduced": 0,
                "rows_delivered": 0,
            }
            if job is not None:
                verdict_nr["job"] = job
            verdicts.append(verdict_nr)
            prev_sample = None
            continue
        if not sides["map"] and not sides["reduce"]:
            # Delivery recorded but no worker-side records at all: the
            # workers' spool is not visible here (multi-host run without
            # a shared RSDL_AUDIT_DIR). That is an incomplete audit, not
            # a data defect — flagging it as a mismatch would abort
            # healthy strict-mode runs.
            verdict_inc: Dict[str, Any] = {
                "epoch": epoch,
                "ok": None,
                "detail": "map/reduce records missing (is "
                "RSDL_AUDIT_DIR on a filesystem shared with the "
                "workers?)",
                "rows_mapped": 0,
                "rows_reduced": 0,
                "rows_delivered": delivered.count,
            }
            if job is not None:
                verdict_inc["job"] = job
            verdicts.append(verdict_inc)
            prev_sample = None
            continue
        if reduced.coverage() != mapped.coverage():
            mismatch.append("reduce")
        if delivered.coverage() != reduced.coverage():
            mismatch.append("delivered")
        deliver_ranks = {r.get("rank") for r in sides["deliver"]}
        consume_ranks = {r.get("rank") for r in sides["consume"]}
        consumed_complete = bool(sides["consume"]) and (
            consume_ranks >= deliver_ranks
        )
        if consumed_complete and consumed.coverage() != delivered.coverage():
            mismatch.append("consumed")
        if (
            sides["staged"]
            and staged.count == delivered.count
            and staged.coverage() != delivered.coverage()
        ):
            mismatch.append("staged")
        ordered = sorted(
            sides["deliver"],
            key=lambda r: (r.get("rank", 0), r.get("offset", 0)),
        )
        sample: List = []
        for r in ordered:
            if r.get("rank") == 0 and "keys" in r:
                sample.extend(r["keys"])
        verdict: Dict[str, Any] = {
            "epoch": epoch,
            "ok": not mismatch,
            "mismatch": mismatch,
            "rows_mapped": mapped.count,
            "rows_reduced": reduced.count,
            "rows_delivered": delivered.count,
            "rows_consumed": consumed.count if sides["consume"] else None,
            "rows_staged": staged.count if sides["staged"] else None,
            "map_digest": mapped.hex(),
            "reduce_digest": reduced.hex(),
            "delivered_digest": delivered.hex(),
            "delivered_seq": f"{_rank_mixed_seq(sides['deliver']):016x}",
            "consumed_digest": (
                consumed.hex() if sides["consume"] else None
            ),
            "plan": plan_label,
        }
        if job is not None:
            verdict["job"] = job
        verdict.update(_quality(sample, prev_sample))
        verdict.update(_entropy(sides["map"]))
        prev_sample = sample or None
        verdicts.append(verdict)
        _emit_metrics(verdict)
        if stats_collector is not None:
            try:
                stats_collector.call_oneway("audit_epoch", epoch, verdict)
            except Exception:
                pass
        if mismatch:
            logger.error(
                "audit: epoch %d digest mismatch at %s — mapped=%d "
                "reduced=%d delivered=%d (%s / %s / %s)",
                epoch, ",".join(mismatch), mapped.count, reduced.count,
                delivered.count, mapped.hex(), reduced.hex(),
                delivered.hex(),
            )
    with _lock:
        if job is None:
            _verdicts[:] = verdicts
        else:
            # Replace only this tenant's verdicts: a concurrent job's
            # reconcile must not clobber another's last view.
            _verdicts[:] = [
                v for v in _verdicts if v.get("job") != job
            ] + verdicts
    bad = [v["epoch"] for v in verdicts if v["ok"] is False]
    if bad and strict():
        raise AuditError(
            f"audit digest mismatch in epoch(s) {bad}; see verdicts"
        )
    return verdicts


def verdicts() -> List[dict]:
    """The last reconcile's per-epoch verdicts (copies)."""
    with _lock:
        return [dict(v) for v in _verdicts]


def summary(reconcile_if_needed: bool = True) -> dict:
    """One embeddable dict: overall ok + the per-epoch verdicts — the
    shape ``tools/audit_report.py --audit-json`` reads. Nothing in the
    program calls it."""
    out = verdicts()
    if not out and reconcile_if_needed:
        try:
            out = reconcile()
        except AuditError:
            out = verdicts()
        except Exception:
            out = []
    # Overall ok is None unless at least one epoch actually reconciled:
    # a run where every verdict is ok=None (wrong key column, unshared
    # spool) was NOT verified, and reporting true would let an audit
    # gate pass with zero coverage.
    audited = [v for v in out if v.get("ok") is not None]
    return {
        "ok": (
            all(v["ok"] for v in audited) if audited else None
        ),
        "mismatch_epochs": [v["epoch"] for v in out if v.get("ok") is False],
        "epochs": out,
    }
