"""Host-side shared-memory object store.

This is the data plane of the runtime: the TPU-native replacement for Ray's
plasma object store (used by the reference for every shuffle intermediate and
for batch delivery — reference ``dataset.py:136-139``, ``shuffle.py:112-124``).
Bulk data never transits the control-plane sockets; producers write columnar
buffers into per-object shared-memory segments and ship only small
:class:`ObjectRef` handles (the reference ships ``ray.ObjectRef`` lists through
its queue actor, ``dataset.py:195-196``).

Design (TPU-first, not a port):

* Objects are **columnar**: a batch is a set of named, dtype-tagged,
  contiguous 64-byte-aligned buffers. This is the layout ``jax.device_put``
  wants — a reducer output can be staged into HBM without any row-wise
  re-packing (the reference instead passes pandas DataFrames and pays
  ``pd.concat``/``torch.as_tensor`` copies, ``torch_dataset.py:223``).
* Segments are plain files in ``/dev/shm`` mapped with ``mmap`` — the same
  mechanism a C++ store would use (``shm_open``), zero-copy across processes,
  and free of the CPython ``resource_tracker`` bookkeeping that
  ``multiprocessing.shared_memory`` imposes.
* Reads return **zero-copy numpy views** over the mapping; the mapping is kept
  alive by the returned :class:`ColumnBatch`.

The store has no server process: the filesystem is the index. Utilization
introspection (`store_stats`) replaces the reference's raylet
``FormatGlobalMemoryInfo`` gRPC probe (``stats.py:675-683``).
"""

from __future__ import annotations

import json
import mmap
import os
import secrets
import struct
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

from . import transport as _transport

# Fault-injection plane (ISSUE 14 gate-integrity): lazy proxy — the
# store's fault sites pay one proxy getattr, the import happens only if
# a site actually runs.
from ray_shuffling_data_loader_tpu._lazy import lazy_module

faults = lazy_module("ray_shuffling_data_loader_tpu.runtime.faults")

_MAGIC = b"RSDL1\x00"
_ALIGN = 64
_HEADER = struct.Struct("<6sI")  # magic, json length


def _default_shm_dir() -> str:
    d = os.environ.get("RSDL_SHM_DIR")
    if d:
        return d
    if os.path.isdir("/dev/shm"):
        return "/dev/shm"
    import tempfile

    return tempfile.gettempdir()


def _default_spill_dir() -> str:
    d = os.environ.get("RSDL_SPILL_DIR")
    if d:
        return d
    import tempfile

    return os.path.join(tempfile.gettempdir(), "rsdl-spill")


_spill_event_last = 0.0
_SPILL_EVENT_INTERVAL_S = 5.0
_spill_lock = threading.Lock()
_spill_pending_bytes = 0
_spill_pending_events = 0


def _emit_spill_event(nbytes: int) -> None:
    """Structured event-log mark that the store hit its budget and
    started spilling. Rate-limited: a budget-pinned run places *every*
    segment on disk, and one event per 5 s per process tells the story
    without flooding the log — but the VOLUME stays exact: every call
    increments the ``store.spill_bytes_total`` counter, and the bytes
    of suppressed calls accumulate onto the next emitted event's
    ``nbytes`` (with the fold count in ``events_folded``), so summing
    the event log reproduces the true spill total. Metrics-gated
    inside emit_event/safe_inc."""
    global _spill_event_last, _spill_pending_bytes, _spill_pending_events
    _metrics.safe_inc("store.spill_bytes_total", float(nbytes))
    now = time.monotonic()
    with _spill_lock:
        _spill_pending_bytes += int(nbytes)
        _spill_pending_events += 1
        if now - _spill_event_last < _SPILL_EVENT_INTERVAL_S:
            return
        _spill_event_last = now
        pending, _spill_pending_bytes = _spill_pending_bytes, 0
        folded, _spill_pending_events = _spill_pending_events, 0
    try:
        from ray_shuffling_data_loader_tpu import telemetry

        telemetry.emit_event(
            "store.spill", nbytes=int(pending), events_folded=int(folded)
        )
    except Exception:
        pass


def _ledger_note(op: str, object_id: str, nbytes: int = 0,
                 tier: Optional[str] = None, ids=None) -> None:
    """Capacity-ledger hook (telemetry.capacity): one cached boolean
    when metrics are off — the module is never imported and the store
    path pays nothing; never raises."""
    if not _metrics.enabled():
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import capacity

        capacity.note(op, object_id, nbytes=nbytes, tier=tier, ids=ids)
    except Exception:
        pass


def _default_capacity_bytes(shm_dir: str) -> Optional[int]:
    """Session budget for shared-memory residency. ``RSDL_STORE_CAPACITY_BYTES``
    absolute, else ``RSDL_STORE_CAPACITY_FRACTION`` (default 0.8) of the
    filesystem size — the reference provisions its object store explicitly
    per node with spilling disabled (reference
    ``benchmarks/cluster.yaml:171-181``); here the default caps tmpfs use
    below the cliff where the kernel OOM-kills or ENOSPCs mid-epoch."""
    env = os.environ.get("RSDL_STORE_CAPACITY_BYTES")
    if env:
        return int(env) if int(env) > 0 else None
    frac = float(os.environ.get("RSDL_STORE_CAPACITY_FRACTION", "0.8"))
    try:
        st = os.statvfs(shm_dir)
        return int(st.f_blocks * st.f_frsize * frac)
    except OSError:
        return None


def fetch_window_depth(default: int = 8) -> int:
    """The ONE parser of ``RSDL_FETCH_WINDOW_DEPTH``, the window-
    pipelining depth knob. Call sites pass their own default (the
    overlapped reduce uses 4 — it also bounds peak fetched-cache
    residency there; the delivery-plane prefetch pool uses 8); the env
    var, when set, overrides both."""
    env = os.environ.get("RSDL_FETCH_WINDOW_DEPTH")
    if not env:
        return default
    try:
        return max(1, int(env))
    except ValueError:
        return default


class GrowingThreadPool:
    """A ThreadPoolExecutor that widens on demand — the shared grow
    mechanism for the store's prefetch pool and the cluster client's
    striped-fetch pool (both bind a width on first use that a later,
    wider caller must be able to raise).

    Growth is by replacement, and replaced pools are RETIRED, never shut
    down: a submit racing a grow may land on the old pool, and a closed
    executor would turn that into a spurious ``RuntimeError``. Retired
    pools drain their queues then idle — bounded at one small pool per
    distinct growth step — until :meth:`shutdown`."""

    def __init__(self, thread_name_prefix: str):
        self._prefix = thread_name_prefix
        self._lock = threading.Lock()
        self._pool = None
        self._retired: list = []
        self.width = 0

    def ensure(self, width: int) -> "GrowingThreadPool":
        """Make the pool at least ``width`` wide; returns self (usable
        wherever an executor's ``submit`` is expected)."""
        import concurrent.futures

        with self._lock:
            if self._pool is None or width > self.width:
                if self._pool is not None:
                    self._retired.append(self._pool)
                self._pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=width, thread_name_prefix=self._prefix
                )
                self.width = width
        return self

    def submit(self, fn, *args, **kwargs):
        with self._lock:
            if self._pool is None:
                raise RuntimeError("GrowingThreadPool: ensure() not called")
            pool = self._pool
        return pool.submit(fn, *args, **kwargs)

    def shutdown(self, wait: bool = False) -> None:
        with self._lock:
            pools, self._retired = list(self._retired), []
            if self._pool is not None:
                pools.append(self._pool)
                self._pool = None
            self.width = 0
        for pool in pools:
            pool.shutdown(wait=wait)


class ObjectLostError(FileNotFoundError):
    """A store object's segment is gone (freed early, host died holding
    the only copy, or an injected ``store.get:lost`` fault). Carries the
    object id so the shuffle driver's lineage recovery can re-execute
    the producing task instead of failing the epoch. Subclasses
    ``FileNotFoundError`` so pre-existing ``except OSError`` paths keep
    working."""

    def __init__(self, object_id: str, detail: str = ""):
        msg = f"store object {object_id!r} lost"
        if detail:
            msg = f"{msg} ({detail})"
        super().__init__(2, msg)
        self.object_id = object_id
        self._detail = detail

    def __reduce__(self):
        # OSError's default reduce would replay (2, msg) into our
        # (object_id, detail) signature; preserve the real fields.
        return (type(self), (self.object_id, self._detail))


class ObjectCorruptError(ObjectLostError):
    """A segment exists but its payload failed validation (bad magic, or
    an injected ``store.get:corrupt`` fault). Recovery is identical to a
    lost object: re-materialize from lineage."""

    def __init__(self, object_id: str, detail: str = "corrupt payload"):
        super().__init__(object_id, detail)


def _align(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _plan_layout(
    spec: Mapping[str, Tuple[Tuple[int, ...], "np.dtype"]],
    layout: Optional[dict] = None,
):
    """The single source of truth for the segment format: per-column meta,
    payload start, and total size for a ``{name: (shape, dtype)}`` spec.
    Used by the disk write path (``create_columns``) and the DCN wire path
    (``serialize_columns``) so the two can never drift.

    ``layout`` is an optional JSON-safe descriptor carried in the meta
    blob — device-direct delivery stamps reducer outputs with their
    staging layout (``{"kind": "device-batch", "batch": B, "columns":
    [...], "dtypes": [...]}``) so every reader of the segment, local mmap
    or cross-host fetch alike, knows the bytes are already in the
    [n_cols, batch]-packed form ``jax.device_put`` stages directly."""
    meta: List[dict] = []
    offset = 0
    for name, (shape, dtype) in spec.items():
        dtype = np.dtype(dtype)
        nbytes = int(dtype.itemsize * int(np.prod(shape, dtype=np.int64)))
        offset = _align(offset)
        meta.append(
            {
                "name": name,
                "dtype": dtype.str,
                "shape": list(shape),
                "offset": offset,
                "nbytes": nbytes,
            }
        )
        offset += nbytes
    payload_bytes = _align(offset)
    head: Dict[str, object] = {"columns": meta}
    if layout is not None:
        head["layout"] = layout
    meta_blob = json.dumps(head).encode()
    payload_start = _align(_HEADER.size + len(meta_blob))
    total = payload_start + payload_bytes
    return meta, meta_blob, payload_start, total


@dataclass(frozen=True)
class ObjectRef:
    """A small, picklable handle to a shared-memory object.

    The control-plane analog of ``ray.ObjectRef``: queues and RPC messages
    carry these, never the underlying buffers. In cluster mode ``owner`` is
    the producing host's store-server address, so any host can pull the
    segment over DCN on first use (:mod:`.cluster`); ``None`` means
    single-host/local.

    ``rows`` restricts the ref to a half-open row window of the segment —
    several refs can hardlink one physical segment (the map stage publishes
    its per-reducer partitions this way, so partitioning writes each row
    once instead of once per copy-out). Each ref owns its own directory
    link; the data dies when the last link is freed.
    """

    object_id: str
    nbytes: int
    session: str = ""
    owner: Optional[Tuple] = None
    rows: Optional[Tuple[int, int]] = None


class ColumnBatch(Mapping[str, np.ndarray]):
    """A named collection of equal-length columns backed by one mapping.

    Zero-copy view over a store segment (or plain in-memory arrays when
    constructed directly). Mapping protocol yields column name -> ndarray.
    """

    def __init__(
        self,
        columns: Dict[str, np.ndarray],
        _keepalive=None,
        layout: Optional[dict] = None,
        packed: Optional[np.ndarray] = None,
    ):
        self._columns = columns
        self._keepalive = _keepalive
        # Device-direct delivery (ISSUE 8): ``layout`` is the segment's
        # staging-layout descriptor; ``packed`` is the contiguous
        # ``[n_cols, batch]`` int32 block backing a single batch's
        # logical column views (set only on per-batch views produced by
        # :func:`iter_packed_batches` — the buffer ``jax.device_put``
        # can stage with zero host-side copies).
        self.layout = layout
        self.packed = packed
        lengths = {len(v) for v in columns.values()}
        if len(lengths) > 1:
            raise ValueError(f"ragged columns: {lengths}")
        self._num_rows = lengths.pop() if lengths else 0

    def __getitem__(self, key: str) -> np.ndarray:
        return self._columns[key]

    def __iter__(self) -> Iterator[str]:
        return iter(self._columns)

    def __len__(self) -> int:
        return len(self._columns)

    @property
    def num_rows(self) -> int:
        return self._num_rows

    @property
    def columns(self) -> Dict[str, np.ndarray]:
        return self._columns

    @property
    def nbytes(self) -> int:
        return sum(v.nbytes for v in self._columns.values())

    def take(self, indices: np.ndarray) -> "ColumnBatch":
        """Row gather: the core shuffle primitive (one gather per column,
        through the C++ kernel when built — ``native.take``)."""
        from ray_shuffling_data_loader_tpu import native

        return ColumnBatch(
            {k: native.take(v, indices) for k, v in self._columns.items()}
        )

    @staticmethod
    def concat_take(
        batches: Sequence["ColumnBatch"],
        indices: np.ndarray,
        out: Optional[Dict[str, np.ndarray]] = None,
    ) -> "ColumnBatch":
        """``concat(batches).take(indices)`` without materializing the
        concat when the native fused kernel is available (reduce-stage hot
        path; the reference pays ``pd.concat`` + ``DataFrame.sample``,
        reference ``shuffle.py:192-194``). ``out`` gathers straight into
        pre-allocated destinations (store-segment views)."""
        from ray_shuffling_data_loader_tpu import native

        batches = [b for b in batches if b is not None and b.num_rows > 0]
        if not batches:
            return ColumnBatch({})
        keys = list(batches[0])
        return ColumnBatch(
            {
                k: native.take_multi(
                    [b[k] for b in batches],
                    indices,
                    # out[k]: a missing destination must raise, not silently
                    # gather into a throwaway array.
                    out=out[k] if out is not None else None,
                )
                for k in keys
            }
        )

    def slice(self, start: int, stop: int) -> "ColumnBatch":
        """Zero-copy row slice. A device-batch segment slices along its
        batch axis, so the layout descriptor stays valid and rides the
        view."""
        return ColumnBatch(
            {k: v[start:stop] for k, v in self._columns.items()},
            _keepalive=self._keepalive,
            layout=self.layout,
        )

    def to_pandas(self):
        import pandas as pd

        return pd.DataFrame({k: v for k, v in self._columns.items()})

    @staticmethod
    def from_pandas(df) -> "ColumnBatch":
        return ColumnBatch(
            {str(c): np.ascontiguousarray(df[c].to_numpy()) for c in df.columns}
        )

    @staticmethod
    def concat(batches: Sequence["ColumnBatch"]) -> "ColumnBatch":
        batches = [b for b in batches if b is not None and b.num_rows > 0]
        if not batches:
            return ColumnBatch({})
        if len(batches) == 1:
            return batches[0]
        keys = list(batches[0])
        return ColumnBatch(
            {k: np.concatenate([b[k] for b in batches]) for k in keys}
        )


# ---------------------------------------------------------------------------
# Device-batch (packed) segment layout (ISSUE 8: device-direct delivery)
# ---------------------------------------------------------------------------
# A reducer that knows the trainer's staging layout emits its batch-
# aligned rows as ONE column named PACKED_COLUMN of shape
# ``[n_batches, n_slots, batch]`` int32: batch ``b`` is the contiguous
# ``[n_slots, batch]`` block the JAX stager ships to the device with a
# single ``device_put`` straight off the mmapped segment (float columns
# ride as int32 bit patterns and are bitcast back on device — the same
# wire trick the legacy host-side pack used). A column of one number a
# row takes one slot; a column of ``width`` numbers a row (a
# ``fixed_size_list``) takes ``width`` slots, and its ``width * batch``
# contiguous words hold the batch's rows one after the other, so that
# they read back as ``[batch, width]``. The ``layout`` descriptor
# in the segment meta names the logical columns, their true dtypes,
# their ``widths`` (left out where every column takes one slot) and
# the batch size, so every consumer — local mmap, legacy pickle fetch,
# or the striped zero-copy TCP plane — can reconstruct zero-copy logical
# column views without a repack.

PACKED_COLUMN = "__packed__"
DEVICE_BATCH_KIND = "device-batch"


def is_device_batch(cb: "ColumnBatch") -> bool:
    """Does this batch hold a packed device-layout body segment?"""
    return (
        cb.layout is not None
        and cb.layout.get("kind") == DEVICE_BATCH_KIND
        and PACKED_COLUMN in cb
    )


def device_batch_rows(cb: "ColumnBatch") -> int:
    """Logical row count of a packed segment (batches x batch size)."""
    mat = cb[PACKED_COLUMN]
    return int(mat.shape[0]) * int(mat.shape[2])


def packed_widths(layout: dict) -> List[int]:
    """Slots each column of a packed layout takes: 1, or a wide
    column's width."""
    return [int(w) for w in layout.get("widths") or [1] * len(layout["columns"])]


def packed_slots(layout: dict) -> List[Tuple[int, int]]:
    """``(first slot, width)`` of each column of a packed layout."""
    out, at = [], 0
    for w in packed_widths(layout):
        out.append((at, w))
        at += w
    return out


def packed_column_view(block: np.ndarray, at: int, width: int, dtype):
    """One column of one batch's ``[n_slots, batch]`` block as a
    zero-copy view in its true dtype: ``[batch]``, or ``[batch, width]``
    of a wide column."""
    if width == 1:
        return block[at].view(dtype)
    return block[at : at + width].reshape(block.shape[1], width).view(dtype)


def iter_packed_batches(cb: "ColumnBatch") -> Iterator["ColumnBatch"]:
    """Split a packed device-batch segment into per-batch views.

    Each yielded batch is an ordinary :class:`ColumnBatch` whose logical
    columns are ZERO-COPY views into the segment (the column's slots of
    the block, bit-viewed back to its true dtype), with ``.packed`` set
    to the contiguous ``[n_slots, batch]`` int32 block for direct
    staging."""
    lay = cb.layout or {}
    mat = cb[PACKED_COLUMN]
    names = lay["columns"]
    dtypes = [np.dtype(d) for d in lay["dtypes"]]
    slots = packed_slots(lay)
    for b in range(mat.shape[0]):
        block = mat[b]
        cols = {
            name: packed_column_view(block, at, w, dt)
            for name, dt, (at, w) in zip(names, dtypes, slots)
        }
        yield ColumnBatch(
            cols, _keepalive=cb._keepalive, layout=lay, packed=block
        )


def packed_logical_column(mat: np.ndarray, at: int, width: int, dtype):
    """One column's logical values over a whole packed body ``[m,
    n_slots, B]``: ``[m * B]`` or ``[m * B, width]`` (one contiguous
    copy of just that column)."""
    if width == 1:
        return mat[:, at, :].reshape(-1).view(dtype)
    # Each batch's slab is its rows one after the other.
    return mat[:, at : at + width, :].reshape(-1, width).view(dtype)


class _LazyLogicalColumns(Mapping[str, np.ndarray]):
    """Logical column access over a whole packed segment without
    materializing every column: column ``name`` is the flattened plane
    of its slots (one contiguous copy of just that column,
    built on first access). Audit digests read only the key column, so
    this keeps the audit path O(key bytes), not O(segment bytes)."""

    def __init__(self, cb: "ColumnBatch"):
        self._mat = cb[PACKED_COLUMN]
        lay = cb.layout or {}
        self._names = list(lay["columns"])
        self._dtypes = [np.dtype(d) for d in lay["dtypes"]]
        self._slots = packed_slots(lay)
        self._cache: Dict[str, np.ndarray] = {}

    def __getitem__(self, name: str) -> np.ndarray:
        out = self._cache.get(name)
        if out is None:
            try:
                i = self._names.index(name)
            except ValueError:
                raise KeyError(name) from None
            out = packed_logical_column(
                self._mat, *self._slots[i], self._dtypes[i]
            )
            self._cache[name] = out
        return out

    def __iter__(self) -> Iterator[str]:
        return iter(self._names)

    def __len__(self) -> int:
        return len(self._names)

    def __contains__(self, name: object) -> bool:
        return name in self._names


def logical_columns(cb: "ColumnBatch") -> Mapping[str, np.ndarray]:
    """Column-name -> 1-D logical array view of any batch: the identity
    for ordinary columnar batches, a lazy per-column flatten for packed
    device-batch segments (only accessed columns are materialized)."""
    if is_device_batch(cb):
        return _LazyLogicalColumns(cb)
    return cb.columns


class PendingColumns:
    """An allocated-but-unpublished segment with writable column views.

    Produced by :meth:`ObjectStore.create_columns`. The mapping stays alive
    as long as this object (or any view of it) does; publishing renames the
    hidden ``.tmp`` file, so readers never observe a half-written segment.
    """

    def __init__(self, store, object_id, tmp_path, path, nbytes, mm, views,
                 ledger_tier: Optional[str] = None):
        self._store = store
        self.object_id = object_id
        self._tmp = tmp_path
        self._path = path
        self.nbytes = nbytes
        self._mm = mm
        self.columns: Dict[str, np.ndarray] = views
        self._published = False
        # Logical capacity-ledger tier override (e.g. "cache" for the
        # shared decode-cache tier, ISSUE 11); None = the physical tier.
        self._ledger_tier = ledger_tier

    @property
    def num_rows(self) -> int:
        for v in self.columns.values():
            return len(v)
        return 0

    def seal(self) -> ObjectRef:
        """Publish as a single object."""
        assert not self._published, "already published"
        os.rename(self._tmp, self._path)
        self._published = True
        _ledger_note(
            "create", self.object_id, self.nbytes,
            self._ledger_tier or self._store.tier_of(self._path),
        )
        return ObjectRef(
            object_id=self.object_id,
            nbytes=self.nbytes,
            session=self._store.session,
            owner=self._store.owner_address,
        )

    def publish_slices(
        self, windows: Sequence[Tuple[int, int]]
    ) -> List[ObjectRef]:
        """Publish one hardlinked ref per row window.

        Each ref owns its own directory entry (tmpfs hardlink), so the
        per-ref ``free()`` semantics are unchanged and the physical pages
        are reclaimed when the last window is freed — a filesystem-level
        refcount standing in for Ray's distributed ref counting.
        """
        assert not self._published, "already published"
        seg_dir = os.path.dirname(self._tmp)  # shm or spill, same fs as tmp
        refs: List[ObjectRef] = []
        try:
            for start, stop in windows:
                link_id = self._store._new_object_id()
                os.link(self._tmp, os.path.join(seg_dir, link_id))
                refs.append(
                    ObjectRef(
                        object_id=link_id,
                        nbytes=self.nbytes,
                        session=self._store.session,
                        owner=self._store.owner_address,
                        rows=(int(start), int(stop)),
                    )
                )
        except BaseException:
            # Partial failure (e.g. ENOSPC mid-loop): reclaim the links
            # already created — no ref for them ever reaches a caller, and
            # each pins the whole segment.
            for ref in refs:
                try:
                    os.unlink(os.path.join(seg_dir, ref.object_id))
                except FileNotFoundError:
                    pass
            raise
        os.unlink(self._tmp)
        self._published = True
        # One ledger segment carrying every link id: the bytes stay
        # resident until the LAST window's link is freed (the fold
        # mirrors the filesystem refcount).
        _ledger_note(
            "create", self.object_id, self.nbytes,
            self._ledger_tier or self._store.tier_of(self._tmp),
            ids=[r.object_id for r in refs],
        )
        return refs

    def abort(self) -> None:
        if not self._published:
            try:
                os.unlink(self._tmp)
            except FileNotFoundError:
                pass
            self._published = True


def map_segment_file(path: str, object_id: str = "?") -> ColumnBatch:
    """mmap a published segment file into zero-copy column views."""
    fd = os.open(path, os.O_RDONLY)
    try:
        size = os.fstat(fd).st_size
        mm = mmap.mmap(fd, size, prot=mmap.PROT_READ)
    finally:
        os.close(fd)
    magic, meta_len = _HEADER.unpack_from(mm, 0)
    if magic != _MAGIC:
        raise ValueError(f"corrupt object segment {object_id!r}")
    meta = json.loads(bytes(mm[_HEADER.size : _HEADER.size + meta_len]))
    payload_start = _align(_HEADER.size + meta_len)
    cols: Dict[str, np.ndarray] = {}
    for m in meta["columns"]:
        arr = np.frombuffer(
            mm,
            dtype=np.dtype(m["dtype"]),
            count=int(np.prod(m["shape"])) if m["shape"] else 1,
            offset=payload_start + m["offset"],
        ).reshape(m["shape"])
        cols[m["name"]] = arr
    return ColumnBatch(cols, _keepalive=mm, layout=meta.get("layout"))


def serialize_columns(
    columns: Mapping[str, np.ndarray], layout: Optional[dict] = None
) -> bytes:
    """Serialize columns into the segment wire/disk format (used by the
    cluster StoreServer to ship a ref's row window without the rest of the
    segment). ``layout`` rides in the meta blob, so a fetched copy of a
    device-batch segment lands on the reader already in staging layout."""
    cols = {k: np.ascontiguousarray(v) for k, v in columns.items()}
    meta, meta_blob, payload_start, total = _plan_layout(
        {k: (v.shape, v.dtype) for k, v in cols.items()}, layout=layout
    )
    out = bytearray(total)
    out[: _HEADER.size] = _HEADER.pack(_MAGIC, len(meta_blob))
    out[_HEADER.size : _HEADER.size + len(meta_blob)] = meta_blob
    view = np.frombuffer(out, dtype=np.uint8)
    for m, arr in zip(meta, cols.values()):
        start = payload_start + m["offset"]
        view[start : start + arr.nbytes] = arr.reshape(-1).view(np.uint8)
    return bytes(out)


_PAD64 = bytes(_ALIGN)


def serialize_columns_vectored(
    columns: Mapping[str, np.ndarray], layout: Optional[dict] = None
) -> Tuple[int, List]:
    """``(total_bytes, buffers)`` for the segment wire/disk format WITHOUT
    materializing the payload: the buffers are the source column views
    themselves (plus a small header and sub-64-byte alignment pads), byte-
    identical when concatenated to :func:`serialize_columns`'s output.
    This is the zero-copy TCP plane's scatter-gather list — a window
    fetch streams straight out of the owner's mmapped segment instead of
    paying a full ``bytearray`` build plus a ``bytes()`` copy plus a
    payload pickle. Callers must keep the source mapping alive until the
    buffers are consumed."""
    cols = {
        k: (v if v.flags.c_contiguous else np.ascontiguousarray(v))
        for k, v in columns.items()
    }
    meta, meta_blob, payload_start, total = _plan_layout(
        {k: (v.shape, v.dtype) for k, v in cols.items()}, layout=layout
    )
    head = bytearray(payload_start)
    head[: _HEADER.size] = _HEADER.pack(_MAGIC, len(meta_blob))
    head[_HEADER.size : _HEADER.size + len(meta_blob)] = meta_blob
    bufs: List = [head]
    pos = payload_start
    for m, arr in zip(meta, cols.values()):
        target = payload_start + m["offset"]
        if target > pos:  # inter-column alignment gap, always < 64 B
            bufs.append(_PAD64[: target - pos])
            pos = target
        if arr.nbytes:
            bufs.append(memoryview(arr).cast("B"))
            pos += arr.nbytes
    if total > pos:  # trailing alignment pad
        bufs.append(_PAD64[: total - pos])
    return total, bufs


@dataclass
class StoreStats:
    num_objects: int = 0
    total_bytes: int = 0
    spill_bytes: int = 0  # portion of total_bytes living on disk, not shm


class ObjectStore:
    """Session-scoped object store over ``/dev/shm`` files.

    All objects created under one session share an id prefix so that
    ``cleanup()`` can reclaim everything the session produced, and
    ``store_stats()`` can report utilization for just this session.
    """

    def __init__(self, session: str, shm_dir: Optional[str] = None):
        self.session = session
        self.shm_dir = shm_dir or _default_shm_dir()
        # RSDL_SHM_DIR may name a fresh subdirectory (e.g. per-session
        # dirs isolating same-machine multi-host tests).
        os.makedirs(self.shm_dir, exist_ok=True)
        # Capacity budgeting (SURVEY §7 hard-part 4): shared-memory
        # residency for this session is capped; segments beyond the budget
        # are created in (or fetched to) the disk-backed spill dir instead
        # of dying on ENOSPC. Admission stays non-blocking, so the pipeline
        # cannot deadlock on its own backpressure.
        self.capacity_bytes: Optional[int] = _default_capacity_bytes(
            self.shm_dir
        )
        self.spill_dir = _default_spill_dir()
        if os.path.realpath(self.spill_dir) == os.path.realpath(self.shm_dir):
            # A spill dir on tmpfs defeats the point; disable budgeting.
            self.capacity_bytes = None
        # Cluster-mode hooks, installed by runtime.init when joined to a
        # cluster: refs minted here get stamped with owner_address; misses
        # on foreign refs go through remote_fetch; frees forward to owners.
        self.owner_address: Optional[Tuple] = None
        self.remote_fetch = None  # Callable[[ObjectRef], bytes]
        # Zero-copy fetch hook (RSDL_TCP_ZEROCOPY): pulls the ref's bytes
        # straight into a buffer the allocator returns (an mmapped cache
        # file) — Callable[[ObjectRef, Callable[[int], buffer]], None].
        self.remote_fetch_into = None
        self.remote_free = None  # Callable[[ObjectRef], None]
        self._foreign: set = set()  # locally cached foreign object ids
        # Grows to the largest max_parallel any prefetch call asks for.
        self._prefetch_pool = GrowingThreadPool("store-prefetch")
        # Cache names freed in this process: a prefetch thread whose fetch
        # lands AFTER the consumer already freed the ref must discard its
        # result instead of orphaning a cache file (object ids are never
        # reused, so entries can only ever refer to dead refs). Bounded in
        # free()/drop_cache: entries only matter while a prefetch could
        # still be in flight (seconds), so the set is cleared when it
        # outgrows any plausible in-flight window.
        self._freed_caches: set = set()
        # Capacity-check cache: _shm_session_bytes listdir+stats the whole
        # shm dir, so the result is reused for a short TTL with creations
        # since the last scan added on top (frees within the TTL leave the
        # estimate high — the conservative direction: spill a hair early).
        self._shm_scan_base = 0
        self._shm_scan_adjust = 0
        self._shm_scan_ts = float("-inf")

    # -- write path ---------------------------------------------------------

    def _new_object_id(self) -> str:
        return f"{self.session}-{secrets.token_hex(8)}"

    def _shm_session_bytes(self) -> int:
        """This session's shared-memory residency (inode-deduped; spilled
        segments excluded), cached for a short TTL so the data path is not
        O(resident objects) per placement decision."""
        import time as _time

        now = _time.monotonic()
        if now - self._shm_scan_ts <= 0.2:
            return self._shm_scan_base + self._shm_scan_adjust
        self._shm_scan_base = self._scan_shm_session_bytes()
        self._shm_scan_adjust = 0
        self._shm_scan_ts = now
        return self._shm_scan_base

    def _scan_shm_session_bytes(self) -> int:
        """The uncached scan. The filesystem is the shared truth across the
        session's processes — worker pools race this check and can
        overshoot by one segment each, which the budget's slack absorbs."""
        prefix = f"{self.session}-"
        total = 0
        seen = set()
        try:
            names = os.listdir(self.shm_dir)
        except FileNotFoundError:
            return 0
        for name in names:
            if name.startswith(prefix):
                try:
                    st = os.stat(os.path.join(self.shm_dir, name))
                except FileNotFoundError:
                    continue
                if st.st_ino not in seen:
                    seen.add(st.st_ino)
                    total += st.st_size
        return total

    def _placement_dir(self, nbytes: int) -> str:
        """Where a new segment of ``nbytes`` goes: shm while the session is
        under budget, else the spill dir."""
        if (
            self.capacity_bytes is not None
            and nbytes + self._shm_session_bytes() > self.capacity_bytes
        ):
            os.makedirs(self.spill_dir, exist_ok=True)
            _emit_spill_event(nbytes)
            return self.spill_dir
        # Count the imminent write against the cached estimate so rapid
        # placements between scans see each other.
        self._shm_scan_adjust += nbytes
        return self.shm_dir

    def tier_of(self, path: str) -> str:
        """Which capacity tier a segment path lives on — ``spill`` for
        the disk spill dir, ``shm`` otherwise (the ledger vocabulary)."""
        return (
            "spill"
            if os.path.dirname(path) == self.spill_dir
            else "shm"
        )

    def _find_segment(self, object_id: str) -> Optional[str]:
        """Resolve a local object id to its segment path (shm, then spill)."""
        path = os.path.join(self.shm_dir, object_id)
        if os.path.exists(path):
            return path
        spath = os.path.join(self.spill_dir, object_id)
        if os.path.exists(spath):
            return spath
        return None

    def create_columns(
        self,
        spec: Mapping[str, Tuple[Tuple[int, ...], "np.dtype"]],
        layout: Optional[dict] = None,
        ledger_tier: Optional[str] = None,
    ) -> "PendingColumns":
        """Allocate an unpublished segment and return writable column views.

        The zero-extra-copy write path: producers (shuffle map/reduce
        kernels) scatter/gather rows *directly into shared memory* instead
        of building host arrays and copying them in via :meth:`put_columns`
        — one full memory pass saved per stage. Fill the views, then
        ``seal()`` (one ref) or ``publish_slices()`` (hardlinked row-window
        refs). ``layout`` stamps the segment with a staging-layout
        descriptor (see :func:`_plan_layout`). ``ledger_tier`` overrides
        the capacity-ledger tier the publish records under (the shared
        decode-cache tier accounts as ``cache``; physical placement is
        unchanged).
        """
        if faults.enabled():
            faults.fire("store.put")
        meta, meta_blob, payload_start, total = _plan_layout(
            spec, layout=layout
        )

        object_id = self._new_object_id()
        path = os.path.join(self._placement_dir(total), object_id)
        tmp = path + ".tmp"
        fd = os.open(tmp, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
        try:
            os.ftruncate(fd, max(total, 1))
            mm = mmap.mmap(fd, max(total, 1))
        finally:
            os.close(fd)
        mm[: _HEADER.size] = _HEADER.pack(_MAGIC, len(meta_blob))
        mm[_HEADER.size : _HEADER.size + len(meta_blob)] = meta_blob
        views: Dict[str, np.ndarray] = {}
        for m in meta:
            views[m["name"]] = np.frombuffer(
                mm,
                dtype=np.dtype(m["dtype"]),
                count=int(np.prod(m["shape"], dtype=np.int64)),
                offset=payload_start + m["offset"],
            ).reshape(m["shape"])
        return PendingColumns(
            self, object_id, tmp, path, total, mm, views,
            ledger_tier=ledger_tier,
        )

    def put_columns(self, columns: Mapping[str, np.ndarray]) -> ObjectRef:
        """Write a columnar batch as one aligned segment; return its ref.
        The segment is reclaimed if the copy-in fails mid-way (``abort``
        is a no-op after a successful ``seal``)."""
        cols = {k: np.ascontiguousarray(v) for k, v in columns.items()}
        pending = self.create_columns(
            {k: (v.shape, v.dtype) for k, v in cols.items()}
        )
        try:
            for k, v in cols.items():
                pending.columns[k][...] = v
            return pending.seal()
        finally:
            pending.abort()

    def put_bytes(self, data: bytes) -> ObjectRef:
        return self.put_columns({"__bytes__": np.frombuffer(data, np.uint8)})

    # -- read path ----------------------------------------------------------

    def get_columns(self, ref: ObjectRef) -> ColumnBatch:
        """Open a segment and return zero-copy column views onto it.

        ``ref.rows`` windows slice the views (still zero-copy). When the
        segment is not on this host and the ref names a remote owner, just
        the ref's window is pulled over DCN once and cached as a local
        standalone segment; subsequent gets map the cache (the plasma
        cross-node transfer analog, SURVEY §2b).

        A missing or unreadable segment raises :class:`ObjectLostError`
        (carrying the object id) so callers with lineage — the shuffle
        driver — can re-materialize instead of failing the run."""
        if faults.enabled():
            kind = faults.should_fire("store.get")
            if kind == "lost":
                raise ObjectLostError(ref.object_id, "injected fault")
            if kind == "corrupt":
                raise ObjectCorruptError(ref.object_id, "injected fault")
        path = self._find_segment(ref.object_id)
        rows = ref.rows
        if path is None and self._is_foreign(ref):
            # Window refs cache under a window-suffixed name (the fetched
            # segment holds only the window; the name keeps that fact
            # consistent across processes on this host).
            cache_path = self._find_cache(ref)
            if cache_path is None:
                cache_path = self._cache_path(ref)
                self._materialize_remote(ref, cache_path)
            path = cache_path
            rows = None
        elif path is None:
            raise ObjectLostError(ref.object_id, "no local segment")
        try:
            batch = self._map_segment(path, ref.object_id)
        except FileNotFoundError:
            # Unlinked between the existence check and the mmap.
            raise ObjectLostError(
                ref.object_id, "segment unlinked mid-read"
            ) from None
        except ValueError as exc:
            raise ObjectCorruptError(ref.object_id, str(exc)) from exc
        # Read-tracking ledger op (ISSUE 11): every successful read
        # stamps the segment's last access — the signal last-touch
        # eviction orders cold epochs by. The AUTHORITATIVE id
        # (ref.object_id, a real ledger link id) gets the touch, so a
        # foreign window read warms the owner's segment, not just this
        # host's cache file; the cache file's own ledger entry (keyed
        # by its window-suffixed name from the fetch op) is touched
        # too when it differs. Rate-limited per id inside
        # capacity.touch; one cached boolean when metrics are off.
        self._ledger_touch(ref.object_id)
        base = os.path.basename(path)
        if base != ref.object_id:
            self._ledger_touch(base)
        if rows is not None:
            batch = batch.slice(rows[0], rows[1])
        return batch

    @staticmethod
    def _ledger_touch(object_id: str) -> None:
        if not _metrics.enabled():
            return
        try:
            from ray_shuffling_data_loader_tpu.telemetry import capacity

            capacity.touch(object_id)
        except Exception:
            pass

    def _is_foreign(self, ref: ObjectRef) -> bool:
        return (
            ref.owner is not None
            and tuple(ref.owner) != self.owner_address
            and self.remote_fetch is not None
        )

    def is_foreign(self, ref: ObjectRef) -> bool:
        """Does reading this ref require (or did it require) a cross-host
        fetch? The shuffle reduce uses this to decide whether the
        overlapped fetch/gather pipeline buys anything."""
        return self._is_foreign(ref)

    def needs_fetch(self, ref: ObjectRef) -> bool:
        """Would reading this ref RIGHT NOW pay a cross-host fetch —
        foreign, not yet cached locally, and not directly mappable
        (sessions sharing one /dev/shm)? The overlap auto-policy keys on
        this instead of :meth:`is_foreign`: a retried reduce whose first
        attempt already cached its windows has no fetch latency to hide
        and should keep the fused gather."""
        return (
            self._is_foreign(ref)
            and self._find_cache(ref) is None
            and self._find_segment(ref.object_id) is None
        )

    def _cache_name(self, ref: ObjectRef) -> str:
        # Caches carry the READER session's prefix (not the producer's):
        # every process sharing this session computes the same name, and
        # the session's ordinary prefix cleanup reclaims caches that pool
        # workers materialized and a failed task never dropped.
        name = f"{self.session}-cache-{ref.object_id}"
        if ref.rows is not None:
            name = f"{name}+w{ref.rows[0]}-{ref.rows[1]}"
        return name

    def _cache_path(self, ref: ObjectRef) -> str:
        """Placement for a NEW cache file (capacity-aware like any other
        segment; ``ref.nbytes`` is the whole-segment size, a safe
        overestimate for window refs)."""
        return os.path.join(
            self._placement_dir(ref.nbytes), self._cache_name(ref)
        )

    def _find_cache(self, ref: ObjectRef) -> Optional[str]:
        """An existing cache of ``ref`` (shm, then spill), or None."""
        name = self._cache_name(ref)
        for d in (self.shm_dir, self.spill_dir):
            path = os.path.join(d, name)
            if os.path.exists(path):
                return path
        return None

    def _map_segment(self, path: str, object_id: str) -> ColumnBatch:
        return map_segment_file(path, object_id)

    def get_bytes(self, ref: ObjectRef) -> bytes:
        return self.get_columns(ref)["__bytes__"].tobytes()

    def prefetch(self, refs, max_parallel: Optional[int] = None) -> List:
        """Start pulling foreign refs' windows into the local cache on
        background threads; returns immediately with the fetch futures.
        ``max_parallel`` defaults to :func:`fetch_window_depth` (the
        ``RSDL_FETCH_WINDOW_DEPTH`` knob; this delivery-plane path
        defaults to 8 when the env is unset, the overlapped reduce to
        4). The pool is process-lifetime but its width follows the
        LARGEST ``max_parallel`` seen: a later call asking for more
        parallelism grows the pool (by replacement — in-flight fetches
        on the old pool complete normally) instead of silently
        serializing its extra fetches behind the first caller's width.

        The ``ray.wait(fetch_local=True)`` analog (reference
        ``dataset.py:132-137``): the reference pulls ALL pending reducer
        outputs to the local node while the trainer consumes the first.
        Kicking this off as soon as a queue ``get_batch`` returns its refs
        overlaps every DCN hop with consumption, instead of stalling the
        iterator on each foreign ref in turn.

        Failures are swallowed here — the consuming ``get_columns`` retries
        the fetch synchronously and is the place errors surface.
        """
        foreign = [
            r
            for r in refs
            if isinstance(r, ObjectRef)
            and self._is_foreign(r)
            and self._find_cache(r) is None
            # Same-filesystem shortcut parity with get_columns: a
            # "foreign" segment that is directly mappable here (sessions
            # sharing one /dev/shm) needs no pull at all.
            and self._find_segment(r.object_id) is None
        ]
        if not foreign:
            return []
        # An explicit prefetch REQUEST supersedes any free/drop_cache
        # tombstone for these refs: the tombstones exist to discard a
        # late-landing fetch from BEFORE the free, but a retried reduce
        # (or a second bench plane) legitimately re-reads dropped
        # windows, and a permanent tombstone would silently no-op its
        # prefetches forever (degrading the retry to serial synchronous
        # fetches). A still-in-flight old fetch that now lands is
        # wanted again — object ids are immutable content, so the copy
        # is identical either way.
        for ref in foreign:
            self._freed_caches.discard(self._cache_name(ref))
        if max_parallel is None:
            max_parallel = fetch_window_depth(default=8)
        # Grow-on-demand (a first narrow caller must not serialize a
        # later wider one's fetches); a ref whose fetch is in flight on
        # a retired pool is at worst one redundant pull — object ids
        # are immutable content and _pull re-checks the cache.
        pool = self._prefetch_pool.ensure(max_parallel)

        def _pull(ref: ObjectRef) -> None:
            name = self._cache_name(ref)
            if name in self._freed_caches or self._find_cache(ref) is not None:
                return
            try:
                self._materialize_remote(ref, self._cache_path(ref))
            except Exception:
                return
            if name in self._freed_caches:
                # The consumer freed the ref while the fetch was in flight
                # (it cache-missed and fetched synchronously); reclaim the
                # now-orphaned copy.
                cache = self._find_cache(ref)
                if cache is not None:
                    try:
                        os.unlink(cache)
                    except FileNotFoundError:
                        pass
                self._foreign.discard(name)

        return [pool.submit(_pull, r) for r in foreign]

    def _materialize_remote(self, ref: ObjectRef, path: str) -> None:
        """Pull a foreign segment's bytes (just the ref's window) and
        publish them locally.

        With the zero-copy plane on (``RSDL_TCP_ZEROCOPY`` + cluster
        wiring), the peer's vectored reply lands via ``recv_into``
        directly in the mmapped destination file — no intermediate
        ``bytes``, no payload pickle on either side. Otherwise the legacy
        path fetches one bytes blob and writes it out.

        Concurrent readers may race here; both write a private tmp file and
        the renames are idempotent (same content), so the winner is
        irrelevant."""
        t0 = time.perf_counter() if _metrics.enabled() else None
        tmp = f"{path}.fetch-{os.getpid()}-{secrets.token_hex(4)}"
        zerocopy = (
            self.remote_fetch_into is not None
            and _transport.zerocopy_enabled()
        )
        nbytes = 0
        if zerocopy:
            holder: Dict[str, mmap.mmap] = {}

            def _alloc(n: int):
                fd = os.open(tmp, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
                try:
                    os.ftruncate(fd, max(n, 1))
                    # MAP_POPULATE prefaults the whole window in one
                    # kernel sweep: without it every 4 KB page of the
                    # fresh cache file faults individually under
                    # recv_into — measured as a large share of the
                    # per-window fetch cost (BENCHLOG r7).
                    flags = mmap.MAP_SHARED | getattr(
                        mmap, "MAP_POPULATE", 0
                    )
                    mm = mmap.mmap(fd, max(n, 1), flags=flags)
                finally:
                    os.close(fd)
                holder["mm"] = mm
                holder["n"] = n
                return mm

            try:
                self.remote_fetch_into(ref, _alloc)
                nbytes = holder.get("n", 0)
            except BaseException:
                try:
                    os.unlink(tmp)
                except FileNotFoundError:
                    pass
                raise
            finally:
                mm = holder.pop("mm", None)
                if mm is not None:
                    try:
                        mm.close()
                    except BufferError:
                        # Belt-and-braces: should be unreachable now that
                        # the transport releases its recv views on every
                        # exit, but a still-exported view must never
                        # replace the recoverable fetch error (the
                        # retry/lineage ladder keys on it); GC closes the
                        # mmap once the exception's traceback is dropped.
                        pass
        else:
            data = self.remote_fetch(ref)
            nbytes = len(data)
            with open(tmp, "wb") as f:
                f.write(data)
        os.rename(tmp, path)
        self._foreign.add(os.path.basename(path))
        _ledger_note(
            "fetch", os.path.basename(path), nbytes, self.tier_of(path)
        )
        if t0 is not None:
            # Per-window DCN latency + bytes — the TCP plane's primary
            # observability (docs/observability.md); labels carry which
            # framing served the window and how many striped streams
            # (RSDL_TCP_STREAMS; always 1 on the legacy pickle path).
            try:
                zc = "1" if zerocopy else "0"
                streams = str(_transport.tcp_streams()) if zerocopy else "1"
                _metrics.registry.histogram(
                    "store.fetch_window_seconds", zerocopy=zc,
                    streams=streams,
                ).observe(time.perf_counter() - t0)
                _metrics.registry.counter(
                    "store.fetch_window_bytes", zerocopy=zc,
                    streams=streams,
                ).inc(float(nbytes))
            except Exception:
                pass

    # -- lifecycle ----------------------------------------------------------

    def free(self, refs) -> None:
        if isinstance(refs, ObjectRef):
            refs = [refs]
        for ref in refs:
            if self._is_foreign(ref):
                # Drop the local window cache and release the authoritative
                # copy (the owner's hardlink) — the physical segment dies
                # when its last window's link is freed. Mark first so an
                # in-flight prefetch landing after this unlink cleans up.
                if len(self._freed_caches) > 8192:
                    # Entries only matter while a prefetch is in flight
                    # (seconds); cap the set instead of leaking for the
                    # process lifetime.
                    self._freed_caches.clear()
                self._freed_caches.add(self._cache_name(ref))
                cache = self._find_cache(ref)
                if cache is not None:
                    try:
                        os.unlink(cache)
                    except FileNotFoundError:
                        pass
                    _ledger_note("delete", self._cache_name(ref))
                self._foreign.discard(self._cache_name(ref))
                if self.remote_free is not None:
                    # The owner's store frees the authoritative segment
                    # in its own process — and logs its own ledger op.
                    self.remote_free(ref)
                continue
            path = self._find_segment(ref.object_id)
            if path is not None:
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                _ledger_note("delete", ref.object_id)

    # -- tiered movement (ISSUE 10: the elastic evictor's actuators) --------

    def _segment_links(self, ids) -> Dict[str, str]:
        """``{name: path}`` for every link name of one segment that is
        currently resolvable (shm first, then spill)."""
        if isinstance(ids, str):
            ids = [ids]
        out: Dict[str, str] = {}
        for name in ids:
            path = self._find_segment(name)
            if path is not None:
                out[name] = path
        return out

    def _move_tier(self, ids, dst_dir: str, tier: str) -> int:
        """Move ALL link names of one physical segment to ``dst_dir``
        atomically-per-link: copy the inode once, hardlink the remaining
        names against the copy (same filesystem), rename over nothing,
        then unlink the sources. Readers racing the move either still
        map the old inode (their mmap survives the unlink) or re-resolve
        via ``_find_segment``, which checks both tiers. Returns the
        bytes moved (0 if the segment vanished or already lives there).
        """
        links = self._segment_links(ids)
        if not links:
            return 0
        first = next(iter(links.values()))
        if os.path.dirname(first) == dst_dir:
            return 0  # already on the target tier
        os.makedirs(dst_dir, exist_ok=True)
        names = list(links)
        primary = names[0]
        # ".tmp" suffix: a crashed move must not leave a file that
        # store_stats or a drain's list_segments would mistake for a
        # published segment.
        tmp = os.path.join(
            dst_dir,
            f"{primary}.move-{os.getpid()}-{secrets.token_hex(4)}.tmp",
        )
        try:
            nbytes = os.path.getsize(links[primary])
            with open(links[primary], "rb") as src, open(tmp, "wb") as dst:
                import shutil as _shutil

                _shutil.copyfileobj(src, dst, length=1 << 20)
            os.rename(tmp, os.path.join(dst_dir, primary))
        except OSError:
            try:
                os.unlink(tmp)
            except FileNotFoundError:
                pass
            return 0
        for name in names[1:]:
            try:
                os.link(
                    os.path.join(dst_dir, primary),
                    os.path.join(dst_dir, name),
                )
            except FileExistsError:
                pass
            except OSError:
                # Partial link failure: roll the whole move back rather
                # than strand some names on each tier.
                for done in names[: names.index(name) + 1]:
                    try:
                        os.unlink(os.path.join(dst_dir, done))
                    except FileNotFoundError:
                        pass
                return 0
        for name, path in links.items():
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
        # Keep the cached shm-residency estimate honest between scans:
        # a demotion frees budgeted shm immediately, a promotion fills
        # it (without this, a burst of promotes inside the scan TTL
        # would each see the pre-burst residency and over-admit).
        if tier == "spill":
            self._shm_scan_adjust -= nbytes
        else:
            self._shm_scan_adjust += nbytes
        _ledger_note("transition", primary, nbytes, tier)
        _metrics.safe_inc(
            "store.tier_moved_bytes_total", float(nbytes), tier=tier
        )
        return nbytes

    def demote(self, ids) -> int:
        """Demote one segment (every hardlinked name in ``ids``) from
        shm to the disk spill tier — the evictor's shm-pressure
        actuator. The segment stays readable in place (``_find_segment``
        and the StoreServer probe both tiers); only the tier moves.
        Emits the capacity-ledger ``transition`` op. Returns bytes
        moved."""
        return self._move_tier(ids, self.spill_dir, "spill")

    def promote(self, ids) -> int:
        """Promote a spilled segment back to shm — only when the move
        fits the session budget (a promote must never trigger the very
        pressure the evictor exists to relieve). Returns bytes moved."""
        links = self._segment_links(ids)
        if not links:
            return 0
        nbytes = 0
        try:
            nbytes = os.path.getsize(next(iter(links.values())))
        except OSError:
            return 0
        if (
            self.capacity_bytes is not None
            and nbytes + self._shm_session_bytes() > self.capacity_bytes
        ):
            return 0
        return self._move_tier(ids, self.shm_dir, "shm")

    def drop_segments(self, ids) -> int:
        """Unconditionally drop a segment (every link name) from
        whichever tier holds it — the evictor's last rung. Readers that
        later miss it raise :class:`ObjectLostError`, which the shuffle
        driver's lineage machinery re-materializes (PR 3). Returns
        bytes dropped."""
        links = self._segment_links(ids)
        if not links:
            return 0
        nbytes = 0
        try:
            nbytes = os.path.getsize(next(iter(links.values())))
        except OSError:
            pass
        for name, path in links.items():
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
            _ledger_note("delete", name)
        return nbytes

    def drop_cache(self, refs) -> None:
        """Release only this host's fetched copy of foreign refs — the
        authoritative segments survive, so a task calling this remains
        retryable (unlike :meth:`free`)."""
        if isinstance(refs, ObjectRef):
            refs = [refs]
        for ref in refs:
            if not self._is_foreign(ref):
                continue
            if len(self._freed_caches) > 8192:
                self._freed_caches.clear()
            self._freed_caches.add(self._cache_name(ref))
            cache = self._find_cache(ref)
            if cache is not None:
                try:
                    os.unlink(cache)
                except FileNotFoundError:
                    pass
                _ledger_note("delete", self._cache_name(ref))
            self._foreign.discard(self._cache_name(ref))

    def exists(self, ref: ObjectRef) -> bool:
        return self._find_segment(ref.object_id) is not None

    def store_stats(self) -> StoreStats:
        """Utilization for this session (replaces the reference's raylet
        ``FormatGlobalMemoryInfo`` probe, ``stats.py:675-683``).

        Hardlinked slice refs share pages; bytes are counted once per inode
        while every ref still counts as an object. Spilled segments are
        included, with their share reported in ``spill_bytes``."""
        stats = StoreStats()
        prefix = f"{self.session}-"
        seen_inodes = set()
        for dirpath, is_spill in (
            (self.shm_dir, False),
            (self.spill_dir, True),
        ):
            try:
                names = os.listdir(dirpath)
            except FileNotFoundError:
                continue
            for name in names:
                if name.startswith(prefix) and not name.endswith(".tmp"):
                    try:
                        st = os.stat(os.path.join(dirpath, name))
                    except FileNotFoundError:
                        continue
                    stats.num_objects += 1
                    if st.st_ino not in seen_inodes:
                        seen_inodes.add(st.st_ino)
                        stats.total_bytes += st.st_size
                        if is_spill:
                            stats.spill_bytes += st.st_size
        return stats

    def cleanup(
        self, session: Optional[str] = None, keep=()
    ) -> None:
        """Reclaim every segment a session produced. Defaults to THIS
        session; passing another session id sweeps a *superseded* one —
        a resumed run (runtime/journal.py) re-attaches the preempted
        driver's surviving segments and owns their reclamation, since
        the session that created them can no longer clean up. ``keep``
        names object ids to spare (segments the resumed run re-attached
        and promoted into the shared decode-cache tier must outlive
        their creating session)."""
        own = session is None or session == self.session
        session = session or self.session
        keep = frozenset(keep)
        if own and not keep:
            # The blanket op: the ledger fold drops everything live.
            _ledger_note("cleanup", session)
        prefix = f"{session}-"
        for dirpath in (self.shm_dir, self.spill_dir):
            try:
                names = os.listdir(dirpath)
            except FileNotFoundError:
                continue
            for name in names:
                if name.startswith(prefix):
                    if name in keep:
                        continue
                    try:
                        os.unlink(os.path.join(dirpath, name))
                    except FileNotFoundError:
                        pass
                    if not own or keep:
                        # Per-name deletes, not the blanket cleanup op:
                        # sweeping a superseded session must not zero
                        # the CURRENT session's live fold (and a kept
                        # segment must stay live in it).
                        _ledger_note("delete", name)
        if own:
            self._foreign.clear()
