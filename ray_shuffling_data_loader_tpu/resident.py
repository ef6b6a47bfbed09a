"""Device-resident per-epoch shuffle: permute + gather in HBM.

The reference (and this repo's general path, ``shuffle.py`` +
``jax_dataset.py``) re-shuffles the dataset **on the host** every epoch —
a map/reduce over worker processes with two full host-memory passes and a
host→device transfer per batch (reference ``shuffle.py:89-200``,
``dataset.py:108-188``). That design is forced by the reference's world:
the dataset outgrows any single GPU and the accelerator is a passive
consumer behind a PCIe copy.

On TPU the bandwidth hierarchy inverts the design. A v5e chip has ~16 GB
of HBM at ~800 GB/s — two orders of magnitude above both host memcpy and
host→device staging. When the (32-bit-narrowed, bit-packed) dataset fits
in a budgeted fraction of HBM, the TPU-native shuffle is:

* **stage once**: decode Parquet on the host worker pool, narrow 64→32
  bit, pack all columns into one ``[n_cols+1, n_rows]`` int32 buffer
  sharded over the mesh's batch axis, streamed to the device in fixed
  width pieces so decode, packing, and H2D overlap;
* **shuffle every epoch on device**: a seeded ``jax.random.permutation``
  plus one ``take`` gather per batch, both jitted — each epoch's full
  re-shuffle rides HBM bandwidth and completely overlaps the train step
  (XLA async dispatch), leaving the host idle in steady state;
* **deliver zero-copy**: a batch is a row-slice gather of the resident
  buffer, unpacked to the feature dict by bitcast — it never exists on
  the host at all.

Capability parity with the epoch-shuffle contract (exactly-once per
epoch, deterministic under a seed, ``drop_last``, disjoint per-rank
shards, mid-epoch ``skip_batches`` resume) is preserved and tested; the
epoch-window/queue machinery is unnecessary here because there is no
host pipeline to backpressure. Datasets that exceed the HBM budget use
the general map/reduce path; ``fits_device`` is the policy gate.

Multi-controller pods are supported opt-in (construct the dataset
explicitly on every process): each process stages its addressable row
range and the per-batch gathers cross the pod as XLA collectives — see
:meth:`DeviceResidentShufflingDataset._load_multiprocess` and
``tests/test_resident_pod.py``.
"""

from __future__ import annotations

import math
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu import runtime, telemetry
from ray_shuffling_data_loader_tpu.jax_dataset import HostToDeviceStats
from ray_shuffling_data_loader_tpu.ops.placement import traced_in_mesh

# Rows per H2D piece: large enough to amortize transfer round-trips,
# small enough that the staging buffer (piece_rows x n_cols x 4 B,
# ~88 MB at 21 columns) stays negligible next to the dataset.
DEFAULT_PIECE_ROWS = 1 << 20


def _decode_narrow_to_store(
    filename: str, columns: Sequence[str], stage_tasks: int = 0
):
    """Pool task: decode one Parquet file, narrow to 32-bit, publish the
    requested columns to the shared-memory store. Returns the ref.
    ``stage_tasks`` = how many decode tasks the stage submitted; the
    thread decision is made HERE, on the worker's own core count."""
    from ray_shuffling_data_loader_tpu.shuffle import (
        _narrow_column,
        read_parquet_columns,
    )
    from ray_shuffling_data_loader_tpu.utils import arrow_decode_threads

    batch = read_parquet_columns(
        filename,
        columns=columns,
        use_threads=stage_tasks > 0 and arrow_decode_threads(stage_tasks),
    )
    cols = {name: _narrow_column(name, batch.columns[name]) for name in columns}
    ctx = runtime.ensure_initialized()
    return ctx.store.put_columns(cols)


def _decode_narrow_range_to_store(
    filename: str,
    columns: Sequence[str],
    row_lo: int,
    row_hi: int,
    stage_tasks: int = 0,
):
    """Pool task: decode only the row range ``[row_lo, row_hi)`` of one
    Parquet file — at row-group granularity, so a pod process staging a
    slice of a boundary-straddling file never decompresses the rest of
    it. Returns the ref (exactly ``row_hi - row_lo`` rows)."""
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu.shuffle import _narrow_column
    from ray_shuffling_data_loader_tpu.utils import parquet_filesystem

    fs, rel = parquet_filesystem(filename)
    pf = pq.ParquetFile(rel, memory_map=fs is None, filesystem=fs)
    md = pf.metadata
    sel = []
    first_row = None
    g_start = 0
    for gi in range(md.num_row_groups):
        g_end = g_start + md.row_group(gi).num_rows
        if g_end > row_lo and g_start < row_hi:
            if first_row is None:
                first_row = g_start
            sel.append(gi)
        g_start = g_end
    # g_start is now the file's total row count; reject ANY range not
    # fully inside it (a numpy slice would silently clamp a too-large
    # row_hi to fewer rows than the contract promises).
    if first_row is None or not 0 <= row_lo < row_hi <= g_start:
        raise ValueError(
            f"row range [{row_lo}, {row_hi}) outside file {filename!r} "
            f"({g_start} rows)"
        )
    from ray_shuffling_data_loader_tpu.utils import arrow_decode_threads

    table = pf.read_row_groups(
        sel,
        columns=list(columns),
        use_threads=stage_tasks > 0 and arrow_decode_threads(stage_tasks),
    )
    a, b = row_lo - first_row, row_hi - first_row
    cols = {}
    for name in columns:
        arr = table.column(name).to_numpy(zero_copy_only=False)
        cols[name] = _narrow_column(name, np.ascontiguousarray(arr[a:b]))
    ctx = runtime.ensure_initialized()
    return ctx.store.put_columns(cols)


def dataset_num_rows(filenames: Sequence[str]) -> int:
    """Total rows across Parquet files from metadata only (no decode)."""
    return sum(m.num_rows for m in _file_metadata(filenames))


def _file_metadata(filenames: Sequence[str]):
    """Per-file Parquet footers, resolving URI inputs (gs://, s3://,
    memory://, ...) through :func:`~.utils.parquet_filesystem`."""
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu.utils import parquet_filesystem

    out = []
    for f in filenames:
        fs, rel = parquet_filesystem(f)
        out.append(pq.ParquetFile(rel, filesystem=fs).metadata)
    return out


def _refuse_wide_columns(filenames: Sequence[str], columns: Sequence[str]) -> None:
    """The resident buffer is ``[columns, rows]``: one number a column a
    row. A ``fixed_size_list`` column (a token sequence) has no place in
    it; say so before anything is decoded."""
    import pyarrow as pa

    from ray_shuffling_data_loader_tpu.shuffle import _open_parquet_file

    if not filenames:
        return
    schema = _open_parquet_file(filenames[0])[0].schema_arrow
    wide = [
        f"{name}: {schema.field(name).type}"
        for name in columns
        if name in schema.names
        and not pa.types.is_primitive(schema.field(name).type)
    ]
    if wide:
        raise ValueError(
            "DeviceResidentShufflingDataset holds one number a column a "
            f"row; these columns hold more: {wide}. Stream them with "
            "JaxShufflingDataset, which delivers a fixed_size_list column "
            "as [batch, width]."
        )


def packed_nbytes(num_rows: int, num_feature_columns: int) -> int:
    """Device residency of the packed ``[features + label, rows]`` int32
    buffer. The TPU lays its last two dimensions out in (8, 128) tiles,
    so the column count is held rounded up to 8 sublanes: 20 columns
    occupy 24 rows' worth of memory."""
    sublanes = -(-(num_feature_columns + 1) // 8) * 8
    return sublanes * 4 * num_rows


def _local_memory_stats() -> Optional[Tuple[int, int]]:
    """``(smallest bytes_limit, largest bytes_in_use)`` over this process's
    devices, or None where the backend keeps no such count (the CPU)."""
    stats = [dev.memory_stats() for dev in jax.local_devices()]
    if not all(s and s.get("bytes_limit") for s in stats):
        return None
    return (
        min(int(s["bytes_limit"]) for s in stats),
        max(int(s.get("bytes_in_use", 0)) for s in stats),
    )


def device_memory_budget(
    budget_frac: float = 0.35,
) -> Tuple[Optional[int], bool]:
    """Memory budget for the resident buffer: ``(bytes, per_device)``.

    Accelerators report ``bytes_limit`` through ``memory_stats`` — a
    PER-DEVICE figure, so an N-way batch-axis mesh holds N x that. The
    CPU backend reports nothing and gets a fraction of host RAM, which is
    a TOTAL figure: virtual CPU "devices" all share the same RAM, so
    sharding buys no extra capacity (``per_device=False``). ``(None, _)``
    means unknown — an accelerator that will not say how much memory it
    has gets no guess, and callers then do not choose resident mode.
    ``RSDL_RESIDENT_BUDGET_GB`` overrides everything, as a total.
    """
    env = os.environ.get("RSDL_RESIDENT_BUDGET_GB")
    if env:
        return int(float(env) * 1e9), False
    stats = _local_memory_stats()
    if stats is not None:
        return int(budget_frac * stats[0]), True
    if jax.local_devices()[0].platform != "cpu":
        return None, False
    ram = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    return int(budget_frac * ram), False


def fits_device(
    filenames: Sequence[str],
    num_feature_columns: int,
    mesh: Optional[Mesh] = None,
    batch_axis: str = "data",
    budget_frac: float = 0.35,
    num_rows: Optional[int] = None,
    pod_consistent: bool = False,
) -> bool:
    """Policy gate: can the packed dataset live resident in device memory?

    The buffer shards over the mesh's batch axis, so the budget applies
    to the per-device slice. ``num_rows`` skips the Parquet-footer sweep
    when the caller already knows the count (remote URIs pay a
    round-trip per file otherwise).

    Multi-controller pods: auto-select only when the caller declares the
    call SPMD (``pod_consistent=True`` — every process calls this at the
    same point, e.g. the bench and the pod examples); the per-process
    decisions are then allgathered and resident engages only if EVERY
    host agrees, so the pod can never split across delivery paths.
    Library callers probing from a single process keep the safe False.
    """
    if jax.process_count() > 1:
        if not pod_consistent:
            # Pod resident mode stays opt-in for non-SPMD callers: auto
            # must never silently swap one process's delivery path.
            return False
        local = bool(
            _fits_device_local(
                filenames,
                num_feature_columns,
                mesh,
                batch_axis,
                budget_frac,
                num_rows,
            )
        )
        from jax.experimental import multihost_utils

        votes = np.asarray(
            multihost_utils.process_allgather(
                jnp.asarray([int(local)], jnp.int32)
            )
        ).reshape(-1)
        return bool(votes.min())
    return _fits_device_local(
        filenames, num_feature_columns, mesh, batch_axis, budget_frac,
        num_rows,
    )


def _fits_device_local(
    filenames: Sequence[str],
    num_feature_columns: int,
    mesh: Optional[Mesh] = None,
    batch_axis: str = "data",
    budget_frac: float = 0.35,
    num_rows: Optional[int] = None,
) -> bool:
    # The mode's entire win is device memory being faster than host
    # memory. On the CPU backend the "device" IS host RAM and XLA-CPU
    # gathers are slow, so auto requires a real accelerator; setting
    # RSDL_RESIDENT_BUDGET_GB (or constructing
    # DeviceResidentShufflingDataset directly) opts in anyway.
    platform = jax.local_devices()[0].platform
    if platform == "cpu" and not os.environ.get("RSDL_RESIDENT_BUDGET_GB"):
        return False
    budget, per_device = device_memory_budget(budget_frac)
    if budget is None:
        return False
    if num_rows is None:
        num_rows = dataset_num_rows(filenames)
    # Sharding only multiplies capacity when each device has its own
    # memory; virtual CPU devices share one host RAM.
    shards = (
        mesh.shape.get(batch_axis, 1)
        if per_device and mesh is not None
        else 1
    )
    return packed_nbytes(num_rows, num_feature_columns) / max(1, shards) <= budget


class DeviceResidentShufflingDataset:
    """Shuffling dataset whose epoch shuffle runs entirely in device memory.

    API-compatible with :class:`~.jax_dataset.JaxShufflingDataset` for the
    training loop: ``set_epoch(epoch, skip_batches=...)`` then iterate
    ``(features, label)`` pairs of batch-axis-sharded ``jax.Array``s.

    Semantics parity with the general path (and the reference engine):

    * every row appears exactly once per epoch across all ranks
      (reference reducer permutation, ``shuffle.py:171-200``);
    * the epoch order is a deterministic function of ``(seed, epoch)``;
    * rank ``r`` of ``num_trainers`` sees a disjoint contiguous slice of
      the epoch permutation (reference ``np.array_split`` rank split,
      ``shuffle.py:125``);
    * ``drop_last=False`` yields the ragged tail batch (reference
      ``dataset.py:179-182``); the default True avoids an extra XLA
      compilation, as in ``JaxShufflingDataset``;
    * ``skip_batches`` resumes mid-epoch without re-gathering skipped
      batches (pairs with ``checkpoint.BatchCursor``).

    Args:
        lookahead: device batches dispatched ahead of consumption. The
            gathers are async XLA work; 2 keeps one batch materializing
            while one is consumed without holding an epoch of outputs.
        materialize_epoch: permute the WHOLE epoch with one device gather
            and cut batches as contiguous slices (None = auto: on when
            buffer + permuted copy fit 75% of the device budget). Both
            paths yield the identical batch stream for a given seed.
    """

    def __init__(
        self,
        filenames: List[str],
        num_epochs: int,
        batch_size: int,
        feature_columns: List[str],
        label_column: str,
        num_trainers: int = 1,
        rank: int = 0,
        drop_last: bool = True,
        seed: int = 0,
        mesh: Optional[Mesh] = None,
        batch_axis: str = "data",
        lookahead: int = 2,
        piece_rows: int = DEFAULT_PIECE_ROWS,
        num_rows: Optional[int] = None,
        progress_cb: Optional[Callable[[], None]] = None,
        materialize_epoch: Optional[bool] = None,
    ):
        if jax.process_count() > 1 and num_trainers != 1:
            # Multi-controller SPMD: every process executes the SAME
            # global batch stream and consumes its addressable shard of
            # each batch — the "rank" concept lives in the sharding, not
            # in disjoint streams.
            raise ValueError(
                "multi-controller resident mode is globally SPMD; use "
                "num_trainers=1 (each process consumes its addressable "
                "shard of every global batch)"
            )
        if not filenames:
            raise ValueError("no input files")
        if not 0 <= rank < num_trainers:
            raise ValueError(f"rank {rank} outside num_trainers {num_trainers}")
        if mesh is None:
            mesh = Mesh(np.array(jax.local_devices()), (batch_axis,))
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.batch_size = int(batch_size)
        self.num_epochs = int(num_epochs)
        self.num_trainers = int(num_trainers)
        self.rank = int(rank)
        self.drop_last = bool(drop_last)
        self.seed = int(seed)
        self._columns = list(feature_columns) + [label_column]
        self._feature_columns = list(feature_columns)
        self._label_column = label_column
        self._lookahead = max(1, int(lookahead))
        self._piece_rows = max(1, int(piece_rows))
        self._epoch: Optional[int] = None
        self._skip = 0
        self._perm_cache: Dict[int, jax.Array] = {}
        self._epoch_buf_cache: Dict[int, jax.Array] = {}
        # The multi-device fused path's (batches, cols, batch) epoch
        # tensor cache. Owned by the dataset (not the fused closure) so
        # the one-epoch-copy-at-a-time invariant can be enforced in BOTH
        # directions: fused clears _epoch_buf_cache, and _epoch_buf
        # clears this (a fused run degraded to per-batch must not keep
        # two epoch-sized HBM copies alive).
        self._fused_xs_cache: Dict[int, jax.Array] = {}
        self._materialize = materialize_epoch
        # Called after every staged piece: lets a long staging pass feed
        # an external liveness watchdog (the bench arms one).
        self._progress_cb = progress_cb
        self.stats = HostToDeviceStats()
        _refuse_wide_columns(filenames, self._columns)
        self._load(filenames, num_rows)

    # -- one-time staging ---------------------------------------------------

    def _load(self, filenames: List[str], num_rows: Optional[int]) -> None:
        """Decode → narrow → pack → stream to the device buffer.

        Decode runs on the worker pool (one file per worker); the driver
        packs completed files into fixed-width int32 pieces and dispatches
        a donated ``dynamic_update_slice`` per piece, so Parquet decode,
        host packing, and H2D transfer overlap. The buffer is padded past
        the real row count by one piece so the update never clamps; pad
        rows are never gathered (the permutation covers real rows only).

        Multi-controller pods branch to :meth:`_load_multiprocess`.
        """
        if jax.process_count() > 1:
            self._load_multiprocess(filenames, num_rows)
            return
        t0 = time.perf_counter()
        ctx = runtime.ensure_initialized()
        # Decode submission runs a SLIDING WINDOW ahead of the consume
        # cursor (pool width + slack), not all files up-front: when the
        # pool decodes faster than the driver packs and stages, completed
        # columnar objects would otherwise pile up un-consumed in /dev/shm
        # (spill keeps that correct but doubles the I/O) — the same
        # backpressure the map/reduce path gets from its epoch window.
        # Scheduler width = cluster-wide worker count when joined to a
        # cluster, else the local pool size.
        window = max(2, getattr(ctx.scheduler, "width", 1) + 2)
        pending = list(filenames)
        futs: List = []
        stage_tasks = min(len(filenames), window)

        def topup():
            while pending and len(futs) < window:
                futs.append(
                    ctx.scheduler.submit(
                        _decode_narrow_to_store,
                        pending.pop(0),
                        self._columns,
                        stage_tasks,
                    )
                )

        topup()
        ncols = len(self._columns)
        data_shards = self.mesh.shape.get(self.batch_axis, 1)

        # A caller-provided count skips the footer sweep; it is verified
        # against the rows actually streamed below.
        self.num_rows = (
            num_rows if num_rows is not None else dataset_num_rows(filenames)
        )
        n = self.num_rows
        w = min(self._piece_rows, max(1, n))
        padded = math.ceil((n + w) / data_shards) * data_shards
        self._padded_rows = padded

        buf_sharding = NamedSharding(self.mesh, P(None, self.batch_axis))
        buf = jax.jit(
            lambda: jnp.zeros((ncols, padded), jnp.int32),
            out_shardings=buf_sharding,
        )()

        update = jax.jit(
            lambda b, piece, start: jax.lax.dynamic_update_slice(
                b, piece, (jnp.int32(0), start)
            ),
            donate_argnums=0,
        )

        self._col_dtypes: Dict[str, str] = {}
        piece = np.empty((ncols, w), np.int32)
        fill = 0
        cursor = 0  # global row index of the piece's first row

        def flush():
            nonlocal buf, piece, fill, cursor
            buf = update(buf, jax.device_put(piece), np.int32(cursor))
            self.stats.bytes_staged += ncols * fill * 4
            cursor += fill
            piece = np.empty((ncols, w), np.int32)
            fill = 0
            if self._progress_cb is not None:
                self._progress_cb()

        while futs:
            fut = futs.pop(0)
            ref = fut.result()
            topup()  # keep the decode window full while this ref packs
            cb = ctx.store.get_columns(ref)
            cols = []
            for name in self._columns:
                arr = np.asarray(cb[name])
                if arr.ndim != 1 or arr.dtype.itemsize != 4:
                    raise TypeError(
                        f"resident mode needs flat 4-byte columns; "
                        f"{name!r} is {arr.dtype} with shape {arr.shape}"
                    )
                prev = self._col_dtypes.setdefault(name, str(arr.dtype))
                if prev != str(arr.dtype):
                    raise TypeError(
                        f"column {name!r} dtype differs across files: "
                        f"{prev} vs {arr.dtype}"
                    )
                cols.append(arr.view(np.int32))
            n_i = cols[0].shape[0]
            off = 0
            while off < n_i:
                take = min(w - fill, n_i - off)
                for ci in range(ncols):
                    piece[ci, fill : fill + take] = cols[ci][off : off + take]
                fill += take
                off += take
                if fill == w:
                    flush()
            del cb, cols
            ctx.store.free([ref])
        if fill:
            flush()
        if cursor != n:
            raise ValueError(
                f"dataset streamed {cursor} rows but num_rows says {n}; "
                "a caller-provided count was wrong"
            )
        jax.block_until_ready(buf)
        self._buf = buf
        self._finalize(t0)

    def _load_multiprocess(
        self, filenames: List[str], num_rows: Optional[int]
    ) -> None:
        """Pod staging: each process decodes and packs exactly the row
        range its devices address, then one
        ``jax.make_array_from_process_local_data`` call assembles the
        global resident buffer. Per-batch gathers over the global
        permutation then cross the pod as XLA collectives (ICI/DCN) —
        the pod-scale analog of the reference's cross-node object pulls
        (``/root/reference/ray_shuffling_data_loader/dataset.py:132-139``),
        but expressed as SPMD device computation instead of host fetches.
        """
        import pyarrow.parquet as pq

        t0 = time.perf_counter()
        ctx = runtime.ensure_initialized()
        ncols = len(self._columns)
        data_shards = self.mesh.shape.get(self.batch_axis, 1)
        self._col_dtypes = {}

        file_metas = _file_metadata(filenames)
        file_rows = [m.num_rows for m in file_metas]
        n = sum(file_rows)
        if num_rows is not None and num_rows != n:
            raise ValueError(
                f"dataset has {n} rows but num_rows says {num_rows}"
            )
        self.num_rows = n

        # Every process maps row offsets from ITS filename order; a
        # divergent order (e.g. numeric vs lexicographic listing) would
        # silently assemble a corrupt global buffer. Compare a digest of
        # the stream identity against process 0's before staging.
        import hashlib

        from jax.experimental import multihost_utils

        # Identity = basename + full Parquet footer fingerprint (schema,
        # created_by, serialized footer size, per-row-group row counts) —
        # same-named same-length files with different CONTENT diverge on
        # the footer, so they no longer assemble a silently corrupt
        # buffer. Deliberately NOT the full path: pods legitimately mount
        # one dataset at different paths per host.
        ident_parts = []
        for f, meta in zip(filenames, file_metas):
            ident_parts.extend(
                (
                    os.path.basename(f),
                    str(meta.num_rows),
                    str(meta.created_by),
                    str(meta.serialized_size),
                    # NOT str(meta.schema): ParquetSchema's repr leads
                    # with the object's memory address.
                    str(meta.schema.to_arrow_schema()),
                    *(
                        str(meta.row_group(i).num_rows)
                        for i in range(meta.num_row_groups)
                    ),
                )
            )
        digest16 = hashlib.blake2s(
            "\x00".join(ident_parts).encode()
        ).digest()[:16]
        digest_words = np.frombuffer(digest16, dtype=np.uint32)
        # allgather (not broadcast-and-compare-locally): EVERY process
        # must raise on divergence, or the agreeing ones proceed into
        # the staging collective and hang waiting for the one that bailed.
        digests = np.asarray(
            multihost_utils.process_allgather(jnp.asarray(digest_words))
        ).reshape(-1, 4)
        if len({tuple(row) for row in digests.tolist()}) != 1:
            raise ValueError(
                "file list (order/rows) differs across processes; all "
                "processes must pass the identical sequence of files"
            )
        padded = math.ceil(n / data_shards) * data_shards
        self._padded_rows = padded

        # Column dtypes must be IDENTICAL on every process (they shape
        # the jitted gather program), so derive them from the schema, not
        # from whichever files this process happens to decode.
        from ray_shuffling_data_loader_tpu.shuffle import narrowed_dtype

        from ray_shuffling_data_loader_tpu.utils import (
            parquet_filesystem,
        )

        _fs0, _rel0 = parquet_filesystem(filenames[0])
        schema = pq.ParquetFile(_rel0, filesystem=_fs0).schema_arrow
        for name in self._columns:
            np_dtype = np.dtype(schema.field(name).type.to_pandas_dtype())
            narrowed = str(narrowed_dtype(np_dtype))
            if np.dtype(narrowed).itemsize != 4:
                raise TypeError(
                    f"resident mode needs 4-byte columns; {name!r} "
                    f"decodes to {narrowed}"
                )
            self._col_dtypes[name] = narrowed

        # This process's addressable column range of the global buffer.
        sharding = NamedSharding(self.mesh, P(None, self.batch_axis))
        imap = sharding.devices_indices_map((ncols, padded))
        me = jax.process_index()
        # set(): devices replicated along non-batch mesh axes (e.g. the
        # model axis) report the SAME span; double-counting them fails
        # the contiguity sum below.
        spans = sorted(
            {
                (
                    idx[1].start or 0,
                    idx[1].stop if idx[1].stop is not None else padded,
                )
                for dev, idx in imap.items()
                if dev.process_index == me
            }
        )
        lo, hi = spans[0][0], spans[-1][1]
        if sum(b - a for a, b in spans) != hi - lo:
            raise ValueError(
                "this process's addressable shards are not contiguous in "
                "the row dimension; use a mesh whose batch axis orders "
                "devices by process"
            )

        local = np.zeros((ncols, hi - lo), np.int32)
        offsets = np.concatenate([[0], np.cumsum(file_rows)])
        # Per-file overlap with this process's range, decoded at
        # row-group granularity (a boundary-straddling file costs only
        # its overlapping groups, not a full decompress). Local pool on
        # purpose: cluster-wide scatter would publish segments on other
        # hosts and pull them straight back over DCN.
        spans_by_file = []
        for i in range(len(filenames)):
            # offsets[-1] == n is validated above, so file spans never
            # exceed n on their own; only the process bound hi clips.
            file_lo = max(lo, int(offsets[i]))
            file_hi = min(hi, int(offsets[i + 1]))
            if file_lo < file_hi:
                spans_by_file.append((i, file_lo, file_hi))
        _stage_tasks = max(1, len(spans_by_file))
        futs = {
            i: ctx.pool.submit(
                _decode_narrow_range_to_store,
                filenames[i],
                self._columns,
                file_lo - int(offsets[i]),
                file_hi - int(offsets[i]),
                _stage_tasks,
            )
            for i, file_lo, file_hi in spans_by_file
        }
        for i, file_lo, file_hi in spans_by_file:
            ref = futs[i].result()
            cb = ctx.store.get_columns(ref)
            dst = slice(file_lo - lo, file_hi - lo)
            for ci, name in enumerate(self._columns):
                arr = np.asarray(cb[name])
                if str(arr.dtype) != self._col_dtypes[name]:
                    raise TypeError(
                        f"column {name!r}: file {filenames[i]!r} decodes "
                        f"to {arr.dtype}, schema says "
                        f"{self._col_dtypes[name]}"
                    )
                local[ci, dst] = arr.view(np.int32)
            self.stats.bytes_staged += ncols * (file_hi - file_lo) * 4
            del cb
            ctx.store.free([ref])
            if self._progress_cb is not None:
                self._progress_cb()
        self._buf = jax.make_array_from_process_local_data(
            sharding, local, (ncols, padded)
        )
        jax.block_until_ready(self._buf)
        self._finalize(t0)

    def _finalize(self, t0: float) -> None:
        n = self.num_rows
        self.stats.batches_staged = 0
        self.stats.first_batch_s = time.perf_counter() - t0

        # Rank split: contiguous near-equal slices, arithmetically (the
        # same boundaries ``np.array_split`` would give over the row
        # space — reference rank split, ``shuffle.py:125`` — without
        # materializing an arange over hundreds of millions of rows).
        base, extra = divmod(n, self.num_trainers)
        r = self.rank
        self._rank_start = r * base + min(r, extra)
        self._rank_rows = base + (1 if r < extra else 0)

        def epoch_permutation(epoch):
            return jax.random.permutation(
                jax.random.fold_in(jax.random.key(self.seed), epoch), n
            )

        self._perm_fn = jax.jit(epoch_permutation)
        self._gather_cache: Dict[Tuple[str, int], object] = {}

        # Epoch materialization policy: ONE whole-epoch gather (then
        # batches are contiguous slices — no per-batch gather dispatch,
        # and in pods one collective per epoch instead of per batch) when
        # buffer + permuted copy both fit; else per-batch gathers. Total
        # gathered bytes are identical either way — every row moves once
        # per epoch — so this trades transient memory for dispatch
        # latency and access locality.
        if self._materialize is None:
            data_shards = max(1, self.mesh.shape.get(self.batch_axis, 1))
            copy_bytes = packed_nbytes(
                self._padded_rows, len(self._feature_columns)
            )
            stats = _local_memory_stats()
            if stats is not None:
                # Real accounting: bytes_in_use already includes the
                # staged buffer AND whatever model/optimizer state the
                # trainer holds, so the epoch copy is the only increment.
                limit, in_use = stats
                decision = in_use + copy_bytes // data_shards <= 0.75 * limit
            else:
                budget, per_device = device_memory_budget(budget_frac=0.75)
                shards = data_shards if per_device else 1
                decision = (
                    budget is not None and 2 * copy_bytes / shards <= budget
                )
            if jax.process_count() > 1:
                # Multi-controller: the two schedules issue DIFFERENT
                # collectives, so every process must pick the same one.
                # bytes_in_use varies across hosts (head-process
                # overhead, allocator jitter) — process 0's call decides
                # for the pod.
                from jax.experimental import multihost_utils

                decision = bool(
                    int(
                        multihost_utils.broadcast_one_to_all(
                            jnp.asarray(int(decision), jnp.int32)
                        )
                    )
                )
            self._materialize = bool(decision)

        buf_sharding = NamedSharding(self.mesh, P(None, self.batch_axis))
        padded = self._padded_rows

        def permute_all(buf, perm):
            # Pad the permutation up to the buffer width so the permuted
            # copy shards evenly; pad rows land at the tail, past every
            # slice any batch can take.
            full = jnp.concatenate(
                [perm, jnp.arange(n, padded, dtype=perm.dtype)]
            )
            return jnp.take(buf, full, axis=1)

        self._permute_all = jax.jit(permute_all, out_shardings=buf_sharding)

    def _unpack_rows(self):
        """Shared tail of both batch paths: packed int32 rows → bitcast
        feature dict + label."""
        names = self._feature_columns
        dtypes = [self._col_dtypes[c] for c in self._columns]

        def unpack(rows):
            feats = {}
            for i, name in enumerate(names):
                col = rows[i]
                if dtypes[i] != "int32":
                    col = jax.lax.bitcast_convert_type(
                        col, jnp.dtype(dtypes[i])
                    )
                feats[name] = col
            label = rows[-1]
            if dtypes[-1] != "int32":
                label = jax.lax.bitcast_convert_type(
                    label, jnp.dtype(dtypes[-1])
                )
            return feats, label

        return unpack

    def _out_shardings(self):
        out_sharding = NamedSharding(self.mesh, P(self.batch_axis))
        return (
            {name: out_sharding for name in self._feature_columns},
            out_sharding,
        )

    def _gather_fn(self, width: int):
        """Jitted batch gather (per-batch path): row-slice of the epoch
        permutation → one-gather batch → bitcast unpack."""
        fn = self._gather_cache.get(("gather", width))
        if fn is None:
            unpack = self._unpack_rows()

            def gather(buf, perm, start):
                idx = jax.lax.dynamic_slice(perm, (start,), (width,))
                return unpack(jnp.take(buf, idx, axis=1))

            fn = jax.jit(gather, out_shardings=self._out_shardings())
            self._gather_cache[("gather", width)] = fn
        return fn

    def _slice_fn(self, width: int):
        """Jitted batch cut (materialized-epoch path): a contiguous slice
        of the already-permuted epoch buffer → bitcast unpack."""
        fn = self._gather_cache.get(("slice", width))
        if fn is None:
            unpack = self._unpack_rows()
            ncols = len(self._columns)

            def cut(ebuf, start):
                rows = jax.lax.dynamic_slice(
                    ebuf, (jnp.int32(0), start), (ncols, width)
                )
                return unpack(rows)

            fn = jax.jit(cut, out_shardings=self._out_shardings())
            self._gather_cache[("slice", width)] = fn
        return fn

    def _epoch_buf(self, epoch: int) -> jax.Array:
        ebuf = self._epoch_buf_cache.get(epoch)
        if ebuf is None:
            # One permuted copy lives at a time — across both caches
            # (see _fused_xs_cache).
            self._epoch_buf_cache.clear()
            self._fused_xs_cache.clear()
            ebuf = self._permute_all(self._buf, self._perm(epoch))
            self._epoch_buf_cache[epoch] = ebuf
        return ebuf

    # -- iteration ----------------------------------------------------------

    @property
    def num_batches(self) -> int:
        """Batches this rank yields per epoch."""
        full, rem = divmod(self._rank_rows, self.batch_size)
        return full + (1 if rem and not self.drop_last else 0)

    def set_epoch(self, epoch: int, skip_batches: int = 0) -> None:
        self._check_open()
        if not 0 <= epoch < self.num_epochs:
            raise ValueError(
                f"epoch {epoch} outside num_epochs {self.num_epochs}"
            )
        self._epoch = epoch
        self._skip = int(skip_batches)

    def close(self) -> None:
        """Release the resident buffers (HBM) deterministically instead
        of waiting for GC — after this the dataset cannot iterate."""
        self._closed = True
        self._buf = None
        self._epoch_buf_cache.clear()
        self._fused_xs_cache.clear()
        self._perm_cache.clear()
        self._gather_cache.clear()
        self._epoch = None

    def _check_open(self) -> None:
        if getattr(self, "_closed", False):
            raise RuntimeError(
                "dataset is closed (close() released its device buffers)"
            )

    def _perm(self, epoch: int) -> jax.Array:
        perm = self._perm_cache.get(epoch)
        if perm is None:
            # Keep only the latest epoch's permutation resident.
            self._perm_cache.clear()
            perm = self._perm_fn(np.int32(epoch))
            self._perm_cache[epoch] = perm
        return perm

    def __iter__(self):
        self._check_open()
        if self._epoch is None:
            raise RuntimeError("set_epoch must be called before iterating")
        epoch, skip = self._epoch, self._skip
        # The epoch boundary is where the process's tracing flag follows
        # a profiler session.
        telemetry.refresh_active()
        b = self.batch_size
        full, rem = divmod(self._rank_rows, b)
        widths = [b] * full
        if rem and not self.drop_last:
            widths.append(rem)

        # Note on stall accounting: handing a batch to the consumer never
        # blocks the host — the gather is async XLA work and the arrays
        # are futures — so ``stats.stall_s`` (host-side trainer wait, the
        # reference's batch-wait-time metric) is genuinely ~0 here. If a
        # gather is slow, the wait surfaces inside the consumer's step
        # as a data dependency, i.e. in step time, not in stall.
        from collections import deque

        pending = deque()
        start = self._rank_start + skip * b

        def dispatch(index: int, width: int) -> None:
            nonlocal start
            # Re-checked per batch: a close() between yields must fail
            # fast here, not crash inside jit on a None buffer (and, on
            # the materialized path, not keep serving from the local
            # ebuf reference after the docstring promised release).
            self._check_open()
            with telemetry.trace_span(
                "resident:dispatch", cat="resident", epoch=epoch, batch=index
            ):
                if self._materialize:
                    item = self._slice_fn(width)(ebuf, np.int32(start))
                else:
                    item = self._gather_fn(width)(
                        self._buf, perm, np.int32(start)
                    )
            pending.append(item)
            start += width
            self.stats.batches_staged += 1

        todo = list(enumerate(widths))[skip:]
        # ``resident:handover``: what the host does between two epochs
        # before the consumer has a batch to step on — the permutation's
        # draw, ``permute_all`` and the first batch's dispatch. The device
        # side of it is in the profiler's trace (``epoch_permutation``,
        # ``permute_all``); nothing here waits for the device.
        with telemetry.trace_span(
            "resident:handover", cat="resident", epoch=epoch, rank=self.rank
        ):
            perm = self._perm(epoch)
            ebuf = self._epoch_buf(epoch) if self._materialize else None
            if todo:
                dispatch(*todo[0])
        for index, width in todo[1:]:
            while len(pending) > self._lookahead:
                yield pending.popleft()
            dispatch(index, width)
        while pending:
            yield pending.popleft()


def make_fused_epoch(
    ds: DeviceResidentShufflingDataset,
    step_body: Callable,
    donate_state: bool = True,
) -> Callable:
    """Fuse a WHOLE training epoch into one jitted device program.

    The resident design's unique capability: with the packed dataset (and
    each epoch's permutation) living in device memory, the entire epoch —
    per-batch slice, bitcast unpack, and the training step — compiles to a
    single ``lax.scan``. One dispatch per epoch replaces one (or more)
    host round-trips per batch. No host-side loader can do this; it is
    the device-resident analog of the reference's tightest possible
    consumption loop.

    ``step_body(state, features, label) -> (state, metrics)`` is the
    UNJITTED per-batch step (e.g. the body of
    :func:`~.parallel.train.make_train_step`); ``metrics`` must be a dict
    containing ``"loss"``.

    Returns ``run_epoch(state, epoch) -> (state, losses)`` where
    ``losses`` is the per-batch loss array for the epoch. Only full
    batches run fused (the resident loader defaults to ``drop_last=True``
    already); the epoch's permutation and (on the materialized schedule)
    the permuted copy are produced on device exactly as the per-batch
    iterator would.

    Multi-device meshes scan a pre-sharded ``(num_batches, ncols,
    batch)`` epoch tensor instead of dynamic-slicing the row-sharded
    buffer: the slice form makes the SPMD partitioner all-gather every
    batch inside the scan (r4 measurements: 5.7x slower at toy scale,
    and a hard rendezvous stall on the 8-virtual-device CPU backend),
    while the scan-layout form keeps every step's data access local so
    only the step's own gradient collectives remain.
    """
    ds._check_open()
    unpack = ds._unpack_rows()
    b = ds.batch_size
    full = ds._rank_rows // b
    ncols = len(ds._columns)
    start0 = ds._rank_start
    ndev = int(ds.mesh.devices.size) if ds.mesh is not None else 1

    if ndev > 1:
        # Multi-device: scanning a dynamic_slice over the row-sharded
        # epoch buffer makes the SPMD partitioner insert a cross-device
        # all-gather of every batch INSIDE the scan (measured r4: 5.7x
        # slower than the xs form below even at toy scale, and on the
        # CPU backend the per-iteration collective rendezvous starves
        # outright with 8 virtual devices on saturated cores). Instead,
        # materialize the epoch directly in scan layout: xs[i] = batch
        # i's packed rows, (full, ncols, b) with the BATCH-ROW axis
        # sharded — every scan step then slices purely locally and the
        # only collectives left are the step's own gradient psums. One
        # gather per epoch (same traffic as ``_permute_all``), same HBM
        # footprint as the materialized epoch copy it replaces.
        xs_sharding = NamedSharding(ds.mesh, P(None, None, ds.batch_axis))

        def make_xs(buf, perm):
            rows = jnp.take(
                buf, perm[start0 : start0 + full * b], axis=1
            )
            return jnp.moveaxis(rows.reshape(ncols, full, b), 0, 1)

        xs_fn = jax.jit(make_xs, out_shardings=xs_sharding)

        def run_epoch(state, xs):
            def body(state, rowsb):
                feats, label = unpack(rowsb)
                state, metrics = step_body(state, feats, label)
                return state, metrics["loss"]

            return jax.lax.scan(body, state, xs)

        fused = jax.jit(
            traced_in_mesh(ds.mesh, run_epoch),
            donate_argnums=(0,) if donate_state else (),
        )
        xs_cache = ds._fused_xs_cache

        def run(state, epoch: int):
            ds._check_open()
            if not 0 <= epoch < ds.num_epochs:
                raise ValueError(f"epoch {epoch} outside {ds.num_epochs}")
            if not ds._materialize:
                # Budget said no epoch-sized copy; fuse over per-batch
                # gathers instead (collectives per step — fine on real
                # ICI, the budget constraint dominates).
                return _run_gather_fused(
                    ds, step_body, donate_state, state, epoch
                )
            xs = xs_cache.get(epoch)
            if xs is None:
                # One epoch-sized device copy at a time, across BOTH
                # caches: a prior per-batch iteration leaves its permuted
                # epoch copy in ds._epoch_buf_cache, and keeping it
                # alongside xs would double the stated HBM footprint.
                xs_cache.clear()
                ds._epoch_buf_cache.clear()
                xs = xs_fn(ds._buf, ds._perm(epoch))
                xs_cache[epoch] = xs
            state, losses = fused(state, xs)
            ds.stats.batches_staged += int(full)
            return state, losses

        return run

    def run_epoch(state, ebuf):
        def body(state, i):
            rows = jax.lax.dynamic_slice(
                ebuf,
                (jnp.int32(0), jnp.int32(start0) + i * jnp.int32(b)),
                (ncols, b),
            )
            feats, label = unpack(rows)
            state, metrics = step_body(state, feats, label)
            return state, metrics["loss"]

        return jax.lax.scan(body, state, jnp.arange(full, dtype=jnp.int32))

    fused = jax.jit(
        traced_in_mesh(ds.mesh, run_epoch),
        donate_argnums=(0,) if donate_state else (),
    )

    def run(state, epoch: int):
        ds._check_open()
        if not 0 <= epoch < ds.num_epochs:
            raise ValueError(f"epoch {epoch} outside {ds.num_epochs}")
        if ds._materialize:
            ebuf = ds._epoch_buf(epoch)
        else:
            # Gather schedule: materializing would blow the budget; fuse
            # over a VIEW of the base buffer permuted per batch instead.
            return _run_gather_fused(
                ds, step_body, donate_state, state, epoch
            )
        state, losses = fused(state, ebuf)
        ds.stats.batches_staged += int(full)
        return state, losses

    return run


def _run_gather_fused(ds, step_body, donate_state, state, epoch):
    """Fused epoch for the per-batch-gather schedule: the scan body
    gathers its batch rows through the epoch permutation instead of
    slicing a materialized copy. The jit cache keys on the step body
    (and donation mode) too — one staged dataset can be fused with
    different models without silently replaying the first's program."""
    unpack = ds._unpack_rows()
    b = ds.batch_size
    full = ds._rank_rows // b
    start0 = ds._rank_start
    # The cache entry pins the step_body object and is verified by
    # identity on hit: a bare id() key could silently alias a new body
    # allocated at a recycled address after the old one was GC'd.
    key = ("fused-gather", b, id(step_body), bool(donate_state))
    hit = ds._gather_cache.get(key)
    fn = None
    if hit is not None and hit[0] is step_body:
        fn = hit[1]
    if fn is None:

        def run_epoch(state, buf, perm):
            def body(state, i):
                idx = jax.lax.dynamic_slice(
                    perm, (jnp.int32(start0) + i * jnp.int32(b),), (b,)
                )
                feats, label = unpack(jnp.take(buf, idx, axis=1))
                state, metrics = step_body(state, feats, label)
                return state, metrics["loss"]

            return jax.lax.scan(
                body, state, jnp.arange(full, dtype=jnp.int32)
            )

        fn = jax.jit(
            traced_in_mesh(ds.mesh, run_epoch),
            donate_argnums=(0,) if donate_state else (),
        )
        ds._gather_cache[key] = (step_body, fn)
    state, losses = fn(state, ds._buf, ds._perm(epoch))
    ds.stats.batches_staged += int(full)
    return state, losses
