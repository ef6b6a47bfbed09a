"""JAX/TPU batch delivery: HBM-resident, mesh-sharded training batches.

This layer replaces the reference's framework adapter + CUDA staging path
(``torch_dataset.py:95-236`` DataFrame→CPU-tensor conversion, with the
``.cuda()`` copy left to the user loop, ``ray_torch_shuffle.py:204-207``)
with the TPU-native design from BASELINE.json's north star:

* a background **stager thread** pulls exact-size columnar batches from
  :class:`~.dataset.ShufflingDataset`, converts columns to device dtypes,
  and dispatches **async ``jax.device_put``** onto the mesh — the transfer
  of batch ``t+k`` overlaps the training step on batch ``t``;
* a bounded ring (``prefetch_depth``, default 2 = double buffering)
  applies backpressure so at most ``prefetch_depth`` batches are in flight
  to HBM — the analog of the reference's ``ray.wait(fetch_local=True)``
  prefetch (``dataset.py:132-137``), but targeting device memory;
* yielded batches are **global ``jax.Array``s sharded along the mesh's
  batch axis** (``NamedSharding(mesh, P('data', ...))``), so a ``pjit``-ed
  train step consumes them with zero further data movement. In multi-host
  pods each process stages its own rank's shard and the global array is
  assembled with ``jax.make_array_from_process_local_data``.

Batch spec parity: ``feature_columns`` / ``feature_types`` / ``label_column``
etc. mirror the reference's Torch data spec (``torch_dataset.py:45-59``);
dtypes default to TPU-friendly 32-bit (int64→int32, float64→float32).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu import telemetry
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
from ray_shuffling_data_loader_tpu.runtime import ColumnBatch
from ray_shuffling_data_loader_tpu.runtime.store import (
    packed_slots,
    packed_widths,
)
from ray_shuffling_data_loader_tpu.telemetry import audit as _audit
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics
from ray_shuffling_data_loader_tpu.telemetry import phases as _phases


def _default_device_dtype(np_dtype: np.dtype) -> jnp.dtype:
    """TPU-friendly narrowing: 64-bit host columns become 32-bit on device
    (embedding indices never exceed int32 range in DATA_SPEC; fp64 is
    unsupported-by-default on TPU anyway)."""
    if np.issubdtype(np_dtype, np.integer):
        return jnp.int32
    if np.issubdtype(np_dtype, np.floating):
        return jnp.float32
    raise TypeError(f"unsupported column dtype {np_dtype}")


@dataclass
class JaxBatchSpec:
    """Feature/label layout for device batches (parity:
    ``_normalize_torch_data_spec``, reference ``torch_dataset.py:144-201``)."""

    feature_columns: List[str]
    # None: a batch of features only (a sequence model's tokens).
    label_column: Optional[str]
    feature_types: Optional[List[Any]] = None
    feature_shapes: Optional[List[Optional[Tuple[int, ...]]]] = None
    label_type: Any = None
    label_shape: Optional[Tuple[int, ...]] = None

    def normalize(self) -> "JaxBatchSpec":
        n = len(self.feature_columns)
        types = self.feature_types or [None] * n
        shapes = self.feature_shapes or [None] * n
        assert len(types) == n, "feature_types size must match feature_columns"
        assert len(shapes) == n, "feature_shapes size must match feature_columns"
        return JaxBatchSpec(
            feature_columns=list(self.feature_columns),
            label_column=self.label_column,
            feature_types=list(types),
            feature_shapes=[
                tuple(s) if s is not None else None for s in shapes
            ],
            label_type=self.label_type,
            label_shape=tuple(self.label_shape)
            if self.label_shape is not None
            else None,
        )


class HostToDeviceStats:
    """Staging instrumentation: bytes staged and consumer stall time (time
    the training loop waited on the ring). The reference measures the
    trainer-side analog as batch wait time
    (``ray_torch_shuffle.py:201-230``). Where tracing was active
    (``RSDL_TRACE`` or a profiler session), :meth:`as_dict` also carries
    the per-layer counts folded from the process's spans."""

    def __init__(self):
        self.bytes_staged = 0
        # Device-direct delivery: bytes handed to ``device_put`` straight
        # off the store's mmapped packed segments — no host-side
        # rebatch/pack copy was paid for them. ``bytes_staged`` keeps
        # counting the HOST-COPIED staging bytes (the amplification the
        # metric always measured); the two together are total H2D.
        self.bytes_staged_direct = 0
        self.batches_staged = 0
        self.batches_staged_direct = 0
        self.stall_s = 0.0
        self.stalls = 0
        # Decomposition of ``stall_s`` by what the stager was doing when
        # the consumer's wait ended (VERDICT r4 item 2 — "loader too slow"
        # vs "transfer too slow" must be distinguishable):
        #   upstream — the stager was itself blocked on the host dataset
        #     (epoch window closed / shuffle still producing; no batch in
        #     flight for this consumer);
        #   staging — a host batch existed and the stall was the H2D
        #     convert+transfer pipeline running behind the consumer.
        self.stall_upstream_s = 0.0
        self.stall_staging_s = 0.0
        self.first_batch_s: Optional[float] = None

    def as_dict(self) -> Dict[str, Any]:
        """The flat counts, plus ``"layers"`` (:func:`layer_counts` of the
        process's span buffer, computed when asked for) where tracing
        recorded something a layer counts; the key is absent otherwise."""
        out: Dict[str, Any] = {
            "bytes_staged": self.bytes_staged,
            "bytes_staged_direct": self.bytes_staged_direct,
            "batches_staged": self.batches_staged,
            "batches_staged_direct": self.batches_staged_direct,
            "stall_s": self.stall_s,
            "stalls": self.stalls,
            "stall_upstream_s": self.stall_upstream_s,
            "stall_staging_s": self.stall_staging_s,
            "first_batch_s": self.first_batch_s or 0.0,
        }
        layers = layer_counts(telemetry.local_spans())
        if layers:
            out["layers"] = layers
        return out


def layer_counts(spans) -> Dict[str, Dict[str, Any]]:
    """What the per-layer metrics read of the layers beneath the loader,
    folded from a span list and kept nowhere else; a layer is present
    only if it recorded one of its spans. Everything else a span carries
    (``put_ns``, ``error``, ``retry``, ``refs``) stays on the span, for
    the merged trace.

    * ``runtime.by_fn[<fn>]``: ``tasks``, ``wait_s`` (submit to the
      worker's start), ``run_s`` of the ``pool:<fn>`` spans
    * ``shuffle.epoch_s``: seconds of each ``shuffle:epoch``, in order;
      ``shuffle.schedules``: the schedule each of them ran, in that order
    * ``delivery``: ``gets``, ``get_wait_s`` of the ``queue:get`` spans
    * ``staging``: ``stager_s`` (``stage:epoch``, the stager thread's
      life), ``ring_put_s`` (``stage:ring-put``, the loader's slack),
      ``transfers`` and ``max_transfer_s`` of the ``stage:transfer`` spans
    * ``train step[<name>]``: of the spans of the category ``train`` (the
      counters a model's step hands over, under the names and with the
      numbers the model chose: ``parallel/train.py``), ``spans`` and
      ``sum``, every number they carry added up; a reader divides. Of
      ``step:ops`` also ``table`` and ``program``, the newest span's: what
      maps a device operation's own name to its scope
    """
    out: Dict[str, Dict[str, Any]] = {}
    epochs: List[Tuple[float, float, Any]] = []
    ops = None  # the newest ``step:ops`` span
    for span in spans:
        name = span["name"]
        dur_s = span["dur"] / 1e6
        if name.startswith("pool:"):
            wait_s = min(dur_s, span["args"].get("wait_ns", 0) / 1e9)
            fn = out.setdefault("runtime", {"by_fn": {}})["by_fn"].setdefault(
                name[len("pool:"):], {"tasks": 0, "wait_s": 0.0, "run_s": 0.0}
            )
            fn["tasks"] += 1
            fn["wait_s"] += wait_s
            fn["run_s"] += dur_s - wait_s
        elif name == "shuffle:epoch":
            epochs.append((span["ts"], dur_s, span["args"].get("schedule")))
        elif name == "queue:get":
            delivery = out.setdefault(
                "delivery", {"gets": 0, "get_wait_s": 0.0}
            )
            delivery["gets"] += 1
            delivery["get_wait_s"] += dur_s
        elif span.get("cat") == "train":
            counter = out.setdefault("train step", {}).setdefault(
                name, {"spans": 0, "sum": {}}
            )
            counter["spans"] += 1
            for key, value in span["args"].items():
                if isinstance(value, (int, float)):
                    counter["sum"][key] = counter["sum"].get(key, 0) + value
            if name == "step:ops" and (ops is None or span["ts"] >= ops["ts"]):
                ops = span
        elif name in ("stage:epoch", "stage:ring-put", "stage:transfer"):
            staging = out.setdefault(
                "staging",
                {"stager_s": 0.0, "ring_put_s": 0.0, "transfers": 0,
                 "max_transfer_s": 0.0},
            )
            if name == "stage:epoch":
                staging["stager_s"] += dur_s
            elif name == "stage:ring-put":
                staging["ring_put_s"] += dur_s
            else:
                staging["transfers"] += 1
                staging["max_transfer_s"] = max(
                    staging["max_transfer_s"], dur_s
                )
    if epochs:
        epochs.sort(key=lambda e: e[0])
        out["shuffle"] = {
            "epoch_s": [dur_s for _, dur_s, _ in epochs],
            "schedules": [schedule for _, _, schedule in epochs],
        }
    if ops is not None:
        out["train step"]["step:ops"].update(
            table=ops["args"]["table"], program=ops["args"]["program"]
        )
    return out


class _TransferWatcher:
    """Times each batch from ``device_put``'s dispatch to its arrays ready
    on the device (``stage:transfer``), on a thread of its own so that
    neither the stager nor the consumer ever blocks for it. It exists only
    while tracing is active: one per epoch's iteration, none otherwise."""

    def __init__(self):
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="hbm-transfer-watch", daemon=True
        )
        self._thread.start()

    def watch(self, epoch, batch, nbytes, put_wall, put, arrays) -> None:
        """``put`` is what ``device_put`` returned, ``arrays`` the batch as
        the consumer gets it (the jitted unpack's outputs)."""
        self._queue.put((epoch, batch, nbytes, put_wall, put, arrays))

    def close(self) -> None:
        """No more batches: the thread ends once it has seen the ones it
        was given ready. Nobody waits for it."""
        self._queue.put(None)

    def _run(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            epoch, batch, nbytes, put_wall, put, arrays = item
            del item
            try:
                jax.block_until_ready(put)
                put_ready = time.time()
                jax.block_until_ready(arrays)
            except RuntimeError:
                # The consumer donated or deleted the batch before it
                # was seen ready: nothing to time.
                continue
            finally:
                del put, arrays
            telemetry.record_span(
                "stage:transfer",
                put_wall,
                time.time() - put_wall,
                cat="staging",
                parent="stage:h2d",
                epoch=epoch,
                batch=batch,
                bytes=nbytes,
                # Dispatch -> the put's own buffer on the device: the
                # transfer proper. The rest of the span is the unpack,
                # which queues behind whatever the device is running.
                put_ns=int(1e9 * (put_ready - put_wall)),
            )


class JaxShufflingDataset:
    """Shuffling dataset yielding mesh-sharded, HBM-resident JAX batches.

    Iterating yields ``(features, label)`` where ``features`` is a dict
    mapping feature column name to a global ``jax.Array`` sharded along
    ``batch_axis``, and ``label`` likewise (``None`` where
    ``label_column`` is None: a batch of features only). A column of one
    number a row arrives as ``[batch]``; a ``fixed_size_list`` column of
    ``width`` numbers a row (a token sequence) as ``[batch, width]``.

    Args mirror :class:`~.dataset.ShufflingDataset` (reference
    ``dataset.py:37-48``) plus the batch spec and device placement:

    Args:
        mesh: ``jax.sharding.Mesh`` to shard batches over. Default: a 1-D
            ``('data',)`` mesh over all local devices.
        batch_axis: mesh axis name carrying the batch dimension.
        prefetch_depth: in-flight device batches (2 = double buffering).
        drop_last: defaults to **True** here (unlike the reference's False,
            ``dataset.py:43``): a ragged final batch would retrigger XLA
            compilation; opt back in explicitly if you want the tail. A
            tail whose row count doesn't divide the data axis arrives
            REPLICATED (single-process only; pods raise with the remedy)
            since ``device_put`` cannot shard it evenly.
    """

    def __init__(
        self,
        filenames: List[str],
        num_epochs: int,
        num_trainers: int,
        batch_size: int,
        rank: int,
        feature_columns: List[str],
        label_column: Optional[str] = None,
        feature_types: Optional[List[Any]] = None,
        feature_shapes: Optional[List[Any]] = None,
        label_type: Any = None,
        label_shape: Optional[Tuple[int, ...]] = None,
        drop_last: bool = True,
        num_reducers: Optional[int] = None,
        max_concurrent_epochs: int = 2,
        seed: int = 0,
        queue_name: str = "BatchQueue",
        mesh: Optional[Mesh] = None,
        batch_axis: str = "data",
        prefetch_depth: int = 2,
        start_epoch: int = 0,
        cache_decoded: Optional[bool] = None,
        stats_collector=None,
    ):
        self._spec = JaxBatchSpec(
            feature_columns=feature_columns,
            label_column=label_column,
            feature_types=feature_types,
            feature_shapes=feature_shapes,
            label_type=label_type,
            label_shape=label_shape,
        ).normalize()
        if mesh is None:
            mesh = Mesh(np.array(jax.local_devices()), (batch_axis,))
        self.mesh = mesh
        self.batch_axis = batch_axis
        # Device-direct delivery (ROADMAP 3): when every spec column holds
        # 4-byte numbers as the files have them and the batch divides
        # this process's slice of the data axis, ask the shuffle to emit
        # reducer output already in the [n_slots, batch] staging layout — the stager then
        # ``device_put``s straight off the store's mmapped segments,
        # killing the host-side rebatch+pack amplification. The layout
        # request must exist BEFORE the underlying dataset construction:
        # rank 0's constructor kicks off the multi-epoch shuffle.
        self._device_layout = self._device_layout_request(batch_size)
        self._ds = ShufflingDataset(
            filenames,
            num_epochs,
            num_trainers,
            batch_size,
            rank,
            drop_last=drop_last,
            num_reducers=num_reducers,
            max_concurrent_epochs=max_concurrent_epochs,
            seed=seed,
            queue_name=queue_name,
            start_epoch=start_epoch,
            # The device path narrows to 32-bit at staging regardless, so
            # narrowing at decode halves every host-side pass for free.
            narrow_to_32=True,
            cache_decoded=cache_decoded,
            stats_collector=stats_collector,
            device_layout=self._device_layout,
        )
        self._prefetch_depth = max(1, prefetch_depth)
        self._unpack_cache: Dict[Any, Any] = {}
        # Device-direct: per-layout-signature eligibility cache.
        self._direct_sig_cache: Dict[Any, bool] = {}
        self.stats = HostToDeviceStats()
        # Pre-resolved H2D instruments: _stage runs per batch on the
        # staging hot path; instruments are registry singletons, so hoist
        # the keyed lookups (format_key + registry lock) out of it.
        if _metrics.enabled():
            reg = _metrics.registry
            self._h2d_bytes = reg.counter("h2d.bytes")
            self._h2d_batches = reg.counter("h2d.batches")
            self._h2d_dispatch_s = reg.histogram("h2d.dispatch_seconds")
        else:
            self._h2d_bytes = None
            self._h2d_batches = None
            self._h2d_dispatch_s = None

    # -- device-direct layout (ROADMAP 3 / ISSUE 8) -------------------------

    def _device_layout_request(self, batch_size: int) -> Optional[Dict]:
        """The staging layout to ask the shuffle for, or None when this
        spec cannot take it: any explicit non-4-byte dtype, any explicit
        feature shape (a reshape on the host; a column's own width, as
        the files have it, is the reducer's to pack), or a batch that
        does not divide this process's slice of the data axis (full
        batches must shard). Columns are ordered features-then-label —
        the exact slot order of the packed block and of the on-device
        unpack."""
        from ray_shuffling_data_loader_tpu.shuffle import (
            device_direct_enabled,
        )

        if not device_direct_enabled():
            return None
        spec = self._spec
        if spec.label_shape is not None or any(
            s is not None for s in spec.feature_shapes
        ):
            return None
        for t in (*spec.feature_types, spec.label_type):
            if t is not None and np.dtype(t).itemsize != 4:
                return None
        if batch_size % self._local_batch_shards() != 0:
            return None
        return {"batch": int(batch_size), "columns": self._spec_columns()}

    def _spec_columns(self) -> List[str]:
        """The spec's columns in delivery order: features, then the label
        where there is one."""
        spec = self._spec
        label = [] if spec.label_column is None else [spec.label_column]
        return [*spec.feature_columns, *label]

    def _spec_types(self) -> List[Any]:
        spec = self._spec
        label = [] if spec.label_column is None else [spec.label_type]
        return [*spec.feature_types, *label]

    def _direct_ok(self, cb: ColumnBatch) -> bool:
        """Can this packed batch ship without any host conversion? The
        layout's PREFIX columns and their ACTUAL dtypes (stamped by the
        reducer; the reducer appends any extra dataset columns after the
        requested prefix) must match what the spec would have produced
        host-side — cached per distinct layout signature. A wide column's
        slab holds whole rows, so it cannot be cut along the batch axis
        where it lies: with more than one local shard such a batch takes
        the host path."""
        lay = cb.layout or {}
        sig = (
            tuple(lay.get("columns", ())),
            tuple(lay.get("dtypes", ())),
            tuple(lay.get("widths") or ()),
        )
        ok = self._direct_sig_cache.get(sig)
        if ok is None:
            want = self._spec_columns()
            n = len(want)
            names = list(sig[0])
            dtypes = [np.dtype(d) for d in sig[1]]
            ok = names[:n] == want and len(dtypes) == len(names)
            if ok and any(w != 1 for w in sig[2][:n]):
                ok = self._local_batch_shards() == 1
            if ok:
                for dt, want_t in zip(dtypes[:n], self._spec_types()):
                    target = np.dtype(
                        want_t if want_t is not None
                        else _default_device_dtype(dt)
                    )
                    if dt != target or dt.itemsize != 4:
                        ok = False
                        break
            self._direct_sig_cache[sig] = ok
        return ok

    def _stage_direct(self, cb: ColumnBatch, prof):
        """Zero-host-copy staging: one async ``device_put`` of the
        batch's contiguous ``[n_spec_slots, batch]`` int32 prefix block
        straight off the store's mmapped segment (the reducer packed the
        requested columns first; extra dataset columns sit after the
        prefix and never ship), then the existing jitted on-device
        unpack (row slices + bitcasts). The H2D DMA sources the mmapped
        pages directly — no rebatch, no host pack, no intermediate
        buffer."""
        lay = cb.layout
        n = len(self._spec_columns())
        widths = tuple(packed_widths(lay)[:n])
        mat = cb.packed[: sum(widths)]  # contiguous prefix view
        sharding = NamedSharding(self.mesh, P(None, self.batch_axis))
        with prof.phase("device_put", nbytes=mat.nbytes):
            if jax.process_count() > 1:
                packed_dev = jax.make_array_from_process_local_data(
                    sharding, mat
                )
            else:
                packed_dev = jax.device_put(mat, sharding)
        with prof.phase("sync"):
            nf = len(self._spec.feature_columns)
            dtypes = tuple(
                str(np.dtype(d)) for d in lay["dtypes"][:n]
            )
            unpack = self._get_unpack(
                tuple(lay["columns"][:nf]), dtypes[:nf],
                dtypes[nf] if n > nf else None, widths,
            )
            features, label_arr = unpack(packed_dev)
        return features, label_arr, mat.nbytes, packed_dev

    # -- spec application ---------------------------------------------------

    def _device_view(self, column: np.ndarray, dtype, shape) -> np.ndarray:
        from ray_shuffling_data_loader_tpu import native

        target = dtype or _default_device_dtype(column.dtype)
        arr = native.narrow(np.asarray(column), np.dtype(target))
        if shape is not None:
            arr = arr.reshape((-1, *shape))
        return arr

    def _stage(self, cb: ColumnBatch):
        """Convert one host batch and dispatch its async H2D transfer.

        Fast path: when every column is a flat 4-byte-wide vector (the
        DLRM norm after int64→int32 narrowing), the whole batch is packed
        into ONE contiguous ``[n_cols, batch]`` int32 buffer and staged
        with a single ``device_put``, then unpacked on-device by one
        jitted computation: each put costs a fixed host↔device round-trip,
        so one large put beats one per column. Heterogeneous
        shapes/dtypes are staged per column. A put or an unpack that the
        backend refuses raises: no path here degrades to another.

        Returns ``((features, label), bytes put, clocks at the put's
        dispatch, what the put returned)``; the last three are what the
        transfer watcher times.
        """
        prof = _phases.stage_profiler("staging")
        # Device-direct fast path: the batch arrived as a packed block
        # already in staging layout — ship it without touching a byte on
        # the host.
        direct = cb.packed is not None and self._direct_ok(cb)
        if direct:
            put_at = self._put_clocks()
            features, label_arr, nbytes, put = self._stage_direct(cb, prof)
            self.stats.bytes_staged_direct += nbytes
            self.stats.batches_staged_direct += 1
        else:
            features, label_arr, nbytes, put, put_at = self._stage_host(
                cb, prof
            )
            self.stats.bytes_staged += nbytes
        self.stats.batches_staged += 1
        if self._h2d_bytes is not None:
            self._h2d_bytes.inc(nbytes)
            self._h2d_batches.inc()
            self._h2d_dispatch_s.observe(time.perf_counter() - put_at[1])
            if direct:
                _metrics.safe_inc("h2d.direct_bytes", float(nbytes))
                _metrics.safe_inc("h2d.direct_batches")
        return (features, label_arr), nbytes, put_at, put

    def _put_clocks(self) -> Optional[Tuple[float, float]]:
        """``(wall, perf_counter)`` at a put's dispatch, read only where
        something times the put: the metrics half's dispatch histogram (a
        ``perf_counter`` delta) or the transfer watcher (a wall-clock
        span)."""
        if self._h2d_bytes is None and not telemetry.active():
            return None
        return time.time(), time.perf_counter()

    def _stage_host(self, cb: ColumnBatch, prof):
        """The host-copied staging of one columnar batch: narrow every
        column to its device dtype, then one packed put or one per
        column."""
        spec = self._spec
        host: Dict[str, np.ndarray] = {}
        packable = True
        with prof.phase("pack") as ph:
            for col, dtype, shape in zip(
                spec.feature_columns, spec.feature_types,
                spec.feature_shapes,
            ):
                arr = self._device_view(cb[col], dtype, shape)
                host[col] = arr
                packable = (
                    packable and arr.ndim == 1 and arr.dtype.itemsize == 4
                )
            label = (
                None if spec.label_column is None
                else self._device_view(
                    cb[spec.label_column], spec.label_type, spec.label_shape
                )
            )
            every = [*host.values(), *([] if label is None else [label])]
            ph.add_bytes(sum(a.nbytes for a in every))
        packable = (
            packable
            and (label is None or (label.ndim == 1 and label.dtype.itemsize == 4))
            and len({a.shape[0] for a in every}) == 1
            # A ragged final partial can't take the row-sharded packed
            # layout; the per-column path replicates it (see _put).
            and self._rows_shardable(every[0].shape[0])
        )

        put_at = self._put_clocks()
        if packable:
            features, label_arr, nbytes, put = self._stage_packed(
                host, label, prof
            )
        else:
            # True final partial (fewer host rows than the configured
            # batch): the only case _put may legally replicate.
            partial = cb.num_rows < self._ds.batch_size
            features = {}
            nbytes = 0
            with prof.phase("device_put"):
                for col, arr in host.items():
                    features[col] = self._put(arr, partial=partial)
                    nbytes += arr.nbytes
                label_arr = None
                if label is not None:
                    label_arr = self._put(label, partial=partial)
                    nbytes += label.nbytes
            put = (features, label_arr)
        return features, label_arr, nbytes, put, put_at

    def _stage_packed(
        self, host: Dict[str, np.ndarray], label: Optional[np.ndarray],
        prof=None,
    ):
        """One transfer for the whole batch: bit-pack all 4-byte columns
        as int32 rows of a ``[n_cols+1, batch]`` buffer (float rows are
        bitcast back on device; no last row where there is no label).

        Multi-controller pods pack their LOCAL shard and assemble the
        global buffer with one ``make_array_from_process_local_data``
        call per batch per process — the same single-transfer economics
        as the single-chip path (a pod previously paid ``n_cols+1``
        per-column assemblies per batch per host)."""
        if prof is None:
            prof = _phases.stage_profiler("staging")
        names = tuple(host)
        rows = [host[name] for name in names]
        if label is not None:
            rows.append(label)
        with prof.phase("pack") as ph:
            packed = np.empty((len(rows), rows[0].shape[0]), np.int32)
            for i, row in enumerate(rows):
                packed[i] = row.view(np.int32)
            ph.add_bytes(packed.nbytes)
        sharding = NamedSharding(self.mesh, P(None, self.batch_axis))
        with prof.phase("device_put", nbytes=packed.nbytes):
            if jax.process_count() > 1:
                packed_dev = jax.make_array_from_process_local_data(
                    sharding, packed
                )
            else:
                packed_dev = jax.device_put(packed, sharding)
        with prof.phase("sync"):
            unpack = self._get_unpack(
                names,
                tuple(str(host[n].dtype) for n in names),
                None if label is None else str(label.dtype),
            )
            features, label_arr = unpack(packed_dev)
        return features, label_arr, packed.nbytes, packed_dev

    def _get_unpack(self, names, dtypes, label_dtype, widths=None):
        """Jitted on-device unpack for the packed layout: row slices +
        bitcasts, executed as ONE device computation (a single dispatch
        round-trip, vs one per column). ``label_dtype`` None: no label
        row, the label comes back None. ``widths`` (None: one slot a
        column) names the slots each column takes, the label's last; a
        wide column's slots read back as ``[batch, width]``.

        The computation is device-local by construction — each device
        already holds its batch shard of every packed row, so unpacking
        never moves data between shards. On multi-controller pods it is
        expressed through ``shard_map`` with pinned specs, which
        GUARANTEES no collective can be inserted: ranks may dispatch it
        at independent staging rates without cross-host rendezvous."""
        # One slot a column is one program, however it was said: the
        # direct and the host-packed path of a scalar stream share it (a
        # second would compile the first time a batch straddles reducers).
        widths = tuple(widths or ())
        if all(w == 1 for w in widths):
            widths = None
        key = (names, dtypes, label_dtype, widths)
        fn = self._unpack_cache.get(key)
        if fn is None:
            columns = len(names) + (label_dtype is not None)
            widths = widths or (1,) * columns
            slots = packed_slots({"columns": range(columns), "widths": widths})

            def row_of(packed, i, dt):
                at, w = slots[i]
                row = (
                    packed[at] if w == 1
                    else packed[at : at + w].reshape(packed.shape[1], w)
                )
                if dt != "int32":
                    row = jax.lax.bitcast_convert_type(row, jnp.dtype(dt))
                return row

            def unpack(packed):
                feats = {
                    name: row_of(packed, i, dt)
                    for i, (name, dt) in enumerate(zip(names, dtypes))
                }
                if label_dtype is None:
                    return feats, None
                return feats, row_of(packed, len(names), label_dtype)

            def rows(i):
                return P(self.batch_axis, *([None] * (widths[i] != 1)))

            feat_specs = {name: rows(i) for i, name in enumerate(names)}
            label_spec = None if label_dtype is None else rows(len(names))
            if jax.process_count() > 1:
                fn = jax.jit(
                    jax.shard_map(
                        unpack,
                        mesh=self.mesh,
                        in_specs=(P(None, self.batch_axis),),
                        out_specs=(feat_specs, label_spec),
                        check_vma=False,
                    )
                )
            else:
                placed = lambda spec: NamedSharding(self.mesh, spec)  # noqa: E731
                fn = jax.jit(
                    unpack,
                    out_shardings=(
                        {n: placed(s) for n, s in feat_specs.items()},
                        None if label_spec is None else placed(label_spec),
                    ),
                )
            self._unpack_cache[key] = fn
        return fn

    def _local_batch_shards(self) -> int:
        """This process's shard count along the batch axis.

        Derived from the mesh's LOCAL devices, not ``global_axis //
        process_count``: on a mesh whose data axis does not span every
        process (e.g. batch axis 4 on a 2-process×8-device pod with the
        other axis crossing hosts), the division heuristic diverges from
        what ``make_array_from_process_local_data`` actually requires."""
        if jax.process_count() == 1:
            return self.mesh.shape.get(self.batch_axis, 1)
        try:
            return max(1, self.mesh.local_mesh.shape.get(self.batch_axis, 1))
        except ValueError:
            # Local devices don't form a contiguous submesh; fall back to
            # the even-split heuristic (exact for all standard pod meshes).
            shards = self.mesh.shape.get(self.batch_axis, 1)
            return max(1, shards // jax.process_count())

    def _rows_shardable(self, local_rows: int) -> bool:
        """Can a batch with this many PROCESS-LOCAL rows take the
        row-sharded layout? Single-process: rows must divide the batch
        axis. Pods: this process's rows land on its own slice of the
        batch axis (``make_array_from_process_local_data``), so the
        constraint is against the LOCAL device count."""
        return local_rows % self._local_batch_shards() == 0

    def _put(self, arr: np.ndarray, partial: bool = False):
        if not self._rows_shardable(arr.shape[0]):
            local = self._local_batch_shards()
            if not partial:
                # A FULL batch that doesn't divide the axis is a
                # misconfiguration — silently replicating every batch
                # would erase data parallelism for the whole run; fail
                # with the remedy instead (the pre-fix device_put error
                # said "not evenly divisible" with no guidance).
                raise ValueError(
                    f"batch rows ({arr.shape[0]}) do not divide the "
                    f"{local}-way local '{self.batch_axis}' slice; pick a "
                    "batch_size divisible by the data-axis device count"
                )
            # A drop_last=False FINAL partial that doesn't divide the
            # data axis: device_put/make_array require exact
            # divisibility. Single-process delivers it REPLICATED (every
            # device holds the whole ragged tail — ragged finals
            # recompile the step anyway, and exactly-once outranks
            # sharding one small batch). Pods can't (each process holds
            # only its local rows; replication would need a gather the
            # loader must not insert) — fail with the remedy.
            if jax.process_count() > 1:
                raise ValueError(
                    f"final partial batch of {arr.shape[0]} rows does not "
                    f"divide the {local}-way local '{self.batch_axis}' "
                    "slice on a multi-controller pod; use drop_last=True "
                    "(the default) or a batch_size/dataset combination "
                    "with no partial tail"
                )
            return jax.device_put(
                arr, NamedSharding(self.mesh, P(*([None] * arr.ndim)))
            )
        sharding = NamedSharding(
            self.mesh, P(self.batch_axis, *([None] * (arr.ndim - 1)))
        )
        if jax.process_count() > 1:
            # Multi-host: this process stages its local shard; the global
            # array spans the pod (SURVEY §7 M3).
            return jax.make_array_from_process_local_data(sharding, arr)
        return jax.device_put(arr, sharding)

    # -- iteration ----------------------------------------------------------

    def set_epoch(self, epoch: int, skip_batches: int = 0) -> None:
        """``skip_batches`` resumes mid-epoch (see
        :meth:`~.dataset.ShufflingDataset.set_epoch`); skipped batches are
        suppressed before staging, so no HBM transfer is paid for them."""
        self._ds.set_epoch(epoch, skip_batches=skip_batches)

    @property
    def batch_size(self) -> int:
        return self._ds.batch_size

    def __iter__(self):
        """Yield device batches through the prefetch ring.

        The stager thread converts + dispatches transfers ``prefetch_depth``
        batches ahead; the bounded queue is the ring's backpressure. Stall
        accounting: time this (consumer) side blocks on the ring.
        """
        ring: "queue.Queue" = queue.Queue(maxsize=self._prefetch_depth)
        SENTINEL = object()
        cancel = threading.Event()
        error: List[BaseException] = []
        epoch_start = time.perf_counter()
        epoch = self._ds._epoch  # pinned before iteration starts
        # The epoch boundary is where the process's tracing flag follows
        # a profiler session; every other site only reads it.
        watcher = _TransferWatcher() if telemetry.refresh_active() else None
        if _metrics.enabled():
            # Resolve the stall counters up front so the stall-by-cause
            # series exists in every snapshot, zeros included — a run with
            # no stalls should report 0.0, not a missing key.
            _metrics.registry.counter("stall_seconds", cause="upstream")
            _metrics.registry.counter("stall_seconds", cause="staging")

        # Stall attribution: the stager publishes which pipeline phase it
        # is in; a consumer stall is charged to the phase observed when
        # its wait BEGINS (sampling at wait end would race the stager
        # flipping back to "upstream" right after the put that ended the
        # wait). "upstream" = blocked on the host dataset (epoch window /
        # shuffle), "staging" = convert+H2D in progress. A plain
        # attribute is enough — one writer, one reader, advisory metric.
        phase = ["upstream"]

        # Audit: staged-side digests — the rows the device path actually
        # staged after rebatching, recorded PER BATCH so every record
        # lands before the dataset's final acks can let the driver
        # reconcile. Reconcile compares staged vs delivered only when the
        # counts match (drop_last legitimately trims the tail).
        audit_on = _audit.enabled()
        staged_rows = 0

        def stage_all():
            nonlocal staged_rows
            for cb in self._ds:
                if cancel.is_set():
                    # Early consumer exit (break mid-epoch): keep
                    # draining the underlying dataset WITHOUT staging so
                    # its task_done acks still flow and the epoch window
                    # can advance; stage nothing more to HBM.
                    continue
                if audit_on:
                    _audit.record_staged(
                        epoch, self._ds._rank, cb, staged_rows
                    )
                    staged_rows += cb.num_rows
                phase[0] = "staging"
                batch = self.stats.batches_staged
                with telemetry.trace_span(
                    "stage:h2d",
                    cat="staging",
                    epoch=epoch,
                    batch=batch,
                    rows=cb.num_rows,
                ):
                    item, nbytes, put_at, put = self._stage(cb)
                if watcher is not None and put_at is not None:
                    watcher.watch(epoch, batch, nbytes, put_at[0], put, item)
                del put
                # ``stage:ring-put``: the stager blocked on the full ring,
                # i.e. ahead of the consumer — the loader's slack.
                with telemetry.trace_span(
                    "stage:ring-put", cat="staging", epoch=epoch, batch=batch
                ):
                    while not cancel.is_set():
                        try:
                            ring.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                phase[0] = "upstream"

        def stager():
            try:
                # ``stage:epoch``: the life of this thread, the parent of
                # everything it does (``queue:get``, ``stage:h2d``,
                # ``stage:ring-put``).
                with telemetry.trace_span(
                    "stage:epoch", cat="staging", epoch=epoch,
                    rank=self._ds._rank,
                ):
                    stage_all()
            except BaseException as exc:  # surfaced on the consumer side
                error.append(exc)
            finally:
                # Place the sentinel without ever displacing a real batch:
                # block politely while the consumer drains; evict only when
                # the consumer has cancelled (its drain may already be done).
                while True:
                    try:
                        ring.put(SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        if cancel.is_set():
                            try:
                                ring.get_nowait()
                            except queue.Empty:
                                pass

        thread = threading.Thread(target=stager, name="hbm-stager", daemon=True)
        thread.start()
        try:
            first = True
            while True:
                # Sample the stager's phase when the wait STARTS: that is
                # the phase that caused an empty ring. Sampling after
                # ring.get() returns would race the stager flipping back
                # to "upstream" right after the put that ended the wait.
                phase_at_wait = phase[0]
                t0 = time.perf_counter()
                item = ring.get()
                waited = time.perf_counter() - t0
                if first:
                    self.stats.first_batch_s = time.perf_counter() - epoch_start
                    first = False
                elif waited > 0.0005:
                    self.stats.stall_s += waited
                    self.stats.stalls += 1
                    if phase_at_wait == "staging":
                        self.stats.stall_staging_s += waited
                    else:
                        self.stats.stall_upstream_s += waited
                    # Same increment, telemetry vocabulary: a span on the
                    # consumer thread's timeline plus the stall-by-cause
                    # counter (both no-op when their half is disabled).
                    telemetry.record_span(
                        "stall",
                        time.time() - waited,
                        waited,
                        cat="staging",
                        epoch=epoch,
                        cause=phase_at_wait,
                    )
                    if _metrics.enabled():
                        _metrics.registry.counter(
                            "stall_seconds", cause=phase_at_wait
                        ).inc(waited)
                if item is SENTINEL:
                    break
                yield item
        finally:
            # Runs on normal completion AND on GeneratorExit (consumer broke
            # out mid-epoch): unblock and retire the stager so the epoch's
            # acks complete and the next epoch can start.
            cancel.set()
            while True:
                try:
                    if ring.get_nowait() is SENTINEL:
                        break
                except queue.Empty:
                    if not thread.is_alive():
                        break
                    time.sleep(0.01)
            thread.join()
            if watcher is not None:
                watcher.close()
            if error:
                raise error[0]
