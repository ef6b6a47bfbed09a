"""Framework-agnostic shuffling dataset API.

Parity with the reference ``ShufflingDataset`` (``dataset.py:15-188``):
rank 0 creates the named batch queue and kicks off the multi-epoch shuffle;
every rank iterates exact-``batch_size`` batches re-cut from streamed
reducer outputs with a carry-over buffer, and acks consumption back to the
queue to drive the epoch-window backpressure.

Differences from the reference (TPU-first, not a port):

* Batches are :class:`~.runtime.ColumnBatch` (named contiguous numpy
  columns, zero-copy views over shared memory) instead of pandas
  DataFrames — the layout the JAX/HBM staging path consumes directly.
  Use ``batch.to_pandas()`` where a DataFrame is wanted.
* The shuffle driver runs on a daemon thread in the rank-0 process,
  submitting stage tasks to the runtime's worker pool (the reference runs it
  as a detached Ray task, ``dataset.py:68-74``).
* Reducer-output segments are freed as soon as they have been sliced into
  training batches; on Linux the pages live until the last view drops.
"""

from __future__ import annotations

import os
import threading
from typing import Iterator, List, Optional

from ray_shuffling_data_loader_tpu import runtime, telemetry
from ray_shuffling_data_loader_tpu.batch_queue import (
    BatchQueue,
    DEFAULT_QUEUE_NAME,
)
from ray_shuffling_data_loader_tpu.runtime import ColumnBatch, ObjectRef
from ray_shuffling_data_loader_tpu.runtime.store import (
    device_batch_rows,
    is_device_batch,
    iter_packed_batches,
    logical_columns,
)
from ray_shuffling_data_loader_tpu.shuffle import BatchConsumer, shuffle
# Gated planes (ISSUE 14 gate-integrity): lazy proxies, resolved on
# first attribute access — importing the dataset layer must not execute
# a telemetry-plane module body.
from ray_shuffling_data_loader_tpu._lazy import lazy_module

_audit = lazy_module("ray_shuffling_data_loader_tpu.telemetry.audit")
_phases = lazy_module("ray_shuffling_data_loader_tpu.telemetry.phases")

# Default reducer share of cluster cores (reference ``dataset.py:12``).
REDUCER_CLUSTER_CORE_SHARE = 0.6


def default_num_reducers(num_trainers: int) -> int:
    return max(
        1,
        int(num_trainers * (os.cpu_count() or 1) * REDUCER_CLUSTER_CORE_SHARE),
    )


class _ShuffleResult:
    """Holds the background shuffle driver's outcome (the analog of the
    detached-task ref the reference ``ray.get``s at ``dataset.py:186-188``)."""

    def __init__(self):
        self.duration: Optional[float] = None
        self.error: Optional[BaseException] = None
        self.thread: Optional[threading.Thread] = None

    def join(self):
        self.thread.join()
        if self.error is not None:
            raise self.error


class CarryRebatcher:
    """The exact-``batch_size`` re-batching algebra, isolated.

    Reducer outputs arrive in arbitrary sizes; training wants exact
    batches with a carry buffer spanning output boundaries (reference
    ``dataset.py:118-182``, minus its dropped-tail bug at ``:160-168``).
    Kept free of queue/store machinery so the hypothesis property suite
    (``tests/test_rebatch_property.py``) drives the PRODUCTION algebra
    with in-memory outputs — the iterator below feeds it the real
    stream. ``skip_batches`` counts suppressed batches in yield order
    (the final partial counts as one batch).
    """

    def __init__(self, batch_size: int, skip_batches: int = 0):
        self.batch_size = batch_size
        self.to_skip = skip_batches
        self.buf: Optional[ColumnBatch] = None

    def feed(self, cb: ColumnBatch) -> Iterator[ColumnBatch]:
        """Yield every full batch completed by this reducer output."""
        batch_size = self.batch_size
        offset = batch_size - (self.buf.num_rows if self.buf else 0)
        # Top up the carry buffer with a front slice.
        self.buf = ColumnBatch.concat([self.buf, cb.slice(0, offset)])
        if self.buf.num_rows == batch_size:
            if self.to_skip > 0:
                self.to_skip -= 1
            else:
                yield self.buf
            self.buf = None
        # Whole batches straight from this output, then the short tail
        # into the carry buffer.
        start = min(offset, cb.num_rows)
        num_full = (cb.num_rows - start) // batch_size
        num_skipped = min(self.to_skip, num_full)
        self.to_skip -= num_skipped
        for i in range(num_skipped, num_full):
            lo = start + i * batch_size
            yield cb.slice(lo, lo + batch_size)
        tail = start + num_full * batch_size
        if tail < cb.num_rows:
            self.buf = cb.slice(tail, cb.num_rows)

    def finish(self, drop_last: bool) -> Optional[ColumnBatch]:
        """The final partial batch, unless dropped/skipped/empty."""
        buf, self.buf = self.buf, None
        if buf is not None and buf.num_rows > 0 and not drop_last:
            if self.to_skip > 0:
                self.to_skip -= 1
                return None
            return buf
        return None


class ShufflingDataset:
    """A shuffling dataset that yields batches upon iteration.

    Constructing this on rank 0 kicks off shuffling for up to
    ``max_concurrent_epochs`` epochs. Constructor signature matches the
    reference (``dataset.py:37-48``) plus a deterministic ``seed``.

    Args:
        filenames: Paths to input Parquet files.
        num_epochs: Number of training epochs.
        num_trainers: Number of trainer workers.
        batch_size: Rows per yielded batch.
        rank: This trainer's rank.
        drop_last: Drop the final incomplete batch. Default False.
        num_reducers: Shuffler reducer count. Default
            ``num_trainers × cores × 0.6`` (reference ``dataset.py:46-48``).
        max_concurrent_epochs: Epoch pipelining window. Default 2.
        seed: Root seed for the per-epoch shuffle permutations.
        queue_name: Name of the shared batch-queue endpoint.
        start_epoch: First epoch to shuffle/consume (checkpoint resume;
            epoch indices stay absolute so permutations match the
            original run).
    """

    def __init__(
        self,
        filenames: List[str],
        num_epochs: int,
        num_trainers: int,
        batch_size: int,
        rank: int,
        drop_last: bool = False,
        num_reducers: Optional[int] = None,
        max_concurrent_epochs: int = 2,
        seed: int = 0,
        queue_name: str = DEFAULT_QUEUE_NAME,
        start_epoch: int = 0,
        narrow_to_32: bool = False,
        cache_decoded: Optional[bool] = None,
        stats_collector=None,
        device_layout: Optional[dict] = None,
    ):
        """``narrow_to_32``: cast 64-bit columns to 32-bit at Parquet
        decode time, inside the map tasks. Every downstream pass
        (partition scatter, concat+permute, shared-memory residency,
        cross-host fetch) then moves half the bytes. Only safe when
        values fit (int32 ids / float32 labels) — the device path
        (:class:`~.jax_dataset.JaxShufflingDataset`) turns it on because
        it narrows to 32-bit at staging anyway.

        ``device_layout``: device-direct delivery (ROADMAP 3) — the
        staging consumer's ``{"batch": B, "columns": [...]}`` layout.
        Reducers then emit batch-aligned packed segments; this iterator
        yields each packed batch as zero-copy logical column views (with
        ``.packed`` exposing the raw ``[n_cols, B]`` staging block) and
        routes only the boundary remainders through the carry rebatcher.
        The yielded row stream is bit-identical to the layout-off path."""
        runtime.ensure_initialized()
        if num_reducers is None:
            num_reducers = default_num_reducers(num_trainers)
        self._batch_size = batch_size

        # Service plane (ISSUE 15): capture the caller's ambient job so
        # the shuffle-driver THREAD below runs inside it (threadlocals
        # do not cross threads) — the queue name created here and the
        # driver's job-scoped resources must agree. NO auto-registration
        # here: trainer ranks in other threads/processes could never
        # learn an implicit job's id and would connect to an unscoped
        # name the producer never spawned — job-scoped queues require
        # the caller's job_context (or RSDL_JOB_ID), docs/service.md
        # "Boundary". Env-guarded before the import: service off means
        # no plane load, no behavior change.
        service_job = None
        if os.environ.get("RSDL_SERVICE"):
            try:
                from ray_shuffling_data_loader_tpu.runtime import service

                if service.enabled():
                    service_job = service.current_job()
            except Exception:
                service_job = None

        if rank == 0:
            # Master: create the queue, then kick off the shuffle driver.
            self._batch_queue = BatchQueue(
                num_epochs,
                num_trainers,
                max_concurrent_epochs,
                name=queue_name,
                connect=False,
            )
            self._consumer = BatchConsumerQueue(self._batch_queue)
            self._batch_queue.ready()
            self._shuffle_result = _ShuffleResult()

            def _drive(result=self._shuffle_result):
                try:
                    if service_job is not None:
                        from ray_shuffling_data_loader_tpu.runtime import (
                            service,
                        )

                        service.set_current_job(service_job)
                    result.duration = shuffle(
                        filenames,
                        self._consumer,
                        num_epochs,
                        num_reducers,
                        num_trainers,
                        seed=seed,
                        start_epoch=start_epoch,
                        narrow_to_32=narrow_to_32,
                        cache_decoded=cache_decoded,
                        stats_collector=stats_collector,
                        device_layout=device_layout,
                    )
                except BaseException as exc:  # surfaced at iterator end
                    result.error = exc

            self._shuffle_result.thread = threading.Thread(
                target=_drive, name="shuffle-driver", daemon=True
            )
            self._shuffle_result.thread.start()
        else:
            # Worker: connect to the named queue with retry.
            self._batch_queue = BatchQueue(
                num_epochs,
                num_trainers,
                max_concurrent_epochs,
                name=queue_name,
                connect=True,
            )
            self._shuffle_result = None

        self._num_epochs = num_epochs
        self._num_trainers = num_trainers
        self._rank = rank
        self._epoch: Optional[int] = None
        self._last_epoch: Optional[int] = None
        self._drop_last = drop_last
        self._skip_batches = 0

    @property
    def batch_size(self) -> int:
        return self._batch_size

    def set_epoch(self, epoch: int, skip_batches: int = 0) -> None:
        """Must be called before each epoch's iteration (reference
        ``dataset.py:96-106``).

        ``skip_batches`` resumes mid-epoch after a preemption: the shuffle
        is deterministic per ``(seed, epoch)`` (``shuffle.py:87-95``), so
        regenerating the epoch and suppressing the first ``skip_batches``
        yields exactly the stream an uninterrupted run would have produced
        from that point (the reference has no resume at all, SURVEY §5).
        Skipped batches still flow through the carry-buffer bookkeeping and
        ``task_done`` acks — only the yields are suppressed.
        """
        self._epoch = epoch
        self._skip_batches = skip_batches

    def __iter__(self) -> Iterator[ColumnBatch]:
        if self._epoch is None or self._epoch == self._last_epoch:
            raise ValueError(
                "You must set the epoch on this dataset via set_epoch() at "
                "the beginning of each epoch, before iterating over this "
                "dataset."
            )
        store = runtime.get_context().store
        rebatch = CarryRebatcher(self._batch_size, self._skip_batches)
        # Staging sub-phase attribution (ISSUE 8 satellite): the carry
        # re-cut used to hide inside the monolithic "staging" stall; its
        # host-copy cost is now its own series. The profiler is the
        # shared no-op when telemetry is off.
        prof = _phases.stage_profiler(
            "staging", epoch=self._epoch, rank=self._rank
        )

        def _recut(cb):
            """Drive ``rebatch.feed`` so only the rebatcher's own slicing
            work is timed — the consumer runs between ``next()`` calls,
            outside the phase."""
            feed = rebatch.feed(cb)
            while True:
                with prof.phase("rebatch"):
                    try:
                        out = next(feed)
                    except StopIteration:
                        return
                yield out

        is_done = False
        consumed_rows = 0  # audit: this rank's consumed-stream offset
        while not is_done:
            # ``queue:get``: this trainer waiting on the queue actor — the
            # shuffle's slack when short, the shuffle binding when long.
            with telemetry.trace_span(
                "queue:get", cat="queue", epoch=self._epoch, rank=self._rank
            ) as span:
                pending = self._batch_queue.get_batch(self._rank, self._epoch)
                span.set(refs=len(pending))
            if pending and pending[-1] is None:
                # Trailing producer-done sentinel; drain the rest first.
                is_done = True
                pending.pop()
            num_outstanding = len(pending)
            # Pull every foreign ref's bytes over DCN in parallel while the
            # first is being consumed (the ``ray.wait(fetch_local=True)``
            # analog, reference ``dataset.py:132-137``); local refs no-op.
            store.prefetch(pending)

            for ref in pending:
                cb = store.get_columns(ref)
                # Segment pages outlive the unlink until views drop.
                store.free(ref)
                if _audit.enabled():
                    # Consumed-side digest BEFORE rebatching: what this
                    # rank actually read back through queue + store. A
                    # row lost (or duplicated) anywhere between the
                    # delivery thread and here breaks delivered==consumed
                    # at reconcile.
                    _audit.record_consume(
                        self._epoch, self._rank, logical_columns(cb),
                        consumed_rows,
                    )
                    consumed_rows += (
                        device_batch_rows(cb)
                        if is_device_batch(cb)
                        else cb.num_rows
                    )
                if (
                    is_device_batch(cb)
                    and cb.layout.get("batch") == self._batch_size
                    and rebatch.buf is None
                ):
                    # Device-direct body: batches already cut at this
                    # rank stream's grid (the producer proved alignment
                    # by construction — the carry is empty exactly when
                    # a body arrives). Yield zero-copy per-batch views;
                    # the carry rebatcher never touches these bytes.
                    for pb in iter_packed_batches(cb):
                        if rebatch.to_skip > 0:
                            rebatch.to_skip -= 1
                            continue
                        yield pb
                elif is_device_batch(cb):
                    # Alignment broken (e.g. an injected delivery fault
                    # upstream shifted the stream): correctness first —
                    # re-cut the logical batches through the carry
                    # buffer like any columnar output.
                    for pb in iter_packed_batches(cb):
                        yield from _recut(pb)
                else:
                    yield from _recut(cb)
                del cb

            if num_outstanding > 0:
                self._batch_queue.task_done(
                    self._rank, self._epoch, num_outstanding
                )

        final = rebatch.finish(self._drop_last)
        if final is not None:
            yield final
        # Ack the producer-done sentinel itself (reference dataset.py:184).
        self._batch_queue.task_done(self._rank, self._epoch, 1)
        self._last_epoch = self._epoch
        if (
            self._epoch == self._num_epochs - 1
            and self._shuffle_result is not None
        ):
            self._shuffle_result.join()


class BatchConsumerQueue(BatchConsumer):
    """Adapts the shuffle engine's consumer interface onto a BatchQueue
    (reference ``dataset.py:191-205``)."""

    def __init__(self, batch_queue: BatchQueue):
        self._batch_queue = batch_queue

    def consume(
        self,
        rank: int,
        epoch: int,
        batches: List[ObjectRef],
        seq: Optional[int] = None,
    ):
        accepted = self._batch_queue.put_batch(
            rank, epoch, batches, seq=seq
        )
        if accepted is False:
            # Idempotency drop (a resumed driver re-published a reducer
            # the surviving queue actor already delivered): nothing will
            # ever consume these refs, so free them here — or the
            # re-executed reducer's segments pin shm for the whole run.
            store = runtime.get_context().store
            for ref in batches:
                try:
                    store.free(ref)
                except Exception:
                    pass

    def producer_done(self, rank: int, epoch: int):
        self._batch_queue.producer_done(rank, epoch)

    def restore_delivery_cursors(self, cursors) -> None:
        # Journal resume (runtime/journal.py): seed the queue actor's
        # idempotency cursors from the journaled delivery state.
        self._batch_queue.restore_delivery_cursors(cursors)

    def wait_until_ready(self, epoch: int):
        self._batch_queue.new_epoch(epoch)

    def wait_until_all_epochs_done(self):
        self._batch_queue.wait_until_all_epochs_done()


if __name__ == "__main__":
    # Smoke run (reference dataset.py:208-252 runs the same shape in CI):
    # generate a small dataset, iterate every epoch, assert exactly-once.
    import numpy as np

    from ray_shuffling_data_loader_tpu.data_generation import generate_data

    num_rows, num_files, num_epochs, batch_size = 10**5, 10, 4, 20_000
    runtime.init()
    filenames, _ = generate_data(
        num_rows, num_files, 2, 0.0, "smoke_data"
    )
    ds = ShufflingDataset(
        filenames,
        num_epochs=num_epochs,
        num_trainers=1,
        batch_size=batch_size,
        rank=0,
        num_reducers=8,
    )
    for epoch in range(num_epochs):
        ds.set_epoch(epoch)
        keys = [k for b in ds for k in b["key"].tolist()]
        assert sorted(keys) == list(range(num_rows)), len(keys)
        print(f"epoch {epoch}: {num_rows} rows exactly once")
    runtime.shutdown()
    print("smoke OK")
