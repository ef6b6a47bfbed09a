"""Shuffle benchmark harness.

Capability parity with the reference benchmark driver
(``benchmarks/benchmark.py:28-337``): generate (or reuse) a synthetic
Parquet dataset, run N trials of the multi-epoch shuffle against per-trainer
consumer actors, collect per-stage stats plus object-store utilization, and
dump trial/epoch/consumer-timeline CSVs.

TPU-native differences: consumers are runtime actor processes on this host's
worker substrate (the reference spreads Ray actors over a placement group,
``benchmarks/benchmark.py:125-147``), and store utilization comes from the
session's shared-memory store instead of the raylet gRPC probe.

Run:
    python benchmarks/benchmark.py --num-rows 1000000 --num-files 10 \
        --num-trainers 4 --num-reducers 8 --num-epochs 5 --num-trials 2

Scope: this harness measures the HOST shuffle engine (map/reduce +
actor consumers). The device-resident loader bypasses that engine
entirely; its end-to-end measurement is the benchmark cell
``resident-train`` (``chipbench/run.py``, ``PERF.md``).
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.runtime import ObjectRef
from ray_shuffling_data_loader_tpu.shuffle import BatchConsumer, shuffle
from ray_shuffling_data_loader_tpu.stats import (
    ObjectStoreStatsCollector,
    TrialStatsCollector,
    human_readable_big_num,
    process_stats,
)


class Consumer:
    """Per-trainer consumer actor: dereferences reducer outputs from the
    store, counts rows/bytes, frees segments (reference ``Consumer`` actor,
    ``benchmarks/benchmark.py:28-62``)."""

    def __init__(self, rank: int):
        self.rank = rank
        self.num_batches = 0
        self.num_rows = 0
        self.num_bytes = 0
        self.consume_times: List[float] = []
        self._epoch_starts: Dict[int, float] = {}

    def new_epoch(self, epoch: int) -> None:
        self._epoch_starts[epoch] = time.time()

    def consume(self, epoch: int, refs: List[ObjectRef]) -> int:
        ctx = runtime.ensure_initialized()
        rows = 0
        for ref in refs:
            cb = ctx.store.get_columns(ref)
            rows += cb.num_rows
            self.num_bytes += cb.nbytes
            del cb
            ctx.store.free(ref)
        self.num_batches += len(refs)
        self.num_rows += rows
        start = self._epoch_starts.get(epoch)
        if start is not None:
            self.consume_times.append(time.time() - start)
        return rows

    def producer_done(self, epoch: int) -> None:
        pass

    def get_stats(self) -> Dict:
        return {
            "rank": self.rank,
            "num_batches": self.num_batches,
            "num_rows": self.num_rows,
            "num_bytes": self.num_bytes,
            "consume_times": self.consume_times,
        }


class ActorBatchConsumer(BatchConsumer):
    """Driver-side adapter implementing the shuffle engine's consumer
    interface over per-rank consumer actors, with the epoch-window admission
    gate (reference ``BatchConsumer`` impl, ``benchmarks/benchmark.py:65-108``;
    window semantics per ``batch_queue.py:395-418``)."""

    def __init__(self, consumers, max_concurrent_epochs: int, num_trainers: int):
        self._consumers = consumers
        self._window = max_concurrent_epochs
        self._num_trainers = num_trainers
        self._cond = threading.Condition()
        self._in_flight: set = set()
        self._done_ranks = collections.defaultdict(set)

    def wait_until_ready(self, epoch: int) -> None:
        with self._cond:
            self._cond.wait_for(lambda: len(self._in_flight) < self._window)
            self._in_flight.add(epoch)
        for c in self._consumers:
            c.call_oneway("new_epoch", epoch)

    def consume(self, rank: int, epoch: int, batches: List[ObjectRef]) -> None:
        # Synchronous call: returning means the consumer has fully processed
        # (and freed) the batch, so window release implies consumption.
        self._consumers[rank].call("consume", epoch, batches)

    def producer_done(self, rank: int, epoch: int) -> None:
        self._consumers[rank].call_oneway("producer_done", epoch)
        with self._cond:
            self._done_ranks[epoch].add(rank)
            if len(self._done_ranks[epoch]) == self._num_trainers:
                self._in_flight.discard(epoch)
                self._cond.notify_all()

    def wait_until_all_epochs_done(self) -> None:
        with self._cond:
            self._cond.wait_for(lambda: not self._in_flight)


def run_trial(
    trial: int,
    filenames: List[str],
    args,
) -> "TrialStats":
    """One trial: fresh consumers + collector, timed shuffle, stats fetch
    (reference ``run_trials`` body, ``benchmarks/benchmark.py:111-184``)."""
    collector = None
    if not args.no_stats:
        collector = runtime.spawn_actor(
            TrialStatsCollector,
            args.num_epochs,
            len(filenames),
            args.num_reducers,
            args.num_rows,
            args.batch_size,
            args.num_trainers,
            trial,
            args.num_row_groups_per_file,
            args.max_concurrent_epochs,
            name=f"stats-trial-{trial}",
        )
        collector.wait_ready()
    # Cluster mode: spread consumers round-robin over the hosts — the
    # reference's SPREAD placement group for its Consumer actors
    # (``benchmarks/benchmark.py:125-130``). Single-host (empty list)
    # spawns locally as before; a host whose agent cannot import this
    # module (bare `runtime.cluster join` from another cwd) degrades to
    # a local spawn rather than sinking the trial.
    hosts = runtime.cluster_hosts()

    def _spawn_consumer(rank: int):
        name = f"consumer-{trial}-{rank}"
        target = hosts[rank % len(hosts)] if hosts else None
        try:
            return runtime.spawn_actor(
                Consumer, rank, name=name, host_id=target
            )
        except Exception:
            if target is None or target == hosts[0]:
                raise
            print(f"[bench] consumer {rank}: spawn on {target} failed; "
                  "falling back to a local spawn", flush=True)
            return runtime.spawn_actor(Consumer, rank, name=name)

    consumers = [_spawn_consumer(rank) for rank in range(args.num_trainers)]
    for c in consumers:
        c.wait_ready()
    batch_consumer = ActorBatchConsumer(
        consumers, args.max_concurrent_epochs, args.num_trainers
    )

    if collector is not None:
        with ObjectStoreStatsCollector(
            collector, sample_period_s=args.store_stats_sample_period
        ):
            duration = shuffle(
                filenames,
                batch_consumer,
                args.num_epochs,
                args.num_reducers,
                args.num_trainers,
                seed=args.seed + trial,
                stats_collector=collector,
                narrow_to_32=args.narrow_to_32,
                cache_decoded=args.cache_decoded,
            )
    else:
        duration = shuffle(
            filenames,
            batch_consumer,
            args.num_epochs,
            args.num_reducers,
            args.num_trainers,
            seed=args.seed + trial,
            narrow_to_32=args.narrow_to_32,
            cache_decoded=args.cache_decoded,
        )
    print(
        f"Trial {trial} done in {duration:.2f}s "
        f"({human_readable_big_num(args.num_rows * args.num_epochs / duration)}"
        f" rows/s)"
    )
    consumed_rows = sum(
        c.call("get_stats")["num_rows"] for c in consumers
    )
    expected = args.num_rows * args.num_epochs
    assert consumed_rows == expected, (consumed_rows, expected)

    stats = None
    if collector is not None:
        stats = collector.call("get_stats", 30)
        collector.terminate()
    for c in consumers:
        c.terminate()
    return stats if stats is not None else duration


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-rows", type=int, default=4 * 10 ** 6)
    p.add_argument("--num-files", type=int, default=100)
    p.add_argument("--num-row-groups-per-file", type=int, default=5)
    p.add_argument("--max-row-group-skew", type=float, default=0.0)
    p.add_argument("--num-reducers", type=int, default=5)
    p.add_argument("--num-trainers", type=int, default=5)
    p.add_argument("--num-epochs", type=int, default=10)
    p.add_argument("--num-trials", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=100)
    p.add_argument("--max-concurrent-epochs", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--data-dir", type=str, default="benchmark_data")
    p.add_argument("--stats-dir", type=str, default="benchmark_stats")
    p.add_argument(
        "--use-old-data",
        action="store_true",
        help="Reuse Parquet files already present in --data-dir.",
    )
    p.add_argument("--clear-old-data", action="store_true")
    p.add_argument("--no-stats", action="store_true")
    p.add_argument("--no-overwrite-stats", action="store_true")
    p.add_argument("--store-stats-sample-period", type=float, default=5.0)
    p.add_argument("--num-workers", type=int, default=None)
    p.add_argument(
        "--narrow-to-32",
        action="store_true",
        help="Cast 64-bit columns to 32-bit at decode (halves bytes "
        "through every shuffle pass; ids must fit int32).",
    )
    cache = p.add_mutually_exclusive_group()
    cache.add_argument(
        "--cache-decoded",
        dest="cache_decoded",
        action="store_true",
        default=None,
        help="Keep decoded columns in the store across epochs "
        "(default: auto by store budget).",
    )
    cache.add_argument(
        "--no-cache-decoded",
        dest="cache_decoded",
        action="store_false",
        help="Force per-epoch Parquet decode.",
    )
    p.add_argument(
        "--address",
        type=str,
        default=None,
        help="Join an existing runtime session instead of creating one.",
    )
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.use_old_data and args.clear_old_data:
        raise ValueError(
            "Only one of --use-old-data and --clear-old-data may be given."
        )
    runtime.init(address=args.address, num_workers=args.num_workers)

    if args.clear_old_data:
        print(f"Clearing old data from {args.data_dir}.")
        for f in glob.glob(os.path.join(args.data_dir, "*.parquet.snappy")):
            os.remove(f)

    if args.use_old_data:
        filenames = sorted(
            glob.glob(os.path.join(args.data_dir, "*.parquet.snappy"))
        )
        if not filenames:
            raise FileNotFoundError(
                f"--use-old-data given but no Parquet files in {args.data_dir}"
            )
        num_bytes = sum(os.path.getsize(f) for f in filenames)
        print(f"Reusing {len(filenames)} files ({num_bytes / 1e9:.2f} GB).")
    else:
        print(
            f"Generating {human_readable_big_num(args.num_rows)} rows over "
            f"{args.num_files} files."
        )
        t0 = time.time()
        filenames, num_bytes = generate_data(
            args.num_rows,
            args.num_files,
            args.num_row_groups_per_file,
            args.max_row_group_skew,
            args.data_dir,
            seed=args.seed,
        )
        print(
            f"Generated {num_bytes / 1e9:.2f} GB in {time.time() - t0:.1f}s."
        )

    print(
        f"Shuffling {human_readable_big_num(args.num_rows)} rows × "
        f"{args.num_epochs} epochs × {args.num_trials} trials: "
        f"{args.num_reducers} reducers → {args.num_trainers} trainers, "
        f"epoch window {args.max_concurrent_epochs}."
    )
    all_stats = []
    for trial in range(args.num_trials):
        all_stats.append(run_trial(trial, filenames, args))

    if not args.no_stats:
        summary = process_stats(
            all_stats,
            stats_dir=args.stats_dir,
            overwrite_stats=not args.no_overwrite_stats,
        )
        print(json.dumps(summary))
        print(f"Stats CSVs written to {args.stats_dir}/")
    else:
        # --no-stats: run_trial returned plain durations.
        print(
            f"Mean trial duration: {sum(all_stats) / len(all_stats):.2f}s"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
