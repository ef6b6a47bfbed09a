"""Stats subsystem tests: collector semantics, end-to-end collection through
the real shuffle path, CSV report generation, and helpers (the reference has
no stats tests at all — SURVEY.md §4 'lesson for the build')."""

import asyncio
import csv
import os
import time

import pytest

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.shuffle import shuffle
from ray_shuffling_data_loader_tpu.stats import (
    ObjectStoreStatsCollector,
    TrialStats,
    TrialStatsCollector,
    human_readable_big_num,
    human_readable_size,
    process_stats,
)


@pytest.fixture(scope="module")
def stats_dataset(local_runtime, tmp_path_factory):
    data_dir = tmp_path_factory.mktemp("stats-data")
    filenames, _ = generate_data(
        num_rows=1200,
        num_files=3,
        num_row_groups_per_file=1,
        max_row_group_skew=0.0,
        data_dir=str(data_dir),
    )
    return filenames


def test_collector_inprocess():
    c = TrialStatsCollector(
        num_epochs=1,
        num_maps_per_epoch=2,
        num_reduces_per_epoch=2,
        num_rows=100,
        batch_size=10,
        num_trainers=2,
    )
    c.epoch_start(0)
    c.epoch_throttle(0, 0.01)
    c.map_start(0)
    c.map_done(0, 0.5, 0.2)
    c.map_start(0)
    c.map_done(0, 0.7, 0.3)
    c.reduce_start(0)
    c.reduce_done(0, 0.4)
    c.reduce_start(0)
    c.reduce_done(0, 0.6)
    c.consume(rank=0, epoch=0, nbytes=1000)
    c.consume(rank=1, epoch=0, nbytes=2000)
    c.store_sample(3, 4096)
    c.trial_done(1.25)

    stats = asyncio.run(c.get_stats(timeout=1))
    assert stats.duration == 1.25
    assert stats.row_throughput == pytest.approx(100 / 1.25)
    assert stats.batch_throughput == pytest.approx(10 / 1.25)
    assert stats.per_trainer_batch_throughput == pytest.approx(5 / 1.25)
    (e,) = stats.epochs
    assert e.map_durations == [0.5, 0.7]
    assert e.map_read_durations == [0.2, 0.3]
    assert e.reduce_durations == [0.4, 0.6]
    assert e.throttle_duration == 0.01
    assert e.map_stage_duration >= 0
    assert len(e.consume_records) == 2
    assert stats.max_store_bytes == 4096

    row = stats.row()
    assert row["avg_map_task_duration"] == pytest.approx(0.6)
    assert row["max_reduce_task_duration"] == pytest.approx(0.6)


def test_trial_row_matches_reference_columns():
    """The trial CSV must carry the reference's full fieldname set
    (reference ``stats.py:335-381``) plus this repo's spill-tier and
    audit columns."""
    reference_fieldnames = [
        "num_files",
        "num_row_groups_per_file",
        "num_reducers",
        "num_trainers",
        "num_epochs",
        "max_concurrent_epochs",
        "trial",
        "duration",
        "row_throughput",
        "batch_throughput",
        "batch_throughput_per_trainer",
        "avg_object_store_utilization",
        "max_object_store_utilization",
    ]
    for agg in ("avg", "std", "max", "min"):
        reference_fieldnames += [
            f"{agg}_epoch_duration",
            f"{agg}_map_stage_duration",
            f"{agg}_reduce_stage_duration",
            f"{agg}_consume_stage_duration",
            f"{agg}_map_task_duration",
            f"{agg}_read_duration",
            f"{agg}_reduce_task_duration",
            f"{agg}_time_to_consume",
        ]
    own_columns = [
        "max_store_shm_bytes",
        "max_store_spill_bytes",
        "audit_epochs_ok",
        "audit_mismatch_epochs",
        "audit_rows_delivered",
    ]
    c = TrialStatsCollector(
        num_epochs=1,
        num_maps_per_epoch=1,
        num_reduces_per_epoch=1,
        num_rows=10,
        batch_size=5,
        num_trainers=1,
        num_row_groups_per_file=2,
        max_concurrent_epochs=2,
    )
    c.epoch_start(0)
    c.map_start(0)
    c.map_done(0, 0.1, 0.05)
    c.reduce_start(0)
    c.reduce_done(0, 0.2)
    c.consume(0, 0, nbytes=100)
    c.trial_done(10.0)
    stats = asyncio.run(c.get_stats(timeout=1))
    row = stats.row()
    missing = [k for k in reference_fieldnames + own_columns
               if k not in row]
    assert not missing, f"trial row missing columns: {missing}"
    assert row["num_row_groups_per_file"] == 2
    assert row["max_concurrent_epochs"] == 2


def test_get_stats_times_out_before_done():
    c = TrialStatsCollector(1, 1, 1)
    with pytest.raises(asyncio.TimeoutError):
        asyncio.run(c.get_stats(timeout=0.05))


def test_shuffle_reports_to_collector_actor(local_runtime, stats_dataset):
    """End-to-end: shuffle tasks in pool workers report to a collector actor;
    the final stats tree has every map/reduce/consume record."""
    num_epochs, num_reducers = 2, 3
    collector = runtime.spawn_actor(
        TrialStatsCollector,
        num_epochs,
        len(stats_dataset),
        num_reducers,
        1200,
        100,
        1,
        name="stats-e2e",
    )
    collector.wait_ready()

    from tests.test_shuffle import CollectingConsumer

    consumer = CollectingConsumer()
    duration = shuffle(
        stats_dataset,
        consumer,
        num_epochs=num_epochs,
        num_reducers=num_reducers,
        num_trainers=1,
        seed=3,
        stats_collector=collector,
    )
    stats = collector.call("get_stats", 10)
    assert isinstance(stats, TrialStats)
    assert stats.duration == pytest.approx(duration, abs=1.0)
    assert len(stats.epochs) == num_epochs
    for e in stats.epochs:
        assert len(e.map_durations) == len(stats_dataset)
        assert len(e.reduce_durations) == num_reducers
        assert len(e.consume_records) == num_reducers
        assert e.duration > 0
        assert all(c.nbytes > 0 for c in e.consume_records)
    collector.terminate()


def test_process_stats_writes_csvs(tmp_path):
    c = TrialStatsCollector(1, 1, 1, num_rows=50, batch_size=5, trial=0)
    c.epoch_start(0)
    c.map_start(0)
    c.map_done(0, 0.1, 0.05)
    c.reduce_start(0)
    c.reduce_done(0, 0.2)
    c.consume(0, 0, nbytes=10)
    c.trial_done(0.5)
    stats = asyncio.run(c.get_stats(timeout=1))

    summary = process_stats([stats], stats_dir=str(tmp_path))
    assert summary["num_trials"] == 1
    assert summary["duration_mean"] == pytest.approx(0.5)
    for fname in ("trial_stats.csv", "epoch_stats.csv", "consume_timeline.csv"):
        path = tmp_path / fname
        assert path.exists(), fname
        with open(path) as f:
            rows = list(csv.DictReader(f))
        assert len(rows) == 1

    # Append mode accumulates without re-writing the header.
    process_stats([stats], stats_dir=str(tmp_path), overwrite_stats=False)
    with open(tmp_path / "trial_stats.csv") as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2

    # Appending under a STALE header (file predates a schema change) must
    # refuse loudly — headerless rows in a new column order would land
    # values under the wrong headers with no error.
    with open(tmp_path / "trial_stats.csv") as f:
        lines = f.read().splitlines()
    old_header = ",".join(lines[0].split(",")[:-2])  # drop two columns
    with open(tmp_path / "trial_stats.csv", "w") as f:
        f.write("\n".join([old_header] + lines[1:]) + "\n")
    with pytest.raises(ValueError, match="does not match"):
        process_stats([stats], stats_dir=str(tmp_path), overwrite_stats=False)


def test_store_stats_sampler(local_runtime):
    import numpy as np

    ref = runtime.put_columns({"x": np.arange(1000)})
    with ObjectStoreStatsCollector(sample_period_s=0.05) as sampler:
        time.sleep(0.25)
    assert sampler.samples
    assert any(s.total_bytes > 0 for s in sampler.samples)
    runtime.free(ref)


def test_human_readable_helpers():
    assert human_readable_big_num(950) == "950"
    assert human_readable_big_num(1500) == "1.5K"
    assert human_readable_big_num(2_000_000) == "2M"
    assert human_readable_big_num(4e11) == "400B"
    assert human_readable_size(512) == "512.0 B"
    assert human_readable_size(2048) == "2.0 KiB"
    assert human_readable_size(3 * 1024 ** 3) == "3.0 GiB"
