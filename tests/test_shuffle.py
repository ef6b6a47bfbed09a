"""Shuffle-engine correctness tests.

Covers the gap called out in SURVEY.md §4: the reference never verifies
exactly-once row delivery through the real map/reduce path. Every test here
checks the ``key`` column partition/permutation invariants end to end."""

import collections

import numpy as np
import pytest

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.shuffle import (
    BatchConsumer,
    shuffle,
    shuffle_map,
    shuffle_reduce,
)


@pytest.fixture(scope="module")
def small_dataset(local_runtime, tmp_path_factory):
    data_dir = tmp_path_factory.mktemp("shuffle-data")
    filenames, num_bytes = generate_data(
        num_rows=2000,
        num_files=4,
        num_row_groups_per_file=2,
        max_row_group_skew=0.0,
        data_dir=str(data_dir),
    )
    assert num_bytes > 0
    return filenames


class CollectingConsumer(BatchConsumer):
    """Synchronous consumer that records refs and resolves keys."""

    def __init__(self):
        self.keys = collections.defaultdict(list)  # (epoch, rank) -> keys
        self.done = collections.defaultdict(bool)

    def consume(self, rank, epoch, batches):
        store = runtime.get_context().store
        for ref in batches:
            cb = store.get_columns(ref)
            self.keys[(epoch, rank)].extend(cb["key"].tolist())
            store.free(ref)

    def producer_done(self, rank, epoch):
        self.done[(epoch, rank)] = True

    def wait_until_ready(self, epoch):
        pass

    def wait_until_all_epochs_done(self):
        pass


def test_map_partitions_exactly_once(local_runtime, small_dataset):
    num_reducers = 4
    refs = shuffle_map(small_dataset[0], 0, num_reducers, epoch=0, seed=7)
    assert len(refs) == num_reducers
    store = runtime.get_context().store
    all_keys = []
    for ref in refs:
        cb = store.get_columns(ref)
        all_keys.extend(cb["key"].tolist())
        store.free(ref)
    assert sorted(all_keys) == list(range(500))  # 2000 rows / 4 files


def test_map_deterministic(local_runtime, small_dataset):
    r1 = shuffle_map(small_dataset[0], 0, 3, epoch=1, seed=42)
    r2 = shuffle_map(small_dataset[0], 0, 3, epoch=1, seed=42)
    store = runtime.get_context().store
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(
            store.get_columns(a)["key"], store.get_columns(b)["key"]
        )
    store.free(r1)
    store.free(r2)


def test_reduce_concat_and_permute(local_runtime, small_dataset):
    store = runtime.get_context().store
    parts = [
        store.put_columns({"key": np.arange(i * 10, (i + 1) * 10)})
        for i in range(3)
    ]
    out = shuffle_reduce(0, epoch=0, seed=3, part_refs=parts)
    cb = store.get_columns(out)
    keys = cb["key"]
    assert sorted(keys.tolist()) == list(range(30))
    assert not np.array_equal(keys, np.arange(30))  # actually permuted
    # Inputs survive the task (the driver frees them once the result lands
    # — keeps reduce retryable after a cluster host death, shuffle.py).
    assert all(store.exists(p) for p in parts)
    store.free(parts)
    store.free(out)


@pytest.mark.parametrize("num_trainers", [1, 3])
def test_full_shuffle_exactly_once(local_runtime, small_dataset, num_trainers):
    consumer = CollectingConsumer()
    num_epochs = 2
    duration = shuffle(
        small_dataset,
        consumer,
        num_epochs=num_epochs,
        num_reducers=5,
        num_trainers=num_trainers,
        seed=11,
    )
    assert duration > 0
    for epoch in range(num_epochs):
        epoch_keys = []
        for rank in range(num_trainers):
            assert consumer.done[(epoch, rank)]
            epoch_keys.extend(consumer.keys[(epoch, rank)])
        # Every row exactly once per epoch.
        assert sorted(epoch_keys) == list(range(2000))


def test_shuffle_error_propagates_without_hang(local_runtime, small_dataset):
    """A bad input file must surface as an error, not a pipeline hang: every
    rank still receives its producer-done sentinel and the driver raises."""
    from ray_shuffling_data_loader_tpu.runtime.tasks import TaskError

    consumer = CollectingConsumer()
    with pytest.raises(TaskError):
        shuffle(
            list(small_dataset) + ["/no/such/file.parquet"],
            consumer,
            num_epochs=1,
            num_reducers=2,
            num_trainers=2,
            seed=0,
        )
    assert consumer.done[(0, 0)] and consumer.done[(0, 1)]


def test_small_file_fewer_rows_than_reducers(local_runtime, tmp_path):
    """Files with <= num_reducers rows are legal (the reference handles any
    size, reference ``shuffle.py:151-163``); regression for the former
    hard assert at map time."""
    import pandas as pd

    path = str(tmp_path / "tiny.parquet")
    pd.DataFrame({"key": np.arange(3, dtype=np.int64)}).to_parquet(path)
    num_reducers = 8
    refs = shuffle_map(path, 0, num_reducers, epoch=0, seed=1)
    assert len(refs) == num_reducers
    store = runtime.get_context().store
    all_keys = []
    for ref in refs:
        all_keys.extend(store.get_columns(ref)["key"].tolist())
    assert sorted(all_keys) == [0, 1, 2]
    # Empty partitions still reduce cleanly.
    out = shuffle_reduce(0, epoch=0, seed=1, part_refs=refs)
    store.free(refs)
    store.free(out)


def test_shuffle_empty_file(local_runtime, tmp_path):
    """A zero-row Parquet file shuffles to zero rows, end to end."""
    import pandas as pd

    path = str(tmp_path / "empty.parquet")
    pd.DataFrame({"key": np.array([], dtype=np.int64)}).to_parquet(path)
    consumer = CollectingConsumer()
    shuffle(
        [path], consumer, num_epochs=1, num_reducers=2, num_trainers=1, seed=0
    )
    assert consumer.done[(0, 0)]
    assert consumer.keys[(0, 0)] == []


def test_epochs_differ(local_runtime, small_dataset):
    consumer = CollectingConsumer()
    shuffle(
        small_dataset,
        consumer,
        num_epochs=2,
        num_reducers=3,
        num_trainers=1,
        seed=5,
    )
    e0 = consumer.keys[(0, 0)]
    e1 = consumer.keys[(1, 0)]
    assert sorted(e0) == sorted(e1)
    assert e0 != e1  # different permutation per epoch


def test_map_decode_cache_roundtrip(local_runtime, small_dataset):
    """publish_cache returns the decoded columns' ref; a second map fed
    that ref must produce byte-identical partitions without touching
    Parquet (VERDICT-era decode work is paid once per file, not per
    epoch)."""
    store = runtime.get_context().store
    refs1, cache_ref = shuffle_map(
        small_dataset[0], 0, 3, epoch=2, seed=11, publish_cache=True
    )
    assert cache_ref is not None
    refs2 = shuffle_map(
        "/nonexistent/never-read.parquet",  # decode would blow up
        0,
        3,
        epoch=2,
        seed=11,
        cache_ref=cache_ref,
    )
    for a, b in zip(refs1, refs2):
        np.testing.assert_array_equal(
            store.get_columns(a)["key"], store.get_columns(b)["key"]
        )
        store.free(a)
        store.free(b)
    store.free(cache_ref)


def test_dataset_with_decode_cache_exactly_once(local_runtime, small_dataset):
    """Multi-epoch run with caching forced on still delivers every row
    exactly once per epoch, with per-epoch permutations differing."""
    from ray_shuffling_data_loader_tpu import ShufflingDataset

    ds = ShufflingDataset(
        list(small_dataset),
        num_epochs=3,
        num_trainers=1,
        batch_size=300,
        rank=0,
        num_reducers=4,
        seed=5,
        queue_name="cache-exactly-once",
        cache_decoded=True,
    )
    first_epoch_order = None
    for epoch in range(3):
        ds.set_epoch(epoch)
        keys = [k for b in ds for k in b["key"].tolist()]
        assert sorted(keys) == list(range(2000))
        if first_epoch_order is None:
            first_epoch_order = keys
        elif epoch == 1:
            assert keys != first_epoch_order


def test_index_schedule_stream_identical(
    local_runtime, small_dataset, index_schedule_pinned
):
    """Steady-state index schedule (plan + sparse gather from the decode
    cache) must deliver a bit-identical stream to the materialized
    map/reduce path — same rows, same order, per (epoch, rank)."""

    def run(cache_decoded, log):
        consumer = CollectingConsumer()
        shuffle(
            small_dataset,
            consumer,
            num_epochs=3,
            num_reducers=5,
            num_trainers=2,
            seed=23,
            cache_decoded=cache_decoded,
            schedule_log=log,
        )
        return consumer

    log_fast, log_slow = [], []
    fast = run(True, log_fast)
    slow = run(False, log_slow)
    # Epoch 0 materializes (cache cold); later epochs take the fast path.
    assert dict(log_fast)[0] == "mapreduce"
    assert dict(log_fast)[1] == "index"
    assert dict(log_fast)[2] == "index"
    assert all(s == "mapreduce" for _, s in log_slow)
    assert dict(fast.keys) == dict(slow.keys)
    assert dict(fast.done) == dict(slow.done)


def test_index_schedule_resume_matches(
    local_runtime, small_dataset, index_schedule_pinned
):
    """Checkpoint resume determinism across schedules: an epoch that ran
    via the index schedule originally must reproduce the exact stream when
    re-run cold (materialized) after a resume."""
    consumer = CollectingConsumer()
    log = []
    shuffle(
        small_dataset,
        consumer,
        num_epochs=3,
        num_reducers=4,
        num_trainers=1,
        seed=5,
        cache_decoded=True,
        schedule_log=log,
    )
    assert dict(log)[2] == "index"
    consumer2 = CollectingConsumer()
    log2 = []
    shuffle(
        small_dataset,
        consumer2,
        num_epochs=3,
        num_reducers=4,
        num_trainers=1,
        seed=5,
        start_epoch=2,
        cache_decoded=True,
        schedule_log=log2,
    )
    assert dict(log2)[2] == "mapreduce"  # cache cold on the resumed run
    assert consumer2.keys[(2, 0)] == consumer.keys[(2, 0)]


def test_index_schedule_env_off(local_runtime, small_dataset, monkeypatch):
    monkeypatch.setenv("RSDL_INDEX_SHUFFLE", "off")
    log = []
    consumer = CollectingConsumer()
    shuffle(
        small_dataset,
        consumer,
        num_epochs=2,
        num_reducers=3,
        num_trainers=1,
        seed=9,
        cache_decoded=True,
        schedule_log=log,
    )
    assert all(s == "mapreduce" for _, s in log)
    assert sorted(consumer.keys[(1, 0)]) == list(range(2000))


def test_index_schedule_gate_is_measured(local_runtime, monkeypatch):
    """The auto gate derives from probed host costs, not core counts
    (VERDICT r3 item 4): the same 25 GB / R=4 workload is declined on a
    1-vCPU-shaped probe and admitted on a many-core-shaped one where
    threaded gathers run near copy speed."""
    import importlib

    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    files = [f"f{i}" for i in range(16)]
    monkeypatch.setattr(
        sh, "_est_decoded_bytes", lambda f, n, c=None: 25e9
    )
    slow_host = {
        "gather_small": 2.4e9,
        "gather_large": 0.5e9,
        "copy": 3.5e9,
        "roundtrip": 1e-3,
    }
    many_core = {
        "gather_small": 60e9,
        "gather_large": 30e9,
        "copy": 20e9,
        "roundtrip": 3e-4,
    }
    monkeypatch.setitem(sh._PROBE_CACHE, "costs", slow_host)
    assert not sh._index_schedule_allowed(files, 4, False)
    monkeypatch.setitem(sh._PROBE_CACHE, "costs", many_core)
    assert sh._index_schedule_allowed(files, 4, False)
    # Tiny datasets engage on either host: the materialized path's
    # F x R store round-trips dominate at that scale.
    monkeypatch.setattr(
        sh, "_est_decoded_bytes", lambda f, n, c=None: 4e5
    )
    monkeypatch.setitem(sh._PROBE_CACHE, "costs", slow_host)
    assert sh._index_schedule_allowed(files[:4], 4, False)


def test_decoded_bytes_estimate_is_probed(local_runtime, small_dataset):
    """_est_decoded_bytes measures bytes/row from a decoded sample plus
    Parquet footers — the estimate must track the real decoded size
    (not an on-disk expansion constant) within the planning headroom."""
    import importlib

    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    est = sh._est_decoded_bytes(list(small_dataset), False)
    batches = [
        sh.read_parquet_columns(f) for f in small_dataset
    ]
    real = sum(
        sum(v.nbytes for v in b.columns.values()) for b in batches
    )
    assert real <= est <= 1.5 * real
    est32 = sh._est_decoded_bytes(list(small_dataset), True)
    assert est32 < est


def test_narrow_to_32_rejects_out_of_range(local_runtime, tmp_path):
    """narrow_to_32 must raise (not silently wrap) on ids outside int32
    range — wraparound would corrupt training data undetectably."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = str(tmp_path / "big_ids.parquet")
    pq.write_table(
        pa.table({"key": [0, 1], "big": [2**31, 5]}), path
    )
    with pytest.raises(ValueError, match="outside int32 range"):
        shuffle_map(path, 0, 2, epoch=0, seed=1, narrow_to_32=True)
