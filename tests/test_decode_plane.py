"""Parallel decode plane tests (ISSUE 11).

Covers: row-group-parallel decode bit-identity against the single-shot
read (odd/skewed row-group sizes, single-row-group files, projection
on/off), row-group selections, the RINAS-style selective schedule's
stream equivalence against the materialized path under a fixed seed,
the cross-epoch shared decode-cache tier (hit + invalidation across
two consecutive ``shuffle()`` calls), pushdown pruned-bytes counters,
and the zero-overhead-off proof for the whole plane.
"""

import importlib
import os
import subprocess
import sys

import numpy as np
import pytest

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.utils import (
    decode_rowgroup_threads,
    shuffle_plan_label,
    shuffle_plan_spec,
)

sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")


def _sum_metric(snap: dict, name: str) -> float:
    """Total of a counter across its labeled series (ISSUE 12 put
    ``{schedule, plan}`` labels on the decode counters) — the shared
    ``export.labeled_sum`` fold, totals only."""
    from ray_shuffling_data_loader_tpu.telemetry import export

    return export.labeled_sum(snap, name)[0]


@pytest.fixture(scope="module")
def rg_dataset(local_runtime, tmp_path_factory):
    """Skewed row groups (odd sizes) — the decode plan's hard case."""
    data_dir = tmp_path_factory.mktemp("decode-plane-data")
    filenames, num_bytes = generate_data(
        num_rows=3000,
        num_files=3,
        num_row_groups_per_file=5,
        max_row_group_skew=0.5,
        data_dir=str(data_dir),
    )
    assert num_bytes > 0
    return filenames


@pytest.fixture
def shared_cache_clean():
    """Isolate shared-registry state per test (the registry is
    process-level by design)."""
    sh.shared_decode_cache_clear()
    yield
    sh.shared_decode_cache_clear()


class _Collecting(sh.BatchConsumer):
    def __init__(self):
        import collections

        self.keys = collections.defaultdict(list)
        self.done = collections.defaultdict(bool)

    def consume(self, rank, epoch, batches):
        from ray_shuffling_data_loader_tpu.runtime.store import (
            logical_columns,
        )

        store = runtime.get_context().store
        for ref in batches:
            cb = store.get_columns(ref)
            self.keys[(epoch, rank)].extend(
                np.asarray(logical_columns(cb)["key"]).tolist()
            )
            store.free(ref)

    def producer_done(self, rank, epoch):
        self.done[(epoch, rank)] = True

    def wait_until_ready(self, epoch):
        pass

    def wait_until_all_epochs_done(self):
        pass


# -- row-group-parallel decode bit-identity ---------------------------------


@pytest.mark.parametrize("threads", [2, 3])
@pytest.mark.parametrize("proj", [None, ["key", "labels"]])
def test_rowgroup_parallel_bit_identical(rg_dataset, threads, proj):
    """The row-group execution plan must assemble EXACTLY the arrays the
    single-shot read produces — values, dtypes, and column set — over
    skewed (odd-sized) row groups, with and without a projection."""
    for fname in rg_dataset:
        base = sh.read_parquet_columns(fname, columns=proj)
        plan = sh.read_parquet_columns(
            fname, columns=proj, rowgroup_threads=threads
        )
        assert list(base.columns) == list(plan.columns)
        for k in base.columns:
            assert base[k].dtype == plan[k].dtype
            np.testing.assert_array_equal(base[k], plan[k])


def test_rowgroup_parallel_single_group_file(local_runtime, tmp_path):
    """A single-row-group file has nothing to parallelize: the plan
    degrades to the single-shot read, bit-identically."""
    filenames, _ = generate_data(
        num_rows=500,
        num_files=1,
        num_row_groups_per_file=1,
        max_row_group_skew=0.0,
        data_dir=str(tmp_path),
    )
    assert len(sh.file_row_group_sizes(filenames[0])) == 1
    base = sh.read_parquet_columns(filenames[0])
    plan = sh.read_parquet_columns(filenames[0], rowgroup_threads=4)
    for k in base.columns:
        np.testing.assert_array_equal(base[k], plan[k])


def test_rowgroup_selection_matches_slices(rg_dataset):
    """A row-group selection decodes exactly the concatenation of those
    groups' row ranges, in ascending group order."""
    fname = rg_dataset[0]
    sizes = sh.file_row_group_sizes(fname)
    assert len(sizes) >= 4
    offs = np.cumsum([0] + sizes)
    whole = sh.read_parquet_columns(fname)
    sel = [1, 3]
    got = sh.read_parquet_columns(
        fname, row_groups=sel, rowgroup_threads=2
    )
    for k in whole.columns:
        expect = np.concatenate(
            [whole[k][offs[g] : offs[g + 1]] for g in sel]
        )
        np.testing.assert_array_equal(got[k], expect)
    empty = sh.read_parquet_columns(
        fname, columns=["key"], row_groups=[]
    )
    assert empty.num_rows == 0
    assert empty["key"].dtype == whole["key"].dtype


def test_rowgroup_parallel_null_column_identical(local_runtime, tmp_path):
    """A column with nulls decodes to a promoted dtype (int64 ->
    float64 with NaN): the plan's per-stripe conversion uses the very
    calls the single-shot path uses, so the promoted result must be
    identical either way."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = str(tmp_path / "nulls.parquet")
    table = pa.table(
        {
            "key": pa.array(list(range(100)), pa.int64()),
            "holey": pa.array(
                [None if i % 7 == 0 else i for i in range(100)],
                pa.int64(),
            ),
        }
    )
    with pq.ParquetWriter(path, table.schema) as w:
        for at in (0, 50):
            w.write_table(table.slice(at, 50), row_group_size=25)
    base = sh.read_parquet_columns(path)
    plan = sh.read_parquet_columns(path, rowgroup_threads=2)
    for k in base.columns:
        assert base[k].dtype == plan[k].dtype
        np.testing.assert_array_equal(base[k], plan[k])


def test_projection_missing_column_semantics(rg_dataset):
    """A typo'd explicit projection raises at the decode site (exactly
    as pq.read_table always did); ONLY the auto-appended audit key is
    tolerated-and-skipped — a keyless dataset must warn-and-skip in
    audit, not fail the map."""
    from ray_shuffling_data_loader_tpu.telemetry import audit as _audit

    with pytest.raises(ValueError, match="not in"):
        sh.read_parquet_columns(
            rg_dataset[0], columns=["labels", "no_such_column"]
        )
    saved = {
        k: os.environ.get(k) for k in ("RSDL_AUDIT", "RSDL_AUDIT_KEY")
    }
    os.environ["RSDL_AUDIT"] = "1"
    os.environ["RSDL_AUDIT_KEY"] = "no_such_column"
    _audit.refresh_from_env()
    try:
        got = sh.read_parquet_columns(
            rg_dataset[0], columns=["labels", "no_such_column"]
        )
        assert list(got.columns) == ["labels"]
        # ... but a projection selecting NOTHING still raises.
        with pytest.raises(ValueError, match="selects no columns"):
            sh.read_parquet_columns(
                rg_dataset[0], columns=["no_such_column"]
            )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _audit.refresh_from_env()


def test_decode_rowgroup_threads_gate(monkeypatch):
    """RSDL_DECODE_ROWGROUPS parsing: unset/off = 1 (no thread ever),
    auto = fair share only when idle cores exist, integers forced."""
    monkeypatch.delenv("RSDL_DECODE_ROWGROUPS", raising=False)
    assert decode_rowgroup_threads(1) == 1
    monkeypatch.setenv("RSDL_DECODE_ROWGROUPS", "off")
    assert decode_rowgroup_threads(1) == 1
    monkeypatch.setenv("RSDL_DECODE_ROWGROUPS", "3")
    assert decode_rowgroup_threads(8) == 3
    monkeypatch.setenv("RSDL_DECODE_ROWGROUPS", "auto")
    cores = os.cpu_count() or 1
    # Saturated stage: auto declines.
    assert decode_rowgroup_threads(cores) == 1
    monkeypatch.setenv("RSDL_DECODE_ROWGROUPS", "on")
    assert decode_rowgroup_threads(cores) >= 2


# -- column pushdown --------------------------------------------------------


def test_pushdown_stream_and_counters(local_runtime, rg_dataset, monkeypatch):
    """An explicit ``columns=`` projection delivers exactly that set
    (plus the audit key when armed) and records pruned rows/bytes."""
    from ray_shuffling_data_loader_tpu.telemetry import metrics

    monkeypatch.setenv("RSDL_METRICS", "1")
    metrics.refresh_from_env()
    metrics.reset()
    try:
        consumer = _Collecting()
        # In-process decode so the counters land in THIS registry (the
        # lane also proves the spooled path end to end).
        refs = sh.shuffle_map(
            rg_dataset[0], 0, 2, epoch=0, seed=3,
            columns=["key", "labels"],
        )
        store = runtime.get_context().store
        got_cols = set(store.get_columns(refs[0]).columns)
        assert "key" in got_cols and "labels" in got_cols
        assert "embeddings_name0" not in got_cols
        store.free(refs)
        snap = metrics.registry.snapshot()
        assert _sum_metric(snap, "shuffle.decode_bytes_pruned") > 0
        assert _sum_metric(snap, "shuffle.decode_rowgroups") >= 1
        # The counters carry the map task's attribution (ISSUE 12);
        # the plan label follows the ambient env (the CI block leg
        # runs this very test under RSDL_SHUFFLE_PLAN=block).
        assert any(
            k.startswith("shuffle.decode_rowgroups{")
            and "schedule=mapreduce" in k
            and f"plan={shuffle_plan_label()}" in k
            for k in snap
        )
        # Full end-to-end projected shuffle still delivers every row.
        sh.shuffle(
            list(rg_dataset), consumer, num_epochs=1, num_reducers=3,
            num_trainers=1, seed=11, columns=["key", "labels"],
        )
        assert sorted(consumer.keys[(0, 0)]) == list(range(3000))
    finally:
        monkeypatch.delenv("RSDL_METRICS")
        metrics.refresh_from_env()
        metrics.reset()


def test_pushdown_declines_without_spec(rg_dataset, monkeypatch):
    """No explicit projection and no ``on`` override: full decode (the
    'decline when the spec is unknown' contract)."""
    monkeypatch.setenv("RSDL_DECODE_PUSHDOWN", "auto")
    assert sh._pushdown_columns(None, None) is None
    layout = {"batch": 8, "columns": ["key"]}
    # auto never derives from the layout alone...
    assert sh._pushdown_columns(layout, None) is None
    # ...on does; off never.
    monkeypatch.setenv("RSDL_DECODE_PUSHDOWN", "on")
    assert sh._pushdown_columns(layout, None) == ["key"]
    monkeypatch.setenv("RSDL_DECODE_PUSHDOWN", "off")
    assert sh._pushdown_columns(layout, ["key"]) is None


def test_stats_task_honors_projection(local_runtime, rg_dataset):
    """_dataset_stats_task must size the PROJECTED decoded footprint
    (satellite: the old estimate summed every schema column and
    mis-sized the store budget under pushdown)."""
    per_row_all, rows = sh._dataset_stats_task(list(rg_dataset), False)
    per_row_proj, rows2 = sh._dataset_stats_task(
        list(rg_dataset), False, ["key", "labels"]
    )
    assert rows == rows2 == 3000
    assert per_row_proj == pytest.approx(16.0)  # int64 key + f64 labels
    assert per_row_all > 10 * per_row_proj


# -- selective schedule (RINAS first cut) -----------------------------------


def test_selective_stream_identical(local_runtime, rg_dataset, monkeypatch):
    """RSDL_SELECTIVE_READS=on: every epoch runs the selective schedule
    (plan counts + row-group-selective reduce, no map materialization)
    and the delivered stream is IDENTICAL to the materialized path —
    same rows, same order, per (epoch, rank), fixed seed."""
    log_sel, log_mat = [], []
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "on")
    selective = _Collecting()
    sh.shuffle(
        list(rg_dataset), selective, num_epochs=2, num_reducers=4,
        num_trainers=2, seed=17, cache_decoded=False,
        schedule_log=log_sel,
    )
    monkeypatch.delenv("RSDL_SELECTIVE_READS")
    materialized = _Collecting()
    sh.shuffle(
        list(rg_dataset), materialized, num_epochs=2, num_reducers=4,
        num_trainers=2, seed=17, cache_decoded=False,
        schedule_log=log_mat,
    )
    assert [s for _, s in log_sel] == ["selective", "selective"]
    assert [s for _, s in log_mat] == ["mapreduce", "mapreduce"]
    assert dict(selective.keys) == dict(materialized.keys)
    assert dict(selective.done) == dict(materialized.done)


def test_selective_narrowed_stream_identical(
    local_runtime, rg_dataset, monkeypatch
):
    """Selective + narrow_to_32: the stream still matches the
    materialized path bit-for-bit (and under the audit-strict CI lane
    this proves the plan's NARROWED map digests reconcile against the
    narrowed reduce/deliver sides)."""
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "on")
    selective = _Collecting()
    sh.shuffle(
        list(rg_dataset), selective, num_epochs=1, num_reducers=4,
        num_trainers=1, seed=31, cache_decoded=False, narrow_to_32=True,
    )
    monkeypatch.delenv("RSDL_SELECTIVE_READS")
    materialized = _Collecting()
    sh.shuffle(
        list(rg_dataset), materialized, num_epochs=1, num_reducers=4,
        num_trainers=1, seed=31, cache_decoded=False, narrow_to_32=True,
    )
    assert dict(selective.keys) == dict(materialized.keys)


def test_selective_with_projection(local_runtime, rg_dataset, monkeypatch):
    """Selective reads compose with pushdown: projected columns only,
    exactly-once delivery intact."""
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "on")
    consumer = _Collecting()
    sh.shuffle(
        list(rg_dataset), consumer, num_epochs=1, num_reducers=5,
        num_trainers=1, seed=23, cache_decoded=False,
        columns=["key", "labels"],
    )
    assert sorted(consumer.keys[(0, 0)]) == list(range(3000))


# -- block-granular plan family (ISSUE 12) ----------------------------------


def test_shuffle_plan_spec_parsing(monkeypatch):
    """RSDL_SHUFFLE_PLAN parsing: rowwise default, block[:G], and a
    LOUD ValueError on anything malformed — the plan family decides the
    delivered stream, so a typo must never silently change it."""
    monkeypatch.delenv("RSDL_SHUFFLE_PLAN", raising=False)
    assert shuffle_plan_spec() == ("rowwise", 0)
    assert shuffle_plan_label() == "rowwise"
    monkeypatch.setenv("RSDL_SHUFFLE_PLAN", "block")
    assert shuffle_plan_spec() == ("block", 1)
    assert shuffle_plan_label() == "block:1"
    monkeypatch.setenv("RSDL_SHUFFLE_PLAN", "block:3")
    assert shuffle_plan_spec() == ("block", 3)
    assert shuffle_plan_label() == "block:3"
    for bad in ("block:0", "block:-1", "block:x", "banana"):
        monkeypatch.setenv("RSDL_SHUFFLE_PLAN", bad)
        with pytest.raises(ValueError, match="RSDL_SHUFFLE_PLAN"):
            shuffle_plan_spec()


def test_block_assignment_group_aligned(rg_dataset):
    """Under a block plan every row of a row group travels to ONE
    reducer, the assignment is deterministic per (seed, epoch, file),
    epochs re-deal, and the guards (missing filename, footer mismatch)
    raise loudly."""
    plan = ("block", 1)
    fname = rg_dataset[0]
    sizes = sh.file_row_group_sizes(fname)
    n = sum(sizes)
    a1 = sh._file_assignment(3, 1, 0, n, 4, fname, plan)
    a2 = sh._file_assignment(3, 1, 0, n, 4, fname, plan)
    np.testing.assert_array_equal(a1, a2)
    off = 0
    for s in sizes:
        assert len(set(a1[off:off + s].tolist())) == 1
        off += s
    a3 = sh._file_assignment(3, 2, 0, n, 4, fname, plan)
    assert not np.array_equal(a1, a3)
    with pytest.raises(ValueError, match="filename"):
        sh._file_assignment(3, 1, 0, n, 4, None, plan)
    with pytest.raises(ValueError, match="footer"):
        sh._file_assignment(3, 1, 0, n + 1, 4, fname, plan)


def test_block_granularity_blocks_groups(rg_dataset):
    """block:G deals CONSECUTIVE runs of G row groups to one reducer
    (the block is the unit of assignment, not the single group)."""
    fname = rg_dataset[0]
    sizes = sh.file_row_group_sizes(fname)
    owners = sh._group_owners(5, 0, 0, sizes, 3, 2)
    assert len(owners) == len(sizes)
    for b in range(0, len(sizes) - 1, 2):
        assert owners[b] == owners[b + 1]


def test_block_selections_disjoint_cover_once(rg_dataset):
    """The tentpole invariant: per-reducer row-group selections under a
    block plan are DISJOINT and cover every group exactly once — each
    group decodes once per epoch instead of ~R times — and per-file
    block counts are balanced to within one."""
    plan = ("block", 1)
    num_reducers = 4
    for i, fname in enumerate(rg_dataset):
        phys = len(sh.file_row_group_sizes(fname))
        sels = [
            sh.selective_file_selection(
                fname, i, r, num_reducers, 0, 9, plan
            )[0]
            for r in range(num_reducers)
        ]
        allg = np.concatenate(sels)
        assert len(allg) == phys
        assert len(np.unique(allg)) == phys
        lens = sorted(len(s) for s in sels)
        assert lens[-1] - lens[0] <= 1


def test_selective_auto_gate(monkeypatch):
    """RSDL_SELECTIVE_READS=auto engages only for prunable (block)
    plans and declines — with a reason — under rowwise."""
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "auto")
    monkeypatch.delenv("RSDL_SHUFFLE_PLAN", raising=False)
    engaged, reason = sh.selective_reads_decision()
    assert not engaged
    assert "declined" in reason and "rowwise" in reason
    monkeypatch.setenv("RSDL_SHUFFLE_PLAN", "block")
    engaged, reason = sh.selective_reads_decision()
    assert engaged
    assert "prunable" in reason
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "off")
    assert sh.selective_reads_decision() == (False, "off")
    # Forced on stays on regardless of plan family.
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "on")
    monkeypatch.delenv("RSDL_SHUFFLE_PLAN", raising=False)
    assert sh.selective_reads_decision()[0]


def test_selective_auto_declines_to_materialized(
    local_runtime, rg_dataset, monkeypatch
):
    """auto + rowwise runs the MATERIALIZED schedule instead of
    silently eating the R-fold selective re-read."""
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "auto")
    monkeypatch.delenv("RSDL_SHUFFLE_PLAN", raising=False)
    log = []
    consumer = _Collecting()
    sh.shuffle(
        list(rg_dataset), consumer, num_epochs=1, num_reducers=4,
        num_trainers=1, seed=3, cache_decoded=False, schedule_log=log,
    )
    assert [s for _, s in log] == ["mapreduce"]
    assert sorted(consumer.keys[(0, 0)]) == list(range(3000))


def test_block_selective_stream_matches_materialized(
    local_runtime, rg_dataset, monkeypatch
):
    """Selective and materialized deliver the SAME stream under the
    block plan family too (the _file_assignment seam is structural), and
    the stream is deterministic per seed."""
    monkeypatch.setenv("RSDL_SHUFFLE_PLAN", "block")
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "auto")
    log1 = []
    a = _Collecting()
    sh.shuffle(
        list(rg_dataset), a, num_epochs=2, num_reducers=4,
        num_trainers=2, seed=17, cache_decoded=False, schedule_log=log1,
    )
    assert [s for _, s in log1] == ["selective", "selective"]
    # Pin OFF (not unset) for the materialized control: under the CI
    # planner lane (RSDL_PLAN=auto) an unset knob is planner-owned and
    # would be planned right back to selective on this prunable shape.
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "off")
    log2 = []
    b = _Collecting()
    sh.shuffle(
        list(rg_dataset), b, num_epochs=2, num_reducers=4,
        num_trainers=2, seed=17, cache_decoded=False, schedule_log=log2,
    )
    assert [s for _, s in log2] == ["mapreduce", "mapreduce"]
    assert dict(a.keys) == dict(b.keys)
    assert dict(a.done) == dict(b.done)
    # Determinism per seed: a rerun delivers the identical stream.
    monkeypatch.setenv("RSDL_SELECTIVE_READS", "auto")
    c = _Collecting()
    sh.shuffle(
        list(rg_dataset), c, num_epochs=1, num_reducers=4,
        num_trainers=2, seed=17, cache_decoded=False,
    )
    assert c.keys[(0, 0)] == a.keys[(0, 0)]
    assert c.keys[(0, 1)] == a.keys[(0, 1)]


def test_block_selective_prunes_in_process(
    local_runtime, rg_dataset, monkeypatch
):
    """One in-process selective reduce under block:1 decodes ONLY its
    own groups: decode_rows_pruned engages (> 0), the rowgroup counter
    carries {schedule=selective, plan=block:1}, and groups decoded stay
    under the physical count (vs ~R x physical for rowwise)."""
    from ray_shuffling_data_loader_tpu.telemetry import metrics

    monkeypatch.setenv("RSDL_METRICS", "1")
    metrics.refresh_from_env()
    metrics.reset()
    try:
        plan = ("block", 1)
        out_ref = sh.shuffle_selective_reduce(
            0, 0, 5, list(rg_dataset), 4, plan=plan
        )
        store = runtime.get_context().store
        phys = sum(
            len(sh.file_row_group_sizes(f)) for f in rg_dataset
        )
        snap = metrics.registry.snapshot()
        groups = _sum_metric(snap, "shuffle.decode_rowgroups")
        assert 0 < groups <= phys
        assert _sum_metric(snap, "shuffle.decode_rows_pruned") > 0
        labeled = [
            k for k in snap
            if k.startswith("shuffle.decode_rowgroups{")
        ]
        assert labeled and all(
            "schedule=selective" in k and "plan=block:1" in k
            for k in labeled
        )
        expect_rows = sum(
            len(
                sh.selective_file_selection(
                    f, i, 0, 4, 0, 5, plan
                )[1]
            )
            for i, f in enumerate(rg_dataset)
        )
        cb = store.get_columns(out_ref)
        assert cb.num_rows == expect_rows
        del cb
        store.free(out_ref)
    finally:
        monkeypatch.delenv("RSDL_METRICS")
        metrics.refresh_from_env()
        metrics.reset()


# -- cross-epoch shared decode-cache tier -----------------------------------


def test_shared_cache_hit_across_runs(
    local_runtime, rg_dataset, monkeypatch, shared_cache_clean,
    index_schedule_pinned,
):
    """Two consecutive shuffle() calls with the shared tier armed: the
    second starts cache-hot (epoch 0 goes straight to the index
    schedule) and delivers the same fixed-seed stream."""
    monkeypatch.setenv("RSDL_DECODE_CACHE_SHARED", "on")
    log1, log2 = [], []
    first = _Collecting()
    sh.shuffle(
        list(rg_dataset), first, num_epochs=2, num_reducers=4,
        num_trainers=1, seed=7, cache_decoded=True, schedule_log=log1,
    )
    assert dict(log1)[0] == "mapreduce"
    assert dict(log1)[1] == "index"
    assert len(sh._SHARED_CACHE) == len(rg_dataset)
    second = _Collecting()
    sh.shuffle(
        list(rg_dataset), second, num_epochs=2, num_reducers=4,
        num_trainers=1, seed=7, cache_decoded=True, schedule_log=log2,
    )
    assert dict(log2)[0] == "index"  # cache-hot from epoch 0
    assert dict(first.keys) == dict(second.keys)


def test_shared_cache_invalidation(
    local_runtime, rg_dataset, monkeypatch, shared_cache_clean
):
    """A shed segment (evictor drop, session cleanup) must never be
    handed out: the registry validates against the store and the next
    run re-decodes — degraded, never broken."""
    monkeypatch.setenv("RSDL_DECODE_CACHE_SHARED", "on")
    warm = _Collecting()
    sh.shuffle(
        list(rg_dataset), warm, num_epochs=2, num_reducers=3,
        num_trainers=1, seed=7, cache_decoded=True,
    )
    store = runtime.get_context().store
    refs = list(sh._SHARED_CACHE.values())
    assert refs and all(store.exists(r) for r in refs)
    store.free(refs)  # simulate the evictor's drop rung
    log = []
    cold = _Collecting()
    sh.shuffle(
        list(rg_dataset), cold, num_epochs=1, num_reducers=3,
        num_trainers=1, seed=7, cache_decoded=True, schedule_log=log,
    )
    assert dict(log)[0] == "mapreduce"  # re-decoded, no dangling ref
    assert sorted(cold.keys[(0, 0)]) == list(range(3000))
    assert dict(cold.keys) == {
        k: v for k, v in warm.keys.items() if k[0] == 0
    }


def test_shared_cache_off_by_default(
    local_runtime, rg_dataset, shared_cache_clean
):
    """Gates unset: per-run cache semantics untouched — no registry
    entry survives the run (zero-overhead contract)."""
    os.environ.pop("RSDL_DECODE_CACHE_SHARED", None)
    consumer = _Collecting()
    sh.shuffle(
        list(rg_dataset), consumer, num_epochs=2, num_reducers=3,
        num_trainers=1, seed=5, cache_decoded=True,
    )
    assert sh._SHARED_CACHE == {}


# -- zero-overhead off ------------------------------------------------------


@pytest.mark.slow
def test_zero_overhead_when_gates_unset(tmp_path):
    """Fresh interpreter, every decode-plane gate unset: a real shuffle
    run spawns no decode threads, imports no capacity ledger, registers
    nothing in the shared tier, and the metrics spool stays absent (so
    no ledger ``touch`` records can exist)."""
    code = """
import os, sys, threading
for k in list(os.environ):
    if k.startswith("RSDL_"):
        del os.environ[k]
os.environ["RSDL_SHM_DIR"] = r"%(shm)s"
os.environ["JAX_PLATFORMS"] = "cpu"

def main():
    import importlib
    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.data_generation import generate_data
    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    runtime.init(num_workers=2)
    files, _ = generate_data(600, 2, 3, 0.0, r"%(data)s")
    class C(sh.BatchConsumer):
        def consume(self, rank, epoch, batches):
            runtime.get_context().store.free(list(batches))
        def producer_done(self, rank, epoch): pass
        def wait_until_ready(self, epoch): pass
        def wait_until_all_epochs_done(self): pass
    sh.shuffle(files, C(), num_epochs=2, num_reducers=2,
               num_trainers=1, seed=1, cache_decoded=True)
    assert "ray_shuffling_data_loader_tpu.telemetry.capacity" \\
        not in sys.modules, "capacity ledger imported with gates unset"
    assert sh._SHARED_CACHE == {}, "shared tier armed with gates unset"
    assert not any(
        t.name.startswith("rsdl-decode") for t in threading.enumerate()
    ), "decode threads with gates unset"
    from ray_shuffling_data_loader_tpu.utils import (
        decode_rowgroup_threads,
    )
    assert decode_rowgroup_threads(1) == 1
    runtime.shutdown()
    print("ZERO-OVERHEAD-OK")

if __name__ == "__main__":
    main()
""" % {"shm": str(tmp_path / "shm"), "data": str(tmp_path / "data")}
    script = tmp_path / "zero_overhead.py"
    script.write_text(code)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "ZERO-OVERHEAD-OK" in out.stdout
