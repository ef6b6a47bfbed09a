"""Data-correctness audit layer tests (ISSUE 2 acceptance).

End-to-end digests through the real map/reduce/delivery pipeline: every
epoch's map == reduce == delivered coverage, an injected row-drop caught
with the failing epoch identified, fixed-seed delivered digests
reproducible across invocations, and the audit-off hot path doing no
digest work at all."""

import collections
import os

import numpy as np
import pytest

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.shuffle import BatchConsumer, shuffle
from ray_shuffling_data_loader_tpu.telemetry import audit, metrics

_AUDIT_ENV = ("RSDL_AUDIT", "RSDL_AUDIT_DIR", "RSDL_METRICS")


@pytest.fixture(scope="module")
def audit_runtime(tmp_path_factory):
    """A runtime whose workers were spawned AFTER auditing was enabled,
    so map/reduce tasks inherit the env and spool digest records."""
    saved = {k: os.environ.get(k) for k in _AUDIT_ENV}
    spool = str(tmp_path_factory.mktemp("audit-spool"))
    os.environ["RSDL_AUDIT"] = "1"
    os.environ["RSDL_AUDIT_DIR"] = spool
    os.environ["RSDL_METRICS"] = "1"
    audit.refresh_from_env()
    metrics.refresh_from_env()
    audit.reset(clear_spool=True)
    metrics.reset()
    ctx = runtime.init(num_workers=2)
    yield ctx
    runtime.shutdown()
    audit.reset(clear_spool=True)
    audit.clear_faults()
    metrics.reset()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    audit.refresh_from_env()
    metrics.refresh_from_env()


@pytest.fixture(scope="module")
def audit_dataset(audit_runtime, tmp_path_factory):
    data_dir = tmp_path_factory.mktemp("audit-data")
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
    def __init__(self):
        self.keys = collections.defaultdict(list)

    def consume(self, rank, epoch, batches):
        store = runtime.get_context().store
        for ref in batches:
            cb = store.get_columns(ref)
            self.keys[(epoch, rank)].extend(cb["key"].tolist())
            store.free(ref)

    def producer_done(self, rank, epoch):
        pass

    def wait_until_ready(self, epoch):
        pass

    def wait_until_all_epochs_done(self):
        pass


def test_digest_math_order_invariant_and_order_sensitive():
    keys = np.arange(1000, dtype=np.int64)
    perm = np.random.default_rng(0).permutation(keys)
    a, b = audit.StreamDigest(), audit.StreamDigest()
    a.update(keys)
    b.update(perm)
    # Coverage ignores order; same multiset -> same (count, xor, sum).
    assert a.coverage() == b.coverage()
    # Associativity: folding two halves == one pass.
    c, lo, hi = audit.StreamDigest(), audit.StreamDigest(), audit.StreamDigest()
    lo.update(keys[:400])
    hi.update(keys[400:])
    c.merge(lo)
    c.merge(hi)
    assert c.coverage() == a.coverage()
    # seq is order-SENSITIVE at matched positions.
    a2, b2 = audit.StreamDigest(), audit.StreamDigest()
    a2.update(keys, offset=0)
    b2.update(perm, offset=0)
    assert a2.seq != b2.seq
    # Position hashing is domain-separated from key hashing: with row-id
    # keys (key == position) a shared domain would make the sorted
    # stream digest to 0 and its reversal cancel to the same value.
    assert a2.seq != 0
    r2 = audit.StreamDigest()
    r2.update(keys[::-1], offset=0)
    assert r2.seq not in (0, a2.seq)
    # A single crossed swap (key i at position j, key j at position i)
    # must change seq.
    swapped = keys.copy()
    swapped[3], swapped[700] = swapped[700], swapped[3]
    s2 = audit.StreamDigest()
    s2.update(swapped, offset=0)
    assert s2.seq != a2.seq
    # A dropped row breaks coverage.
    d = audit.StreamDigest()
    d.update(keys[:-1])
    assert d.coverage() != a.coverage()
    # Int32/int64 key VALUES hash identically (decode narrowing must not
    # split the digest equality).
    e = audit.StreamDigest()
    e.update(keys.astype(np.int32))
    assert e.coverage() == a.coverage()


def test_multi_epoch_exactly_once_verdicts(audit_runtime, audit_dataset):
    """Acceptance: a multi-epoch end-to-end run reports map == reduce ==
    delivered digests and row counts for every epoch, and the audit.*
    counters land in the PR-1 metrics registry."""
    consumer = CollectingConsumer()
    num_epochs = 3
    shuffle(
        audit_dataset,
        consumer,
        num_epochs=num_epochs,
        num_reducers=5,
        num_trainers=2,
        seed=11,
    )
    verdicts = audit.verdicts()
    assert [v["epoch"] for v in verdicts] == list(range(num_epochs))
    for v in verdicts:
        assert v["ok"] is True, v
        assert v["rows_mapped"] == 2000
        assert v["rows_reduced"] == 2000
        assert v["rows_delivered"] == 2000
        assert v["map_digest"] == v["reduce_digest"] == v["delivered_digest"]
    snap = metrics.registry.snapshot()
    assert snap["audit.rows_mapped"] == num_epochs * 2000
    assert snap["audit.rows_delivered"] == num_epochs * 2000
    assert snap["audit.digest_mismatch"] == 0.0
    assert snap[metrics.format_key("audit.epoch_ok", {"epoch": 2})] == 1.0


def test_shuffle_quality_metrics(audit_runtime, audit_dataset):
    """A healthy seeded reshuffle looks random by the numbers: near-zero
    adjacent-pair retention, mean displacement near 1/3 (the uniform-
    permutation expectation), and near-uniform source-file entropy."""
    consumer = CollectingConsumer()
    shuffle(
        audit_dataset,
        consumer,
        num_epochs=3,
        num_reducers=4,
        num_trainers=1,
        seed=7,
    )
    verdicts = audit.verdicts()
    assert verdicts[0]["adjacent_pair_retention"] is None  # no prior epoch
    for v in verdicts[1:]:
        assert v["adjacent_pair_retention"] < 0.05
        assert 0.15 < v["mean_normalized_displacement"] < 0.55
    for v in verdicts:
        assert 0.9 < v["source_entropy_mean"] <= 1.0
        assert v["source_entropy_min"] > 0.8


def test_shuffle_quality_metrics_block_plan(
    audit_runtime, tmp_path_factory, monkeypatch
):
    """The block plan family's quality-vs-pruning tradeoff gets a
    regression FENCE, not a BENCHLOG paragraph (ISSUE 12): with
    RSDL_AUDIT on, a block:1 run at a bench-like shape (blocks per file
    = 2x reducers) emits retention/displacement/entropy per epoch, the
    gauges carry the plan label, and every metric stays within the
    bounds documented in TUNING.md — and within range of the same
    shape under rowwise."""
    data_dir = tmp_path_factory.mktemp("audit-block-data")
    filenames, _ = generate_data(
        num_rows=2000,
        num_files=4,
        num_row_groups_per_file=8,
        max_row_group_skew=0.0,
        data_dir=str(data_dir),
    )

    def run(plan_env):
        if plan_env is None:
            monkeypatch.delenv("RSDL_SHUFFLE_PLAN", raising=False)
        else:
            monkeypatch.setenv("RSDL_SHUFFLE_PLAN", plan_env)
        consumer = CollectingConsumer()
        shuffle(
            filenames, consumer, num_epochs=3, num_reducers=4,
            num_trainers=1, seed=13,
        )
        return audit.verdicts()

    block = run("block")
    # Per-epoch emission with RSDL_AUDIT on: every epoch reconciled ok
    # and carries the quality numbers (retention/displacement need a
    # prior epoch by definition).
    assert [v["epoch"] for v in block] == [0, 1, 2]
    for v in block:
        assert v["ok"] is True
        assert v["source_entropy_mean"] is not None
        assert v["source_entropy_min"] is not None
    for v in block[1:]:
        assert v["adjacent_pair_retention"] is not None
        assert v["mean_normalized_displacement"] is not None
    # The quality gauges are plan-labeled (observability.md vocabulary).
    snap = metrics.registry.snapshot()
    assert (
        metrics.format_key(
            "audit.source_entropy_mean", {"epoch": 1, "plan": "block:1"}
        )
        in snap
    )
    # Documented bounds (TUNING.md RSDL_SHUFFLE_PLAN row): with blocks
    # per file >= 2R, block:1 keeps a healthy reshuffle profile...
    for v in block[1:]:
        assert v["adjacent_pair_retention"] < 0.05
        assert 0.15 < v["mean_normalized_displacement"] < 0.55
    for v in block:
        assert v["source_entropy_min"] > 0.8
    # ... and stays within range of rowwise at the same shape (the
    # per-reducer file mix loses at most 0.1 normalized entropy).
    rowwise = run(None)
    for vb, vr in zip(block, rowwise):
        assert vb["source_entropy_mean"] > vr["source_entropy_mean"] - 0.1
    for vb, vr in zip(block[1:], rowwise[1:]):
        assert (
            abs(
                vb["mean_normalized_displacement"]
                - vr["mean_normalized_displacement"]
            )
            < 0.2
        )


def test_injected_row_drop_detected(audit_runtime, audit_dataset):
    """Acceptance: a test-only delivery fault (one row silently dropped)
    is detected as a digest mismatch with the failing epoch identified —
    the healthy epoch stays clean."""
    audit.inject_fault("drop-row", epoch=1)
    try:
        consumer = CollectingConsumer()
        shuffle(
            audit_dataset,
            consumer,
            num_epochs=2,
            num_reducers=4,
            num_trainers=1,
            seed=3,
        )
    finally:
        audit.clear_faults()
    # The fault is real: the consumer saw 1999 rows in epoch 1.
    assert len(consumer.keys[(1, 0)]) == 1999
    by_epoch = {v["epoch"]: v for v in audit.verdicts()}
    assert by_epoch[0]["ok"] is True
    assert by_epoch[1]["ok"] is False
    assert by_epoch[1]["mismatch"] == ["delivered"]
    assert by_epoch[1]["rows_delivered"] == 1999
    assert by_epoch[1]["rows_mapped"] == 2000
    assert metrics.registry.snapshot()["audit.digest_mismatch"] == 1.0
    summary = audit.summary()
    assert summary["ok"] is False
    assert summary["mismatch_epochs"] == [1]


def test_strict_mode_raises(audit_runtime, audit_dataset, monkeypatch):
    monkeypatch.setenv("RSDL_AUDIT_STRICT", "1")
    audit.inject_fault("drop-row", epoch=0)
    try:
        with pytest.raises(audit.AuditError, match=r"epoch\(s\) \[0\]"):
            shuffle(
                audit_dataset,
                CollectingConsumer(),
                num_epochs=1,
                num_reducers=3,
                num_trainers=1,
                seed=2,
            )
    finally:
        audit.clear_faults()


def test_fixed_seed_delivered_digests_reproducible(
    audit_runtime, audit_dataset
):
    """Acceptance: two invocations with the same seed produce identical
    per-epoch delivered digests — including the order-sensitive sequence
    digest — and a different seed produces different ones."""

    def run(seed):
        shuffle(
            audit_dataset,
            CollectingConsumer(),
            num_epochs=2,
            num_reducers=4,
            num_trainers=2,
            seed=seed,
        )
        return [
            (v["delivered_digest"], v["delivered_seq"])
            for v in audit.verdicts()
        ]

    first = run(5)
    second = run(5)
    other = run(6)
    assert first == second
    # Same rows (coverage equal), different permutation (seq differs).
    assert [d for d, _ in other] == [d for d, _ in first]
    assert [s for _, s in other] != [s for _, s in first]


def test_index_schedule_audited(
    audit_runtime, audit_dataset, index_schedule_pinned
):
    """The steady-state index schedule (plan + sparse gather from the
    decode cache) carries the same digest equality as the materialized
    path — the audit covers both schedules."""
    log = []
    shuffle(
        audit_dataset,
        CollectingConsumer(),
        num_epochs=3,
        num_reducers=4,
        num_trainers=1,
        seed=5,
        cache_decoded=True,
        schedule_log=log,
    )
    assert dict(log)[1] == "index"  # the fast path actually engaged
    for v in audit.verdicts():
        assert v["ok"] is True, v
        assert v["rows_delivered"] == 2000


def test_dataset_consumed_side_folds(audit_runtime, audit_dataset):
    """The trainer-side dataset records consumed digests; with the
    consumer in-process the verdict folds all four sides."""
    from ray_shuffling_data_loader_tpu import ShufflingDataset

    ds = ShufflingDataset(
        list(audit_dataset),
        num_epochs=2,
        num_trainers=1,
        batch_size=300,
        rank=0,
        num_reducers=4,
        seed=9,
        queue_name="audit-consume",
    )
    for epoch in range(2):
        ds.set_epoch(epoch)
        keys = [k for b in ds for k in b["key"].tolist()]
        assert sorted(keys) == list(range(2000))
    for v in audit.verdicts():
        assert v["ok"] is True, v
        assert v["rows_consumed"] == 2000
        assert v["consumed_digest"] == v["delivered_digest"]


def test_reconcile_dedups_retried_task_records(monkeypatch):
    """Cluster failover can execute a map/reduce task twice (the first
    agent died after flushing its digest record); reconcile must fold
    each logical unit of work once, not report a false mismatch."""
    monkeypatch.delenv("RSDL_AUDIT_DIR", raising=False)
    audit.reset()
    try:
        lo = {"key": np.arange(50)}
        hi = {"key": np.arange(50, 100)}
        both = {"key": np.arange(100)}
        audit.record_map(0, 0, both, per_reducer=[50, 50])
        audit.record_map(0, 0, both, per_reducer=[50, 50])  # retried
        audit.record_reduce(0, 0, lo)
        audit.record_reduce(0, 0, lo)  # retried attempt
        audit.record_reduce(0, 1, hi)
        audit.record_deliver(0, 0, 0, lo, 0)
        audit.record_deliver(0, 1, 0, hi, 50)
        (v,) = audit.reconcile([0])
        assert v["ok"] is True, v
        assert v["rows_mapped"] == 100
        assert v["rows_reduced"] == 100
    finally:
        audit.reset()


def test_reconcile_missing_worker_records_is_incomplete_not_mismatch(
    monkeypatch,
):
    """Deliver records without ANY map/reduce records (multi-host run
    whose spool dir is not shared) is an incomplete audit, not a data
    defect: ok=None with the remedy, never a strict-mode abort."""
    monkeypatch.delenv("RSDL_AUDIT_DIR", raising=False)
    monkeypatch.setenv("RSDL_AUDIT_STRICT", "1")
    audit.reset()
    try:
        audit.record_deliver(0, 0, 0, {"key": np.arange(10)}, 0)
        (v,) = audit.reconcile([0])  # strict: must not raise
        assert v["ok"] is None
        assert "RSDL_AUDIT_DIR" in v["detail"]
        assert v["rows_delivered"] == 10
        # Zero audited epochs must not read as a pass.
        assert audit.summary(reconcile_if_needed=False)["ok"] is None
    finally:
        audit.reset()


def test_summary_none_when_nothing_audited(monkeypatch):
    monkeypatch.delenv("RSDL_AUDIT_DIR", raising=False)
    audit.reset()
    try:
        assert audit.summary()["ok"] is None
    finally:
        audit.reset()


def test_audit_off_is_noop(tmp_path):
    """No digest work when RSDL_AUDIT is unset: record sites early-return
    and no spool file is created (the enabled() gate is the only cost on
    the hot path)."""
    saved = {k: os.environ.get(k) for k in _AUDIT_ENV}
    os.environ.pop("RSDL_AUDIT", None)
    os.environ["RSDL_AUDIT_DIR"] = str(tmp_path / "spool")
    audit.refresh_from_env()
    try:
        assert not audit.enabled()
        # Sites all guard on enabled(); even called directly, safe_flush
        # must not touch the filesystem while disabled.
        audit.safe_flush()
        assert not os.path.exists(str(tmp_path / "spool"))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        audit.refresh_from_env()
