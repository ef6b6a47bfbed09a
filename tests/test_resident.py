"""Device-resident shuffle tests: exactly-once per epoch, determinism,
rank splits, drop_last, skip_batches resume, sharded gather — all on the
8-virtual-device CPU mesh.

The resident path replaces the host map/reduce per epoch with an
on-device permutation + gather (see ``resident.py``); these tests pin the
same shuffle contract the reference engine provides (reference
``shuffle.py:171-200``, ``dataset.py:108-188``), which the reference
itself never tested for the real shuffle path (SURVEY §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.data_generation import (
    EMBEDDING_COLUMNS,
    LABEL_COLUMN,
)
from ray_shuffling_data_loader_tpu.parallel import DATA_AXIS, make_mesh
from ray_shuffling_data_loader_tpu.resident import (
    DeviceResidentShufflingDataset,
    dataset_num_rows,
    fits_device,
    packed_nbytes,
)

NUM_ROWS = 8192
FEATURES = EMBEDDING_COLUMNS[:3] + ["key"]


@pytest.fixture(scope="module")
def resident_files(local_runtime, tmp_path_factory):
    from ray_shuffling_data_loader_tpu.data_generation import generate_data

    data_dir = tmp_path_factory.mktemp("resident-data")
    filenames, _ = generate_data(
        num_rows=NUM_ROWS,
        num_files=3,  # deliberately not a divisor of the row count
        num_row_groups_per_file=2,
        max_row_group_skew=0.0,
        data_dir=str(data_dir),
    )
    return filenames


def _make(files, **kw):
    kw.setdefault("num_epochs", 3)
    kw.setdefault("batch_size", 512)
    kw.setdefault("feature_columns", FEATURES)
    kw.setdefault("label_column", LABEL_COLUMN)
    kw.setdefault("mesh", make_mesh(model_parallelism=1))
    kw.setdefault("seed", 7)
    # Exercise the piece-streaming loop: several pieces per file and a
    # ragged final piece.
    kw.setdefault("piece_rows", 1000)
    return DeviceResidentShufflingDataset(files, **kw)


def test_exactly_once_and_sharded(local_runtime, resident_files):
    ds = _make(resident_files)
    assert ds.num_rows == NUM_ROWS
    orders = []
    for epoch in range(2):
        ds.set_epoch(epoch)
        seen = []
        for features, label in ds:
            assert set(features) == set(FEATURES)
            arr = features["key"]
            assert isinstance(arr, jax.Array)
            assert arr.dtype == jnp.int32
            assert arr.shape == (512,)
            assert arr.sharding.spec == (DATA_AXIS,)
            assert label.dtype == jnp.float32
            assert float(jnp.min(label)) >= 0.0
            assert float(jnp.max(label)) <= 1.0
            seen.append(np.asarray(arr))
        flat = np.concatenate(seen)
        # 8192 rows / 512 = 16 exact batches: every row exactly once.
        assert len(flat) == NUM_ROWS
        assert np.array_equal(np.sort(flat), np.arange(NUM_ROWS))
        orders.append(flat)
    # Epochs shuffle differently.
    assert not np.array_equal(orders[0], orders[1])


def test_label_values_roundtrip(local_runtime, resident_files):
    """The bitcast unpack must reproduce the decoded float values, not
    just their set membership: compare against a direct Parquet read."""
    import pyarrow.parquet as pq

    expected = {}
    for f in resident_files:
        t = pq.read_table(f, columns=["key", LABEL_COLUMN])
        keys = t.column("key").to_numpy()
        vals = t.column(LABEL_COLUMN).to_numpy().astype(np.float32)
        expected.update(zip(keys.tolist(), vals.tolist()))
    ds = _make(resident_files)
    ds.set_epoch(0)
    for features, label in ds:
        keys = np.asarray(features["key"])
        vals = np.asarray(label)
        for k, v in zip(keys.tolist(), vals.tolist()):
            assert expected[k] == pytest.approx(v)
        break  # one batch is plenty at this cost


def test_deterministic_given_seed(local_runtime, resident_files):
    a = _make(resident_files)
    b = _make(resident_files)
    a.set_epoch(1)
    b.set_epoch(1)
    fa, la = next(iter(a))
    fb, lb = next(iter(b))
    assert np.array_equal(np.asarray(fa["key"]), np.asarray(fb["key"]))
    assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_rank_split_disjoint_and_complete(local_runtime, resident_files):
    ranks = [
        _make(resident_files, num_trainers=2, rank=r, drop_last=False)
        for r in range(2)
    ]
    all_keys = []
    for ds in ranks:
        ds.set_epoch(0)
        rank_keys = np.concatenate(
            [np.asarray(f["key"]) for f, _ in ds]
        )
        all_keys.append(rank_keys)
    assert not set(all_keys[0].tolist()) & set(all_keys[1].tolist())
    union = np.concatenate(all_keys)
    assert np.array_equal(np.sort(union), np.arange(NUM_ROWS))


def test_drop_last_and_ragged_tail(local_runtime, resident_files):
    # 8192 rows at batch 480: 17 full batches + 32-row tail.
    ds = _make(resident_files, batch_size=480, drop_last=True)
    ds.set_epoch(0)
    batches = [np.asarray(f["key"]) for f, _ in ds]
    assert len(batches) == NUM_ROWS // 480
    assert all(len(b) == 480 for b in batches)

    ds2 = _make(resident_files, batch_size=480, drop_last=False)
    assert ds2.num_batches == NUM_ROWS // 480 + 1
    ds2.set_epoch(0)
    batches = [np.asarray(f["key"]) for f, _ in ds2]
    assert len(batches[-1]) == NUM_ROWS % 480
    flat = np.concatenate(batches)
    assert np.array_equal(np.sort(flat), np.arange(NUM_ROWS))


def test_skip_batches_resume(local_runtime, resident_files):
    ds = _make(resident_files)
    ds.set_epoch(2)
    full = [np.asarray(f["key"]) for f, _ in ds]
    ds.set_epoch(2, skip_batches=5)
    resumed = [np.asarray(f["key"]) for f, _ in ds]
    assert len(resumed) == len(full) - 5
    for a, b in zip(full[5:], resumed):
        assert np.array_equal(a, b)


def test_materialized_and_gather_paths_identical(local_runtime, resident_files):
    """materialize_epoch changes the schedule (one whole-epoch gather vs
    per-batch gathers), never the stream: same seed -> same batches, so
    checkpoints resume exactly across the setting."""
    mat = _make(resident_files, materialize_epoch=True)
    gat = _make(resident_files, materialize_epoch=False)
    assert mat._materialize is True and gat._materialize is False
    for epoch in (0, 1):
        mat.set_epoch(epoch)
        gat.set_epoch(epoch)
        for (fa, la), (fb, lb) in zip(mat, gat):
            assert np.array_equal(np.asarray(fa["key"]), np.asarray(fb["key"]))
            assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_epoch_bounds_and_bad_rank(local_runtime, resident_files):
    ds = _make(resident_files)
    with pytest.raises(ValueError):
        ds.set_epoch(99)
    with pytest.raises(RuntimeError):
        next(iter(_make(resident_files)))
    with pytest.raises(ValueError):
        _make(resident_files, num_trainers=2, rank=2)


def test_close_releases_and_blocks_iteration(local_runtime, resident_files):
    ds = _make(resident_files)
    ds.set_epoch(0)
    next(iter(ds))
    ds.close()
    assert ds._buf is None
    with pytest.raises(RuntimeError, match="closed"):
        next(iter(ds))
    with pytest.raises(RuntimeError, match="closed"):
        ds.set_epoch(0)


def test_close_invalidates_live_iterator(local_runtime, resident_files):
    ds = _make(resident_files, lookahead=1)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)
    ds.close()
    with pytest.raises(RuntimeError, match="closed"):
        # Drain: the lookahead may hold a couple of pre-dispatched
        # batches, but the next dispatch must fail fast.
        for _ in range(5):
            next(it)


def test_stats_accounting(local_runtime, resident_files):
    ds = _make(resident_files)
    # Features + label, 4 bytes per value, every real row staged once.
    assert ds.stats.bytes_staged == (len(FEATURES) + 1) * 4 * NUM_ROWS
    ds.set_epoch(0)
    n = sum(1 for _ in ds)
    assert ds.stats.batches_staged == n


def test_packed_nbytes_counts_sublane_padding():
    """Device residency is counted as the chip lays the buffer out: the
    column count rounded up to 8 sublanes (20 columns hold 24 rows' worth),
    so that what ``fits_device`` admits does not then exhaust HBM."""
    assert packed_nbytes(1000, 19) == 24 * 4 * 1000  # 20 columns -> 24
    assert packed_nbytes(1000, 7) == 8 * 4 * 1000  # exactly one tile
    assert packed_nbytes(1000, 8) == 16 * 4 * 1000


def test_range_decode(local_runtime, resident_files):
    """Row-group-granular range decode (pod staging's per-file slice):
    exact rows, within one group and across the group boundary."""
    from ray_shuffling_data_loader_tpu import runtime as rt
    from ray_shuffling_data_loader_tpu.resident import (
        _decode_narrow_range_to_store,
    )

    store = rt.get_context().store
    # resident_files[0] holds keys [0, ~2731) in 2 row groups.
    for lo, hi in ((100, 900), (1000, 2400)):
        ref = _decode_narrow_range_to_store(
            resident_files[0], ["key"], lo, hi
        )
        keys = np.asarray(store.get_columns(ref)["key"])
        assert np.array_equal(keys, np.arange(lo, hi))
        store.free([ref])
    with pytest.raises(ValueError, match="outside"):
        _decode_narrow_range_to_store(resident_files[0], ["key"], 10**9, 10**9 + 1)
    # Partially-overlapping ranges must raise too, never silently truncate.
    with pytest.raises(ValueError, match="outside"):
        _decode_narrow_range_to_store(resident_files[0], ["key"], 2000, 10**9)


def test_num_rows_hint(local_runtime, resident_files):
    ds = _make(resident_files, num_rows=NUM_ROWS)
    assert ds.num_rows == NUM_ROWS
    # A wrong hint must be rejected, not silently mis-index.
    with pytest.raises(ValueError, match="num_rows"):
        _make(resident_files, num_rows=NUM_ROWS - 1)


def test_fits_device_policy(local_runtime, resident_files, monkeypatch):
    assert dataset_num_rows(resident_files) == NUM_ROWS
    # Auto never picks resident on the CPU backend (the "device" is host
    # RAM — measured slower than the map/reduce path there) ...
    monkeypatch.delenv("RSDL_RESIDENT_BUDGET_GB", raising=False)
    assert fits_device(resident_files, len(FEATURES)) is False
    # ... unless the operator opts in with an explicit budget.
    monkeypatch.setenv("RSDL_RESIDENT_BUDGET_GB", "1")
    assert fits_device(resident_files, len(FEATURES)) is True
    # An explicit budget the dataset exceeds still says no.
    monkeypatch.setenv("RSDL_RESIDENT_BUDGET_GB", "1e-9")
    assert fits_device(resident_files, len(FEATURES)) is False


@pytest.mark.parametrize("materialize", [True, False])
def test_fused_epoch_matches_per_batch(
    local_runtime, resident_files, materialize
):
    """Epoch-fused training (one jitted lax.scan per epoch) must produce
    the same final state and per-batch losses as driving the identical
    step through the per-batch iterator — on both epoch schedules."""
    from ray_shuffling_data_loader_tpu.resident import make_fused_epoch

    def make_ds():
        return DeviceResidentShufflingDataset(
            list(resident_files),
            num_epochs=2,
            batch_size=1024,
            feature_columns=FEATURES,
            label_column=LABEL_COLUMN,
            seed=41,
            materialize_epoch=materialize,
        )

    def step_body(state, feats, label):
        def loss_fn(w):
            pred = w * feats["key"].astype(jnp.float32) / NUM_ROWS
            return jnp.mean((pred - label) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(state)
        return state - 0.05 * g, {"loss": loss}

    ds_f = make_ds()
    run = make_fused_epoch(ds_f, step_body, donate_state=False)
    state_f = jnp.float32(0.5)
    all_losses = []
    for epoch in range(2):
        state_f, losses = run(state_f, epoch)
        all_losses.append(np.asarray(losses))
    ds_f.close()

    ds_p = make_ds()
    step = jax.jit(step_body)
    state_p = jnp.float32(0.5)
    ref_losses = []
    for epoch in range(2):
        ds_p.set_epoch(epoch)
        ep = []
        for feats, label in ds_p:
            state_p, metrics = step(state_p, feats, label)
            ep.append(float(metrics["loss"]))
        ref_losses.append(np.asarray(ep, np.float32))
    ds_p.close()

    for got, want in zip(all_losses, ref_losses):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        float(state_f), float(state_p), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("materialize", [True, False])
def test_fused_epoch_multi_device_matches(
    local_runtime, resident_files, materialize
):
    """The multi-device fused path (scan over the pre-sharded epoch
    tensor — no per-step data collectives) must match the per-batch
    iterator bit-for-bit on an 8-device mesh, on both epoch schedules
    (VERDICT r3 item 3: fusion may not be single-device-only)."""
    from jax.sharding import Mesh

    from ray_shuffling_data_loader_tpu.resident import make_fused_epoch

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    assert mesh.devices.size > 1, "conftest provides 8 virtual devices"

    def make_ds():
        return DeviceResidentShufflingDataset(
            list(resident_files),
            num_epochs=2,
            batch_size=1024,
            feature_columns=FEATURES,
            label_column=LABEL_COLUMN,
            seed=43,
            mesh=mesh,
            materialize_epoch=materialize,
        )

    def step_body(state, feats, label):
        def loss_fn(w):
            pred = w * feats["key"].astype(jnp.float32) / NUM_ROWS
            return jnp.mean((pred - label) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(state)
        return state - 0.05 * g, {"loss": loss}

    ds_f = make_ds()
    run = make_fused_epoch(ds_f, step_body, donate_state=False)
    state_f = jnp.float32(0.5)
    all_losses = []
    for epoch in range(2):
        state_f, losses = run(state_f, epoch)
        all_losses.append(np.asarray(losses))
    ds_f.close()

    ds_p = make_ds()
    step = jax.jit(step_body)
    state_p = jnp.float32(0.5)
    ref_losses = []
    for epoch in range(2):
        ds_p.set_epoch(epoch)
        ep = []
        for feats, label in ds_p:
            state_p, metrics = step(state_p, feats, label)
            ep.append(float(metrics["loss"]))
        ref_losses.append(np.asarray(ep, np.float32))
    ds_p.close()

    for got, want in zip(all_losses, ref_losses):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        float(state_f), float(state_p), rtol=1e-5, atol=1e-6
    )
