"""A column of more than one number a row (ISSUE 28): a ``fixed_size_list``
column of a 32-bit type goes from Parquet to the device as ``[batch,
width]`` through map, reduce, the packed body, the device-direct stager and
``unpack``, under the guarantees every column has; ``label_column=None`` is
a batch of features only. CPU only, toy sizes."""

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import datagen  # noqa: E402
from ray_shuffling_data_loader_tpu import ShufflingDataset, runtime  # noqa: E402
from ray_shuffling_data_loader_tpu.runtime import store as st  # noqa: E402

sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")

SPEC = {
    "tokens": [0, 1000, "int32", 16],
    "weight": [0, 1, "float64", 3],
    "flag": [0, 3, "int64"],
}
ROWS, FILES, BATCH = 256, 4, 8


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("wide"))
    names = [
        datagen.write_file(SPEC, i, (ROWS // FILES) * i, ROWS // FILES, 2, d, 2**31 + 5)[0]
        for i in range(FILES)
    ]
    return names, datagen.read_truth(names)


@pytest.fixture(scope="module")
def session():
    runtime.init(num_workers=2)
    yield
    runtime.shutdown()


def test_a_fixed_size_list_column_decodes_to_rows_by_width(wide):
    names, truth = wide
    cols = sh.read_parquet_columns(names[1]).columns
    assert cols["tokens"].shape == (64, 16) and cols["tokens"].dtype == np.int32
    assert cols["weight"].shape == (64, 3) and cols["weight"].dtype == np.float64
    assert cols["tokens"].flags.c_contiguous
    assert np.array_equal(cols["tokens"], truth["tokens"][64:128])
    # A projection, a row-group selection, the column-striped decode and an
    # empty selection give the same rows, or none of the same shape.
    part = sh.read_parquet_columns(
        names[1], columns=["tokens", "key"], row_groups=[1], rowgroup_threads=2
    ).columns
    assert np.array_equal(part["tokens"], truth["tokens"][96:128])
    none = sh.read_parquet_columns(names[1], columns=["tokens"], row_groups=[]).columns
    assert none["tokens"].shape == (0, 16) and none["tokens"].dtype == np.int32
    narrowed = sh._narrow_column("weight", cols["weight"])
    assert narrowed.shape == (64, 3) and narrowed.dtype == np.float32


def test_a_packed_body_holds_a_wide_column_s_rows_whole():
    layout = {"kind": st.DEVICE_BATCH_KIND, "batch": 4,
              "columns": ["tokens", "key", "w"], "dtypes": ["<i4", "<i4", "<f4"],
              "widths": [3, 1, 2]}
    assert st.packed_widths(layout) == [3, 1, 2]
    assert st.packed_slots(layout) == [(0, 3), (3, 1), (4, 2)]
    assert st.packed_widths({"columns": ["a", "b"]}) == [1, 1]
    mat = np.zeros((2, 6, 4), np.int32)
    tokens = np.arange(24, dtype=np.int32).reshape(8, 3)
    w = np.linspace(0, 1, 16, dtype=np.float32).reshape(8, 2)
    for b in range(2):
        st.packed_column_view(mat[b], 0, 3, np.int32)[...] = tokens[4 * b : 4 * b + 4]
        st.packed_column_view(mat[b], 3, 1, np.int32)[...] = np.arange(4) + 4 * b
        st.packed_column_view(mat[b], 4, 2, np.float32)[...] = w[4 * b : 4 * b + 4]
    # A wide column's slab is its rows one after the other.
    assert mat[0, 0:3].reshape(-1).tolist() == list(range(12))
    cb = st.ColumnBatch({st.PACKED_COLUMN: mat}, layout=layout)
    batches = list(st.iter_packed_batches(cb))
    assert np.array_equal(batches[1]["tokens"], tokens[4:]) and batches[1]["w"].shape == (4, 2)
    assert batches[1]["key"].tolist() == [4, 5, 6, 7]
    assert batches[0].packed.shape == (6, 4)
    logical = st.logical_columns(cb)
    assert np.array_equal(logical["tokens"], tokens)
    assert np.array_equal(logical["w"], w) and logical["key"].tolist() == list(range(8))


@pytest.mark.parametrize("overlap", ["off", "on"])
def test_the_reducer_packs_a_wide_column_by_gather_and_by_scatter(
    wide, session, overlap, monkeypatch
):
    """The fused gather and the overlapped scatter write the same packed
    body, and both deliver every row intact, exactly once."""
    monkeypatch.setenv("RSDL_REDUCE_FETCH_OVERLAP", overlap)
    names, truth = wide
    ds = ShufflingDataset(
        names, num_epochs=1, num_trainers=1, batch_size=BATCH, rank=0,
        num_reducers=2, narrow_to_32=True, queue_name=f"wide-{overlap}",
        device_layout={"batch": BATCH, "columns": ["tokens", "key"]},
    )
    ds.set_epoch(0)
    keys, packed = [], 0
    for batch in ds:
        k = np.asarray(batch["key"])
        keys.append(k)
        assert batch["tokens"].shape == (BATCH, 16)
        assert np.array_equal(batch["tokens"], truth["tokens"][k])
        assert np.array_equal(batch["weight"], truth["weight"][k])
        if batch.packed is not None:
            packed += 1
            assert batch.layout["widths"][:2] == [16, 1]
            assert batch.packed.shape[1] == BATCH
    assert np.array_equal(np.sort(np.concatenate(keys)), np.arange(ROWS))
    assert packed >= ROWS // BATCH - 4


@pytest.mark.parametrize("label", [None, "flag"])
def test_a_wide_column_reaches_the_device_as_batch_by_width(wide, session, label):
    import jax

    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.parallel import make_mesh

    names, truth = wide
    ds = JaxShufflingDataset(
        names, num_epochs=2, num_trainers=1, batch_size=BATCH, rank=0,
        feature_columns=["tokens", "weight", "key"], label_column=label,
        num_reducers=2, mesh=make_mesh(devices=jax.devices()[:1]),
        queue_name=f"wide-jax-{label}",
    )
    orders = []
    for epoch in range(2):
        ds.set_epoch(epoch)
        keys = []
        for features, got_label in ds:
            k = np.asarray(features["key"])
            keys.append(k)
            assert features["tokens"].shape == (BATCH, 16)
            assert features["tokens"].dtype == np.int32
            assert features["weight"].dtype == np.float32
            assert np.array_equal(np.asarray(features["tokens"]), truth["tokens"][k])
            assert np.array_equal(np.asarray(features["weight"]), truth["weight"][k])
            if label is None:
                assert got_label is None
            else:
                assert np.array_equal(np.asarray(got_label), truth["flag"][k])
        orders.append(np.concatenate(keys))
        assert np.array_equal(np.sort(orders[-1]), np.arange(ROWS))
    assert not np.array_equal(*orders)
    stats = ds.stats.as_dict()
    assert stats["batches_staged"] == 2 * ROWS // BATCH
    # All but the batches that straddle two reducers went straight off the
    # packed segments, the wide columns with them.
    assert stats["batches_staged_direct"] >= stats["batches_staged"] - 4
    assert stats["bytes_staged_direct"] == stats["batches_staged_direct"] * BATCH * 4 * (
        16 + 3 + 1 + (label is not None)
    )


def test_device_direct_off_delivers_the_same_tensors(wide, session, monkeypatch):
    import jax

    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.parallel import make_mesh

    names, _ = wide

    def epoch(queue):
        ds = JaxShufflingDataset(
            names, num_epochs=1, num_trainers=1, batch_size=BATCH, rank=0,
            feature_columns=["tokens", "key"], label_column=None,
            num_reducers=2, seed=11, mesh=make_mesh(devices=jax.devices()[:1]),
            queue_name=queue,
        )
        ds.set_epoch(0)
        out = [np.asarray(f["tokens"]) for f, _ in ds]
        return np.concatenate(out), ds.stats.as_dict()

    direct, stats = epoch("wide-direct")
    assert stats["batches_staged_direct"] > 0
    monkeypatch.setenv("RSDL_DEVICE_DIRECT", "off")
    copied, stats = epoch("wide-copied")
    assert stats["batches_staged_direct"] == 0
    assert np.array_equal(direct, copied)


def test_the_resident_loader_refuses_a_wide_column(wide, session):
    from ray_shuffling_data_loader_tpu.resident import (
        DeviceResidentShufflingDataset,
    )

    names, _ = wide
    with pytest.raises(ValueError, match="tokens: fixed_size_list"):
        DeviceResidentShufflingDataset(
            names, num_epochs=1, batch_size=BATCH,
            feature_columns=["tokens", "key"], label_column="flag",
        )


def test_a_scalar_stream_compiles_one_unpack_for_both_staging_paths(tmp_path, session):
    """The batches that straddle two reducers take the host-packed path, the
    rest the direct one: the same jitted unpack serves both, so nothing
    compiles when the first straddling batch comes (chip run of PR 28: two
    compilations inside ``stream-train``'s window while they did not)."""
    import jax

    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.parallel import make_mesh

    spec = {"a": [0, 50, "int64"], "b": [0, 1, "float64"]}
    names = [
        datagen.write_file(spec, i, 100 * i, 100, 2, str(tmp_path), 3)[0]
        for i in range(2)
    ]
    ds = JaxShufflingDataset(
        names, num_epochs=1, num_trainers=1, batch_size=8, rank=0,
        feature_columns=["a", "key"], label_column="b", num_reducers=3,
        mesh=make_mesh(devices=jax.devices()[:1]), queue_name="one-unpack",
    )
    ds.set_epoch(0)
    assert sum(1 for _ in ds) == 25
    stats = ds.stats.as_dict()
    assert 0 < stats["batches_staged_direct"] < stats["batches_staged"]
    assert len(ds._unpack_cache) == 1
