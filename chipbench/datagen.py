"""The benchmark's own data generator.

A copy of the arithmetic of ``data_generation.generate_row_group`` /
``generate_file`` (upstream ``data_generation.py:30-93``), kept here so that
a later change to the program's generator cannot change the yardstick. The
schema comes from the configuration's file (``data_spec``), the values from
``--seed``: the same seed gives the same files, byte for byte.

A schema entry is ``[low, high, dtype]``, one number a row as upstream has
it, or ``[low, high, dtype, width]``: ``width`` numbers a row, written as one
Parquet column of ``fixed_size_list<dtype>[width]`` and read back as
``[rows, width]``. A sample's shape is data, like its ranges.

The files are the benchmark's inputs and its ground truth: ``read_truth``
reads them back, so that what the loaders delivered is compared with what
was put on disk, by key.
"""

from __future__ import annotations

import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

KEY_COLUMN = "key"


def _np_dtype(name: str):
    return {"int64": np.int64, "int32": np.int32, "float64": np.float64}[name]


def generate_row_group(
    data_spec: Dict[str, Sequence],
    group_index: int,
    global_row_index: int,
    num_rows_in_group: int,
    seed: int,
) -> Dict[str, np.ndarray]:
    """One row group as numpy columns; the key is the global row index."""
    rng = np.random.default_rng(
        np.random.SeedSequence(
            entropy=seed, spawn_key=(group_index, global_row_index)
        )
    )
    buffer = {
        KEY_COLUMN: np.arange(
            global_row_index,
            global_row_index + num_rows_in_group,
            dtype=np.int64,
        )
    }
    for col, (low, high, dtype, *width) in data_spec.items():
        dtype = _np_dtype(dtype)
        # Three elements: the upstream draw, call for call.
        shape = (num_rows_in_group, int(width[0])) if width else num_rows_in_group
        if np.issubdtype(dtype, np.integer):
            buffer[col] = rng.integers(low, high, shape, dtype=dtype)
        else:
            buffer[col] = (high - low) * rng.random(
                shape, dtype=np.float64
            ) + low
    return buffer


def _arrow_column(values: np.ndarray):
    """A ``[rows]`` array as a plain column, a ``[rows, width]`` array as a
    column of fixed-size lists."""
    import pyarrow as pa

    if values.ndim == 1:
        return pa.array(values)
    return pa.FixedSizeListArray.from_arrays(
        pa.array(values.reshape(-1)), values.shape[1]
    )


def write_file(
    data_spec: Dict[str, Sequence],
    file_index: int,
    global_row_index: int,
    num_rows_in_file: int,
    num_row_groups_per_file: int,
    data_dir: str,
    seed: int,
) -> Tuple[str, int]:
    """One snappy Parquet file of uniform row groups. Returns its name and
    its size on disk."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    group_size = max(1, num_rows_in_file // num_row_groups_per_file)
    groups = []
    for group_index, at in enumerate(range(0, num_rows_in_file, group_size)):
        groups.append(
            generate_row_group(
                data_spec,
                group_index,
                global_row_index + at,
                min(group_size, num_rows_in_file - at),
                seed,
            )
        )
    table = pa.table(
        {
            name: _arrow_column(np.concatenate([g[name] for g in groups]))
            for name in groups[0]
        }
    )
    filename = os.path.join(data_dir, f"input_data_{file_index}.parquet.snappy")
    pq.write_table(
        table, filename, compression="snappy", row_group_size=group_size
    )
    return filename, os.path.getsize(filename)


def generate(
    submit,
    data_spec: Dict[str, Sequence],
    num_rows: int,
    num_files: int,
    num_row_groups_per_file: int,
    data_dir: str,
    seed: int,
) -> Tuple[List[str], int]:
    """Write the data set, one task a file, through ``submit(fn, *args)``
    (a worker pool's). Returns the file names and the bytes on disk."""
    os.makedirs(data_dir, exist_ok=True)
    rows_per_file = max(1, num_rows // num_files)
    futures = [
        submit(
            write_file,
            data_spec,
            file_index,
            start,
            min(rows_per_file, num_rows - start),
            num_row_groups_per_file,
            data_dir,
            seed,
        )
        for file_index, start in enumerate(range(0, num_rows, rows_per_file))
    ]
    names, sizes = zip(*(f.result() for f in futures))
    return list(names), int(sum(sizes))


def narrowed(col: np.ndarray) -> np.ndarray:
    """A column in the 32-bit type the device gets (int64 -> int32, float64
    -> float32: the configuration's values all fit)."""
    narrow = np.int32 if np.issubdtype(col.dtype, np.integer) else np.float32
    return col.astype(narrow)


def _numpy_column(column) -> np.ndarray:
    """One file's column: ``[rows]``, or ``[rows, width]`` of a column of
    fixed-size lists."""
    import pyarrow as pa

    if pa.types.is_fixed_size_list(column.type):
        flat = column.combine_chunks().flatten().to_numpy(zero_copy_only=False)
        return flat.reshape(-1, column.type.list_size)
    return column.to_numpy(zero_copy_only=False)


def read_truth(filenames: Sequence[str]) -> Dict[str, np.ndarray]:
    """Every column of the data set as it lies on disk, in key order and
    narrowed to the 32-bit types the device gets."""
    import pyarrow.parquet as pq

    parts = [pq.read_table(f) for f in filenames]
    out = {}
    for name in parts[0].column_names:
        out[name] = narrowed(
            np.concatenate([_numpy_column(p.column(name)) for p in parts])
        )
    keys = out[KEY_COLUMN]
    if not np.array_equal(keys, np.arange(len(keys), dtype=np.int32)):
        raise AssertionError("the files' keys are not 0..n-1 in order")
    return out
