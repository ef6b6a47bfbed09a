"""What the loaders and the chip give, whatever model is trained.

The bytes of the resident loader's packed buffer and of its permutation,
from the configuration's sizes alone, and the chip's published peaks. What
belongs to a model (parameters, FLOPs a row, a kernel's operations and
bytes) is its family's: ``families/<family>/counts.py``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def packed_nbytes(num_rows: int, num_feature_columns: int) -> int:
    """The resident loader's packed ``[features + label, rows]`` int32
    buffer as the TPU holds it: the column count rounded up to 8
    sublanes."""
    sublanes = -(-(num_feature_columns + 1) // 8) * 8
    return sublanes * 4 * num_rows


def permute_bytes(cfg: dict) -> int:
    """Least traffic of one epoch's permutation: the packed buffer read
    once and its permuted copy written once."""
    ncols = len(cfg["data_spec"])  # model columns + key, label counted by +1
    return 2 * packed_nbytes(int(cfg["num_rows"]), ncols)


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in chipbench/peaks.json"
        )
    return table[device_kind]
