"""What the algorithm needs, from the configuration's sizes alone.

Operations, bytes and resident state are functions of the widths in a
configuration file; nothing here looks at the program. A per-layer metric
divides these by a time from the trace and by a peak from ``peaks.json``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))


def model_columns(cfg: dict) -> List[str]:
    """The columns the model embeds, in the order it stacks them: every
    column of the schema but the label, sorted by name (``models/dlrm.py``
    iterates ``sorted(vocab_sizes)``)."""
    return sorted(c for c in cfg["data_spec"] if c != cfg["label_column"])


def vocab_sizes(cfg: dict, vocab_cap: int = 0) -> Dict[str, int]:
    return {
        c: min(int(cfg["data_spec"][c][1]), vocab_cap)
        if vocab_cap
        else int(cfg["data_spec"][c][1])
        for c in model_columns(cfg)
    }


def mlp_shapes(cfg: dict) -> List[Sequence[int]]:
    """``(fan_in, fan_out)`` of every dense layer, the 1-wide logit layer
    last. The first layer reads the flattened embeddings and the pairwise
    interactions side by side."""
    n = len(model_columns(cfg))
    d = int(cfg["model"]["embed_dim"])
    widths = [n * d + n * (n - 1) // 2, *cfg["model"]["top_mlp"], 1]
    return list(zip(widths[:-1], widths[1:]))


def num_parameters(cfg: dict, vocab_cap: int = 0) -> int:
    d = int(cfg["model"]["embed_dim"])
    tables = sum(vocab_sizes(cfg, vocab_cap).values()) * d
    dense = sum(i * o + o for i, o in mlp_shapes(cfg))
    return tables + dense


def state_bytes(cfg: dict) -> int:
    """Parameters and Adam's two moments, float32: what stays on the
    device all run."""
    return 3 * 4 * num_parameters(cfg)


def flops_per_row(cfg: dict) -> int:
    """Forward and backward, no recomputation: 2 FLOPs a multiply-add,
    three matmul-sized passes (forward, gradient to the input, gradient to
    the weight) over the MLP weights and over the n x n x d Gram of the
    interaction. Embedding lookups, the optimizer and elementwise work are
    not counted."""
    n = len(model_columns(cfg))
    d = int(cfg["model"]["embed_dim"])
    mlp = sum(i * o for i, o in mlp_shapes(cfg))
    return 3 * 2 * mlp + 3 * 2 * n * n * d


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


def interaction_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward pairwise interaction over ``rows`` rows, whatever
    implements it: n(n-1)/2 dot products of length d a row, the stacked
    embeddings read and the pairs written in the compute type (2 bytes)."""
    n = len(model_columns(cfg))
    d = int(cfg["model"]["embed_dim"])
    pairs = n * (n - 1) // 2
    return {
        "flops": rows * pairs * d * 2,
        "bytes": rows * n * d * 2 + rows * pairs * 2,
    }


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
