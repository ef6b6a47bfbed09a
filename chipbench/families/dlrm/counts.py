"""What the DLRM needs, from the configuration's sizes alone.

Operations, bytes and resident state are functions of the widths in a
configuration file; nothing here looks at the program. A per-layer metric
divides these by a time from the trace and by a peak from ``peaks.json``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence


def model_columns(cfg: dict) -> List[str]:
    """The columns the model embeds, in the order it stacks them: every
    column of the schema but the label, sorted by name (``models/dlrm.py``
    iterates ``sorted(vocab_sizes)``)."""
    return sorted(c for c in cfg["data_spec"] if c != cfg["label_column"])


def vocab_sizes(cfg: dict) -> Dict[str, int]:
    """Rows of each table: the column's range, under the configuration's
    ``vocab_cap`` where it states one (the rehearsal sizes do)."""
    cap = int(cfg.get("vocab_cap", 0))
    return {
        c: min(int(cfg["data_spec"][c][1]), cap) if cap
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


def num_parameters(cfg: dict) -> int:
    d = int(cfg["model"]["embed_dim"])
    tables = sum(vocab_sizes(cfg).values()) * d
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
