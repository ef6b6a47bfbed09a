"""The comparison that decides ``correct``.

Two layers, as the cells' ``why`` names them. Delivery is exact: what the
loader handed to the timed loop against the benchmark's files and the
guarantees the configuration states (limit 0 on every count). The train step
is compared with the plain reference over its first steps by three numbers,
each with a limit of its own from the configuration's ``limits``; two more
are printed beside them (``PRINTED``).
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

SKETCH_WIDTH = 256


def sketch(x):
    """A leaf folded to ``SKETCH_WIDTH`` numbers: each element under a
    pseudo-random sign (a hash of its flat index), summed by flat index
    modulo the width. Whatever the errors' pattern, the norm of the
    difference of two leaves' sketches estimates the norm of the leaves'
    difference (to about 1/sqrt(2 x width)), without either side keeping
    the other's tensor."""
    flat = x.reshape(-1).astype(jnp.float32)
    h = jax.lax.iota(jnp.uint32, flat.size)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    flat = jnp.where((h ^ (h >> 16)) & 1, -flat, flat)
    flat = jnp.pad(flat, (0, -flat.size % SKETCH_WIDTH))
    return flat.reshape(-1, SKETCH_WIDTH).sum(axis=0)


def sketches(tree) -> Dict[str, jax.Array]:
    return {k: sketch(v) for k, v in tree.items()}


def norms(tree) -> Dict[str, jax.Array]:
    """The Euclidean norm of every leaf of a flat ``{leaf: array}``."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


# Numbers of ``training_numbers`` that a run prints and does not compare.
PRINTED = ("loss_gap", "grad_norm_gap")


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref``: ``{"loss": [..], "grad_norm": {leaf: ..},
    "grad_sketch": {leaf: [..]}, "change_norm": {leaf: ..}}``.

    * ``grad_diff``: the median leaf's norm of the difference between the
      two first gradients, estimated from their sketches
      (``sketch``), as a share of the reference's norm of that
      leaf or of its median leaf, whichever is larger. The number that
      tells one compute precision from the next (PERF.md section 2).
    * ``grad_norm_mid_gap``: the median leaf's gap between the two norms
      of the first gradient (not the norm of a difference), over the same
      denominator: some gradients are all but zero.
    * ``grad_norm_gap``: the same of the worst leaf. Printed, not compared:
      a leaf of one number (a logit's bias) is a mean over the batch that
      all but cancels on some seeds, and the compute type's rounding then
      reads as a large share of it (PERF.md section 2).
    * ``change_norm_gap``: the same of the parameters' change over the
      steps followed, leaving out leaves whose reference gradient is under
      a thousandth of the median leaf's (they move by round-off alone).
    * ``loss_gap``: the worst step's gap between the losses, as a share of
      the reference's loss. Printed, not compared: no fault read far
      enough above sound runs to give it a limit (PERF.md section 2).
    """
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])
    )
    if len(prog["loss"]) != len(ref["loss"]):
        loss_gap = float("inf")

    def over(name: str, k: str) -> float:
        return max(ref[name][k], statistics.median(ref[name].values()))

    def gaps(name: str, leaves: Sequence[str]) -> List[float]:
        return [
            abs(prog[name][k] - ref[name][k]) / over(name, k) for k in leaves
        ]

    def worst(name: str, leaves: Sequence[str]) -> float:
        return max(gaps(name, leaves))

    grads = ref["grad_norm"]
    floor = 1e-3 * statistics.median(grads.values())
    moving = [k for k in grads if grads[k] >= floor]
    sketch = lambda side, k: np.asarray(side["grad_sketch"][k])  # noqa: E731
    numbers = {
        "grad_diff": statistics.median(
            float(np.linalg.norm(sketch(prog, k) - sketch(ref, k)))
            / over("grad_norm", k)
            for k in grads
        ),
        "grad_norm_mid_gap": statistics.median(gaps("grad_norm", grads)),
        "grad_norm_gap": worst("grad_norm", list(grads)),
        "change_norm_gap": worst("change_norm", moving),
        "loss_gap": loss_gap,
    }
    return {
        k: (float(v) if np.isfinite(v) else float("inf"))
        for k, v in numbers.items()
    }


def delivery_numbers(
    num_rows: int,
    batch_size: int,
    epochs: List[List[np.ndarray]],
    whole: List[bool],
    samples: List[dict],
    truth: Dict[str, np.ndarray],
    key_column: str,
) -> Dict[str, int]:
    """``epochs``: for each epoch the loop entered, the key column of every
    batch it was handed, in order; ``whole[i]`` says whether the loop took
    that epoch to its end. ``samples``: delivered batches as
    ``{column: numpy}`` (features and label together).

    * ``keys_off``: keys missing from or repeated in a whole epoch, plus
      keys repeated or out of range in a part of one.
    * ``rows_altered``: sampled rows in which any column differs from the
      file's row of that key, in any element where a column holds more
      than one number a row.
    * ``epochs_in_same_order``: pairs of successive whole epochs that came
      in the same order.
    * ``batches_short``: batches of another size than the configuration's.
    """
    keys_off = 0
    short = 0
    for batches, is_whole in zip(epochs, whole):
        short += sum(len(b) != batch_size for b in batches)
        if not batches:
            continue
        keys = np.concatenate(batches).astype(np.int64)
        in_range = (keys >= 0) & (keys < num_rows)
        keys_off += int((~in_range).sum())
        counts = np.bincount(keys[in_range], minlength=num_rows)
        keys_off += int(np.maximum(counts - 1, 0).sum())
        if is_whole:
            # drop_last: the rows past the last full batch are not due.
            due = (num_rows // batch_size) * batch_size
            keys_off += max(0, due - int((counts > 0).sum()))
    same = 0
    full = [np.concatenate(b) for b, w in zip(epochs, whole) if w and b]
    for a, b in zip(full, full[1:]):
        same += int(len(a) == len(b) and np.array_equal(a, b))
    altered = 0
    for batch in samples:
        keys = np.asarray(batch[key_column]).astype(np.int64)
        ok = (keys >= 0) & (keys < num_rows)
        bad = ~ok
        safe = np.where(ok, keys, 0)
        for col, got in batch.items():
            differs = np.asarray(got) != truth[col][safe]
            bad |= differs.reshape(len(keys), -1).any(axis=1)
        altered += int(bad.sum())
    return {
        "keys_off": keys_off,
        "rows_altered": altered,
        "epochs_in_same_order": same,
        "batches_short": short,
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, compared)``: each number beside its limit. A number
    with no limit is a fault of the configuration, not a pass."""
    compared = {}
    correct = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the configuration")
        limit = limits[name]
        ok = bool(value <= limit)
        compared[name] = {"value": value, "limit": limit, "ok": ok}
        correct = correct and ok
    return correct, compared
