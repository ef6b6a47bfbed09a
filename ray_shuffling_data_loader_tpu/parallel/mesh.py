"""Device mesh construction and sharding rules.

The reference's only parallelism is data parallelism via Horovod/NCCL
allreduce (SURVEY §2b: ``ray_torch_shuffle.py:188-193``). Here DP is
expressed the idiomatic TPU way — a named mesh axis — and composes with a
``model`` axis for sharding large embedding tables, so the same batch
delivery machinery serves data×model layouts (SURVEY §2b closing note).

Axes:
    ``data``  — batch dimension; gradient reduction rides ICI here.
    ``model`` — vocab dimension of large embedding tables.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.ops.placement import (  # noqa: F401
    DATA_AXIS,
    MODEL_AXIS,
)

# Embedding tables at least this tall get their vocab dim sharded across
# MODEL_AXIS; everything smaller replicates.
DEFAULT_VOCAB_SHARD_THRESHOLD = 16_384


def make_mesh(
    model_parallelism: int = 1,
    devices: Optional[list] = None,
) -> Mesh:
    """A 2-D ``(data, model)`` mesh over the given (default: all) devices.

    ``model_parallelism`` must divide the device count; the data axis takes
    the rest. ``model_parallelism=1`` degenerates to pure DP.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if n % model_parallelism != 0:
        raise ValueError(
            f"model_parallelism={model_parallelism} does not divide "
            f"device count {n}"
        )
    grid = np.asarray(devices).reshape(n // model_parallelism, model_parallelism)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def batch_spec(ndim: int) -> P:
    """Batch-axis-sharded PartitionSpec for an ``ndim``-dim array."""
    return P(DATA_AXIS, *([None] * (ndim - 1)))


def batch_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(ndim))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_spec(
    shape: Tuple[int, ...],
    mesh: Mesh,
    vocab_shard_threshold: int = DEFAULT_VOCAB_SHARD_THRESHOLD,
) -> P:
    """Sharding rule for one parameter array.

    2-D arrays with a tall leading (vocab) dimension that divides the model
    axis are sharded ``P('model', None)``; everything else replicates.
    Meshes without a model axis (e.g. the 1-D data mesh) replicate all.
    """
    model_size = dict(mesh.shape).get(MODEL_AXIS, 1)
    if (
        len(shape) == 2
        and shape[0] >= vocab_shard_threshold
        and shape[0] % model_size == 0
        and model_size > 1
    ):
        return P(MODEL_AXIS, None)
    return P()


def param_shardings(
    tree: Any,
    mesh: Mesh,
    vocab_shard_threshold: int = DEFAULT_VOCAB_SHARD_THRESHOLD,
):
    """Map a pytree of arrays (or ShapeDtypeStructs) to NamedShardings via
    :func:`param_spec`."""
    return jax.tree.map(
        lambda x: NamedSharding(
            mesh, param_spec(tuple(x.shape), mesh, vocab_shard_threshold)
        ),
        tree,
    )
