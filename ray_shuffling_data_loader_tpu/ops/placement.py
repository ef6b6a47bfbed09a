"""Where a Pallas kernel runs: which backend takes it, and how it is
split over a device mesh.

A ``pallas_call`` is opaque to the SPMD partitioner: left bare in a
multi-device ``jit`` its operands are gathered and every device computes
the whole batch. ``jax.experimental.custom_partitioning`` is no way out on
a TPU — libtpu 0.0.34 exposes no PJRT custom-partitioner extension, so the
partition callback is never registered and compilation ends in ``Custom
emitter for CustomSPMDPartitioning not found``. The kernels are therefore
split with ``jax.shard_map`` over the mesh the caller has put in context:
:func:`traced_in_mesh` does it for a function about to be jitted
(:func:`~..parallel.train.make_train_step`, ``init_state`` and
:func:`~..resident.make_fused_epoch` use it), ``jax.set_mesh`` for a
whole block of code.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

# Axis names of the meshes ``parallel/mesh.py`` builds: the batch dimension
# is split over ``data``, vocabularies and attention heads over ``model``.
DATA_AXIS = "data"
MODEL_AXIS = "model"

Dims = Tuple[Optional[str], ...]


def auto_pallas() -> bool:
    """Auto policy: the kernels on a TPU backend, the XLA reference on
    every other backend, which Mosaic cannot target."""
    return jax.default_backend() == "tpu"


def traced_in_mesh(mesh: Mesh, fn: Callable) -> Callable:
    """``fn`` with ``mesh`` in context while it is traced, for ``jax.jit``
    to wrap: :func:`over_mesh` reads it from there."""

    @functools.wraps(fn)
    def wrapped(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return wrapped


def over_mesh(
    fn: Callable, in_dims: Sequence[Dims], out_dims: Sequence[Dims]
) -> Callable:
    """``fn`` run per device under ``shard_map`` over the context mesh.

    ``in_dims``/``out_dims`` name, per argument and per result, the mesh
    axis each dimension may be split over (``None`` = never split). An
    axis the context mesh lacks or already runs manually (the call sits
    inside a ``shard_map`` body) is dropped. A single result takes a
    one-element ``out_dims``."""
    mesh = jax.sharding.get_abstract_mesh()
    free = [n for n in mesh.axis_names if n not in mesh.manual_axes]
    if math.prod(mesh.shape[n] for n in free) == 1:
        return fn  # no mesh, one device, or already inside a shard_map

    def spec(dims: Dims) -> P:
        return P(*(d if d in free else None for d in dims))

    out_specs = tuple(spec(d) for d in out_dims)
    return jax.shard_map(
        fn,
        in_specs=tuple(spec(d) for d in in_dims),
        out_specs=out_specs[0] if len(out_specs) == 1 else out_specs,
        # Every free axis goes manual, the ones no dimension is split over
        # too: Pallas refuses to lower a Mosaic call that any axis is left
        # to the partitioner for.
        axis_names=frozenset(free),
        check_vma=False,
    )
