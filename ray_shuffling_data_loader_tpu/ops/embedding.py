"""Embedding lookup: a row gather whose physical row fills the chip's lanes.

A TPU holds a 2-D float32 array in tiles of 8 sublanes x 128 lanes. A
``[V, embed_dim]`` table narrower than 128 would be padded four times over
(``embed_dim`` 32) were its rows laid along the lanes, so the compiler
keeps a tall one the other way round (``{0,1:T(8,128)}``: the vocabulary
along the lanes). A table row is then a column of tiles, and a gather or
a scatter-add of rows runs one lane at a time: the three 0.8-0.9 M-row
tables of the shipped DLRM cost 31.8 ms a scatter-add at ``embed_dim`` 32
and 3.9 ms at 128, same ids, four times the bytes (PERF.md, PR 26).

:func:`embedding_lookup` therefore reads such a table through a
lane-filled view: ``pack = 128 // embed_dim`` logical rows side by side in
one 128-lane row, ``[ceil(V / pack), 128]`` row-major. It gathers row
``id // pack`` of the view and keeps the ``id % pack``-th ``embed_dim``
lanes of it. The transpose is left to ``jax.grad``: the cotangent row,
placed in its part of a zero 128-lane row, is scatter-added into a zero
view, which is viewed back as ``[V, embed_dim]``. Same values into the
same rows, in float32; the parameter keeps its shape.

The width decides, never a flag: ``pack`` is 1 where ``embed_dim`` does
not divide 128 or reaches it, and ``pack == 1`` is ``jnp.take`` on the
table as it is.
"""

from __future__ import annotations

from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_shuffling_data_loader_tpu.ops.placement import MODEL_AXIS

LANES = 128


def _vocab_shards(mesh) -> int:
    """Over how many devices ``mesh`` (abstract) may split a vocabulary:
    its ``model`` axis, unless the trace is already inside a ``shard_map``
    over it (tables are whole per device there)."""
    if MODEL_AXIS not in mesh.axis_names or MODEL_AXIS in mesh.manual_axes:
        return 1
    return mesh.shape[MODEL_AXIS]


def lookup_pack(vocab: int, embed_dim: int, vocab_shards: int = 1) -> int:
    """How many rows of a ``[vocab, embed_dim]`` table share one row of
    the view :func:`embedding_lookup` reads it through; 1 is the plain
    path.

    Where a mesh splits vocabularies ``vocab_shards`` ways
    (``parallel.mesh.param_spec``), the view is kept only if every
    device's share of the rows is whole rows of the view: merging rows
    across the cut, or padding the vocabulary, would have the partitioner
    gather the split table onto every device."""
    if embed_dim >= LANES or LANES % embed_dim:
        return 1
    pack = LANES // embed_dim
    if vocab_shards > 1 and vocab % (vocab_shards * pack):
        return 1
    return pack


def packed_tables(
    vocab_sizes: Mapping[str, int], embed_dim: int, mesh: Optional[Mesh] = None
) -> Tuple[int, int]:
    """``(tables read through the view, their pack)`` for a model's
    tables in a step traced under ``mesh``: what :func:`embedding_lookup`
    will choose there, table by table. ``pack`` is 1 where no table takes
    the view."""
    shards = _vocab_shards(mesh.abstract_mesh) if mesh is not None else 1
    packs = [lookup_pack(v, embed_dim, shards) for v in vocab_sizes.values()]
    viewed = [p for p in packs if p > 1]
    return len(viewed), (viewed[0] if viewed else 1)


def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Rows ``ids % V`` of ``table`` (``[V, embed_dim]``), as ``[B,
    embed_dim]``.

    The ids are folded into the table (the hashing trick; a no-op for ids
    in range): ``jnp.take`` fills a row out of range with NaN, which
    would poison the loss under a capped vocabulary."""
    vocab, embed_dim = table.shape
    pack = lookup_pack(
        vocab, embed_dim, _vocab_shards(jax.sharding.get_abstract_mesh())
    )
    with jax.named_scope("embedding"):
        ids = ids.reshape(-1) % vocab
        if pack == 1:
            return jnp.take(table, ids, axis=0)
        rows = -(-vocab // pack)
        view = jnp.pad(table, ((0, rows * pack - vocab), (0, 0)))
        view = view.reshape(rows, LANES)
        wide = jnp.take(view, ids // pack, axis=0)  # [B, 128]
        # The part of each wide row that is the row asked for: a select
        # per part, no second gather and no arithmetic on the values.
        part = (ids % pack)[:, None]
        out = wide[:, :embed_dim]
        for k in range(1, pack):
            out = jnp.where(
                part == k, wide[:, k * embed_dim:(k + 1) * embed_dim], out
            )
        return out
