"""The embedding lookup alone (ISSUE 26): the lane-filled view reads the
rows ``jnp.take`` reads, bit for bit, and adds the same gradient rows; the
width (and the mesh) choose the path. All on the CPU: what the view is
worth on the chip is PERF.md's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
from ray_shuffling_data_loader_tpu.ops import (
    embedding_lookup,
    lookup_pack,
    packed_tables,
)
from ray_shuffling_data_loader_tpu.ops.placement import traced_in_mesh
from ray_shuffling_data_loader_tpu.parallel import make_mesh

N_IDS = 4096


def _case(vocab, embed_dim, seed=0):
    """A table, ids that run past the vocabulary (the hashing) and, for
    the small vocabularies, repeat heavily, and a cotangent."""
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((vocab, embed_dim), dtype=np.float32)
    ids = rng.integers(0, 5 * vocab, N_IDS, dtype=np.int32)
    weight = rng.standard_normal((N_IDS, embed_dim), dtype=np.float32)
    return jnp.asarray(table), jnp.asarray(ids), jnp.asarray(weight)


def _plain(table, ids):
    return jnp.take(table, ids % table.shape[0], axis=0)


@pytest.mark.parametrize("vocab", [1031, 1024, 3, 1])
@pytest.mark.parametrize("embed_dim", [32, 16])
def test_view_reads_and_adds_what_take_does(vocab, embed_dim):
    table, ids, weight = _case(vocab, embed_dim)
    assert lookup_pack(vocab, embed_dim) == 128 // embed_dim
    got = jax.jit(embedding_lookup)(table, ids)
    want = _plain(table, ids)
    assert got.dtype == want.dtype and got.shape == want.shape
    # Bit for bit: the view moves values, it computes nothing with them.
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32)
    )

    def grad_of(lookup):
        return jax.jit(
            jax.grad(lambda t: jnp.sum(lookup(t, ids) * weight))
        )(table)

    g, g0 = np.asarray(grad_of(embedding_lookup)), np.asarray(grad_of(_plain))
    assert g.shape == table.shape
    # The same float32 values into the same rows; only the order of the
    # additions may differ.
    np.testing.assert_allclose(
        g, g0, rtol=1e-5, atol=1e-5 * float(np.abs(g0).max())
    )
    # Rows no id named stay exactly zero (the view's padding too).
    hit = np.zeros(vocab, bool)
    hit[np.asarray(ids) % vocab] = True
    assert not g[~hit].any()


def test_view_keeps_the_sign_of_a_zero():
    table = jnp.asarray(np.full((8, 32), -0.0, np.float32))
    got = np.asarray(embedding_lookup(table, jnp.arange(8)))
    assert np.signbit(got).all()


@pytest.mark.parametrize("embed_dim", [128, 48, 256])
def test_other_widths_take_the_plain_path(embed_dim):
    table, ids, _ = _case(1031, embed_dim)
    assert lookup_pack(1031, embed_dim) == 1
    jaxpr = jax.make_jaxpr(embedding_lookup)(table, ids).jaxpr
    # The table goes into the gather as it is: never reshaped or padded.
    users = [e for e in jaxpr.eqns if jaxpr.invars[0] in e.invars]
    assert [e.params.get("name") for e in users] == ["_take"]
    assert not {"reshape", "pad"} & {e.primitive.name for e in jaxpr.eqns}
    np.testing.assert_array_equal(
        np.asarray(embedding_lookup(table, ids)), np.asarray(_plain(table, ids))
    )


@pytest.mark.parametrize("vocab", [1031, 3])
def test_bfloat16_compute_downstream(vocab):
    """As the models use it: the rows are cast to the compute dtype and
    the cotangent comes back through that cast."""
    table, ids, weight = _case(vocab, 32, seed=1)

    def loss(lookup, t):
        rows = lookup(t, ids).astype(jnp.bfloat16)
        return jnp.sum((rows * weight.astype(jnp.bfloat16)).astype(jnp.float32))

    (l, g), (l0, g0) = (
        jax.jit(jax.value_and_grad(lambda t, f=f: loss(f, t)))(table)
        for f in (embedding_lookup, _plain)
    )
    assert g.dtype == jnp.float32
    np.testing.assert_allclose(float(l), float(l0), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g0),
        rtol=1e-5, atol=1e-5 * float(jnp.abs(g0).max()),
    )


@pytest.mark.parametrize(
    "vocab, shards, pack",
    [
        (1024, 2, 4),  # 512 rows a device: 128 whole rows of the view
        (1004, 2, 1),  # 502 rows a device: a view row would straddle the cut
        (1031, 2, 1),  # not split evenly at all
        (1031, 1, 4),  # one device: padded up to 1032
    ],
)
def test_a_split_vocabulary_keeps_the_view_only_in_whole_rows(
    vocab, shards, pack
):
    assert lookup_pack(vocab, 32, shards) == pack
    # The lookup reads the same answer from the mesh it is traced under.
    mesh = make_mesh(model_parallelism=shards)
    table, ids, _ = _case(vocab, 32)
    jaxpr = str(
        jax.make_jaxpr(traced_in_mesh(mesh, embedding_lookup))(table, ids)
    )
    assert (f"f32[{-(-vocab // 4)},128]" in jaxpr) == (pack == 4)


def test_engage_count_is_what_the_shapes_say():
    sizes = dlrm_for_data_spec().vocab_sizes
    count, pack = packed_tables(sizes, 32)
    assert count >= 3 and pack == 4
    assert packed_tables(sizes, 128) == (0, 1)
    assert packed_tables(sizes, 48) == (0, 1)
    assert packed_tables(sizes, 16) == (len(sizes), 8)
    # On a mesh that splits vocabularies two ways the odd ones drop out.
    split = make_mesh(model_parallelism=2)
    even = sum(v % 8 == 0 for v in sizes.values())
    assert packed_tables(sizes, 32, split) == (even, 4 if even else 1)
    assert packed_tables(sizes, 32, make_mesh(model_parallelism=1)) == (
        count, 4
    )
