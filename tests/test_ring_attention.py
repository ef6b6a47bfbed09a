"""Ring attention vs dense reference on the 8-virtual-device mesh:
forward (causal and not), gradients, bf16, and sharding of the output.

No reference-repo analog (the reference has no attention, SURVEY §5);
this pins the sequence-parallel op the model layer uses for long
contexts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.ops import (
    attention_reference,
    blockwise_attention,
    make_ring_attention,
    make_ulysses_attention,
)

B, T, H, D = 2, 64, 2, 8
SEQ_AXIS = "sp"


@pytest.fixture(scope="module")
def seq_mesh():
    return Mesh(np.array(jax.devices()), (SEQ_AXIS,))


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((B, T, H, D)).astype(np.float32), dtype=dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_reference(seq_mesh, causal):
    q, k, v = _qkv()
    ring = make_ring_attention(seq_mesh, SEQ_AXIS, causal=causal)
    got = ring(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    # Output stays sequence-sharded — no device gathered the full T.
    assert got.sharding.spec == (None, SEQ_AXIS, None, None)


def test_gradients_match_dense(seq_mesh):
    q, k, v = _qkv(seed=1)
    ring = make_ring_attention(seq_mesh, SEQ_AXIS, causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_gradients_match_dense_noncausal(seq_mesh):
    """The custom ring VJP's non-causal branch (no mask recompute)."""
    q, k, v = _qkv(seed=7)
    ring = make_ring_attention(seq_mesh, SEQ_AXIS, causal=False)
    g_ring = jax.grad(
        lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), (0, 1, 2)
    )(q, k, v)
    g_dense = jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(q, k, v) ** 2),
        (0, 1, 2),
    )(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_hops_match(seq_mesh, causal):
    """Ring with per-hop compute forced through the flash kernel
    (interpret on CPU): the kernel's emitted (m, l) statistics merge
    across hops exactly; forward and the custom-VJP gradients match the
    dense reference."""
    q, k, v = _qkv(seed=10)
    ring = make_ring_attention(
        seq_mesh, SEQ_AXIS, causal=causal, use_flash=True, interpret=True
    )
    got = ring(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    g_r = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), (0, 1, 2))(
        q, k, v
    )
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=causal) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gr, gd in zip(g_r, g_d):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_ulysses_flash_local_matches(seq_mesh):
    """Ulysses with the local body forced through the flash kernel
    (interpret mode on CPU) — the TPU lowering's exactness, fwd + grad."""
    rng = np.random.default_rng(8)
    shape = (1, 32, 8, 4)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        for _ in range(3)
    )
    fn = make_ulysses_attention(
        seq_mesh, SEQ_AXIS, causal=True, use_flash=True, interpret=True
    )
    got = fn(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    g_u = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), (0, 1, 2))(
        q, k, v
    )
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gu, gd in zip(g_u, g_d):
        np.testing.assert_allclose(
            np.asarray(gu), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_blockwise_gradients_match_dense():
    """blockwise_attention's custom VJP (chunk recompute) vs dense."""
    rng = np.random.default_rng(9)
    shape = (1, 56, 2, 8)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        for _ in range(3)
    )
    from ray_shuffling_data_loader_tpu.ops import blockwise_attention

    g_b = jax.grad(
        lambda q, k, v: jnp.sum(
            blockwise_attention(q, k, v, causal=True, kv_chunk=24) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gb, gd in zip(g_b, g_d):
        np.testing.assert_allclose(
            np.asarray(gb), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_bfloat16_inputs(seq_mesh):
    q, k, v = _qkv(seed=2, dtype=jnp.bfloat16)
    ring = make_ring_attention(seq_mesh, SEQ_AXIS)
    got = ring(q, k, v)
    assert got.dtype == jnp.bfloat16
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32),
        np.asarray(want, dtype=np.float32),
        rtol=5e-2,
        atol=5e-2,
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense_reference(seq_mesh, causal):
    """The all-to-all strategy: exact for any mask (full T per device),
    heads split across the axis (H=8 divides the 8-device mesh). The
    odd kv_chunk forces the blockwise path's ragged final chunk."""
    rng = np.random.default_rng(4)
    shape = (2, 64, 8, 4)  # heads divisible by the axis size
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        for _ in range(3)
    )
    fn = make_ulysses_attention(seq_mesh, SEQ_AXIS, causal=causal, kv_chunk=24)
    got = fn(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    assert got.sharding.spec == (None, SEQ_AXIS, None, None)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_chunk", [16, 24, 1024])
def test_blockwise_matches_dense(causal, kv_chunk):
    """Single-device KV-chunked attention (the Ulysses local compute):
    exact incl. ragged final chunk and chunk > T."""
    rng = np.random.default_rng(6)
    shape = (2, 56, 2, 8)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        for _ in range(3)
    )
    got = blockwise_attention(q, k, v, causal=causal, kv_chunk=kv_chunk)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_ulysses_gradients_match_dense(seq_mesh):
    rng = np.random.default_rng(5)
    shape = (1, 32, 8, 4)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        for _ in range(3)
    )
    fn = make_ulysses_attention(seq_mesh, SEQ_AXIS, causal=True)
    g_u = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), (0, 1, 2))(
        q, k, v
    )
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gu, gd in zip(g_u, g_d):
        np.testing.assert_allclose(
            np.asarray(gu), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_respects_presharded_inputs(seq_mesh):
    """Feeding already-sequence-sharded arrays works and keeps shards."""
    q, k, v = _qkv(seed=3)
    sh = NamedSharding(seq_mesh, P(None, SEQ_AXIS, None, None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    ring = make_ring_attention(seq_mesh, SEQ_AXIS, causal=True)
    got = ring(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
