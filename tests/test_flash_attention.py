"""Pallas flash-attention kernel vs the dense reference, interpreter mode
(the compiled-on-TPU check lives in ``tests/test_ops_tpu.py``'s pattern;
CI has no TPU)."""

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from ray_shuffling_data_loader_tpu.ops import attention_reference
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
    flash_attention,
)


# (``ops.flash_attention`` is the function; this is its module.)
flash_module = importlib.import_module(
    "ray_shuffling_data_loader_tpu.ops.flash_attention"
)


def _qkv(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal(shape).astype(np.float32), dtype)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "shape,blocks",
    [
        ((2, 64, 2, 8), (16, 16)),  # multiple kv blocks per q block
        ((1, 56, 2, 8), (16, 24)),  # ragged: seq divides neither block
        ((2, 8, 1, 4), (128, 128)),  # seq smaller than the block
    ],
)
def test_matches_dense_reference(causal, shape, blocks):
    q, k, v = _qkv(shape, seed=1)
    got = flash_attention(
        q,
        k,
        v,
        causal=causal,
        use_pallas=True,
        block_q=blocks[0],
        block_k=blocks[1],
        interpret=True,
    )
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_bfloat16(seed=3):
    q, k, v = _qkv((2, 32, 2, 8), seed=seed, dtype=jnp.bfloat16)
    got = flash_attention(
        q, k, v, use_pallas=True, block_q=16, block_k=16, interpret=True
    )
    assert got.dtype == jnp.bfloat16
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32),
        np.asarray(want, dtype=np.float32),
        rtol=5e-2,
        atol=5e-2,
    )


def test_gradients_exact():
    """The custom VJP is the dense reference's gradient — exact."""
    q, k, v = _qkv((1, 32, 2, 8), seed=4)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, use_pallas=True,
                block_q=16, block_k=16, interpret=True,
            )
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_f = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_multi_chunk_ragged(causal):
    """Backward with several KV chunks and a ragged tail (T=300 over
    128-wide chunks) — the chunked-VJP path the single-chunk test
    misses."""
    q, k, v = _qkv((1, 300, 2, 8), seed=6)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=causal, use_pallas=True, interpret=True
            )
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_f = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-3, atol=1e-4
        )


def test_gradients_sharded_mesh():
    """Forward AND fused backward in a multi-device jit with the mesh in
    context: ``shard_map`` splits both pallas calls batch-wise on the
    8-device mesh (each device's kernel sees batch 1, not 8); gradients
    match the dense reference."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    q, k, v = _qkv((8, 64, 2, 8), seed=11)
    sh = NamedSharding(mesh, P("data", None, None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, use_pallas=True, interpret=True
            )
            ** 2
        )

    with jax.set_mesh(mesh):
        grad_fn = jax.jit(jax.grad(loss_flash, (0, 1, 2)))
        assert "f32[1,64,2,8]" in str(jax.make_jaxpr(grad_fn)(qs, ks, vs))
        g_f = grad_fn(qs, ks, vs)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


# -- the residuals' names ---------------------------------------------------------


def _kernel_loss(q, k, v):
    out = flash_attention(
        q, k, v, causal=True, use_pallas=True,
        block_q=16, block_k=16, interpret=True,
    )
    return jnp.sum(out.astype(jnp.float32) ** 2)


def _kernels(jaxpr):
    """How often each Pallas kernel is called in ``jaxpr`` and what it
    calls, by the kernel's name."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _kernels(sub)
    return found


def test_the_residuals_names_change_nothing_for_a_bare_caller(monkeypatch):
    """Outside a policy that lists them the names are identities: value and
    gradients are, bit for bit, those of a forward rule that names nothing
    (the parent's)."""
    q, k, v = _qkv((2, 48, 4, 8), seed=11, dtype=jnp.bfloat16)
    k, v = k[:, :, :2], v[:, :, :2]
    grad = jax.value_and_grad(_kernel_loss, (0, 1, 2))
    named = grad(q, k, v)

    def names():
        fwd = jax.make_jaxpr(flash_module._fwd, static_argnums=(3, 4, 5, 6))
        return [
            e.params["name"] for e in fwd(q, k, v, True, 16, 16, True).eqns
            if e.primitive.name == "name"
        ]

    assert names() == [ATTENTION_OUT, ATTENTION_STATS, ATTENTION_STATS]
    monkeypatch.setattr(flash_module, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()
    assert names() == []
    bare = grad(q, k, v)
    for got, want in zip(jax.tree.leaves(named), jax.tree.leaves(bare)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize(
    "policy,forwards",
    [
        # ``models/lm.py`` / ``models/transformer.py``: a plain checkpoint, or
        # a policy that lists neither name, recomputes the kernel as before.
        ("none", 2),
        ("dots", 2),
        # A caller that lists the names keeps what the backward reads.
        ("names", 1),
    ],
)
def test_only_a_policy_that_lists_the_names_keeps_the_forward_kernel_s_outputs(
    policy, forwards
):
    policies = jax.checkpoint_policies
    kw = {
        "none": {},
        "dots": {"policy": policies.dots_with_no_batch_dims_saveable},
        "names": {
            "policy": policies.save_only_these_names(ATTENTION_OUT, ATTENTION_STATS)
        },
    }[policy]
    q, k, v = _qkv((1, 32, 2, 8), seed=12)
    grad = jax.grad(jax.checkpoint(_kernel_loss, **kw), (0, 1, 2))
    assert _kernels(jax.make_jaxpr(grad)(q, k, v).jaxpr) == {
        "flash_attention_fwd": forwards,
        "flash_attention_bwd_dkv": 1,
        "flash_attention_bwd_dq": 1,
    }
    for got, want in zip(grad(q, k, v), jax.grad(_kernel_loss, (0, 1, 2))(q, k, v)):
        assert np.array_equal(got, want)


def test_flash_backward_xla_escape_hatch(monkeypatch):
    """RSDL_FLASH_BWD=xla routes the VJP through the chunked-XLA
    backward; gradients stay exact."""
    monkeypatch.setenv("RSDL_FLASH_BWD", "xla")
    q, k, v = _qkv((1, 48, 2, 8), seed=12)
    g_f = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(
                q, k, v, causal=True, use_pallas=True, interpret=True,
                block_q=16, block_k=16,
            )
            ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_xla_fallback_path():
    q, k, v = _qkv((1, 16, 2, 4), seed=5)
    got = flash_attention(q, k, v, use_pallas=False)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
