"""CausalLM family: forward contract, causality, and the dp x sp
sequence-parallel path matching the dense lowering."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.models import (
    CausalLM,
    next_token_loss,
    synthetic_tokens,
)
from ray_shuffling_data_loader_tpu.ops import make_ring_attention

VOCAB, SEQ = 32, 64


def _model(**kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("max_seq_len", SEQ)
    kw.setdefault("embed_dim", 16)
    kw.setdefault("num_layers", 1)
    kw.setdefault("num_heads", 2)
    kw.setdefault("compute_dtype", jnp.float32)
    return CausalLM(**kw)


def test_forward_contract_and_causality():
    model = _model()
    tokens = jnp.asarray(synthetic_tokens(2, SEQ, VOCAB, seed=1))
    params = model.init(jax.random.key(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, SEQ, VOCAB)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # Causality: changing a future token must not change earlier logits.
    perturbed = tokens.at[:, SEQ // 2 :].set(
        (tokens[:, SEQ // 2 :] + 1) % VOCAB
    )
    logits_p = model.apply(params, perturbed)
    np.testing.assert_allclose(
        np.asarray(logits[:, : SEQ // 2]),
        np.asarray(logits_p[:, : SEQ // 2]),
        rtol=1e-5,
        atol=1e-5,
    )
    assert not np.allclose(
        np.asarray(logits[:, SEQ // 2 :]), np.asarray(logits_p[:, SEQ // 2 :])
    )


def test_sequence_parallel_matches_dense():
    """Same params under the dp x sp ring schedule and the dense lowering."""
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "sp"))
    tokens = jnp.asarray(synthetic_tokens(4, SEQ, VOCAB, seed=2))
    dense = _model()
    params = dense.init(jax.random.key(1), tokens)
    want = dense.apply(params, tokens)
    sp = _model(
        attention_fn=make_ring_attention(
            mesh, "sp", causal=True, batch_axis="data"
        )
    )
    tokens_sharded = jax.device_put(
        tokens, NamedSharding(mesh, P("data", "sp"))
    )
    got = sp.apply(params, tokens_sharded)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_next_token_loss_learns():
    import optax

    model = _model(num_layers=2, embed_dim=32, num_heads=4)
    tokens = jnp.asarray(synthetic_tokens(8, SEQ, VOCAB, seed=3))
    params = model.init(jax.random.key(2), tokens)
    optimizer = optax.adam(3e-3)
    opt_state = optimizer.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(model.apply(p, tokens), tokens)
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(15):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses
