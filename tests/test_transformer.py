"""TabTransformer family: forward contract, sharded training step on the
8-device mesh, and the sequence-parallel (ring attention) encoder path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from jax.sharding import Mesh

from ray_shuffling_data_loader_tpu.models import (
    TabTransformer,
    example_features,
    transformer_for_data_spec,
)
from ray_shuffling_data_loader_tpu.ops import make_ring_attention
from ray_shuffling_data_loader_tpu.parallel import (
    batch_sharding,
    init_state,
    make_train_step,
)
from ray_shuffling_data_loader_tpu.parallel.mesh import make_mesh


def test_forward_contract():
    model = transformer_for_data_spec(
        embed_dim=16, num_layers=1, num_heads=2, vocab_cap=64
    )
    feats = example_features(model, batch_size=32)
    params = model.init(jax.random.key(0), feats)
    logits = model.apply(params, feats)
    assert logits.shape == (32,)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_sharded_train_step_loss_decreases():
    mesh = make_mesh(model_parallelism=2)
    model = transformer_for_data_spec(
        embed_dim=16, num_layers=1, num_heads=2, vocab_cap=2048
    )
    batch = 64
    feats = example_features(model, batch_size=batch)
    optimizer = optax.adam(1e-2)
    state, shardings = init_state(
        model, optimizer, mesh, feats, vocab_shard_threshold=512
    )
    step = make_train_step(model, optimizer, mesh, shardings)
    bsh = batch_sharding(mesh, 1)
    feats = {k: jax.device_put(v, bsh) for k, v in feats.items()}
    labels = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(0, 2, batch).astype(np.float32)
        ),
        bsh,
    )
    losses = []
    for _ in range(5):
        state, metrics = step(state, feats, labels)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # The big tables genuinely sharded over the model axis.
    table = state.params["params"]["embed_embeddings_name12"]
    assert table.sharding.spec[0] == "model"


def test_ring_attention_encoder_matches_dense():
    """The same params run with dense vs ring attention must agree: the
    sequence-parallel path changes the schedule, not the math."""
    n_cols = 16  # divisible by the 8-device ring
    vocab_sizes = {f"c{i:02d}": 97 for i in range(n_cols)}
    feats = {
        c: jnp.asarray(
            np.random.default_rng(i).integers(0, 97, 24, dtype=np.int32)
        )
        for i, c in enumerate(sorted(vocab_sizes))
    }
    dense_model = TabTransformer(
        vocab_sizes=vocab_sizes,
        embed_dim=16,
        num_layers=2,
        num_heads=2,
        compute_dtype=jnp.float32,
    )
    params = dense_model.init(jax.random.key(1), feats)
    want = dense_model.apply(params, feats)

    mesh = Mesh(np.array(jax.devices()), ("sp",))
    ring_model = TabTransformer(
        vocab_sizes=vocab_sizes,
        embed_dim=16,
        num_layers=2,
        num_heads=2,
        compute_dtype=jnp.float32,
        attention_fn=make_ring_attention(mesh, "sp"),
    )
    got = ring_model.apply(params, feats)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )
