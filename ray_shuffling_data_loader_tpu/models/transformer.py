"""Second model family: transformer encoder over tabular embedding tokens.

A TabTransformer-style classifier for the same DATA_SPEC workload the
loader feeds: each categorical column embeds to one token, a transformer
encoder attends across the column-token sequence, and a pooled head emits
the binary logit. The reference repo ships only a mocked ConvNet
(``examples/horovod/ray_torch_shuffle.py:124-140,214``); this family
exists so the framework exercises an attention-bearing model end to end
— including the sequence-parallel path.

TPU-first choices mirror the flagship DLRM (``models/dlrm.py``):
float32 params with bfloat16 compute (MXU-rate matmuls), embedding
lookups as gathers, and no data-dependent control flow. Attention is
pluggable: the default is :func:`~.ops.flash_attention.flash_attention`
(auto: fused Pallas kernel on TPU backends incl. pod meshes, dense XLA reference
elsewhere); pass ``attention_fn=make_ring_attention(mesh, axis)`` to run
the encoder with sequence-parallel ring attention when the token
sequence is sharded across the mesh (long-context configurations — see
``tests/test_transformer.py`` for the wiring).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np

from ray_shuffling_data_loader_tpu.ops.embedding import embedding_lookup
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    flash_attention,
)


class EncoderBlock(nn.Module):
    """Pre-norm transformer block; ``attention_fn(q, k, v) -> out`` over
    ``[batch, seq, heads, head_dim]``."""

    num_heads: int
    mlp_ratio: int = 4
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        head_dim = d // self.num_heads
        assert head_dim * self.num_heads == d, (
            f"embed_dim {d} not divisible by num_heads {self.num_heads}"
        )
        dense = lambda feats, name: nn.Dense(
            feats,
            dtype=self.compute_dtype,
            param_dtype=jnp.float32,
            name=name,
        )

        h = nn.LayerNorm(dtype=self.compute_dtype, name="ln_attn")(x)
        qkv = dense(3 * d, "qkv")(h).reshape(b, t, 3, self.num_heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        # Default lowering mirrors the DLRM interaction auto-policy: the
        # fused Pallas flash kernel on TPU backends (pods included), the
        # dense XLA reference everywhere else (flash_attention resolves
        # this internally).
        attn = (self.attention_fn or flash_attention)(q, k, v)
        x = x + dense(d, "proj")(attn.reshape(b, t, d))

        h = nn.LayerNorm(dtype=self.compute_dtype, name="ln_mlp")(x)
        h = dense(self.mlp_ratio * d, "mlp_up")(h)
        h = nn.gelu(h)
        x = x + dense(d, "mlp_down")(h)
        return x


class TabTransformer(nn.Module):
    """Transformer encoder over one token per categorical column.

    Same input/output contract as :class:`~.models.dlrm.TabularDLRM`
    (features dict of int32 ``[batch]`` arrays -> float32 ``[batch]``
    logits), so it drops into ``parallel.make_train_step`` and every
    loader unchanged.
    """

    vocab_sizes: Dict[str, int]
    embed_dim: int = 32
    num_layers: int = 2
    num_heads: int = 4
    mlp_ratio: int = 4
    compute_dtype: jnp.dtype = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, features: Dict[str, jax.Array]) -> jax.Array:
        cols = sorted(self.vocab_sizes)
        tokens = []
        for col in cols:
            table = self.param(
                f"embed_{col}",
                nn.initializers.normal(stddev=1.0 / np.sqrt(self.embed_dim)),
                (self.vocab_sizes[col], self.embed_dim),
                jnp.float32,
            )
            tokens.append(embedding_lookup(table, features[col]))
        x = jnp.stack(tokens, axis=1)  # [batch, n_cols, dim]
        col_embed = self.param(
            "col_embed",
            nn.initializers.normal(stddev=0.02),
            (len(cols), self.embed_dim),
            jnp.float32,
        )
        x = (x + col_embed[None]).astype(self.compute_dtype)
        for i in range(self.num_layers):
            x = EncoderBlock(
                num_heads=self.num_heads,
                mlp_ratio=self.mlp_ratio,
                compute_dtype=self.compute_dtype,
                attention_fn=self.attention_fn,
                name=f"block_{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.compute_dtype, name="ln_out")(x)
        pooled = x.mean(axis=1)
        logit = nn.Dense(
            1, dtype=self.compute_dtype, param_dtype=jnp.float32, name="head"
        )(pooled)
        return logit.reshape(-1).astype(jnp.float32)


def transformer_for_data_spec(
    embed_dim: int = 32,
    num_layers: int = 2,
    num_heads: int = 4,
    vocab_cap: Optional[int] = None,
    attention_fn: Optional[Callable] = None,
) -> TabTransformer:
    """Build the tabular transformer for the synthetic DATA_SPEC schema
    (cardinalities from ``data_generation.py:56-77`` parity)."""
    from ray_shuffling_data_loader_tpu.data_generation import (
        DATA_SPEC,
        LABEL_COLUMN,
    )

    vocab_sizes = {
        col: int(min(high, vocab_cap) if vocab_cap else high)
        for col, (low, high, dtype) in DATA_SPEC.items()
        if col != LABEL_COLUMN
    }
    return TabTransformer(
        vocab_sizes=vocab_sizes,
        embed_dim=embed_dim,
        num_layers=num_layers,
        num_heads=num_heads,
        attention_fn=attention_fn,
    )
