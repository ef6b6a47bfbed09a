"""Plain reference for the DLRM train step, and the weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
embedding lookup, pairwise dot interaction (strict upper triangle, row
major), top MLP with ReLU, a 1-wide logit layer, mean sigmoid cross-entropy
against the soft label; Adam is the benchmark's own (``chipbench/follow.py``).
No kernels, no sharding, no bfloat16. It imports nothing of the program and
takes nothing the program has made: the weights come from ``init_params``
(the benchmark's, from ``--seed``), the rows from the benchmark's own files.

Departures from the published DLRM, which the configuration files list under
``assumed``: no bottom MLP (the schema has no dense features) and the two
one-hot columns are embedded like the other seventeen.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (embeddings, activations, weights) rounded to float8 e4m3 (4 exponent
and 3 mantissa bits, ``lax.reduce_precision``) under a per-tensor
power-of-two scale, accumulation in float32 and gradients passed
straight through the rounding. That is the nearest precision below the
bfloat16 compute the configurations state, in its most forgiving form.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.follow import AdamFollower, seed_key

from . import counts

BLOCK_ROWS = 50_000
# The control's ``quant``: the nearest precision below the stated bfloat16.
CONTROL = "fp8"


def init_params(cfg: dict, seed: int, sharding=None):
    """Weights from the seed, float32, made on the device in one jitted
    call: tables normal with deviation 1/sqrt(d), dense kernels normal
    with deviation 1/sqrt(fan_in), biases zero. A flat dict by leaf name."""
    d = int(cfg["model"]["embed_dim"])
    shapes = {
        f"embed_{c}": (v, d) for c, v in counts.vocab_sizes(cfg).items()
    }
    for i, (fan_in, fan_out) in enumerate(counts.mlp_shapes(cfg)):
        shapes[f"dense_{i}.w"] = (fan_in, fan_out)
        shapes[f"dense_{i}.b"] = (fan_out,)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith(".b"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                scale = 1.0 / np.sqrt(d if name.startswith("embed_") else shape[0])
                out[name] = scale * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                )
        return out

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def _fake_fp8(x):
    """``x`` rounded to 4 exponent and 3 mantissa bits (float8 e4m3) under a
    power-of-two scale that puts its largest magnitude in the type's top
    binade; the gradient passes straight through. ``reduce_precision`` and
    not a pair of casts: XLA:TPU drops a cast to a narrower type and back
    as excess precision, and the control then computes in float32."""
    top = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.floor(jnp.log2(240.0 / jnp.maximum(top, 1e-30))))
    rounded = jax.lax.reduce_precision(x * scale, 4, 3) / scale
    return x + jax.lax.stop_gradient(rounded - x)


def forward(cfg: dict, params, features, quant: Optional[str] = None):
    """``features``: column -> int32 ``[rows]``. Returns float32 logits."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    cols = counts.model_columns(cfg)
    embeds = []
    for c in cols:
        table = params[f"embed_{c}"]
        embeds.append(q(table[features[c] % table.shape[0]]))
    stacked = jnp.stack(embeds, axis=1)  # [rows, n, d]
    gram = jnp.einsum("bnd,bmd->bnm", stacked, stacked)
    iu, ju = np.triu_indices(len(cols), k=1)
    x = jnp.concatenate(
        [stacked.reshape(stacked.shape[0], -1), gram[:, iu, ju]], axis=-1
    )
    last = len(counts.mlp_shapes(cfg)) - 1
    for i in range(last + 1):
        x = q(x) @ q(params[f"dense_{i}.w"]) + params[f"dense_{i}.b"]
        if i < last:
            x = jax.nn.relu(x)
    return x.reshape(-1)


def loss_sum(cfg, params, features, labels, quant=None):
    """Summed (not mean) sigmoid cross-entropy against soft labels."""
    logits = forward(cfg, params, features, quant)
    return -jnp.sum(
        labels * jax.nn.log_sigmoid(logits)
        + (1.0 - labels) * jax.nn.log_sigmoid(-logits)
    )


def batch_of(cfg: dict, rows) -> tuple:
    """The reference's batch from the files' rows of a batch's keys
    (``{column: numpy [rows]}``): ``(features, labels)``."""
    return (
        {c: rows[c] for c in counts.model_columns(cfg)},
        rows[cfg["label_column"]].astype(np.float32),
    )


class Reference(AdamFollower):
    """Follows the train step from the seed's weights over batches of
    ``batch_of``; ``quant`` names the control."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        super().__init__(
            cfg["optimizer"],
            lambda params, block: loss_sum(cfg, params, *block, quant),
            BLOCK_ROWS,
        )
