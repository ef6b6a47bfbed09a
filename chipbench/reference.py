"""Plain reference for the DLRM train step, and the weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
embedding lookup, pairwise dot interaction (strict upper triangle, row
major), top MLP with ReLU, a 1-wide logit layer, mean sigmoid cross-entropy
against the soft label, Adam. No kernels, no sharding, no bfloat16. It
imports nothing of the program and takes nothing the program has made: the
weights come from ``init_params`` (the benchmark's, from ``--seed``), the
rows from the benchmark's own files.

Departures from the published DLRM, which the configuration files list under
``assumed``: no bottom MLP (the schema has no dense features) and the two
one-hot columns are embedded like the other seventeen.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (embeddings, activations, weights) rounded to float8 e4m3 (4 exponent
and 3 mantissa bits, ``lax.reduce_precision``) under a per-tensor
power-of-two scale, accumulation in float32 and gradients passed
straight through the rounding. That is the nearest precision below the
bfloat16 compute the configurations state, in its most forgiving form.

The whole batch never sits in float32 at once: gradients are summed over
blocks of rows, so the reference fits beside nothing else on one chip.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import work

BLOCK_ROWS = 50_000


def _root_key(seed: int):
    # --seed may pass 2**31; a key holds 32 bits, the rest is folded in.
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def init_params(cfg: dict, seed: int, vocab_cap: int = 0, sharding=None):
    """Weights from the seed, float32, made on the device in one jitted
    call: tables normal with deviation 1/sqrt(d), dense kernels normal
    with deviation 1/sqrt(fan_in), biases zero. A flat dict by leaf name."""
    d = int(cfg["model"]["embed_dim"])
    shapes = {
        f"embed_{c}": (v, d) for c, v in work.vocab_sizes(cfg, vocab_cap).items()
    }
    for i, (fan_in, fan_out) in enumerate(work.mlp_shapes(cfg)):
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

    return jax.jit(make, out_shardings=sharding)(_root_key(seed))


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
    cols = work.model_columns(cfg)
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
    last = len(work.mlp_shapes(cfg)) - 1
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


def _norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


SKETCH_WIDTH = 256


def sketch(x):
    """A leaf folded to ``SKETCH_WIDTH`` numbers: each element under a
    pseudo-random sign (a hash of its flat index), summed by flat index
    modulo the width. Whatever the errors' pattern, the norm of the
    difference of two leaves' sketches estimates the norm of the leaves'
    difference (to about 1/sqrt(2 x width)), without either side keeping
    the other's tensor."""
    flat = x.reshape(-1).astype(jnp.float32)
    h = jax.lax.iota(jnp.uint32, flat.size)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    flat = jnp.where((h ^ (h >> 16)) & 1, -flat, flat)
    flat = jnp.pad(flat, (0, -flat.size % SKETCH_WIDTH))
    return flat.reshape(-1, SKETCH_WIDTH).sum(axis=0)


def sketches(tree) -> Dict[str, jax.Array]:
    return {k: sketch(v) for k, v in tree.items()}


class Reference:
    """Follows the train step from the seed's weights over given batches.

    ``rows_used`` is a fault for the tests and the limits' readings: only
    the first ``rows_used`` rows of each batch enter the step, the mean
    taken over them."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        self.cfg = cfg
        opt = cfg["optimizer"]
        if opt["name"] != "adam":
            raise ValueError(f"the reference follows Adam only, not {opt['name']!r}")
        self.lr, self.b1, self.b2, self.eps = (
            float(opt[k]) for k in ("learning_rate", "b1", "b2", "eps")
        )

        def block(acc, loss, params, feats, labels):
            l, g = jax.value_and_grad(
                functools.partial(loss_sum, cfg), argnums=0
            )(params, feats, labels, quant)
            return jax.tree.map(jnp.add, acc, g), loss + l

        def adam(params, m, v, g, t, rows):
            g = jax.tree.map(lambda x: x / rows, g)
            m = jax.tree.map(lambda m, g: self.b1 * m + (1 - self.b1) * g, m, g)
            v = jax.tree.map(
                lambda v, g: self.b2 * v + (1 - self.b2) * g * g, v, g
            )
            c1, c2 = 1 - self.b1**t, 1 - self.b2**t
            params = jax.tree.map(
                lambda p, m, v: p
                - self.lr * (m / c1) / (jnp.sqrt(v / c2) + self.eps),
                params, m, v,
            )
            return params, m, v, _norms(g), sketches(g)

        self._block = jax.jit(block, donate_argnums=(0, 1))
        self._adam = jax.jit(adam, donate_argnums=(0, 1, 2))
        self._change = jax.jit(
            lambda p, p0: _norms(jax.tree.map(jnp.subtract, p, p0))
        )

    def follow(self, make_params, batches, rows_used: Optional[int] = None):
        """``make_params()`` gives the starting weights (called twice: the
        start is made anew for the change, not kept); ``batches`` is a list
        of ``(features, labels)`` as numpy arrays. Returns ``{"loss": [..],
        "grad_norm": {leaf: ..} and "grad_sketch": {leaf: [..]} (of the first
        step), "change_norm": {leaf: ..} (after the last)}`` as Python
        floats."""
        with jax.default_matmul_precision("highest"):
            params = make_params()
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
            losses, first_grad, first_sketch = [], None, None
            for t, (feats, labels) in enumerate(batches, start=1):
                rows = int(rows_used or len(labels))
                blk = next(
                    b for b in range(min(rows, BLOCK_ROWS), 0, -1) if rows % b == 0
                )
                acc = jax.tree.map(jnp.zeros_like, params)
                loss = jnp.zeros((), jnp.float32)
                for lo in range(0, rows, blk):
                    fb = {
                        c: jnp.asarray(feats[c][lo : lo + blk])
                        for c in work.model_columns(self.cfg)
                    }
                    lb = jnp.asarray(labels[lo : lo + blk], jnp.float32)
                    acc, loss = self._block(acc, loss, params, fb, lb)
                losses.append(float(loss) / rows)
                params, m, v, gn, gs = self._adam(
                    params, m, v, acc, jnp.float32(t), jnp.float32(rows)
                )
                if first_grad is None:
                    first_grad = {k: float(x) for k, x in gn.items()}
                    first_sketch = {k: np.asarray(x).tolist() for k, x in gs.items()}
            change = {
                k: float(x)
                for k, x in self._change(params, make_params()).items()
            }
        return {
            "loss": losses,
            "grad_norm": first_grad,
            "grad_sketch": first_sketch,
            "change_norm": change,
        }
