"""Adam, followed step by step in plain float32: the part of a plain
reference that is the same whatever the model.

A family's ``reference.py`` gives the summed loss of one block of rows
(``loss_sum(params, block)``: its forward pass and its loss, nothing else)
and gets back what the comparison reads: each step's mean loss, the norm and
the sketch of every leaf of the first gradient, and the norm of every leaf's
change after the last step. No kernels, no sharding, no bfloat16; it imports
nothing of the program.

The whole batch never sits in float32 at once: gradients are summed over
blocks of rows, so the reference fits beside nothing else on one chip.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.check import norms, sketches


def seed_key(seed: int):
    """A key from ``--seed``, which may pass 2**31: a key holds 32 bits, the
    rest is folded in."""
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


class AdamFollower:
    """Follows the train step from the seed's weights over given batches.

    ``loss_sum(params, block)`` is the summed (not mean) loss over a block
    of rows; ``params`` is a flat dict by leaf name, a batch any tree of
    numpy arrays with the rows leading.

    ``rows_used`` is a fault for the tests and the limits' readings: only
    the first ``rows_used`` rows of each batch enter the step, the mean
    taken over them."""

    def __init__(self, optimizer: dict, loss_sum: Callable, block_rows: int):
        if optimizer["name"] != "adam":
            raise ValueError(
                f"the reference follows Adam only, not {optimizer['name']!r}"
            )
        self.block_rows = int(block_rows)
        lr, b1, b2, eps = (
            float(optimizer[k]) for k in ("learning_rate", "b1", "b2", "eps")
        )

        def block(acc, loss, params, rows):
            l, g = jax.value_and_grad(loss_sum)(params, rows)
            return jax.tree.map(jnp.add, acc, g), loss + l

        def adam(params, m, v, g, t, rows):
            g = jax.tree.map(lambda x: x / rows, g)
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            c1, c2 = 1 - b1**t, 1 - b2**t
            params = jax.tree.map(
                lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
                params, m, v,
            )
            return params, m, v, norms(g), sketches(g)

        self._block = jax.jit(block, donate_argnums=(0, 1))
        self._adam = jax.jit(adam, donate_argnums=(0, 1, 2))
        self._change = jax.jit(
            lambda p, p0: norms(jax.tree.map(jnp.subtract, p, p0))
        )

    def follow(self, make_params, batches, rows_used: Optional[int] = None):
        """``make_params()`` gives the starting weights (called twice: the
        start is made anew for the change, not kept); ``batches`` is a list
        of the family's batches as numpy arrays. Returns ``{"loss": [..],
        "grad_norm": {leaf: ..} and "grad_sketch": {leaf: [..]} (of the first
        step), "change_norm": {leaf: ..} (after the last)}`` as Python
        floats."""
        with jax.default_matmul_precision("highest"):
            params = make_params()
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
            losses, first_grad, first_sketch = [], None, None
            for t, batch in enumerate(batches, start=1):
                rows = int(rows_used or len(jax.tree.leaves(batch)[0]))
                blk = next(
                    b
                    for b in range(min(rows, self.block_rows), 0, -1)
                    if rows % b == 0
                )
                acc = jax.tree.map(jnp.zeros_like, params)
                loss = jnp.zeros((), jnp.float32)
                for lo in range(0, rows, blk):
                    cut = jax.tree.map(
                        lambda x: jnp.asarray(x[lo : lo + blk]), batch
                    )
                    acc, loss = self._block(acc, loss, params, cut)
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
