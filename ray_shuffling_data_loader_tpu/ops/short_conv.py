"""Short causal depthwise convolution along the sequence.

``y[b, t, c] = sum_j w[c, j] * u[b, t - (k - 1) + j, c]`` with zeros before
the sequence's start: every channel has a filter of its own, ``k`` taps long
(3 in the gated short-convolution blocks), and position ``t`` sees positions
``t - k + 1 .. t`` only. It is ``k`` shifted multiply-adds over the
activation, which XLA fuses with the gates around it into one pass over HBM:
the op is bound by memory traffic, and a kernel of its own would add a pass.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def causal_depthwise_conv1d(
    u: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None
) -> jax.Array:
    """``u [batch, seq, channels]``, ``w [channels, taps]`` (tap ``taps -
    1`` weighs the current position, as a ``Conv1d`` with left padding
    ``taps - 1`` has it), ``bias [channels]`` added where given (a Mamba
    layer's width-4 convolution has one, the gated short convolutions
    none). Returns ``[batch, seq, channels]`` in ``u``'s type."""
    taps = w.shape[1]
    seq = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(u.dtype)
    with jax.named_scope("short_conv_taps"):
        out = padded[:, 0:seq] * w[:, 0]
        for j in range(1, taps):
            out = out + padded[:, j : j + seq] * w[:, j]
        if bias is not None:
            out = out + bias.astype(u.dtype)
    return out
