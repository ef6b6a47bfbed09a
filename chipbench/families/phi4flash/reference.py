"""Plain reference of one chip's share of the Phi-4-mini-flash train step,
and the weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
following the layer equations (``model_type`` ``phi4flash``, SambaY; what the
published config has no key for is the configuration's ``assumed``). With
``n`` published layers and 0-based index ``l``:

* kind of layer ``l``: even ``l <= n/2``: Mamba; odd ``l < n/2``: attention
  over ``sliding_window``; ``l = n/2 + 1``: full causal attention, whose keys
  and values every cross layer reads; even ``l >= n/2 + 2``: a gated memory
  unit reading layer ``n/2``'s memory; odd ``l >= n/2 + 3``: cross-attention
  (queries only);
* every layer: ``h = x + Mixer(LN1(x)); y = h + MLP(LN2(h))``; ``LN`` is
  LayerNorm with scale and bias; a final LayerNorm before the tied head;
  ``MLP(x) = W2 (silu(g) * u)``, ``[g, u] = x W1`` (held as ``w1``, ``w3``);
* Mamba (``d_inner = expand * hidden``, ``N`` states, ``conv_kernel`` taps
  with bias, rank ``R``): ``[u, z] = x W_in``; ``u <- silu(conv(u) + b)``;
  ``[delta, B_t, C_t] = u W_x``; ``Delta = softplus(delta W_dt + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(Delta_t (x) A) * h_{t-1} + (Delta_t *
  u_t) (x) B_t``; ``s_t = h_t C_t + D * u_t``; ``out = (s * silu(z))
  W_out``. Layer ``n/2`` also hands on ``M = s`` (before the gate by ``z``);
* gated memory unit: ``out = (silu(x W1) * M) W2``;
* attention (window and full): ``[q, k, v] = x W_qkv + b``, no positional
  encoding; differential heads: query heads ``2p, 2p+1`` form pair ``p``, key
  heads ``2g, 2g+1`` pair ``g`` (pair ``p`` reads ``g = p // (pairs / key
  pairs)``), ``V_g = [v_2g | v_2g+1]``; ``o_p = (softmax(q_2p k_2g^T /
  sqrt(d) + mask) - lambda softmax(q_2p+1 k_2g+1^T / sqrt(d) + mask)) V_g``,
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init
  = 0.8 - 0.6 exp(-0.3 l)`` with the published index ``l``; ``o_p <-
  RMSNorm(o_p) (1 - lambda_init)`` (learned scale over the ``2 d``);
  the pairs concatenated, ``W_o`` with bias. The window includes the
  current position;
* cross-attention: ``q = x W_q + b`` only; ``k``, ``v`` are layer ``n/2 +
  1``'s tensors themselves; full causal; its own lambdas and norm;
* next-token cross-entropy over the vocabulary rows held, the mean over a
  sequence's positions but its last.

The scan is a ``lax.scan`` over the positions, in blocks of ``SCAN_BLOCK``
whose states are recomputed in the backward pass (all 8,192 positions'
states would be 2.7 GB a layer); attention runs one key pair's group of
query pairs and a block of queries at a time; every layer and every part of
it is recomputed in the backward pass, so that one 8,192-token sequence fits
in float32 beside the weights, Adam's moments and the gradients (11 GB of
the chip's 16.9): the arithmetic is the dense formula's. Adam is the
benchmark's own (``chipbench/follow.py``). It imports nothing of the program
and takes nothing the program has made.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (activations, weights, attention's q, k, v and probabilities)
rounded to float8 e4m3 under a per-tensor power-of-two scale
(``families/laguna/reference.py`` ``_fake_fp8``, as ``follow`` is its),
accumulation in float32, gradients passed straight through the rounding: the
nearest precision below the bfloat16 compute the configuration states. The scan is
float32 on both, as the configuration states it.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.families.laguna.reference import Reference as _LeanFollower
from chipbench.families.laguna.reference import _fake_fp8
from chipbench.follow import AdamFollower, seed_key

from . import counts

BLOCK_ROWS = 1
QUERY_BLOCK = 512
SCAN_BLOCK = 256
# The control's ``quant``: the nearest precision below the stated bfloat16.
CONTROL = "fp8"
DT_MIN, DT_MAX = 1e-3, 1e-1


def init_params(cfg: dict, seed: int, sharding=None):
    """Weights from the seed, float32, made on the device in one jitted
    call: matrices normal with deviation 1/sqrt(fan_in) (the embedding's
    rows 1/sqrt(hidden), the convolution's taps 1/sqrt(taps)), norms one,
    biases zero, the lambda vectors normal(0, 0.1), and Mamba's own for the
    scan: ``A_log = log(1..N)`` a channel, ``D`` one, ``dt_bias`` such that
    ``softplus(dt_bias)`` is log-uniform in [0.001, 0.1]. A flat dict by
    leaf name."""
    shapes = counts.leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            last = name.rsplit(".", 1)[-1]
            if last in ("scale", "norm", "D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif last == "bias" or last.endswith("_bias") and last != "dt_bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif last == "A_log":
                out[name] = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape
                )
            elif last == "dt_bias":
                step = jnp.exp(
                    jax.random.uniform(k, shape, jnp.float32)
                    * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)
                )
                out[name] = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
            elif last in ("lq1", "lk1", "lq2", "lk2"):
                out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = shape[-1] if last in ("embed", "conv") else shape[-2]
                out[name] = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        return out

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def _layernorm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * scale + bias


def _divisor(n: int, most: int) -> int:
    return next(b for b in range(min(most, n), 0, -1) if n % b == 0)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def selective_scan(u, delta, a, b, c, d):
    """``s [rows, seq, d_inner]`` of the recurrence, a position at a time,
    a block of positions recomputed at a time in the backward pass."""
    rows, seq, _ = u.shape
    block = _divisor(seq, SCAN_BLOCK)

    def cut(x):  # [rows, seq, w] -> [blocks, block, rows, w]
        return jnp.moveaxis(x.reshape(rows, seq // block, block, -1), 0, 2)

    def step(h, at):
        u_t, delta_t, b_t, c_t = at
        h = jnp.exp(delta_t[..., None] * a) * h + (
            (delta_t * u_t)[..., None] * b_t[:, None, :]
        )
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def of_block(h, xs):
        return jax.lax.scan(step, h, xs)

    h0 = jnp.zeros((rows, *a.shape), jnp.float32)
    _, s = jax.lax.scan(of_block, h0, tuple(cut(x) for x in (u, delta, b, c)))
    return jnp.moveaxis(s, 2, 0).reshape(rows, seq, -1) + d * u


def _conv(u, taps, bias):
    """Causal depthwise: tap ``k - 1`` weighs the current position."""
    seq, k = u.shape[1], taps.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j : j + seq] * taps[:, j] for j in range(k)) + bias


def mamba(cfg, p, prefix, x, q):
    """``(out, s)``: the layer's output and its scan's, before the gate."""
    n, r = int(cfg["ssm"]["state_size"]), counts.dt_rank(cfg)
    u, z = jnp.split(q(x) @ q(p[prefix + "ssm.in"]), 2, axis=-1)
    u = jax.nn.silu(_conv(u, p[prefix + "ssm.conv"], p[prefix + "ssm.conv_bias"]))
    low, b, c = jnp.split(q(u) @ q(p[prefix + "ssm.x"]), [r, r + n], axis=-1)
    delta = jax.nn.softplus(q(low) @ q(p[prefix + "ssm.dt"]) + p[prefix + "ssm.dt_bias"])
    s = selective_scan(
        u, delta, -jnp.exp(p[prefix + "ssm.A_log"]), b, c, p[prefix + "ssm.D"]
    )
    return q(s * jax.nn.silu(z)) @ q(p[prefix + "ssm.out"]), s


def memory_unit(cfg, p, prefix, x, q, memory):
    gate = jax.nn.silu(q(x) @ q(p[prefix + "gmu.in"]))
    return q(gate * memory) @ q(p[prefix + "gmu.out"])


def attention(cfg, p, prefix, x, q, index, window=None, shared=None):
    """``(out, (k, v))``. One key pair and the query pairs that read it at
    a time (the pairs are independent, and their concatenation times
    ``W_o`` is the sum of each group's pairs times its rows of ``W_o``),
    and within a group a block of queries at a time against all the keys."""
    rows, seq, hidden = x.shape
    d = counts.head_dim(cfg)
    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    kv_pairs, group = nkv // 2, (nq // 2) // (nkv // 2)
    name = "attn." if shared is None else "cross."
    if shared is None:
        bias = jnp.concatenate([p[prefix + f"attn.{n}_bias"] for n in "qkv"])
        proj = q(x) @ q(p[prefix + "attn.qkv"]) + bias
        qs, k, v = jnp.split(proj, [nq * d, (nq + nkv) * d], axis=-1)
        k, v = k.reshape(rows, seq, nkv, d), v.reshape(rows, seq, nkv, d)
    else:
        qs = q(x) @ q(p[prefix + "cross.q"]) + p[prefix + "cross.q_bias"]
        k, v = shared
    start = lambda_init(index)
    lam = (
        jnp.exp(jnp.sum(p[prefix + name + "lq1"] * p[prefix + name + "lk1"]))
        - jnp.exp(jnp.sum(p[prefix + name + "lq2"] * p[prefix + name + "lk2"]))
        + start
    )
    norm_scale = p[prefix + name + "norm"]
    eps = float(cfg["layer_norm_eps"])
    block = _divisor(seq, QUERY_BLOCK)
    span = seq if window is None else window

    @jax.checkpoint
    def of_group(qg, kg, vg, wo):
        # qg [rows, seq, group, 2, d]; kg [rows, seq, 2, d]; vg [rows, seq, 2 d]
        qg, kg, vg = q(qg), q(kg), q(vg)

        @jax.checkpoint
        def of_block(lo):
            qb = jax.lax.dynamic_slice_in_dim(qg, lo, block, axis=1)
            s = jnp.einsum("rqpmd,rkmd->rpmqk", qb, kg) / np.sqrt(d)
            behind = (lo + jnp.arange(block))[:, None] - jnp.arange(seq)[None, :]
            visible = (behind >= 0) & (behind < span)
            probs = q(jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1))
            maps = jnp.einsum("rpmqk,rkd->rqpmd", probs, vg)
            return maps[:, :, :, 0] - lam * maps[:, :, :, 1]  # [rows, block, group, 2 d]

        out = jax.lax.map(of_block, jnp.arange(0, seq, block))
        out = jnp.moveaxis(out, 0, 1).reshape(rows, seq, group, 2 * d)
        out = out * jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + eps)
        out = out * norm_scale * (1.0 - start)
        return q(out.reshape(rows, seq, group * 2 * d)) @ q(wo)

    q_groups = jnp.moveaxis(qs.reshape(rows, seq, kv_pairs, group, 2, d), 2, 0)
    k_groups = jnp.moveaxis(k.reshape(rows, seq, kv_pairs, 2, d), 2, 0)
    v_groups = jnp.moveaxis(v.reshape(rows, seq, kv_pairs, 2 * d), 2, 0)
    w_o = p[prefix + name + "o"].reshape(kv_pairs, group * 2 * d, hidden)
    out, _ = jax.lax.scan(
        lambda out, of: (out + of_group(*of), None),
        jnp.zeros_like(x), (q_groups, k_groups, v_groups, w_o),
    )
    return out + p[prefix + name + "o_bias"], (k, v)


def mlp(cfg, p, prefix, x, q):
    x = q(x)
    up = jax.nn.silu(x @ q(p[prefix + "mlp.w1"])) * (x @ q(p[prefix + "mlp.w3"]))
    return q(up) @ q(p[prefix + "mlp.w2"])


def hidden(cfg: dict, params, tokens, quant: Optional[str] = None):
    """The final normed activations ``[rows, seq, hidden]`` of ``tokens
    [rows, seq]``."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    eps = float(cfg["layer_norm_eps"])
    memory_layer = counts.published_layers(cfg) // 2
    x = params["embed"][tokens]
    handed: dict = {}
    for i, kind in counts.layers(cfg):
        prefix = f"l{i}."

        @jax.checkpoint
        def layer(x, handed, p, prefix=prefix, kind=kind, i=i):
            # Each part recomputed on its own in the backward pass too, so
            # that only one part's float32 intermediates exist at a time.
            part = lambda f, *a, **kw: jax.checkpoint(  # noqa: E731
                lambda x, p, *more: f(cfg, p, prefix, x, q, *a, *more, **kw)
            )
            handed = dict(handed)
            normed = _layernorm(x, p[prefix + "norm1.scale"], p[prefix + "norm1.bias"], eps)
            if kind == counts.MAMBA:
                mixed, s = part(mamba)(normed, p)
                if i == memory_layer:
                    handed["memory"] = s
            elif kind == counts.MEMORY_UNIT:
                mixed = part(memory_unit)(normed, p, handed["memory"])
            elif kind == counts.WINDOW:
                mixed, _ = part(attention, i, int(cfg["sliding_window"]))(normed, p)
            elif kind == counts.FULL:
                mixed, handed["kv"] = part(attention, i)(normed, p)
            else:
                mixed, _ = part(attention, i, None)(normed, p, handed["kv"])
            x = x + mixed
            normed = _layernorm(x, p[prefix + "norm2.scale"], p[prefix + "norm2.bias"], eps)
            return x + part(mlp)(normed, p), handed

        x, handed = layer(
            x, handed, {k: v for k, v in params.items() if k.startswith(prefix)}
        )
    return _layernorm(x, params["final_norm.scale"], params["final_norm.bias"], eps)


def logits(cfg: dict, params, tokens, quant: Optional[str] = None):
    """Over the vocabulary rows held; the head is the embedding's matrix."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    return q(hidden(cfg, params, tokens, quant)) @ q(params["embed"]).T


def loss_sum(cfg: dict, params, tokens, quant: Optional[str] = None):
    """Summed over the sequences (not their mean), each sequence's mean
    cross-entropy of position ``t``'s logits against token ``t + 1``."""
    out = logits(cfg, params, tokens, quant)[:, :-1]
    targets = tokens[:, 1:]
    picked = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(jax.nn.logsumexp(out, axis=-1) - picked, axis=-1))


def batch_of(cfg: dict, rows):
    """The reference's batch from the files' rows of a batch's keys
    (``{column: numpy [rows, ..]}``): the token ids ``[rows, seq]``."""
    return np.asarray(rows[counts.token_column(cfg)], np.int32)


class Reference(_LeanFollower):
    """Follows the train step from the seed's weights over batches of
    ``batch_of``, one sequence a block; ``quant`` names the control.
    ``follow`` is ``families/laguna/reference.py``'s (the benchmark's Adam
    with two arrays fewer on the device while a gradient is taken: at 697 M
    parameters the plain follower's accumulator, gradient and both moments
    would not fit beside the weights); the loss is this family's."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        loss = lambda params, block: loss_sum(cfg, params, block, quant)  # noqa: E731
        AdamFollower.__init__(self, cfg["optimizer"], loss, BLOCK_ROWS)
        self._first_block = jax.jit(jax.value_and_grad(loss))
