"""Plain reference of one chip's share of the LFM2-MoE train step, and the
weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
following the published layer equations (``model_type`` ``lfm2_moe``):

* every layer: ``x = x + op(rmsnorm(x)); x = x + ffn(rmsnorm(x))``;
* ``conv``: ``B, C, h = split(W_in x)``; ``y = C * conv(B * h)`` with a
  causal depthwise filter of ``conv_L_cache`` taps; ``out = W_out y``;
* ``full_attention``: grouped-query causal softmax attention with an RMS norm
  on each head's q and k and rotary positions (half-split), then ``W_o``;
* dense FFN: ``W2 (silu(W1 x) * W3 x)``;
* experts: ``s = sigmoid(W_g x)`` over all the published experts; the
  ``top_k`` largest ``s + bias``; weights ``s / sum(s)`` of those chosen,
  times ``routed_scaling_factor``; of ``sum_e w_e FFN_e(x)`` only the experts
  held here are added up (every token goes through every held expert and is
  masked by its weight: no sorting, no kernels);
* final RMS norm, the head over the vocabulary rows held, next-token
  cross-entropy, the mean over a sequence's positions but its last.

Attention is computed a block of queries at a time and every layer is
recomputed in the backward pass, so that one 8,192-token sequence fits in
float32 beside the weights; the arithmetic is the dense formula's. Adam is
the benchmark's own (``chipbench/follow.py``). It imports nothing of the
program and takes nothing the program has made.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (activations, weights, attention's q, k, v and probabilities)
rounded to float8 e4m3 under a per-tensor power-of-two scale, accumulation
in float32, gradients passed straight through the rounding: the nearest
precision below the bfloat16 compute the configuration states.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.follow import AdamFollower, seed_key

from . import counts

BLOCK_ROWS = 1
QUERY_BLOCK = 512
# The control's ``quant``: the nearest precision below the stated bfloat16.
CONTROL = "fp8"


def init_params(cfg: dict, seed: int, sharding=None):
    """Weights from the seed, float32, made on the device in one jitted
    call: matrices normal with deviation 1/sqrt(fan_in) (the embedding's
    rows 1/sqrt(hidden), the taps 1/sqrt(taps)), norms one, the selection
    bias normal with deviation 0.01 (it decides near ties, as a trained
    balancing bias does, and leaves the load about even). A flat dict by
    leaf name."""
    shapes = counts.leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            if name.endswith("moe.bias"):
                scale = 0.01
            elif name == "embed" or name.endswith("conv.taps"):
                scale = 1.0 / np.sqrt(shape[-1])
            else:
                scale = 1.0 / np.sqrt(shape[-2])
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
    as excess precision."""
    top = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.floor(jnp.log2(240.0 / jnp.maximum(top, 1e-30))))
    rounded = jax.lax.reduce_precision(x * scale, 4, 3) / scale
    return x + jax.lax.stop_gradient(rounded - x)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """``x [rows, seq, heads, d]``: dimension ``i`` turns with ``i + d/2``."""
    seq, d = x.shape[1], x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _conv_op(cfg, p, prefix, x, q):
    gates = q(x) @ q(p[prefix + "conv.in"])
    gate_b, gate_c, u = jnp.split(gates, 3, axis=-1)
    taps = p[prefix + "conv.taps"]
    k = taps.shape[1]
    seq = x.shape[1]
    padded = jnp.pad(gate_b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j : j + seq] * taps[:, j] for j in range(k))
    return q(gate_c * conv) @ q(p[prefix + "conv.out"])


def _attention_op(cfg, p, prefix, x, q):
    rows, seq, _ = x.shape
    d = counts.head_dim(cfg)
    heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    xq = q(x)
    qs = (xq @ q(p[prefix + "attn.q"])).reshape(rows, seq, heads, d)
    ks = (xq @ q(p[prefix + "attn.k"])).reshape(rows, seq, kv_heads, d)
    vs = (xq @ q(p[prefix + "attn.v"])).reshape(rows, seq, kv_heads, d)
    qs = _rotary(_rmsnorm(qs, p[prefix + "attn.q_norm"], eps), theta)
    ks = _rotary(_rmsnorm(ks, p[prefix + "attn.k_norm"], eps), theta)
    ks = jnp.repeat(ks, heads // kv_heads, axis=2)
    vs = jnp.repeat(vs, heads // kv_heads, axis=2)
    qs, ks, vs = q(qs), q(ks), q(vs)
    block = next(b for b in range(min(QUERY_BLOCK, seq), 0, -1) if seq % b == 0)

    @jax.checkpoint
    def of_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qs, start, block, axis=1)
        s = jnp.einsum("rqhd,rkhd->rhqk", qb, ks) / np.sqrt(d)
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
        probs = jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", q(probs), vs)

    out = jax.lax.map(of_block, jnp.arange(0, seq, block))  # [blocks, rows, block, h, d]
    out = jnp.moveaxis(out, 0, 1).reshape(rows, seq, heads * d)
    return q(out) @ q(p[prefix + "attn.o"])


def _dense_ffn(cfg, p, prefix, x, q):
    xq = q(x)
    up = jax.nn.silu(xq @ q(p[prefix + "ffn.w1"])) * (xq @ q(p[prefix + "ffn.w3"]))
    return q(up) @ q(p[prefix + "ffn.w2"])


def route(cfg, p, prefix, x):
    """``(experts [.., top_k], weights [.., top_k])`` over all the
    published experts, in float32 whatever the control rounds."""
    scores = jax.nn.sigmoid(x @ p[prefix + "moe.gate"])
    chosen_by = scores
    if cfg.get("use_expert_bias"):
        chosen_by = scores + p[prefix + "moe.bias"]
    _, experts = jax.lax.top_k(chosen_by, int(cfg["num_experts_per_tok"]))
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.get("norm_topk_prob"):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts, weights * float(cfg["routed_scaling_factor"])


def experts_ffn(cfg, p, prefix, x, q, first: Optional[int] = None,
                held: Optional[int] = None):
    """The part of the expert layer that experts ``first .. first + held``
    give (default: the configuration's share): every token through every
    one of them, weighed by its routing weight, which is 0 where the token
    did not choose the expert."""
    first = int(cfg["first_expert"]) if first is None else first
    held = int(cfg["num_experts"]) if held is None else held
    experts, weights = route(cfg, p, prefix, x)
    xq = q(x)
    y = jnp.zeros_like(x)
    for e in range(held):
        weight = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        up = jax.nn.silu(xq @ q(p[prefix + "moe.w1"][e])) * (
            xq @ q(p[prefix + "moe.w3"][e])
        )
        y = y + weight[..., None] * (q(up) @ q(p[prefix + "moe.w2"][e]))
    return y


def hidden(cfg: dict, params, tokens, quant: Optional[str] = None):
    """The final normed activations ``[rows, seq, hidden]`` of ``tokens
    [rows, seq]``."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    eps = float(cfg["norm_eps"])
    x = params["embed"][tokens]
    for i, kind, dense in counts.layers(cfg):
        prefix = f"l{i}."

        @jax.checkpoint
        def layer(x, p, prefix=prefix, kind=kind, dense=dense):
            op = _conv_op if kind == "conv" else _attention_op
            x = x + op(cfg, p, prefix, _rmsnorm(x, p[prefix + "op_norm"], eps), q)
            ffn = _dense_ffn if dense else experts_ffn
            return x + ffn(cfg, p, prefix, _rmsnorm(x, p[prefix + "ffn_norm"], eps), q)

        x = layer(x, {k: v for k, v in params.items() if k.startswith(prefix)})
    return _rmsnorm(x, params["final_norm"], eps)


def logits(cfg: dict, params, tokens, quant: Optional[str] = None):
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    return q(hidden(cfg, params, tokens, quant)) @ q(params["head"])


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


class Reference(AdamFollower):
    """Follows the train step from the seed's weights over batches of
    ``batch_of``, one sequence a block; ``quant`` names the control."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        super().__init__(
            cfg["optimizer"],
            lambda params, block: loss_sum(cfg, params, block, quant),
            BLOCK_ROWS,
        )
