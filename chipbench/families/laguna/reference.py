"""Plain reference of one chip's share of the Laguna train step, and the
weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
following the layer equations (``model_type`` ``laguna``; every norm is RMS
with a learned scale, no bias anywhere):

* every layer: ``x = x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))``;
* ``attn`` of layer ``i``: ``num_attention_heads_per_layer[i]`` query heads
  over ``num_key_value_heads`` key/value heads of ``head_dim``; rotary
  positions on q and k (half-split); scores ``q k^T / sqrt(head_dim)``;
  softmax over ``j <= i`` (``full_attention``) or over ``i - sliding_window <
  j <= i`` (``sliding_attention``); heads concatenated, times ``W_o``;
* rotary, sliding layers: the whole head, ``inv_freq_i = theta^(-2i/dim)``;
* rotary, full layers: the first ``partial_rotary_factor`` of each head, the
  rest passes through; YaRN: ``f_i = theta^(2i/dim)``, ``c(r) = dim
  ln(original / (2 pi r)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)),
  0)``, ``high = min(ceil(c(beta_slow)), dim - 1)``, ``ramp_i = clip((i -
  low) / (high - low), 0, 1)``, ``inv_freq_i = ramp_i / (factor f_i) + (1 -
  ramp_i) / f_i``; cos and sin times ``attention_factor``;
* dense FFN: ``W2 (silu(W1 x) * W3 x)``;
* sparse FFN: ``Shared(x) + scale * sum_chosen w_e E_e(x)`` with ``s =
  sigmoid(W_r x)`` over all the published experts, the ``top_k`` largest
  chosen, ``w_e = s_e / sum_chosen s``; ``Shared`` and ``E_e`` are the gated
  form; of the routed sum only the experts held here are added up (every
  token goes through every held expert and is masked by its weight: no
  sorting, no kernels), the shared expert once;
* final RMS norm, the untied head over the vocabulary rows held, next-token
  cross-entropy, the mean over a sequence's positions but its last.

Attention is computed one key/value head's group of query heads and a block
of queries at a time, the routed experts one at a time, and every layer and
every part of it is recomputed in the backward pass, so that one
8,192-token sequence fits in float32 beside the weights, Adam's moments and
the gradients (11 GB of the chip's 16.9); the arithmetic is the dense
formula's. Adam is
the benchmark's own (``chipbench/follow.py``). It imports nothing of the
program and takes nothing the program has made.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (activations, weights, attention's q, k, v and probabilities)
rounded to float8 e4m3 under a per-tensor power-of-two scale, accumulation
in float32, gradients passed straight through the rounding: the nearest
precision below the bfloat16 compute the configuration states. The router
scores in float32 on both.
"""

from __future__ import annotations

import math
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
    rows 1/sqrt(hidden)), norms one. A flat dict by leaf name."""
    shapes = counts.leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            fan_in = shape[-1] if name == "embed" else shape[-2]
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) / np.sqrt(fan_in)
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


def yarn_ramp(dim: int, rope: dict):
    """``(low, high, ramp [dim / 2])`` of YaRN over ``dim`` dimensions."""
    base = float(rope["rope_theta"])
    original = float(rope["original_max_position_embeddings"])

    def c(turns):
        return dim * math.log(original / (2 * math.pi * turns)) / (2 * math.log(base))

    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return low, high, ramp


def rope_table(cfg: dict, kind: str, seq: int):
    """``(cos, sin [seq, dim / 2], dim)`` of the layers of ``kind``: the
    ``dim`` leading dimensions of each head that turn, float64 on the
    host, float32 on the way out."""
    rope = cfg["rope_parameters"][kind]
    dim = int(int(cfg["head_dim"]) * float(rope.get("partial_rotary_factor", 1)))
    f = float(rope["rope_theta"]) ** (2.0 * np.arange(dim // 2) / dim)
    inv_freq, factor = 1.0 / f, 1.0
    if rope["rope_type"] == "yarn":
        _, _, ramp = yarn_ramp(dim, rope)
        inv_freq = ramp / (float(rope["factor"]) * f) + (1.0 - ramp) / f
        factor = float(rope["attention_factor"])
    angle = np.arange(seq)[:, None] * inv_freq[None, :]
    return (
        jnp.asarray(factor * np.cos(angle), jnp.float32),
        jnp.asarray(factor * np.sin(angle), jnp.float32),
        dim,
    )


def _rotary(x, table):
    """``x [rows, seq, heads, d]``: of the first ``dim`` dimensions,
    dimension ``i`` turns with ``i + dim / 2``; the others pass through."""
    cos, sin, dim = table
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2 : dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(cfg, p, prefix, x, q, kind, heads):
    """One key/value head and the query heads that read it at a time (the
    heads are independent, and their concatenation times ``W_o`` is the sum
    of each group's heads times its rows of ``W_o``), and within a group a
    block of queries at a time against all the keys."""
    rows, seq, hidden = x.shape
    d = int(cfg["head_dim"])
    kv_heads = int(cfg["num_key_value_heads"])
    group = heads // kv_heads
    window = int(cfg["sliding_window"]) if kind == counts.SLIDING else seq
    table = rope_table(cfg, kind, seq)
    xq = q(x)
    block = next(b for b in range(min(QUERY_BLOCK, seq), 0, -1) if seq % b == 0)

    def by_group(w, per_group):  # [hidden, groups * n] -> [groups, hidden, n]
        return jnp.moveaxis(w.reshape(hidden, kv_heads, per_group * d), 1, 0)

    @jax.checkpoint
    def of_group(wq, wk, wv, wo):
        qs = _rotary((xq @ q(wq)).reshape(rows, seq, group, d), table)
        ks = _rotary((xq @ q(wk)).reshape(rows, seq, 1, d), table)
        vs = (xq @ q(wv)).reshape(rows, seq, 1, d)
        qs, ks, vs = q(qs), q(ks)[:, :, 0], q(vs)[:, :, 0]

        @jax.checkpoint
        def of_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qs, start, block, axis=1)
            s = jnp.einsum("rqhd,rkd->rhqk", qb, ks) / np.sqrt(d)
            behind = (start + jnp.arange(block))[:, None] - jnp.arange(seq)[None, :]
            visible = (behind >= 0) & (behind < window)
            probs = jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1)
            return jnp.einsum("rhqk,rkd->rqhd", q(probs), vs)

        out = jax.lax.map(of_block, jnp.arange(0, seq, block))  # [blocks, rows, block, g, d]
        out = jnp.moveaxis(out, 0, 1).reshape(rows, seq, group * d)
        return q(out) @ q(wo)

    out, _ = jax.lax.scan(
        lambda out, of: (out + of_group(*of), None),
        jnp.zeros_like(x),
        (
            by_group(p[prefix + "attn.q"], group),
            by_group(p[prefix + "attn.k"], 1),
            by_group(p[prefix + "attn.v"], 1),
            p[prefix + "attn.o"].reshape(kv_heads, group * d, hidden),
        ),
    )
    return out


def _gated(x, w1, w3, w2, q):
    """``W2 (silu(W1 x) * W3 x)``, ``x`` already rounded."""
    return q(jax.nn.silu(x @ q(w1)) * (x @ q(w3))) @ q(w2)


def route(cfg, p, prefix, x):
    """``(experts [.., top_k], weights [.., top_k])`` over all the
    published experts, in float32 whatever the control rounds: sigmoid
    scores, the largest chosen, renormalised, times the routed scale."""
    scores = jax.nn.sigmoid(x @ p[prefix + "moe.gate"])
    weights, experts = jax.lax.top_k(scores, int(cfg["num_experts_per_tok"]))
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts, weights * float(cfg["moe_routed_scaling_factor"])


def routed_ffn(cfg, p, prefix, x, q, first: Optional[int] = None,
               held: Optional[int] = None):
    """The part of the routed sum that experts ``first .. first + held``
    give (default: the configuration's share): every token through every
    one of them, weighed by its routing weight, which is 0 where the token
    did not choose the expert."""
    first = int(cfg["first_expert"]) if first is None else first
    held = int(cfg["num_experts"]) if held is None else held
    experts, weights = route(cfg, p, prefix, x)
    xq = q(x)

    @jax.checkpoint
    def of_expert(e, w1, w3, w2):
        weight = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return weight[..., None] * _gated(xq, w1, w3, w2, q)

    # One expert at a time, each recomputed in the backward pass: 32 of
    # them at 8,192 tokens would not fit side by side in float32.
    y, _ = jax.lax.scan(
        lambda y, of: (y + of_expert(*of), None), jnp.zeros_like(x),
        (jnp.arange(held), *(p[prefix + f"moe.{w}"][:held] for w in ("w1", "w3", "w2"))),
    )
    return y


def dense_ffn(cfg, p, prefix, x, q):
    return _gated(
        q(x), p[prefix + "ffn.w1"], p[prefix + "ffn.w3"], p[prefix + "ffn.w2"], q
    )


def shared_ffn(cfg, p, prefix, x, q):
    """The shared expert: every token, on every chip alike."""
    return _gated(
        q(x), p[prefix + "shared.w1"], p[prefix + "shared.w3"],
        p[prefix + "shared.w2"], q,
    )


def hidden(cfg: dict, params, tokens, quant: Optional[str] = None):
    """The final normed activations ``[rows, seq, hidden]`` of ``tokens
    [rows, seq]``."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens]
    for i, kind, heads, dense in counts.layers(cfg):
        prefix = f"l{i}."

        @jax.checkpoint
        def layer(x, p, prefix=prefix, kind=kind, heads=heads, dense=dense):
            # Each part recomputed on its own in the backward pass too, so
            # that only one part's float32 intermediates exist at a time.
            part = lambda f, *a: jax.checkpoint(  # noqa: E731
                lambda x, p: f(cfg, p, prefix, x, q, *a)
            )
            normed = _rmsnorm(x, p[prefix + "op_norm"], eps)
            x = x + part(_attention, kind, heads)(normed, p)
            normed = _rmsnorm(x, p[prefix + "ffn_norm"], eps)
            if dense:
                return x + part(dense_ffn)(normed, p)
            return x + part(shared_ffn)(normed, p) + part(routed_ffn)(normed, p)

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
    ``batch_of``, one sequence a block; ``quant`` names the control.

    ``follow`` is the base class's arithmetic (its jitted block, Adam and
    change) with two arrays fewer on the device while a gradient is taken:
    a batch's first block gives the accumulator instead of being added to
    one of zeros, and Adam's moments are made when the first gradient is
    there. The parameters, the two moments, an accumulator and a gradient
    would be 13.8 GB of the chip's 16.9 before any activation."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        loss = lambda params, block: loss_sum(cfg, params, block, quant)  # noqa: E731
        super().__init__(cfg["optimizer"], loss, BLOCK_ROWS)
        self._first_block = jax.jit(jax.value_and_grad(loss))

    def follow(self, make_params, batches, rows_used: Optional[int] = None):
        with jax.default_matmul_precision("highest"):
            params = make_params()
            m = v = None
            losses, first_grad, first_sketch = [], None, None
            for t, batch in enumerate(batches, start=1):
                rows = int(rows_used or len(jax.tree.leaves(batch)[0]))
                blk = next(
                    b
                    for b in range(min(rows, self.block_rows), 0, -1)
                    if rows % b == 0
                )
                acc = None
                for lo in range(0, rows, blk):
                    cut = jax.tree.map(lambda x: jnp.asarray(x[lo : lo + blk]), batch)
                    if acc is None:
                        loss, acc = self._first_block(params, cut)
                    else:
                        acc, loss = self._block(acc, loss, params, cut)
                losses.append(float(loss) / rows)
                if m is None:
                    m = jax.tree.map(jnp.zeros_like, params)
                    v = jax.tree.map(jnp.zeros_like, params)
                params, m, v, gn, gs = self._adam(
                    params, m, v, acc, jnp.float32(t), jnp.float32(rows)
                )
                del acc
                if first_grad is None:
                    first_grad = {k: float(x) for k, x in gn.items()}
                    first_sketch = {k: np.asarray(x).tolist() for k, x in gs.items()}
            del m, v
            change = {
                k: float(x) for k, x in self._change(params, make_params()).items()
            }
        return {
            "loss": losses,
            "grad_norm": first_grad,
            "grad_sketch": first_sketch,
            "change_norm": change,
        }
