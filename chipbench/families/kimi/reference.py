"""Plain reference of one chip's share of Kimi-VL's language model's train
step, and the weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
following the layer equations (DeepSeek-V2/V3's multi-head latent attention
and ``noaux_tc`` router at the configuration's sizes; every norm is RMS
with a learned scale, no bias anywhere but the router's selection bias):

* every layer: ``x = x + mla(rmsnorm(x)); x = x + ffn(rmsnorm(x))``;
* ``mla``: ``q = W_q x`` in ``num_attention_heads`` heads of
  ``qk_nope_head_dim + qk_rope_head_dim``, split ``[q_nope ; q_rope]``;
  ``[c ; k_rope] = W_kva x``, ``c`` the ``kv_lora_rank``-wide latent,
  ``c = rmsnorm(c)``; ``[k_nope ; v] = W_kvb c`` a head at a time; rotary
  positions at ``rope_theta`` (half-split) on ``q_rope`` and on the one
  ``k_rope``; the key of head ``h`` is ``[k_nope_h ; k_rope]``, the shared
  part repeated to every head and concatenated; scores ``q k^T /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)`` over ``j <= i``, softmax,
  times ``v``; heads concatenated, times ``W_o``;
* dense FFN (layers under ``first_k_dense_replace``): ``W2 (silu(W1 x) *
  W3 x)``;
* sparse FFN: ``Shared(x) + scale * sum_chosen w_e E_e(x)``: ``Shared`` one
  gated FFN of ``n_shared_experts * moe_intermediate_size``; ``s =
  sigmoid(W_r x)`` over all the published experts; ``noaux_tc``: ``s + b``
  (``b`` the selection bias) in ``n_group`` groups, each group scored by the
  sum of its two largest, the ``topk_group`` best groups kept, the
  ``num_experts_per_tok`` largest ``s + b`` among their experts chosen;
  ``w_e = s_e / (sum_chosen s + 1e-20)``; of the routed sum only the experts
  held here are added up (every token goes through every held expert and
  is masked by its weight: no sorting, no kernels), the shared experts
  once;
* final RMS norm, the untied head over the vocabulary rows held, next-token
  cross-entropy, the mean over a sequence's positions but its last.

Attention is computed a block of queries at a time against every key, the
routed experts one at a time, the FFNs and the head's loss a block of
positions at a time, and every layer and part is recomputed in the backward
pass, so that one 16,384-token sequence fits in float32 beside the weights,
Adam's moments and the gradients. It imports nothing of the program and
takes nothing the program has made.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (activations, weights, the latent, q, k, v and the probabilities)
rounded to float8 e4m3 under a per-tensor power-of-two scale, gradients
passed straight through: the nearest precision below the bfloat16 compute
the configuration states. The router scores in float32 on both.
"""

from __future__ import annotations

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
TOKEN_BLOCK = 2048
# The control's ``quant``: the nearest precision below the stated bfloat16.
CONTROL = "fp8"


def init_params(cfg: dict, seed: int, sharding=None):
    """Weights from the seed, float32, made on the device in one jitted
    call: matrices normal with deviation 1/sqrt(fan_in) (the embedding's
    rows 1/sqrt(hidden)), norms one, the selection bias normal with
    deviation 0.01 (it decides near ties, as a trained balancing bias does,
    and leaves the load about even). A flat dict by leaf name."""
    shapes = counts.leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            if name.endswith("moe.bias"):
                scale = 0.01
            else:
                scale = 1.0 / np.sqrt(shape[-1] if name == "embed" else shape[-2])
            out[name] = scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            )
        return out

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_table(theta: float, dim: int, seq: int):
    """``(cos, sin [seq, dim / 2])`` of plain rotary over ``dim``, float64
    on the host, float32 on the way out."""
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    angle = np.arange(seq)[:, None] * inv_freq[None, :]
    return jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)


def _rotary(x, table):
    """``x [rows, seq, heads, dim]`` turned whole: dimension ``i`` with
    ``i + dim / 2``."""
    cos, sin = table
    half = cos.shape[-1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _by_tokens(f, x):
    """``f`` of ``x [rows, seq, ..]`` a block of positions at a time, each
    recomputed in the backward pass."""
    seq = x.shape[1]
    block = next(b for b in range(min(TOKEN_BLOCK, seq), 0, -1) if seq % b == 0)
    out = jax.lax.map(
        jax.checkpoint(lambda s: f(jax.lax.dynamic_slice_in_dim(x, s, block, axis=1))),
        jnp.arange(0, seq, block),
    )  # [blocks, rows, block, ..]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[:2] + out.shape[3:])


def _attention(cfg, p, prefix, x, q):
    """Latent attention, the shared key part concatenated to every head's,
    a block of queries at a time against all the keys."""
    rows, seq, _ = x.shape
    heads, rank = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rope, dv = counts.head_dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    table = rope_table(float(cfg["rope_theta"]), rope, seq)
    xq = q(x)
    qs = (xq @ q(p[prefix + "attn.q"])).reshape(rows, seq, heads, nope + rope)
    qs = jnp.concatenate([qs[..., :nope], _rotary(qs[..., nope:], table)], axis=-1)
    ck = xq @ q(p[prefix + "attn.kv_a"])
    c = _rmsnorm(ck[..., :rank], p[prefix + "attn.kv_norm"], eps)
    k_rope = _rotary(ck[..., rank:].reshape(rows, seq, 1, rope), table)
    kv = (q(c) @ q(p[prefix + "attn.kv_b"])).reshape(rows, seq, heads, nope + dv)
    ks = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (rows, seq, heads, rope))], axis=-1
    )
    qs, ks, vs = q(qs), q(ks), q(kv[..., nope:])
    block = next(b for b in range(min(QUERY_BLOCK, seq), 0, -1) if seq % b == 0)

    @jax.checkpoint
    def of_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qs, start, block, axis=1)
        s = jnp.einsum("rqhd,rkhd->rhqk", qb, ks) / np.sqrt(nope + rope)
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
        probs = jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", q(probs), vs)

    out = jax.lax.map(of_block, jnp.arange(0, seq, block))  # [blocks, rows, block, h, dv]
    out = jnp.moveaxis(out, 0, 1).reshape(rows, seq, heads * dv)
    return q(out) @ q(p[prefix + "attn.o"])


def _gated(x, w1, w3, w2, q):
    """``W2 (silu(W1 x) * W3 x)``, ``x`` already rounded."""
    return q(jax.nn.silu(x @ q(w1)) * (x @ q(w3))) @ q(w2)


def noaux_tc(cfg, scores, bias):
    """``(experts [.., top_k], weights [.., top_k])`` of DeepSeek-V3's
    ``noaux_tc`` from the sigmoid ``scores`` over all the published experts
    and the selection ``bias``: the choice is by ``scores + bias``, within
    the ``topk_group`` of ``n_group`` groups whose two largest sum highest;
    the weights are the chosen ``scores``, renormalised where
    ``norm_topk_prob``, times ``routed_scaling_factor``."""
    top_k = int(cfg["num_experts_per_tok"])
    groups, keep = int(cfg["n_group"]), int(cfg["topk_group"])
    choice = scores + bias
    by_group = choice.reshape(*choice.shape[:-1], groups, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, keep)
    in_kept = jnp.any(jnp.arange(groups)[:, None] == kept[..., None, :], axis=-1)
    masked = jnp.where(in_kept[..., None], by_group, -jnp.inf).reshape(choice.shape)
    _, experts = jax.lax.top_k(masked, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts, weights * float(cfg["routed_scaling_factor"])


def route(cfg, p, prefix, x):
    """The router in float32, whatever the control rounds."""
    scores = jax.nn.sigmoid(x @ p[prefix + "moe.gate"])
    return noaux_tc(cfg, scores, p[prefix + "moe.bias"])


def routed_ffn(cfg, p, prefix, x, q, first: Optional[int] = None,
               held: Optional[int] = None):
    """The part of the routed sum that experts ``first .. first + held``
    give (default: the configuration's share): every token through every
    one of them, weighed by its routing weight, which is 0 where the token
    did not choose the expert."""
    first = int(cfg["first_expert"]) if first is None else first
    held = counts.experts_held(cfg) if held is None else held
    experts, weights = route(cfg, p, prefix, x)
    xq = q(x)

    @jax.checkpoint
    def of_expert(e, w1, w3, w2):
        weight = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return weight[..., None] * _gated(xq, w1, w3, w2, q)

    y, _ = jax.lax.scan(
        lambda y, of: (y + of_expert(*of), None), jnp.zeros_like(x),
        (jnp.arange(held), *(p[prefix + f"moe.{w}"][:held] for w in ("w1", "w3", "w2"))),
    )
    return y


def dense_ffn(cfg, p, prefix, x, q):
    w = [p[prefix + f"ffn.{n}"] for n in ("w1", "w3", "w2")]
    return _by_tokens(lambda xb: _gated(q(xb), *w, q), x)


def shared_ffn(cfg, p, prefix, x, q):
    """The shared experts: every token, on every chip alike."""
    w = [p[prefix + f"shared.{n}"] for n in ("w1", "w3", "w2")]
    return _by_tokens(lambda xb: _gated(q(xb), *w, q), x)


def layer_out(cfg, p, prefix, x, dense, q):
    """One layer, each part recomputed on its own in the backward pass, so
    that only one part's float32 intermediates exist at a time."""
    eps = float(cfg["rms_norm_eps"])

    def part(f):
        return jax.checkpoint(lambda x, p: f(cfg, p, prefix, x, q))

    x = x + part(_attention)(_rmsnorm(x, p[prefix + "in_norm"], eps), p)
    normed = _rmsnorm(x, p[prefix + "post_norm"], eps)
    if dense:
        return x + part(dense_ffn)(normed, p)
    return x + part(shared_ffn)(normed, p) + part(routed_ffn)(normed, p)


def hidden(cfg: dict, params, tokens, quant: Optional[str] = None):
    """The final normed activations ``[rows, seq, hidden]`` of ``tokens
    [rows, seq]``."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    x = params["embed"][tokens]
    for i, dense in counts.layers(cfg):
        prefix = f"l{i}."
        layer = jax.checkpoint(
            lambda x, p, prefix=prefix, dense=dense: layer_out(cfg, p, prefix, x, dense, q)
        )
        x = layer(x, {k: v for k, v in params.items() if k.startswith(prefix)})
    return _rmsnorm(x, params["final_norm"], float(cfg["rms_norm_eps"]))


def loss_sum(cfg: dict, params, tokens, quant: Optional[str] = None):
    """Summed over the sequences (not their mean), each sequence's mean
    cross-entropy of position ``t``'s logits against token ``t + 1``. The
    logits a block of positions at a time."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    x = hidden(cfg, params, tokens, quant)
    seq = x.shape[1]
    head = q(params["head"])
    targets = jnp.roll(tokens, -1, axis=1)
    counted = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
    block = next(b for b in range(min(TOKEN_BLOCK, seq), 0, -1) if seq % b == 0)

    @jax.checkpoint
    def of_block(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=1)
        tb = jax.lax.dynamic_slice_in_dim(targets, start, block, axis=1)
        cb = jax.lax.dynamic_slice_in_dim(counted, start, block)
        logits = q(xb) @ head
        picked = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * cb)

    return jnp.sum(jax.lax.map(of_block, jnp.arange(0, seq, block))) / (seq - 1)


def batch_of(cfg: dict, rows):
    """The reference's batch from the files' rows of a batch's keys
    (``{column: numpy [rows, ..]}``): the token ids ``[rows, seq]``."""
    return np.asarray(rows[counts.token_column(cfg)], np.int32)


class Reference(_LeanFollower):
    """Follows the train step from the seed's weights over batches of
    ``batch_of``, one sequence a block; ``quant`` names the control.
    ``follow`` is ``families/laguna/reference.py``'s (the benchmark's Adam
    with two arrays fewer on the device while a gradient is taken); the
    loss is this family's."""

    def __init__(self, cfg: dict, quant: Optional[str] = None):
        loss = lambda params, block: loss_sum(cfg, params, block, quant)  # noqa: E731
        AdamFollower.__init__(self, cfg["optimizer"], loss, BLOCK_ROWS)
        self._first_block = jax.jit(jax.value_and_grad(loss))
