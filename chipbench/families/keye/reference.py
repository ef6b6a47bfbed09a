"""Plain reference of one chip's share of the Keye-VL-2.0 language model's
train step, and the weights both sides start from.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
following the layer equations (``model_type`` ``KeyeVL2``, DeepSeek Sparse
Attention's indexer from ``sa_config``; no bias anywhere but the indexer's
key norm):

* every layer: ``x = x + attn(rmsnorm(x)); x = x + moe(rmsnorm(x))``;
* ``attn``: ``q = W_q x``, ``k = W_k x``, ``v = W_v x`` in heads of
  ``head_dim``, an RMS norm with a learned scale on each head of q and k,
  MRoPE (``rope_scaling.mrope_section``: the frequency pairs split among the
  time, height and width position ids, all three the token's position for
  text) at ``rope_theta``, half-split; scores ``q k^T / sqrt(head_dim)``
  over the query's selected keys ``S_t`` only, softmax, ``W_o``;
* the indexer, from ``x`` with no gradient: ``q^I = W^I_q x`` in
  ``indexer_num_heads`` heads of ``indexer_head_dim``, ``k^I =
  LayerNorm(W^I_k x)`` (one head, a learned scale and bias), ``w = W^I_w x /
  sqrt(heads * dim)``; the first ``indexer_rope_head_dim`` dimensions of
  ``q^I`` and ``k^I`` turned by rotary at ``rope_theta``; ``I[t, s] =
  sum_j w[t, j] relu(q^I[t, j] . k^I[s])``; ``S_t``: ``lax.top_k`` of the
  causal row, ``min(topk, t + 1)`` keys (ties to the lower index);
* the indexer's loss ``mean_t sum_{s in S_t} pbar (log pbar - log
  softmax_{S_t}(I[t]))``, ``pbar`` the attention's probabilities averaged
  over the heads with no gradient, added to the next-token loss;
* ``moe``: ``softmax(W_r x)`` over all the published experts, the
  ``num_experts_per_tok`` largest chosen and divided by their sum; of the
  routed sum only the experts held here are added up (every token goes
  through every held expert and is masked by its weight: no sorting, no
  kernels);
* final RMS norm, the untied head over the vocabulary rows held, next-token
  cross-entropy, the mean over a sequence's positions but its last.

Attention is computed a block of queries at a time over the selected keys
gathered for it (no mask over the square), the experts one at a time, the
head's loss a block of positions at a time, and every layer and part is
recomputed in the backward pass, so that one 16,384-token sequence fits in
float32 beside the weights, Adam's moments and the gradients. It imports
nothing of the program and takes nothing the program has made. Its own
selection is the one it trains on: where a near tie falls otherwise in the
program, the two attend to a key apart (``matched`` counts how often).

``quant="fp8"`` is the control: the same mathematics with every matmul
operand of the attention, the experts and the head (activations, weights,
q, k, v and the probabilities) rounded to float8 e4m3 under a per-tensor
power-of-two scale, gradients passed straight through: the nearest
precision below the bfloat16 compute the configuration states. The router
and the indexer score in float32 on both, as the configuration states.
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
QUERY_BLOCK = 64
HEAD_BLOCK = 2048
# The control's ``quant``: the nearest precision below the stated bfloat16.
CONTROL = "fp8"


def init_params(cfg: dict, seed: int, sharding=None):
    """Weights from the seed, float32, made on the device in one jitted
    call: matrices normal with deviation 1/sqrt(fan_in) (the embedding's
    rows 1/sqrt(hidden)), norms one, the indexer's key-norm bias zero. A
    flat dict by leaf name."""
    shapes = counts.leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            if name.endswith("bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
                continue
            fan_in = shape[-1] if name == "embed" else shape[-2]
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) / np.sqrt(fan_in)
        return out

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * scale + bias


def mrope_table(cfg: dict, positions):
    """``(cos, sin [seq, head_dim / 2])`` of MRoPE: frequency pair ``i`` at
    ``rope_theta ** (-2 i / head_dim)`` turns with the position id of its
    section (``mrope_section``: time, height, width); ``positions [3,
    seq]``."""
    d = int(cfg["head_dim"])
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (np.arange(0, d, 2) / d)
    sections = cfg["rope_scaling"]["mrope_section"]
    which = np.repeat(np.arange(len(sections)), sections)  # [d / 2]
    pos = np.asarray(positions, np.float64)[which]  # [d / 2, seq]
    angle = pos.T * inv_freq[None, :]
    return jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)


def rope_table(theta: float, dim: int, seq: int):
    """``(cos, sin [seq, dim / 2])`` of plain rotary over ``dim``."""
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    angle = np.arange(seq)[:, None] * inv_freq[None, :]
    return jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)


def _rotary(x, table):
    """``x [rows, seq, heads, d]``: of the first ``2 * cos.shape[-1]``
    dimensions, dimension ``i`` turns with ``i + half``; the rest pass."""
    cos, sin = table
    half = cos.shape[-1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def indexer(cfg, p, prefix, x):
    """``(q^I [rows, seq, heads, dim], k^I [rows, seq, dim], w [rows, seq,
    heads])`` from ``x`` (already without gradient), float32."""
    rows, seq, _ = x.shape
    heads, dim, _ = counts.index_sizes(cfg)
    table = rope_table(float(cfg["rope_theta"]), int(cfg["indexer_rope_head_dim"]), seq)
    qi = _rotary((x @ p[prefix + "idx.q"]).reshape(rows, seq, heads, dim), table)
    ki = _layernorm(
        x @ p[prefix + "idx.k"], p[prefix + "idx.k_norm"], p[prefix + "idx.k_bias"],
        float(cfg.get("indexer_norm_eps", 1e-6)),
    )
    ki = _rotary(ki[:, :, None], table)[:, :, 0]
    return qi, ki, (x @ p[prefix + "idx.w"]) / np.sqrt(heads * dim)


def _selection(qi, ki, w, start, block, topk):
    """``(keys [rows, block, K], valid, scores)`` of queries ``start ..
    start + block``: the indexer's row against every key, the causal ones
    kept, ``lax.top_k`` of them."""
    seq = ki.shape[1]
    q = jax.lax.dynamic_slice_in_dim(qi, start, block, axis=1)
    wb = jax.lax.dynamic_slice_in_dim(w, start, block, axis=1)
    z = jnp.einsum("rqhd,rkd->rqhk", q, ki)
    scores = jnp.einsum("rqhk,rqh->rqk", jax.nn.relu(z), wb)
    pos = start + jnp.arange(block)
    scores = jnp.where(pos[:, None] >= jnp.arange(seq)[None, :], scores, -jnp.inf)
    picked, keys = jax.lax.top_k(scores, min(topk, seq))
    return keys, keys <= pos[None, :, None], picked


def _attention(cfg, p, prefix, x, q):
    """``(out [rows, seq, hidden], indexer loss summed over the rows)``: a
    block of queries at a time against the keys its rows selected,
    gathered."""
    rows, seq, hidden = x.shape
    d = int(cfg["head_dim"])
    heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    group = heads // kv_heads
    eps = float(cfg["rms_norm_eps"])
    _, _, topk = counts.index_sizes(cfg)
    table = mrope_table(cfg, np.tile(np.arange(seq), (3, 1)))
    qi, ki, w = indexer(cfg, p, prefix, jax.lax.stop_gradient(x))
    xq = q(x)
    qs = (xq @ q(p[prefix + "attn.q"])).reshape(rows, seq, heads, d)
    ks = (xq @ q(p[prefix + "attn.k"])).reshape(rows, seq, kv_heads, d)
    vs = (xq @ q(p[prefix + "attn.v"])).reshape(rows, seq, kv_heads, d)
    qs = _rotary(_rmsnorm(qs, p[prefix + "attn.q_norm"], eps), table)
    ks = _rotary(_rmsnorm(ks, p[prefix + "attn.k_norm"], eps), table)
    qs, ks, vs = q(qs), q(ks), q(vs)
    block = next(b for b in range(min(QUERY_BLOCK, seq), 0, -1) if seq % b == 0)
    take = jax.vmap(lambda a, i: a[i])  # a row's [seq, ..] at [block, K] keys

    @jax.checkpoint
    def of_block(start):
        keys, valid, picked = _selection(qi, ki, w, start, block, topk)
        qb = jax.lax.dynamic_slice_in_dim(qs, start, block, axis=1)
        qb = qb.reshape(rows, block, kv_heads, group, d)
        kb, vb = take(ks, keys), take(vs, keys)  # [rows, block, K, kv, d]
        s = jnp.einsum("rqhgd,rqkhd->rqhgk", qb, kb) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(valid[:, :, None, None], s, -1e30), axis=-1)
        out = jnp.einsum("rqhgk,rqkhd->rqhgd", q(probs), vb)
        pbar = jax.lax.stop_gradient(jnp.mean(probs, axis=(2, 3)))
        log_soft = jax.nn.log_softmax(jnp.where(valid, picked, -jnp.inf), axis=-1)
        safe = jnp.where(pbar > 0, pbar, 1.0)
        terms = jnp.where(valid & (pbar > 0),
                          pbar * (jnp.log(safe) - jnp.where(valid, log_soft, 0.0)), 0.0)
        return out.reshape(rows, block, heads * d), jnp.sum(terms)

    out, loss = jax.lax.map(of_block, jnp.arange(0, seq, block))
    out = jnp.moveaxis(out, 0, 1).reshape(rows, seq, heads * d)
    return q(out) @ q(p[prefix + "attn.o"]), jnp.sum(loss) / seq


def route(cfg, p, prefix, x):
    """``(experts [.., top_k], weights [.., top_k])`` over all the
    published experts, in float32 whatever the control rounds: softmax
    scores, the largest chosen, renormalised."""
    scores = jax.nn.softmax(x @ p[prefix + "moe.gate"], axis=-1)
    weights, experts = jax.lax.top_k(scores, int(cfg["num_experts_per_tok"]))
    if cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts, weights


def routed_ffn(cfg, p, prefix, x, q, first: Optional[int] = None,
               held: Optional[int] = None):
    """The part of the routed sum that experts ``first .. first + held``
    give (default: the configuration's share): every token through every
    one of them, weighed by its routing weight, 0 where the token did not
    choose the expert."""
    first = int(cfg["first_expert"]) if first is None else first
    held = int(cfg["num_experts"]) if held is None else held
    experts, weights = route(cfg, p, prefix, x)
    xq = q(x)

    @jax.checkpoint
    def of_expert(e, w1, w3, w2):
        weight = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        up = q(jax.nn.silu(xq @ q(w1)) * (xq @ q(w3)))
        return weight[..., None] * (up @ q(w2))

    y, _ = jax.lax.scan(
        lambda y, of: (y + of_expert(*of), None), jnp.zeros_like(x),
        (jnp.arange(held), *(p[prefix + f"moe.{w}"][:held] for w in ("w1", "w3", "w2"))),
    )
    return y


def hidden(cfg: dict, params, tokens, quant: Optional[str] = None):
    """``(x, indexer loss)``: the final normed activations ``[rows, seq,
    hidden]`` of ``tokens [rows, seq]`` and the layers' indexer losses,
    summed over the rows."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens]
    index_loss = 0.0
    for i in counts.layer_indices(cfg):
        prefix = f"l{i}."

        @jax.checkpoint
        def layer(x, p, prefix=prefix):
            attn = jax.checkpoint(lambda x, p: _attention(cfg, p, prefix, x, q))
            moe = jax.checkpoint(lambda x, p: routed_ffn(cfg, p, prefix, x, q))
            y, loss = attn(_rmsnorm(x, p[prefix + "in_norm"], eps), p)
            x = x + y
            return x + moe(_rmsnorm(x, p[prefix + "post_norm"], eps), p), loss

        x, loss = layer(x, {k: v for k, v in params.items() if k.startswith(prefix)})
        index_loss = index_loss + loss
    return _rmsnorm(x, params["final_norm"], eps), index_loss


def loss_sum(cfg: dict, params, tokens, quant: Optional[str] = None):
    """Summed over the sequences (not their mean): each sequence's mean
    cross-entropy of position ``t``'s logits against token ``t + 1``, plus
    its indexer losses. The logits a block of positions at a time."""
    q = _fake_fp8 if quant == "fp8" else (lambda x: x)
    x, index_loss = hidden(cfg, params, tokens, quant)
    rows, seq, _ = x.shape
    head = q(params["head"])
    targets = jnp.roll(tokens, -1, axis=1)
    counted = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
    block = next(b for b in range(min(HEAD_BLOCK, seq), 0, -1) if seq % b == 0)

    @jax.checkpoint
    def of_block(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=1)
        tb = jax.lax.dynamic_slice_in_dim(targets, start, block, axis=1)
        cb = jax.lax.dynamic_slice_in_dim(counted, start, block)
        logits = q(xb) @ head
        picked = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * cb)

    ce = jnp.sum(jax.lax.map(of_block, jnp.arange(0, seq, block)))
    return ce / (seq - 1) + index_loss


def selection(cfg: dict, params, tokens, layer: int):
    """``[rows, seq, seq]`` bool: the keys layer ``layer``'s indexer keeps
    for each query, from the reference's own activations (a test's size)."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens]
    for i in counts.layer_indices(cfg):
        prefix = f"l{i}."
        p = {k: v for k, v in params.items() if k.startswith(prefix)}
        normed = _rmsnorm(x, p[prefix + "in_norm"], eps)
        if i == layer:
            qi, ki, w = indexer(cfg, p, prefix, normed)
            seq = tokens.shape[1]
            keys, valid, _ = _selection(qi, ki, w, 0, seq, counts.index_sizes(cfg)[2])
            out = jnp.zeros((tokens.shape[0], seq, seq), bool)
            r = jnp.arange(tokens.shape[0])[:, None, None]
            return out.at[r, jnp.arange(seq)[None, :, None], keys].set(valid)
        y, _ = _attention(cfg, p, prefix, normed, lambda v: v)
        x = x + y
        x = x + routed_ffn(cfg, p, prefix, _rmsnorm(x, p[prefix + "post_norm"], eps),
                           lambda v: v)
    raise KeyError(f"layer {layer} is not kept")


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
