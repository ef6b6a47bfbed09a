"""What the sequence models share (``lfm2_moe.py``, ``laguna.py``,
``phi4flash.py``, ``keye.py``, ``kimi.py``): the parts of a pre-norm
residual layer, the language model around a stack of recomputed layers,
and its loss.

* :class:`RMSNorm`, :class:`LayerNorm`; :class:`Rope` and :func:`rotary` (the half-split
  convention, plain or YaRN frequencies, on all or on the first dimensions
  of a head);
* :class:`Attention`: grouped-query causal attention through
  ``ops/flash_attention.py``, full or over a sliding window;
* :class:`LatentAttention`: keys and values through a low-rank latent, a
  rotary key part shared by every head;
* :class:`DenseFFN` (the gated three-matrix form) and :class:`ExpertFFN`
  (the experts ONE chip holds of a routed layer, ``ops/moe.py``);
* :class:`SequenceLM`: embedding, the layers (each recomputed in the
  backward pass but for what its model's policy keeps; tensors that a layer
  hands on to later ones pass from layer to layer beside the stream), final
  norm, the head over the vocabulary rows held (a matrix of its own, or the
  embedding's where the model ties them), :func:`next_token_loss`, and,
  where the model has expert layers, the step's counters of their load.

Parameters are float32, compute is ``dtype`` (bfloat16 on the chip).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_shuffling_data_loader_tpu.ops import moe
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    flash_attention,
    grid_steps,
)


def fan_in(shape, fan_in_axis=-2):
    """Normal initializer of deviation ``1 / sqrt(fan_in)``."""
    return nn.initializers.normal(stddev=1.0 / math.sqrt(shape[fan_in_axis]))


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (x32 * inv * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """Mean and variance over the last axis, a learned scale and bias."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(
            jnp.mean(centred * centred, axis=-1, keepdims=True) + self.eps
        )
        return (centred * inv * scale + bias).astype(self.dtype)


# -- rotary positions --------------------------------------------------------------


def yarn_bounds(
    dim: int, theta: float, original_length: int, beta_fast: float,
    beta_slow: float,
) -> Tuple[int, int]:
    """``(low, high)``: the pair indices between which YaRN's ramp runs.
    ``c(r) = dim ln(original_length / (2 pi r)) / (2 ln theta)`` is the
    index of the pair that turns ``r`` times over the original length;
    pairs under ``low`` (more than ``beta_fast`` turns) keep their
    frequency, pairs over ``high`` (fewer than ``beta_slow``) are
    interpolated."""

    def pair(turns: float) -> float:
        return dim * math.log(original_length / (2 * math.pi * turns)) / (
            2 * math.log(theta)
        )

    return (
        max(math.floor(pair(beta_fast)), 0),
        min(math.ceil(pair(beta_slow)), dim - 1),
    )


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary positions of one kind of layer. The first ``dim`` dimensions
    of each head turn (the rest pass through), pair ``i`` at ``theta **
    (-2 i / dim)`` a position; with ``factor`` > 1 (YaRN) the slow pairs
    are interpolated by it over a linear ramp (:func:`yarn_bounds`), and
    cos and sin are multiplied by ``attention_factor``."""

    dim: int
    theta: float
    factor: float = 1.0
    original_length: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self) -> jax.Array:
        """``[dim / 2]`` float32: the angle each pair turns a position."""
        freq = 1.0 / (
            self.theta ** (jnp.arange(0, self.dim, 2, dtype=jnp.float32) / self.dim)
        )
        if self.factor == 1.0:
            return freq
        low, high = yarn_bounds(
            self.dim, self.theta, self.original_length, self.beta_fast,
            self.beta_slow,
        )
        ramp = jnp.clip(
            (jnp.arange(self.dim // 2, dtype=jnp.float32) - low)
            / max(high - low, 1e-3),
            0.0, 1.0,
        )
        return ramp * freq / self.factor + (1.0 - ramp) * freq


def rotary(x: jax.Array, rope: Rope) -> jax.Array:
    """Rotary positions on ``[batch, seq, heads, head_dim]`` (the
    half-split convention: of the ``rope.dim`` dimensions that turn,
    dimension ``i`` turns with ``i + rope.dim / 2``), in float32."""
    seq, dim = x.shape[1], rope.dim
    freq = rope.inv_freq()
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if rope.attention_factor != 1.0:
        cos, sin = cos * rope.attention_factor, sin * rope.attention_factor
    turned, passed = x.astype(jnp.float32), []
    if dim < x.shape[-1]:
        turned, passed = turned[..., :dim], [turned[..., dim:]]
    x1, x2 = jnp.split(turned, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, *passed], axis=-1
    ).astype(x.dtype)


# -- the operators and the FFNs ----------------------------------------------------


class Attention(nn.Module):
    """Grouped-query causal attention: ``heads`` query heads over
    ``kv_heads`` key/value heads of ``head_dim``, rotary positions on q and
    k (after an RMS norm on each head's q and k where ``qk_norm_eps`` is
    given), every key up to the query's own or the last ``window`` of
    them, an output projection. ``scope_name`` names its operations in a
    trace.

    Called with ``selected`` (``ops/sparse_attention.py``'s words), a query
    sees only the causal keys the selection keeps, and the call returns
    ``(y, q, k, lse)``: beside the output, the heads' q and k as the
    kernels read them and the log-sum-exp of each query's scores (``[b, h,
    t]``), for an indexer's loss."""

    heads: int
    kv_heads: int
    head_dim: int
    rope: Rope
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int
    qk_norm_eps: Optional[float] = None
    window: Optional[int] = None
    scope_name: str = "attention"

    @nn.compact
    def __call__(self, x, selected=None):
        h, d = x.shape[-1], self.head_dim
        nq, nkv = self.heads, self.kv_heads
        wq = self.param("q_proj", fan_in((h, nq * d)), (h, nq * d))
        wk = self.param("k_proj", fan_in((h, nkv * d)), (h, nkv * d))
        wv = self.param("v_proj", fan_in((h, nkv * d)), (h, nkv * d))
        wo = self.param("out_proj", fan_in((nq * d, h)), (nq * d, h))
        b, t, _ = x.shape
        with jax.named_scope(self.scope_name):
            q = jnp.dot(x, wq.astype(self.dtype)).reshape(b, t, nq, d)
            k = jnp.dot(x, wk.astype(self.dtype)).reshape(b, t, nkv, d)
            v = jnp.dot(x, wv.astype(self.dtype)).reshape(b, t, nkv, d)
            if self.qk_norm_eps is not None:
                q = RMSNorm(self.qk_norm_eps, self.dtype, name="q_norm")(q)
                k = RMSNorm(self.qk_norm_eps, self.dtype, name="k_norm")(k)
            q = rotary(q, self.rope)
            k = rotary(k, self.rope)
            out = flash_attention(
                q, k, v, causal=True, use_pallas=self.use_pallas,
                interpret=self.interpret,
                block_q=self.block_q, block_k=self.block_k,
                window=self.window, selected=selected,
            )
            if selected is not None:
                out, lse = out
            y = jnp.dot(out.reshape(b, t, nq * d), wo.astype(self.dtype))
            return y if selected is None else (y, q, k, lse)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2's, the query projected
    straight from ``x``): ``heads`` query heads of ``nope_dim + rope_dim``;
    ``[c ; k_rope] = W_kva x`` with ``c`` the ``kv_rank``-wide latent, RMS
    normed, and ``k_rope`` ONE key of ``rope_dim`` that every head reads;
    ``[k_nope ; v] = W_kvb c`` in heads of ``nope_dim`` and ``value_dim``;
    rotary positions on the query's last ``rope_dim`` dimensions and on
    ``k_rope`` only; causal softmax of ``(q_nope · k_nope + q_rope · k_rope)
    / sqrt(nope_dim + rope_dim)``; an output projection.

    Layout: a query head is ``[nope ; rope]`` (``q_proj``'s columns a head
    at a time, as published), ``kv_a_proj``'s columns ``[latent ; rope]``,
    ``kv_b_proj``'s ``[k_nope ; v]`` a head at a time. The kernels take the
    query whole and the key in its two parts, ``k_rope`` as one head of
    ``[b, t, 1, rope_dim]`` (``flash_attention``'s ``k_shared``): it is never
    repeated to the heads. The low-rank path (``kv_a_proj``, the latent's
    norm, ``kv_b_proj`` and ``k_rope``'s rotary) runs under the scope
    ``latent``, inside ``scope_name``."""

    heads: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    kv_rank: int
    rope: Rope
    norm_eps: float
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int
    scope_name: str = "attention"

    @nn.compact
    def __call__(self, x):
        h, n = x.shape[-1], self.heads
        nope, rope_dim, dv, rank = self.nope_dim, self.rope_dim, self.value_dim, self.kv_rank
        wq = self.param("q_proj", fan_in((h, n * (nope + rope_dim))), (h, n * (nope + rope_dim)))
        wa = self.param("kv_a_proj", fan_in((h, rank + rope_dim)), (h, rank + rope_dim))
        wb = self.param("kv_b_proj", fan_in((rank, n * (nope + dv))), (rank, n * (nope + dv)))
        wo = self.param("out_proj", fan_in((n * dv, h)), (n * dv, h))
        b, t, _ = x.shape
        with jax.named_scope(self.scope_name):
            q = jnp.dot(x, wq.astype(self.dtype)).reshape(b, t, n, nope + rope_dim)
            q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], self.rope)], axis=-1)
            with jax.named_scope("latent"):
                ck = jnp.dot(x, wa.astype(self.dtype))
                c = RMSNorm(self.norm_eps, self.dtype, name="kv_a_norm")(ck[..., :rank])
                k_rope = rotary(ck[..., rank:].reshape(b, t, 1, rope_dim), self.rope)
                kv = jnp.dot(c, wb.astype(self.dtype)).reshape(b, t, n, nope + dv)
            out = flash_attention(
                q, kv[..., :nope], kv[..., nope:], causal=True,
                use_pallas=self.use_pallas, interpret=self.interpret,
                block_q=self.block_q, block_k=self.block_k, k_shared=k_rope,
            )
            return jnp.dot(out.reshape(b, t, n * dv), wo.astype(self.dtype))


class DenseFFN(nn.Module):
    """``W2 (silu(W1 x) * W3 x)`` at ``width``."""

    width: int
    dtype: Any
    scope_name: str = "dense_ffn"

    @nn.compact
    def __call__(self, x):
        h, width = x.shape[-1], self.width
        w1 = self.param("w1", fan_in((h, width)), (h, width))
        w3 = self.param("w3", fan_in((h, width)), (h, width))
        w2 = self.param("w2", fan_in((width, h)), (width, h))
        with jax.named_scope(self.scope_name):
            up = jax.nn.silu(jnp.dot(x, w1.astype(self.dtype))) * jnp.dot(
                x, w3.astype(self.dtype)
            )
            return jnp.dot(up, w2.astype(self.dtype))


@dataclasses.dataclass(frozen=True)
class Experts:
    """One chip's share of a routed layer: ``held`` experts of ``width``
    from ``first`` on, of the ``routed`` that the router scores, ``top_k``
    a token; a ``selection_bias`` enters the choice only; the chosen
    scores are divided by their sum where ``norm_topk`` and multiplied by
    ``scaling`` (``ops/moe.py`` :func:`~..ops.moe.route`)."""

    width: int
    routed: int
    held: int
    first: int
    top_k: int
    selection_bias: bool
    norm_topk: bool = True
    scaling: float = 1.0
    scoring: str = "sigmoid"


class ExpertFFN(nn.Module):
    """Returns ``(y, counts)``: ``load [held]``, ``dropped`` and
    ``fallback`` of :func:`~..ops.moe.experts_ffn`."""

    experts: Experts
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    row_tile: int

    @nn.compact
    def __call__(self, x):
        spec = self.experts
        h, width, held = x.shape[-1], spec.width, spec.held
        gate = self.param("gate", fan_in((h, spec.routed)), (h, spec.routed))
        bias = (
            self.param(
                "expert_bias", nn.initializers.normal(stddev=0.01),
                (spec.routed,),
            )
            if spec.selection_bias
            else None
        )
        w1 = self.param("w1", fan_in((held, h, width)), (held, h, width))
        w3 = self.param("w3", fan_in((held, h, width)), (held, h, width))
        w2 = self.param("w2", fan_in((held, width, h)), (held, width, h))
        tokens = x.reshape(-1, h)
        with jax.named_scope("router"):
            experts, weights = moe.route(
                tokens, gate, bias, spec.top_k, spec.norm_topk, spec.scaling,
                spec.scoring,
            )
        with jax.named_scope("experts"):
            y, load, dropped, fallback = moe.experts_ffn(
                tokens, experts, weights, w1, w3, w2, spec.first,
                spec.routed, tile=self.row_tile,
                use_pallas=self.use_pallas, interpret=self.interpret,
            )
        counts = {"load": load, "dropped": dropped, "fallback": fallback}
        return y.reshape(x.shape), counts


def moe_load_counts(load, dropped, fallback) -> dict:
    """What the ``moe:load`` counter of one step carries, from the step's
    ``[expert layers, experts_held]`` token counts and its ``[expert
    layers]`` counts of assignments left out of the buffer and of layers
    that ran in the worst-case buffer: the fullest expert, the mean, the
    assignments dropped (the layer is built to drop none; this is the
    count that says so), the expert layers, and those of them whose load
    outgrew the bounded buffer. A step without an expert layer (a cut that
    keeps none) counts zeros."""
    return {
        "max": int(load.max()) if load.size else 0,
        "mean": float(load.mean()) if load.size else 0.0,
        "dropped": int(dropped.sum()),
        "layers": int(load.shape[0]),
        "fallback": int(fallback.sum()),
    }


# -- the language model around the layers --------------------------------------------


class SequenceLM(nn.Module):
    """``__call__({"tokens": [batch, seq] int32}) -> (loss, counters)``:
    the mean next-token cross-entropy over the vocabulary rows held, and
    ``{"moe_load": [expert layers, experts_held], "moe_dropped": [expert
    layers], "moe_fallback": [expert layers]}``: the tokens routed to each
    held expert, the assignments left out, and 1 where the layer ran in the
    worst-case buffer; ``{}`` from a model that holds no experts
    (``cfg.experts_held`` 0). A layer whose counts carry a ``"loss"``
    (a scalar: an indexer's loss) adds it to the loss, and one whose counts
    carry a ``"select"`` (``ops/sparse_attention.py`` ``select_counts``)
    adds the layers' sum under ``"sparse_select"``. ``logits=True``
    returns the logits instead (float32 ``[batch, seq, vocab]``: a test's
    size only).

    A model gives ``cfg`` (``vocab_size``, ``hidden_size``, ``norm_eps``,
    ``experts_held``, ``layers()``: what each layer kept is, its published
    index first; ``tie_word_embeddings`` where the head is the embedding's
    own matrix), :meth:`recomputed_layer`, ``build_facts``,
    :meth:`attention_calls` and, where its final norm is not RMS,
    :meth:`final_norm`.
    ``use_pallas`` / ``interpret`` go to the attention and expert kernels
    (None: the kernels on a TPU backend)."""

    cfg: Any
    compute_dtype: Any = jnp.bfloat16
    use_pallas: Optional[bool] = None
    interpret: bool = False
    block_q: int = 512
    block_k: int = 512
    row_tile: int = moe.ROW_TILE

    # How ``parallel/train.py`` drives a model that brings its own loss:
    # one step input (the features, no labels), and the step's counters
    # beside the loss.
    batch_inputs = 1

    @property
    def step_counters(self) -> dict:
        """``{span name: (metrics keys, what the span carries of their
        values)}``: the expert layers' load; nothing from a model that
        holds no experts, whose step returns no such metrics."""
        if not self.cfg.experts_held:
            return {}
        return {
            "moe:load": (
                ("moe_load", "moe_dropped", "moe_fallback"), moe_load_counts
            )
        }

    def recomputed_layer(self, *of_layer) -> nn.Module:
        """The layer that one entry of ``cfg.layers()`` describes, under
        ``nn.remat`` with the model's policy of what is kept. It maps ``x``
        to ``(x, counts)``: an expert layer's ``{"load", "dropped",
        "fallback"}``, a dense layer's ``{}``. A model whose layers hand
        tensors on to later layers maps ``(x, *handed)`` to ``(x, counts,
        *handed)``: what a layer returns after its counts is what the next
        one is called with, and being a recomputed layer's input it is
        kept for the backward pass, not computed again."""
        raise NotImplementedError

    def final_norm(self, dtype) -> nn.Module:
        return RMSNorm(self.cfg.norm_eps, dtype, name="final_norm")

    def attention_calls(self) -> Sequence[Tuple[int, Optional[int]]]:
        """``(query heads, window)`` of the attention kernels' call in
        each attention layer kept."""
        raise NotImplementedError

    def traced_facts(self, features) -> dict:
        """What ``step:build`` can say only of a batch's shape (so when the
        step is traced): the grid steps, and the blocks with work among
        them, of one forward call of every attention layer kept, summed
        (the kernels' grid, also where the XLA path runs in their place)."""
        batch, seq = features["tokens"].shape
        steps = blocks = 0
        for heads, window in self.attention_calls():
            of_head = grid_steps(seq, self.block_q, self.block_k, window=window)
            steps += batch * heads * of_head[0]
            blocks += batch * heads * of_head[1]
        return {"attention_grid_steps": steps, "attention_blocks": blocks}

    def loss_fn(self, params, features):
        """``(loss, counters)`` of one batch of features."""
        return self.apply(params, features)

    @nn.compact
    def __call__(self, features, logits: bool = False):
        cfg = self.cfg
        tokens = features["tokens"]
        dt = self.compute_dtype
        embed = self.param(
            "embed", fan_in((cfg.vocab_size, cfg.hidden_size), -1),
            (cfg.vocab_size, cfg.hidden_size),
        )
        if getattr(cfg, "tie_word_embeddings", False):
            # One matrix: its gradient is the embedding's plus the head's.
            head = embed.T
        else:
            head = self.param(
                "head", fan_in((cfg.hidden_size, cfg.vocab_size)),
                (cfg.hidden_size, cfg.vocab_size),
            )
        with jax.named_scope("embed"):
            x = jnp.take(embed, tokens, axis=0).astype(dt)
        counts, handed, terms = [], (), []
        for of_layer in cfg.layers():
            x, layer_counts, *handed = self.recomputed_layer(*of_layer)(x, *handed)
            if "loss" in layer_counts:
                layer_counts = dict(layer_counts)
                terms.append(layer_counts.pop("loss"))
            if layer_counts:
                counts.append(layer_counts)
        x = self.final_norm(dt)(x)
        none = {"load": (0, cfg.experts_held), "dropped": (0,), "fallback": (0,)}
        counters = {
            f"moe_{name}": jnp.stack([c[name] for c in counts])
            if counts else jnp.zeros(shape, jnp.int32)
            for name, shape in none.items()
        } if cfg.experts_held else {}
        selects = [c["select"] for c in counts if "select" in c]
        if selects:
            counters["sparse_select"] = sum(selects)
        with jax.named_scope("head"):
            head = head.astype(dt)
            if logits:
                return jnp.dot(x, head, preferred_element_type=jnp.float32)
            loss = next_token_loss(x, head, tokens)
        return loss + sum(terms) if terms else loss, counters


def next_token_loss(x: jax.Array, head: jax.Array, tokens: jax.Array):
    """Mean cross-entropy of position ``t``'s logits against token ``t +
    1``, over every position but each sequence's last. One sequence's
    logits at a time, recomputed in the backward pass: ``[seq, vocab]``
    float32 is all that ever exists of them."""
    seq = tokens.shape[1]
    targets = jnp.roll(tokens, -1, axis=1)
    counted = (jnp.arange(seq) < seq - 1).astype(jnp.float32)

    @jax.checkpoint
    def of_sequence(args):
        x_row, target_row = args
        logits = jnp.dot(x_row, head, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, target_row[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * counted)

    total = jnp.sum(jax.lax.map(of_sequence, (x, targets)))
    return total / (tokens.shape[0] * (seq - 1))
