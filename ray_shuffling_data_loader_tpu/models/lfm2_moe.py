"""LFM2-MoE: gated short convolutions, grouped-query attention and a sparse
mixture of experts, as one chip of an expert-parallel deployment runs it.

The layer equations (``model_type`` ``lfm2_moe``; every size comes from the
configuration):

* every layer: ``x = x + op(rmsnorm(x)); x = x + ffn(rmsnorm(x))``;
* ``conv`` op: ``B, C, h = split(W_in x)``; ``y = C * causal_depthwise_conv1d(
  B * h, taps)``; ``out = W_out y``;
* ``full_attention`` op: grouped-query causal softmax attention, RMS norm on
  each head's q and k, rotary positions on q and k, an output projection;
* dense FFN (the first ``num_dense_layers`` published layers): ``W2 (silu(W1
  x) * W3 x)``;
* expert FFN (every other layer): sigmoid scores over ALL ``num_experts``,
  the ``num_experts_per_tok`` largest ``score + expert_bias`` chosen, their
  own scores normalised as weights; this chip adds up the experts it holds
  (``first_expert`` .. ``first_expert + experts_held``) and leaves the rest
  out (``ops/moe.py``);
* embedding, final RMS norm, output head over the vocabulary rows held
  here; next-token cross-entropy, the mean over positions.

Parameters are float32, compute is bfloat16 (``compute_dtype``). Every layer
is recomputed in the backward pass (``nn.remat``) but for what costs most to
make again: what stays from the forward pass is a layer's input, the outputs
of its plain matmuls (the projections of the operator and of the dense FFN)
and what the attention kernel's backward reads of its forward (``KEPT``: the
output and the softmax row statistics, ``2 * (heads * head_dim + 4 * heads)``
bytes a token in bfloat16: 142.6 MB an attention layer at 4 x 8,192 tokens
and 32 heads of 64), so that the kernel's forward runs once a step. The
elementwise work (norms, rotary positions, gates) and the whole expert layer
(routing, dispatch, grouped products, combine) run again. The output head's
logits are recomputed one sequence at a time.

The model brings its own loss (``loss_fn``) and its step's counters, which
``parallel/train.py`` picks up: a batch is ``{"tokens": [batch, seq]}`` and
has no label.
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
    ATTENTION_OUT,
    ATTENTION_STATS,
    flash_attention,
)
from ray_shuffling_data_loader_tpu.ops.short_conv import causal_depthwise_conv1d


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Sizes under the names of the published ``config.json``; what one
    chip's share adds: ``experts_held`` / ``first_expert`` (of
    ``num_experts`` routed over), ``first_layer`` (the published index of
    the first layer kept; ``num_hidden_layers`` follow), ``vocab_size`` as
    the rows of the vocabulary held."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: int
    first_expert: int = 0
    first_layer: int = 0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    rope_theta: float = 1_000_000.0

    @classmethod
    def from_dict(cls, cfg: dict) -> "Lfm2MoeConfig":
        """From a dict of the published keys (a configuration file's top
        level); ``rope_parameters.rope_theta`` is read where it lies."""
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in cfg.items() if k in names}
        kwargs["layer_types"] = tuple(cfg["layer_types"])
        rope = cfg.get("rope_parameters") or {}
        if "rope_theta" in rope:
            kwargs["rope_theta"] = float(rope["rope_theta"])
        kwargs.setdefault("experts_held", cfg["num_experts"])
        return cls(**kwargs)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layers(self) -> Sequence[Tuple[int, str, bool]]:
        """``(published index, op kind, dense FFN?)`` of each layer kept."""
        kept = range(self.first_layer, self.first_layer + self.num_hidden_layers)
        return [
            (i, self.layer_types[i], i < self.num_dense_layers) for i in kept
        ]


def _fan_in(shape, fan_in_axis=-2):
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


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on ``[batch, seq, heads, head_dim]`` (the
    half-split convention: dimension ``i`` turns with ``i + head_dim/2``),
    in float32."""
    seq, dim = x.shape[1], x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


class ShortConv(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: Any

    @nn.compact
    def __call__(self, x):
        h = self.cfg.hidden_size
        w_in = self.param("in_proj", _fan_in((h, 3 * h)), (h, 3 * h))
        taps = self.param(
            "conv", _fan_in((h, self.cfg.conv_L_cache), -1),
            (h, self.cfg.conv_L_cache),
        )
        w_out = self.param("out_proj", _fan_in((h, h)), (h, h))
        with jax.named_scope("short_conv"):
            gate_b, gate_c, u = jnp.split(
                jnp.dot(x, w_in.astype(self.dtype)), 3, axis=-1
            )
            y = gate_c * causal_depthwise_conv1d(gate_b * u, taps)
            return jnp.dot(y, w_out.astype(self.dtype))


class Attention(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        wq = self.param("q_proj", _fan_in((h, nq * d)), (h, nq * d))
        wk = self.param("k_proj", _fan_in((h, nkv * d)), (h, nkv * d))
        wv = self.param("v_proj", _fan_in((h, nkv * d)), (h, nkv * d))
        wo = self.param("out_proj", _fan_in((nq * d, h)), (nq * d, h))
        b, t, _ = x.shape
        with jax.named_scope("attention"):
            q = jnp.dot(x, wq.astype(self.dtype)).reshape(b, t, nq, d)
            k = jnp.dot(x, wk.astype(self.dtype)).reshape(b, t, nkv, d)
            v = jnp.dot(x, wv.astype(self.dtype)).reshape(b, t, nkv, d)
            q = RMSNorm(cfg.norm_eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, self.dtype, name="k_norm")(k)
            q = rotary(q, cfg.rope_theta)
            k = rotary(k, cfg.rope_theta)
            out = flash_attention(
                q, k, v, causal=True, use_pallas=self.use_pallas,
                interpret=self.interpret,
                block_q=self.block_q, block_k=self.block_k,
            )
            return jnp.dot(out.reshape(b, t, nq * d), wo.astype(self.dtype))


class DenseFFN(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: Any

    @nn.compact
    def __call__(self, x):
        h, width = self.cfg.hidden_size, self.cfg.intermediate_size
        w1 = self.param("w1", _fan_in((h, width)), (h, width))
        w3 = self.param("w3", _fan_in((h, width)), (h, width))
        w2 = self.param("w2", _fan_in((width, h)), (width, h))
        with jax.named_scope("dense_ffn"):
            up = jax.nn.silu(jnp.dot(x, w1.astype(self.dtype))) * jnp.dot(
                x, w3.astype(self.dtype)
            )
            return jnp.dot(up, w2.astype(self.dtype))


class ExpertFFN(nn.Module):
    """Returns ``(y, counts)``: ``load [experts_held]``, ``dropped`` and
    ``fallback`` of :func:`~..ops.moe.experts_ffn`."""

    cfg: Lfm2MoeConfig
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    row_tile: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, width, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.experts_held
        gate = self.param("gate", _fan_in((h, cfg.num_experts)), (h, cfg.num_experts))
        bias = (
            self.param(
                "expert_bias", nn.initializers.normal(stddev=0.01),
                (cfg.num_experts,),
            )
            if cfg.use_expert_bias
            else None
        )
        w1 = self.param("w1", _fan_in((held, h, width)), (held, h, width))
        w3 = self.param("w3", _fan_in((held, h, width)), (held, h, width))
        w2 = self.param("w2", _fan_in((held, width, h)), (held, width, h))
        tokens = x.reshape(-1, h)
        with jax.named_scope("router"):
            experts, weights = moe.route(
                tokens, gate, bias, cfg.num_experts_per_tok,
                cfg.norm_topk_prob, cfg.routed_scaling_factor,
            )
        with jax.named_scope("experts"):
            y, load, dropped, fallback = moe.experts_ffn(
                tokens, experts, weights, w1, w3, w2, cfg.first_expert,
                cfg.num_experts, tile=self.row_tile,
                use_pallas=self.use_pallas, interpret=self.interpret,
            )
        counts = {"load": load, "dropped": dropped, "fallback": fallback}
        return y.reshape(x.shape), counts


class Layer(nn.Module):
    """One published layer: its operator and its FFN, each behind an RMS
    norm and added to the stream. Returns ``(x, counts)``: an expert
    layer's ``{"load", "dropped", "fallback"}``
    (:func:`~..ops.moe.experts_ffn`), a dense layer's ``{}``."""

    cfg: Lfm2MoeConfig
    kind: str
    dense: bool
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int
    row_tile: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(x)
        if self.kind == "conv":
            x = x + ShortConv(cfg, self.dtype, name="conv")(normed)
        elif self.kind == "full_attention":
            x = x + Attention(
                cfg, self.dtype, self.use_pallas, self.interpret,
                self.block_q, self.block_k, name="self_attn",
            )(normed)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(x)
        if self.dense:
            return x + DenseFFN(cfg, self.dtype, name="feed_forward")(normed), {}
        y, counts = ExpertFFN(
            cfg, self.dtype, self.use_pallas, self.interpret, self.row_tile,
            name="feed_forward",
        )(normed)
        return x + y, counts


def moe_load_counts(load, dropped, fallback) -> dict:
    """What the ``moe:load`` counter of one step carries, from the step's
    ``[expert layers, experts_held]`` token counts and its ``[expert
    layers]`` counts of assignments left out of the buffer and of layers
    that ran in the worst-case buffer: the fullest expert, the mean, the
    assignments dropped (the layer is built to drop none; this is the
    count that says so), the expert layers, and those of them whose load
    outgrew the bounded buffer."""
    return {
        "max": int(load.max()),
        "mean": float(load.mean()),
        "dropped": int(dropped.sum()),
        "layers": int(load.shape[0]),
        "fallback": int(fallback.sum()),
    }


# What a recomputed layer keeps of its forward pass: the outputs of its plain
# matmuls, and the residuals the attention kernel names for its backward.
KEPT = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(ATTENTION_OUT, ATTENTION_STATS),
)


class Lfm2MoeLM(nn.Module):
    """``__call__({"tokens": [batch, seq] int32}) -> (loss, counters)``:
    the mean next-token cross-entropy over the vocabulary rows held, and
    ``{"moe_load": [expert layers, experts_held], "moe_dropped": [expert
    layers], "moe_fallback": [expert layers]}``: the tokens routed to each
    held expert, the assignments left out, and 1 where the layer ran in the
    worst-case buffer. ``logits=True`` returns the logits instead (float32
    ``[batch, seq, vocab]``: a test's size only).

    ``use_pallas`` / ``interpret`` go to the attention and expert kernels
    (None: the kernels on a TPU backend). Every layer is recomputed in the
    backward pass but for ``KEPT``: its plain matmuls' outputs and the
    attention kernel's output and row statistics (142.6 MB an attention
    layer at 4 x 8,192 tokens), so that kernel's forward is not."""

    cfg: Lfm2MoeConfig
    compute_dtype: Any = jnp.bfloat16
    use_pallas: Optional[bool] = None
    interpret: bool = False
    block_q: int = 512
    block_k: int = 512
    row_tile: int = moe.ROW_TILE

    # How ``parallel/train.py`` drives a model that brings its own loss:
    # one step input (the features, no labels), and the step's counters
    # beside the loss: ``{span name: (metrics keys, what the span carries
    # of their values)}``.
    batch_inputs = 1
    step_counters = {
        "moe:load": (
            ("moe_load", "moe_dropped", "moe_fallback"), moe_load_counts
        )
    }

    @property
    def build_facts(self) -> dict:
        """What ``step:build`` says of the step this model makes."""
        return {
            "model": "lfm2_moe",
            "experts_held": self.cfg.experts_held,
            "layers": self.cfg.num_hidden_layers,
            # The layers whose attention residuals ``KEPT`` holds on to.
            "attention_kept": sum(
                kind == "full_attention" for _, kind, _ in self.cfg.layers()
            ),
        }

    def loss_fn(self, params, features):
        """``(loss, counters)`` of one batch of features."""
        return self.apply(params, features)

    @nn.compact
    def __call__(self, features, logits: bool = False):
        cfg = self.cfg
        tokens = features["tokens"]
        dt = self.compute_dtype
        embed = self.param(
            "embed", _fan_in((cfg.vocab_size, cfg.hidden_size), -1),
            (cfg.vocab_size, cfg.hidden_size),
        )
        head = self.param(
            "head", _fan_in((cfg.hidden_size, cfg.vocab_size)),
            (cfg.hidden_size, cfg.vocab_size),
        )
        with jax.named_scope("embed"):
            x = jnp.take(embed, tokens, axis=0).astype(dt)
        layer_cls = nn.remat(Layer, policy=KEPT)
        counts = []
        for index, kind, dense in cfg.layers():
            x, of_layer = layer_cls(
                cfg, kind, dense, dt, self.use_pallas, self.interpret,
                self.block_q, self.block_k, self.row_tile,
                name=f"layer_{index}",
            )(x)
            if of_layer:
                counts.append(of_layer)
        x = RMSNorm(cfg.norm_eps, dt, name="final_norm")(x)
        none = {"load": (0, cfg.experts_held), "dropped": (0,), "fallback": (0,)}
        counters = {
            f"moe_{name}": jnp.stack([c[name] for c in counts])
            if counts else jnp.zeros(shape, jnp.int32)
            for name, shape in none.items()
        }
        with jax.named_scope("head"):
            head = head.astype(dt)
            if logits:
                return jnp.dot(x, head, preferred_element_type=jnp.float32)
            return next_token_loss(x, head, tokens), counters


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
