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
and 32 heads of 64), so that the kernel's forward runs once a step, and an
expert layer's routing bookkeeping (``ops/moe.py`` ``ROUTING``: the experts
chosen and the dispatch plan, 2,131,048 B a layer at 4 x 8,192 tokens, 4
choices and 8 experts held), so that ``top_k``, the plan's sort and its
scatters run once a step. The elementwise work (norms, rotary positions,
gates) and the rest of the expert layer (the router's scores, dispatch,
grouped products, combine) run again. The output head's logits are
recomputed one sequence at a time.

The model brings its own loss (``loss_fn``) and its step's counters, which
``parallel/train.py`` picks up: a batch is ``{"tokens": [batch, seq]}`` and
has no label. The layer's parts and the language model around the layers
are ``models/blocks.py``'s, shared with ``models/laguna.py``; the short
convolution, the layer pattern and what is kept are this model's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_shuffling_data_loader_tpu.models.blocks import (
    Attention,
    DenseFFN,
    ExpertFFN,
    Experts,
    RMSNorm,
    Rope,
    SequenceLM,
    fan_in,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
)
from ray_shuffling_data_loader_tpu.ops.moe import ROUTING
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

    @property
    def experts(self) -> Experts:
        return Experts(
            self.moe_intermediate_size, self.num_experts, self.experts_held,
            self.first_expert, self.num_experts_per_tok, self.use_expert_bias,
            self.norm_topk_prob, self.routed_scaling_factor,
        )

    def layers(self) -> Sequence[Tuple[int, str, bool]]:
        """``(published index, op kind, dense FFN?)`` of each layer kept."""
        kept = range(self.first_layer, self.first_layer + self.num_hidden_layers)
        return [
            (i, self.layer_types[i], i < self.num_dense_layers) for i in kept
        ]


class ShortConv(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: Any

    @nn.compact
    def __call__(self, x):
        h = self.cfg.hidden_size
        w_in = self.param("in_proj", fan_in((h, 3 * h)), (h, 3 * h))
        taps = self.param(
            "conv", fan_in((h, self.cfg.conv_L_cache), -1),
            (h, self.cfg.conv_L_cache),
        )
        w_out = self.param("out_proj", fan_in((h, h)), (h, h))
        with jax.named_scope("short_conv"):
            gate_b, gate_c, u = jnp.split(
                jnp.dot(x, w_in.astype(self.dtype)), 3, axis=-1
            )
            y = gate_c * causal_depthwise_conv1d(gate_b * u, taps)
            return jnp.dot(y, w_out.astype(self.dtype))


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
                cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                Rope(cfg.head_dim, cfg.rope_theta), self.dtype,
                self.use_pallas, self.interpret, self.block_q, self.block_k,
                qk_norm_eps=cfg.norm_eps, name="self_attn",
            )(normed)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(x)
        if self.dense:
            ffn = DenseFFN(cfg.intermediate_size, self.dtype, name="feed_forward")
            return x + ffn(normed), {}
        y, counts = ExpertFFN(
            cfg.experts, self.dtype, self.use_pallas, self.interpret,
            self.row_tile, name="feed_forward",
        )(normed)
        return x + y, counts


# What a recomputed layer keeps of its forward pass: the outputs of its plain
# matmuls, the residuals the attention kernel names for its backward (142.6
# MB an attention layer), and an expert layer's routing bookkeeping
# (``ops/moe.py`` ``ROUTING``: the experts chosen and the dispatch plan,
# integers but for a row's weight, 2.1 MB a layer), so that ``top_k``, the
# plan's sort and its scatters run once a step.
KEPT = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(
        ATTENTION_OUT, ATTENTION_STATS, ROUTING
    ),
)


class Lfm2MoeLM(SequenceLM):
    """The LFM2-MoE of one chip's share (:class:`~.blocks.SequenceLM`).
    Every layer is recomputed in the backward pass but for ``KEPT``: its
    plain matmuls' outputs, the attention kernel's output and row
    statistics (142.6 MB an attention layer at 4 x 8,192 tokens) and an
    expert layer's routing and dispatch plan (2.1 MB), so that neither that
    kernel's forward nor the plan's sort and scatters are."""

    cfg: Lfm2MoeConfig

    @property
    def build_facts(self) -> dict:
        """What ``step:build`` says of the step this model makes."""
        layers = self.cfg.layers()
        return {
            "model": "lfm2_moe",
            "experts_held": self.cfg.experts_held,
            "layers": self.cfg.num_hidden_layers,
            # The layers whose attention residuals ``KEPT`` holds on to,
            # and the expert layers whose routing and plan it does.
            "attention_kept": sum(kind == "full_attention" for _, kind, _ in layers),
            "routing_kept": sum(not dense for *_, dense in layers),
        }

    def attention_calls(self):
        heads = self.cfg.num_attention_heads
        return [
            (heads, None)
            for _, kind, _ in self.cfg.layers() if kind == "full_attention"
        ]

    def recomputed_layer(self, index, kind, dense) -> nn.Module:
        return nn.remat(Layer, policy=KEPT)(
            self.cfg, kind, dense, self.compute_dtype, self.use_pallas,
            self.interpret, self.block_q, self.block_k, self.row_tile,
            name=f"layer_{index}",
        )
