"""Laguna: full and sliding-window attention mixed, a routed mixture of
experts with a shared expert beside it, as one chip of an expert-parallel
deployment runs it.

The layer equations (``model_type`` ``laguna``; every size comes from the
configuration; every norm is RMS with a learned scale; no bias anywhere):

* every layer: ``x = x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))``;
* ``attn`` of layer ``i``: ``num_attention_heads_per_layer[i]`` query heads
  over ``num_key_value_heads`` key/value heads of ``head_dim``, rotary
  positions on q and k, softmax over the keys up to the query's own
  (``full_attention``) or over the last ``sliding_window`` of them, its own
  among them (``sliding_attention``), an output projection;
* rotary positions by kind of layer (``rope_parameters``): the sliding
  layers turn the whole head at a plain ``rope_theta``; the full layers
  turn the first ``partial_rotary_factor`` of each head at YaRN's
  frequencies, cos and sin times ``attention_factor``;
* dense FFN (``mlp_layer_types[i] == "dense"``): ``W2 (silu(W1 x) * W3 x)``;
* sparse FFN: the shared expert (the same gated form at
  ``shared_expert_intermediate_size``, every token) plus the routed
  experts: sigmoid scores over ALL ``num_experts``, the
  ``num_experts_per_tok`` largest chosen, their scores divided by their sum
  and multiplied by ``moe_routed_scaling_factor``; this chip adds up the
  experts it holds (``first_expert`` .. ``first_expert + experts_held``) and
  leaves the rest out (``ops/moe.py``). The shared expert is whole on every
  chip;
* embedding, final RMS norm, an untied output head over the vocabulary rows
  held here; next-token cross-entropy, the mean over positions.

The parts are ``models/blocks.py``'s, shared with ``models/lfm2_moe.py``;
the layer, its two kinds of attention and what is kept are this model's.
Every layer is recomputed in the backward pass (``nn.remat``) but for
``KEPT``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax

from ray_shuffling_data_loader_tpu.models.blocks import (
    Attention,
    DenseFFN,
    ExpertFFN,
    Experts,
    RMSNorm,
    Rope,
    SequenceLM,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
)
from ray_shuffling_data_loader_tpu.ops.moe import ROUTING

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Sizes under the names of the published ``config.json``; what one
    chip's share adds: ``experts_held`` / ``first_expert`` (of
    ``num_experts`` routed over), ``first_layer`` (the published index of
    the first layer kept; ``num_hidden_layers`` follow), ``vocab_size`` as
    the rows of the vocabulary held. ``rope_full`` / ``rope_sliding`` are
    ``rope_parameters`` by kind of layer."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_attention_heads_per_layer: Tuple[int, ...]
    num_key_value_heads: int
    head_dim: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_experts: int
    num_experts_per_tok: int
    experts_held: int
    sliding_window: int
    rope_full: Rope
    rope_sliding: Rope
    first_expert: int = 0
    first_layer: int = 0
    moe_routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6

    @classmethod
    def from_dict(cls, cfg: dict) -> "LagunaConfig":
        """From a dict of the published keys (a configuration file's top
        level)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in cfg.items() if k in names}
        for key in (
            "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"
        ):
            kwargs[key] = tuple(cfg[key])
        kwargs.setdefault("experts_held", cfg["num_experts"])

        def rope(kind: str) -> Rope:
            of_kind = cfg["rope_parameters"][kind]
            turned = int(
                cfg["head_dim"] * of_kind.get("partial_rotary_factor", 1)
            )
            if of_kind["rope_type"] == "default":
                return Rope(turned, float(of_kind["rope_theta"]))
            if of_kind["rope_type"] != "yarn":
                raise ValueError(f"unknown rope_type {of_kind['rope_type']!r}")
            return Rope(
                turned, float(of_kind["rope_theta"]),
                factor=float(of_kind["factor"]),
                original_length=int(of_kind["original_max_position_embeddings"]),
                beta_fast=float(of_kind["beta_fast"]),
                beta_slow=float(of_kind["beta_slow"]),
                attention_factor=float(of_kind["attention_factor"]),
            )

        return cls(rope_full=rope(FULL), rope_sliding=rope(SLIDING), **kwargs)

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def experts(self) -> Experts:
        """No selection bias; the chosen scores renormalised, times the
        routed scale."""
        return Experts(
            self.moe_intermediate_size, self.num_experts, self.experts_held,
            self.first_expert, self.num_experts_per_tok, False, True,
            self.moe_routed_scaling_factor,
        )

    def layers(self) -> Sequence[Tuple[int, str, int, bool]]:
        """``(published index, attention kind, query heads, dense FFN?)``
        of each layer kept."""
        kept = range(self.first_layer, self.first_layer + self.num_hidden_layers)
        return [
            (
                i, self.layer_types[i], self.num_attention_heads_per_layer[i],
                self.mlp_layer_types[i] == "dense",
            )
            for i in kept
        ]

    def heads_of(self, kind: str) -> int:
        """Query heads of the layers of ``kind`` among those kept; 0 where
        there is none."""
        return max(
            (heads for _, k, heads, _ in self.layers() if k == kind), default=0
        )


class Layer(nn.Module):
    """One published layer: its attention and its FFN, each behind an RMS
    norm and added to the stream. Returns ``(x, counts)``: a sparse
    layer's ``{"load", "dropped", "fallback"}``
    (:func:`~..ops.moe.experts_ffn`), a dense layer's ``{}``."""

    cfg: LagunaConfig
    kind: str
    heads: int
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
        if self.kind not in (FULL, SLIDING):
            raise ValueError(f"unknown layer type {self.kind!r}")
        sliding = self.kind == SLIDING
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(x)
        x = x + Attention(
            self.heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.rope_sliding if sliding else cfg.rope_full, self.dtype,
            self.use_pallas, self.interpret, self.block_q, self.block_k,
            window=cfg.sliding_window if sliding else None,
            scope_name="attention_window" if sliding else "attention",
            name="self_attn",
        )(normed)
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(x)
        if self.dense:
            ffn = DenseFFN(cfg.intermediate_size, self.dtype, name="feed_forward")
            return x + ffn(normed), {}
        # Every chip computes the shared expert alike; the routed part is
        # this chip's share.
        shared = DenseFFN(
            cfg.shared_expert_intermediate_size, self.dtype, "shared_expert",
            name="shared_expert",
        )(normed)
        routed, counts = ExpertFFN(
            cfg.experts, self.dtype, self.use_pallas, self.interpret,
            self.row_tile, name="feed_forward",
        )(normed)
        return x + shared + routed, counts


# What a recomputed layer keeps of its forward pass beside its input: the
# residuals that the attention kernels name for their backward (the output
# and the softmax row statistics), so that neither kernel's forward runs
# twice a step, and an expert layer's routing bookkeeping (``ops/moe.py``
# ``ROUTING``: the experts chosen and the dispatch plan built from them,
# integers but for a row's weight: 1,083,784 B a layer at 8,192 tokens, 8
# choices and 32 experts held), so that ``top_k``, the plan's sort and its
# scatters run once. Every matmul runs again: at 691 M parameters the state
# takes 8.3 GB of the chip and a sequence's kept dots would not fit beside it.
KEPT = jax.checkpoint_policies.save_only_these_names(
    ATTENTION_OUT, ATTENTION_STATS, ROUTING
)


class LagunaLM(SequenceLM):
    """The Laguna of one chip's share (:class:`~.blocks.SequenceLM`)."""

    cfg: LagunaConfig

    @property
    def build_facts(self) -> dict:
        """What ``step:build`` says of the step this model makes."""
        cfg = self.cfg
        return {
            "model": "laguna",
            "experts_held": cfg.experts_held,
            "layers": cfg.num_hidden_layers,
            "window": cfg.sliding_window,
            "heads_full": cfg.heads_of(FULL),
            "heads_window": cfg.heads_of(SLIDING),
            # The layers whose attention residuals ``KEPT`` holds on to,
            # and the expert layers whose routing and plan it does.
            "attention_kept": cfg.num_hidden_layers,
            "routing_kept": sum(not dense for *_, dense in cfg.layers()),
        }

    def attention_calls(self):
        window = self.cfg.sliding_window
        return [
            (heads, window if kind == SLIDING else None)
            for _, kind, heads, _ in self.cfg.layers()
        ]

    def recomputed_layer(self, index, kind, heads, dense) -> nn.Module:
        return nn.remat(Layer, policy=KEPT)(
            self.cfg, kind, heads, dense, self.compute_dtype, self.use_pallas,
            self.interpret, self.block_q, self.block_k, self.row_tile,
            name=f"layer_{index}",
        )
