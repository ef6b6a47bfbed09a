"""Kimi-VL's language model: multi-head latent attention and a
sigmoid-routed mixture of experts with shared experts beside it, as one
chip of an expert-parallel deployment runs it.

The layer equations (DeepSeek-V2/V3's, whose keys the configuration carries:
``kv_lora_rank``, ``qk_rope_head_dim``, ``topk_method``; every size comes
from the configuration; every norm is RMS with a learned scale; no bias
anywhere):

* every layer: ``x = x + mla(rmsnorm(x)); x = x + ffn(rmsnorm(x))``;
* ``mla`` (``q_lora_rank`` null: the query straight from ``x``):
  ``num_attention_heads`` query heads of ``qk_nope_head_dim +
  qk_rope_head_dim``; ``[c ; k_rope] = W_kva x``, ``c`` the
  ``kv_lora_rank``-wide latent, RMS normed, ``k_rope`` one key of
  ``qk_rope_head_dim`` for every head; ``[k_nope ; v] = W_kvb c`` a head at
  a time (``v_head_dim``); rotary positions at ``rope_theta`` on the
  query's rope part and ``k_rope`` only; causal softmax of the two parts'
  products summed, over ``sqrt(qk_nope_head_dim + qk_rope_head_dim)``; an
  output projection (``models/blocks.py`` :class:`~.blocks.LatentAttention`);
* dense FFN (the first ``first_k_dense_replace`` layers): ``W2 (silu(W1 x) *
  W3 x)`` at ``intermediate_size``;
* sparse FFN: ``n_shared_experts`` shared experts (one gated FFN of their
  summed width, every token, on every chip alike) plus the routed experts:
  sigmoid scores over ALL ``n_routed_experts``, the ``num_experts_per_tok``
  with the largest score plus the selection bias chosen (``noaux_tc`` with
  one group), their scores divided by their sum and multiplied by
  ``routed_scaling_factor``; this chip adds up the experts it holds
  (``first_expert`` .. ``first_expert + experts_held``) and leaves the rest
  out (``ops/moe.py``);
* embedding, final RMS norm, an untied output head over the vocabulary rows
  held here; next-token cross-entropy, the mean over positions.

The parts are ``models/blocks.py``'s; the layer and what is kept are this
model's. Every layer is recomputed in the backward pass (``nn.remat``) but
for ``KEPT``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax

from ray_shuffling_data_loader_tpu.models.blocks import (
    DenseFFN,
    ExpertFFN,
    Experts,
    LatentAttention,
    RMSNorm,
    Rope,
    SequenceLM,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
)
from ray_shuffling_data_loader_tpu.ops.moe import ROUTING
from ray_shuffling_data_loader_tpu.ops.placement import auto_pallas


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    """Sizes under the names of the published ``config.json``; what one
    chip's share adds: ``experts_held`` / ``first_expert`` (of
    ``n_routed_experts`` routed over), ``first_layer`` (the published index
    of the first layer kept; ``num_hidden_layers`` follow), ``vocab_size``
    as the rows of the vocabulary held."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    n_shared_experts: int
    n_routed_experts: int
    experts_held: int
    num_experts_per_tok: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    first_k_dense_replace: int = 0
    moe_layer_freq: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    first_expert: int = 0
    first_layer: int = 0
    rms_norm_eps: float = 1e-6

    @classmethod
    def from_dict(cls, cfg: dict) -> "KimiConfig":
        """From a dict of the published keys (a configuration file's top
        level). Only the choice the layer implements is taken: sigmoid
        scores, ``noaux_tc`` over one group, no query latent, no rotary
        scaling."""
        checks = {
            "scoring_func": "sigmoid", "topk_method": "noaux_tc", "q_lora_rank": None,
            "rope_scaling": None,
        }
        for key, want in checks.items():
            if cfg.get(key, want) != want:
                raise ValueError(f"{key} {cfg[key]!r}: this layer implements {want!r}")
        if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
            raise ValueError("the group-limited choice over more than one group is not implemented")
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in cfg.items() if k in names}
        kwargs.setdefault("experts_held", cfg["n_routed_experts"])
        return cls(**kwargs)

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def experts(self) -> Experts:
        """Sigmoid scores, the selection bias, the chosen renormalised,
        times the routed scale."""
        return Experts(
            self.moe_intermediate_size, self.n_routed_experts, self.experts_held,
            self.first_expert, self.num_experts_per_tok, True,
            self.norm_topk_prob, self.routed_scaling_factor,
        )

    def layers(self) -> Sequence[Tuple[int, bool]]:
        """``(published index, dense FFN?)`` of each layer kept."""
        kept = range(self.first_layer, self.first_layer + self.num_hidden_layers)
        return [
            (
                i,
                i < self.first_k_dense_replace
                or (i - self.first_k_dense_replace) % self.moe_layer_freq != 0,
            )
            for i in kept
        ]


class Layer(nn.Module):
    """One published layer: latent attention and its FFN, each behind an RMS
    norm and added to the stream. Returns ``(x, counts)``: a sparse layer's
    ``{"load", "dropped", "fallback"}`` (:func:`~..ops.moe.experts_ffn`), a
    dense layer's ``{}``."""

    cfg: KimiConfig
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
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="input_layernorm")(x)
        x = x + LatentAttention(
            cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank,
            Rope(cfg.qk_rope_head_dim, float(cfg.rope_theta)), cfg.norm_eps,
            self.dtype, self.use_pallas, self.interpret, self.block_q, self.block_k,
            name="self_attn",
        )(normed)
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="post_attention_layernorm")(x)
        if self.dense:
            return x + DenseFFN(cfg.intermediate_size, self.dtype, name="mlp")(normed), {}
        # Every chip computes the shared experts alike; the routed part is
        # this chip's share.
        shared = DenseFFN(
            cfg.n_shared_experts * cfg.moe_intermediate_size, self.dtype,
            "shared_expert", name="shared_experts",
        )(normed)
        routed, counts = ExpertFFN(
            cfg.experts, self.dtype, self.use_pallas, self.interpret,
            self.row_tile, name="mlp",
        )(normed)
        return x + shared + routed, counts


# What a recomputed layer keeps of its forward pass beside its input, as
# Laguna's: the residuals that the attention kernels name for their backward
# (the output and the softmax row statistics), so that the latent kernels'
# forward runs once a step, and an expert layer's routing and dispatch plan
# (``ops/moe.py`` ``ROUTING``). Every matmul runs again, the latent path's
# among them.
KEPT = jax.checkpoint_policies.save_only_these_names(
    ATTENTION_OUT, ATTENTION_STATS, ROUTING
)


class KimiLM(SequenceLM):
    """Kimi-VL's language model of one chip's share
    (:class:`~.blocks.SequenceLM`)."""

    cfg: KimiConfig

    @property
    def build_facts(self) -> dict:
        """What ``step:build`` says of the step this model makes."""
        cfg = self.cfg
        layers = cfg.num_hidden_layers
        return {
            "model": "kimi",
            "experts_held": cfg.experts_held,
            "layers": layers,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_head_dim": cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "rope_head_dim": cfg.qk_rope_head_dim,
            # One rotary key read by every head, never repeated to them.
            "shared_key": True,
            # The ``flash_attention_latent_*`` kernels, not the XLA path.
            "latent_kernels": bool(
                auto_pallas() if self.use_pallas is None else self.use_pallas
            ),
            # The layers whose attention residuals ``KEPT`` holds on to,
            # and the expert layers whose routing and plan it does.
            "attention_kept": layers,
            "routing_kept": sum(not dense for _, dense in cfg.layers()),
        }

    def attention_calls(self):
        return [(self.cfg.num_attention_heads, None)] * self.cfg.num_hidden_layers

    def recomputed_layer(self, index, dense) -> nn.Module:
        return nn.remat(Layer, policy=KEPT)(
            self.cfg, dense, self.compute_dtype, self.use_pallas, self.interpret,
            self.block_q, self.block_k, self.row_tile, name=f"layer_{index}",
        )
