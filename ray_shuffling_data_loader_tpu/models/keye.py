"""Keye-VL-2.0's language model: grouped-query attention over the keys a
learned indexer chooses (DeepSeek Sparse Attention), and a routed mixture
of experts, as one chip of an expert-parallel deployment runs it.

The layer equations (``model_type`` ``KeyeVL2``; every size comes from the
configuration; no bias anywhere; every layer alike):

* every layer: ``x = x + attn(rmsnorm(x)); x = x + moe(rmsnorm(x))``;
* ``attn``: ``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim``, an RMS norm on each head's q and k,
  rotary positions at ``rope_theta`` over the whole head (MRoPE with three
  equal position ids, as text tokens have them, is 1-D rotary), softmax
  over the keys ``S_t`` the indexer chose for the query, an output
  projection;
* the indexer (``sa_config``): ``q^I = W^I_q x`` (``indexer_num_heads``
  heads of ``indexer_head_dim``), ``k^I = LayerNorm(W^I_k x)`` (one head),
  ``w = W^I_w x / sqrt(heads * dim)``, the first ``indexer_rope_head_dim``
  dimensions of ``q^I`` and ``k^I`` turned by rotary at ``rope_theta``;
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])``; ``S_t`` the
  ``min(topk, t + 1)`` causal keys of largest ``I[t, s]``
  (``ops/sparse_attention.py``). Its input is detached;
* the indexer's loss, added to the next-token loss: the KL divergence of
  ``softmax_{S_t}(I[t])`` from the main attention's probabilities summed
  over the heads (no gradient), the mean over positions; it trains the
  indexer's leaves alone;
* ``moe``: softmax over all ``num_experts`` router logits, the
  ``num_experts_per_tok`` largest kept and renormalised; this chip adds up
  the experts it holds (``first_expert`` .. ``first_expert +
  experts_held``) and leaves the rest out (``ops/moe.py``); no shared
  expert;
* embedding, final RMS norm, an untied head over the vocabulary rows held
  here; next-token cross-entropy, the mean over positions.

The parts are ``models/blocks.py``'s; the indexer and what is kept are this
model's. Every layer is recomputed in the backward pass (``nn.remat``) but
for ``KEPT``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_shuffling_data_loader_tpu.models.blocks import (
    Attention,
    ExpertFFN,
    Experts,
    LayerNorm,
    RMSNorm,
    Rope,
    SequenceLM,
    fan_in,
    rotary,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
)
from ray_shuffling_data_loader_tpu.ops.moe import ROUTING
from ray_shuffling_data_loader_tpu.ops.sparse_attention import (
    SELECTION,
    index_loss,
    index_select,
    select_counts,
    selected_pairs,
)


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """Sizes under the names of the published ``config.json`` (the
    indexer's from its ``sa_config``); what one chip's share adds:
    ``experts_held`` / ``first_expert`` (of ``num_experts`` routed over),
    ``first_layer`` (the published index of the first layer kept;
    ``num_hidden_layers`` follow), ``vocab_size`` as the rows of the
    vocabulary held."""

    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_hidden_layers: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: int
    rope_theta: float
    index_topk: int
    index_heads: int
    index_head_dim: int
    index_rope_dim: int
    first_expert: int = 0
    first_layer: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    index_norm_eps: float = 1e-6

    @classmethod
    def from_dict(cls, cfg: dict) -> "KeyeConfig":
        """From a dict of the published keys (a configuration file's top
        level)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in cfg.items() if k in names}
        kwargs.setdefault("experts_held", cfg["num_experts"])
        sa = cfg["sa_config"]
        return cls(
            index_topk=int(sa["topk"]),
            index_heads=int(sa["indexer_num_heads"]),
            index_head_dim=int(sa["indexer_head_dim"]),
            index_rope_dim=int(cfg["indexer_rope_head_dim"]),
            index_norm_eps=float(cfg.get("indexer_norm_eps", 1e-6)),
            **{k: v for k, v in kwargs.items() if not k.startswith("index_")},
        )

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def experts(self) -> Experts:
        """Softmax scores, no selection bias, the chosen renormalised."""
        return Experts(
            self.moe_intermediate_size, self.num_experts, self.experts_held,
            self.first_expert, self.num_experts_per_tok, False,
            self.norm_topk_prob, 1.0, "softmax",
        )

    def layers(self):
        """``(published index,)`` of each layer kept: all alike."""
        return [
            (i,) for i in range(self.first_layer,
                                self.first_layer + self.num_hidden_layers)
        ]


class Indexer(nn.Module):
    """``(q^I [b, t, heads, dim], k^I [b, t, dim], w [b, t, heads])`` of the
    lightning indexer, float32 at the highest precision from ``x`` (already
    detached): the scores they make decide a discrete selection."""

    cfg: KeyeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, h = x.shape
        heads, dim = cfg.index_heads, cfg.index_head_dim
        wq = self.param("q_proj", fan_in((h, heads * dim)), (h, heads * dim))
        wk = self.param("k_proj", fan_in((h, dim)), (h, dim))
        ww = self.param("weights_proj", fan_in((h, heads)), (h, heads))
        x32 = x.astype(jnp.float32)

        def dot(w):
            return jnp.dot(x32, w, precision=jax.lax.Precision.HIGHEST)

        rope = Rope(cfg.index_rope_dim, cfg.rope_theta)
        q = rotary(dot(wq).reshape(b, t, heads, dim), rope)
        k = LayerNorm(cfg.index_norm_eps, jnp.float32, name="k_norm")(dot(wk))
        k = rotary(k[:, :, None], rope)[:, :, 0]
        return q, k, dot(ww) * (heads * dim) ** -0.5


class Layer(nn.Module):
    """One published layer: the indexer and the attention over what it
    chose, then the experts, each behind an RMS norm and added to the
    stream. Returns ``(x, counts)``: the expert layer's ``{"load",
    "dropped", "fallback"}`` (:func:`~..ops.moe.experts_ffn`), the
    indexer's ``"loss"`` and its selection's ``"select"``
    (``ops/sparse_attention.py`` ``select_counts``)."""

    cfg: KeyeConfig
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int
    row_tile: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        seq = x.shape[1]
        block_q, block_k = min(self.block_q, seq), min(self.block_k, seq)
        blocks = dict(block_q=block_q, block_k=block_k,
                      use_pallas=self.use_pallas, interpret=self.interpret)
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="input_layernorm")(x)
        # The indexer is a sibling of the attention's scope, never inside it.
        with jax.named_scope("indexer"):
            qi, ki, wi = Indexer(cfg, name="indexer")(jax.lax.stop_gradient(normed))
            words, lse_i = index_select(qi, ki, wi, cfg.index_topk, **blocks)
            select = select_counts(words, block_k)
        y, q, k, lse = Attention(
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            Rope(cfg.head_dim, cfg.rope_theta), self.dtype, self.use_pallas,
            self.interpret, block_q, block_k,
            qk_norm_eps=cfg.norm_eps, name="self_attn",
        )(normed, selected=words)
        with jax.named_scope("indexer"):
            loss = index_loss(qi, ki, wi, q, k, lse, lse_i, words, **blocks)
        x = x + y
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="post_attention_layernorm")(x)
        routed, counts = ExpertFFN(
            cfg.experts, self.dtype, self.use_pallas, self.interpret,
            self.row_tile, name="mlp",
        )(normed)
        return x + routed, {**counts, "loss": loss, "select": select}


# What a recomputed layer keeps of its forward pass beside its input: the
# residuals the attention kernels name for their backward, the routing and
# dispatch plan (``ROUTING``), and the selection with what the indexer's loss
# computed from it (``SELECTION``: the bitmask, 32 MB a layer at 16,384
# tokens, the selected scores' log-sum-exp, the loss and its three
# gradients), so that neither indexer kernel runs twice a step.
KEPT = jax.checkpoint_policies.save_only_these_names(
    ATTENTION_OUT, ATTENTION_STATS, ROUTING, SELECTION
)


def select_fold(select) -> dict:
    """What the ``sparse:select`` counter of one step carries, from the
    layers' summed ``select_counts``."""
    blocks, causal, pairs = (int(x) for x in select)
    return {"blocks": blocks, "causal_blocks": causal, "pairs": pairs}


class KeyeLM(SequenceLM):
    """The Keye-VL-2.0 language model of one chip's share
    (:class:`~.blocks.SequenceLM`)."""

    cfg: KeyeConfig

    @property
    def build_facts(self) -> dict:
        """What ``step:build`` says of the step this model makes."""
        cfg = self.cfg
        layers = cfg.num_hidden_layers
        return {
            "model": "keye",
            "experts_held": cfg.experts_held,
            "layers": layers,
            "index_topk": cfg.index_topk,
            "index_heads": cfg.index_heads,
            # The layers whose attention residuals, routing and selection
            # ``KEPT`` holds on to.
            "attention_kept": layers,
            "routing_kept": layers,
            "selection_kept": layers,
        }

    @property
    def step_counters(self) -> dict:
        """The expert layers' load and the selection's blocks and pairs."""
        return {
            **super().step_counters,
            "sparse:select": (("sparse_select",), select_fold),
        }

    def attention_calls(self):
        return [(self.cfg.num_attention_heads, None)] * self.cfg.num_hidden_layers

    def traced_facts(self, features) -> dict:
        """:meth:`SequenceLM.traced_facts` and the (query, key) pairs the
        selection keeps in one forward of every layer kept."""
        batch, seq = features["tokens"].shape
        return {
            **super().traced_facts(features),
            "selected_pairs": batch * self.cfg.num_hidden_layers
            * selected_pairs(seq, self.cfg.index_topk),
        }

    def recomputed_layer(self, index) -> nn.Module:
        return nn.remat(Layer, policy=KEPT)(
            self.cfg, self.compute_dtype, self.use_pallas, self.interpret,
            self.block_q, self.block_k, self.row_tile, name=f"layer_{index}",
        )
