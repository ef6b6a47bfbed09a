"""Phi-4-mini-flash (SambaY): Mamba scans, sliding-window and full
differential attention in a self-decoder; gated memory units and
cross-attention over ONE shared set of keys and values in a cross-decoder.

The layer equations (``model_type`` ``phi4flash``; ``n`` published layers,
0-based index ``l``; what the published ``config.json`` has no key for
follows the family's code and papers: SambaY arXiv:2507.06607, Mamba
arXiv:2312.00752, Differential Transformer arXiv:2410.05258):

* kind of layer ``l``: ``l % mb_per_layer == 0`` and ``l <= n/2``: **Mamba**;
  the other ``l < n/2``: **attention over a window**; ``l = n/2 + 1``:
  **full causal attention**, whose keys and values every cross layer reads;
  ``l % mb_per_layer == 0`` above ``n/2``: **gated memory unit**, reading
  the memory that layer ``n/2``'s scan hands on; the rest: **cross-attention**
  (queries only);
* every layer: ``h = x + Mixer(LN1(x)); y = h + MLP(LN2(h))``, LayerNorm
  with scale and bias; ``MLP(x) = W2 (silu(g) * u)``, ``[g, u] = x W1``;
* Mamba (``d_inner = expand * hidden``, ``N`` states, a convolution of
  ``d_conv`` taps with bias, rank ``R``): ``[u, z] = x W_in``; ``u <-
  silu(conv(u) + b)``; ``[delta, B, C] = u W_x``; ``Delta = softplus(delta
  W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
  (``ops/selective_scan.py``, float32) gives ``s``; ``out = (s * silu(z))
  W_out``. Layer ``n/2`` hands on ``M = s``;
* gated memory unit: ``out = (silu(x W1) * M) W2``;
* attention: ``[q, k, v] = x W_qkv + b``, no positional encoding;
  differential heads: query heads ``2p, 2p + 1`` are pair ``p``, key heads
  ``2g, 2g + 1`` pair ``g = p // (pairs / key pairs)``, ``V_g = [v_2g |
  v_2g+1]``; ``o_p = (softmax(q_2p k_2g^T / sqrt(d) + mask) - lambda
  softmax(q_2p+1 k_2g+1^T / sqrt(d) + mask)) V_g``, ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``o_p <- RMSNorm(o_p) (1 - lambda_init)``; the pairs
  concatenated, ``W_o`` with bias. The window includes the current position;
* cross-attention: ``q = x W_q + b``; ``k``, ``v`` are layer ``n/2 + 1``'s;
  full causal; its own lambdas and norm;
* one matrix is embedding and head; a final LayerNorm before it.

Both maps of a pair run in ONE call of the attention kernel, as heads of
their own over values of twice their width (``ops/flash_attention.py``):
the query heads are put in the order (key pair, map, pair in the group), so
that the kernel's grouped-query rule sends map ``w`` of key pair ``g`` to
key head ``2g + w``, and ``V_g`` is given to both key heads.

Every layer is recomputed in the backward pass (``nn.remat``) but for
``KEPT``; the memory and the shared keys and values cross the layers as
inputs and outputs (``models/blocks.py`` :class:`SequenceLM`), so they are
kept, and their gradients are the sums over their readers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_shuffling_data_loader_tpu.models.blocks import (
    DenseFFN,
    LayerNorm,
    RMSNorm,
    SequenceLM,
    fan_in,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
    flash_attention,
)
from ray_shuffling_data_loader_tpu.ops.selective_scan import selective_scan
from ray_shuffling_data_loader_tpu.ops.short_conv import causal_depthwise_conv1d

MAMBA, WINDOW, FULL, MEMORY_UNIT, CROSS = (
    "mamba", "attention_window", "attention", "memory_unit", "cross_attention"
)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """Sizes under the names of the published ``config.json``;
    ``published_layers`` is its ``num_hidden_layers`` (the kinds of layers
    follow from it), ``num_hidden_layers`` the layers kept from
    ``first_layer`` on, ``vocab_size`` the rows of the vocabulary held. The
    scan's sizes are Mamba's own defaults (the config has no key for
    them)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_hidden_layers: int
    published_layers: int
    sliding_window: int
    first_layer: int = 0
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    ssm_state_size: int = 16
    ssm_conv_kernel: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None
    # No expert layer: ``SequenceLM`` counts no load and records no span.
    experts_held: int = 0

    @classmethod
    def from_dict(cls, cfg: dict) -> "Phi4FlashConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in cfg.items() if k in names})

    @property
    def norm_eps(self) -> float:
        return self.layer_norm_eps

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.hidden_size // 16)

    @property
    def memory_layer(self) -> int:
        """The published index of the Mamba layer that hands on its scan's
        output; the layer after it hands on its keys and values."""
        return self.published_layers // 2

    def kind(self, index: int) -> str:
        half = self.memory_layer
        if index % self.mb_per_layer == 0:
            return MAMBA if index <= half else MEMORY_UNIT
        if index < half:
            return WINDOW
        return FULL if index == half + 1 else CROSS

    def layers(self) -> Sequence[Tuple[int, str]]:
        """``(published index, kind)`` of each layer kept."""
        kept = range(self.first_layer, self.first_layer + self.num_hidden_layers)
        return [(i, self.kind(i)) for i in kept]


def lambda_init(index: int) -> float:
    """The differential maps' starting weight at published layer ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


class DifferentialAttention(nn.Module):
    """Differential attention of published layer ``index``: over the last
    ``window`` keys, over all of them, or (``cross``) over the keys and
    values ``shared`` that another layer made, with queries of its own
    only. Returns ``(out, (k, v))``: the keys and values it read."""

    cfg: Phi4FlashConfig
    index: int
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int
    window: Optional[int] = None
    cross: bool = False
    scope_name: str = FULL

    @nn.compact
    def __call__(self, x, shared=None):
        cfg = self.cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        pairs, kv_pairs = nq // 2, nkv // 2
        group = pairs // kv_pairs
        widths = {"q": nq * d} if self.cross else {"q": nq * d, "k": nkv * d, "v": nkv * d}
        width = sum(widths.values())
        w_in = self.param(
            "q_proj" if self.cross else "qkv_proj", fan_in((h, width)), (h, width)
        )
        # The projection's bias, held by part: the keys' part has no
        # gradient but round-off (a softmax ignores a shift of every score).
        b_in = jnp.concatenate([
            self.param(f"{n}_bias", nn.initializers.zeros, (w,))
            for n, w in widths.items()
        ])
        w_out = self.param("out_proj", fan_in((nq * d, h)), (nq * d, h))
        b_out = self.param("out_proj_bias", nn.initializers.zeros, (h,))
        lambdas = [
            self.param(f"lambda_{n}", nn.initializers.normal(stddev=0.1), (d,))
            for n in ("q1", "k1", "q2", "k2")
        ]
        b, t, _ = x.shape
        with jax.named_scope(self.scope_name):
            proj = jnp.dot(x, w_in.astype(self.dtype)) + b_in.astype(self.dtype)
            if self.cross:
                q, (k, v) = proj, shared
            else:
                q, k, v = jnp.split(proj, [nq * d, (nq + nkv) * d], axis=-1)
                k = k.reshape(b, t, nkv, d)
                v = v.reshape(b, t, nkv, d)
            # Query head 2p + w (pair p = group * g + j, map w) to the
            # place (g, w, j): the kernel's head // group is then key head
            # 2g + w.
            q = q.reshape(b, t, kv_pairs, group, 2, d)
            q = jnp.swapaxes(q, 3, 4).reshape(b, t, nq, d)
            # V_g = [v_2g | v_2g+1], for both key heads of the pair.
            wide = jnp.repeat(v.reshape(b, t, kv_pairs, 2 * d), 2, axis=2)
            out = flash_attention(
                q, k, wide, causal=True, use_pallas=self.use_pallas,
                interpret=self.interpret, block_q=self.block_q,
                block_k=self.block_k, window=self.window,
            ).astype(jnp.float32)
            out = out.reshape(b, t, kv_pairs, 2, group, 2 * d)
            lq1, lk1, lq2, lk2 = lambdas
            start = lambda_init(self.index)
            weight = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + start
            out = out[:, :, :, 0] - weight * out[:, :, :, 1]  # [b, t, g, j, 2d]
            out = RMSNorm(cfg.norm_eps, jnp.float32, name="head_norm")(out)
            out = (out * (1.0 - start)).astype(self.dtype).reshape(b, t, nq * d)
            out = jnp.dot(out, w_out.astype(self.dtype)) + b_out.astype(self.dtype)
        return out, (k, v)


class Mamba(nn.Module):
    """Returns ``(out, s)``: the layer's output and its scan's, before the
    gate (the memory, where this layer hands it on)."""

    cfg: Phi4FlashConfig
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, di, n, r = cfg.hidden_size, cfg.d_inner, cfg.ssm_state_size, cfg.dt_rank
        taps = cfg.ssm_conv_kernel
        w_in = self.param("in_proj", fan_in((h, 2 * di)), (h, 2 * di))
        conv = self.param("conv", fan_in((di, taps), -1), (di, taps))
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (di,))
        w_x = self.param("x_proj", fan_in((di, r + 2 * n)), (di, r + 2 * n))
        w_dt = self.param("dt_proj", fan_in((r, di)), (r, di))
        dt_bias = self.param("dt_bias", _dt_bias_init, (di,))
        a_log = self.param("A_log", _a_log_init, (di, n))
        skip = self.param("D", nn.initializers.ones, (di,))
        w_out = self.param("out_proj", fan_in((di, h)), (di, h))
        dt = self.dtype
        with jax.named_scope(MAMBA):
            u, z = jnp.split(jnp.dot(x, w_in.astype(dt)), 2, axis=-1)
            u = jax.nn.silu(causal_depthwise_conv1d(u, conv, conv_bias))
            low, b_t, c_t = jnp.split(
                jnp.dot(u, w_x.astype(dt), preferred_element_type=jnp.float32),
                [r, r + n], axis=-1,
            )
            delta = jax.nn.softplus(
                jnp.dot(
                    low.astype(dt), w_dt.astype(dt),
                    preferred_element_type=jnp.float32,
                )
                + dt_bias
            )
            with jax.named_scope("ssm_scan"):
                s = selective_scan(
                    u, delta, -jnp.exp(a_log), b_t, c_t, skip,
                    use_pallas=self.use_pallas, interpret=self.interpret,
                )
            out = (s * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
            return jnp.dot(out, w_out.astype(dt)), s.astype(dt)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1 .. N)`` on every channel (Mamba's own)."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape
    )


def _dt_bias_init(key, shape, dtype=jnp.float32, low=1e-3, high=1e-1):
    """Such that ``softplus(bias)`` is log-uniform in ``[low, high]``
    (Mamba's own)."""
    step = jnp.exp(
        jax.random.uniform(key, shape, dtype)
        * (math.log(high) - math.log(low)) + math.log(low)
    )
    return step + jnp.log(-jnp.expm1(-step))


class MemoryUnit(nn.Module):
    """``(silu(x W1) * M) W2``: the memory gated by the stream."""

    cfg: Phi4FlashConfig
    dtype: Any

    @nn.compact
    def __call__(self, x, memory):
        h, di = self.cfg.hidden_size, self.cfg.d_inner
        w1 = self.param("in_proj", fan_in((h, di)), (h, di))
        w2 = self.param("out_proj", fan_in((di, h)), (di, h))
        with jax.named_scope(MEMORY_UNIT):
            gate = jax.nn.silu(jnp.dot(x, w1.astype(self.dtype)))
            return jnp.dot(gate * memory, w2.astype(self.dtype))


class Layer(nn.Module):
    """One published layer: ``(x, handed) -> (x, {}, handed)``. ``handed``
    is what earlier layers hand on, ``{"memory": [b, t, d_inner]}`` from
    the memory layer on and ``{"k", "v": [b, t, kv heads, head_dim]}`` from
    the layer after it: the layers that make them add them, the memory
    units and cross layers read them, every layer passes them on."""

    cfg: Phi4FlashConfig
    index: int
    kind: str
    dtype: Any
    use_pallas: Optional[bool]
    interpret: bool
    block_q: int
    block_k: int

    @nn.compact
    def __call__(self, x, handed=None):
        cfg, dt = self.cfg, self.dtype
        handed = dict(handed or {})
        normed = LayerNorm(cfg.norm_eps, dt, name="norm1")(x)
        attention = lambda **kw: DifferentialAttention(  # noqa: E731
            cfg, self.index, dt, self.use_pallas, self.interpret,
            self.block_q, self.block_k, name="mixer", **kw,
        )
        if self.kind == MAMBA:
            mixed, s = Mamba(
                cfg, dt, self.use_pallas, self.interpret, name="mixer"
            )(normed)
            if self.index == cfg.memory_layer:
                handed["memory"] = s
        elif self.kind == MEMORY_UNIT:
            mixed = MemoryUnit(cfg, dt, name="mixer")(normed, handed["memory"])
        elif self.kind == WINDOW:
            mixed, _ = attention(window=cfg.sliding_window, scope_name=WINDOW)(normed)
        elif self.kind == FULL:
            mixed, (handed["k"], handed["v"]) = attention()(normed)
        elif self.kind == CROSS:
            mixed, _ = attention(cross=True, scope_name=CROSS)(
                normed, (handed["k"], handed["v"])
            )
        else:
            raise ValueError(f"unknown kind of layer {self.kind!r}")
        x = x + mixed
        normed = LayerNorm(cfg.norm_eps, dt, name="norm2")(x)
        x = x + DenseFFN(cfg.intermediate_size, dt, name="mlp")(normed)
        return x, {}, handed


# What a recomputed layer keeps of its forward pass beside its inputs (the
# stream, and the memory and the shared keys and values once they exist):
# the attention kernels' residuals, so that no forward kernel runs twice a
# step. The scans run again (4 ms each at 8,192 x 5,120, against 168 MB for
# the output and 42 MB for the boundary states), and so does every matmul.
KEPT = jax.checkpoint_policies.save_only_these_names(
    ATTENTION_OUT, ATTENTION_STATS
)


class Phi4FlashLM(SequenceLM):
    """The Phi-4-mini-flash of one chip's share
    (:class:`~.blocks.SequenceLM`): the layers kept, the vocabulary rows
    held, embedding and head one matrix."""

    cfg: Phi4FlashConfig

    @property
    def build_facts(self) -> dict:
        """What ``step:build`` says of the step this model makes."""
        cfg = self.cfg
        kinds = [kind for _, kind in cfg.layers()]
        attention = sum(k in (WINDOW, FULL, CROSS) for k in kinds)
        return {
            "model": "phi4flash",
            "layers": cfg.num_hidden_layers,
            "ssm_layers": kinds.count(MAMBA),
            "memory_units": kinds.count(MEMORY_UNIT),
            "cross_layers": kinds.count(CROSS),
            "window": cfg.sliding_window,
            # The published layers that hand on the memory and k, v.
            "shared_from": [cfg.memory_layer, cfg.memory_layer + 1],
            # The layers whose attention residuals ``KEPT`` holds on to,
            # and the tensors kept because they cross layers: the memory,
            # where a kept layer hands it on.
            "attention_kept": attention,
            "memory_kept": int((cfg.memory_layer, MAMBA) in cfg.layers()),
        }

    def attention_calls(self):
        cfg = self.cfg
        return [
            (cfg.num_attention_heads, cfg.sliding_window if kind == WINDOW else None)
            for _, kind in cfg.layers() if kind in (WINDOW, FULL, CROSS)
        ]

    def final_norm(self, dtype) -> nn.Module:
        return LayerNorm(self.cfg.norm_eps, dtype, name="final_norm")

    def recomputed_layer(self, index, kind) -> nn.Module:
        return nn.remat(Layer, policy=KEPT)(
            self.cfg, index, kind, self.compute_dtype, self.use_pallas,
            self.interpret, self.block_q, self.block_k, name=f"layer_{index}",
        )
