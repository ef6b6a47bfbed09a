"""What one chip's share of Phi-4-mini-flash needs, from the configuration's
sizes alone. Nothing here looks at the program.

A row of this family is one sequence of ``seq_len`` tokens. The layers kept
are the published layers ``first_layer .. first_layer + num_hidden_layers``;
with ``n = published.num_hidden_layers``, layer ``l`` is a Mamba layer (``l``
a multiple of ``mb_per_layer``, up to ``n/2``), attention over a window (the
other ``l < n/2``), the one full attention layer (``n/2 + 1``), a gated memory
unit (a multiple of ``mb_per_layer`` above ``n/2``) or cross-attention (the
rest). Every layer has the gated MLP and two LayerNorms; embedding and head
are one matrix, counted once.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

MAMBA, WINDOW, FULL, MEMORY_UNIT, CROSS = (
    "mamba", "attention_window", "attention", "memory_unit", "cross_attention"
)


def seq_len(cfg: dict) -> int:
    """Tokens a row: the width of the one token column."""
    return int(cfg["data_spec"][token_column(cfg)][3])


def token_column(cfg: dict) -> str:
    return cfg["token_column"]


def model_columns(cfg: dict) -> List[str]:
    return [token_column(cfg)]


def published_layers(cfg: dict) -> int:
    return int(cfg["published"]["num_hidden_layers"])


def kind(cfg: dict, index: int) -> str:
    half = published_layers(cfg) // 2
    if index % int(cfg["mb_per_layer"]) == 0:
        return MAMBA if index <= half else MEMORY_UNIT
    if index < half:
        return WINDOW
    return FULL if index == half + 1 else CROSS


def layers(cfg: dict) -> List[Tuple[int, str]]:
    """``(published index, kind)`` of every layer kept."""
    first = int(cfg["first_layer"])
    return [
        (i, kind(cfg, i)) for i in range(first, first + int(cfg["num_hidden_layers"]))
    ]


def layers_of(cfg: dict, *kinds: str) -> int:
    return sum(k in kinds for _, k in layers(cfg))


def head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def d_inner(cfg: dict) -> int:
    return int(cfg["ssm"]["expand"]) * int(cfg["hidden_size"])


def dt_rank(cfg: dict) -> int:
    return int(cfg["ssm"]["dt_rank"])


def mixer_shapes(cfg: dict, of_kind: str) -> Dict[str, Tuple[int, ...]]:
    """The mixer's parameters of one layer of ``of_kind``, by the
    reference's leaf name after ``l<i>.``. ``W_qkv``'s bias is held as
    its three parts: the keys' part has a gradient of zero in exact
    arithmetic (a softmax does not change when every key's score moves by
    the same ``q . b``), so as a leaf of its own it falls under the floor
    of ``check.py``'s ``change_norm_gap`` instead of moving by round-off
    alone inside a leaf that does move."""
    h, d = int(cfg["hidden_size"]), head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * d
    kv = int(cfg["num_key_value_heads"]) * d
    di, n, r = d_inner(cfg), int(cfg["ssm"]["state_size"]), dt_rank(cfg)
    taps = int(cfg["ssm"]["conv_kernel"])
    heads = {"lq1": (d,), "lk1": (d,), "lq2": (d,), "lk2": (d,), "norm": (2 * d,)}
    if of_kind == MAMBA:
        return {
            "ssm.in": (h, 2 * di), "ssm.conv": (di, taps), "ssm.conv_bias": (di,),
            "ssm.x": (di, r + 2 * n), "ssm.dt": (r, di), "ssm.dt_bias": (di,),
            "ssm.A_log": (di, n), "ssm.D": (di,), "ssm.out": (di, h),
        }
    if of_kind == MEMORY_UNIT:
        return {"gmu.in": (h, di), "gmu.out": (di, h)}
    if of_kind in (WINDOW, FULL):
        return {
            "attn.qkv": (h, q + 2 * kv), "attn.q_bias": (q,),
            "attn.k_bias": (kv,), "attn.v_bias": (kv,),
            "attn.o": (q, h), "attn.o_bias": (h,),
            **{"attn." + k: s for k, s in heads.items()},
        }
    if of_kind == CROSS:
        return {
            "cross.q": (h, q), "cross.q_bias": (q,), "cross.o": (q, h),
            "cross.o_bias": (h,), **{"cross." + k: s for k, s in heads.items()},
        }
    raise ValueError(f"unknown kind of layer {of_kind!r}")


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter of this chip's share, by the reference's leaf name,
    in the order the weights are drawn."""
    h, v, wide = int(cfg["hidden_size"]), int(cfg["vocab_size"]), int(cfg["intermediate_size"])
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (v, h)}
    for i, of_kind in layers(cfg):
        p = f"l{i}."
        shapes[p + "norm1.scale"] = shapes[p + "norm1.bias"] = (h,)
        for leaf, shape in mixer_shapes(cfg, of_kind).items():
            shapes[p + leaf] = shape
        shapes[p + "norm2.scale"] = shapes[p + "norm2.bias"] = (h,)
        shapes[p + "mlp.w1"] = shapes[p + "mlp.w3"] = (h, wide)
        shapes[p + "mlp.w2"] = (wide, h)
    shapes["final_norm.scale"] = shapes["final_norm.bias"] = (h,)
    return shapes


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def num_parameters(cfg: dict) -> int:
    return sum(_size(s) for s in leaf_shapes(cfg).values())


def layer_parameters(cfg: dict, of_kind: str) -> int:
    """One whole layer of ``of_kind``: mixer, MLP, two norms."""
    h, wide = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    return sum(_size(s) for s in mixer_shapes(cfg, of_kind).values()) + 3 * h * wide + 4 * h


def published_parameters(cfg: dict) -> int:
    """The whole published model by the same arithmetic: every layer of
    its ``num_hidden_layers``, the whole tied vocabulary, the final norm."""
    whole = {
        **cfg, "first_layer": 0, "num_hidden_layers": published_layers(cfg),
        "vocab_size": int(cfg["published"]["vocab_size"]),
    }
    return num_parameters(whole)


def state_bytes(cfg: dict) -> int:
    """Parameters and Adam's two moments, float32: what stays on the
    device all run (the gradients are a fourth copy while a step runs)."""
    return 3 * 4 * num_parameters(cfg)


def band_pairs(cfg: dict) -> int:
    """(query, key) pairs of one head's sliding window over a sequence:
    position ``i`` sees ``min(i + 1, sliding_window)`` keys."""
    t, w = seq_len(cfg), min(int(cfg["sliding_window"]), seq_len(cfg))
    return w * (w + 1) // 2 + (t - w) * w


def _attention_work(cfg: dict, rows: int, pairs: int) -> Dict[str, int]:
    """Both maps of every differential pair: ``num_attention_heads`` heads
    of ``head_dim`` score ``pairs`` (query, key) pairs each and multiply
    them into values of twice that width, 2 FLOPs a multiply-add; q and the
    two maps' outputs moved once in the compute type (2 bytes), k once, and
    v once (a key pair's ``V_g`` is its two heads' values side by side)."""
    t, d = seq_len(cfg), head_dim(cfg)
    heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return {
        "flops": rows * heads * 2 * pairs * (d + 2 * d),
        "bytes": rows * t * 2 * (heads * d + heads * 2 * d + 2 * kv_heads * d),
    }


def attention_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a full causal attention layer (the full layer, or a
    cross layer over its keys and values) over ``rows`` sequences, whatever
    implements it: the lower triangle."""
    t = seq_len(cfg)
    return _attention_work(cfg, rows, t * t // 2)


def attention_window_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a sliding-window layer: the band."""
    return _attention_work(cfg, rows, band_pairs(cfg))


def ssm_scan_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a Mamba layer's selective scan over ``rows``
    sequences, whatever implements it: a (position, channel, state)
    element is ``delta A``, the decay times the state, ``delta u B`` added,
    and the state times ``C`` added to the output, 6 FLOPs (its exponential
    is counted beside them, ``exps``: the vector unit's work, which no
    published peak covers); ``u``, ``delta`` read and ``s`` written once in
    float32, ``B`` and ``C`` read once. The bytes bind."""
    t, di, n = seq_len(cfg), d_inner(cfg), int(cfg["ssm"]["state_size"])
    return {
        "flops": rows * t * di * n * 6,
        "exps": rows * t * di * n,
        "bytes": rows * t * 4 * (3 * di + 2 * n),
    }


def matmul_parameters(cfg: dict, of_kind: str) -> int:
    """The matrices of one layer that every token is multiplied by."""
    h, wide = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    mixer = mixer_shapes(cfg, of_kind)
    return 3 * h * wide + sum(
        _size(s) for k, s in mixer.items()
        if len(s) == 2 and not k.endswith((".conv", ".A_log"))
    )


def flops_per_row(cfg: dict) -> int:
    """Forward and backward of one sequence on this chip's share, no
    recomputation: 6 FLOPs a matrix parameter a token (the projections of
    every mixer, the MLPs, the head over the vocabulary rows held), three
    passes of each attention layer's products (the full and the cross
    layers over the triangle, the window layers over the band) and of each
    scan's recurrence. The embedding's lookup, norms, the convolution, the
    gates, the optimizer and other elementwise work are not counted."""
    t = seq_len(cfg)
    per_token = int(cfg["hidden_size"]) * int(cfg["vocab_size"]) + sum(
        matmul_parameters(cfg, k) for _, k in layers(cfg)
    )
    passes = 3 * (
        layers_of(cfg, FULL, CROSS) * attention_fwd_work(cfg, 1)["flops"]
        + layers_of(cfg, WINDOW) * attention_window_fwd_work(cfg, 1)["flops"]
        + layers_of(cfg, MAMBA) * ssm_scan_fwd_work(cfg, 1)["flops"]
    )
    return 6 * per_token * t + passes
