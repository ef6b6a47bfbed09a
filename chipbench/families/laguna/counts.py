"""What one chip's share of Laguna needs, from the configuration's sizes
alone. Nothing here looks at the program.

A row of this family is one sequence of ``seq_len`` tokens. The layers kept
are the published layers ``first_layer .. first_layer + num_hidden_layers``;
layer ``i`` attends as ``layer_types[i]`` says with
``num_attention_heads_per_layer[i]`` query heads, and has a dense FFN where
``mlp_layer_types[i]`` is ``dense``, else a shared expert and the routed
experts, of which ``num_experts`` (the configuration's key, reduced) are
held here and ``published.num_experts`` routed over.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

FULL, SLIDING = "full_attention", "sliding_attention"


def seq_len(cfg: dict) -> int:
    """Tokens a row: the width of the one token column."""
    return int(cfg["data_spec"][token_column(cfg)][3])


def token_column(cfg: dict) -> str:
    return cfg["token_column"]


def model_columns(cfg: dict) -> List[str]:
    return [token_column(cfg)]


def experts_routed(cfg: dict) -> int:
    """Experts the router scores: the published count, whatever is held."""
    return int(cfg["published"]["num_experts"])


def layers(cfg: dict) -> List[Tuple[int, str, int, bool]]:
    """``(published index, attention kind, query heads, dense FFN?)`` of
    every layer kept."""
    first = int(cfg["first_layer"])
    return [
        (
            i, cfg["layer_types"][i],
            int(cfg["num_attention_heads_per_layer"][i]),
            cfg["mlp_layer_types"][i] == "dense",
        )
        for i in range(first, first + int(cfg["num_hidden_layers"]))
    ]


def heads_of(cfg: dict, kind: str) -> int:
    """Query heads of the layers of ``kind`` among those kept (0: none)."""
    return max((n for _, k, n, _ in layers(cfg) if k == kind), default=0)


def layers_of(cfg: dict, kind: str) -> int:
    return sum(k == kind for _, k, _, _ in layers(cfg))


def attention_parameters(cfg: dict, heads: int) -> int:
    """The four projections of one attention layer of ``heads`` heads."""
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    kv = int(cfg["num_key_value_heads"]) * d
    return 2 * h * heads * d + 2 * h * kv


def expert_parameters(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def shared_expert_parameters(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["shared_expert_intermediate_size"])


def dense_ffn_parameters(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter of this chip's share, by the reference's leaf name,
    in the order the weights are drawn."""
    h = int(cfg["hidden_size"])
    v = int(cfg["vocab_size"])
    d = int(cfg["head_dim"])
    kv = int(cfg["num_key_value_heads"]) * d
    held = int(cfg["num_experts"])
    routed = experts_routed(cfg)
    wide = int(cfg["intermediate_size"])
    narrow = int(cfg["moe_intermediate_size"])
    shared = int(cfg["shared_expert_intermediate_size"])
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (v, h)}
    for i, _, heads, dense in layers(cfg):
        p = f"l{i}."
        shapes[p + "op_norm"] = (h,)
        shapes[p + "attn.q"] = (h, heads * d)
        shapes[p + "attn.k"] = (h, kv)
        shapes[p + "attn.v"] = (h, kv)
        shapes[p + "attn.o"] = (heads * d, h)
        shapes[p + "ffn_norm"] = (h,)
        if dense:
            shapes[p + "ffn.w1"] = (h, wide)
            shapes[p + "ffn.w3"] = (h, wide)
            shapes[p + "ffn.w2"] = (wide, h)
        else:
            shapes[p + "shared.w1"] = (h, shared)
            shapes[p + "shared.w3"] = (h, shared)
            shapes[p + "shared.w2"] = (shared, h)
            shapes[p + "moe.gate"] = (h, routed)
            shapes[p + "moe.w1"] = (held, h, narrow)
            shapes[p + "moe.w3"] = (held, h, narrow)
            shapes[p + "moe.w2"] = (held, narrow, h)
    shapes["final_norm"] = (h,)
    shapes["head"] = (h, v)
    return shapes


def num_parameters(cfg: dict) -> int:
    total = 0
    for shape in leaf_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def state_bytes(cfg: dict) -> int:
    """Parameters and Adam's two moments, float32: what stays on the
    device all run (the gradients are a fourth copy while a step runs)."""
    return 3 * 4 * num_parameters(cfg)


def band_pairs(cfg: dict) -> int:
    """(query, key) pairs of one head's sliding window over a sequence:
    position ``i`` sees ``min(i + 1, sliding_window)`` keys."""
    t, w = seq_len(cfg), min(int(cfg["sliding_window"]), seq_len(cfg))
    return w * (w + 1) // 2 + (t - w) * w


def _attention_work(cfg: dict, rows: int, heads: int, pairs: int) -> Dict[str, int]:
    """``q k^T`` and ``p v`` over ``pairs`` (query, key) pairs a head, 2
    FLOPs a multiply-add; q and the output read and written once in the
    compute type (2 bytes), k and v once for their own heads."""
    t, d = seq_len(cfg), int(cfg["head_dim"])
    kv_heads = int(cfg["num_key_value_heads"])
    return {
        "flops": rows * heads * 2 * 2 * pairs * d,
        "bytes": rows * t * d * 2 * (2 * heads + 2 * kv_heads),
    }


def attention_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a full-attention layer over ``rows`` sequences,
    whatever implements it: the lower triangle (half of ``seq x seq``) at
    the full layers' head count."""
    t = seq_len(cfg)
    return _attention_work(cfg, rows, heads_of(cfg, FULL), t * t // 2)


def attention_window_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a sliding-window layer over ``rows`` sequences,
    whatever implements it: the band (:func:`band_pairs`) at the sliding
    layers' head count."""
    return _attention_work(cfg, rows, heads_of(cfg, SLIDING), band_pairs(cfg))


def experts_fwd_work(cfg: dict, tokens_routed: int) -> Dict[str, int]:
    """The forward grouped products of one expert layer over
    ``tokens_routed`` (token, expert) assignments to the experts held: three
    ``hidden x width`` products an assignment; each held expert's weights
    read once, each assignment's input read and output written once, its
    two ``width`` intermediates written and read, in the compute type. (At
    256 tokens an expert the weights' bytes bind, not the FLOPs.)"""
    h = int(cfg["hidden_size"])
    w = int(cfg["moe_intermediate_size"])
    held = int(cfg["num_experts"])
    return {
        "flops": tokens_routed * 3 * 2 * h * w,
        "bytes": held * 3 * h * w * 2 + tokens_routed * 2 * (2 * h + 4 * w),
    }


def tokens_routed_here(cfg: dict, rows: int) -> int:
    """Assignments that reach the experts held here a step and a layer,
    under even routing: the share ``held / routed`` of ``top_k`` a token."""
    return (
        rows * seq_len(cfg) * int(cfg["num_experts_per_tok"])
        * int(cfg["num_experts"]) // experts_routed(cfg)
    )


def flops_per_row(cfg: dict) -> int:
    """Forward and backward of one sequence on this chip's share, no
    recomputation: 6 FLOPs a matrix parameter a token (2 a multiply-add,
    three matmul-sized passes) over the attention projections, the dense
    FFN, the routers, the shared experts, the output head, and the routed
    experts at the share of a token's ``top_k`` that is held here under
    even routing; plus three passes of each attention layer's two
    products, the full layers over the triangle, the sliding ones over the
    band. Embedding lookups, norms, rotary positions, the optimizer and
    elementwise work are not counted."""
    t = seq_len(cfg)
    h = int(cfg["hidden_size"])
    routed = experts_routed(cfg)
    per_token = h * int(cfg["vocab_size"])
    for _, _, heads, dense in layers(cfg):
        per_token += attention_parameters(cfg, heads)
        if dense:
            per_token += dense_ffn_parameters(cfg)
        else:
            per_token += h * routed + shared_expert_parameters(cfg)
            per_token += (
                int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
                * expert_parameters(cfg) // routed
            )
    attention = 3 * (
        layers_of(cfg, FULL) * attention_fwd_work(cfg, 1)["flops"]
        + layers_of(cfg, SLIDING) * attention_window_fwd_work(cfg, 1)["flops"]
    )
    return 6 * per_token * t + attention
