"""What one chip's share of the LFM2-MoE needs, from the configuration's
sizes alone. Nothing here looks at the program.

A row of this family is one sequence of ``seq_len`` tokens. The layers kept
are the published layers ``first_layer .. first_layer + num_hidden_layers``;
layer ``i`` has the operator ``layer_types[i]`` and a dense FFN where ``i <
num_dense_layers``, else the experts, of which ``num_experts`` (the
configuration's key, reduced) are held here and ``published.num_experts``
routed over.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


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


def head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def layers(cfg: dict) -> List[Tuple[int, str, bool]]:
    """``(published index, operator, dense FFN?)`` of every layer kept."""
    first = int(cfg["first_layer"])
    return [
        (i, cfg["layer_types"][i], i < int(cfg["num_dense_layers"]))
        for i in range(first, first + int(cfg["num_hidden_layers"]))
    ]


def operator_parameters(cfg: dict, kind: str) -> int:
    """Matrix parameters of one operator (its norms and the convolution's
    taps are counted in ``num_parameters``, not in the FLOPs)."""
    h = int(cfg["hidden_size"])
    if kind == "conv":
        return h * 3 * h + h * h
    d = head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * d
    kv = int(cfg["num_key_value_heads"]) * d
    return h * q + 2 * h * kv + q * h


def expert_parameters(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def dense_ffn_parameters(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter of this chip's share, by the reference's leaf name,
    in the order the weights are drawn."""
    h = int(cfg["hidden_size"])
    v = int(cfg["vocab_size"])
    d = head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * d
    kv = int(cfg["num_key_value_heads"]) * d
    held = int(cfg["num_experts"])
    routed = experts_routed(cfg)
    wide = int(cfg["intermediate_size"])
    narrow = int(cfg["moe_intermediate_size"])
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (v, h)}
    for i, kind, dense in layers(cfg):
        p = f"l{i}."
        shapes[p + "op_norm"] = (h,)
        if kind == "conv":
            shapes[p + "conv.in"] = (h, 3 * h)
            shapes[p + "conv.taps"] = (h, int(cfg["conv_L_cache"]))
            shapes[p + "conv.out"] = (h, h)
        else:
            shapes[p + "attn.q"] = (h, q)
            shapes[p + "attn.k"] = (h, kv)
            shapes[p + "attn.v"] = (h, kv)
            shapes[p + "attn.o"] = (q, h)
            shapes[p + "attn.q_norm"] = (d,)
            shapes[p + "attn.k_norm"] = (d,)
        shapes[p + "ffn_norm"] = (h,)
        if dense:
            shapes[p + "ffn.w1"] = (h, wide)
            shapes[p + "ffn.w3"] = (h, wide)
            shapes[p + "ffn.w2"] = (wide, h)
        else:
            shapes[p + "moe.gate"] = (h, routed)
            if cfg.get("use_expert_bias"):
                shapes[p + "moe.bias"] = (routed,)
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


def attention_layers(cfg: dict) -> int:
    return sum(kind == "full_attention" for _, kind, _ in layers(cfg))


def attention_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward causal attention over ``rows`` sequences, whatever
    implements it: ``q k^T`` and ``p v`` over the lower triangle (half of
    ``seq x seq``), 2 FLOPs a multiply-add; q and the output read and
    written once in the compute type (2 bytes), k and v once for their own
    heads."""
    t = seq_len(cfg)
    d = head_dim(cfg)
    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    return {
        "flops": rows * heads * 2 * 2 * t * t * d // 2,
        "bytes": rows * t * d * 2 * (2 * heads + 2 * kv_heads),
    }


def experts_fwd_work(cfg: dict, tokens_routed: int) -> Dict[str, int]:
    """The forward grouped products of one expert layer over
    ``tokens_routed`` (token, expert) assignments to the experts held: three
    ``hidden x width`` products an assignment; each held expert's weights
    read once, each assignment's input read and output written once, its
    two ``width`` intermediates written and read, in the compute type."""
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
    three matmul-sized passes) over the operators, the dense FFNs, the
    routers, the output head, and the experts at the share of a token's
    ``top_k`` that is held here under even routing; plus three passes of
    the causal attention's two products. Embedding lookups, norms, the
    convolution's taps, the optimizer and elementwise work are not
    counted."""
    t = seq_len(cfg)
    h = int(cfg["hidden_size"])
    routed = experts_routed(cfg)
    per_token = h * int(cfg["vocab_size"])
    for _, kind, dense in layers(cfg):
        per_token += operator_parameters(cfg, kind)
        if dense:
            per_token += dense_ffn_parameters(cfg)
        else:
            per_token += h * routed
            per_token += (
                int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
                * expert_parameters(cfg) // routed
            )
    attention = 3 * attention_layers(cfg) * attention_fwd_work(cfg, 1)["flops"]
    return 6 * per_token * t + attention
