"""What one chip's share of Kimi-VL's language model needs, from the
configuration's sizes alone. Nothing here looks at the program.

A row of this family is one sequence of ``seq_len`` tokens. The layers kept
are the published layers ``first_layer .. first_layer + num_hidden_layers``;
every one has multi-head latent attention (``kv_lora_rank``,
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``, ``q_lora_rank``
null), and the first ``first_k_dense_replace`` a dense FFN, the others the
shared experts and the routed experts, of which ``n_routed_experts`` (the
configuration's key, reduced) are held here and ``published.n_routed_experts``
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
    return int(cfg["published"]["n_routed_experts"])


def experts_held(cfg: dict) -> int:
    return int(cfg["n_routed_experts"])


def layers(cfg: dict) -> List[Tuple[int, bool]]:
    """``(published index, dense FFN?)`` of every layer kept."""
    first, dense = int(cfg["first_layer"]), int(cfg["first_k_dense_replace"])
    freq = int(cfg["moe_layer_freq"])
    return [
        (i, i < dense or (i - dense) % freq != 0)
        for i in range(first, first + int(cfg["num_hidden_layers"]))
    ]


def head_dims(cfg: dict) -> Tuple[int, int, int]:
    """``(nope, rope, value)`` widths of a head: a query or key head is
    ``nope + rope`` wide, its rope part one key head every head reads."""
    return (
        int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]),
    )


def attention_parameters(cfg: dict) -> int:
    """The four projections of one latent attention layer (the latent's
    norm not counted)."""
    h, n, rank = int(cfg["hidden_size"]), int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rope, dv = head_dims(cfg)
    return h * n * (nope + rope) + h * (rank + rope) + rank * n * (nope + dv) + n * dv * h


def shared_width(cfg: dict) -> int:
    """The shared experts as one gated FFN of their summed width."""
    return int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"])


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter of this chip's share, by the reference's leaf name,
    in the order the weights are drawn."""
    h = int(cfg["hidden_size"])
    v = int(cfg["vocab_size"])
    n, rank = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rope, dv = head_dims(cfg)
    held, routed = experts_held(cfg), experts_routed(cfg)
    wide, narrow = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    shared = shared_width(cfg)
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (v, h)}
    for i, dense in layers(cfg):
        p = f"l{i}."
        shapes[p + "in_norm"] = (h,)
        shapes[p + "attn.q"] = (h, n * (nope + rope))
        shapes[p + "attn.kv_a"] = (h, rank + rope)
        shapes[p + "attn.kv_norm"] = (rank,)
        shapes[p + "attn.kv_b"] = (rank, n * (nope + dv))
        shapes[p + "attn.o"] = (n * dv, h)
        shapes[p + "post_norm"] = (h,)
        if dense:
            shapes[p + "ffn.w1"] = (h, wide)
            shapes[p + "ffn.w3"] = (h, wide)
            shapes[p + "ffn.w2"] = (wide, h)
        else:
            shapes[p + "shared.w1"] = (h, shared)
            shapes[p + "shared.w3"] = (h, shared)
            shapes[p + "shared.w2"] = (shared, h)
            shapes[p + "moe.gate"] = (h, routed)
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


def causal_pairs(cfg: dict) -> int:
    """(query, key) pairs of one sequence under the causal mask."""
    t = seq_len(cfg)
    return t * (t + 1) // 2


def attention_latent_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a layer's latent attention over ``rows`` sequences,
    whatever implements it: at every head, ``q k^T`` over the causal pairs
    at the query-key width ``nope + rope`` and ``p v`` at ``v_head_dim`` (2
    FLOPs a multiply-add); q, ``k_nope``, the one ``k_rope`` head, v and the
    output moved once, in the compute type (2 bytes). The masked-off halves
    of the diagonal's blocks are not work."""
    t, n = seq_len(cfg), int(cfg["num_attention_heads"])
    nope, rope, dv = head_dims(cfg)
    return {
        "flops": rows * causal_pairs(cfg) * n * (nope + rope + dv) * 2,
        "bytes": rows * t * 2 * (n * (nope + rope) + n * nope + rope + 2 * n * dv),
    }


def experts_fwd_work(cfg: dict, tokens_routed: int) -> Dict[str, int]:
    """The forward grouped products of one expert layer over
    ``tokens_routed`` (token, expert) assignments to the experts held: three
    ``hidden x width`` products an assignment; each held expert's weights
    read once, each assignment's input read and output written once, its
    two ``width`` intermediates written and read, in the compute type."""
    h = int(cfg["hidden_size"])
    w = int(cfg["moe_intermediate_size"])
    return {
        "flops": tokens_routed * 3 * 2 * h * w,
        "bytes": experts_held(cfg) * 3 * h * w * 2 + tokens_routed * 2 * (2 * h + 4 * w),
    }


def tokens_routed_here(cfg: dict, rows: int) -> int:
    """Assignments that reach the experts held here a step and a layer,
    under even routing: the share ``held / routed`` of ``top_k`` a token."""
    return (
        rows * seq_len(cfg) * int(cfg["num_experts_per_tok"])
        * experts_held(cfg) // experts_routed(cfg)
    )


def flops_per_row(cfg: dict) -> int:
    """Forward and backward of one sequence on this chip's share, no
    recomputation: 6 FLOPs a matrix parameter a token (2 a multiply-add,
    three matmul-sized passes) over the latent attention's four
    projections, the dense FFN, the routers, the shared experts, the output
    head, and the routed experts at the share of a token's ``top_k`` that
    is held here under even routing; plus three passes of each layer's two
    attention products over the causal pairs. Embedding lookups, norms,
    rotary positions, the optimizer and elementwise work are not counted."""
    t, h = seq_len(cfg), int(cfg["hidden_size"])
    routed = experts_routed(cfg)
    expert = 3 * h * int(cfg["moe_intermediate_size"])
    per_token = h * int(cfg["vocab_size"])
    for _, dense in layers(cfg):
        per_token += attention_parameters(cfg)
        if dense:
            per_token += 3 * h * int(cfg["intermediate_size"])
        else:
            per_token += h * routed + 3 * h * shared_width(cfg)
            per_token += int(cfg["num_experts_per_tok"]) * experts_held(cfg) * expert // routed
    attention = 3 * len(layers(cfg)) * attention_latent_fwd_work(cfg, 1)["flops"]
    return 6 * per_token * t + attention
