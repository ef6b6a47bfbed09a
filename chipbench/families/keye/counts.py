"""What one chip's share of Keye-VL-2.0's language model needs, from the
configuration's sizes alone. Nothing here looks at the program.

A row of this family is one sequence of ``seq_len`` tokens. Every layer
kept is alike: grouped-query attention with q/k norms over the keys the
indexer chose, the indexer (``sa_config``), a softmax router over the
published ``num_experts`` and the experts held here (the configuration's
``num_experts``, reduced).
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


def layer_indices(cfg: dict) -> List[int]:
    first = int(cfg["first_layer"])
    return list(range(first, first + int(cfg["num_hidden_layers"])))


def index_sizes(cfg: dict) -> Tuple[int, int, int]:
    """``(heads, head dim, topk)`` of the indexer."""
    sa = cfg["sa_config"]
    return int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]), int(sa["topk"])


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter of this chip's share, by the reference's leaf name,
    in the order the weights are drawn."""
    h = int(cfg["hidden_size"])
    v = int(cfg["vocab_size"])
    d = int(cfg["head_dim"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"]) * d
    held = int(cfg["num_experts"])
    width = int(cfg["moe_intermediate_size"])
    ih, idim, _ = index_sizes(cfg)
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (v, h)}
    for i in layer_indices(cfg):
        p = f"l{i}."
        shapes[p + "in_norm"] = (h,)
        shapes[p + "attn.q"] = (h, heads * d)
        shapes[p + "attn.k"] = (h, kv)
        shapes[p + "attn.v"] = (h, kv)
        shapes[p + "attn.o"] = (heads * d, h)
        shapes[p + "attn.q_norm"] = (d,)
        shapes[p + "attn.k_norm"] = (d,)
        shapes[p + "idx.q"] = (h, ih * idim)
        shapes[p + "idx.k"] = (h, idim)
        shapes[p + "idx.w"] = (h, ih)
        shapes[p + "idx.k_norm"] = (idim,)
        shapes[p + "idx.k_bias"] = (idim,)
        shapes[p + "post_norm"] = (h,)
        shapes[p + "moe.gate"] = (h, experts_routed(cfg))
        shapes[p + "moe.w1"] = (held, h, width)
        shapes[p + "moe.w3"] = (held, h, width)
        shapes[p + "moe.w2"] = (held, width, h)
    shapes["final_norm"] = (h,)
    shapes["head"] = (h, v)
    return shapes


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def num_parameters(cfg: dict) -> int:
    return sum(_size(s) for s in leaf_shapes(cfg).values())


def state_bytes(cfg: dict) -> int:
    """Parameters and Adam's two moments, float32: what stays on the
    device all run (the gradients are a fourth copy while a step runs)."""
    return 3 * 4 * num_parameters(cfg)


def causal_pairs(cfg: dict) -> int:
    """(query, key) pairs of one sequence under the causal mask."""
    t = seq_len(cfg)
    return t * (t + 1) // 2


def selected_pairs(cfg: dict) -> int:
    """(query, key) pairs of one sequence the selection keeps: ``min(topk,
    t + 1)`` a query."""
    t = seq_len(cfg)
    k = min(index_sizes(cfg)[2], t)
    return k * (k + 1) // 2 + (t - k) * k


def attention_sparse_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a layer's attention over ``rows`` sequences, whatever
    implements it: ``q k^T`` and ``p v`` over the selected pairs (2 FLOPs a
    multiply-add) at every query head; q and the output read and written
    once, k and v once for their own heads, in the compute type (2 bytes)."""
    t, d = seq_len(cfg), int(cfg["head_dim"])
    heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return {
        "flops": rows * heads * 2 * 2 * selected_pairs(cfg) * d,
        "bytes": rows * t * d * 2 * (2 * heads + 2 * kv_heads),
    }


def index_fwd_work(cfg: dict, rows: int) -> Dict[str, int]:
    """One forward of a layer's indexer over ``rows`` sequences, whatever
    implements it: ``q^I k^I`` over the causal pairs at every indexer head
    (2 FLOPs a multiply-add); ``q^I``, ``k^I`` and ``w`` read once in
    float32, the selection's bitmask written once (a bit a pair of the
    square)."""
    t = seq_len(cfg)
    ih, idim, _ = index_sizes(cfg)
    return {
        "flops": rows * causal_pairs(cfg) * ih * idim * 2,
        "bytes": rows * (t * (ih * idim + idim + ih) * 4 + t * t // 8),
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
    recomputation: 6 FLOPs a matrix parameter a token over the attention
    projections, the indexer's projections, the router, the output head,
    and the routed experts at the share of a token's ``top_k`` held here
    under even routing; three passes of the attention's two products over
    the selected pairs, and three passes of the indexer's scores over the
    causal pairs (its forward, and the two products of its loss's
    gradient). The main probabilities that the indexer's loss reads are the
    attention's own. Embedding lookups, norms, rotary positions, the
    selection, the optimizer and elementwise work are not counted."""
    t = seq_len(cfg)
    h = int(cfg["hidden_size"])
    d = int(cfg["head_dim"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    ih, idim, _ = index_sizes(cfg)
    attention = 2 * h * heads * d + 2 * h * kv * d
    indexer = h * (ih * idim + idim + ih)
    experts = (
        int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
        * 3 * h * int(cfg["moe_intermediate_size"]) // experts_routed(cfg)
    )
    layers = int(cfg["num_hidden_layers"])
    per_token = h * int(cfg["vocab_size"]) + layers * (
        attention + indexer + h * experts_routed(cfg) + experts
    )
    mixing = 3 * layers * (
        attention_sparse_fwd_work(cfg, 1)["flops"] + index_fwd_work(cfg, 1)["flops"]
    )
    return 6 * per_token * t + mixing
