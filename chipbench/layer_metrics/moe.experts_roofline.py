"""The expert layer's forward grouped products' share of their roofline:
the work of one layer's three products over the (token, expert) assignments
that reached the experts held (the family's counts.py, ``experts_fwd_work``;
the assignments are the step's own count where the program recorded it,
``layers["train step"]["moe:load"]``, else even routing's share), against the
larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, over the device time
the forward kernel's events take a layer. At 2,048 tokens an expert the FLOPs
bind: compute roof.

The program names the kernel (``name="moe_experts_fwd"`` on its Pallas
call); a layer's forward pass is three events (W1, W3, W2), so the time a
layer is three times the mean event. Where the grouped product is not that
kernel (another backend's ``ragged_dot`` has no name the trace shows) there
is nothing to read."""

from chipbench import trace_reduce

NEEDLE = "moe_experts_fwd"
PRODUCTS_A_LAYER = 3


def read(ctx):
    tr = ctx["trace"]
    counts = ctx["family"].counts
    work = getattr(counts, "experts_fwd_work", None)
    if not tr or not ctx["peaks"] or work is None:
        return None
    durs = trace_reduce.durations_of(tr["ops"], NEEDLE)
    if not durs:
        return None
    cfg = ctx["cfg"]
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    load = (layers.get("train step") or {}).get("moe:load") or {}
    if load.get("spans"):
        routed = int(load["sum"]["mean"] / load["spans"] * int(cfg["num_experts"]))
    else:
        routed = counts.tokens_routed_here(
            cfg, int(cfg["batch_size"]) // ctx["chips"]
        )
    w = work(cfg, routed)
    least_s = max(
        w["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        w["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    layer_s = PRODUCTS_A_LAYER * (sum(durs) / len(durs)) / 1e9
    return 100.0 * least_s / layer_s
