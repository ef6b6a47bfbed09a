"""The forward latent attention's share of its roofline: the work of one
layer's attention for one batch, whatever implements it (the family's
counts.py, ``attention_latent_fwd_work``: ``q k^T`` over the causal pairs at
the query-key width, a per-head part and a part that every head shares,
and ``p v`` at the value width, at every head; q, the keys' two parts, v
and the output moved once; a family without latent attention has none),
against the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth, over the median device time of the latent forward kernel's
events in the trace. A step runs it once a forward pass (the recomputed
layer keeps its output).

The program names the kernel (``name="flash_attention_latent_fwd"`` on its
Pallas call), and an operation's text in the trace begins with its own name:
the name is matched there, so an operation that merely reads the kernel's
output does not count. A program without the kernel has no such event:
nothing to read."""

import statistics

NEEDLE = "flash_attention_latent_fwd"


def own_name(text: str) -> str:
    """An operation's own name: what its text begins with."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(ctx):
    tr = ctx["trace"]
    work = getattr(ctx["family"].counts, "attention_latent_fwd_work", None)
    if not tr or not ctx["peaks"] or work is None:
        return None
    durs = [d for text, _, d in tr["ops"] if own_name(text).startswith(NEEDLE)]
    if not durs:
        return None
    rows = int(ctx["cfg"]["batch_size"]) // ctx["chips"]
    w = work(ctx["cfg"], rows)
    least_s = max(
        w["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        w["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (statistics.median(durs) / 1e9)
