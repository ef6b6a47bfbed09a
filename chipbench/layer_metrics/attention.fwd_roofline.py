"""The forward causal attention's share of its roofline: the work of the
algorithm for one batch, whatever implements it (the family's counts.py,
``attention_fwd_work``: the lower triangle of ``q k^T`` and ``p v``, q, k, v
and the output moved once; a family without attention has none), against the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, over the
median device time of the forward kernel's events in the trace. At 8,192
positions and heads of 64 the FLOPs bind: compute roof.

The program names the kernel (``name="flash_attention_fwd"`` on its Pallas
call), and the trace shows that name in the operation's. A step runs it once
a forward pass and once more where the layer is recomputed: the median is
over both."""

import statistics

from chipbench import trace_reduce

NEEDLE = "flash_attention_fwd"


def read(ctx):
    tr = ctx["trace"]
    work = getattr(ctx["family"].counts, "attention_fwd_work", None)
    if not tr or not ctx["peaks"] or work is None:
        return None
    durs = trace_reduce.durations_of(tr["ops"], NEEDLE)
    if not durs:
        return None
    rows = int(ctx["cfg"]["batch_size"]) // ctx["chips"]
    w = work(ctx["cfg"], rows)
    least_s = max(
        w["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        w["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (statistics.median(durs) / 1e9)
