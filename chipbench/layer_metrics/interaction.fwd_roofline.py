"""The forward pairwise interaction's share of its roofline: the work of the
algorithm for one batch, whatever implements it (the family's counts.py,
``interaction_fwd_work``: a family without that kernel has none), against
the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, over
the median device time of the forward kernel's events in the trace. At
these shapes (19 vectors of 32 or 128) the bytes bind: memory roof.

The program gives the kernel no name of its own today: the trace shows it
as the step's one Mosaic call (``custom_call_target="tpu_custom_call"``,
once a step; the backward interaction is plain XLA)."""

import statistics

from chipbench import trace_reduce

NEEDLE = "tpu_custom_call"


def read(ctx):
    tr = ctx["trace"]
    work = getattr(ctx["family"].counts, "interaction_fwd_work", None)
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
