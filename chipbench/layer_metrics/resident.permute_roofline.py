"""The per-epoch permutation's share of its memory roofline: the least time
to read the packed buffer once and write its permuted copy once at the
chip's HBM bandwidth, over the device time of the ``permute_all`` program
(median over the epochs in the trace). Bound: memory; it does no FLOPs."""

import statistics

from chipbench import trace_reduce, work

NEEDLE = "permute_all"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["peaks"] or ctx["cfg"]["loader"] != "resident":
        return None
    durs = trace_reduce.durations_of(tr["modules"], NEEDLE)
    if not durs:
        return None
    least_s = work.permute_bytes(ctx["cfg"]) / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (statistics.median(durs) / 1e9)
