"""Median device time of the streaming loader's jitted unpack (row slices
and bitcasts of the packed batch): the ``XLA Modules`` events of the trace
whose name holds ``unpack``, the name of the function the loader jits."""

import statistics

from chipbench import trace_reduce

NEEDLE = "unpack"


def read(ctx):
    tr = ctx["trace"]
    if not tr or ctx["cfg"]["loader"] != "stream":
        return None
    durs = trace_reduce.durations_of(tr["modules"], NEEDLE)
    if not durs:
        return None
    return statistics.median(durs) / 1e6
