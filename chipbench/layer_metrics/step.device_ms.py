"""Median device time of the train-step program: the ``XLA Modules`` events
of the trace whose name holds the jitted step's name. The program puts no
named scope on the step today, so the name is the one jit gives the traced
function (``step_fn`` in parallel/train.py)."""

import statistics

from chipbench import trace_reduce

NEEDLE = "step_fn"


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    durs = trace_reduce.durations_of(tr["modules"], NEEDLE)
    if not durs:
        return None
    return statistics.median(durs) / 1e6
