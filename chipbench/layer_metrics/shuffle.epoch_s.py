"""How long the host takes to make one epoch: the median ``shuffle:epoch``
span, first map submitted to last reducer output handed to the queue, over
the epochs that began and ended while the trace was on (``layers.shuffle``).
While the step hides the loader this is the loader's own speed; the step
side of an epoch is batches x step time."""

import statistics


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    epoch_s = (layers.get("shuffle") or {}).get("epoch_s")
    if not epoch_s:
        return None
    return statistics.median(epoch_s)
