"""The share of the window's epochs that the host shuffled by the ``index``
schedule (the one the decode cache exists for: a file's rows are decoded once
and every later epoch gathers from the cached columns by a permutation), of
the ``shuffle:epoch`` spans that began and ended while the trace was on
(``layers.shuffle.schedules``, in the order of ``epoch_s``). It says which
schedule ran, not whether it is the faster one: that is for
``shuffle.epoch_s`` beside this reading to decide (ROADMAP D11). A program
that records no schedule: nothing to read."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    schedules = (layers.get("shuffle") or {}).get("schedules")
    if not schedules:
        return None
    return 100.0 * sum(s == "index" for s in schedules) / len(schedules)
