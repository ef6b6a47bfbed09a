"""Device time of the resident loader's epoch hand-over: per epoch, the
``epoch_permutation`` program (``jax.random.permutation`` of the row
indices) plus the ``permute_all`` program that follows it (the gather of
the packed buffer into the epoch's copy); median over the epochs whose two
programs lie inside the trace. Nothing overlaps them with the step today."""

import statistics

NEEDLES = ("epoch_permutation", "permute_all")


def read(ctx):
    tr = ctx["trace"]
    if not tr or ctx["cfg"]["loader"] != "resident":
        return None
    draws, gathers = (
        sorted((s, d) for name, s, d in tr["modules"] if needle in name)
        for needle in NEEDLES
    )
    # An epoch's gather takes its permutation: pair each with the last
    # draw that began before it.
    sums = []
    for start, dur in gathers:
        before = [d for s, d in draws if s <= start]
        if before:
            sums.append(before[-1] + dur)
    if not sums:
        return None
    return statistics.median(sums) / 1e6
