"""The indexer's forward kernel's share of its roofline: the work of one
layer's scores over the causal (query, key) pairs for one batch, whatever
implements them (the family's counts.py, ``index_fwd_work``: ``q^I k^I`` at
every indexer head over the causal pairs; ``q^I``, ``k^I`` and ``w`` read
once, the selection's bitmask written once; a family without an indexer has
none), against the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth, over the median device time of the kernel's events. The kernel
also finds each query's top-k, which the count leaves out: the share says
what the scores alone would allow.

The program names the kernel (``name="sparse_index_fwd"``), matched at the
head of an operation's text as the other kernels' readers match theirs. A
program without it: nothing to read."""

import statistics

NEEDLE = "sparse_index_fwd"


def own_name(text: str) -> str:
    """An operation's own name: what its text begins with."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(ctx):
    tr = ctx["trace"]
    work = getattr(ctx["family"].counts, "index_fwd_work", None)
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
