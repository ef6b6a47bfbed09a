"""The forward selective scan's share of its roofline: the work of one Mamba
layer's recurrence for one batch, whatever implements it (the family's
counts.py, ``ssm_scan_fwd_work``: 6 FLOPs a (position, channel, state)
element; ``u`` and ``delta`` read and ``s`` written once in float32, ``B`` and
``C`` read once; a family without a scan has none), against the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, over the median device
time of the forward scan kernel's events in the trace. The bytes bind that
bound: a sixteenth of a FLOP a byte is nothing to the matrix unit. What
really binds the kernel is the vector unit (an exponential and six
multiply-adds an element, a sequential dependence over the sequence), whose
peak ``peaks.json`` does not hold: expect a share of a few per cent to a few
tens, and read a change in it as a change in the kernel's time.

The program names the kernel (``name="selective_scan_fwd"`` on its Pallas
call), and an operation's text in the trace begins with its own name
(``%selective_scan_fwd.3 = f32[..] custom-call(..)``): the name is matched
there, so an operation that merely reads the kernel's output does not count.
A step runs the kernel once a Mamba layer in the forward pass and once more
where the layer is recomputed: the median is over both. A program without
the kernel has no such event: nothing to read."""

import statistics

NEEDLE = "selective_scan_fwd"


def own_name(text: str) -> str:
    """An operation's own name: what its text begins with."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(ctx):
    tr = ctx["trace"]
    work = getattr(ctx["family"].counts, "ssm_scan_fwd_work", None)
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
