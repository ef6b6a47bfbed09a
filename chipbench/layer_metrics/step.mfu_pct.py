"""The whole step's share of the chip's bf16 peak: the FLOPs the forward and
backward passes need per row (the family's counts.py, from the
configuration's widths), times the rows completed in the window, over the
window and the chips' peak. Recomputation, embedding traffic and the
optimizer count for nothing, so this bounds every kernel's roofline from the
end-to-end side."""


def read(ctx):
    if not ctx["peaks"] or not ctx["window_s"]:
        return None
    done = ctx["family"].counts.flops_per_row(ctx["cfg"]) * ctx["rows"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * done / ctx["window_s"] / peak
