"""Device time a step of the selective scans, forward and backward: the sum
over the traced window of every event whose own name begins
``selective_scan_`` (the program names its two Pallas calls
``selective_scan_fwd`` and ``selective_scan_bwd``; the name is matched at the
head of an operation's text, so an operation that reads a kernel's output
does not count), divided by the train-step programs that ran in the window
(``XLA Modules`` events named after ``step_fn``, as ``step.device_ms`` counts
them). It stands beside ``step.device_ms``: the scans' share of the step. A
program without the kernels has no such event: nothing to read."""

NEEDLE = "selective_scan_"
STEP = "step_fn"


def own_name(text: str) -> str:
    """An operation's own name: what its text begins with."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    total = sum(d for text, _, d in tr["ops"] if own_name(text).startswith(NEEDLE))
    steps = sum(1 for name, _, _ in tr["modules"] if STEP in name)
    if not total or not steps:
        return None
    return total / steps / 1e6
