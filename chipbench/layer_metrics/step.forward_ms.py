"""Device milliseconds a step in the forward pass: the operations whose
``op_name`` lies under the step's scope ``loss`` (``jvp(loss)``) with no
``transpose(`` in the path. A layer run again for the backward pass is not
here: it is traced under the transpose, and counts as backward.

One of four (``step.forward_ms``, ``step.backward_ms``,
``step.optimizer_ms``, ``step.unscoped_ms``) that add up to the self time
of every operation inside the window's train-step programs, a step: what
``step.device_ms`` is the median of, less the device's gaps inside a step.
``chipbench/scope_time.py`` says how an event's self time and its phase
are found. A program that hands over no ``step:ops`` table: nothing to read."""

from chipbench import scope_time


def read(ctx):
    return scope_time.phase_ms(ctx, "forward")
