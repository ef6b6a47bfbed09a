"""Device milliseconds a step under the model's scope ``short_conv``: LFM2's
gated short-convolution operators, projections and taps.

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``: an operation inside a ``cond`` or a ``while``
is counted once, the container for what is its own). It stands beside
``step.device_ms``: the scope's share of the step. A program that hands
over no ``step:ops`` table, or a model without the scope: nothing to read."""

from chipbench import scope_time

SCOPE = "short_conv"
NOT_AFTER = ()


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
