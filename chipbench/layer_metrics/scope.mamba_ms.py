"""Device milliseconds a step of the Mamba layers OUTSIDE their scans: the scope
``mamba`` without ``ssm_scan`` behind it in the path (the projections, the
convolution, softplus and the gate). The scans are ``ssm.scan_ms``'s.

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``: an operation inside a ``cond`` or a ``while``
is counted once, the container for what is its own). It stands beside
``step.device_ms``: the scope's share of the step. A program that hands
over no ``step:ops`` table, or a model without the scope: nothing to read."""

from chipbench import scope_time

SCOPE = "mamba"
NOT_AFTER = ("ssm_scan",)


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
