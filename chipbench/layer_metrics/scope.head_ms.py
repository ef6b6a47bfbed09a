"""Device milliseconds a step under the models' scope ``head``: the logits, a
sequence at a time (a ``while`` and the operations it runs), the
cross-entropy and their backward pass.

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``: an operation inside a ``cond`` or a ``while``
is counted once, the container for what is its own). It stands beside
``step.device_ms``: the scope's share of the step. A program that hands
over no ``step:ops`` table, or a model without the scope: nothing to read."""

from chipbench import scope_time

SCOPE = "head"
NOT_AFTER = ()


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
