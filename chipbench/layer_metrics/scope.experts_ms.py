"""Device milliseconds a step under the models' scope ``experts``: the dispatch
plan, the ``cond`` that picks the bounded or the worst-case buffer (its own
time, and its branch's gathers, casts and grouped products once each) and
the combine. Copies inside a branch that the compiler gave no ``op_name``
read as ``step.unscoped_ms``.

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``: an operation inside a ``cond`` or a ``while``
is counted once, the container for what is its own). It stands beside
``step.device_ms``: the scope's share of the step. A program that hands
over no ``step:ops`` table, or a model without the scope: nothing to read."""

from chipbench import scope_time

SCOPE = "experts"
NOT_AFTER = ()


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
