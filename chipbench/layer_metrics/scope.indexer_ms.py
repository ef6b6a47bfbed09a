"""Device milliseconds a step under the models' scope ``indexer``: a learned
sparse attention's indexer (its projections, the scores and the top-k
kernel, the selection's counts) and its loss (the fused pass that reads the
main attention's probabilities again and the gradients it hands back).

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``). The scope is a sibling of ``attention``,
never inside it, so that ``scope.attention_ms`` does not hold it. A program
that hands over no ``step:ops`` table, or a model without the scope:
nothing to read."""

from chipbench import scope_time

SCOPE = "indexer"
NOT_AFTER = ()


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
