"""Device milliseconds a step under the models' scope ``latent``: latent
attention's low-rank path (the projection down to the latent and the shared
rotary key, the latent's norm, the projection up to each head's key part
and value, the shared key's rotary). It is a part of
``scope.attention_ms``: the scope lies inside ``attention``, so that
reader holds it too, with the query and output projections and the
kernels beside it.

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``). A program that hands over no ``step:ops``
table, or a model without the scope: nothing to read."""

from chipbench import scope_time

SCOPE = "latent"
NOT_AFTER = ()


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
