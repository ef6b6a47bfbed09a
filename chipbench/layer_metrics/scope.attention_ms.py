"""Device milliseconds a step under the models' scope ``attention``: the full
causal attention layers with their projections, rotary embedding, the
kernels (``flash_attention_fwd``, ``_bwd_dkv``, ``_bwd_dq``) and the relayouts
of their statistics. Windowed and cross-attention layers have scopes of their
own and are not here.

Self time of the window's train-step operations whose ``op_name`` holds the
scope, forward, backward and recomputation together, a step
(``chipbench/scope_time.py``: an operation inside a ``cond`` or a ``while``
is counted once, the container for what is its own). It stands beside
``step.device_ms``: the scope's share of the step. A program that hands
over no ``step:ops`` table, or a model without the scope: nothing to read."""

from chipbench import scope_time

SCOPE = "attention"
NOT_AFTER = ()


def read(ctx):
    return scope_time.scope_ms(ctx, SCOPE, NOT_AFTER)
