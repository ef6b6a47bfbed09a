"""The share of the causal (query block, key block) pairs that hold a pair
the indexer selected: ``100 * blocks / causal_blocks``, each summed over
the steps that recorded a ``sparse:select`` counter while the trace was on
(``layers["train step"]["sparse:select"]``: the program counts, a step and
summed over its layers, the blocks whose words hold a set bit, the causal
blocks, and the selected pairs). The sparse kernels visit these blocks and
skip the rest: 100 is a selection spread over every block, and every point
under it is a block no kernel fetched. A family without a learned selection
records none."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    select = ((layers.get("train step") or {}).get("sparse:select") or {}).get("sum")
    if not select or not select.get("causal_blocks"):
        return None
    return 100.0 * select["blocks"] / select["causal_blocks"]
