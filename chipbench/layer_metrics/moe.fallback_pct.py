"""The share of expert-layer executions that outgrew the bounded dispatch
buffer and ran in the worst-case one: ``100 * fallback / layers``, each
summed over the steps that recorded a ``moe:load`` counter while the trace
was on (``layers["train step"]["moe:load"]``: the program counts, a step,
its expert layers and those of them whose load did not fit the buffer for
twice the even share). 0 is a router that stays under twice the even load;
every point above it is a layer that moved the worst case's rows. A family
without experts, and a program that counts no ``fallback``, record none."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    load = ((layers.get("train step") or {}).get("moe:load") or {}).get("sum")
    if not load or not load.get("layers") or "fallback" not in load:
        return None
    return 100.0 * load["fallback"] / load["layers"]
