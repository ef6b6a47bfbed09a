"""How unevenly the router loads the experts held: the fullest held expert's
tokens over the mean held expert's, each summed over the steps that recorded
a ``moe:load`` counter while the trace was on (``layers["train
step"]["moe:load"]``: the program's sums of what its step computes on the
device and hands over after the window). 1 is even; the fullest expert's
rows are what a step waits for. A family without experts records none."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    load = ((layers.get("train step") or {}).get("moe:load") or {}).get("sum")
    if not load or not load.get("mean"):
        return None
    return load["max"] / load["mean"]
