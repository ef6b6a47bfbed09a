"""Share of the stager thread's life spent waiting in ``get_batch`` on the
queue actor: the ``queue:get`` spans over the ``stage:epoch`` spans
(``layers.delivery``, ``layers.staging``). Near zero while reducer outputs
wait in the queue; it grows when the shuffle starts to bind."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    delivery = layers.get("delivery") or {}
    stager_s = (layers.get("staging") or {}).get("stager_s")
    if not stager_s or not delivery.get("gets"):
        return None
    return 100.0 * delivery["get_wait_s"] / stager_s
