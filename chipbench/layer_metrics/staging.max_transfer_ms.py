"""The longest ``stage:transfer``: from ``device_put``'s dispatch to the
unpacked arrays ready on the device, the one wait of a batch that a clock
around ``next()`` cannot see (``layers.staging``). A batch's arrays are
ready only once the device reaches its unpack, which queues behind the steps
already enqueued: the floor of this reading is the step time x the steps in
flight (two run-ahead steps of 193 ms read 388 ms; the mix's twelve, since PR
27, twelve of them), not the link's time. It
is a stall detector: seconds in a run that stalls inside a transfer."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    staging = layers.get("staging") or {}
    if not staging.get("transfers"):
        return None
    return 1e3 * staging["max_transfer_s"]
