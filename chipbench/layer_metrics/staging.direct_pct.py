"""Share of the staged batches that went to the device straight off the
reducers' packed segments, with no host copy: a count the streaming loader
keeps (``HostToDeviceStats``), over the whole run."""


def read(ctx):
    stats = ctx["loader_stats"] or {}
    if ctx["cfg"]["loader"] != "stream" or not stats.get("batches_staged"):
        return None
    return 100.0 * stats["batches_staged_direct"] / stats["batches_staged"]
