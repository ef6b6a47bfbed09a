"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals over the window, averaged over the
chips. Source: the profiler's trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
