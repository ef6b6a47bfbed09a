"""Share of the window the loop spent inside ``next()`` of the loader, by the
benchmark's own host clock: the same reading for both loaders."""


def read(ctx):
    if not ctx["window_s"]:
        return None
    return 100.0 * ctx["wait_s"] / ctx["window_s"]
