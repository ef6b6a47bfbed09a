"""The longest iteration of the window: where an epoch hand-over (the
resident loader's permutation, the streaming loader's next epoch) shows."""


def read(ctx):
    if not ctx["iter_s"]:
        return None
    return 1e3 * max(ctx["iter_s"])
