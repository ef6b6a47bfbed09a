"""From the call that constructs the loader (runtime up, files on disk, model
state on the device) to the first batch of epoch 0 ready on the device: what
a user pays at every start and resume before the first step. One sample to
a run, and too unsteady in ``stream-train`` for an end-to-end bound (PERF.md
section 2), so it stands here beside ``setup_s``, which contains it."""


def read(ctx):
    return ctx["first_batch_s"]
