"""Images per second of the cell's loader drained alone for a few seconds in
the traced run's set-up, no device step behind it. Mixes whose batches are
resident have no loader to drain: nothing to read."""


def read(ctx):
    return ctx["loader_img_per_s"]
