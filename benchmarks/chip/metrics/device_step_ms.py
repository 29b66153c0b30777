"""Device time of one run of the step program: mean duration of the trace's
module events named after the trainer's compiled step, whole steps inside the
traced window only."""


def read(ctx):
    return ctx["trace"]["device_step_ms"]
