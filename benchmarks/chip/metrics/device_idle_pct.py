"""1 - (union of the device's operation intervals) / traced window."""


def read(ctx):
    return ctx["trace"]["idle_pct"]
