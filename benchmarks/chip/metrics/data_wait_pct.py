"""Share of the window the host spent inside the loader's `next()` (the
benchmark's `bench.loader_next` span). Under the trainer's prefetcher that
time overlaps the device's step, so it limits throughput only once it nears
100 %."""


def read(ctx):
    spent = ctx["spans"].total("bench.loader_next", ctx["t_open"], ctx["t_close"])
    return 100.0 * spent / ctx["window_s"]
