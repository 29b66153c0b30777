"""Seconds inside the benchmark's `bench.compile` span: lowering and compiling
the trainer's step (a read of the persistent cache on every run but a
checkout's first)."""


def read(ctx):
    return ctx["compile_s"]
