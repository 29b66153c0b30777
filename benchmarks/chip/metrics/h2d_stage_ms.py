"""Host time per batch inside the program's `tpudist.h2d` annotation
(`dist.shard_host_batch`: issuing the batch's placement on the mesh), from the
profiler trace's host plane."""


def read(ctx):
    total, n = ctx["trace"]["host_spans"].get("tpudist.h2d", (0.0, 0))
    return 1e3 * total / n if n else None
