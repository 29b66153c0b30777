"""Host time per step inside the trainer's own step annotation (`train`,
tpudist/trainer.py) that is not the dispatch of the step program (the
benchmark's `bench.dispatch` span) nor batch placement (`tpudist.h2d`, which
with the prefetcher on happens outside it): the loop's self time. Read from
the profiler trace's host plane."""


def read(ctx):
    spans = ctx["trace"]["host_spans"]
    if "train" not in spans or "bench.dispatch" not in spans:
        return None
    total, n = spans["train"]
    return 1e3 * (total - spans["bench.dispatch"][0]) / n if n else None
