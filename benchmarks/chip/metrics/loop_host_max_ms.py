"""The longest single thing the trainer's loop did on the host inside the
window: the longest span among the loop's five activities
(`tpudist.loop_prologue`, `loop_hooks`, `loop_meters`, `loop_log`,
`loop_epoch_end`: spans beside `tpudist.loop_host`;
tpudist/trainer.py::train_epoch). None of them waits on the device
(`tpudist.drain_ready` and `tpudist.metric_drain` do and are left out), so a
long one is the host standing still: a mean over steps (`loop_self_ms`)
passes a one-off of seconds through, this does not. Read from the host plane
of the run's newest trace, in the trace's own nanoseconds, clipped to
`bench.window`; `trace_reduce.reduce` sums a span's events away, so this
reader takes the single events itself. The line `bench loop_host` prints
each one's count, mean and longest. Nothing to read where the trace holds
none of the five (a program without them)."""

import json
import os

ACTIVITIES = ("tpudist.loop_prologue", "tpudist.loop_hooks",
              "tpudist.loop_meters", "tpudist.loop_log",
              "tpudist.loop_epoch_end")
WINDOW_SPAN = "bench.window"


def host_rows(xplane_path):
    """[name, start_ns, duration_ns] of the host planes' `tpudist.loop_*`
    and window spans."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in ACTIVITIES or e.name == WINDOW_SPAN]


def longest(rows):
    """({activity: [count, mean ms, longest ms]}, the longest of all in ms)
    over the activities' spans that start inside the window span (anywhere
    where the rows hold none); (None, None) where none of them is there."""
    window = next((r for r in rows if r[0] == WINDOW_SPAN), None)
    lo, hi = ((window[1], window[1] + window[2]) if window
              else (float("-inf"), float("inf")))
    spent = {}
    for name, start, duration in rows:
        if name in ACTIVITIES and lo <= start < hi:
            spent.setdefault(name, []).append(
                (min(start + duration, hi) - start) / 1e6)
    if not spent:
        return None, None
    return ({name: [len(ms), sum(ms) / len(ms), max(ms)]
             for name, ms in sorted(spent.items())},
            max(max(ms) for ms in spent.values()))


def read(ctx):
    from harness import scope_reduce, trace_reduce
    chip_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace_dir = scope_reduce._newest_trace_dir(chip_dir)
    if trace_dir is None:
        return None
    by_name, value = longest(host_rows(trace_reduce.newest_xplane(trace_dir)))
    if value is None:
        return None
    print("bench loop_host " + json.dumps(
        {"count_mean_ms_max_ms": by_name, "loop_host_max_ms": value}),
        flush=True)
    return value
