"""How many programs were built for the backend while the window ran: the
compile requests (`/jax/core/compile/backend_compile_duration`: served by
the compiler or by the persistent cache) that the program's own listener
kept (`tpudist.telemetry.compile_events()`, read in process) and whose end
lies between the window's opening and its close, on the same
`time.perf_counter`. 0 is expected: every shape is warmed before the window
opens, and a compile inside it (a retraced step, a stray eager operation)
stalls the host for its whole length. The line `bench compiles` prints every
event of the process by the trainer's step at the time (compiles and
compile-cache reads: how many, how many seconds) and each one of the window
whole. A program without the listener reads nothing."""

import json

COMPILE = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    try:
        from tpudist import telemetry
        events = telemetry.compile_events()
    except (ImportError, AttributeError):
        return None
    by_step = {}
    for ev in events:
        kind = "compile" if ev["event"] == COMPILE else "cache_read"
        cell = by_step.setdefault(str(ev["step"]), {})
        cell[kind] = cell.get(kind, 0) + 1
        cell[kind + "_s"] = cell.get(kind + "_s", 0.0) + ev["seconds"]
    inside = [ev for ev in events
              if ctx["t_open"] <= ev["t_end"] <= ctx["t_close"]]
    count = sum(ev["event"] == COMPILE for ev in inside)
    print("bench compiles " + json.dumps({
        "events": len(events), "by_step": by_step,
        "in_window": [dict(ev, t_end=ev["t_end"] - ctx["t_open"])
                      for ev in inside],
        "window_compile_count": count}), flush=True)
    return count
