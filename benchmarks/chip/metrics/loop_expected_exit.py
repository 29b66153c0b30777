"""The pass at which a position is expected to exit, in a model whose layers
run several times: the mean over positions of `sum_t t p_t` (`p_t`: the exit
distribution the gate states after pass `t`), the mean over the window's
steps. At 1 every position would leave after the first pass; at
`total_ut_steps` none before the last; a gate at `lambda = 1/2` everywhere
reads 1.875 of 4.

A program counter: the trainer's metric drain keeps every step's
`loop_expected_exit` and `loop_exit_entropy` (the mean of `H(p)`, nats;
`tpudist.telemetry.counters()`), read in process. The line `bench
loop_counters` prints both for the compared steps (the run's first; the
reference prints its own on `bench loop_exit_reference`) and for the window.
A program without the counters reads nothing."""

import json

NAMES = ("loop_expected_exit", "loop_exit_entropy")


def read(ctx):
    try:
        from tpudist import telemetry
        kept = telemetry.counters()
    except (ImportError, AttributeError):
        return None
    kept = {name: kept[name] for name in NAMES if kept.get(name)}
    if NAMES[0] not in kept:
        return None
    steps = int(ctx["steps"])
    compared = int(ctx["config"]["compared_steps"])
    window = {name: values[-steps:] for name, values in kept.items()}
    print("bench loop_counters " + json.dumps({
        name: {"compared": kept[name][:compared],
               "window_mean": sum(values) / len(values),
               "window_min": min(values), "window_max": max(values)}
        for name, values in window.items()}), flush=True)
    return sum(window[NAMES[0]]) / len(window[NAMES[0]])
