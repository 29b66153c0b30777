"""How unevenly the held experts are loaded: a step's largest held expert's
(token, expert) pairs over the mean of the held experts, the mean over the
layers and over the window's steps. 1 is even; the grouped products run as
long as the sum, but a deployment's exchange waits for the fullest.

A program counter: the trainer's metric drain keeps every step's
`moe_load_max_over_mean.layer_<l>` (`tpudist.telemetry.counters()`), read in
process. The line `bench moe_route` prints, for the compared steps (the
run's first) and for the window, each layer's pairs computed by the held
experts and its load ratio; the reference prints its own per-expert counts of
the compared steps on `bench moe_route_reference`. A program without the
counters reads nothing."""

import json


def read(ctx):
    try:
        from tpudist import telemetry
        kept = telemetry.counters()
    except (ImportError, AttributeError):
        return None
    load = {k: v for k, v in kept.items()
            if k.startswith("moe_load_max_over_mean")}
    if not load:
        return None
    steps = int(ctx["steps"])
    compared = int(ctx["config"]["compared_steps"])

    def mean(xs):
        return sum(xs) / len(xs) if xs else None

    said = {}
    for name, values in sorted(kept.items()):
        if name.startswith("moe_"):
            said[name] = {"compared": values[:compared],
                          "window_mean": mean(values[-steps:])}
    print("bench moe_route " + json.dumps(said), flush=True)
    return mean([mean(values[-steps:]) for values in load.values()])
