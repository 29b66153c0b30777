"""The second prediction depth's cross entropy over the first's: the mean
over the window's steps of `mtp_loss / lm_loss_main` (both unweighted: the
id after the next against the next id). About 1 at seeded weights; it falls
below 1 only once the module has learnt something the trunk has not; 0 or
nothing to read where the second loss is not taken.

A program counter: the trainer's metric drain keeps every step's `mtp_loss`
and `lm_loss_main` (`tpudist.telemetry.counters()`), read in process. The
line `bench mtp_counters` prints both for the compared steps (the run's
first; the reference prints its own on `bench moe_route_reference`) and for
the window. A program without the counters reads nothing."""

import json

NAMES = ("mtp_loss", "lm_loss_main")


def read(ctx):
    try:
        from tpudist import telemetry
        kept = telemetry.counters()
    except (ImportError, AttributeError):
        return None
    kept = {name: kept[name] for name in NAMES if kept.get(name)}
    if set(kept) != set(NAMES):
        return None
    steps = int(ctx["steps"])
    compared = int(ctx["config"]["compared_steps"])
    window = {name: values[-steps:] for name, values in kept.items()}
    ratios = [a / b for a, b in zip(*(window[n] for n in NAMES)) if b]
    if not ratios:
        return None
    print("bench mtp_counters " + json.dumps({
        name: {"compared": kept[name][:compared],
               "window_mean": sum(values) / len(values)}
        for name, values in window.items()}), flush=True)
    return sum(ratios) / len(ratios)
