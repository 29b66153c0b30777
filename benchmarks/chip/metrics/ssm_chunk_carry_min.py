"""How much of a state outlives one chunk where it fades fastest: the least
`exp(sum of dt A over a chunk)` over heads, chunks and rows, the least over
the Mamba-2 blocks and over the window's steps. At 0 (a float32 underflow:
a head with a large `|A|` and a large `dt`) the pass between chunks carries
nothing for that head and its scan is independent blocks; near 1 a state
crosses many chunks.

A program counter: the trainer's metric drain keeps every step's
`ssm_chunk_carry_min.layer_<l>` and `ssm_dt_mean.layer_<l>` (the mean of
`softplus(dt + dt_bias)`; `tpudist.telemetry.counters()`), read in process.
The line `bench ssm_counters` prints both, a block, for the compared steps
(the run's first) and for the window; the reference prints its own `dt` mean
and what outlives a whole row on `bench moe_route_reference`. A program
without the counters reads nothing."""

import json


def read(ctx):
    try:
        from tpudist import telemetry
        kept = telemetry.counters()
    except (ImportError, AttributeError):
        return None
    kept = {k: v for k, v in kept.items() if k.startswith("ssm_") and v}
    carry = {k: v for k, v in kept.items()
             if k.startswith("ssm_chunk_carry_min")}
    if not carry:
        return None
    steps = int(ctx["steps"])
    compared = int(ctx["config"]["compared_steps"])
    print("bench ssm_counters " + json.dumps({
        name: {"compared": values[:compared],
               "window_mean": sum(values[-steps:]) / len(values[-steps:]),
               "window_min": min(values[-steps:])}
        for name, values in sorted(kept.items())}), flush=True)
    return min(min(values[-steps:]) for values in carry.values())
