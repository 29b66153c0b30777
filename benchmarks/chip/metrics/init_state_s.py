"""Seconds of `Trainer.__init__` spent building the train state: the
program's own set-up phases `init.model_state` (the eager `model.init` and
`tx.init`) and `init.shard_state`, read in process from
`tpudist.telemetry.phases()`. Prints every `init.*` phase on `bench
init_phases`; their sum is the constructor's wall time (the benchmark's
`trainer_init_s` also holds the import of `tpudist.trainer`). A program
without the phases reads nothing."""

import json


def read(ctx):
    try:
        from tpudist import telemetry
        phases = telemetry.phases()
    except (ImportError, AttributeError):
        return None
    if "init.model_state" not in phases:
        return None
    booked = {k: v for k, v in phases.items() if k.startswith("init.")}
    print("bench init_phases " + json.dumps(
        dict(booked, sum_s=sum(booked.values()))), flush=True)
    return phases["init.model_state"] + phases.get("init.shard_state", 0.0)
