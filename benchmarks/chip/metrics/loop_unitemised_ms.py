"""`step_unitemised_ms` for a step that holds the two leaf scopes this
reader's configuration brought: the busy time in named operations whose HLO
`op_name` lies under none of `STEP_PARTS`, `step_unitemised_ms.py`'s list
with `dense_mlp`, `loop_exit` and `loop_carry` behind it
(`tests/test_chip_harness.py` holds the whole equal to
`tpudist.obs.scopes.STEP_PARTS`). That file's list is the benchmark's own
copy and lacks the three, so under its name a dense feed-forward would read
as unitemised; a cell whose step has the scopes lists this reader instead. The line `bench loop_unitemised` prints the ten
longest such operations. Nothing to read where the step has no scopes or no
operation under `dense_mlp` (every other cell: theirs is
`step_unitemised_ms`)."""

import json

from metrics import step_unitemised_ms as listed

STEP_PARTS = listed.STEP_PARTS + ("dense_mlp", "loop_exit", "loop_carry")


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    named = [row for row in scopes["ops"] if row[2] != "layout_copy"]
    if not any(scope_sum.under(row[3], "dense_mlp") for row in named):
        return None
    left = [row for row in named
            if not any(scope_sum.under(row[3], part) for part in STEP_PARTS)]
    total = sum(row[1] for row in left)
    print("bench loop_unitemised " + json.dumps({
        "loop_unitemised_ms": total, "operations": len(left),
        "busy_step_ms": scopes["busy_step_ms"],
        "longest": [[name, ms, op_name] for name, ms, _, op_name
                    in sorted(left, key=lambda row: -row[1])[:10]]}),
        flush=True)
    return total
