"""`step_unitemised_ms` for a step that holds a multi-token-prediction
module: the busy time in named operations whose HLO `op_name` lies under
none of `STEP_PARTS`, `loop_unitemised_ms.py`'s list with `mtp_merge` behind
it (`tests/test_chip_harness.py` holds the whole equal to
`tpudist.obs.scopes.STEP_PARTS`). Under the older readers' lists the
module's merge would read as unitemised; a cell whose step has the scope
lists this reader instead. The line `bench mtp_unitemised` prints the ten
longest such operations. Nothing to read where the step has no scopes or no
operation under `mtp_module` (every other cell)."""

import json

from metrics import loop_unitemised_ms as listed

STEP_PARTS = listed.STEP_PARTS + ("mtp_merge",)


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    named = [row for row in scopes["ops"] if row[2] != "layout_copy"]
    if not any(scope_sum.under(row[3], "mtp_module") for row in named):
        return None
    left = [row for row in named
            if not any(scope_sum.under(row[3], part) for part in STEP_PARTS)]
    total = sum(row[1] for row in left)
    print("bench mtp_unitemised " + json.dumps({
        "mtp_unitemised_ms": total, "operations": len(left),
        "busy_step_ms": scopes["busy_step_ms"],
        "longest": [[name, ms, op_name] for name, ms, _, op_name
                    in sorted(left, key=lambda row: -row[1])[:10]]}),
        flush=True)
    return total
