"""Device time a step in a top-k expert layer: the operations, forward and
transposed, whose HLO `op_name` lies under one of the program's four scopes
`moe_router` (softmax over all experts, the top k), `moe_dispatch` (sorting
the held pairs, gathering their rows), `moe_experts` (the grouped products
over the experts held) and `moe_combine` (the weighted sum back to tokens),
in every layer. Prints the four beside the sum on `bench moe_ms`. A fusion
counts whole under its root's name (`harness/scope_reduce.py`). Nothing to
read where the step has no such scope (a program without the layer)."""

import json

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    parts = scope_sum.scope_ms(scopes, SCOPES)
    total = sum(parts.values())
    if not total:
        return None
    print("bench moe_ms " + json.dumps(dict(parts, moe_ms=total)), flush=True)
    return total
