"""Device time a step in the Mamba-2 mixers: the operations, forward and
transposed, whose HLO `op_name` lies under the program's `ssm_mixer` scope
(the whole mixer behind a block's norm: the input projection, the causal
depthwise convolution and SiLU, the selective recurrence by a chunked scan,
the gated group norm, the output projection), in every block. The line
`bench ssm_ms` prints the five parts by their own scopes (`ssm_in_proj`,
`ssm_conv`, `ssm_scan`, `ssm_gate_norm`, `ssm_out_proj`) and what of the
mixer lies under none of them, beside the sum. A fusion counts whole under
its root's name (`harness/scope_reduce.py`). Nothing to read where the step
has no such scope (a program without the mixer)."""

import json

PARTS = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
         "ssm_out_proj")


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    total = scope_sum.scope_ms(scopes, ("ssm_mixer",))["ssm_mixer"]
    if not total:
        return None
    parts = scope_sum.scope_ms(scopes, PARTS)
    print("bench ssm_ms " + json.dumps(dict(
        parts, other_ms=total - sum(parts.values()), ssm_ms=total)),
        flush=True)
    return total
