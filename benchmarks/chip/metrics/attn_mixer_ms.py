"""Device time a step in a decoder's attention blocks: the operations,
forward and transposed, whose HLO `op_name` lies under the program's
`attn_mixer` scope (the whole of `GroupedQueryAttention`: the q, k and v
projections, q / k RMSNorm and RoPE, the kernels' calls, the output
projection), in every block that has attention. The line `bench
attn_mixer_ms` prints its four parts by their own scopes (`attn_qkv_proj`,
`attn_qk_norm_rope`, `attn_fused`, `attn_out_proj`) and what of the mixer
lies under none of them, beside the sum. A fusion counts whole under its
root's name (`harness/scope_reduce.py`): the moves into the kernels' layout
read under whichever part roots them. Nothing to read where the step has no
such scope (a classifier, or a program without the scope)."""

import json

PARTS = ("attn_qkv_proj", "attn_qk_norm_rope", "attn_fused", "attn_out_proj")


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    total = scope_sum.scope_ms(scopes, ("attn_mixer",))["attn_mixer"]
    if not total:
        return None
    inside = dict(scopes, ops=[row for row in scopes["ops"]
                               if scope_sum.under(row[3], "attn_mixer")])
    parts = scope_sum.scope_ms(inside, PARTS)
    print("bench attn_mixer_ms " + json.dumps(dict(
        parts, other_ms=total - sum(parts.values()), attn_mixer_ms=total)),
        flush=True)
    return total
