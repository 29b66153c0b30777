"""Device time a step that no part of the program accounts for: the busy
time in named operations whose HLO `op_name` lies under none of the leaf
scopes a step's device time may lie under (`STEP_PARTS`: the benchmark's own
copy of `tpudist.obs.scopes.STEP_PARTS`; `tests/test_chip_harness.py` holds
the two equal). What a mixer holds under none of its parts counts here;
instructions the compiler added with no name at all (layout copies) stay
`layout_copy_ms`'s. The line `bench step_unitemised` prints the ten longest
such operations with their `op_name`: what to scope next, or what belongs to
no part. Nothing to read where the step has no scopes, or none of this
reader's time (no operation under `block_norm`: a classifier, or a program
that does not itemise its blocks)."""

import json

STEP_PARTS = (
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
    "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_out_proj",
    "attn_qkv_proj", "attn_qk_norm_rope", "attn_fused", "attn_out_proj",
    "block_norm", "lm_embed", "lm_head", "tpudist_loss", "bd_noise",
    "tpudist_grad_reduce", "tpudist_optimizer", "tpudist_metrics")


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    named = [row for row in scopes["ops"] if row[2] != "layout_copy"]
    if not any(scope_sum.under(row[3], "block_norm") for row in named):
        return None
    left = [row for row in named
            if not any(scope_sum.under(row[3], part) for part in STEP_PARTS)]
    total = sum(row[1] for row in left)
    print("bench step_unitemised " + json.dumps({
        "step_unitemised_ms": total, "operations": len(left),
        "busy_step_ms": scopes["busy_step_ms"],
        "longest": [[name, ms, op_name] for name, ms, _, op_name
                    in sorted(left, key=lambda row: -row[1])[:10]]}),
        flush=True)
    return total
