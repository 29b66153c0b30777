"""Device time a step between the projections and the attention kernels:
the operations, forward and transposed, under the program's
`attn_qk_norm_rope` scope (RMSNorm over `head_dim` on q and k in float32
with the casts behind it, the rotation's tables, RoPE on both), in every
attention block. The two share one scope because their elementwise chains
fuse. Nothing to read where the step has no such scope, or where it holds
no operation (an attention that neither norms nor rotates q and k)."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(
        scopes, ("attn_qk_norm_rope",))["attn_qk_norm_rope"] or None
