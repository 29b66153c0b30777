"""Device time a step in a decoder's block-level norms and residual sums:
the operations, forward and transposed, under the program's `block_norm`
scope (every block's RMSNorm before a mixer with the cast behind it, the
decoder's last norm, the sums `x + mixer(norm(x))`). Nothing to read where
the step has no such scope."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(scopes, ("block_norm",))["block_norm"] or None
