"""Device time a step in the selective state-space recurrence itself: the
operations, forward and transposed, under the program's `ssm_scan` scope
(`softplus` of dt, the running sums of `dt A` and their exps, the products
within a chunk, the chunks' states, the pass between chunks that carries
states forward, the carried state's part, `D x`), in every Mamba-2 block:
the part of `ssm_ms` that is not a dense projection, a convolution or a
norm, and what a scan kernel would replace. Nothing to read where the step
has no such scope."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(scopes, ("ssm_scan",))["ssm_scan"] or None
