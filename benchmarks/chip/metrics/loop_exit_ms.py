"""Device time a step in the exit arithmetic of layers that run several
times: the operations, forward and transposed, under the program's
`loop_exit` scope, at every pass (the exit gate's product and sigmoid, the
exit distribution `p_t` and what survives it, its entropy, the sums the
passes carry). Each pass's head and weighted cross entropy stay `lm_head_ms`'s.
Nothing to read where the step has no such scope (a model whose layers run
once)."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(scopes, ("loop_exit",))["loop_exit"] or None
