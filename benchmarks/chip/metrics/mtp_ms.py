"""Device time a step in the multi-token-prediction module: every operation,
forward and transposed, under the program's `mtp_module` scope: the next
id's embedding, the merge, the module's block (its attention and experts
lie under their own parts as well), its pass through the shared head and
its loss. Nothing to read where the step has no such scope (a model with
one prediction depth)."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(scopes, ("mtp_module",))["mtp_module"] or None
