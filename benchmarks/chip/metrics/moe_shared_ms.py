"""Device time a step in the shared experts: the operations, forward and
transposed, under the program's `moe_shared` scope (the dense `relu^2`
expert every token visits, beside the routed ones, whole on every holder of
a layer), in every expert block. Not part of `moe_ms`, which sums the routed
path's four scopes. Nothing to read where the step has no such scope (a
model without a shared expert)."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(scopes, ("moe_shared",))["moe_shared"] or None
