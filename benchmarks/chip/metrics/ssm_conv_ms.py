"""Device time a step in the Mamba-2 mixers' causal convolution: the
operations, forward and transposed, under the program's `ssm_conv` scope
(the depthwise convolution over time with its bias, SiLU, the cast, and
whatever moves xBC in or x, B and C out), in every Mamba-2 block: the part
of `ssm_ms` between `in_proj` and the scan, whatever implements it (XLA's
shifted slices and a split, or a kernel pair that reads and writes by
column). Nothing to read where the step has no such scope."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scope_sum.scope_ms(scopes, ("ssm_conv",))["ssm_conv"] or None
