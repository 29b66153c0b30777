"""Device time a step in fusions whose members span two of forward, backward
and optimizer (by the members' `op_name`s in the fused computation): the part
of `fwd_ms` + `bwd_ms` + `opt_ms` that the three cannot split, because a
fusion is one device operation with one duration. `bench scope_ms` says which
phases (`mixed_by_phases_ms`). Not a cost of its own: fusing is what the
compiler should do; it says how far the three metrics can be trusted."""


def read(ctx):
    from harness import scope_reduce
    scopes = scope_reduce.step_scopes(ctx)
    return None if scopes is None else scopes["mixed_ms"]
