"""Device time a step in operations rooted in the forward pass: those whose
HLO `op_name` lies under the program's `tpudist_forward` scope and is not
transposed, plus the loss (`tpudist_loss`). A fusion counts whole under its
root's name, so this follows fusion roots, not work: forward members that XLA
fused into a backward fusion are in `bwd_ms`, and `mixed_ms` says how much of
the step sits in such fusions. Whole steps of the traced window only
(`harness/scope_reduce.py`)."""


def read(ctx):
    from harness import scope_reduce
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    return scopes["phase_ms"]["fwd"] + scopes["phase_ms"]["loss"]
