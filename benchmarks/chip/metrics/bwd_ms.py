"""Device time a step in operations rooted in the backward pass: those jax
traced as the transpose of the forward pass or of the loss
(`transpose(jvp(tpudist_forward))`, `transpose(jvp(tpudist_loss))` in their
HLO `op_name`). A fusion counts whole under its root's name, so this follows
fusion roots, not work: it holds the optimizer updates and the recomputed
forward tails that XLA fused into weight-gradient fusions (`mixed_ms`). Whole
steps of the traced window only (`harness/scope_reduce.py`)."""


def read(ctx):
    from harness import scope_reduce
    scopes = scope_reduce.step_scopes(ctx)
    return None if scopes is None else scopes["phase_ms"]["bwd"]
