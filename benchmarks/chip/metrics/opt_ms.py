"""Device time a step in operations rooted after the backward pass: gradient
and BN-statistics reduction (`tpudist_grad_reduce`), the optimizer update with
its skips and EMA (`tpudist_optimizer`), and the metrics (`tpudist_metrics`).
A fusion counts whole under its root's name, so this follows fusion roots: it
is the optimizer time XLA left UNFUSED. The updates it fused into a layer's
weight-gradient fusion are in `bwd_ms` (`mixed_ms`, `bwd+opt`): judge an
optimizer change by `device_step_ms`, and read this and `mixed_ms` together.
Whole steps of the traced window only (`harness/scope_reduce.py`)."""


def read(ctx):
    from harness import scope_reduce
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    phase = scopes["phase_ms"]
    return phase["reduce"] + phase["opt"] + phase["metrics"]
