"""Model FLOP/s utilisation over the traced window: cost-analysis FLOPs of the
very executable the window ran (per device) x steps / window seconds, over the
benchmark's own peak for this device kind. End to end, idle time included: not
a kernel's roofline share."""


def read(ctx):
    if not ctx["flops_per_step"]:
        return None
    achieved = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * achieved / ctx["peak"]["flops_per_s_bf16"]
