"""Share of the device's busy time spent in operations that feed the matrix
unit: trace events whose name or HLO category says convolution or dot."""


def read(ctx):
    return ctx["trace"]["mxu_share_pct"]
