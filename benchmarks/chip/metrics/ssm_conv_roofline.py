"""The Mamba-2 mixers' causal convolution and SiLU as a share of their
roofline: what the chip would need at its peaks to convolve and gate one
step's xBC, over the device time a step in the operations under the
program's `ssm_conv` scope, whatever implements them (XLA's shifted slices,
a float32 intermediate and a split, or a kernel pair that reads xBC where
`in_proj` wrote it and writes x, B and C apart).

The counts are the benchmark's own, from the configuration's shape and not
from the program: `b` rows a chip, `t` ids a row, `h` heads of `p` channels,
`g` groups with a state of `n`, `k` taps, operands of `itemsize` bytes. With
`e = b t (h p + 2 g n)` elements a block (x, B and C side by side):

- forward, one call a block: xBC read as the projection wrote it and x, B
  and C written once, `2 e itemsize` bytes, plus the float32 taps and bias
  (`4 (k + 1) (h p + 2 g n)`); a product and a sum a tap, SiLU and the
  casts, some fifteen operations an element;
- backward, one call a block: the cotangents of x, B and C and xBC read,
  the cotangent of xBC written, `3 e itemsize` bytes, plus the taps and
  bias read and their float32 cotangents written; the forward's sums again,
  SiLU's derivative, the transposed taps and the sums over positions for the
  taps' and the bias's cotangents, some thirty-five an element.

Each once a Mamba block (the `M`s among the first `num_hidden_layers` letters
of `hybrid_override_pattern`), rematerialised or not: a program that runs the
forward twice pays for it in its share, as `ssd_scan_roofline` has it. Both
calls are memory-bound on the chips of `peaks.json` (the operations need a
fortieth of the bytes' time at the MXU's rate, which elementwise work never
sees). Nothing to read, and so no metric, where the configuration has no
`mamba_num_heads` or no operation lies under the scope.
"""

from harness import roofline

SCOPE = "ssm_conv"
FORWARD_OPS, BACKWARD_OPS = 15, 35


def elements(b, t, h, p, g, n):
    """Elements of x, B and C a block."""
    return b * t * (h * p + 2 * g * n)


def _taps_bytes(h, p, g, n, k):
    """The float32 taps and bias."""
    return 4 * (k + 1) * (h * p + 2 * g * n)


def forward_call(b, t, h, p, g, n, k, itemsize):
    """(operations, HBM bytes) of one block's forward."""
    e = elements(b, t, h, p, g, n)
    return FORWARD_OPS * e, 2 * e * itemsize + _taps_bytes(h, p, g, n, k)


def backward_call(b, t, h, p, g, n, k, itemsize):
    """(operations, HBM bytes) of one block's backward."""
    e = elements(b, t, h, p, g, n)
    return BACKWARD_OPS * e, 3 * e * itemsize + 2 * _taps_bytes(h, p, g, n, k)


def read(ctx):
    cfg = ctx["config"]
    if "mamba_num_heads" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    shape = (ctx["batch"] // ctx["chips"],
             int(argv[argv.index("--seq-len") + 1]),
             int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]),
             int(cfg["n_groups"]), int(cfg["ssm_state_size"]),
             int(cfg["conv_kernel"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    blocks = cfg["hybrid_override_pattern"][
        :int(cfg["num_hidden_layers"])].count("M")
    calls = [tuple(blocks * x for x in forward_call(*shape)),
             tuple(blocks * x for x in backward_call(*shape))]
    return roofline.share(ctx, "ssm_conv_roofline", SCOPE, calls)
