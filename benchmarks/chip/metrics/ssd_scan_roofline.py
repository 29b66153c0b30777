"""The Mamba-2 chunked scan's share of its roofline: what the chip would need
at its peaks for the scans of one step, over the device time a step in the
operations under the program's `ssm_scan` scope, whatever implements them
(XLA's fusions and loops, or a kernel pair).

The counts are the benchmark's own, from the mathematics at the
configuration's shape and not from the program: `b` rows a chip, `t` ids a
row, `h` heads of `p` channels, `g` groups (a group's B and C serve `h / g`
heads), a state of `n`, chunks of `q` positions, operands of `itemsize`
bytes (refs/nemotron3_nano_ep16.py has the recurrence; tpudist/ops/ssd.py
its chunked form):

- forward, one call a block: the four products over whole chunks, `C B^T`
  a group (`2 b t g q n`), the mixed scores times `dt x` a head (`2 b t h q
  p`), a chunk's own state and the carried state's part (`2 b t h p n`
  each); it reads x [b, t, h p] and B, C [b, t, g n] in the operands' dtype
  and dt [b, t, h] in float32, and writes y [b, t, h p] in float32 (the gated
  norm reads float32);
- backward, one call a block: each product's two transposes and nothing
  recomputed (twice the forward's operations); it reads x, B, C, dt and the
  float32 cotangent of y, and writes the cotangents of x, B and C in the
  operands' dtype and dt's in float32.

Each once a Mamba block (the `M`s among the first `num_hidden_layers` letters
of `hybrid_override_pattern`), rematerialised or not: a program that runs the
forward twice pays for it in its share, as it pays for the running sums, the
softplus and whatever it keeps between its two passes. The program's own
`cost_estimate` of the Mosaic calls under the scope, read from the step's
HLO, is printed beside these on `bench roofline` (none where XLA's fusions
run the scan). Nothing to read, and so no metric, where the configuration has
no `mamba_num_heads` or no operation lies under the scope.
"""

import re

from harness import roofline

SCOPE = "ssm_scan"


def forward_call(b, t, h, p, g, n, q, itemsize):
    """(operations, HBM bytes) of one block's forward."""
    return (2 * b * t * (g * q * n + h * q * p + 2 * h * p * n),
            b * t * (h * p + 2 * g * n) * itemsize + 4 * b * t * h
            + 4 * b * t * h * p)


def backward_call(b, t, h, p, g, n, q, itemsize):
    """(operations, HBM bytes) of one block's backward."""
    return (2 * forward_call(b, t, h, p, g, n, q, itemsize)[0],
            2 * b * t * (h * p + 2 * g * n) * itemsize + 2 * 4 * b * t * h
            + 4 * b * t * h * p)


def program_cost_estimate(step_hlo):
    """[operations, bytes, calls] summed over the step's Mosaic calls under
    the scope, as the program stated them; None where the HLO has none."""
    flops = nbytes = found = 0
    for line in (step_hlo or "").splitlines():
        if "tpu_custom_call" not in line or f"/{SCOPE}/" not in line:
            continue
        m = re.search(r'"cost_estimate":\{"flops":"(\d+)".*?'
                      r'"bytes_accessed":"(\d+)"', line)
        if m:
            flops, nbytes = flops + int(m[1]), nbytes + int(m[2])
            found += 1
    return [flops, nbytes, found] if found else None


def read(ctx):
    cfg = ctx["config"]
    if "mamba_num_heads" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    shape = (ctx["batch"] // ctx["chips"],
             int(argv[argv.index("--seq-len") + 1]),
             int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]),
             int(cfg["n_groups"]), int(cfg["ssm_state_size"]),
             int(cfg["chunk_size"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    blocks = cfg["hybrid_override_pattern"][
        :int(cfg["num_hidden_layers"])].count("M")
    calls = [tuple(blocks * x for x in forward_call(*shape)),
             tuple(blocks * x for x in backward_call(*shape))]
    return roofline.share(
        ctx, "ssd_scan_roofline", SCOPE, calls,
        program_cost_estimate=program_cost_estimate(ctx.get("step_hlo")))
