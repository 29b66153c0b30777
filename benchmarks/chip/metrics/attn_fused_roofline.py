"""The whole-sequence attention kernels' share of their roofline: what the
chip would need at its peaks for the calls of one step, over the device time
a step in the operations under the program's `attn_fused` scope.

The counts are the benchmark's own, from the kernels' description in
`docs/ATTENTION.md` at the configuration's shape (B rows a chip, T = patches
+ 1 tokens, H heads of D, bf16 operands), a layer:

- forward, one call: two products (`S^T = K Q^T`, `O = P V`), each
  `2 B H T^2 D` operations; it reads the fused projection `[B, T, H, 3, D]`
  once and writes `O` `[B, T, H, D]` and the float32 logsumexp `[B, H, T]`;
- backward, one call: five products (`S^T` again, `dV`, `dP^T`, `dK`, `dQ`);
  it reads the projection once, `dO` and the logsumexp, and writes the
  projection's cotangent `[B, T, H, 3, D]`.

No `T x T` tensor reaches HBM in either. The program's own `cost_estimate` of
the same calls, read from the step's HLO, is printed beside these on `bench
roofline`: it leaves the recomputed `S^T` out of the backward (four
products), as fits `mfu_pct`; a roofline counts what the kernel executes.
Nothing to read, and so no metric, where the program did not run the kernel
(`attention_kernel` is not `flash`) or no operation lies under the scope.
"""

import re

from harness import roofline

SCOPE = "attn_fused"


def forward_call(b, t, h, d, itemsize):
    """(operations, HBM bytes) of one forward call."""
    return (2 * 2 * b * h * t * t * d,
            (3 + 1) * b * t * h * d * itemsize + 4 * b * h * t)


def backward_call(b, t, h, d, itemsize):
    """(operations, HBM bytes) of one backward call."""
    return (5 * 2 * b * h * t * t * d,
            (3 + 1 + 3) * b * t * h * d * itemsize + 4 * b * h * t)


def program_cost_estimate(step_hlo):
    """[operations, bytes] summed over the step's Mosaic calls under the
    scope, as the program stated them; None where the HLO has none."""
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
    if ctx.get("attention_kernel") != "flash":
        return None
    cfg = ctx["config"]
    shape = (ctx["batch"] // ctx["chips"],
             (int(cfg["image_size"]) // int(cfg["patch_size"])) ** 2 + 1,
             int(cfg["num_attention_heads"]), int(cfg["head_dim"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    layers = int(cfg["num_hidden_layers"])
    calls = [tuple(layers * x for x in forward_call(*shape)),
             tuple(layers * x for x in backward_call(*shape))]
    return roofline.share(
        ctx, "attn_fused_roofline", SCOPE, calls,
        program_cost_estimate=program_cost_estimate(ctx.get("step_hlo")))
