"""The streaming attention kernels' share of their roofline under the mask of
training by diffusion over blocks: what the chip would need at its peaks for
the calls of one step, over the device time a step in the operations under
the program's `attn_fused` scope.

The counts are the benchmark's own, of the MASK and not of the program: they
read the same however the program walks the mask's two pieces. At the
configuration's shape (B rows a chip, L ids a row, so 2L positions `[x_t ;
x_0]`, blocks of `block_length` = bl, H query heads over Hkv key-value heads
of D, bf16 operands), a layer:

- what the mask allows (refs/sdar_30b_ep8.py, its four rules): a noised
  query sees its own block's bl noised keys and the clean keys of the
  blocks before it, a clean query the clean keys up to its block's end:
  `L (L + bl)` (query, key) pairs a head and a row where bl divides L
  (`pairs` counts the ragged case too), of the square's `4 L^2`;
- forward, one call: two products (`S = Q K^T`, `O = P V`), each `2 B H
  pairs D` operations; it reads q [B, 2L, H, D], k and v [B, 2L, Hkv, D] and
  writes o [B, 2L, H, D] and the float32 logsumexp [B, H, 2L];
- backward, one call of each of its two passes: seven products (the dQ pass
  recomputes S and takes dP and dQ; the dKV pass recomputes S and dP and
  takes dV and dK), as `attn_stream_roofline.py` counts a causal call's;
  together they read q, k, v, o and dO, the logsumexp and delta (float32
  [B, H, 2L] each) and write dQ, dK, dV, each moved once.

The forward call runs once a layer, rematerialised or not (the program keeps
the kernel's two results through `--remat`). The program's own
`cost_estimate` of the same calls, read from the step's HLO, is printed
beside these on `bench roofline` (it counts whole blocks and leaves the
recomputed products out). The scope also holds what XLA does around the
calls (the [B, T, H, D] <-> [B, Hkv, G, T, D] moves, whatever would join two
pieces): their time counts against the kernels, their bytes are not in the
least. Nothing to read, and so no metric, where the program did not run the
kernel (`attention_kernel` is not `flash`), the configuration states no
`block_length`, or no operation lies under the scope.
"""

import re

from harness import roofline

SCOPE = "attn_fused"


def pairs(length, block):
    """(query, key) pairs the mask allows a head and a row of `length` ids:
    each of the two copies' positions sees up to its block's end, the noised
    one its own block in place of the clean one's."""
    ends = [min((i // block + 1) * block, length) for i in range(length)]
    return 2 * sum(ends)


def forward_call(b, length, block, h, hkv, d, itemsize):
    """(operations, HBM bytes) of one forward call."""
    t = 2 * length
    return (2 * 2 * b * h * pairs(length, block) * d,
            (2 * h + 2 * hkv) * b * t * d * itemsize + 4 * b * h * t)


def backward_call(b, length, block, h, hkv, d, itemsize):
    """(operations, HBM bytes) of one backward call, both passes."""
    t = 2 * length
    return (7 * 2 * b * h * pairs(length, block) * d,
            (4 * h + 4 * hkv) * b * t * d * itemsize + 2 * 4 * b * h * t)


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
    if ctx.get("attention_kernel") != "flash" or "block_length" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    shape = (ctx["batch"] // ctx["chips"],
             int(argv[argv.index("--seq-len") + 1]), int(cfg["block_length"]),
             int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
             int(cfg["head_dim"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    layers = int(cfg["num_hidden_layers"])
    calls = [tuple(layers * x for x in forward_call(*shape)),
             tuple(layers * x for x in backward_call(*shape))]
    return roofline.share(
        ctx, "attn_bd_roofline", SCOPE, calls,
        program_cost_estimate=program_cost_estimate(ctx.get("step_hlo")))
