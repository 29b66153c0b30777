"""The streaming attention kernels' share of their roofline: what the chip
would need at its peaks for the calls of one step, over the device time a
step in the operations under the program's `attn_fused` scope.

The counts are the benchmark's own, from the kernels' description in
`docs/ATTENTION.md` at the configuration's shape (B rows a chip, T ids a row,
H query heads over Hkv key-value heads of D, bf16 operands), a layer:

- what the mask allows: query i sees key j where `j <= i`, so `T (T + 1) / 2`
  (query, key) pairs in a `full_attention` layer, and with a window W also
  `i - j < W`: `W T - W (W - 1) / 2` in a `sliding_attention` layer;
- forward, one call: two products (`S = Q K^T`, `O = P V`), each `2 B H
  pairs D` operations; it reads q [B, T, H, D], k and v [B, T, Hkv, D] and
  writes o [B, T, H, D] and the float32 logsumexp [B, H, T];
- backward, one call of each of its two passes: seven products (the dQ pass
  recomputes S and takes dP and dQ; the dKV pass recomputes S and dP and
  takes dV and dK); together they read q, k, v, o and dO, the logsumexp and
  delta (float32 [B, H, T] each) and write dQ, dK, dV.

A roofline counts what the kernels execute: the forward call runs once a
layer, rematerialised or not (the program keeps the kernel's two results
through `--remat`). The program's own `cost_estimate` of the same
calls, read from the step's HLO, is printed beside these on `bench
roofline`: it counts whole blocks inside the band and leaves the recomputed
products out of the backward, as fits `mfu_pct`. The scope also holds what
XLA does around the calls (the [B, T, H, D] <-> [B, H, T, D] transposes,
the temperature folded into q, delta): their time counts against the
kernels, their bytes are not in the least. Nothing to read, and so no
metric, where the program did not run the kernel (`attention_kernel` is
not `flash`), the configuration is not a decoder's (`layer_types`), or no
operation lies under the scope.
"""

import re

from harness import roofline

SCOPE = "attn_fused"


def pairs(t, window=None):
    """(query, key) pairs a causal mask allows, with or without a window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * t - window * (window - 1) // 2


def forward_call(b, t, h, hkv, d, itemsize, window=None):
    """(operations, HBM bytes) of one forward call."""
    return (2 * 2 * b * h * pairs(t, window) * d,
            (2 * h + 2 * hkv) * b * t * d * itemsize + 4 * b * h * t)


def backward_call(b, t, h, hkv, d, itemsize, window=None):
    """(operations, HBM bytes) of one backward call, both passes."""
    return (7 * 2 * b * h * pairs(t, window) * d,
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
    if ctx.get("attention_kernel") != "flash" or "layer_types" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    seq_len = int(argv[argv.index("--seq-len") + 1])
    shape = (ctx["batch"] // ctx["chips"], seq_len,
             int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
             int(cfg["head_dim"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    kinds = cfg["layer_types"][:int(cfg["num_hidden_layers"])]
    calls = []
    for kind, window in (("sliding_attention", int(cfg["sliding_window"])),
                         ("full_attention", None)):
        n = kinds.count(kind)
        if n:
            calls.append(tuple(n * x for x in forward_call(*shape, window)))
            calls.append(tuple(n * x for x in backward_call(*shape, window)))
    return roofline.share(
        ctx, "attn_stream_roofline", SCOPE, calls,
        program_cost_estimate=program_cost_estimate(ctx.get("step_hlo")))
