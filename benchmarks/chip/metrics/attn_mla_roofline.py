"""The latent-attention kernels' share of their roofline: what the chip would
need at its peaks for the calls of one step, over the device time a step in
the operations under the program's `attn_fused` scope.

The counts are the benchmark's own, from the layer's equations at the
configuration's shape (B rows a chip, T ids a row, H heads whose queries and
keys are `qk_nope_head_dim + qk_rope_head_dim` = 192 wide and whose values
are `v_head_dim` = 128, bf16 operands), what ANY implementation of the
training form must do, never what one kernel does:

- what the mask allows: query i sees key j where `j <= i`, `T (T + 1) / 2`
  (query, key) pairs;
- forward, one call: `S = Q K^T` over the keys' true 192 columns and `O = P
  V` over the values' 128, `2 B H pairs (192 + 128)` operations; it reads q
  [B, T, H, 192], k_nope and v [B, T, H, 128] and the rotated key [B, T, 64]
  ONCE (one head for all H: a kernel that fetched it a head would move 32
  times that and still be held to this), and writes o [B, T, H, 128] and the
  float32 logsumexp [B, H, T];
- backward, one call of each of its two passes: the dQ pass recomputes S
  (192) and takes dP (128) and dQ (192); the dKV pass recomputes S (192) and
  dP (128) and takes dV (128) and dK (192): `2 B H pairs (4 x 192 + 3 x
  128)`; together they read q, k_nope, v, the rotated key, o and dO, the
  logsumexp and delta (float32 [B, H, T] each) and write dQ, dK_nope, dV
  and the rotated key's gradient [B, T, 64], once;
- calls a step: one forward and one backward a layer kept and a
  multi-token-prediction module (`num_hidden_layers` +
  `num_nextn_predict_layers`); the forward runs once a layer, rematerialised
  or not (the program keeps the kernel's two results through `--remat`).

The program's own `cost_estimate` of the same calls, read from the step's
HLO, is printed beside these on `bench roofline`. The scope also holds what
XLA does around the calls (the [B, T, H, D] <-> [B, H, T, D] moves): their
time counts against the kernels, their bytes are not in the least. Nothing
to read, and so no metric, where the program did not run the kernel
(`attention_kernel` is not `flash`), the configuration has no latent
attention (`kv_lora_rank`), or no operation lies under the scope.
"""

from harness import roofline
from metrics.attn_stream_roofline import SCOPE, pairs, program_cost_estimate


def forward_call(b, t, h, nope, rope, v, itemsize):
    """(operations, HBM bytes) of one forward call."""
    return (2 * b * h * pairs(t) * (nope + rope + v),
            b * t * (h * (2 * nope + rope + 2 * v) + rope) * itemsize
            + 4 * b * h * t)


def backward_call(b, t, h, nope, rope, v, itemsize):
    """(operations, HBM bytes) of one backward call, both passes."""
    return (2 * b * h * pairs(t) * (4 * (nope + rope) + 3 * v),
            # q, k_nope, v, o, dO in and dQ, dK_nope, dV out a head; the
            # rotated key in and its gradient out, once
            b * t * (h * (4 * nope + 2 * rope + 4 * v) + 2 * rope) * itemsize
            + 2 * 4 * b * h * t)


def read(ctx):
    cfg = ctx["config"]
    if ctx.get("attention_kernel") != "flash" or "kv_lora_rank" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    shape = (ctx["batch"] // ctx["chips"],
             int(argv[argv.index("--seq-len") + 1]),
             int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
             int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    n = int(cfg["num_hidden_layers"]) + int(
        cfg.get("num_nextn_predict_layers", 0))
    calls = [tuple(n * x for x in forward_call(*shape)),
             tuple(n * x for x in backward_call(*shape))]
    return roofline.share(
        ctx, "attn_mla_roofline", SCOPE, calls,
        program_cost_estimate=program_cost_estimate(ctx.get("step_hlo")))
