"""Latent attention's elementwise work between its projections and its
kernels as a share of its roofline: what the chip would need at its peaks to
norm the two latents and rotate one step's q_rope and k_r, over the device
time a step in the operations under the program's `attn_qk_norm_rope` scope,
whatever implements them (XLA's fusions with the cuts of q and kv, their
cotangents' joins and the moves into the kernels' layout, or a kernel pair
that writes q where the attention kernels read it).

The counts are the benchmark's own, from the configuration's shape and not
from the program: `b` rows a chip, `t` ids a row, `h` heads whose rotated
part is `rope` columns, latents of `q_rank` and `kv_rank`, the rotated key
ONE head of `rope` columns, operands of `itemsize` bytes. A block has `n =
b t (q_rank + kv_rank)` normed and `r = b t (h + 1) rope` rotated elements:

- forward, once a block: c_q and c_kv read and written once (the two
  RMSNorms), q_rope and k_r read and written once, `2 (n + r) itemsize`
  bytes, plus the two float32 `[t, rope]` tables; a square, a sum, an
  `rsqrt` a row and two products a normed element, three operations a
  rotated one, the casts;
- backward, once a block: a norm reads its operand and its cotangent and
  writes one (three arrays), the rotation's transpose needs the cotangent
  alone, read and written (two): `(3 n + 2 r) itemsize` bytes, plus the
  tables.

q_nope's way into the kernels' layout is deliberately NOT in the least: a
program that moves it (`h x nope` columns a position, three times the rotated
part) pays for the move in its share, one that need not does not, and the
count reads under 100 % for either. Each call once a block
(`num_hidden_layers` + `num_nextn_predict_layers`: the layers kept and the
multi-token-prediction module's), rematerialised or not: a program that runs
the forward twice pays for it in its share, as `ssd_scan_roofline` has it.
Both calls are memory-bound on the chips of `peaks.json`. Nothing to read,
and so no metric, where the configuration has no latent attention
(`kv_lora_rank`) or no operation lies under the scope.
"""

from harness import roofline

SCOPE = "attn_qk_norm_rope"
# operations an element: (normed, rotated), forward and backward
FORWARD_OPS, BACKWARD_OPS = (8, 5), (16, 5)


def elements(b, t, h, rope, q_rank, kv_rank):
    """(normed, rotated) elements a block."""
    return b * t * (q_rank + kv_rank), b * t * (h + 1) * rope


def forward_call(b, t, h, rope, q_rank, kv_rank, itemsize):
    """(operations, HBM bytes) of one block's forward."""
    n, r = elements(b, t, h, rope, q_rank, kv_rank)
    return (FORWARD_OPS[0] * n + FORWARD_OPS[1] * r,
            2 * (n + r) * itemsize + 2 * 4 * t * rope)


def backward_call(b, t, h, rope, q_rank, kv_rank, itemsize):
    """(operations, HBM bytes) of one block's backward."""
    n, r = elements(b, t, h, rope, q_rank, kv_rank)
    return (BACKWARD_OPS[0] * n + BACKWARD_OPS[1] * r,
            (3 * n + 2 * r) * itemsize + 2 * 4 * t * rope)


def read(ctx):
    cfg = ctx["config"]
    if "kv_lora_rank" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    shape = (ctx["batch"] // ctx["chips"],
             int(argv[argv.index("--seq-len") + 1]),
             int(cfg["num_attention_heads"]), int(cfg["qk_rope_head_dim"]),
             int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    blocks = int(cfg["num_hidden_layers"]) + int(
        cfg.get("num_nextn_predict_layers", 0))
    calls = [tuple(blocks * x for x in forward_call(*shape)),
             tuple(blocks * x for x in backward_call(*shape))]
    return roofline.share(ctx, "mla_qk_rope_roofline", SCOPE, calls)
