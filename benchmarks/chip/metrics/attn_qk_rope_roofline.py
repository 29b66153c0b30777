"""q's and k's RMSNorm and RoPE between the projections and the attention
kernels, as a share of their roofline: what the chip would need at its
peaks to norm and rotate one step's q and k, over the device time a step in
the operations under the program's `attn_qk_norm_rope` scope, whatever
implements them (XLA's fusions with the move into the kernels' layout, or a
kernel pair that writes q and k there).

The counts are the benchmark's own, from the configuration's shape and not
from the program: `b` rows a chip, `t` positions a row (the configuration's
`--seq-len`, doubled where it states a `block_length`: the noised and the
clean copy, as `attn_bd_roofline.py` reads it), `h` query heads over `hkv`
key-value heads of `d`, operands of `itemsize` bytes. With `n = b t (h +
hkv) d` elements a layer:

- forward, one call a layer: q and k read as the projections wrote them and
  written once, `2 n itemsize` bytes, plus the two float32 `[t, d]` tables;
  a square, a sum, an `rsqrt` a head, two products for the norm and three
  operations for the rotation, some fifteen an element with the casts;
- backward, one call a layer: the cotangents of q and k and raw q and k
  read, the raw cotangents written, `3 n itemsize` bytes, plus the tables;
  some thirty operations an element.

Each once an attention layer (`num_hidden_layers`: every layer of the
configurations that list the metric has attention), rematerialised or not: a
program that runs the forward twice pays for it in its share, as
`ssd_scan_roofline` has it. Both calls are memory-bound on the chips of
`peaks.json` (the operations need a tenth of the bytes' time at the MXU's
rate, which elementwise work never sees). The configurations name their
rotation differently (`rope_parameters`, `rope_theta`), so the reader
decides by the scope and not by a key: nothing to read, and so no metric,
where the program did not run the streaming kernels (`attention_kernel` is
not `flash`), the configuration states no grouped heads, or no operation
lies under the scope (an attention that neither norms nor rotates).
"""

from harness import roofline

SCOPE = "attn_qk_norm_rope"
FORWARD_OPS, BACKWARD_OPS = 15, 30


def elements(b, t, h, hkv, d):
    """Elements of q and k a layer."""
    return b * t * (h + hkv) * d


def forward_call(b, t, h, hkv, d, itemsize):
    """(operations, HBM bytes) of one layer's forward."""
    n = elements(b, t, h, hkv, d)
    return FORWARD_OPS * n, 2 * n * itemsize + 2 * 4 * t * d


def backward_call(b, t, h, hkv, d, itemsize):
    """(operations, HBM bytes) of one layer's backward."""
    n = elements(b, t, h, hkv, d)
    return BACKWARD_OPS * n, 3 * n * itemsize + 2 * 4 * t * d


def read(ctx):
    cfg = ctx["config"]
    if ctx.get("attention_kernel") != "flash" or any(
            key not in cfg for key in ("num_attention_heads",
                                       "num_key_value_heads", "head_dim")):
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    t = int(argv[argv.index("--seq-len") + 1])
    if "block_length" in cfg:
        t *= 2
    shape = (ctx["batch"] // ctx["chips"], t,
             int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
             int(cfg["head_dim"]),
             {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    layers = int(cfg["num_hidden_layers"])
    calls = [tuple(layers * x for x in forward_call(*shape)),
             tuple(layers * x for x in backward_call(*shape))]
    return roofline.share(ctx, "attn_qk_rope_roofline", SCOPE, calls)
