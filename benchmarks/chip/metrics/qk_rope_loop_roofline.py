"""q's and k's rotation between the projections and the attention kernels
as a share of its roofline, in a model whose layers run several times and
that rotates q and k without a norm: `attn_qk_rope_roofline.py`'s count of
a layer's elements and of its forward (q and k read as the projections
wrote them and written once, the two float32 tables), a call a layer AND a
pass (`num_hidden_layers` x `total_ut_steps`), over the device time a step
under the same `attn_qk_norm_rope` scope. The backward is the rotation's
transpose: without a norm it needs the cotangents alone, read and written
once, the forward's bytes (that file's `backward_call` also reads raw q and
k, which only a norm's statistics need). `attn_qk_rope_roofline.py` itself
counts a call a layer, so it does not list such a cell. Both calls are
memory-bound. Nothing to read, and so no metric, where the configuration
states no passes, the program did not run the streaming kernels or no
operation lies under the scope."""

from harness import roofline
from metrics import attn_qk_rope_roofline as once
from metrics.attn_loop_roofline import call_shape


def calls(cfg, rows):
    """[(operations, bytes)] of a step's forward and backward calls."""
    n = int(cfg["num_hidden_layers"]) * int(cfg["total_ut_steps"])
    forward = tuple(n * x for x in once.forward_call(*call_shape(cfg, rows)))
    return [forward, forward]


def read(ctx):
    cfg = ctx["config"]
    if ctx.get("attention_kernel") != "flash" or any(
            key not in cfg for key in (
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "total_ut_steps")):
        return None
    return roofline.share(ctx, "qk_rope_loop_roofline", once.SCOPE,
                          calls(cfg, ctx["batch"] // ctx["chips"]))
