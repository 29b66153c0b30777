"""The streaming attention kernels' share of their roofline in a model whose
layers run several times: `attn_stream_roofline.py`'s counts of one call
(what the mask allows, the forward's two products, the backward's seven,
their bytes; that file says what a roofline counts and what the scope holds
besides), a call a layer AND a pass: the configuration's `num_hidden_layers`
layers are each run `total_ut_steps` times a step, forward once a pass (the
program keeps the kernel's two results through `--remat`) and backward once
a pass, over the device time a step under the same `attn_fused` scope.
`attn_stream_roofline.py` itself counts a call a layer, so it does not list
such a cell. Nothing to read, and so no metric, where the configuration
states no passes, the program did not run the kernel or no operation lies
under the scope."""

from harness import roofline
from metrics import attn_stream_roofline as once


def call_shape(cfg, rows):
    """(rows, positions, heads, key-value heads, head size, bytes an
    element) of one call, as both looped rooflines' counting functions take
    them."""
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    return (rows, int(argv[argv.index("--seq-len") + 1]),
            int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]),
            {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])


def calls(cfg, rows):
    """[(operations, bytes)] of a step's forward and backward calls."""
    shape = call_shape(cfg, rows)
    kinds = cfg["layer_types"][:int(cfg["num_hidden_layers"])]
    out = []
    for kind, window in (("sliding_attention", cfg.get("sliding_window")),
                         ("full_attention", None)):
        n = kinds.count(kind) * int(cfg["total_ut_steps"])
        if n:
            window = int(window) if kind == "sliding_attention" else None
            out.append(tuple(n * x for x in once.forward_call(*shape, window)))
            out.append(tuple(n * x for x in once.backward_call(*shape,
                                                               window)))
    return out


def read(ctx):
    cfg = ctx["config"]
    if ctx.get("attention_kernel") != "flash" or "layer_types" not in cfg \
            or "total_ut_steps" not in cfg:
        return None
    return roofline.share(
        ctx, "attn_loop_roofline", once.SCOPE,
        calls(cfg, ctx["batch"] // ctx["chips"]),
        program_cost_estimate=once.program_cost_estimate(ctx.get("step_hlo")))
