"""How closely the streaming kernel's tiles follow the mask of training by
diffusion over blocks: 100 x the `band_fill` of the forward call's plan,
scores the mask allows (`L (L + block_length)` a head and a row) over scores
the programs run (whole tiles of `block_q` x `block_k`). 100 would be a
program that computes no score it then masks.

A program counter: the plan is the program's own statement of the call it
makes at the configuration's shape (`tpudist.ops.attention_dispatch.program`,
what the trainer's `attention dispatch` line and event carry), asked in
process; printed whole on `bench attn_bd_plan`. Nothing to read where the
program did not run the kernel, the configuration states no `block_length`,
or the program's plan knows no such mask (a program before this metric)."""

import json


def read(ctx):
    cfg = ctx["config"]
    if ctx.get("attention_kernel") != "flash" or "block_length" not in cfg:
        return None
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    length = int(argv[argv.index("--seq-len") + 1])
    try:
        from tpudist.ops import attention_dispatch
        plan = attention_dispatch.program(
            2 * length, int(cfg["num_attention_heads"]), int(cfg["head_dim"]),
            cfg["compute_dtype"], kv_heads=int(cfg["num_key_value_heads"]),
            block_diffusion=(length, int(cfg["block_length"])))
    except (ImportError, AttributeError, TypeError):
        return None
    print("bench attn_bd_plan " + json.dumps(plan), flush=True)
    return 100.0 * float(plan["band_fill"])
