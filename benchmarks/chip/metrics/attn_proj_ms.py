"""Device time a step in the dense projections of a decoder's attention
blocks: the operations, forward and transposed, under the program's
`attn_qkv_proj` scope (the q, k and v products, the cut into heads, the
weights' casts) or its `attn_out_proj` scope (the output product with the
reshape before it). The line `bench attn_proj_ms` prints the two beside the
sum, and the least time the chip needs for the products alone at its peak
rate (`peaks.json`), by the benchmark's own count from the configuration's
shape: a block's four products are `2 P d D (2 H + 2 Hkv)` operations
forward over the step's `P` positions (rows x ids, twice under diffusion
over blocks), run once more where the layer is rematerialised and about
twice transposed. No share of a roofline is reported: the scopes also hold
what XLA fuses behind the products. Nothing to read where the step has no
such scope."""

import json


def products_flops(cfg, rows):
    """Operations a step of the attention blocks' four products, forward,
    rematerialised forward and backward."""
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    positions = rows * int(argv[argv.index("--seq-len") + 1]) * (
        2 if cfg.get("objective") == "block_diffusion" else 1)
    kept = int(cfg["num_hidden_layers"])
    if "hybrid_override_pattern" in cfg:
        blocks = cfg["hybrid_override_pattern"][:kept].count("*")
    else:
        blocks = kept            # every layer is a pair with attention
    forward = 2 * positions * int(cfg["hidden_size"]) * int(cfg["head_dim"]) \
        * (2 * int(cfg["num_attention_heads"])
           + 2 * int(cfg["num_key_value_heads"]))
    return blocks * forward * (4 if "--remat" in argv else 3)


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    parts = scope_sum.scope_ms(scopes, ("attn_qkv_proj", "attn_out_proj"))
    total = sum(parts.values())
    if not total:
        return None
    said = dict(parts, attn_proj_ms=total)
    try:
        flops = products_flops(ctx["config"], ctx["batch"] // ctx["chips"])
        said.update(products_flops=flops, products_least_ms=1e3 * flops
                    / ctx["peak"]["flops_per_s_bf16"])
    except (KeyError, ValueError):
        pass                     # not a decoder's configuration
    print("bench attn_proj_ms " + json.dumps(said), flush=True)
    return total
