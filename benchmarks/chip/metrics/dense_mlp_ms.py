"""Device time a step in the dense feed-forward layers: the operations,
forward, rematerialised and transposed, under the program's `dense_mlp` scope
(the gate, up and down products of a SwiGLU and the `silu(gate) * up` between
them, with the weights' casts), in every layer that has one and at every pass
over it. The line `bench dense_mlp_ms` prints beside it the least time the
chip needs for the three products at its peak rate (`peaks.json`), by the
benchmark's own count from the configuration's shape: `2 P d f` operations a
product forward over the step's `P` positions, a layer and a pass
(`total_ut_steps`; once where the configuration states none), run once more
where the layer is rematerialised and about twice transposed. No share of a
roofline is reported: the scope also holds what XLA fuses behind the
products. Nothing to read where the step has no such scope (a model whose
feed-forward layers are experts)."""

import json


def products_flops(cfg, rows):
    """Operations a step of the dense layers' three products: forward,
    rematerialised forward and backward, every pass."""
    argv = [str(a) for a in cfg.get("trainer_argv", [])]
    positions = rows * int(argv[argv.index("--seq-len") + 1])
    forward = 3 * 2 * positions * int(cfg["hidden_size"]) * int(
        cfg["intermediate_size"])
    return (int(cfg["num_hidden_layers"]) * int(cfg.get("total_ut_steps", 1))
            * forward * (4 if "--remat" in argv else 3))


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    total = scope_sum.scope_ms(scopes, ("dense_mlp",))["dense_mlp"]
    if not total:
        return None
    said = {"dense_mlp_ms": total}
    try:
        flops = products_flops(ctx["config"], ctx["batch"] // ctx["chips"])
        said.update(products_flops=flops, products_least_ms=1e3 * flops
                    / ctx["peak"]["flops_per_s_bf16"])
    except (KeyError, ValueError):
        pass                     # not a dense decoder's configuration
    print("bench dense_mlp_ms " + json.dumps(said), flush=True)
    return total
