"""By hand, here, with no chip: compile a configuration's step program and its
plain reference at the real size for a described v5e and print what they need.

    JAX_PLATFORMS=cpu python benchmarks/chip/selftest/compile_for_chip.py \
        <config.json> [chips]

What the chip's compiler refuses here costs no chip time. A compile that
passes is not a chip run.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, ROOT]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)
    config = json.load(open(sys.argv[1]))
    chips = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    only = sys.argv[3] if len(sys.argv) > 3 else "both"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    batch = int(config["per_chip_batch"]) * chips
    size = int(config["image_size"])

    def gib(ma):
        return {k: round(getattr(ma, k) / 2 ** 30, 3) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}

    images = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32,
                                  sharding=split)
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=split)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)

    if only in ("both", "program"):
        from tpudist.config import from_args
        from tpudist.models import create_model
        from tpudist.train import (compute_dtype, create_train_state,
                                   make_train_step)
        argv = [str(a).format(batch=batch, seed=0, outpath="/tmp/x")
                for a in config["trainer_argv"]]
        cfg = from_args(argv).finalize(chips)
        # `auto` compiles XLA's attention here: no chip, no probe, no verdict
        kw = ({"flash": cfg.flash == "on"} if cfg.arch.startswith("vit")
              else {})
        model = create_model(cfg.arch, num_classes=cfg.num_classes,
                             dtype=compute_dtype(cfg),
                             sync_batchnorm=cfg.sync_batchnorm,
                             bn_axis_name="data", **kw)
        from tpudist.ops import norm_dispatch
        norm_dispatch.set_mode(cfg.fused_bn)
        state = jax.eval_shape(lambda: create_train_state(
            jax.random.PRNGKey(0), model, cfg))
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            state)
        compiled = make_train_step(mesh, model, cfg).lower(
            state, images, labels, lr).compile()
        ma = compiled.memory_analysis()
        print("program step GiB:", gib(ma), "step total",
              round((ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                    / 2 ** 30, 3), flush=True)

    if only in ("both", "reference"):
        from harness import check
        ref = check.load_reference(CHIP, config["reference_module"])
        model_cfg = {k: v for k, v in config.items()
                     if isinstance(v, (int, float, str))}
        one = NamedSharding(Mesh(np.asarray(topo.devices[:1]), ("data",)), P())
        p, s = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0),
                                               model_cfg))
        shaped = lambda t: jax.tree_util.tree_map(          # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
        p, s = shaped(p), shaped(s)
        opt = shaped(jax.eval_shape(ref.init_opt, p))
        items = tuple(sorted(model_cfg.items()))
        im1 = jax.ShapeDtypeStruct(images.shape, images.dtype, sharding=one)
        lb1 = jax.ShapeDtypeStruct(labels.shape, labels.dtype, sharding=one)
        lr1 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
        for quant in (None, config.get("control_quant")):
            compiled = ref._step.lower(p, s, opt, im1, lb1, items, lr1,
                                       quant=quant).compile()
            print(f"reference step (quant={quant}) GiB:",
                  gib(compiled.memory_analysis()), flush=True)


if __name__ == "__main__":
    main()
