"""By hand, on the chip: the control's readings at a cell's own size, for a
configuration under any resident mix (`read_limits.py` reads under `staged`,
the image mix, alone).

    python benchmarks/chip/selftest/read_limits_mix.py <config.json> <mix> \
        <seed> [...]

For each seed: the configuration's seeded weights and the mix's first
batches, the plain float32 reference's numbers, and the same reference
computed with the configuration's `control_quant` operands (fp8 for a bf16
configuration) put in the program's place. Prints the control's gap for every
number `correct` compares, and first, where the reference routes tokens
(`routed_otherwise`), the share of (token, expert) pairs a layer that
bfloat16 operands send to another expert on the first batch. The sound
program's gaps are on the `correct-check`
lines of every benchmark run; a limit goes between the two (PERF.md section 2).
The float32 readings go to the host before the control runs: two sets of a
large configuration's state do not fit one chip.
"""

import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.dirname(os.path.dirname(CHIP))]


def main():
    import jax
    import numpy as np
    from harness import check, traffic
    config = json.load(open(sys.argv[1]))
    ref = check.load_reference(CHIP, config["reference_module"])
    mix = json.load(open(os.path.join(CHIP, "traffic", sys.argv[2] + ".json")))
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    n = int(config["compared_steps"])
    for seed in (int(s) % (2 ** 31 - 1) for s in sys.argv[3:]):
        p0, s0 = check.seeded_weights(ref, config, seed, dev)
        src = traffic.make_source(
            mix, seed=seed, batch=int(config["per_chip_batch"]),
            config=config, sharding=dev)
        batches = [(np.asarray(i), np.asarray(l)) for i, l in src.first(n)]
        src.close()
        if hasattr(ref, "routed_otherwise"):
            print(f"routing seed {seed} pairs routed otherwise under bf16 "
                  f"operands, share a layer: "
                  f"{ref.routed_otherwise(p0, *batches[0], config)}",
                  flush=True)
        lr = float(config["window_lr"])
        sound = check.reference_readings(ref, config, p0, s0, batches, lr)
        sound = jax.tree_util.tree_map(np.asarray, sound)
        gc.collect()
        names = {"first_grad": check.leaf_names(p0),
                 "param_change": check.leaf_names(p0),
                 "stats_change": check.leaf_names(s0)}
        quant = config["control_quant"]
        got = check.reference_readings(ref, config, p0, s0, batches, lr,
                                       quant=quant)
        ok, rows = check.compare(got, sound, config["correct_limits"], names)
        for name, value, limit, passed, note in rows:
            print(f"control seed {seed} quant {quant} {name}: value "
                  f"{value:.6g} limit {limit:.6g} "
                  f"{'passes' if passed else 'FAILS'} ({note})", flush=True)
        print(f"control seed {seed} quant {quant} correct={ok}", flush=True)
        del sound, got, batches, p0, s0
        gc.collect()


if __name__ == "__main__":
    main()
