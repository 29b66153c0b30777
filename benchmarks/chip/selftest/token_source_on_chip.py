"""By hand, on the chip: the token feed alone, with no trainer behind it.

    python benchmarks/chip/selftest/token_source_on_chip.py \
        <traffic mix> <vocab_size> <rows> [<rows> ...]

For each number of rows: the mix's batches made from a seed on the device
under the program's batch sharding (`tpudist.dist.batch_sharding` over a
one-axis mesh of every chip the machine holds), the source's `info`, the
seconds the first making (which compiles) and a second one took, and what
lies on the device: shapes, types, shardings, the range of the ids, next-id
targets. Exits 1 where any of it is not as the mix states.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.dirname(os.path.dirname(CHIP))]


def main() -> int:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from harness import traffic
    from tpudist.dist import batch_sharding
    mix = json.load(open(os.path.join(CHIP, "traffic", sys.argv[1] + ".json")))
    vocab = int(sys.argv[2])
    devices = jax.devices()
    sharding = batch_sharding(Mesh(np.asarray(devices), ("data",)), "data")
    print("device", devices[0].platform, devices[0].device_kind, len(devices),
          flush=True)
    ok = True
    for rows in map(int, sys.argv[3:]):
        config = {"name": f"held_vocab_{vocab}", "vocab_size": vocab,
                  "per_chip_batch": rows // len(devices)}
        took = []
        for seed in (2 ** 31 + 29, 2 ** 31 + 30):   # first compiles
            t0 = time.perf_counter()
            src = traffic.make_source(mix, seed=seed % (2 ** 31 - 1),
                                      batch=rows, config=config,
                                      sharding=sharding)
            jax.block_until_ready(src.first(3))
            took.append(time.perf_counter() - t0)
        t = mix["seq_len"]
        facts = []
        for inputs, targets in src.first(3):
            host_in, host_tg = np.asarray(inputs), np.asarray(targets)
            facts.append({
                "shapes": [list(inputs.shape), list(targets.shape)],
                "dtypes": [str(inputs.dtype), str(targets.dtype)],
                "sharded_as_a_batch": bool(
                    inputs.sharding == sharding == targets.sharding),
                "ids": [int(host_in.min()), int(host_in.max())],
                "next_id_targets": bool(
                    np.array_equal(host_tg[:, :-1], host_in[:, 1:])),
                "distinct_rows": len({bytes(r) for r in host_in})})
        good = all(f["shapes"] == [[rows, t]] * 2
                   and f["dtypes"] == ["int32"] * 2
                   and f["sharded_as_a_batch"] and f["next_id_targets"]
                   and 0 <= f["ids"][0] and f["ids"][1] < vocab
                   and f["distinct_rows"] == rows for f in facts) \
            and took[1] < 1.0
        ok = ok and good
        print("token_source " + json.dumps(
            {"rows": rows, "info": src.info, "first_make_s": took[0],
             "second_make_s": took[1], "batches": facts, "ok": good}),
            flush=True)
        src.close()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
