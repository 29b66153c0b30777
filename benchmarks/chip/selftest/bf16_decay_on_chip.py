"""By hand, on the chip: a cell with Mamba-2 mixers run with the scan's
decays taken in bfloat16 (the running sums of `dt A` inside a chunk, and with
them every `exp` of one: the program states them float32), at the cell's own
size. It must run, train, and come out `correct: false` by at least one of
the configuration's limits (PERF.md section 2 has the readings):

    python benchmarks/chip/selftest/bf16_decay_on_chip.py \
        --workload nemotron3_nano_ep16_staged_8k --seed <n> --seconds <s>

Everything but the running sum's precision is `run.py`'s: the same arguments,
the same result line last. A comparison that this passes is too loose.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.dirname(os.path.dirname(CHIP))]


def main():
    import jax.numpy as jnp
    import run
    from tpudist.ops import ssd

    def in_bfloat16(da):
        return jnp.cumsum(da.astype(jnp.bfloat16), axis=-1)

    ssd._running_sum = in_bfloat16
    return run.main(sys.argv[1:] + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
