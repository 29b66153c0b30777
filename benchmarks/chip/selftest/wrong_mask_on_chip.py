"""By hand, on the chip: a cell trained by diffusion over blocks with the
program walking the WRONG mask, a causal one over the doubled row `[x_t ;
x_0]` (twice the pairs the block-diffusion mask allows), at the cell's own
size. It must run, train, and come out `correct: false` by at least one of
the configuration's limits (PERF.md section 2 has the readings):

    python benchmarks/chip/selftest/wrong_mask_on_chip.py \
        --workload sdar_30b_ep8_staged_8k --seed <n> --seconds <s>

Everything but the mask is `run.py`'s: the same arguments, the same result
line last. The CPU twin of this is `test_sdar_cpu.py`.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.dirname(os.path.dirname(CHIP))]


def main():
    import run
    import tpudist.ops.pallas as pallas
    real = pallas.flash_attention

    def causal_over_the_doubled_row(q, k, v, **mask):
        if "block_diffusion" not in mask:
            raise SystemExit("this cell states no block-diffusion mask")
        return real(q, k, v, causal=True)

    pallas.flash_attention = causal_over_the_doubled_row
    return run.main(sys.argv[1:] + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
