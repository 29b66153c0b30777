"""By hand, on the chip: a traced cell in which the host stands still once
and a program is built once, both inside the window, at the cell's own size.
The stall is the program's own fault point (`slow_peer`, armed through
`TPUDIST_INJECT` for one step of the window, as every test arms it), the
compile a dummy `jax.jit` on a fresh shape called where the benchmark
dispatches a step:

    python benchmarks/chip/selftest/stall_on_chip.py \
        --workload resnet18_staged --seed <n> --seconds <s> \
        [--stall-ms 1500] [--compile 1]

It must come out `correct: true`, with the device's idle gap named by the
loop's activity and not by a catch-all: `breakdown.idle_gaps` led by
`tpudist.loop_hooks` at about the stall less one step's time,
`loop_host_max_ms` at about `--stall-ms`, `device_idle_pct` at about the
stall over the traced window, and `window_compile_count` 1 (0 with
`--compile 0`). Everything but the two is `run.py`'s: the same arguments,
the same result line last (PERF.md section 6 has the readings).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, ROOT]

# the window's steps these land on, counted from its first
STALL_AT, COMPILE_AT = 8, 14


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stall-ms", type=int, default=1500)
    ap.add_argument("--compile", type=int, choices=(0, 1), default=1)
    args, rest = ap.parse_known_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        warm = int(json.load(f)["compared_steps"])    # before the window
    if args.stall_ms:
        os.environ["TPUDIST_INJECT"] = \
            f"slow_peer:ms={args.stall_ms}@step={warm + STALL_AT}"
    import run
    from harness import cellrun
    calls = [0]

    def hook(compiled, state, images, labels, lr):
        calls[0] += 1
        if args.compile and calls[0] == warm + COMPILE_AT + 1:
            import jax
            import numpy as np
            jax.jit(lambda x: 2.0 * x + 1.0)(np.zeros((3, 5, 7), np.float32))
        return compiled(state, images, labels, lr)

    run_cell = cellrun.run_cell
    cellrun.run_cell = lambda **kw: run_cell(**kw, step_hook=hook)
    return run.main(["--workload", args.workload] + rest + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
