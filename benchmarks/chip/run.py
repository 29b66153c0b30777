"""The chip benchmark's one command: one cell, once, in one process.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Load, warm up, measure for `--seconds`, compare with the plain reference,
print one JSON line last, exit. Refuses (exit 3, no result line) any platform
but a TPU, a device kind missing from `peaks.json`, another number of chips
than the cell asks for, and a checkout without the program. It never falls
back. Everything that belongs to one cell is data: `BENCHMARK.json` names the
cell, `configs/`, `traffic/`, `refs/` and `metrics/` hold the files it finds
by name (see `harness/`).
"""

import time

T_START = time.time()          # set-up is counted from here

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

CHIP_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for path in (CHIP_DIR, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        config = load_json(ROOT, conf["file"])
        traffic_spec = load_json(CHIP_DIR, "traffic", cell["traffic"] + ".json")
        peaks = load_json(CHIP_DIR, "peaks.json")["device_kinds"]
        from harness.cellrun import Refuse, run_cell
        try:
            result = run_cell(
                bench=bench, workload=cell, config=config,
                traffic_spec=traffic_spec, peaks=peaks, seed=args.seed,
                seconds=args.seconds, trace=bool(args.trace),
                chip_dir=CHIP_DIR, t_start=T_START)
        except Refuse as e:
            print(f"refused: {e}", file=sys.stderr)
            return 3
    except (ImportError, FileNotFoundError, StopIteration) as e:
        print(f"refused: this checkout cannot run the cell: {e!r}",
              file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
