"""The reference's headline artifact, re-created on TPU: a four-row table
comparing training recipes on one fixed workload.

Reference table (``/root/reference/README.md:9-14``): resnet18 / ImageNet /
5 epochs on 3× TITAN Xp, rows = DataParallel, DDP, DDP+AMP, DDP+AMP+SyncBN,
columns = time + per-GPU peak memory. The reference's rows differ by process
topology; under SPMD there is one topology, so the rows that still exist as
distinct recipes are the precision/BN states:

  fp32          (use_amp off — reference rows 1-2)
  bf16          (TPU-native AMP — reference row 3's autocast)
  bf16+SyncBN   (reference row 4)
  fp16+scaler   (literal torch.cuda.amp semantics: fp16 + DynamicScale)

Each row reports images/sec, step ms, MFU and peak HBM (runtime allocator
high-water mark, falling back to the compiler's memory analysis on backends
without allocator stats). Results go to stdout (one JSON line per row) and
``benchmarks/results/recipe_table.json``; run with the repo root on PYTHONPATH
or from the repo root.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402  (the root bench module: platform check + measure_row)

ROWS = (
    ("fp32", dict(use_amp=False)),
    ("bf16", dict(use_amp=True, amp_dtype="bfloat16")),
    ("bf16_syncbn", dict(use_amp=True, amp_dtype="bfloat16",
                         sync_batchnorm=True)),
    ("fp16_scaler", dict(use_amp=True, amp_dtype="float16")),
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--per-device-batch", type=int, default=128)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="the backend this table is FOR: exits non-zero if "
                         "jax initializes on another one")
    ap.add_argument("--out", default=os.path.join(
        _REPO, "benchmarks", "results", "recipe_table.json"))
    ap.add_argument("--rows", default=",".join(name for name, _ in ROWS),
                    help="comma-separated subset of rows to run")
    args = ap.parse_args()

    bench.require_platform(args.platform)

    want = set(args.rows.split(","))
    records = []
    for name, overrides in ROWS:
        if name not in want:
            continue
        rec = bench.measure_row(args.arch, args.per_device_batch,
                                args.image_size, args.steps, args.warmup,
                                **overrides)
        rec = {"row": name, **rec}
        records.append(rec)
        print(json.dumps(rec), flush=True)

    out = {
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "command": " ".join(sys.argv),
        "rows": records,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"recipe_table: wrote {len(records)} rows to {args.out}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
