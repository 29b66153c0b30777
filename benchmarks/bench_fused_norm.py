"""Fused BN-epilogue microbenchmark: Pallas BN+ReLU / BN+add+ReLU kernels vs
the XLA epilogue on the attached chip (ISSUE 6 tentpole: the A/B evidence
behind ``--fused-bn auto``).

Times forward+backward (the training configuration — BN epilogues only
matter there) for both implementations at the resnet18@224/bs128 stage
workloads — the canonical bench's ACTUAL epilogue shapes, where PR 5's
attribution table says the VPU time goes — plus a wide-channel bottleneck
shape. Timing goes through the shared dispatch harness
(``ops/dispatch.measure_ms``, which ends in a host readback), so
bench rows and dispatch verdicts cannot drift in methodology.

Every numeric row is appended to ``benchmarks/results/bench_history.jsonl``
as its own gateable ``unit: ms`` series (``tpudist-regress`` trips on time
INCREASE), and each pallas/XLA pair carries the measurement-honest dispatch
verdict derived from the very numbers in the row; on TPU that verdict is
written into the dispatch cache — a ``--fused-bn auto`` cache warm **at the
benched workloads** (a training run at a different per-device batch still
measures its own shapes once). Off-TPU nothing is appended or cached:
interpreter timings are not measurements.

Usage: python benchmarks/bench_fused_norm.py [--steps N] [--batch B]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_row(fn, args, steps: int, metric: str, rows: int, channels: int,
              dtype: str, residual: bool) -> dict:
    from tpudist.ops.dispatch import measure_ms
    row = {"metric": metric, "unit": "ms", "shape": [rows, channels],
           "dtype": dtype}
    try:
        ms = measure_ms(fn, args, steps, warmup=3)
        row["value"] = round(ms, 3)
        # epilogue traffic across fwd+bwd, in activation-tensor passes:
        # plain = fwd read x, write y + bwd read x, dy, write dx (5);
        # residual = fwd read x, res, write y + bwd read x, res, dy
        # (the relu mask recompute needs both), write dx, dres (8). A
        # bandwidth number, the roofline the kernel plays against.
        passes = 8 if residual else 5
        nbytes = np.dtype(dtype).itemsize * rows * channels
        row["gb_per_s"] = round(passes * nbytes / (ms / 1e3) / 1e9, 1)
    except Exception as e:
        row["value"] = None
        row["error"] = f"{type(e).__name__}: {e}"[:200]
    print(json.dumps(row), flush=True)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--batch", type=int, default=128,
                    help="per-device batch the resnet stage shapes derive "
                         "from (canonical bench: 128)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from tpudist.ops import norm_dispatch

    platform = jax.default_backend()
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    b = args.batch
    # resnet18@224 stage activations (NHWC rows = B·H·W), plain BN+ReLU at
    # every stage plus the residual epilogue at the two ends; one
    # wide-channel bottleneck shape rides along for resnet50 coverage.
    shapes = [
        ("stage1", b * 56 * 56, 64, False),
        ("stage1_res", b * 56 * 56, 64, True),
        ("stage2", b * 28 * 28, 128, False),
        ("stage3", b * 14 * 14, 256, False),
        ("stage4", b * 7 * 7, 512, False),
        ("stage4_res", b * 7 * 7, 512, True),
        ("wide", b * 7 * 7, 2048, True),
    ]
    if platform != "tpu":
        print(f"[bench_fused_norm] WARNING: platform={platform} — Pallas "
              f"runs in interpreter mode, numbers are meaningless off-TPU",
              file=sys.stderr)
        shapes = [("tiny", 256, 64, False), ("tiny_res", 256, 64, True)]

    failed = False
    for name, rows, channels, residual in shapes:
        # The workload pair comes from norm_dispatch's OWN builder: bench
        # rows and dispatch verdicts measure the same computation by
        # construction, not by parallel maintenance.
        pallas_c, xla_c, fargs = norm_dispatch.build_measure_fns(
            rows, channels, dt, residual, interpret=platform != "tpu")

        rows_out = {}
        for label, fn in (("pallas", pallas_c), ("xla", xla_c)):
            row = _time_row(
                fn, fargs, args.steps,
                f"fusednorm_{name}_b{b}_{label}_fwdbwd_ms_{platform}",
                rows, channels, args.dtype, residual)
            rows_out[label] = row
            failed |= "error" in row
        _embed_dispatch_and_append(rows_out, rows, channels, args.dtype,
                                   residual, platform)
    return 1 if failed else 0


def _embed_dispatch_and_append(rows_out: dict, rows: int, channels: int,
                               dtype: str, residual: bool,
                               platform: str) -> None:
    """Stamp the measurement-honest dispatch verdict onto the pallas/XLA
    pair and append both to the bench history as regress-gateable ms
    series. On TPU the verdict (derived from the rows' own timings via the
    ``measure_pair`` hook) also lands in the dispatch cache — a bench run
    doubles as a ``--fused-bn auto`` cache warm; off-TPU ``decide``
    resolves to XLA on platform grounds and caches nothing, and nothing is
    appended (interpreter timings are not measurements)."""
    from tpudist.ops import norm_dispatch
    from tpudist.regress import append_history

    pr, xr = rows_out.get("pallas"), rows_out.get("xla")
    if pr and xr and pr.get("value") is not None \
            and xr.get("value") is not None:
        try:
            dec = norm_dispatch.decide(
                rows, channels, dtype, residual=residual, mode="auto",
                platform=platform, refresh=True,
                measure_pair=lambda: (pr["value"], xr["value"]))
            disp = {"kernel": dec["kernel"], "source": dec["source"],
                    "pallas_ms": pr["value"], "xla_ms": xr["value"]}
            pr["dispatch"] = disp
            xr["dispatch"] = disp
        except Exception as e:
            print(f"[bench_fused_norm] dispatch verdict failed: {e!r}",
                  file=sys.stderr)
    if platform != "tpu":
        print("[bench_fused_norm] platform != tpu — rows NOT appended to "
              "bench history (interpreter timings are not measurements)",
              file=sys.stderr)
        return
    now = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    appended = 0
    for row in rows_out.values():
        if isinstance(row.get("value"), (int, float)):
            append_history({**row, "measured_at": now})
            appended += 1
    if appended:
        print(f"[bench_fused_norm] {appended} row(s) appended to bench "
              f"history", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
