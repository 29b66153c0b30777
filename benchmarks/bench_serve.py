"""Serving load-test harness: open-loop rate sweep → latency/throughput
curve artifact + gateable bench-history series (ISSUE 14 tentpole (d)).

Drives a ``ServeEngine`` + ``ContinuousBatcher`` with synthetic Poisson
arrivals at each swept rate (OPEN loop: submission is independent of
completion, so saturation shows up as latency growth, not silently
throttled offered load) and writes:

- a curve artifact (``benchmarks/results/serve_curve_<arch>_<plat>.json``:
  one row per rate — offered vs achieved req/s, p50/p99 latency, batch
  occupancy) — the latency/throughput curve;
- ``bench_history.jsonl`` series ``tpudist-regress`` gates in the correct
  directions: per-rate p99 rows (``unit: ms`` — regress UPWARD) and ONE
  saturation row (``unit: req/s``, the max achieved completion rate across
  the sweep — regress DOWNWARD);
- the AOT cold-start numbers (``aot_s`` / ``aot_compile_s`` / cache
  provenance) embedded in the artifact, so the warm-vs-cold startup claim
  rides the same file.

Metric names embed arch, image size, rate, and PLATFORM (a CPU sweep can
never gate TPU history — same convention as every other bench). Weights
are fresh-init: serving performance does not depend on their values, and
a checkpoint requirement would couple the perf harness to a training run.

Usage::

    python benchmarks/bench_serve.py --arch resnet18 --rates 5,10,20,40
    python benchmarks/bench_serve.py --regress-strict   # CI: exit 2 on gate
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--arch", default="resnet18")
    p.add_argument("--image-size", type=int, default=224, dest="image_size")
    p.add_argument("--num-classes", type=int, default=1000,
                   dest="num_classes")
    p.add_argument("--buckets", default="1,2,4,8")
    p.add_argument("--rates", default="5,10,20,40",
                   help="comma-separated offered request rates (req/s) to "
                        "sweep, low to high")
    p.add_argument("--duration", type=float, default=5.0,
                   help="seconds of open-loop load per rate point")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   dest="max_wait_ms")
    p.add_argument("--compile-cache", default="", dest="compile_cache")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="",
                   help="curve artifact path (default: benchmarks/results/"
                        "serve_curve_<arch>_<platform>.json)")
    p.add_argument("--no-history", action="store_true", dest="no_history",
                   help="skip bench_history.jsonl appends (exploratory "
                        "runs)")
    p.add_argument("--regress-strict", action="store_true",
                   dest="regress_strict",
                   help="exit 2 when any appended series trips the "
                        "regression gate")
    args = p.parse_args(argv)

    from tpudist.serve.batching import (ContinuousBatcher, open_loop_load,
                                        parse_buckets)
    from tpudist.serve.cache import configure_compile_cache
    buckets = parse_buckets(args.buckets)
    rates = [float(r) for r in args.rates.split(",") if r]
    if not rates:
        p.error("--rates needs at least one rate")
    _, cache = configure_compile_cache(args.compile_cache)

    import jax
    import numpy as np
    from tpudist.serve.engine import ServeEngine
    from tpudist.serve.export import load_serve_state
    from tpudist.telemetry import percentile

    plat = jax.default_backend()
    model, variables = load_serve_state(
        args.arch, num_classes=args.num_classes,
        image_size=args.image_size, max_batch=buckets[-1], seed=args.seed,
        log=lambda m: print(m, flush=True))
    engine = ServeEngine(model, variables, image_size=args.image_size,
                         buckets=buckets, cache=cache,
                         log=lambda m: print(m, flush=True))

    shape = (1, args.image_size, args.image_size, 3)

    def make_images(rng):
        return rng.standard_normal(shape).astype(np.float32)

    import time
    curve = []
    for rate in rates:
        batcher = ContinuousBatcher(engine,
                                    max_wait_s=args.max_wait_ms / 1e3)
        t0 = time.perf_counter()
        results = open_loop_load(batcher, rate, args.duration, make_images,
                                 seed=args.seed)
        span = time.perf_counter() - t0
        batcher.close()
        errs = [r for r in results if r.error is not None]
        if errs:
            # open_loop_load completes errored futures instead of raising
            # (so the serving CLI can shut down cleanly); for the BENCH a
            # failed request invalidates the measurement — refuse to
            # write a curve over failures.
            print(f"[bench_serve] {len(errs)}/{len(results)} requests "
                  f"errored at rate {rate:g} (first: {errs[0].error!r}) — "
                  f"a latency curve over failing requests is not a "
                  f"measurement; aborting", flush=True)
            return 1
        lats = sorted(r.latency_s for r in results)
        occ = (sum(i["n_valid"] / i["bucket"] for i in engine.last_info)
               / max(len(engine.last_info), 1))
        row = {
            "rate": rate,
            "n_requests": len(results),
            "achieved_req_s": round(len(results) / max(span, 1e-9), 2),
            "p50_ms": round(percentile(lats, 50) * 1e3, 3),
            "p99_ms": round(percentile(lats, 99) * 1e3, 3),
            "occupancy_last": round(occ, 4),
        }
        curve.append(row)
        print(f"[bench_serve] rate {rate:g} req/s: achieved "
              f"{row['achieved_req_s']:g}, p50 {row['p50_ms']:.1f} ms, "
              f"p99 {row['p99_ms']:.1f} ms", flush=True)

    saturation = max(r["achieved_req_s"] for r in curve)
    measured_at = datetime.datetime.now(
        datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
    artifact = {
        "arch": args.arch, "image_size": args.image_size,
        "buckets": list(buckets), "platform": plat,
        "device_kind": jax.devices()[0].device_kind,
        "duration_per_rate_s": args.duration,
        "aot_s": round(engine.aot_s, 3),
        "aot_compile_s": round(engine.aot_compile_s, 3),
        "compile_cache": cache,
        "curve": curve,
        "saturation_req_s": saturation,
        "measured_at": measured_at,
    }
    out_path = args.out or os.path.join(
        _REPO, "benchmarks", "results",
        f"serve_curve_{args.arch}_{plat}.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"[bench_serve] wrote curve artifact {out_path}", flush=True)

    rc = 0
    if not args.no_history:
        from tpudist.regress import (analyze_history, append_history,
                                     format_verdict, history_path,
                                     load_history)
        base = f"serve_{args.arch}_{args.image_size}px"
        rows = []
        for r in curve:
            # Per-rate latency series: unit ms → the gate regresses UPWARD.
            rows.append({
                "metric": f"{base}_r{r['rate']:g}_p99_ms_{plat}",
                "unit": "ms", "value": r["p99_ms"],
                "per_device_batch": buckets[-1],
                "achieved_req_s": r["achieved_req_s"],
                "p50_ms": r["p50_ms"], "measured_at": measured_at,
            })
        # THE saturation row: highest achieved completion rate across the
        # sweep; unit req/s → the gate regresses DOWNWARD (value drop).
        rows.append({
            "metric": f"{base}_sat_req_s_{plat}", "unit": "req/s",
            "value": saturation, "per_device_batch": buckets[-1],
            "aot_s": round(engine.aot_s, 3), "compile_cache": cache,
            "measured_at": measured_at,
        })
        hist = history_path()
        for row in rows:
            append_history(row, hist)
            # Echo the row as a JSONL line (platform-stamped metric names).
            print(json.dumps(row), flush=True)
        for row in rows:
            v = analyze_history(load_history(hist), metric=row["metric"])
            print("[bench_serve] " + format_verdict(v), flush=True)
            if v["status"] == "regression":
                rc = 2
    else:
        # --no-history runs (the warm-cache pass of perfci's serve_ab
        # stage) still print a platform-stamped JSONL line.
        print(json.dumps({"serve_curve": out_path, "platform": plat,
                          "saturation_req_s": saturation,
                          "aot_s": round(engine.aot_s, 3),
                          "aot_compile_s": round(engine.aot_compile_s, 3),
                          "compile_cache": cache,
                          "measured_at": measured_at}), flush=True)
    print("SERVE_BENCH_OK" if rc == 0 else "SERVE_BENCH_REGRESSION",
          flush=True)
    return rc if args.regress_strict else 0


if __name__ == "__main__":
    sys.exit(main())
