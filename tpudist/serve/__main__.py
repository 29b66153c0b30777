"""CLI: ``python -m tpudist.serve`` — export a checkpoint and serve it.

One process = one serving replica. The replica AOT-compiles its bucket set
(persistent-cache-backed), starts the continuous batcher, and — in this
repo's harness form — drives itself with synthetic open-loop traffic
(``--load-rate``/``--load-duration``); a zero rate just warms the cache
and reports the AOT numbers (the "pre-warm a replica" mode). Telemetry and
the per-rank metrics endpoint work exactly as in training (``--telemetry``
``--metrics-port``), so ``summarize`` prints the serving section and the
launcher's fleet view aggregates replicas.

Multi-replica: ``python -m tpudist.launch -n 1 --scale-up 2@10 -- python
-m tpudist.serve ... --telemetry --outpath <shared>`` — the launcher
spawns the second replica under load and the fleet endpoint shows both
(the 2-replica e2e in ``tests/test_serve.py``). Rank identity comes from
``TPUDIST_PROCESS_ID`` like a training rank's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tpudist.serve",
        description="Serve a tpudist checkpoint: AOT bucket compilation + "
                    "continuous batching + telemetry (docs/SERVING.md)")
    p.add_argument("-a", "--arch", default="resnet18")
    p.add_argument("--checkpoint", default="",
                   help="checkpoint.msgpack file or run dir; '' = fresh "
                        "init weights (bench/smoke)")
    p.add_argument("--num-classes", type=int, default=1000,
                   dest="num_classes")
    p.add_argument("--image-size", type=int, default=224, dest="image_size")
    p.add_argument("--buckets", default="1,2,4,8",
                   help="comma-separated micro-batch bucket sizes; every "
                        "request batch is padded to the smallest fitting "
                        "bucket, so steady-state traffic compiles exactly "
                        "len(buckets) programs — at startup")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   dest="max_wait_ms",
                   help="how long the batcher holds a micro-batch open for "
                        "more requests to coalesce (latency vs occupancy "
                        "knob)")
    p.add_argument("--compile-cache", default="", dest="compile_cache",
                   help="persistent XLA compilation cache dir (env "
                        "TPUDIST_COMPILE_CACHE; default "
                        "<checkout>/.jax_cache; ignored when "
                        "JAX_COMPILATION_CACHE_DIR is set): a warm replica "
                        "AOT-starts in seconds instead of minutes")
    p.add_argument("--flash", default="auto", choices=("auto", "on", "off"),
                   help="attention backend for vit archs, resolved through "
                        "the measurement-honest dispatch layer with the "
                        "eval-mode (train=False) workload key")
    p.add_argument("--load-rate", type=float, default=0.0, dest="load_rate",
                   help="synthetic open-loop arrivals per second (0 = no "
                        "load: warm the cache, report AOT numbers, exit)")
    p.add_argument("--load-duration", type=float, default=10.0,
                   dest="load_duration",
                   help="seconds of synthetic load")
    p.add_argument("--load-batch", type=int, default=1, dest="load_batch",
                   help="rows per synthetic request")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--outpath", default="",
                   help="run dir for telemetry/portfiles (required with "
                        "--telemetry)")
    p.add_argument("--telemetry", action="store_true",
                   help="write events.<rank>.jsonl (serve_start/request/"
                        "serve_batch + compile events) + heartbeats")
    p.add_argument("--metrics-port", type=int, default=-1,
                   dest="metrics_port",
                   help="with --telemetry: per-replica Prometheus endpoint "
                        "(request p50/p99 latency, queue depth, batch "
                        "occupancy, req/s); 0 = ephemeral, written to "
                        "<outpath>/metrics.<rank>.port")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.telemetry and not args.outpath:
        build_parser().error("--telemetry needs --outpath")
    if args.metrics_port >= 0 and not args.telemetry:
        build_parser().error("--metrics-port requires --telemetry (the "
                             "endpoint serves gauges derived from the "
                             "telemetry event stream)")

    from tpudist.serve.batching import parse_buckets
    buckets = parse_buckets(args.buckets)

    # Cache config BEFORE any jax compilation.
    def log(msg: str) -> None:
        print(msg, flush=True)

    from tpudist.serve.cache import configure_compile_cache
    _, cache = configure_compile_cache(args.compile_cache, log=log)

    import jax

    telemetry = None
    metrics_server = None
    rank = 0
    try:
        rank = int(os.environ.get("TPUDIST_PROCESS_ID", "0"))
    except ValueError:
        pass
    if args.telemetry:
        from tpudist import telemetry as telemetry_lib
        os.makedirs(args.outpath, exist_ok=True)
        telemetry = telemetry_lib.Telemetry(args.outpath, rank=rank)
        telemetry.emit("run_start", platform=jax.default_backend(),
                       n_devices=jax.device_count(),
                       device_kind=jax.devices()[0].device_kind,
                       arch=args.arch, global_batch=buckets[-1],
                       mode="serve")
        if args.metrics_port >= 0:
            from tpudist.obs.server import MetricsRegistry, MetricsServer
            reg = MetricsRegistry(rank=rank)
            telemetry.add_sink(reg.observe)
            try:
                metrics_server = MetricsServer(
                    reg, port=args.metrics_port).start()
            except OSError as e:
                # --scale-up hands every replica the SAME command line,
                # fixed --metrics-port included; the newcomer losing the
                # bind race must degrade to an ephemeral port
                # (discoverable via the port file), not die and silently
                # yield a one-replica fleet (trainer's pattern).
                log(f"=> serve metrics port {args.metrics_port} "
                    f"unavailable ({e!r}) — falling back to an ephemeral "
                    f"port")
                metrics_server = MetricsServer(reg, port=0).start()
            metrics_server.write_portfile(args.outpath, rank)
            log(f"=> serve metrics on :{metrics_server.port} (/metrics)")

    from tpudist.serve.batching import ContinuousBatcher, open_loop_load
    from tpudist.serve.engine import ServeEngine
    from tpudist.serve.export import load_serve_state

    model, variables = load_serve_state(
        args.arch, args.checkpoint, num_classes=args.num_classes,
        image_size=args.image_size, max_batch=buckets[-1],
        flash=args.flash, seed=args.seed, telemetry=telemetry, log=log)
    engine = ServeEngine(model, variables, image_size=args.image_size,
                         buckets=buckets, telemetry=telemetry, cache=cache,
                         log=log)

    summary = {"arch": args.arch, "buckets": list(buckets),
               "aot_s": round(engine.aot_s, 3),
               "aot_compile_s": round(engine.aot_compile_s, 3),
               "cache": cache, "rank": rank}
    t_serve0 = time.perf_counter()
    if args.load_rate > 0:
        import numpy as np
        batcher = ContinuousBatcher(engine,
                                    max_wait_s=args.max_wait_ms / 1e3,
                                    telemetry=telemetry)
        shape = (args.load_batch, args.image_size, args.image_size, 3)

        def make_images(rng):
            return rng.standard_normal(shape).astype(np.float32)

        log(f"=> serving synthetic open-loop load: {args.load_rate} req/s "
            f"for {args.load_duration}s")
        results = open_loop_load(batcher, args.load_rate,
                                 args.load_duration, make_images,
                                 seed=args.seed)
        batcher.close()
        # Engine errors complete the future with .error set instead of
        # raising out of the load run — the replica's shutdown path
        # (telemetry.close → run_end, SERVE_SUMMARY) must run even when
        # requests failed, or the operator loses the evidence exactly
        # when diagnosing the failure.
        ok = [r for r in results if r.error is None]
        n_errors = len(results) - len(ok)
        lats = sorted(r.latency_s for r in ok)
        from tpudist.telemetry import percentile
        span = max(time.perf_counter() - t_serve0, 1e-9)
        summary.update(
            n_requests=len(results), n_errors=n_errors,
            achieved_req_s=round(len(ok) / span, 2),
            latency_p50_ms=(round(percentile(lats, 50) * 1e3, 3)
                            if lats else None),
            latency_p99_ms=(round(percentile(lats, 99) * 1e3, 3)
                            if lats else None))
        if lats:
            log(f"=> served {len(ok)} requests: p50 "
                f"{summary['latency_p50_ms']:.1f} ms, p99 "
                f"{summary['latency_p99_ms']:.1f} ms, "
                f"{summary['achieved_req_s']:.1f} req/s"
                + (f" ({n_errors} errored)" if n_errors else ""))
        else:
            first_err = next(r.error for r in results
                             if r.error is not None)
            log(f"=> every request errored ({n_errors} of {n_errors}; "
                f"first: {first_err!r})")

    if telemetry is not None:
        telemetry.close(mode="serve")
    if metrics_server is not None:
        metrics_server.close()
    print("SERVE_SUMMARY " + json.dumps(summary), flush=True)
    # Partial errors still count as a served run (reported above); a run
    # where NOTHING succeeded is a failure — after clean shutdown.
    if summary.get("n_requests") and not (summary["n_requests"]
                                          - summary.get("n_errors", 0)):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
