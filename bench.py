"""Benchmark: resnet18 ImageNet-shape training throughput on the local chip(s).

One process, one jax initialization, one measurement, one stdout line:
  {"metric", "value", "unit", ...extras}
with extras: step_time_ms, mfu, goodput (productive step time over
compile+warmup+measure wall — tpudist/telemetry.py's run-level accounting
scoped to the bench), peak_hbm_gb, platform, device_kind, n_devices,
per_device_batch, steps — plus "vs_baseline" on resnet18 rows ONLY (the
reference baseline is a resnet18 number; a cross-arch ratio would mislead).

The row is what THIS process measured on the platform that was asked for
(``--platform``, default ``tpu``): if jax initializes on anything else the
bench exits non-zero before compiling — it never re-prints an older
record, never shrinks the workload for another backend, and never starts a
child that would need the chip its parent holds.

Baseline (BASELINE.md): the reference's DDP row — 5 ImageNet epochs in 4612 s
on 3× TITAN Xp = 1,281,167*5/4612 ≈ 1389 images/sec aggregate. ``vs_baseline``
is our measured training throughput divided by that number.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time

import numpy as np

REFERENCE_IMAGES_PER_SEC = 1_281_167 * 5 / 4612.0   # ≈ 1389 (BASELINE.md DDP row)

# Peak FLOP/s table lives in tpudist.telemetry (single source shared with
# the trainer's per-step MFU accounting); resolve_peak_flops also honors the
# TPUDIST_PEAK_FLOPS env override.
from tpudist.telemetry import resolve_peak_flops as _peak_flops  # noqa: E402


def _phase(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def require_platform(want: str) -> None:
    """Initialize jax (once, in this process) and exit non-zero unless it
    landed on ``want`` — a measurement path that finds no chip fails; it
    does not fall back. Also turns the persistent compilation cache on
    through the repo's one resolver."""
    from tpudist.serve.cache import configure_compile_cache
    cache_dir, state = configure_compile_cache(log=_phase)
    import jax
    got = jax.default_backend()
    if got != want:
        raise SystemExit(f"bench: --platform {want} was asked for but jax "
                         f"initialized on '{got}' — refusing to measure")
    d = jax.devices()
    _phase(f"backend ok: {got} x{len(d)} ({d[0].device_kind}); "
           f"compile cache {cache_dir} ({state})")


def build_compiled_step(arch: str, per_device_batch: int, image_size: int,
                        *, use_amp: bool = True, amp_dtype: str = "bfloat16",
                        sync_batchnorm: bool = False, remat: bool = False,
                        s2d: bool = False, seed: int = 0):
    """Build + compile the canonical SPMD train step on the already-
    initialized backend. Returns ``(cfg, compiled, state, images, labels,
    lr, compile_s)`` — shared by ``measure_row`` (which then times it) and
    by the compiled-cost fingerprint test (``tests/test_compiled_cost.py``),
    which pins cost/memory analysis of THIS exact program so stem/remat/
    fusion changes can't silently shift the canonical program between rare
    hardware windows (VERDICT r4 next #6)."""
    import jax
    import jax.numpy as jnp
    from tpudist.config import Config
    from tpudist.dist import make_mesh, shard_host_batch
    from tpudist.models import create_model
    from tpudist.train import compute_dtype, create_train_state, make_train_step

    n = jax.device_count()
    mesh = make_mesh((n,), ("data",))
    cfg = Config(arch=arch, num_classes=1000, image_size=image_size,
                 batch_size=per_device_batch * n, use_amp=use_amp,
                 amp_dtype=amp_dtype, sync_batchnorm=sync_batchnorm,
                 remat=remat, seed=seed).finalize(n)

    _phase(f"initializing {cfg.arch} (global batch {cfg.batch_size}, "
           f"amp={use_amp}/{amp_dtype if use_amp else '-'}, "
           f"syncbn={sync_batchnorm}, remat={remat})...")
    model = create_model(cfg.arch, num_classes=cfg.num_classes,
                         dtype=compute_dtype(cfg),
                         **({"remat": True} if remat else {}),
                         **({"s2d_stem": True} if s2d else {}))
    state = create_train_state(jax.random.PRNGKey(0), model, cfg)
    train_step = make_train_step(mesh, model, cfg)

    rng = np.random.default_rng(0)
    images = rng.standard_normal(
        (cfg.batch_size, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
    labels = rng.integers(0, cfg.num_classes, size=(cfg.batch_size,)).astype(np.int32)
    images, labels = shard_host_batch(mesh, (images, labels))
    lr = jnp.asarray(cfg.lr, jnp.float32)

    _phase("lowering + compiling train step (first compile can take 20-40s)...")
    t_c0 = time.perf_counter()
    compiled = train_step.lower(state, images, labels, lr).compile()
    compile_s = time.perf_counter() - t_c0
    _phase(f"compiled in {compile_s:.1f}s")
    return cfg, compiled, state, images, labels, lr, compile_s


def compiled_flops(compiled) -> float | None:
    """Per-device FLOPs of a compiled executable (best-effort; the unwrap
    lives in tpudist.telemetry so the trainer's MFU shares it)."""
    from tpudist.telemetry import cost_analysis_flops
    return cost_analysis_flops(compiled, log=_phase)


def measure_row(arch: str, per_device_batch: int, image_size: int,
                steps: int, warmup: int, *, use_amp: bool = True,
                amp_dtype: str = "bfloat16", sync_batchnorm: bool = False,
                remat: bool = False, s2d: bool = False, seed: int = 0) -> dict:
    """Compile + time one training-recipe row on the already-initialized
    backend; returns the measurement dict (metric name excluded).

    Shared by the single-row driver bench below and by
    ``benchmarks/recipe_table.py`` (the reference's four-row README table,
    ``/root/reference/README.md:9-14``, re-created on TPU)."""
    import jax

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    n = jax.device_count()

    cfg, compiled, state, images, labels, lr, compile_s = build_compiled_step(
        arch, per_device_batch, image_size, use_amp=use_amp,
        amp_dtype=amp_dtype, sync_batchnorm=sync_batchnorm, remat=remat,
        s2d=s2d, seed=seed)

    # XLA introspection (tpudist/obs/xla_introspect.py): ONE pass over the
    # compiler surfaces yields the MFU numerator, the compiled-HBM view,
    # and the collective census + temp-buffer attribution — so a row that
    # got slower also says whether comms or scratch HBM grew.
    try:
        from tpudist.obs.xla_introspect import event_fields, introspect
        intro = event_fields(introspect(compiled, log=_phase))
    except Exception as e:
        _phase(f"xla introspection unavailable: {e!r}")
        intro = {}
    flops_per_step = intro.get("flops") or None
    hbm_compiled_gb = (round(intro["hbm_compiled_bytes"] / 2**30, 3)
                       if intro.get("hbm_compiled_bytes") is not None
                       else None)

    # Timing notes:
    # - run the `compiled` executable directly: calling the jitted fn would
    #   recompile (~20s) since lower().compile() does not seed the jit cache;
    # - the timed region ends in a host readback of the final metrics, which
    #   transitively depends on every step in the chain.
    _phase(f"warmup x{warmup}...")
    t_w0 = time.perf_counter()
    for _ in range(warmup):
        state, metrics = compiled(state, images, labels, lr)
    jax.device_get(metrics["loss"])
    dt_warmup = time.perf_counter() - t_w0

    _phase(f"measuring {steps} steps...")
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = compiled(state, images, labels, lr)
    jax.device_get(metrics["loss"])
    dt = time.perf_counter() - t0

    step_time_ms = dt / steps * 1e3
    images_per_sec = cfg.batch_size * steps / dt
    # Bench-scope goodput (telemetry.py's run-level definition, scoped to
    # this process's work): productive step time over compile+warmup+measure
    # wall. Dominated by compile amortization at bench step counts — the
    # number a short real run would see, which is why BENCH rows carry it.
    goodput = round((dt_warmup + dt) / (compile_s + dt_warmup + dt), 4)

    mfu = None
    peak = _peak_flops(device_kind)
    if flops_per_step and peak:
        # cost_analysis() reports the per-device (SPMD-partitioned) module's
        # FLOPs, so normalize by ONE device's peak — not peak * n.
        mfu = round(flops_per_step / (dt / steps) / peak, 4)
        if mfu > 1.0:
            _phase(f"WARNING: mfu={mfu} > 1 — timing did not capture real "
                   "execution (async platform?); treat throughput as invalid")

    # Runtime allocator view. On the v5e runtime it leaves the step
    # program's scratch out (PERF.md, PR 21), so it is a lower bound:
    # hbm_compiled_gb beside it decides what fits. CPU returns nothing.
    from tpudist.utils import peak_hbm_gb as _runtime_peak_hbm
    peak_hbm_gb = _runtime_peak_hbm()
    if peak_hbm_gb is None:
        peak_hbm_gb = hbm_compiled_gb

    _phase(f"row done: {images_per_sec:.1f} img/s, {step_time_ms:.1f} ms/step, "
           f"mfu={mfu}, peak_hbm={peak_hbm_gb}GB")
    row = {
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "step_time_ms": round(step_time_ms, 2),
        "mfu": mfu,
        "goodput": goodput,
        "peak_hbm_gb": peak_hbm_gb,
        "hbm_compiled_gb": hbm_compiled_gb,
        "platform": platform,
        "device_kind": device_kind,
        "n_devices": n,
        "per_device_batch": per_device_batch,
        "steps": steps,
        "compile_s": round(compile_s, 1),
        "arch": arch,
        "image_size": image_size,
        "remat": remat,
        "s2d": s2d,
    }
    if intro.get("temp_bytes") is not None:
        row["hbm_temp_gb"] = round(intro["temp_bytes"] / 2**30, 3)
    for k in ("collective_ops", "collective_bytes_per_step",
              "collective_link_bytes",
              "all_reduce_count", "all_reduce_bytes", "bytes_accessed"):
        if intro.get(k) is not None:
            row[k] = intro[k]
    if arch == "resnet18":
        # The 3×TITAN-Xp reference baseline IS a resnet18 number (BASELINE.md
        # DDP row): stamping the ratio onto resnet50/vit rows would compare
        # different architectures and mislead anyone quoting it (ADVICE r5).
        row["vs_baseline"] = round(images_per_sec / REFERENCE_IMAGES_PER_SEC,
                                   4)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="the backend this measurement is FOR: the bench "
                         "exits non-zero if jax initializes on another one")
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--per-device-batch", type=int, default=128)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--remat", action="store_true",
                    help="bench with --remat (activation recompute): "
                         "quantifies the HBM/throughput trade")
    ap.add_argument("--s2d", action="store_true",
                    help="bench with the space-to-depth stem rewrite instead "
                         "of the direct 7x7/s2 conv (resnets only)")
    ap.add_argument("--regress-strict", action="store_true",
                    dest="regress_strict",
                    help="exit 3 when the post-bench regression gate trips "
                         "(default: the REGRESSION banner on stderr only — "
                         "the row already printed to stdout stays usable)")
    args = ap.parse_args()
    if args.s2d and not args.arch.startswith(
            ("resnet", "resnext", "wide_resnet")):
        # Fail BEFORE the compile: only the resnet family has the s2d stem
        # lever; anything else would TypeError in create_model.
        ap.error(f"--s2d applies to the resnet family; got '{args.arch}'")

    require_platform(args.platform)
    rec = measure_row(args.arch, args.per_device_batch, args.image_size,
                      args.steps, args.warmup, remat=args.remat,
                      s2d=args.s2d)
    # Suffix from the platform measure_row actually ran on.
    suffix = (f"{rec['n_devices']}chip" if rec["platform"] == "tpu"
              else f"{rec['n_devices']}dev_{rec['platform']}")
    remat_tag = "remat_" if args.remat else ""
    stem_tag = "s2d_" if args.s2d else ""
    rec = {"metric": f"{args.arch}_{args.image_size}_bf16_{remat_tag}"
                     f"{stem_tag}train_images_per_sec_{suffix}", **rec}
    print(json.dumps(rec), flush=True)

    # Every measurement lands in the history; then the regression gate
    # (tpudist/regress.py, also runnable standalone as tpudist-regress)
    # compares it to the trailing median of its own workload. The verdict
    # goes to stderr (stdout's one line stays the row); --regress-strict
    # makes a tripped gate fail the bench process itself.
    from tpudist.regress import (analyze_history, append_history,
                                 format_verdict, history_path, load_history)
    hist_row = dict(rec)
    hist_row["measured_at"] = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    append_history(hist_row)
    verdict = analyze_history(load_history(history_path()),
                              metric=rec["metric"])
    print(format_verdict(verdict), file=sys.stderr, flush=True)
    if verdict["status"] == "regression" and args.regress_strict:
        sys.exit(3)


if __name__ == "__main__":
    main()
