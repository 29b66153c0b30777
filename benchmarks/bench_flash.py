"""Flash-attention microbenchmark: Pallas kernel vs plain XLA attention on
the attached chip (VERDICT r1 #7: 'fwd+bwd kernel benched vs attention() on
the real chip, numbers in repo').

Times forward and forward+backward for both implementations at ViT-B shape
(T=197, the actual zoo workload) and a long-context shape (T=2048, where
flash's O(T) memory matters). Timing goes through jax.device_get of a value
depending on the full computation.

Every numeric row is also appended to ``benchmarks/results/
bench_history.jsonl`` as its own gateable series — ``fwd`` and ``fwd+bwd``
separately, flash and XLA separately — so ``tpudist-regress`` (which gates
``unit: ms`` rows on time INCREASE) covers kernel perf round over round.
Each flash/XLA pair additionally carries the measurement-honest dispatch
verdict (``tpudist/ops/attention_dispatch``) derived from the very numbers
in the row; on TPU that verdict is written into the dispatch cache — a
cache warm for ``--flash auto`` **at the benched shapes** (the cache keys
on batch too, so a training run at a different per-device batch still
measures its own shape once).

Usage: python benchmarks/bench_flash.py   (on the TPU env; falls back to
interpreter-mode Pallas on CPU, where numbers are meaningless — the platform
is stamped into the metric name so they can't be misread, and no dispatch
verdict is cached).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_row(fn, qkv, steps: int, metric: str, shape, dtype: str,
              flops: float) -> dict:
    """One JSON row timed by THE timing harness (attention_dispatch.
    measure_ms, which ends in a host readback), so bench rows
    and dispatch verdicts cannot drift in methodology; failures become an
    'error' field ('oom' normalized) so the capability probe can report
    XLA's expected long-context OOM."""
    from tpudist.ops.attention_dispatch import measure_ms
    row = {"metric": metric, "unit": "ms", "shape": list(shape),
           "dtype": dtype}
    try:
        ms = measure_ms(fn, qkv, steps, warmup=3)
        row["value"] = round(ms, 3)
        row["tflops_per_s"] = round(flops / (ms / 1e3) / 1e12, 2)
    except Exception as e:
        row["value"] = None
        row["error"] = _norm_error(e)
    print(json.dumps(row), flush=True)
    return row


def _norm_error(e: Exception) -> str:
    """Normalize any out-of-memory-shaped failure to 'oom' (ADVICE r3:
    allocator/Mosaic phrasings vary — substring-matching only XLA's
    RESOURCE_EXHAUSTED flipped the capability-proof exit code on wording).
    'allocat' alone is NOT enough: device-lost/semaphore errors say
    'failed to allocate <resource>' without being memory exhaustion, and the
    long-context capability proof treats an XLA 'oom' as the one tolerated
    failure — so the allocation phrasing must also mention memory."""
    s = str(e).lower()
    if ("resource_exhausted" in s or "out of memory" in s
            or ("allocat" in s and "memory" in s)):
        return "oom"
    return f"{type(e).__name__}: {e}"[:200]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--sweep-blocks", action="store_true",
                    help="sweep (block_q, block_k) for the flash kernel at "
                         "the long-context shape instead of the default "
                         "flash-vs-XLA comparison")
    ap.add_argument("--long-context", type=int, default=0, metavar="T",
                    help="add a (1, T, 12, 64) shape; XLA attention is "
                         "attempted and reported as 'oom' when its O(T^2) "
                         "logits exceed HBM — the flash capability proof")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from tpudist.ops.pallas import flash_attention
    from tpudist.parallel.ring_attention import attention

    platform = jax.default_backend()
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    long_t = args.long_context
    shapes = [
        ("vitb_224", (8, 197, 12, 64)),     # ViT-B/16 @224: B=8, T=196+cls
        ("long_2k", (2, 2048, 12, 64)),     # long-context: flash O(T) memory
    ]
    if platform != "tpu":
        # Interpreter-mode Pallas is both meaningless to time and hours-slow
        # at real shapes, and XLA's O(T^2) logits can OOM the host — cap
        # everything, including the long-context/sweep shapes, off-TPU.
        print(f"[bench_flash] WARNING: platform={platform} — Pallas runs in "
              f"interpreter mode, numbers are meaningless off-TPU",
              file=sys.stderr)
        shapes = [("tiny_64", (1, 64, 4, 16))]
        if long_t:
            long_t = min(long_t, 256)
    if long_t:
        shapes.append((f"long_{long_t}", (1, long_t, 12, 64)))

    rng = np.random.default_rng(0)

    def qkv(shape):
        return tuple(jnp.asarray(rng.standard_normal(shape), dt)
                     for _ in range(3))

    flash_failed = False

    if args.sweep_blocks:
        b, t, h, d = shapes[-1][1] if long_t else (2, 2048, 12, 64)
        if platform != "tpu":
            b, t, h, d = (1, min(t, 256), 4, 16)
        try:
            args_qkv = qkv((b, t, h, d))
        except Exception as e:
            # Input allocation for the long-context shape can itself OOM;
            # classify it like a kernel OOM instead of crashing (ADVICE r3).
            print(json.dumps({"metric": f"attn_sweep_inputs_{platform}",
                              "value": None, "shape": [b, t, h, d],
                              "dtype": args.dtype,
                              "error": _norm_error(e)}), flush=True)
            return 1
        # flash_attention clamps blocks to ceil8(T); dedupe by the clamped
        # values so the JSON never labels the same compiled kernel as two
        # different configs (a reader picking the fastest row must get a
        # block size that actually ran).
        ceil8 = (t + 7) // 8 * 8
        seen = set()
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                eff = (min(bq, ceil8), min(bk, ceil8))
                if eff in seen:
                    continue
                seen.add(eff)
                def loss(q, k, v, bq=bq, bk=bk):
                    return flash_attention(
                        q, k, v, block_q=bq,
                        block_k=bk).astype(jnp.float32).sum()
                # tpudist: ignore[RECOMP01] — block-size sweep: each iteration IS a distinct program; _time_row excludes compile
                fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                row = _time_row(
                    fn, args_qkv, args.steps,
                    f"attn_sweep_bq{eff[0]}_bk{eff[1]}_fwdbwd_ms_{platform}",
                    (b, t, h, d), args.dtype, 12.0 * b * h * t * t * d)
                flash_failed |= "error" in row
        return 1 if flash_failed else 0

    for name, (b, t, h, d) in shapes:
        try:
            q, k, v = qkv((b, t, h, d))
        except Exception as e:
            row = {"metric": f"attn_{name}_inputs_{platform}", "value": None,
                   "shape": [b, t, h, d], "dtype": args.dtype,
                   "error": _norm_error(e)}
            print(json.dumps(row), flush=True)
            flash_failed = True
            continue

        # tpudist: ignore[RECOMP01] — per-shape A/B bench: one jit per benched workload, compile excluded by _time_row
        flash_f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
        # tpudist: ignore[RECOMP01] — per-shape A/B bench: one jit per benched workload, compile excluded by _time_row
        plain_f = jax.jit(lambda q, k, v: attention(q, k, v))

        def loss_flash(q, k, v):
            return flash_attention(q, k, v).astype(jnp.float32).sum()

        def loss_plain(q, k, v):
            return attention(q, k, v).astype(jnp.float32).sum()

        # tpudist: ignore[RECOMP01] — per-shape A/B bench: one jit per benched workload, compile excluded by _time_row
        flash_g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
        # tpudist: ignore[RECOMP01] — per-shape A/B bench: one jit per benched workload, compile excluded by _time_row
        plain_g = jax.jit(jax.grad(loss_plain, argnums=(0, 1, 2)))

        rows: dict[str, dict] = {}
        for label, fn in (("flash_fwd", flash_f), ("xla_fwd", plain_f),
                          ("flash_fwdbwd", flash_g), ("xla_fwdbwd", plain_g)):
            # attention flops: 2 matmuls of [T,d]x[d,T] and [T,T]x[T,d]
            # per head (x3 for fwd+bwd rule of thumb).
            flops = 4.0 * b * h * t * t * d * (3.0 if "bwd" in label else 1.0)
            row = _time_row(fn, (q, k, v), args.steps,
                            f"attn_{name}_{label}_ms_{platform}",
                            (b, t, h, d), args.dtype, flops)
            rows[label] = row
            # Any erroring row fails the bench EXCEPT the one expected
            # capability-proof outcome: XLA reporting 'oom' at a
            # long-context shape. A flash error is a kernel regression; an
            # XLA non-oom error (or an oom at the ViT shape) is a broken
            # baseline — neither may exit 0.
            if "error" in row and not (
                    label.startswith("xla") and row["error"] == "oom"
                    and name.startswith("long_")):
                flash_failed = True
        _embed_dispatch_and_append(rows, b, t, h, d, args.dtype, platform)
    return 1 if flash_failed else 0


def _embed_dispatch_and_append(rows: dict, b: int, t: int, h: int, d: int,
                               dtype: str, platform: str) -> None:
    """Stamp the measurement-honest dispatch verdict onto each flash/XLA
    pair (separately for fwd = eval and fwd+bwd = train) and append every
    numeric row to the bench history as its own regress-gateable series.
    On TPU the verdict (derived from the rows' own timings via the
    ``measure_pair`` hook) is also written into the dispatch cache — a
    bench run doubles as a ``--flash auto`` cache warm; off-TPU ``decide``
    resolves to XLA on platform grounds and caches nothing."""
    from tpudist.ops import attention_dispatch
    from tpudist.regress import append_history

    for pass_name, train in (("fwd", False), ("fwdbwd", True)):
        fr = rows.get(f"flash_{pass_name}")
        xr = rows.get(f"xla_{pass_name}")
        if not fr or not xr or fr.get("value") is None \
                or xr.get("value") is None:
            continue
        try:
            dec = attention_dispatch.decide(
                b, t, h, d, dtype, train=train, mode="auto",
                platform=platform, refresh=True,
                measure_pair=lambda fr=fr, xr=xr: (fr["value"], xr["value"]))
        except Exception as e:
            print(f"[bench_flash] dispatch verdict failed: {e!r}",
                  file=sys.stderr)
            continue
        disp = {"kernel": dec["kernel"], "source": dec["source"],
                "flash_ms": fr["value"], "xla_ms": xr["value"]}
        fr["dispatch"] = disp
        xr["dispatch"] = disp
    if platform != "tpu":
        # Interpreter-mode timings are "meaningless off-TPU" by this file's
        # own banner — they must not become gateable history either
        # (tpudist-regress now trips ms series UPWARD, and interpreter
        # noise routinely exceeds any threshold). Stdout still carries the
        # rows for capability probing; history stays measurement-only.
        print("[bench_flash] platform != tpu — rows NOT appended to bench "
              "history (interpreter timings are not measurements)",
              file=sys.stderr)
        return
    now = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    appended = 0
    for row in rows.values():
        if isinstance(row.get("value"), (int, float)):
            append_history({**row, "measured_at": now})
            appended += 1
    if appended:
        print(f"[bench_flash] {appended} row(s) appended to bench history",
              file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
