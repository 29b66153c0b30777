"""Perf-regression gate over the bench history
(``python -m tpudist.regress`` / ``tpudist-regress``).

``bench.py`` appends every fresh measurement to
``benchmarks/results/bench_history.jsonl`` (one JSON row per line, the same
shape it prints to stdout plus ``measured_at``). This gate compares the
NEWEST fresh row of a workload against the trailing median of its
predecessors and **fails loudly** (exit 2, ``REGRESSION`` banner) when
images/sec or MFU dropped — or, for latency series (``unit: ms``, e.g. the
``bench_flash`` kernel rows), the time ROSE — or, on any row carrying
``collective_bytes_per_step`` (the XLA census), the per-step collective
bytes GREW (a step-builder change silently re-densifying a compressed
exchange, or a sharding change widening a gather) — more than
``--threshold`` (default 10%) — the
automated tripwire the ROADMAP's "as fast as the hardware allows" needs,
instead of a human eyeballing bench rows across rounds.

Row identity is the row's ``metric`` name — it encodes arch, image size,
precision, remat/s2d levers, AND the platform suffix (``..._1chip`` vs
``..._8dev_cpu``), so a CPU bench can never gate against
TPU history — PLUS ``per_device_batch``, which the metric name does NOT
encode: a batch sweep (b=16 after b=128 history) must open its own series,
not trip a false REGRESSION against the other batch's median.

Median (not mean) over the trailing window: one noisy historical row must
not move the baseline; an improvement simply raises future medians.
``analyze_history`` is a pure function of the row list so the gate is
unit-testable against synthetic histories.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def history_path() -> str:
    """The bench history file, resolved at CALL time so a test/tool setting
    ``TPUDIST_BENCH_HISTORY`` after import still redirects appends."""
    return os.environ.get(
        "TPUDIST_BENCH_HISTORY",
        os.path.join(_REPO, "benchmarks", "results", "bench_history.jsonl"))


def load_history(path: str) -> list[dict]:
    """All parseable rows, file order (= append order)."""
    rows: list[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, dict) and row.get("metric") \
                        and isinstance(row.get("value"), (int, float)):
                    rows.append(row)
    except OSError:
        pass
    return rows


def append_history(row: dict, path: Optional[str] = None) -> None:
    """One fresh bench row → one history line (callers stamp measured_at)."""
    path = path or history_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")


def _median(xs: list[float]) -> float:
    # telemetry.percentile is the repo's one interpolated-percentile
    # implementation (import-light, jax-free) — q=50 IS the median for
    # both parities.
    from tpudist.telemetry import percentile
    return percentile(xs, 50)


def _series_key(row: dict) -> tuple:
    return (row.get("metric"), row.get("per_device_batch"))


def analyze_history(rows: list[dict], metric: Optional[str] = None,
                    window: int = 5, threshold: float = 0.10,
                    min_history: int = 1) -> dict:
    """Gate verdict for one workload's newest row vs its trailing median.

    ``metric`` selects the workload; default = the workload of the LAST row
    in the history (what bench just appended). The series the newest row
    gates against additionally matches on ``per_device_batch``
    (``_series_key``). Returns a dict with ``status`` in {"pass",
    "regression", "no_history", "no_baseline"} and the numbers behind it;
    ``reasons`` lists every tripped dimension.
    """
    cands = rows if metric is None \
        else [r for r in rows if r.get("metric") == metric]
    if not cands:
        return {"status": "no_history", "metric": metric, "n_history": 0}
    key = _series_key(cands[-1])
    metric = cands[-1]["metric"]
    group = [r for r in rows if _series_key(r) == key]
    newest, prior = group[-1], group[:-1][-window:]
    out: dict = {"status": "pass", "metric": metric,
                 "per_device_batch": newest.get("per_device_batch"),
                 "value": newest["value"],
                 "n_history": len(group) - 1, "window": len(prior),
                 "threshold": threshold, "reasons": [],
                 "measured_at": newest.get("measured_at")}
    if len(prior) < min_history:
        out["status"] = "no_baseline"
        return out
    base_v = _median([r["value"] for r in prior])
    out["baseline_value"] = round(base_v, 2)
    out["ratio"] = round(newest["value"] / base_v, 4) if base_v else None
    # Gate direction follows the series' unit: throughput series
    # (images/sec, MFU) regress DOWNWARD; latency series (the bench_flash
    # ``unit: ms`` rows) regress UPWARD. A row may also state it outright
    # (``lower_is_better``) for units this heuristic doesn't know.
    lower_better = bool(newest.get("lower_is_better",
                                   newest.get("unit") == "ms"))
    out["lower_is_better"] = lower_better
    if lower_better:
        if base_v and newest["value"] > (1.0 + threshold) * base_v:
            out["status"] = "regression"
            out["reasons"].append(
                f"{newest.get('unit', 'value')} {newest['value']:.3f} is "
                f"{(newest['value'] / base_v - 1):.1%} above the trailing "
                f"median {base_v:.3f} (n={len(prior)})")
    elif base_v and newest["value"] < (1.0 - threshold) * base_v:
        out["status"] = "regression"
        # Name the series' own unit (req/s for the serving saturation
        # rows, images/sec for the throughput default) so the banner
        # reads correctly for every higher-is-better series.
        out["reasons"].append(
            f"{newest.get('unit') or 'images/sec'} {newest['value']:.1f} "
            f"is {(1 - newest['value'] / base_v):.1%} below the trailing "
            f"median {base_v:.1f} (n={len(prior)})")
    prior_mfu = [r["mfu"] for r in prior
                 if isinstance(r.get("mfu"), (int, float))]
    if isinstance(newest.get("mfu"), (int, float)) and \
            len(prior_mfu) >= min_history:
        base_m = _median(prior_mfu)
        out["mfu"] = newest["mfu"]
        out["baseline_mfu"] = round(base_m, 4)
        if base_m and newest["mfu"] < (1.0 - threshold) * base_m:
            out["status"] = "regression"
            out["reasons"].append(
                f"MFU {newest['mfu']:.4f} is "
                f"{(1 - newest['mfu'] / base_m):.1%} below the trailing "
                f"median {base_m:.4f} (n={len(prior_mfu)})")
    # Collective-bytes gate (PR 11: communication is a first-class gated
    # dimension beside img/s and MFU): the census bytes are a deterministic
    # property of the compiled program, so a rise above the trailing median
    # means the program grew its comms — a step-builder change silently
    # re-densifying a compressed exchange, or a sharding change widening a
    # gather. Bytes regress UPWARD regardless of the series' value unit.
    prior_cb = [r["collective_bytes_per_step"] for r in prior
                if isinstance(r.get("collective_bytes_per_step"),
                              (int, float))]
    if isinstance(newest.get("collective_bytes_per_step"), (int, float)) \
            and len(prior_cb) >= min_history:
        base_b = _median(prior_cb)
        out["collective_bytes_per_step"] = newest[
            "collective_bytes_per_step"]
        out["baseline_collective_bytes"] = round(base_b, 1)
        if base_b and newest["collective_bytes_per_step"] \
                > (1.0 + threshold) * base_b:
            out["status"] = "regression"
            out["reasons"].append(
                f"collective bytes/step "
                f"{newest['collective_bytes_per_step']:.3e} is "
                f"{(newest['collective_bytes_per_step'] / base_b - 1):.1%} "
                f"above the trailing median {base_b:.3e} "
                f"(n={len(prior_cb)})")
    return out


def format_verdict(v: dict) -> str:
    m = v.get("metric") or "<no rows>"
    if v["status"] == "no_history":
        return f"[regress] no history for {m} — nothing to gate"
    if v["status"] == "no_baseline":
        return (f"[regress] {m}: {v['n_history']} prior row(s) — below "
                f"min history, gate not armed (value {v['value']})")
    head = (f"[regress] {m}: value {v['value']} vs trailing median "
            f"{v.get('baseline_value')} (ratio {v.get('ratio')}"
            + (f", mfu {v['mfu']} vs {v['baseline_mfu']}"
               if "mfu" in v else "") + ")")
    if v["status"] == "regression":
        return ("REGRESSION: " + "; ".join(v["reasons"]) + "\n" + head)
    return head + " — PASS"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Gate the newest bench row against its trailing-median "
                    "history (exit 2 on >threshold regression)")
    # default=None, resolved below at CALL time: an argparse default of
    # history_path() would re-freeze the env var at parse time — the exact
    # dual-path bug the old module-level DEFAULT_HISTORY snapshot had
    # (a caller setting TPUDIST_BENCH_HISTORY after import gated against
    # the wrong file).
    p.add_argument("--history", default=None,
                   help="bench_history.jsonl path "
                        "(env TPUDIST_BENCH_HISTORY)")
    p.add_argument("--metric", default=None,
                   help="workload metric name to gate (default: the "
                        "history's newest row)")
    p.add_argument("--window", type=int, default=5,
                   help="trailing rows the baseline median is taken over")
    p.add_argument("--threshold", type=float, default=0.10,
                   help="fractional drop in images/sec or MFU that fails "
                        "the gate")
    p.add_argument("--min-history", type=int, default=1, dest="min_history",
                   help="prior rows required before the gate arms "
                        "(below it: informational pass)")
    p.add_argument("--json", action="store_true",
                   help="emit the verdict as JSON (status still drives the "
                        "exit code)")
    args = p.parse_args(argv)

    rows = load_history(args.history or history_path())
    v = analyze_history(rows, metric=args.metric, window=args.window,
                        threshold=args.threshold,
                        min_history=args.min_history)
    if args.json:
        print(json.dumps(v))
    else:
        print(format_verdict(v))
    return 2 if v["status"] == "regression" else 0


if __name__ == "__main__":
    sys.exit(main())
