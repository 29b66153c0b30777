"""From a profiler trace to numbers. Two stages, so that the second can be
checked on a small recorded trace (`selftest/recorded_trace.json.gz`):

1. `extract(xplane_path, ...)`: the `.xplane.pb` -> a plain dict of the
   device planes' op and module lines and of the host plane's annotation
   spans (`bench.*`, `tpudist.*`, the trainer's `train` step annotation);
2. `reduce(events, ...)`: that dict -> busy seconds, idle gaps attributed to
   the host span open at the time, op totals, per-step device time.

All times are the trace's own nanoseconds; nothing here reads a host clock.
"""

from __future__ import annotations

import glob
import os

HOST_PREFIXES = ("bench.", "tpudist.")
HOST_NAMES = ("train",)
WINDOW_SPAN = "bench.window"


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _op_name(event) -> tuple[str, str]:
    """A device event's short name and what says its kind. The TPU trace
    names an op by its whole HLO line (`%fusion.60 = (...) fusion(...),
    kind=kOutput, calls=...`): keep the result's name, and as the kind the
    `hlo_category` stat where the trace has it, else the HLO's own words."""
    text = event.name
    stats = {str(k): v for k, v in event.stats}
    short = text.split(" = ", 1)[0].lstrip("%") if " = " in text else text
    if stats.get("tf_op"):
        short = f"{short}_{str(stats['tf_op'])[-48:]}"
    kind = str(stats.get("hlo_category", ""))
    if not kind and " = " in text:
        rhs = text.split(" = ", 1)[1]
        op = rhs.split(")", 1)[-1] if rhs.startswith("(") else rhs
        words = [w for w in ("convolution", "dot(", "kind=kOutput",
                             "kind=kConv", "kind=kLoop", "kind=kInput")
                 if w in op or w in rhs[-120:]]
        kind = " ".join(words)
    return short[:96], kind


def extract(xplane_path: str, device_plane_prefix: str,
            op_lines=("XLA Ops",), module_lines=("XLA Modules",)) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = {"devices": [], "host": [], "seen": [], "sample_stats": []}
    for plane in data.planes:
        lines = list(plane.lines)
        out["seen"].append([plane.name, [ln.name for ln in lines]])
        if plane.name.startswith(device_plane_prefix):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for ln in lines:
                key = ("ops" if ln.name.startswith(tuple(op_lines)) else
                       "modules" if module_lines and ln.name.startswith(
                           tuple(module_lines)) else None)
                if key is None:
                    continue
                for e in ln.events:
                    name, cat = _op_name(e)
                    row = [name, int(e.start_ns), int(e.duration_ns)]
                    if key == "ops":
                        row.append(cat)
                        if len(out["sample_stats"]) < 40:
                            out["sample_stats"].append(
                                [e.name[:300], {str(k): str(v)[:200]
                                                for k, v in e.stats}])
                    dev[key].append(row)
            out["devices"].append(dev)
        if plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIXES) or e.name in HOST_NAMES:
                        out["host"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    out["host"].sort(key=lambda r: r[1])
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(rows, lo, hi):
    for r in rows:
        s, e = max(r[1], lo), min(r[1] + r[2], hi)
        if e > s:
            yield r, s, e


def reduce(events: dict, step_module: str = "",
           mxu_words=("conv", "dot", "koutput"),
           min_gap_ns: int = 20_000) -> dict:
    """Numbers of the traced window. Returns None-valued entries for what the
    trace does not hold (a reader then leaves its metric out)."""
    host = events.get("host", [])
    win = [r for r in host if r[0] == WINDOW_SPAN]
    devices = [d for d in events.get("devices", []) if d["ops"]]
    if not devices:
        return {"busy_s": None}
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(r[1] for d in devices for r in d["ops"])
        hi = max(r[1] + r[2] for d in devices for r in d["ops"])
    spans = [r for r in host if r[0] != WINDOW_SPAN]
    busy, op_total, mxu, gaps, steps = [], {}, 0.0, {}, []
    for d in devices:
        clipped = list(_clip(d["ops"], lo, hi))
        merged = _union([[s, e] for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged))
        for r, s, e in clipped:
            op_total[r[0]] = op_total.get(r[0], 0) + (e - s)
            text = (r[0] + " " + (r[3] if len(r) > 3 else "")).lower()
            if any(w in text for w in mxu_words):
                mxu += e - s
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 < min_gap_ns:
                continue
            best, best_ov = "host.unattributed", 0
            for name, s, dur in spans:
                ov = min(g1, s + dur) - max(g0, s)
                # the innermost span wins a tie: later rows start later
                if ov > 0 and ov >= best_ov:
                    best, best_ov = name, ov
            gaps[best] = gaps.get(best, 0) + (g1 - g0)
        for r, s, e in _clip(d["modules"], lo, hi):
            if not step_module or r[0].startswith(step_module):
                if s == r[1] and e == r[1] + r[2]:      # whole steps only
                    steps.append(r[2])
    n = len(devices)
    total_busy = sum(busy)
    top = sorted(op_total.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": total_busy / n / 1e9,
        "idle_pct": 100.0 * (1.0 - total_busy / n / (hi - lo)),
        "mxu_share_pct": 100.0 * mxu / total_busy if total_busy else None,
        "device_step_ms": (sum(steps) / len(steps) / 1e6) if steps else None,
        "device_steps": len(steps),
        "device_ops": [[k, v / n / 1e9] for k, v in top],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        "host_spans": {name: [sum(r[2] for r in host if r[0] == name) / 1e9,
                              sum(1 for r in host if r[0] == name)]
                       for name in {r[0] for r in host}},
    }
