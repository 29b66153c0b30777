"""MFU-budget report from a run dir's telemetry (``python -m tpudist.summarize
<rundir>``).

Answers the two questions console meters cannot (VERDICT #4): *where does
the missing MFU go* and *which rank is slow*. Reads every
``events.*.jsonl`` a run (or its launcher) wrote — see ``tpudist/telemetry.py``
for the schema — and prints:

- run **goodput** (productive step time ÷ wall time) with the non-productive
  remainder attributed to init / compile / checkpoint / eval;
- **MFU** from the compiled step's cost-analysis FLOPs against the device
  peak (``--peak-flops`` or ``TPUDIST_PEAK_FLOPS`` override the table —
  required on backends with no public spec, e.g. CPU);
- the per-step **time budget** (data wait / host→device / device compute /
  metric drain / other-host, p50 and p95);
- per-rank step-time table with straggler flags, plus the fault /
  preemption / restart timeline.

``analyze()`` is a pure function of the event list so the goodput/MFU math
is unit-testable against synthetic timelines (``tests/test_telemetry.py``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Optional

from tpudist.telemetry import (find_stragglers, percentile,
                               resolve_peak_flops, resolve_peak_hbm,
                               validate_event)


def load_events(rundir: str, strict: bool = False) -> list[dict]:
    """Every event from every ``events.*.jsonl`` in ``rundir``, time-sorted.
    The glob also picks up size-rotated segments (``events.<rank>.1.jsonl``,
    from ``--telemetry-max-mb``) — time-sorting reassembles the stream.
    Malformed lines are counted and skipped (a rank killed mid-write leaves
    at most one torn final line) unless ``strict``."""
    events: list[dict] = []
    bad = 0
    for path in sorted(glob.glob(os.path.join(rundir, "events.*.jsonl"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                    validate_event(ev)
                except (ValueError, TypeError) as e:
                    if strict:
                        raise ValueError(f"{path}: {e}") from e
                    bad += 1
                    continue
                events.append(ev)
    if bad:
        print(f"[summarize] skipped {bad} malformed event line(s)",
              file=sys.stderr)
    events.sort(key=lambda e: e["t"])
    return events


def _pcts(xs: list[float]) -> Optional[dict]:
    if not xs:
        return None
    return {"p50": percentile(xs, 50), "p95": percentile(xs, 95),
            "total": sum(xs)}


def analyze(events: list[dict],
            peak_flops: Optional[float] = None) -> dict:
    """Pure goodput/MFU/budget accounting over a telemetry event list."""
    steps = [e for e in events if e["type"] == "step"]
    run_starts = [e for e in events if e["type"] == "run_start"]
    run_ends = [e for e in events if e["type"] == "run_end"]
    programs = [e for e in events if e["type"] == "program"]
    faults = [e for e in events if e["type"] in
              ("fault", "preempt", "rank_exit", "restart", "straggler",
               "eviction", "collective_deadline")]
    # -- topology timeline (elastic plane): every launch attempt's world,
    # gang reformations, and cross-world reshards, in time order ----------
    topology = []
    for e in events:
        if e["type"] == "launcher_start":
            topology.append({"t": e["t"], "kind": "launch",
                             "attempt": e["attempt"],
                             "world": e.get("nprocs"),
                             "mesh": e.get("mesh", "")})
        elif e["type"] == "topology_change":
            topology.append({"t": e["t"],
                             "kind": ("scale_up"
                                      if e.get("mesh_action") == "scale_up"
                                      else "reform"),
                             "attempt": e["attempt"],
                             "from_world": e["from_world"],
                             "to_world": e["to_world"],
                             "from_mesh": e.get("from_mesh", ""),
                             "to_mesh": e.get("to_mesh", ""),
                             "mesh_action": e.get("mesh_action", ""),
                             "lost_ranks": e.get("lost_ranks", "")})
        elif e["type"] == "eviction":
            topology.append({"t": e["t"], "kind": "evict",
                             "attempt": e["attempt"],
                             "rank": e.get("straggler_rank"),
                             "windows": e.get("windows")})
        elif e["type"] == "reshard":
            topology.append({"t": e["t"], "kind": "reshard",
                             "attempt": e["attempt"], "rank": e["rank"],
                             "from_world": e["from_world"],
                             "to_world": e["to_world"],
                             "detail": e.get("detail", "")})
    ckpts = [e for e in events if e["type"] in
             ("checkpoint_save", "checkpoint_restore")]
    attempts = sorted({e["attempt"] for e in events})

    out: dict = {
        "n_events": len(events),
        "n_steps": len(steps),
        "ranks": sorted({e["rank"] for e in events if e["rank"] >= 0}),
        "attempts": attempts,
        "arch": run_starts[0].get("arch") if run_starts else None,
        "platform": run_starts[0].get("platform") if run_starts else None,
        "device_kind": run_starts[0].get("device_kind") if run_starts
        else None,
        "n_faults": len([e for e in faults if e["type"] == "fault"]),
        "faults": faults,
        "checkpoint_events": len(ckpts),
        "topology": topology,
    }

    # -- step-time budget (one rank is representative under lockstep SPMD;
    # mixing ranks would double-count the same wall time — the same scoping
    # applies to checkpoint cost: collective saves emit one event PER rank
    # for the same wall-clock save) ----------------------------------------
    r0 = min(out["ranks"]) if out["ranks"] else 0
    r0_steps = [e for e in steps if e["rank"] == r0]
    out["checkpoint_s"] = sum(e["seconds"] for e in ckpts
                              if e["rank"] == r0)
    # First-dispatch compile rides inside that step's step_s (the step
    # event has no compile field; the paired compile event carries it) —
    # subtract it wherever productive time is reconstructed from raw
    # steps, and EXCLUDE those steps from the steady-state percentiles
    # (one 6s compile step among ten 0.5s steps would otherwise put the
    # compile into the "device compute" p95 and deflate MFU).
    r0_compile_s = sum(e["seconds"] for e in events
                       if e["type"] == "compile" and e["rank"] == r0
                       and e.get("phase") == "train_step")
    compile_step_nums = {e["step"] for e in events
                         if e["type"] == "compile" and e["rank"] == r0
                         and e.get("phase") == "train_step" and "step" in e}
    steady_steps = [e for e in r0_steps
                    if e["step"] not in compile_step_nums] or r0_steps
    budget = {}
    for key in ("data_s", "h2d_s", "compute_s", "drain_s", "step_s"):
        budget[key] = _pcts([e[key] for e in steady_steps])
    # Overlap-aware phase accounting (device prefetch): ``prefetch_s`` is
    # host time spent staging the NEXT batch while THIS step's compute was
    # in flight. It is a disjoint host interval like the others, so it gets
    # its own bucket AND is subtracted from the other-host residue — the
    # serial buckets then hold only exposed time, and the whole budget sums
    # to ≤ step_s by construction (no phase is ever counted twice).
    has_prefetch = any("prefetch_s" in e for e in steady_steps)
    if has_prefetch:
        budget["prefetch_s"] = _pcts([e.get("prefetch_s", 0.0)
                                      for e in steady_steps])
    # Async metric drain rides the same overlapped contract (its own
    # bucket, subtracted from the other-host residue — never counted
    # beside the serial drain_s it replaced).
    if any("drain_ovl_s" in e for e in steady_steps):
        budget["drain_ovl_s"] = _pcts([e.get("drain_ovl_s", 0.0)
                                       for e in steady_steps])
    other = [max(0.0, e["step_s"] - e["data_s"] - e["h2d_s"] - e["compute_s"]
                 - e["drain_s"] - e.get("prefetch_s", 0.0)
                 - e.get("drain_ovl_s", 0.0))
             for e in steady_steps]
    budget["other_host_s"] = _pcts(other)
    out["budget"] = budget

    # -- persistent-compile-cache provenance (--compile-cache): stamped on
    # compile events; surfaces beside the compile bucket so a warm restart
    # is attributable as cache-hit seconds, not a real compile -----------
    out["compile_cache"] = next(
        (e["cache"] for e in events
         if e["type"] == "compile" and e.get("cache")), None)

    # -- serving plane (tpudist/serve/): request latency/throughput and
    # the AOT cold-start numbers, from the serve event stream ------------
    reqs = [e for e in events if e["type"] == "request"]
    batches = [e for e in events if e["type"] == "serve_batch"]
    serve_start = next(
        (e for e in reversed(events) if e["type"] == "serve_start"), None)
    if serve_start is not None or reqs:
        # Errored requests (error=1) count toward traffic but not the
        # latency percentiles — p50/p99 is service latency.
        lat = [e["latency_s"] for e in reqs
               if isinstance(e.get("latency_s"), (int, float))
               and not e.get("error")]
        span = (reqs[-1]["t"] - reqs[0]["t"]) if len(reqs) > 1 else 0.0
        occ = [e["n_valid"] / e["bucket"] for e in batches
               if e.get("bucket")]
        aot_compiles = [e for e in events if e["type"] == "compile"
                        and e.get("phase") == "serve_aot"]
        out["serving"] = {
            "n_requests": len(reqs),
            "n_errors": len([e for e in reqs if e.get("error")]),
            "n_batches": len(batches),
            "latency_p50_ms": (round(percentile(lat, 50) * 1e3, 3)
                               if lat else None),
            "latency_p99_ms": (round(percentile(lat, 99) * 1e3, 3)
                               if lat else None),
            "req_per_s": (round(len(reqs) / span, 2) if span > 0 else None),
            "occupancy_p50": (round(percentile(occ, 50), 4)
                              if occ else None),
            "aot_s": (serve_start or {}).get("aot_s"),
            "aot_compile_s": (serve_start or {}).get("aot_compile_s"),
            "cache": (serve_start or {}).get("cache"),
            "n_buckets": (serve_start or {}).get("n_buckets"),
            "buckets": (serve_start or {}).get("buckets"),
            "aot_compiles": len(aot_compiles),
            # The zero-recompile proof: every compile event in a serving
            # run must be an AOT bucket compile (or the trainer-side
            # phases of a mixed run dir) — steady-state traffic through
            # the bucketed queue never compiles.
            "non_aot_compiles": len(
                [e for e in events if e["type"] == "compile"
                 and e.get("phase") not in ("serve_aot",)]),
        }
    else:
        out["serving"] = None

    # -- doctor plane (tpudist/doctor/): every intervention and every SDC
    # probe, so a run where weights were un-written says so ---------------
    doctor_evs = [e for e in events if e["type"] == "doctor"]
    sdc_evs = [e for e in events if e["type"] == "sdc_probe"]
    if doctor_evs or sdc_evs:
        by_action: dict = {}
        for e in doctor_evs:
            a_ = str(e.get("action"))
            by_action[a_] = by_action.get(a_, 0) + 1
        out["doctor"] = {
            "interventions": len(doctor_evs),
            "by_action": by_action,
            "probes": len(sdc_evs),
            "divergent_probes": len([e for e in sdc_evs
                                     if e.get("divergent") or e.get("tie")]),
            "events": doctor_evs,
        }
    else:
        out["doctor"] = None

    # -- perf-CI console (tpudist/perfci.py): unattended bench-matrix runs
    # emit one perfci_run event each into the report dir, so summarizing
    # benchmarks/results/ yields the trend-gate history -------------------
    perfci_evs = [e for e in events if e["type"] == "perfci_run"]
    if perfci_evs:
        out["perfci"] = {
            "runs": len(perfci_evs),
            "regressions": sum(int(e.get("regressions") or 0)
                               for e in perfci_evs),
            "events": perfci_evs,
        }
    else:
        out["perfci"] = None

    # -- blackbox plane (tpudist/blackbox.py): every incident trigger, by
    # class, with the capture-vs-cooldown split; bundle inventory comes
    # from the run dir at render time (analyze stays pure on events) ------
    incident_evs = [e for e in events if e["type"] == "incident"]
    if incident_evs:
        by_trigger: dict = {}
        for e in incident_evs:
            tr = str(e.get("trigger"))
            by_trigger[tr] = by_trigger.get(tr, 0) + 1
        out["incidents"] = {
            "triggers": len(incident_evs),
            "by_trigger": by_trigger,
            "captures": len([e for e in incident_evs if e.get("captured")]),
            "suppressed": len([e for e in incident_evs
                               if not e.get("captured")]),
            "events": incident_evs,
        }
    else:
        out["incidents"] = None

    # -- goodput -----------------------------------------------------------
    # Per-attempt run_end events carry the trainer's own accounting; prefer
    # the primary rank's LAST one. Across restarts, also compute the
    # whole-job view: everything from the first run_start to the last
    # run_end, so the crashed attempt's lost work shows up as lost goodput.
    r0_end = next((e for e in reversed(run_ends) if e["rank"] == r0), None)
    out["run_end"] = r0_end
    if r0_end is not None:
        out["goodput"] = r0_end["goodput"]
        out["wall_s"] = r0_end["wall_s"]
        out["productive_s"] = r0_end["productive_s"]
    elif r0_steps:
        # Crashed run (no run_end): reconstruct from the step stream. The
        # first step's step_s holds the XLA compile — subtract the paired
        # compile events or a 60s-compile/10s-train crash reads as ~1.0.
        wall = max(1e-9, r0_steps[-1]["t"] - (run_starts[0]["t"]
                                              if run_starts
                                              else r0_steps[0]["t"]))
        productive = max(0.0, sum(e["step_s"] for e in r0_steps)
                         - r0_compile_s)
        out["wall_s"] = wall
        out["productive_s"] = productive
        out["goodput"] = min(1.0, productive / wall)
    else:
        out["goodput"] = None
    if len(attempts) > 1 and run_starts and (run_ends or steps):
        t_first = run_starts[0]["t"]
        # run_ends AND steps: a final attempt that died without a run_end
        # (os._exit, OOM) still contributed steps whose productive time is
        # summed below — its wall must be in the denominator too.
        t_last = max(e["t"] for e in run_ends + steps)
        wall_all = max(1e-9, t_last - t_first)
        productive_all = max(0.0, sum(e["step_s"] for e in steps
                                      if e["rank"] == r0) - r0_compile_s)
        out["goodput_incl_restarts"] = min(1.0, productive_all / wall_all)
        out["wall_incl_restarts_s"] = wall_all

    # -- MFU ---------------------------------------------------------------
    flops = next((e["flops_per_step"] for e in reversed(programs)
                  if e.get("flops_per_step")), None)
    out["flops_per_step"] = flops
    if peak_flops is None:
        peak_flops = resolve_peak_flops(out["device_kind"])
    out["peak_flops"] = peak_flops
    out["mfu"] = None
    if flops and peak_flops and r0_steps:
        # Steady-state MFU: FLOPs per step over the p50 step time (the mean
        # would let one compile-polluted or paused step poison the number).
        out["mfu"] = round(flops / budget["step_s"]["p50"] / peak_flops, 4)
        step_mfus = [e["mfu"] for e in r0_steps if "mfu" in e]
        if step_mfus:
            out["mfu_p50"] = round(percentile(step_mfus, 50), 4)

    # -- XLA program introspection (tpudist/obs/xla_introspect.py fields
    # riding the cost_analysis compile event) ------------------------------
    xla = None
    from tpudist.obs.xla_introspect import EVENT_FIELDS
    xla_keys = EVENT_FIELDS + ("all_reduce_count", "all_reduce_bytes")
    for e in reversed(events):
        if e["type"] == "compile" and e.get("phase") == "cost_analysis" \
                and any(k in e for k in ("hbm_compiled_bytes",
                                         "collective_ops", "bytes_accessed")):
            xla = {k: e[k] for k in xla_keys if k in e}
            break
    out["xla"] = xla

    # -- kernel dispatch (the two ops/dispatch clients): what --flash and
    # --compress-grads resolved to, on what evidence — the newest decision
    # of each wins ---------------------------------------------------------
    out["attention_dispatch"] = next(
        (e for e in reversed(events) if e["type"] == "attention_dispatch"),
        None)
    out["comm_dispatch"] = next(
        (e for e in reversed(events) if e["type"] == "comm_dispatch"), None)
    # Compression ratio (--compress-grads): the dispatch event's
    # dense-equivalent gradient payload held against the census's ACTUAL
    # per-step collective bytes — the before/after meter for ROADMAP item
    # 2's "shrink what crosses the interconnect".
    cd = out["comm_dispatch"]
    if cd and isinstance(cd.get("dense_bytes"), (int, float)) \
            and cd["dense_bytes"] > 0 and xla \
            and isinstance(xla.get("collective_bytes_per_step"),
                           (int, float)) \
            and xla["collective_bytes_per_step"] > 0:
        ratio = {"dense_bytes": cd["dense_bytes"],
                 "actual_bytes": xla["collective_bytes_per_step"],
                 "payload_ratio": round(
                     cd["dense_bytes"] / xla["collective_bytes_per_step"],
                     3)}
        w = cd.get("world")
        if isinstance(xla.get("collective_link_bytes"), (int, float)) \
                and xla["collective_link_bytes"] > 0 \
                and isinstance(w, (int, float)) and w and w > 1:
            # Dense baseline wire traffic: a ring all-reduce of the f32
            # gradients moves 2(W-1)/W x their bytes.
            dense_link = 2.0 * (w - 1) / w * cd["dense_bytes"]
            ratio["link_bytes"] = xla["collective_link_bytes"]
            ratio["link_ratio"] = round(
                dense_link / xla["collective_link_bytes"], 3)
        out["compression"] = ratio
    else:
        out["compression"] = None

    # -- op-category time attribution (first bite at VERDICT r5 weak #4:
    # where the non-MXU time goes). Roofline lower bounds from the compiled
    # program's FLOPs/bytes against device peaks, held against the measured
    # steady-state device-compute p50: the residual is host/pipeline/non-
    # roofline overhead neither bound explains. ---------------------------
    attr = None
    if xla and xla.get("flops") and peak_flops and budget.get("compute_s"):
        attr = {"mxu_ms_lb": round(xla["flops"] / peak_flops * 1e3, 3)}
        peak_hbm = resolve_peak_hbm(out["device_kind"])
        if xla.get("bytes_accessed") and peak_hbm:
            attr["hbm_ms_lb"] = round(
                xla["bytes_accessed"] / peak_hbm * 1e3, 3)
            attr["peak_hbm_bps"] = peak_hbm
        compute_ms = budget["compute_s"]["p50"] * 1e3
        attr["compute_p50_ms"] = round(compute_ms, 3)
        bound = max(attr["mxu_ms_lb"], attr.get("hbm_ms_lb", 0.0))
        attr["bound"] = ("mxu" if attr["mxu_ms_lb"]
                         >= attr.get("hbm_ms_lb", 0.0) else "hbm")
        attr["residual_ms"] = round(max(0.0, compute_ms - bound), 3)
        cats = {k[4:]: xla[k] for k in xla
                if k.startswith("ops_") and isinstance(xla[k], (int, float))}
        if cats:
            attr["op_counts"] = cats
    out["op_attribution"] = attr

    # -- per-rank straggler view ------------------------------------------
    per_rank = {}
    for rank in out["ranks"]:
        rs = [e for e in steps if e["rank"] == rank]
        if not rs:
            continue
        host = [max(0.0, e["step_s"] - e["compute_s"]) for e in rs]
        per_rank[rank] = {
            "rank": rank, "n": len(rs),
            "step_p50": round(percentile([e["step_s"] for e in rs], 50), 6),
            "host_p50": round(percentile(host, 50), 6),
            "updated_at": rs[-1]["t"], "attempt": rs[-1]["attempt"],
        }
    out["per_rank"] = per_rank
    out["stragglers"] = find_stragglers(
        per_rank, attempt=None, max_age_s=float("inf"))
    return out


def _ms(v: Optional[float]) -> str:
    return f"{v * 1e3:8.1f}" if v is not None else "       -"


def format_report(a: dict, rundir: str = "") -> str:
    L = [f"tpudist run summary — {rundir or '<events>'}"]
    L.append(f"  arch {a['arch'] or '?'} on {a['platform'] or '?'} "
             f"({a['device_kind'] or 'unknown device'}); "
             f"ranks {a['ranks'] or '[]'}; attempts {a['attempts']}; "
             f"{a['n_steps']} step events")
    # goodput budget
    if a.get("goodput") is not None:
        L.append(f"  goodput {a['goodput']:.3f}  "
                 f"(productive {a['productive_s']:.2f}s / "
                 f"wall {a['wall_s']:.2f}s)")
        re = a.get("run_end") or {}
        for name, key in (("init", "init_s"), ("compile", "compile_s"),
                          ("checkpoint", "checkpoint_s"), ("eval", "eval_s")):
            if re.get(key):
                note = ""
                if key == "compile_s" and a.get("compile_cache"):
                    note = f", persistent cache {a['compile_cache']}"
                L.append(f"    {name:<11}{re[key]:9.2f}s "
                         f"({re[key] / max(a['wall_s'], 1e-9):6.1%} of wall"
                         f"{note})")
        if a.get("goodput_incl_restarts") is not None:
            L.append(f"  goodput incl. restarts "
                     f"{a['goodput_incl_restarts']:.3f} "
                     f"(wall {a['wall_incl_restarts_s']:.2f}s across "
                     f"{len(a['attempts'])} attempts)")
    else:
        L.append("  goodput: n/a (no step events)")
    # MFU
    if a.get("mfu") is not None:
        L.append(f"  MFU {a['mfu']:.4f}  (flops/step "
                 f"{a['flops_per_step']:.3e} per device, peak "
                 f"{a['peak_flops']:.3e} FLOP/s)")
        if a["mfu"] > 1.0:
            # Same trap bench.py guards: async dispatch returned at enqueue
            # rather than execution-complete, so step_s under-measured.
            L.append("  WARNING: MFU > 1 is physically impossible — the "
                     "host-side step timing did not capture real device "
                     "execution (async dispatch without backpressure); "
                     "treat the step breakdown as dispatch-side only")
    elif a.get("flops_per_step"):
        L.append(f"  MFU: n/a — no peak FLOP/s known for "
                 f"'{a['device_kind']}' (flops/step "
                 f"{a['flops_per_step']:.3e}; set TPUDIST_PEAK_FLOPS or "
                 f"--peak-flops)")
    else:
        L.append("  MFU: n/a (no compiled-program cost analysis in events)")
    # XLA program introspection (where the HBM and FLOPs go INSIDE the step)
    x = a.get("xla")
    if x:
        from tpudist.obs.xla_introspect import format_section
        info = dict(x)
        # The compile event's only per-op detail is all-reduce (the headline
        # DP-sync op); when the program IS pure all-reduce show it per-op,
        # otherwise format_section's flat-field fallback prints the totals.
        if x.get("all_reduce_count") and \
                x.get("all_reduce_count") == x.get("collective_ops"):
            info["collectives"] = {"all-reduce": {
                "count": x["all_reduce_count"],
                "bytes": x.get("all_reduce_bytes", 0)}}
        lines = format_section(info)
        if lines:
            L.append("  XLA program (per device, compiled train step):")
            L.extend(lines)
    # attention dispatch (which kernel --flash resolved to, on what evidence)
    ad = a.get("attention_dispatch")
    if ad:
        prov = ad["source"]
        if ad["source"] == "cache":
            prov = "cache hit"
        elif ad["source"] == "measured":
            prov = "measured now, cached"
        line = (f"  attention dispatch: {ad['kernel']} attention "
                f"(mode {ad['mode']}, {prov}")
        if isinstance(ad.get("flash_ms"), (int, float)) \
                and isinstance(ad.get("xla_ms"), (int, float)):
            line += (f"; flash {ad['flash_ms']:.3f} ms vs "
                     f"xla {ad['xla_ms']:.3f} ms")
            if isinstance(ad.get("margin"), (int, float)):
                line += f", margin {ad['margin']:.1%}"
        if ad.get("shape_key"):
            line += f"; shape {ad['shape_key']}"
        L.append(line + ")")
    # comm dispatch (which gradient wire format --compress-grads resolved to)
    cd = a.get("comm_dispatch")
    if cd:
        prov = cd["source"]
        if prov == "cache":
            prov = "cache hit"
        elif prov == "measured":
            prov = "measured now, cached"
        line = (f"  comm dispatch: {cd['kernel']} gradient exchange "
                f"(mode {cd['mode']}, {prov}")
        if isinstance(cd.get("int8_ms"), (int, float)) \
                and isinstance(cd.get("dense_ms"), (int, float)):
            line += (f"; int8 {cd['int8_ms']:.3f} ms vs "
                     f"dense {cd['dense_ms']:.3f} ms")
            if isinstance(cd.get("margin"), (int, float)):
                line += f", margin {cd['margin']:.1%}"
        if cd.get("reason"):
            line += f"; {cd['reason']}"
        L.append(line + ")")
    comp = a.get("compression")
    if comp:
        line = (f"  gradient compression: dense-equivalent "
                f"{comp['dense_bytes'] / 2**20:.1f} MiB/step vs "
                f"{comp['actual_bytes'] / 2**20:.1f} MiB actual collective "
                f"payload ({comp['payload_ratio']:.2f}x)")
        if comp.get("link_ratio") is not None:
            line += (f"; est. link traffic "
                     f"{comp['link_bytes'] / 2**20:.1f} MiB "
                     f"({comp['link_ratio']:.2f}x less than the dense "
                     f"ring all-reduce)")
        L.append(line)
    # op-category attribution (where the non-MXU time goes)
    at = a.get("op_attribution")
    if at:
        comp = at["compute_p50_ms"]

        def share(ms: float) -> str:
            return f" ({ms / comp:6.1%} of compute)" if comp > 0 else ""

        L.append("  op-category attribution (steady-state compute p50 "
                 f"{comp:.1f} ms, {at['bound']}-bound):")
        L.append(f"    MXU roofline      {at['mxu_ms_lb']:8.3f} ms lower "
                 f"bound{share(at['mxu_ms_lb'])}")
        if at.get("hbm_ms_lb") is not None:
            L.append(f"    HBM roofline      {at['hbm_ms_lb']:8.3f} ms "
                     f"lower bound{share(at['hbm_ms_lb'])}")
        L.append(f"    unattributed      {at['residual_ms']:8.3f} ms "
                 f"(non-roofline: launch/layout/fusion overhead)")
        cats = at.get("op_counts")
        if cats:
            per = ", ".join(f"{k} x{int(v)}" for k, v in
                            sorted(cats.items(), key=lambda kv: -kv[1])
                            if v)
            L.append(f"    HLO ops by unit:  {per}")
    # step budget
    b = a.get("budget") or {}
    if b.get("step_s"):
        L.append("  step-time budget (rank-0 p50 / p95 ms):")
        rows = [("data wait", "data_s"), ("host→device", "h2d_s"),
                ("device compute", "compute_s"),
                ("metric drain", "drain_s")]
        if b.get("prefetch_s"):
            # Overlapped bucket (device prefetch): staged under compute —
            # in the serial sum it displaces other-host, not data/h2d.
            rows.append(("prefetch (ovl.)", "prefetch_s"))
        if b.get("drain_ovl_s"):
            rows.append(("drain (ovl.)", "drain_ovl_s"))
        rows += [("other host", "other_host_s"), ("total step", "step_s")]
        for name, key in rows:
            p = b.get(key)
            if p:
                L.append(f"    {name:<15}{_ms(p['p50'])} /{_ms(p['p95'])}")
    # serving plane (tpudist/serve/): latency/throughput + cold-start
    sv = a.get("serving")
    if sv:
        head = f"  serving: {sv['n_requests']} requests"
        if sv.get("n_errors"):
            head += f" ({sv['n_errors']} errored)"
        if sv.get("n_batches"):
            head += f" in {sv['n_batches']} bucketed batches"
        if sv.get("occupancy_p50") is not None:
            head += f" (occupancy p50 {sv['occupancy_p50']:.0%})"
        L.append(head)
        if sv.get("latency_p50_ms") is not None:
            line = (f"    latency p50 {sv['latency_p50_ms']:.1f} ms / "
                    f"p99 {sv['latency_p99_ms']:.1f} ms")
            if sv.get("req_per_s") is not None:
                line += f"; {sv['req_per_s']:.1f} req/s"
            L.append(line)
        if sv.get("aot_s") is not None:
            line = (f"    AOT startup: {sv['n_buckets']} bucket programs "
                    f"[{sv.get('buckets', '?')}] in {sv['aot_s']:.2f}s")
            if sv.get("aot_compile_s") is not None:
                line += f" (XLA compile {sv['aot_compile_s']:.2f}s)"
            if sv.get("cache"):
                line += f", persistent cache {sv['cache']}"
            L.append(line)
        if sv.get("aot_compiles"):
            extra = sv.get("non_aot_compiles") or 0
            L.append(f"    compiles: {sv['aot_compiles']} AOT bucket "
                     f"programs, {extra} other — "
                     + ("ZERO steady-state recompiles" if extra == 0
                        else "(non-AOT compiles present: mixed "
                             "train+serve run dir, or a recompile)"))
    # doctor plane: interventions + SDC probe census (docs/DOCTOR.md)
    dc = a.get("doctor")
    if dc:
        acts = ", ".join(f"{k} x{v}" for k, v in sorted(dc["by_action"].items()))
        L.append(f"  doctor: {dc['interventions']} intervention(s)"
                 + (f" ({acts})" if acts else "")
                 + (f"; SDC probes {dc['probes']} "
                    f"({dc['divergent_probes']} divergent)"
                    if dc["probes"] else ""))
        for e in dc["events"][:12]:
            act = e.get("action")
            if act == "skip_step":
                what = "non-finite step — update zeroed in-program"
            elif act == "spike":
                what = (f"loss spike {e.get('loss', '?')} vs EWMA "
                        f"{e.get('mean', '?')} (+{e.get('sigmas', '?')}σ)")
            elif act == "rollback":
                what = (f"{e.get('reason', 'rollback')} → re-entered epoch "
                        f"{e.get('to_epoch', '?')}")
                if e.get("window_start") is not None:
                    what += (f", replay minus samples "
                             f"[{e['window_start']}, {e['window_end']})")
            elif act == "sdc_divergence":
                what = ("replicated-state digest divergence"
                        + (" (2-replica tie — unattributable)"
                           if e.get("tie") else
                           f" (rank(s) {e.get('divergent_ranks', '?')})"))
            elif act == "evict":
                what = (f"rank {e.get('divergent_rank', '?')} "
                        f"self-quarantined after "
                        f"{e.get('windows', '?')} divergent probes")
            else:
                what = str(act)
            L.append(f"    [doctor] rank {e['rank']} step "
                     f"{e.get('step', '?')}: {what}")
        if len(dc["events"]) > 12:
            L.append(f"    ... {len(dc['events']) - 12} more")
    # perf-CI console: unattended bench-matrix runs (tpudist-perfci)
    pc = a.get("perfci")
    if pc:
        L.append(f"  perfci: {pc['runs']} run(s), "
                 f"{pc['regressions']} regression(s) flagged")
        for e in pc["events"][-6:]:
            L.append(f"    [perfci] {e.get('platform', '?')}: "
                     f"{e.get('stages_ok', '?')}/{e.get('stages_total', '?')}"
                     f" stages ok ({e.get('stages_failed', 0)} failed, "
                     f"{e.get('stages_skipped', 0)} skipped), "
                     f"{e.get('series_gated', 0)} series gated, "
                     f"{e.get('regressions', 0)} regression(s), "
                     f"exit {e.get('exit', '?')}")
        if len(pc["events"]) > 6:
            L.append(f"    ... {len(pc['events']) - 6} earlier run(s)")
    # blackbox plane: incident triggers + the bundles on disk
    # (docs/INCIDENTS.md). Bundles are read from the run dir here, not in
    # analyze(), which stays pure on events.
    inc = a.get("incidents")
    bundles = []
    if rundir:
        try:
            from tpudist.blackbox import list_incidents
            bundles = list_incidents(rundir)
        except Exception:
            bundles = []
    if inc or bundles:
        trig = ", ".join(f"{k} x{v}" for k, v in
                         sorted((inc or {}).get("by_trigger", {}).items()))
        L.append(f"  incidents: {(inc or {}).get('triggers', 0)} trigger(s)"
                 + (f" ({trig})" if trig else "")
                 + (f", {inc['captures']} deep capture(s), "
                    f"{inc['suppressed']} cooldown-suppressed"
                    if inc else "")
                 + f"; {len(bundles)} bundle(s) on disk")
        for m in bundles[-6:]:
            dumps = m.get("dumps") or []
            ranks = sorted({d.get("rank") for d in dumps
                            if d.get("rank") is not None})
            arts = len(m.get("artifacts") or [])
            L.append(f"    [incident] {m.get('id', '?')}: trigger "
                     f"{m.get('trigger', '?')}, suspect rank "
                     f"{m.get('suspect_rank', '?')}"
                     + (f", dumps from rank(s) {ranks}" if ranks else "")
                     + f", {arts} artifact(s)"
                     + (f", {len(m.get('captures') or [])} capture dir(s)"
                        if m.get("captures") else ""))
        if len(bundles) > 6:
            L.append(f"    ... {len(bundles) - 6} earlier bundle(s)")
        L.append("    (inspect: tpudist-incident report <rundir> [id])")
    # per-rank
    if len(a.get("per_rank", {})) > 1:
        flagged = {s["straggler_rank"] for s in a["stragglers"]}
        L.append("  per-rank (n steps, step p50 ms, host p50 ms):")
        for rank, r in sorted(a["per_rank"].items()):
            mark = "  ← STRAGGLER" if rank in flagged else ""
            L.append(f"    rank {rank}: n={r['n']:<5} "
                     f"step {_ms(r['step_p50']).strip()} ms  "
                     f"host {_ms(r['host_p50']).strip()} ms{mark}")
    # topology timeline (elastic plane): only interesting once a reform or
    # cross-world reshard happened, or the job launched more than once.
    topo = a.get("topology") or []
    if any(t["kind"] != "launch" for t in topo) or len(topo) > 1:
        L.append("  topology timeline:")
        t0 = topo[0]["t"] if topo else 0.0
        for t in topo:
            dt = f"+{t['t'] - t0:7.1f}s"
            if t["kind"] == "launch":
                mesh = (f", mesh {t['mesh']}"
                        if t.get("mesh") and t["mesh"] != "default" else "")
                L.append(f"    {dt} [launch]  attempt {t['attempt']}: "
                         f"world {t['world']}{mesh}")
            elif t["kind"] == "reform":
                lost = f" (lost rank(s) {t['lost_ranks']})" \
                    if t.get("lost_ranks") else ""
                mesh = ""
                if t.get("from_mesh") and t["from_mesh"] != "default":
                    act = f" {t['mesh_action']}" if t.get("mesh_action") \
                        else ""
                    mesh = (f", mesh {t['from_mesh']} -> "
                            f"{t['to_mesh']}{act}")
                L.append(f"    {dt} [reform]  world {t['from_world']} -> "
                         f"{t['to_world']}{mesh}{lost}")
            elif t["kind"] == "scale_up":
                L.append(f"    {dt} [scale]   world {t['from_world']} -> "
                         f"{t['to_world']} (serving replicas scaled up "
                         f"under load)")
            elif t["kind"] == "evict":
                L.append(f"    {dt} [evict]   rank {t['rank']}: persistent "
                         f"straggler drained after {t.get('windows', '?')} "
                         f"flagged windows")
            else:
                L.append(f"    {dt} [reshard] rank {t['rank']}: checkpoint "
                         f"world {t['from_world']} -> {t['to_world']}")
    # fault timeline
    if a["faults"]:
        L.append(f"  faults/restarts ({len(a['faults'])}):")
        for e in a["faults"][:20]:
            if e["type"] == "restart":
                what = f"relaunch (prev exit {e.get('prev_exit', '?')})"
            elif e["type"] == "straggler":
                # straggler_rank can be 0 — no falsy `or` chains here.
                what = (f"rank {e['straggler_rank']} at "
                        f"{e.get('factor', '?')}x the fleet median")
            elif e["type"] == "eviction":
                what = (f"rank {e['straggler_rank']} evicted "
                        f"(straggler {e.get('windows', '?')} consecutive "
                        f"windows)")
            elif e["type"] == "collective_deadline":
                what = (f"gang wedged (no heartbeat progress; suspect "
                        f"rank {e['suspect_rank']} stale "
                        f"{e.get('max_age_s', '?')}s) — draining")
            else:
                what = e.get("point") or e.get("classification") \
                    or e.get("signal") or e["type"]
            # rank_exit/straggler events come from the LAUNCHER stream
            # (envelope rank -1); the rank they are ABOUT is in their own
            # field.
            rank = e.get("exit_rank",
                         e.get("straggler_rank",
                               e.get("suspect_rank", e["rank"])))
            L.append(f"    [{e['type']}] rank {rank} attempt "
                     f"{e['attempt']}: {what}")
        if len(a["faults"]) > 20:
            L.append(f"    ... {len(a['faults']) - 20} more")
    return "\n".join(L)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Summarize a tpudist run's telemetry "
                    "(goodput, MFU budget, stragglers)")
    p.add_argument("rundir", help="run output dir containing events.*.jsonl")
    p.add_argument("--peak-flops", type=float, default=None,
                   dest="peak_flops",
                   help="peak FLOP/s for the MFU denominator (overrides the "
                        "device table and TPUDIST_PEAK_FLOPS)")
    p.add_argument("--json", action="store_true",
                   help="emit the analysis as JSON instead of the report "
                        "(goodput, MFU, percentiles, stragglers, XLA "
                        "introspection) for CI/regression-gate consumption")
    p.add_argument("--strict", action="store_true",
                   help="fail on any malformed event line")
    p.add_argument("--trace", default="", metavar="OUT.json",
                   help="also merge every rank's events (launcher + rotated "
                        "segments included) into a Chrome/Perfetto "
                        "trace-event JSON at this path — open it at "
                        "ui.perfetto.dev")
    p.add_argument("--no-align", action="store_true", dest="no_align",
                   help="with --trace: keep raw host clocks instead of "
                        "aligning each rank's run_start anchor")
    args = p.parse_args(argv)

    events = load_events(args.rundir, strict=args.strict)
    if not events:
        print(f"no events.*.jsonl found in {args.rundir} "
              f"(run with --telemetry)", file=sys.stderr)
        return 2
    if args.trace:
        from tpudist.obs.trace import export_trace_file
        obj = export_trace_file(events, args.trace, align=not args.no_align)
        print(f"[summarize] wrote {len(obj['traceEvents'])} trace events "
              f"to {args.trace} (open at ui.perfetto.dev)", file=sys.stderr)
    a = analyze(events, peak_flops=args.peak_flops)
    if args.json:
        print(json.dumps(a, indent=1, default=str))
    else:
        print(format_report(a, args.rundir))
    return 0


if __name__ == "__main__":
    sys.exit(main())
