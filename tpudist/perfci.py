"""Unattended bench-matrix runner + regression gate
(``python -m tpudist.perfci`` / ``tpudist-perfci``).

ROADMAP item 5's promotion of ``tpudist-regress``: the bench matrix lives
in a declarative manifest
(``benchmarks/perfci.json``) and this runner executes it end to end with
nobody watching:

- **crash isolation** — every stage runs as its own subprocess with its
  own timeout; a crashing or hanging bench marks its stage failed and the
  matrix moves on (an unattended runner that dies on stage 2 of 9 wasted
  the capture window);
- **one append path** — fresh rows land in the bench history through
  ``regress.append_history`` exactly once each: self-appending benches
  (the repo norm — they decide platform-honesty themselves) are detected
  by the history file's growth and never double-appended; stages that opt
  in (``append_stdout_rows``) have their stdout JSON rows appended by the
  runner with one shared ``measured_at`` stamp;
- **every series gated** — each stage's produced series (and every
  ``series`` the manifest says it must produce) goes through
  ``regress.analyze_history``, the same trailing-median math the CLI gate
  and the dashboard use;
- **machine-readable outcome** — ``perfci_report.json`` (overwritten per
  run, bounded by design) plus a ``perfci_run`` telemetry event, and the
  ``tpudist-check`` exit contract: 0 = clean, 1 = gate regressions,
  2 = usage/operational error (bad manifest, stage crash/timeout/missing
  series — operational failure outranks gate findings, the same way
  check's unparseable-file rule outranks its findings).

``--dashboard out.html`` renders the post-run trend dashboard
(``obs.dashboard``) as a static artifact. ``--stages a,b`` selects a
subset. Import-light: no jax in the runner — a chip belongs to one process
at a time, so the runner stays off jax and every stage is the only
process on the chip while it runs (stages check their own platform; ours
comes from env or a one-shot subprocess that exits before the first
stage starts).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
from typing import Optional

from tpudist import regress

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_MANIFEST = os.path.join(_REPO, "benchmarks", "perfci.json")
DEFAULT_REPORT = os.path.join(_REPO, "benchmarks", "results",
                              "perfci_report.json")
ENV_PLATFORM = "TPUDIST_PERFCI_PLATFORM"


class ManifestError(ValueError):
    """Invalid manifest — a usage error (exit 2), not a stage failure."""


class PlatformError(RuntimeError):
    """The platform probe failed — an operational error (exit 2): a matrix
    whose platform guards were evaluated against a guess is worthless."""


def detect_platform() -> str:
    """The backend stages will land on: the ``TPUDIST_PERFCI_PLATFORM``
    override wins (tests, forced matrices), else ``JAX_PLATFORMS``'s first
    entry, else a one-shot subprocess probe (the runner itself never
    imports jax; the probe exits, releasing the chip, before any stage
    starts). A probe that fails raises ``PlatformError`` — never a silent
    ``cpu``."""
    env = os.environ.get(ENV_PLATFORM, "").strip()
    if env:
        return env
    jp = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if jp:
        return jp
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True, text=True, timeout=180)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise PlatformError(f"jax platform probe did not run: {e!r}")
    if out.returncode != 0 or not out.stdout.strip():
        raise PlatformError(
            f"jax platform probe failed (exit {out.returncode}): "
            f"{(out.stderr or out.stdout).strip()[-500:]}")
    return out.stdout.strip().splitlines()[-1]


def load_manifest(path: str) -> dict:
    """Parse + validate; raises ManifestError on anything a typo could
    cause — an unattended runner must fail loudly at arm time, not
    silently skip half its matrix at capture time."""
    try:
        with open(path, encoding="utf-8") as f:
            man = json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read manifest {path}: {e}")
    except ValueError as e:
        raise ManifestError(f"manifest {path} is not valid JSON: {e}")
    if not isinstance(man, dict) or not isinstance(man.get("stages"), list) \
            or not man["stages"]:
        raise ManifestError(f"manifest {path} needs a non-empty 'stages' "
                            f"list")
    defaults = man.get("defaults", {})
    if not isinstance(defaults, dict):
        raise ManifestError("'defaults' must be an object")
    seen = set()
    for i, st in enumerate(man["stages"]):
        if not isinstance(st, dict) or not st.get("name"):
            raise ManifestError(f"stage #{i} needs a 'name'")
        name = st["name"]
        if name in seen:
            raise ManifestError(f"duplicate stage name '{name}'")
        seen.add(name)
        cmds = stage_cmds(st)
        if not cmds:
            raise ManifestError(f"stage '{name}' needs 'module', 'cmd' or "
                                f"'cmds'")
        for c in cmds:
            if not (isinstance(c, list)
                    and all(isinstance(t, str) for t in c) and c):
                raise ManifestError(f"stage '{name}': every command must "
                                    f"be a non-empty list of strings")
        t = st.get("timeout_s", defaults.get("timeout_s", 600))
        if not (isinstance(t, (int, float)) and t > 0):
            raise ManifestError(f"stage '{name}': timeout_s must be > 0")
        for key in ("series", "platforms"):
            v = st.get(key, [])
            if not (isinstance(v, list)
                    and all(isinstance(s, str) for s in v)):
                raise ManifestError(f"stage '{name}': '{key}' must be a "
                                    f"list of strings")
    return man


def stage_cmds(st: dict) -> list[list]:
    """A stage's argv sequence: ``module``+``args`` sugar, a raw ``cmd``,
    or a ``cmds`` list (run in order, first failure stops the stage)."""
    if st.get("module"):
        return [[sys.executable, "-m", st["module"]]
                + [str(a) for a in st.get("args", [])]]
    if st.get("cmd"):
        return [list(st["cmd"])]
    return [list(c) for c in st.get("cmds", [])]


def _history_lines(path: str) -> list[str]:
    try:
        with open(path, encoding="utf-8") as f:
            return f.read().splitlines()
    except OSError:
        return []


def _stdout_rows(text: str) -> list[dict]:
    """Bench-convention rows from a stage's stdout: one JSON object per
    line with a ``metric`` and a numeric ``value`` (non-row lines
    ignored)."""
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if isinstance(row, dict) and row.get("metric") \
                and isinstance(row.get("value"), (int, float)):
            rows.append(row)
    return rows


def _row_key(row: dict) -> tuple:
    return (row.get("metric"), row.get("per_device_batch"),
            row.get("value"))


def run_stage(st: dict, defaults: dict, platform: str,
              history: str) -> dict:
    """Execute one stage with crash isolation; returns its report entry."""
    name = st["name"]
    out: dict = {"name": name, "status": "ok", "rc": 0, "duration_s": 0.0,
                 "rows_self_appended": 0, "rows_runner_appended": 0,
                 "series": []}
    plats = st.get("platforms") or []
    if plats and platform not in plats:
        out["status"] = "skipped_platform"
        out["detail"] = f"platform {platform} not in {plats}"
        return out
    corpus = st.get("corpus")
    if corpus and not os.path.isdir(corpus):
        out["status"] = "skipped_corpus"
        out["detail"] = f"corpus dir {corpus} absent"
        return out
    timeout = float(st.get("timeout_s", defaults.get("timeout_s", 600)))
    env = dict(os.environ)
    env.update({k: str(v) for k, v in defaults.get("env", {}).items()})
    env.update({k: str(v) for k, v in st.get("env", {}).items()})
    before = _history_lines(history)
    t0 = time.monotonic()
    stdout_all: list[str] = []
    for cmd in stage_cmds(st):
        try:
            proc = subprocess.run(cmd, cwd=_REPO, env=env, timeout=timeout,
                                  capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            out["status"], out["rc"] = "timeout", -1
            out["detail"] = f"killed after {timeout:g}s: {' '.join(cmd)}"
            break
        except OSError as e:
            out["status"], out["rc"] = "failed", -1
            out["detail"] = f"spawn failed: {e}"
            break
        stdout_all.append(proc.stdout or "")
        if proc.returncode != 0:
            out["status"], out["rc"] = "failed", proc.returncode
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            out["detail"] = " | ".join(tail)[:500]
            break
    out["duration_s"] = round(time.monotonic() - t0, 3)

    # One append path, once per fresh row: rows the stage appended itself
    # (history growth) are taken as-is; stdout rows are appended by the
    # runner only when the stage opts in AND the stage didn't already
    # append that same row.
    after = _history_lines(history)
    self_rows = []
    for line in after[len(before):]:
        try:
            r = json.loads(line)
        except ValueError:
            continue
        if isinstance(r, dict):
            self_rows.append(r)
    out["rows_self_appended"] = len(self_rows)
    fresh = list(self_rows)
    if st.get("append_stdout_rows") and out["status"] in ("ok", "failed"):
        # A failed stage may still have produced honest rows before dying
        # — append what it printed; the gate decides what they mean.
        seen = {_row_key(r) for r in self_rows}
        now = datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
        for row in _stdout_rows("\n".join(stdout_all)):
            if _row_key(row) in seen:
                continue
            regress.append_history({**row, "measured_at": now},
                                   path=history)
            out["rows_runner_appended"] += 1
            fresh.append(row)
    produced = []
    for r in fresh:
        if r.get("metric") and r["metric"] not in produced:
            produced.append(r["metric"])
    out["series"] = produced
    expected = [s.format(platform=platform) for s in st.get("series", [])]
    missing = [s for s in expected if s not in produced]
    if missing and out["status"] == "ok":
        # An expected series that never appeared is an operational failure
        # — the silent no-op an unattended matrix must not absorb.
        out["status"] = "missing_series"
        out["detail"] = f"expected series never produced: {missing}"
    out["missing_series"] = missing
    return out


def gate_series(stage_reports: list[dict], history: str, window: int,
                threshold: float, min_history: int) -> list[dict]:
    """The regress gate on every series this run produced, through the
    exact math the CLI/dashboard use."""
    rows = regress.load_history(history)
    verdicts = []
    gated = set()
    for st in stage_reports:
        for metric in st.get("series", []):
            if metric in gated:
                continue
            gated.add(metric)
            v = regress.analyze_history(rows, metric=metric, window=window,
                                        threshold=threshold,
                                        min_history=min_history)
            v["stage"] = st["name"]
            verdicts.append(v)
    return verdicts


def _emit_event(report: dict, report_path: str) -> None:
    """One schema-valid ``perfci_run`` telemetry event beside the report
    (``events.perfci.jsonl``) — the same flight-recorder format every
    other plane uses, so ``summarize`` can show perfci runs in a run dir
    and TELEM01/03 hold the docs to it. Best-effort: a telemetry problem
    must not change the gate verdict."""
    try:
        from tpudist.telemetry import Telemetry
        s = report["summary"]
        tel = Telemetry(os.path.dirname(report_path) or ".", rank=-1,
                        name="perfci", heartbeat=False, max_mb=8.0)
        tel.emit("perfci_run", manifest=report["manifest"],
                 platform=report["platform"],
                 stages_total=s["stages_total"],
                 stages_ok=s["stages_ok"],
                 stages_failed=s["stages_failed"],
                 stages_skipped=s["stages_skipped"],
                 rows_appended=s["rows_appended"],
                 series_gated=s["series_gated"],
                 regressions=s["regressions"],
                 duration_s=report["duration_s"], exit=report["exit"])
        if s["regressions"] or s["stages_failed"]:
            # A failed gate is an anomaly like any other: a blackbox
            # `gate`-class incident event marks the perf-CI timeline in
            # the same stream the bundler/summarize/fleet gauges read.
            # No live job to capture — event only, captured=0.
            tel.emit("incident", trigger="gate", suspect_rank=-1,
                     captured=0,
                     detail=f"{s['regressions']} regression(s), "
                            f"{s['stages_failed']} failed stage(s)")
    except Exception as e:
        print(f"[perfci] telemetry event failed (non-fatal): {e!r}",
              file=sys.stderr)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tpudist-perfci",
        description="Run the declarative bench matrix unattended: per-"
                    "stage timeouts + crash isolation, history appends "
                    "through regress.append_history, the trailing-median "
                    "gate on every produced series, perfci_report.json. "
                    "Exit 0 clean / 1 regression / 2 usage or stage "
                    "error.")
    p.add_argument("--manifest", default=DEFAULT_MANIFEST,
                   help="bench-matrix manifest (benchmarks/perfci.json)")
    p.add_argument("--stages", default=None,
                   help="comma-separated subset to run (default: all)")
    p.add_argument("--history", default=None,
                   help="bench_history.jsonl (env TPUDIST_BENCH_HISTORY)")
    p.add_argument("--report", default=DEFAULT_REPORT,
                   help="machine-readable run report path (overwritten "
                        "per run)")
    p.add_argument("--dashboard", default=None, metavar="OUT_HTML",
                   help="render the post-run trend dashboard to this file")
    p.add_argument("--platform", default=None,
                   help="override platform detection for manifest guards")
    p.add_argument("--window", type=int, default=5)
    p.add_argument("--threshold", type=float, default=0.10)
    p.add_argument("--min-history", type=int, default=1,
                   dest="min_history")
    p.add_argument("--dry-run", action="store_true",
                   help="validate the manifest and print the plan, run "
                        "nothing")
    args = p.parse_args(argv)

    try:
        man = load_manifest(args.manifest)
    except ManifestError as e:
        print(f"[perfci] {e}", file=sys.stderr)
        return 2
    stages = man["stages"]
    if args.stages:
        want = [s.strip() for s in args.stages.split(",") if s.strip()]
        known = {st["name"] for st in stages}
        unknown = [w for w in want if w not in known]
        if unknown:
            print(f"[perfci] unknown stage(s) {unknown} — manifest has "
                  f"{sorted(known)}", file=sys.stderr)
            return 2
        stages = [st for st in stages if st["name"] in want]
    try:
        platform = args.platform or detect_platform()
    except PlatformError as e:
        print(f"[perfci] {e}", file=sys.stderr)
        return 2
    history = args.history or regress.history_path()

    if args.dry_run:
        print(f"[perfci] manifest {args.manifest} OK: {len(stages)} "
              f"stage(s), platform={platform}, history={history}")
        for st in stages:
            guard = f" platforms={st['platforms']}" \
                if st.get("platforms") else ""
            print(f"[perfci]   {st['name']}: {len(stage_cmds(st))} cmd(s), "
                  f"timeout {st.get('timeout_s', man.get('defaults', {}).get('timeout_s', 600))}s"
                  f"{guard}")
        return 0

    t0 = time.monotonic()
    reports = []
    for st in stages:
        print(f"[perfci] stage {st['name']} ...", file=sys.stderr,
              flush=True)
        try:
            rep = run_stage(st, man.get("defaults", {}), platform, history)
        except Exception as e:            # crash isolation, runner side
            rep = {"name": st["name"], "status": "failed", "rc": -1,
                   "duration_s": 0.0, "series": [],
                   "rows_self_appended": 0, "rows_runner_appended": 0,
                   "detail": f"runner error: {e!r}"}
        reports.append(rep)
        rows = rep["rows_self_appended"] + rep["rows_runner_appended"]
        print(f"[perfci] stage {rep['name']}: {rep['status']} "
              f"({rep['duration_s']:.1f}s, {rows} fresh row(s))"
              + (f" — {rep['detail']}" if rep.get("detail") else ""),
              file=sys.stderr, flush=True)

    verdicts = gate_series(reports, history, args.window, args.threshold,
                           args.min_history)
    for v in verdicts:
        print(regress.format_verdict(v), flush=True)

    ok_states = ("ok",)
    skip_states = ("skipped_platform", "skipped_corpus")
    n_ok = sum(r["status"] in ok_states for r in reports)
    n_skip = sum(r["status"] in skip_states for r in reports)
    n_fail = len(reports) - n_ok - n_skip
    n_reg = sum(v.get("status") == "regression" for v in verdicts)
    # check.py's contract: operational failure (its unparseable files, our
    # failed/timed-out/silent stages) outranks gate findings.
    rc = 2 if n_fail else (1 if n_reg else 0)
    report = {
        "manifest": os.path.abspath(args.manifest),
        "platform": platform,
        "history": os.path.abspath(history),
        "duration_s": round(time.monotonic() - t0, 3),
        "stages": reports,
        "gates": verdicts,
        "summary": {"stages_total": len(reports), "stages_ok": n_ok,
                    "stages_failed": n_fail, "stages_skipped": n_skip,
                    "series_gated": len(verdicts), "regressions": n_reg,
                    "rows_appended": sum(
                        r["rows_self_appended"] + r["rows_runner_appended"]
                        for r in reports)},
        "exit": rc,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                exist_ok=True)
    with open(args.report, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    _emit_event(report, os.path.abspath(args.report))
    if args.dashboard:
        from tpudist.obs import dashboard
        path = dashboard.write_static(args.dashboard, history=history,
                                      window=args.window,
                                      threshold=args.threshold)
        print(f"[perfci] dashboard -> {path} "
              f"({os.path.getsize(path)} bytes)", file=sys.stderr)
    s = report["summary"]
    print(f"[perfci] {s['stages_ok']}/{s['stages_total']} stage(s) ok "
          f"({s['stages_failed']} failed, {s['stages_skipped']} skipped) · "
          f"{s['series_gated']} series gated · {s['regressions']} "
          f"regression(s) · exit {rc}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
