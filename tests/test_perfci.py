"""The observability console (ISSUE 17): fleet time-series recorder
(``tpudist/obs/tsdb.py``), trend dashboard (``tpudist/obs/dashboard.py``),
and the unattended bench-matrix runner (``tpudist-perfci``).

Tiers (all marked ``perfci``; run standalone with ``-m perfci``):

- unit: the tsdb sampling math pinned numerically against a synthetic
  gauge/heartbeat timeline (median/max/mean/sum aggregation, stale-attempt
  beat filtering), rotation under a tiny byte cap, the pure ``query``
  window/name semantics, dashboard HTML goldens over a fixed history
  fixture (gate-band data attributes drawn from the SAME
  ``regress.analyze_history`` math the CLI gate uses, regression flags,
  the zero-external-dependency property), manifest validation;
- integration: ``tpudist-perfci`` end to end on tiny CPU matrices — the
  whole exit contract (0 clean / 1 regression / 2 operational), crash
  isolation around a deliberately dying stage, platform/corpus guards,
  self-append vs runner-append dedup, the ``perfci_run`` telemetry event
  (schema-valid, visible to ``summarize``), call-time
  ``TPUDIST_BENCH_HISTORY`` resolution (the regress import-snapshot fix);
- e2e (acceptance): a real 2-child ``tpudist.launch --metrics-port 0``
  serves ``/dashboard`` with live tsdb panels while recording
  ``fleet_ts.0.jsonl`` on the supervision poll, and
  ``tools/perfci_smoke.sh`` chains dry-run → matrix → gate → dashboard.
"""

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from tpudist import perfci, regress, telemetry
from tpudist.obs import dashboard, tsdb

pytestmark = pytest.mark.perfci

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_telemetry_globals():
    telemetry.set_current(None)
    telemetry.clear_pending()
    yield
    telemetry.set_current(None)
    telemetry.clear_pending()


# -- unit: tsdb sampling math -------------------------------------------------

class _FakeFleet:
    """gauges()-shaped stand-in: exactly what FleetMetrics.gauges returns."""

    def __init__(self, g):
        self._g = g

    def gauges(self):
        return dict(self._g)


_GAUGES = {
    "world": 4, "attempt": 1, "restarts": 2, "reforms": 1, "evictions": 0,
    "collective_deadlines": 0, "rank_exits": 3, "stragglers": 1,
    "rank_samples": {
        0: {"steps": 10, "goodput": 0.8, "mfu": 0.4, "faults": 1,
            "queue_depth": 2, "serve_p99": 0.5},
        1: {"steps": 14, "goodput": 0.6, "mfu": 0.2, "faults": 0,
            "queue_depth": 1, "serve_p99": 0.7},
    },
}

_BEATS = {
    0: {"attempt": 1, "step_p50": 0.10, "step_p95": 0.2, "host_p50": 0.01,
        "updated_at": 99.0},
    1: {"attempt": 1, "step_p50": 0.30, "step_p95": 0.4, "host_p50": 0.03,
        "updated_at": 98.0},
    # a previous attempt's leftover heartbeat must not pollute the sample
    2: {"attempt": 0, "step_p50": 9.0, "step_p95": 9.0, "host_p50": 9.0,
        "updated_at": 0.0},
}


def test_fleet_row_math_pinned():
    """Every aggregation direction pinned numerically: median across ranks
    for p50s, max for p95/age/serve tails, sum for counters, mean for
    goodput/MFU — and stale-attempt beats excluded."""
    row = tsdb.fleet_row(_FakeFleet(_GAUGES), _BEATS, now=100.0)
    assert row["t"] == 100.0 and row["attempt"] == 1
    assert row["world"] == 4 and row["restarts"] == 2
    assert row["rank_exits"] == 3 and row["stragglers"] == 1
    assert row["alive"] == 2                       # rank 2 is attempt 0
    assert row["step_p50_s"] == pytest.approx(0.20)   # median(0.1, 0.3)
    assert row["step_p95_s"] == pytest.approx(0.40)   # max
    assert row["host_p50_s"] == pytest.approx(0.02)
    assert row["heartbeat_age_s"] == pytest.approx(2.0)  # max(1.0, 2.0)
    assert row["steps"] == pytest.approx(24)          # sum
    assert row["goodput"] == pytest.approx(0.7)       # mean
    assert row["mfu"] == pytest.approx(0.3)
    assert row["faults"] == pytest.approx(1)
    assert row["queue_depth"] == pytest.approx(3)
    assert row["serve_p99_s"] == pytest.approx(0.7)   # max across replicas
    # every emitted series name is in the declared field set
    assert all(k in tsdb.SERIES_FIELDS for k in row
               if k not in ("t", "attempt"))


def test_fleet_row_degenerate_inputs():
    """No fleet, no beats: still a valid row (alive 0). Beats without an
    attempt stamp count as current-attempt."""
    row = tsdb.fleet_row(None, None, attempt=3, now=5.0)
    assert row == {"t": 5.0, "attempt": 3, "alive": 0}
    row = tsdb.fleet_row(None, {0: {"step_p50": 0.5, "updated_at": 4.0}},
                         attempt=0, now=5.0)
    assert row["alive"] == 1
    assert row["step_p50_s"] == pytest.approx(0.5)
    assert row["heartbeat_age_s"] == pytest.approx(1.0)


def test_recorder_rotation_and_cap(tmp_path):
    """The telemetry --telemetry-max-mb convention exactly: past the cap
    the live file rolls to fleet_ts.<n>.1.jsonl (replacing the previous
    rollover), disk stays bounded at ~2x, newest rows win."""
    cap_mb = 0.0005                                 # ~524 bytes
    rec = tsdb.FleetSeriesRecorder(str(tmp_path), attempt=0, max_mb=cap_mb)
    fleet = _FakeFleet(_GAUGES)
    for i in range(40):
        assert rec.sample(fleet, _BEATS, now=1000.0 + i) is not None
    rec.close()
    live = tsdb.ts_path(str(tmp_path), 0)
    rot = tsdb.rotated_path(live)
    assert os.path.exists(live) and os.path.exists(rot)
    # each segment is bounded by cap + one row (rotation fires on the
    # write that crosses the cap), so disk stays ~2x the cap as documented
    cap = int(cap_mb * 2**20)
    row_len = len(json.dumps(tsdb.fleet_row(fleet, _BEATS, now=1000.0))) + 1
    assert os.path.getsize(live) <= cap + row_len
    assert os.path.getsize(rot) <= cap + row_len
    rows = tsdb.load_rows(live)
    assert 0 < len(rows) < 40                       # oldest rows rotated out
    ts = [r["t"] for r in rows]
    assert ts == sorted(ts) and ts[-1] == 1039.0    # newest survives
    # a torn final line (recorder killed mid-write) must not break readers
    with open(live, "a") as f:
        f.write('{"t": 99')
    assert tsdb.load_rows(live) == rows


def test_recorder_throttle_and_close(tmp_path):
    rec = tsdb.FleetSeriesRecorder(str(tmp_path), attempt=0,
                                   min_interval_s=10.0)
    assert rec.sample(None, None, now=100.0) is not None
    assert rec.sample(None, None, now=105.0) is None      # throttled
    assert rec.sample(None, None, now=111.0) is not None
    rec.close()
    assert rec.sample(None, None, now=200.0) is None      # closed


def test_query_window_and_names():
    rows = [{"t": float(i), "mfu": 0.1 * i, "alive": 2} for i in range(10)]
    rows[3]["mfu"] = "not-a-number"                 # dropped per-series
    q = tsdb.query(rows, window=4.5, names=["mfu"])
    assert list(q) == ["mfu"]
    # trailing window anchors on the NEWEST row's t (9 - 4.5), no wall clock
    assert [t for t, _ in q["mfu"]] == [5.0, 6.0, 7.0, 8.0, 9.0]
    assert q["mfu"][-1] == (9.0, pytest.approx(0.9))
    # default names: every SERIES_FIELDS key present, declared order
    assert list(tsdb.query(rows)) == ["alive", "mfu"]
    assert tsdb.query([]) == {}


def test_latest_path_picks_highest_attempt(tmp_path):
    assert tsdb.latest_path(str(tmp_path)) is None
    for name in ("fleet_ts.0.jsonl", "fleet_ts.2.jsonl",
                 "fleet_ts.2.1.jsonl"):              # rotated segment: not it
        (tmp_path / name).write_text('{"t": 1.0}\n')
    assert tsdb.latest_path(str(tmp_path)) == str(tmp_path
                                                  / "fleet_ts.2.jsonl")


# -- unit: dashboard HTML -----------------------------------------------------

def _history_fixture():
    rows = [{"metric": "a_ips", "value": float(v), "unit": "images/sec",
             "per_device_batch": 128}
            for v in (1000, 1010, 990, 1005, 995)]
    rows.append({"metric": "a_ips", "value": 700.0, "unit": "images/sec",
                 "per_device_batch": 128})           # 30% down: regression
    rows += [{"metric": "b_ms", "value": v, "unit": "ms"}
             for v in (10.0, 10.2, 9.9, 10.1)]       # unchanged: pass
    return rows


def test_dashboard_history_golden():
    """Panel per series; the gate band is the trailing median ±threshold
    from the SAME analyze_history math the CLI uses; the regressed series
    is flagged; the footer carries machine-readable totals."""
    doc = dashboard.render(history_rows=_history_fixture())
    assert 'data-series="2"' in doc and 'data-regressions="1"' in doc
    # a_ips: prior median 1000 → band 900–1100, newest 700 trips it
    m = re.search(r'<div class="panel regression" ([^>]*)>', doc)
    assert m, doc[-800:]
    attrs = m.group(1)
    assert 'data-metric="a_ips"' in attrs and 'data-pdb="128"' in attrs
    assert 'data-baseline="1000"' in attrs
    assert 'data-band-lo="900"' in attrs and 'data-band-hi="1100"' in attrs
    assert "REGRESSION" in doc
    assert 'data-metric="b_ms"' in doc and 'data-status="pass"' in doc
    # one sparkline svg per panel, red polyline only on the regressed one
    assert doc.count("<svg") == 2
    assert doc.count('stroke="#e05252"') == 1


def test_dashboard_is_self_contained():
    """Zero external dependencies: no scripts, no fetches, no URLs — the
    page must render over file:// behind an airgap."""
    doc = dashboard.render(history_rows=_history_fixture(),
                           live_rows=[{"t": 1.0, "alive": 2}],
                           refresh_s=5)
    low = doc.lower()
    for banned in ("<script", "<link", "http://", "https://", "src=",
                   "@import", "url("):
        assert banned not in low, banned
    assert '<meta http-equiv="refresh" content="5">' in doc


def test_dashboard_live_panels_and_empty():
    live = [{"t": float(i), "alive": 2, "goodput": 0.5 + 0.01 * i}
            for i in range(5)]
    doc = dashboard.render(live_rows=live)
    assert "fleet (live tsdb window)" in doc
    assert 'data-series="alive"' in doc and 'data-series="goodput"' in doc
    empty = dashboard.render()
    assert "nothing to draw yet" in empty


def test_dashboard_cli_static_artifact(tmp_path):
    hist = tmp_path / "h.jsonl"
    with open(hist, "w") as f:
        for r in _history_fixture():
            f.write(json.dumps(r) + "\n")
    out = tmp_path / "dash.html"
    r = subprocess.run(
        [sys.executable, "-m", "tpudist.obs.dashboard", "--history",
         str(hist), "--out", str(out)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    stamp = json.loads(r.stdout)
    assert stamp["dashboard"] == str(out) and stamp["bytes"] > 0
    assert 'data-regressions="1"' in out.read_text()


# -- unit: manifest validation ------------------------------------------------

def _write_manifest(tmp_path, stages, defaults=None):
    p = tmp_path / "manifest.json"
    man = {"stages": stages}
    if defaults:
        man["defaults"] = defaults
    p.write_text(json.dumps(man))
    return str(p)


@pytest.mark.parametrize("stages,err", [
    ([], "non-empty"),
    ([{"cmd": [["x"]]}], "needs a 'name'"),
    ([{"name": "a", "cmd": ["x"]}, {"name": "a", "cmd": ["x"]}],
     "duplicate"),
    ([{"name": "a"}], "'module', 'cmd' or 'cmds'"),
    ([{"name": "a", "cmd": [1, 2]}], "list of strings"),
    ([{"name": "a", "cmd": ["x"], "timeout_s": 0}], "timeout_s"),
    ([{"name": "a", "cmd": ["x"], "platforms": "tpu"}], "'platforms'"),
])
def test_manifest_validation_rejects(tmp_path, stages, err):
    path = _write_manifest(tmp_path, stages)
    with pytest.raises(perfci.ManifestError, match=re.escape(err)):
        perfci.load_manifest(path)


def test_repo_manifest_is_valid():
    """The committed matrix must always pass its own arm-time validation
    (what an unattended run checks first)."""
    man = perfci.load_manifest(perfci.DEFAULT_MANIFEST)
    names = [st["name"] for st in man["stages"]]
    assert "chaos" in names and "parity1000" in names
    # CPU-host honesty: every bench stage is platform-guarded; only the
    # CPU-safe chaos gate runs unguarded
    unguarded = [st["name"] for st in man["stages"]
                 if not st.get("platforms")]
    assert unguarded == ["chaos"]


def test_perfci_dry_run_cli():
    r = subprocess.run(
        [sys.executable, "-m", "tpudist.perfci", "--dry-run",
         "--platform", "cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "platform=cpu" in r.stdout
    assert "parity1000" in r.stdout


# -- integration: the runner + exit contract ----------------------------------

def _row_cmd(metric, value, extra=""):
    """A stage command that prints one bench-convention JSON row."""
    return [sys.executable, "-c",
            "import json; print(json.dumps({'metric': %r, 'value': %r, "
            "'unit': 'images/sec'%s}))" % (metric, value, extra)]


def _run(tmp_path, stages, args=(), defaults=None, seed_rows=()):
    """Drive perfci.main in-process against a tmp manifest/history/report;
    returns (rc, report dict)."""
    manifest = _write_manifest(tmp_path, stages, defaults)
    hist = tmp_path / "hist.jsonl"
    if seed_rows:
        with open(hist, "w") as f:
            for r in seed_rows:
                f.write(json.dumps(r) + "\n")
    report = tmp_path / "report" / "perfci_report.json"
    rc = perfci.main(["--manifest", manifest, "--history", str(hist),
                      "--report", str(report), "--platform", "cpu",
                      *args])
    rep = json.loads(report.read_text()) if report.exists() else None
    return rc, rep


def test_perfci_clean_run_exit0(tmp_path):
    """Happy path: a stage opts into runner-side stdout appends, its row
    lands in history exactly once, gate unarmed (no prior rows) → 0."""
    rc, rep = _run(tmp_path, [
        {"name": "good", "cmd": _row_cmd("ci_ips", 1000.0),
         "append_stdout_rows": True, "series": ["ci_ips"]},
        {"name": "guarded", "cmd": _row_cmd("never", 1.0),
         "platforms": ["tpu"]},
    ])
    assert rc == 0
    s = rep["summary"]
    assert s == {"stages_total": 2, "stages_ok": 1, "stages_failed": 0,
                 "stages_skipped": 1, "series_gated": 1, "regressions": 0,
                 "rows_appended": 1}
    by_name = {st["name"]: st for st in rep["stages"]}
    assert by_name["good"]["status"] == "ok"
    assert by_name["good"]["rows_runner_appended"] == 1
    assert by_name["guarded"]["status"] == "skipped_platform"
    assert rep["gates"][0]["status"] == "no_baseline"
    rows = regress.load_history(str(tmp_path / "hist.jsonl"))
    assert len(rows) == 1 and rows[0]["metric"] == "ci_ips"
    assert rows[0]["measured_at"]                  # runner stamps UTC
    # one schema-valid perfci_run event beside the report
    evp = tmp_path / "report" / "events.perfci.jsonl"
    evs = [json.loads(line) for line in evp.read_text().splitlines()]
    assert len(evs) == 1 and evs[0]["type"] == "perfci_run"
    telemetry.validate_event(evs[0])
    assert evs[0]["rank"] == -1 and evs[0]["exit"] == 0
    assert evs[0]["stages_total"] == 2 and evs[0]["regressions"] == 0


def test_perfci_regression_exit1(tmp_path):
    """A produced series that trips the trailing-median gate → exit 1
    (findings, not operational failure) — and the dashboard artifact
    flags the same series, because they share the math."""
    seed = [{"metric": "ci_ips", "value": 1000.0 + d, "unit": "images/sec"}
            for d in (0, 5, -5, 2, -2)]
    dash = tmp_path / "dash.html"
    rc, rep = _run(
        tmp_path,
        [{"name": "slow", "cmd": _row_cmd("ci_ips", 700.0),
          "append_stdout_rows": True, "series": ["ci_ips"]}],
        args=["--dashboard", str(dash)], seed_rows=seed)
    assert rc == 1
    assert rep["summary"]["regressions"] == 1
    assert rep["gates"][0]["status"] == "regression"
    assert rep["gates"][0]["stage"] == "slow"
    doc = dash.read_text()
    assert 'data-metric="ci_ips"' in doc
    assert 'data-status="regression"' in doc


def test_perfci_crash_isolation_exit2(tmp_path):
    """A dying stage and a hanging stage are contained — later stages
    still run and append — but operational failure outranks everything:
    exit 2 even though the surviving series gates clean."""
    rc, rep = _run(tmp_path, [
        {"name": "dies", "cmd": [sys.executable, "-c",
                                 "import sys; sys.exit(3)"]},
        {"name": "hangs", "cmd": [sys.executable, "-c",
                                  "import time; time.sleep(60)"],
         "timeout_s": 1},
        {"name": "good", "cmd": _row_cmd("ci_ips", 1000.0),
         "append_stdout_rows": True, "series": ["ci_ips"]},
    ])
    assert rc == 2
    by_name = {st["name"]: st for st in rep["stages"]}
    assert by_name["dies"]["status"] == "failed"
    assert by_name["dies"]["rc"] == 3
    assert by_name["hangs"]["status"] == "timeout"
    assert by_name["good"]["status"] == "ok"       # matrix moved on
    assert rep["summary"]["stages_failed"] == 2
    assert rep["exit"] == 2


def test_perfci_missing_series_exit2(tmp_path):
    """An expected series that never appears is the silent no-op an
    unattended matrix must not absorb: operational failure, with
    {platform} substitution in the expectation."""
    rc, rep = _run(tmp_path, [
        {"name": "silent", "cmd": [sys.executable, "-c", "print('hi')"],
         "series": ["ips_{platform}"]},
    ])
    assert rc == 2
    st = rep["stages"][0]
    assert st["status"] == "missing_series"
    assert st["missing_series"] == ["ips_cpu"]


def test_perfci_corpus_gate_refunds(tmp_path):
    rc, rep = _run(tmp_path, [
        {"name": "needs_data", "cmd": _row_cmd("x", 1.0),
         "corpus": str(tmp_path / "no_such_corpus")},
    ])
    assert rc == 0
    assert rep["stages"][0]["status"] == "skipped_corpus"


def test_perfci_self_append_dedup(tmp_path):
    """The repo norm: benches append their own rows. The runner must
    detect the growth and NOT double-append the identical stdout echo."""
    hist = tmp_path / "hist.jsonl"
    code = ("import json, sys; from tpudist import regress\n"
            "row = {'metric': 'self_ips', 'value': 500.0}\n"
            "regress.append_history(row, path=%r)\n"
            "print(json.dumps(row))" % str(hist))
    rc, rep = _run(tmp_path, [
        {"name": "selfie", "cmd": [sys.executable, "-c", code],
         "append_stdout_rows": True, "series": ["self_ips"]},
    ])
    assert rc == 0
    st = rep["stages"][0]
    assert st["rows_self_appended"] == 1
    assert st["rows_runner_appended"] == 0         # dedup held
    assert len(regress.load_history(str(hist))) == 1


def test_perfci_usage_errors_exit2(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert perfci.main(["--manifest", str(bad)]) == 2
    manifest = _write_manifest(tmp_path, [{"name": "a",
                                           "cmd": ["true"]}])
    assert perfci.main(["--manifest", manifest, "--stages", "nope",
                        "--dry-run"]) == 2


def test_perfci_stage_subset_and_env(tmp_path):
    """--stages selects; defaults.env + stage env reach the child."""
    code = ("import os; print('{\"metric\": \"env_ips\", \"value\": ' "
            "+ os.environ['PERFCI_T_VAL'] + '}')")
    rc, rep = _run(
        tmp_path,
        [{"name": "envy", "cmd": [sys.executable, "-c", code],
          "append_stdout_rows": True, "env": {"PERFCI_T_VAL": "42.5"}},
         {"name": "unrun", "cmd": [sys.executable, "-c",
                                   "import sys; sys.exit(1)"]}],
        args=["--stages", "envy"])
    assert rc == 0
    assert [st["name"] for st in rep["stages"]] == ["envy"]
    rows = regress.load_history(str(tmp_path / "hist.jsonl"))
    assert rows[0]["value"] == 42.5


# -- satellite: regress resolves history at CALL time -------------------------

def test_history_path_resolved_at_call_time(tmp_path, monkeypatch):
    """The import-snapshot bug class: no module-level DEFAULT_HISTORY
    frozen at import; env set AFTER import must redirect both the API and
    the CLI."""
    assert not hasattr(regress, "DEFAULT_HISTORY")
    p = tmp_path / "redirected.jsonl"
    monkeypatch.setenv("TPUDIST_BENCH_HISTORY", str(p))
    assert regress.history_path() == str(p)
    with open(p, "w") as f:
        for v in (1000.0, 1001.0, 999.0, 700.0):   # newest row regressed
            f.write(json.dumps({"metric": "m", "value": v}) + "\n")
    # CLI with no --history must gate against the redirected file (the
    # module was imported long before the env var existed)
    assert regress.main([]) == 2
    # perfci's default history goes through the same call-time resolution
    manifest = _write_manifest(tmp_path, [
        {"name": "noop", "cmd": [sys.executable, "-c", "pass"]}])
    report = tmp_path / "report.json"
    assert perfci.main(["--manifest", manifest, "--report", str(report),
                        "--platform", "cpu"]) == 0
    rep = json.loads(report.read_text())
    assert rep["history"] == str(p)


# -- integration: summarize renders the perfci run census ---------------------

def test_summarize_perfci_section(tmp_path):
    rc, _ = _run(tmp_path, [
        {"name": "good", "cmd": _row_cmd("ci_ips", 1000.0),
         "append_stdout_rows": True, "series": ["ci_ips"]}])
    assert rc == 0
    r = subprocess.run(
        [sys.executable, "-m", "tpudist.summarize",
         str(tmp_path / "report")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "perfci: 1 run(s), 0 regression(s) flagged" in r.stdout
    assert re.search(r"\[perfci\] cpu: 1/1 stages ok", r.stdout), r.stdout


# -- e2e: live /dashboard + fleet_ts on a real 2-rank launch ------------------

_FLEET_CHILD = r"""
import os, time
from tpudist.telemetry import Telemetry
rank = int(os.environ["TPUDIST_PROCESS_ID"])
tel = Telemetry(os.environ["TPUDIST_TEST_OUT"], rank=rank)
for s in range(40):
    tel.step(step=s, epoch=0, data_s=0.0, h2d_s=0.0, compute_s=0.01,
             drain_s=0.0, step_s=0.1)
    time.sleep(0.1)
tel.close()
print(f"RANK{rank}_DONE", flush=True)
"""


def test_launch_dashboard_and_tsdb_e2e(tmp_path):
    """Acceptance: the launcher's fleet endpoint serves /dashboard while
    the supervision poll records fleet_ts rows from the live run — the
    live panel draws real samples, and the recorded file survives the
    run for post-hoc query."""
    out = tmp_path / "run"
    out.mkdir()
    env = dict(os.environ)
    env["TPUDIST_TEST_OUT"] = str(out)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tpudist.launch", "--nprocs", "2",
         "--telemetry-dir", str(out), "--metrics-port", "0",
         "--", sys.executable, "-c", _FLEET_CHILD],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        port = None
        deadline = time.time() + 90
        while time.time() < deadline:
            line = proc.stderr.readline()
            m = re.search(r"fleet metrics on :(\d+)", line or "")
            if m:
                port = int(m.group(1))
                break
        assert port, "launcher never announced the fleet endpoint"
        doc = ""
        while time.time() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/dashboard",
                        timeout=2) as r:
                    assert r.headers.get_content_type() == "text/html"
                    doc = r.read().decode()
            except OSError:
                doc = ""
            if "fleet (live tsdb window)" in doc:
                break
            time.sleep(0.3)
        assert "fleet (live tsdb window)" in doc, doc[-1500:]
        assert 'data-series="alive"' in doc
        assert '<meta http-equiv="refresh"' in doc  # the live mechanism
        proc.wait(timeout=60)
    finally:
        proc.terminate()
        proc.wait(timeout=30)
    ts = tsdb.latest_path(str(out))
    assert ts and ts.endswith("fleet_ts.0.jsonl")
    rows = tsdb.load_rows(ts)
    assert rows, "supervision poll recorded no samples"
    assert any(r.get("alive", 0) >= 1 for r in rows)
    assert any(isinstance(r.get("step_p50_s"), (int, float)) for r in rows)
    q = tsdb.query(rows, names=["alive"])
    assert q["alive"], "query found no alive series in the recording"


# -- e2e: the console smoke script --------------------------------------------

def test_perfci_smoke_script(tmp_path):
    """Satellite: tools/perfci_smoke.sh chains manifest dry-run → a tiny
    CPU matrix → history append → gate verdict → dashboard artifact."""
    env = dict(os.environ)
    env["TPUDIST_PERFCI_SMOKE_DIR"] = str(tmp_path)
    r = subprocess.run(
        ["bash", os.path.join(REPO, "tools", "perfci_smoke.sh")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-4000:], r.stderr[-4000:])
    assert "PERFCI_SMOKE_OK" in r.stdout, r.stdout[-4000:]
