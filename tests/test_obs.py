"""Live observability plane (tpudist/obs/*): metrics endpoint, cross-rank
trace export, XLA introspection, regression gate.

Tiers (all marked ``obs``, like test_telemetry):

- unit: Prometheus text building/escaping, the event-driven MetricsRegistry
  against synthetic timelines (numeric consistency with summarize.analyze
  over the SAME events), trace-event geometry + clock-skew alignment, HLO
  census parsing, telemetry size rotation, the regression gate's verdicts;
- integration: the fleet registry over real heartbeat files; an HTTP
  round-trip through MetricsServer;
- e2e (acceptance): an in-process ``--telemetry --metrics-port 0`` CPU run
  serves valid Prometheus text whose gauges agree with the events file;
  ``summarize --trace`` emits a loadable Chrome trace (per-rank pid/tid
  spans covering compile + steps) from a 2-rank run dir; the gate flags an
  injected 20% slowdown on synthetic history while passing an unchanged
  one; a 2-child ``tpudist.launch --metrics-port 0`` serves the fleet view;
  and ``tools/obs_smoke.sh`` chains endpoint→trace→gate in one script.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from tpudist import telemetry
from tpudist.obs import xla_introspect as xi
from tpudist.obs.server import (FleetMetrics, MetricsRegistry, MetricsServer,
                                PromText)
from tpudist.obs.trace import clock_offsets, export_trace, to_trace_events
from tpudist.regress import analyze_history, load_history
from tpudist.summarize import analyze, load_events

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_telemetry_globals():
    telemetry.set_current(None)
    telemetry.clear_pending()
    yield
    telemetry.set_current(None)
    telemetry.clear_pending()


def _parse_prom(text: str) -> dict:
    """{metric{labels}: value} for every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val)
    return out


# -- unit: Prometheus text ----------------------------------------------------

def test_prom_text_families_and_escaping():
    p = PromText()
    p.sample("m_a", 1.5, help="a gauge", quantile="0.5")
    p.sample("m_a", 2.5, quantile="0.95")
    p.sample("m_b", 3, help='quo"te', type="counter", label='x"y\n')
    text = p.render()
    assert text.count("# HELP m_a") == 1 and text.count("# TYPE m_a") == 1
    assert 'm_a{quantile="0.5"} 1.5' in text
    assert 'm_a{quantile="0.95"} 2.5' in text
    assert "# TYPE m_b counter" in text
    assert r'm_b{label="x\"y\n"} 3' in text
    p2 = PromText()
    p2.sample("m_none", None)               # Nones are dropped entirely
    assert "m_none" not in p2.render()


# -- unit: registry vs the same synthetic timeline --------------------------

def _feed(reg, events):
    for ev in events:
        reg.observe(ev)


def _synthetic_events(n_steps=8, step_s=0.5, compile_s=4.0):
    t = 1000.0
    ev = [{"t": t, "type": "run_start", "rank": 0, "attempt": 0,
           "platform": "cpu", "n_devices": 8, "device_kind": "cpu",
           "arch": "resnet18", "global_batch": 64}]
    ev.append({"t": t, "type": "program", "rank": 0, "attempt": 0,
               "flops_per_step": 2e9})
    for i in range(n_steps):
        extra = compile_s if i == 0 else 0.0
        t += step_s + extra
        if i == 0:
            ev.append({"t": t, "type": "compile", "rank": 0, "attempt": 0,
                       "seconds": compile_s, "phase": "train_step",
                       "step": 0})
        ev.append({"t": t, "type": "step", "rank": 0, "attempt": 0,
                   "step": i, "epoch": 0, "data_s": 0.05, "h2d_s": 0.01,
                   "compute_s": 0.4 + extra, "drain_s": 0.0,
                   "step_s": step_s + extra, "mfu": 0.5})
    ev.append({"t": t + 0.3, "type": "checkpoint_save", "rank": 0,
               "attempt": 0, "seconds": 0.3, "kind": "epoch"})
    ev.append({"t": t + 0.5, "type": "fault", "rank": 0, "attempt": 0,
               "point": "slow_peer"})
    ev.append({"t": t + 0.6, "type": "epoch", "rank": 0, "attempt": 0,
               "epoch": 0, "seconds": 8.0, "samples_skipped": 3,
               "samples_retried": 7})
    return ev


def test_registry_matches_telemetry_accounting():
    ev = _synthetic_events(n_steps=8, step_s=0.5, compile_s=4.0)
    reg = MetricsRegistry(rank=0)
    _feed(reg, ev)
    m = _parse_prom(reg.render())
    assert m["tpudist_steps_total"] == 8
    assert m["tpudist_last_step"] == 7
    # productive excludes the first dispatch's compile — same number the
    # run_end accounting would report
    assert m["tpudist_productive_seconds_total"] == pytest.approx(8 * 0.5)
    assert m['tpudist_overhead_seconds_total{bucket="compile"}'] == 4.0
    assert m['tpudist_overhead_seconds_total{bucket="checkpoint"}'] == 0.3
    # the compile-carrying step is EXCLUDED from the percentile window
    # (matching the heartbeat window and summarize's steady state): even
    # the p95 must not show the 4.5 s compile step
    assert m['tpudist_step_time_seconds{quantile="0.5"}'] == 0.5
    assert m['tpudist_step_time_seconds{quantile="0.95"}'] == 0.5
    assert m['tpudist_phase_time_seconds{phase="data",quantile="0.5"}'] \
        == pytest.approx(0.05)
    assert m["tpudist_mfu"] == 0.5
    assert m["tpudist_flops_per_step"] == 2e9
    assert m['tpudist_faults_total{point="slow_peer"}'] == 1
    assert m["tpudist_samples_skipped_total"] == 3
    assert m["tpudist_samples_retried_total"] == 7
    # ISSUE 13 satellite: quarantines get a dedicated headline counter
    # beside the per-point fault counts.
    assert m["tpudist_checkpoint_quarantined_total"] == 0
    reg.observe({"t": 1999.0, "type": "fault", "rank": 0, "attempt": 0,
                 "point": "checkpoint_quarantine",
                 "path": "checkpoint.msgpack.corrupt"})
    mq = _parse_prom(reg.render())
    assert mq["tpudist_checkpoint_quarantined_total"] == 1
    assert mq['tpudist_faults_total{point="checkpoint_quarantine"}'] == 1
    assert m["tpudist_run_ended"] == 0
    assert 0.0 < m["tpudist_goodput"] <= 1.0
    info = [k for k in m if k.startswith("tpudist_run_info")]
    assert info and 'arch="resnet18"' in info[0]

    # run_end switches goodput to the trainer's authoritative number
    reg.observe({"t": 2000.0, "type": "run_end", "rank": 0, "attempt": 0,
                 "wall_s": 10.0, "productive_s": 4.0, "goodput": 0.4,
                 "init_s": 1.0})
    m2 = _parse_prom(reg.render())
    assert m2["tpudist_goodput"] == 0.4
    assert m2["tpudist_run_ended"] == 1
    assert m2['tpudist_overhead_seconds_total{bucket="init"}'] == 1.0


def test_registry_xla_fields_ride_compile_event():
    reg = MetricsRegistry(rank=0)
    reg.observe({"t": 1.0, "type": "compile", "rank": 0, "attempt": 0,
                 "seconds": 0.5, "phase": "cost_analysis",
                 "collective_bytes_per_step": 1.5e6, "collective_ops": 12,
                 "temp_bytes": 3e7})
    m = _parse_prom(reg.render())
    assert m["tpudist_collective_bytes_per_step"] == 1.5e6
    assert m["tpudist_collective_ops_per_step"] == 12
    assert m["tpudist_hbm_temp_bytes"] == 3e7


# -- unit: trace export -------------------------------------------------------

def _two_rank_events(skew=5.0, n_steps=6):
    """Two ranks' timelines whose run_start anchors disagree by ``skew``
    (rank 1's host clock runs ahead)."""
    evs = []
    for rank, off in ((0, 0.0), (1, skew)):
        t = 100.0 + off
        evs.append({"t": t, "type": "run_start", "rank": rank, "attempt": 0,
                    "platform": "cpu", "n_devices": 2, "arch": "x",
                    "global_batch": 16})
        evs.append({"t": t + 6.0, "type": "compile", "rank": rank,
                    "attempt": 0, "seconds": 6.0, "phase": "train_step",
                    "step": 0})
        for i in range(n_steps):
            t += (6.5 if i == 0 else 0.5)
            evs.append({"t": t, "type": "step", "rank": rank, "attempt": 0,
                        "step": i, "epoch": 0, "data_s": 0.1, "h2d_s": 0.05,
                        "compute_s": 0.3, "drain_s": 0.01,
                        "step_s": 6.5 if i == 0 else 0.5})
    evs.append({"t": 130.0, "type": "straggler", "rank": -1, "attempt": 0,
                "straggler_rank": 1, "factor": 5.0})
    return sorted(evs, key=lambda e: e["t"])


def test_clock_offsets_align_run_start_anchors():
    evs = _two_rank_events(skew=5.0)
    off = clock_offsets(evs)
    assert off == {1: pytest.approx(5.0)}
    assert clock_offsets(evs, align=False) == {}
    # single-rank stream: nothing to align
    assert clock_offsets([e for e in evs if e.get("rank") == 0]) == {}


def test_trace_export_geometry_and_tracks():
    evs = _two_rank_events(skew=5.0, n_steps=6)
    obj = export_trace(evs)
    tev = obj["traceEvents"]
    assert obj["displayTimeUnit"] == "ms"
    pids = {e["pid"] for e in tev}
    assert pids == {0, 1, -1}
    names = {(e["pid"], e["args"]["name"]) for e in tev
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert (0, "rank 0") in names and (1, "rank 1") in names \
        and (-1, "launcher") in names
    for rank in (0, 1):
        steps = [e for e in tev if e["ph"] == "X" and e["pid"] == rank
                 and e["name"].startswith("step ")]
        assert len(steps) == 6
        compiles = [e for e in tev if e["ph"] == "X" and e["pid"] == rank
                    and e["name"].startswith("compile:")]
        assert len(compiles) == 1
        for e in steps + compiles:
            assert e["ts"] >= 0 and e["dur"] > 0
        # phase sub-spans tile inside their step in execution order
        phases = [e for e in tev if e["ph"] == "X" and e["pid"] == rank
                  and e["tid"] == 1]
        assert {p["name"] for p in phases} == {"data wait", "h2d", "compute",
                                               "drain"}
    # alignment: the two ranks' step-5 spans land within float noise of
    # each other even though their raw stamps differ by the 5 s skew
    s5 = {e["pid"]: e["ts"] for e in tev
          if e["ph"] == "X" and e["name"] == "step 5"}
    assert abs(s5[0] - s5[1]) < 1.0
    raw = {e["pid"]: e["ts"] for e in export_trace(evs, align=False)
           ["traceEvents"] if e["ph"] == "X" and e["name"] == "step 5"}
    assert abs(raw[0] - raw[1]) == pytest.approx(5e6, rel=1e-3)
    # the launcher's straggler flag is an instant on its own track
    inst = [e for e in tev if e["ph"] == "i" and e["pid"] == -1]
    assert any("straggler rank 1" in e["name"] for e in inst)
    json.dumps(obj)                       # must be serializable as-is


# -- unit: HLO census ---------------------------------------------------------

_HLO_SAMPLE = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main (p0: f32[64,128], p1: f32[64,128]) -> f32[64,128] {
  %p0 = f32[64,128]{1,0} parameter(0)
  %p1 = f32[64,128]{1,0} parameter(1)
  %dot.1 = f32[64,64]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}
  %all-reduce.1 = f32[64,128]{1,0} all-reduce(%p1), replica_groups={}
  %ag = bf16[128,128]{1,0} all-gather(%p1), dimensions={0}
  %ars = f32[32,128]{1,0} reduce-scatter(%p1), dimensions={0}
  %ar-tiled = f32[8,128]{1,0:T(8,128)} all-reduce(%p1), replica_groups={}
  %conv = f32[4,4,4,8]{3,2,1,0:T(8,128)S(1)} convolution(%p1, %p1), dim_labels=b01f_01io->b01f
  %cp-start = (f32[64,128]{1,0}, f32[64,128]{1,0}, u32[], u32[]) collective-permute-start(%p1)
  %cp-done = f32[64,128]{1,0} collective-permute-done(%cp-start)
  ROOT %fusion = f32[64,128]{1,0} fusion(%all-reduce.1), kind=kLoop
}
"""


def test_hlo_op_census_counts_and_bytes():
    c = xi.hlo_op_census(_HLO_SAMPLE)
    # TPU tiling/memory-space layout annotations ({1,0:T(8,128)S(1)}) must
    # not hide instructions from the census
    assert c["op_counts"]["all-reduce"] == 2
    assert c["op_counts"]["convolution"] == 1
    assert c["op_counts"]["dot"] == 1
    assert c["op_counts"]["fusion"] == 1
    # -start folds into the base op, -done is skipped (no double count)
    assert c["op_counts"]["collective-permute"] == 1
    assert "collective-permute-done" not in c["op_counts"]
    colls = c["collectives"]
    assert colls["all-reduce"] == {"count": 2,
                                   "bytes": (64 * 128 + 8 * 128) * 4}
    assert colls["all-gather"]["bytes"] == 128 * 128 * 2       # bf16
    assert colls["reduce-scatter"]["bytes"] == 32 * 128 * 4
    # async -start tuples alias the input beside the output (+u32 context):
    # the 64x128 f32 transfer must count ONCE, not summed over the tuple
    assert colls["collective-permute"]["bytes"] == 64 * 128 * 4
    assert xi.shape_bytes("(f32[2,3]{1,0}, bf16[4])") == 24 + 8
    assert xi.shape_bytes("(f32[2,3]{1,0}, bf16[4])", largest_only=True) == 24
    assert xi.shape_bytes("f32[<=8,128]") == 8 * 128 * 4   # dynamic bound
    assert xi.shape_bytes("opaque[]") == 0


def test_event_fields_flatten():
    info = {"flops": 1e9, "temp_bytes": 5, "op_counts": {"dot": 2},
            "collectives": {"all-reduce": {"count": 3, "bytes": 99}},
            "collective_ops": 3, "collective_bytes_per_step": 99,
            "bytes_accessed_detail": {"x": 1.0}}
    f = xi.event_fields(info)
    assert f["all_reduce_count"] == 3 and f["all_reduce_bytes"] == 99
    assert f["collective_bytes_per_step"] == 99
    assert "op_counts" not in f and "bytes_accessed_detail" not in f
    json.dumps(f)


# -- unit: telemetry size rotation -------------------------------------------

def test_telemetry_rotation_and_rotated_read(tmp_path):
    tel = telemetry.Telemetry(str(tmp_path), rank=0, attempt=0,
                              heartbeat=False, max_mb=2e-3)   # ~2 KB cap
    for i in range(40):
        tel.step(step=i, epoch=0, data_s=0.0, h2d_s=0.0, compute_s=0.01,
                 drain_s=0.0, step_s=0.02)
    tel.close()
    live = tmp_path / "events.0.jsonl"
    rolled = tmp_path / "events.0.1.jsonl"
    assert live.exists() and rolled.exists()
    assert live.stat().st_size < 3000 and rolled.stat().st_size < 3000
    # summarize's loader reassembles the stream across segments
    events = load_events(str(tmp_path), strict=True)
    steps = [e["step"] for e in events if e["type"] == "step"]
    assert steps == sorted(steps) and steps[-1] == 39
    assert any(e["type"] == "run_end" for e in events)
    # only the newest two segments are kept (bounded disk)
    assert len(list(tmp_path.glob("events.*.jsonl"))) == 2


def test_telemetry_sink_sees_events_and_survives_breakage(tmp_path):
    seen = []
    tel = telemetry.Telemetry(str(tmp_path), rank=0, heartbeat=False)
    tel.add_sink(seen.append)
    tel.add_sink(lambda ev: 1 / 0)                 # must not break emits
    tel.emit("fault", point="x")
    tel.close()
    assert [e["type"] for e in seen] == ["fault", "run_end"]


# -- unit: regression gate ----------------------------------------------------

def _rows(n, value=1000.0, mfu=0.4, metric="resnet18_224_1chip"):
    return [{"metric": metric, "value": value, "mfu": mfu,
             "unit": "images/sec"} for _ in range(n)]


def test_regress_passes_unchanged_and_flags_20pct_slowdown():
    hist = _rows(5)
    ok = analyze_history(hist + _rows(1, value=990.0))
    assert ok["status"] == "pass" and not ok["reasons"]
    bad = analyze_history(hist + _rows(1, value=800.0))
    assert bad["status"] == "regression"
    assert "images/sec" in bad["reasons"][0]
    badm = analyze_history(hist + _rows(1, mfu=0.3))
    assert badm["status"] == "regression"
    assert "MFU" in badm["reasons"][0]
    # within threshold: 8% down passes
    assert analyze_history(hist + _rows(1, value=920.0))["status"] == "pass"


def test_regress_grouping_min_history_and_unparseable(tmp_path):
    # a different workload's rows never gate this one
    other = _rows(5, value=10.0, metric="vit_s_224_1chip")
    v = analyze_history(other + _rows(1, value=800.0))
    assert v["status"] == "no_baseline" and v["n_history"] == 0
    # a batch sweep opens its OWN series: the metric name doesn't encode
    # per_device_batch, so b=16 after b=128 history must not false-flag
    b128 = [dict(r, per_device_batch=128) for r in _rows(5)]
    b16 = dict(_rows(1, value=300.0)[0], per_device_batch=16)
    v = analyze_history(b128 + [b16])
    assert v["status"] == "no_baseline" and v["per_device_batch"] == 16
    assert analyze_history(b128 + [dict(b16, per_device_batch=128)]
                           )["status"] == "regression"
    assert analyze_history([])["status"] == "no_history"
    # median over the window ignores one noisy historical row
    hist = _rows(4) + _rows(1, value=5000.0)
    assert analyze_history(hist + _rows(1, value=980.0))["status"] == "pass"
    # unparseable lines are skipped at load time
    h = tmp_path / "hist.jsonl"
    with open(h, "w") as f:
        for r in _rows(3):
            f.write(json.dumps(r) + "\n")
        f.write("not json\n")
    rows = load_history(str(h))
    assert len(rows) == 3


def test_regress_cli_exit_codes(tmp_path):
    h = tmp_path / "hist.jsonl"
    with open(h, "w") as f:
        for r in _rows(5) + _rows(1, value=790.0):
            f.write(json.dumps(r) + "\n")
    r = subprocess.run([sys.executable, "-m", "tpudist.regress",
                        "--history", str(h), "--json"],
                       capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 2, r.stdout + r.stderr
    v = json.loads(r.stdout)
    assert v["status"] == "regression"
    with open(h, "a") as f:
        f.write(json.dumps(_rows(1, value=1010.0)[0]) + "\n")
    r2 = subprocess.run([sys.executable, "-m", "tpudist.regress",
                         "--history", str(h)],
                        capture_output=True, text=True, cwd=REPO)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "PASS" in r2.stdout


# -- integration: fleet view --------------------------------------------------

def test_fleet_metrics_heartbeats_and_straggler_gauges(tmp_path):
    hb = telemetry.heartbeat_dir(str(tmp_path))
    os.makedirs(hb)
    for rank, host in ((0, 0.01), (1, 0.6)):
        with open(os.path.join(hb, f"rank{rank}.json"), "w") as f:
            json.dump({"rank": rank, "attempt": 0, "step": 9, "n": 8,
                       "step_p50": 0.7, "step_p95": 0.8, "host_p50": host,
                       "updated_at": time.time()}, f)
    fleet = FleetMetrics(str(tmp_path), nprocs=2, straggler_factor=4.0)
    fleet.observe({"t": 1.0, "type": "launcher_start", "rank": -1,
                   "attempt": 0, "nprocs": 2})
    fleet.observe({"t": 2.0, "type": "rank_exit", "rank": -1, "attempt": 0,
                   "code": 9, "classification": "crash (exit 9)",
                   "exit_rank": 1})
    fleet.refresh(attempt=0)
    m = _parse_prom(fleet.render())
    assert m["tpudist_fleet_nprocs"] == 2
    assert m['tpudist_fleet_rank_exits_total{classification="crash (exit 9)"}'] == 1
    assert m['tpudist_straggler{rank="1"}'] == 1
    assert m['tpudist_straggler{rank="0"}'] == 0
    assert m['tpudist_rank_host_seconds{quantile="0.5",rank="1"}'] == 0.6
    assert m['tpudist_rank_last_step{rank="0"}'] == 9

    # served over HTTP like the launcher does
    srv = MetricsServer(fleet, port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert 'tpudist_straggler{rank="1"} 1' in text
    finally:
        srv.close()


# -- e2e: trainer endpoint (acceptance) --------------------------------------

def test_trainer_metrics_endpoint_consistent_with_events(tmp_path):
    """Acceptance: a --telemetry --metrics-port 0 CPU run serves valid
    Prometheus text whose step/MFU/goodput gauges agree with the events
    file the same run wrote."""
    from tpudist.config import Config
    from tpudist.trainer import Trainer

    out = str(tmp_path / "out")
    cfg = Config(arch="resnet18", num_classes=4, image_size=16,
                 batch_size=16, epochs=1, lr=0.02, workers=2, print_freq=1,
                 synthetic=True, synthetic_size=48, use_amp=False,
                 outpath=out, overwrite="delete", seed=0, telemetry=True,
                 metrics_port=0)
    t = Trainer(cfg, writer=None)
    assert t.metrics_server is not None and t.metrics_server.port > 0
    portfile = os.path.join(out, "metrics.0.port")
    assert os.path.exists(portfile)
    assert int(open(portfile).read()) == t.metrics_server.port

    url = f"http://127.0.0.1:{t.metrics_server.port}"
    scrapes: list[str] = []
    stop = threading.Event()

    ctypes: list[str] = []

    def scraper():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(f"{url}/metrics",
                                            timeout=2) as r:
                    ctypes.append(r.headers.get("Content-Type", ""))
                    scrapes.append(r.read().decode())
            except (OSError, ValueError):
                pass
            time.sleep(0.05)

    th = threading.Thread(target=scraper, daemon=True)
    th.start()
    try:
        t.fit()
    finally:
        stop.set()
        th.join(timeout=10)
    assert t.metrics_server is None                  # closed by fit()
    assert not os.path.exists(portfile)              # port file cleaned up
    assert scrapes, "endpoint was never scrapeable during the run"
    assert all("text/plain" in c for c in ctypes)

    events = load_events(out, strict=True)
    step_events = {e["step"]: e for e in events if e["type"] == "step"}
    compile_train = {e.get("step"): e["seconds"] for e in events
                     if e["type"] == "compile"
                     and e.get("phase") == "train_step"}
    # the last scrape that saw at least one step
    final = None
    for text in reversed(scrapes):
        if "tpudist_last_step" in text:
            final = _parse_prom(text)
            break
    assert final is not None, "no scrape observed a completed step"
    last = int(final["tpudist_last_step"])
    assert last in step_events
    seen = [e for s, e in step_events.items() if s <= last]
    # steps counter == step events up to the scraped watermark
    assert final["tpudist_steps_total"] == len(seen)
    # productive seconds == sum(step_s) - compile, same accounting as
    # run_end (6-dp rounding on the event fields)
    expect = sum(e["step_s"] for e in seen) \
        - sum(v for s, v in compile_train.items() if s <= last)
    assert final["tpudist_productive_seconds_total"] == \
        pytest.approx(expect, abs=1e-3)
    assert 0.0 < final["tpudist_goodput"] <= 1.0
    prog = next(e for e in events if e["type"] == "program")
    if prog["flops_per_step"]:
        assert final["tpudist_flops_per_step"] == \
            pytest.approx(prog["flops_per_step"], rel=1e-5)
    # XLA introspection fields rode the compile event into both surfaces
    intro_ev = next((e for e in events if e["type"] == "compile"
                     and e.get("phase") == "cost_analysis"
                     and "collective_ops" in e), None)
    assert intro_ev is not None, "no XLA introspection on the compile event"
    assert intro_ev["collective_ops"] > 0            # 8-device grad psum
    assert intro_ev["all_reduce_bytes"] > 0
    assert intro_ev["temp_bytes"] > 0
    if "tpudist_collective_ops_per_step" in final:
        assert final["tpudist_collective_ops_per_step"] == \
            intro_ev["collective_ops"]
    # summarize surfaces the same introspection
    a = analyze(events)
    assert a["xla"] is not None
    assert a["xla"]["collective_ops"] == intro_ev["collective_ops"]


# -- e2e: 2-rank trace export (acceptance) -----------------------------------

def test_summarize_trace_from_two_rank_rundir(tmp_path, capsys):
    """Acceptance: ``summarize --trace`` on a 2-rank run dir emits a
    Chrome-trace JSON with valid per-rank pid/tid spans covering compile +
    >= 5 steps per rank."""
    from tpudist.summarize import main as summarize_main

    out = tmp_path / "run"
    for rank in (0, 1):
        tel = telemetry.Telemetry(str(out), rank=rank, attempt=0)
        tel.emit("run_start", platform="cpu", n_devices=2,
                 device_kind="cpu", arch="resnet18", global_batch=16)
        for i in range(6):
            tel.step(step=i, epoch=0, data_s=0.001, h2d_s=0.001,
                     compute_s=0.01, drain_s=0.0, step_s=0.02,
                     compile_s=0.01 if i == 0 else 0.0)
        tel.close()
    trace_path = str(tmp_path / "trace.json")
    rc = summarize_main([str(out), "--trace", trace_path,
                         "--peak-flops", "1e12"])
    assert rc == 0
    obj = json.load(open(trace_path))
    tev = obj["traceEvents"]
    assert {e["pid"] for e in tev if e["ph"] != "M"} == {0, 1}
    for rank in (0, 1):
        steps = [e for e in tev if e["ph"] == "X" and e["pid"] == rank
                 and e["name"].startswith("step ")]
        assert len(steps) >= 5
        assert all(isinstance(e["tid"], int) and e["dur"] > 0
                   and e["ts"] >= 0 for e in steps)
        assert any(e["ph"] == "X" and e["pid"] == rank
                   and e["name"].startswith("compile:") for e in tev)
    # per-rank process metadata names the tracks
    assert {(e["pid"], e["args"]["name"]) for e in tev
            if e["ph"] == "M" and e["name"] == "process_name"} \
        >= {(0, "rank 0"), (1, "rank 1")}


# -- e2e: launcher fleet endpoint --------------------------------------------

_FLEET_CHILD = r"""
import os, time
from tpudist.telemetry import Telemetry
rank = int(os.environ["TPUDIST_PROCESS_ID"])
tel = Telemetry(os.environ["TPUDIST_TEST_OUT"], rank=rank)
for s in range(30):
    tel.step(step=s, epoch=0, data_s=0.0, h2d_s=0.0, compute_s=0.01,
             drain_s=0.0, step_s=0.1)
    time.sleep(0.1)
tel.close()
print(f"RANK{rank}_DONE", flush=True)
"""


def test_launch_fleet_metrics_endpoint(tmp_path):
    """launch --metrics-port 0 serves the fleet view while ranks run: the
    bound port is announced on stderr; /metrics carries supervision +
    per-rank heartbeat gauges."""
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
        deadline = time.time() + 60
        while time.time() < deadline:
            line = proc.stderr.readline()
            m = re.search(r"fleet metrics on :(\d+)", line or "")
            if m:
                port = int(m.group(1))
                break
        assert port, "launcher never announced the fleet endpoint"
        text = ""
        while time.time() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=2) as r:
                    text = r.read().decode()
            except OSError:
                text = ""
            if "tpudist_rank_last_step" in text:
                break
            time.sleep(0.3)
        assert "tpudist_fleet_nprocs 2" in text, text[-2000:]
        assert 'tpudist_rank_last_step{rank="0"}' in text, text[-2000:]
        assert 'tpudist_straggler{rank="0"} 0' in text
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# -- e2e: the observability smoke script -------------------------------------

def test_obs_smoke_script(tmp_path, mp_timeout):
    """Satellite: tools/obs_smoke.sh chains a --telemetry --metrics-port
    run, the trace export, and the regression gate in one command."""
    env = dict(os.environ)
    env["TPUDIST_OBS_SMOKE_DIR"] = str(tmp_path)
    r = subprocess.run(["bash", os.path.join(REPO, "tools", "obs_smoke.sh")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=mp_timeout(1, compile_cost=2.0))
    assert r.returncode == 0, (r.stdout[-4000:], r.stderr[-4000:])
    assert "OBS_SMOKE_OK" in r.stdout, r.stdout[-4000:]
