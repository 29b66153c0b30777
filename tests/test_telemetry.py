"""Unified telemetry layer (tpudist/telemetry.py + tpudist/summarize.py).

Three tiers, all marked ``obs`` (run standalone with ``pytest -m obs``):

- unit: event schema validation, goodput/MFU math on known synthetic
  timelines, straggler detection, peak-FLOPs resolution, the profiling
  satellites (all-device peak HBM, attempt-suffixed trace dirs), the
  faults→telemetry observer;
- integration: a full in-process ``Trainer.fit()`` with ``--telemetry``
  produces schema-valid ``events.<rank>.jsonl`` (step timing breakdown,
  compile/checkpoint/fault events, run_end goodput) that
  ``python -m tpudist.summarize`` turns into the MFU-budget report;
- e2e: two REAL ``tpudist.launch`` ranks with a ``slow_peer`` injection on
  rank 1 — the launcher propagates the spec via TPUDIST_INJECT, the rank
  gate selects rank 1, its heartbeats show the host-side stall, and the
  launcher's aggregation flags the straggler in its output and its
  events.launcher.jsonl. (The ranks run independent jit steps rather than
  a cross-process collective: this container's CPU runtime cannot compile
  multiprocess programs at all — every ``test_multiprocess_scale`` chain
  fails at HEAD with "Multiprocess computations aren't implemented on the
  CPU backend" — and the straggler signal, per-step HOST overhead, is
  deliberately the one that works with or without lockstep collectives.)
"""

import json
import os
import subprocess
import sys
import time

import pytest

from tpudist import faults, telemetry
from tpudist.summarize import analyze, format_report, load_events

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_telemetry_globals():
    telemetry.set_current(None)
    telemetry.clear_pending()
    faults.set_observer(None)
    faults.configure("")
    yield
    telemetry.set_current(None)
    telemetry.clear_pending()
    faults.set_observer(None)
    faults.configure("")


# -- unit: schema ------------------------------------------------------------

def _step_ev(t=0.0, rank=0, **kw):
    ev = {"t": t, "type": "step", "rank": rank, "attempt": 0, "step": 0,
          "epoch": 0, "data_s": 0.01, "h2d_s": 0.002, "compute_s": 0.1,
          "drain_s": 0.0, "step_s": 0.115}
    ev.update(kw)
    return ev


def test_validate_event_accepts_every_schema_type():
    base = {"t": 1.0, "rank": 0, "attempt": 0}
    fillers = {"platform": "cpu", "n_devices": 8, "arch": "resnet18",
               "global_batch": 64, "flops_per_step": 1e9, "step": 3,
               "epoch": 1, "data_s": 0.1, "h2d_s": 0.1, "compute_s": 0.1,
               "drain_s": 0.1, "step_s": 0.4, "seconds": 1.5,
               "phase": "train_step", "kind": "epoch", "path": "/x",
               "point": "slow_peer", "signal": "SIGTERM", "wall_s": 10.0,
               "productive_s": 5.0, "goodput": 0.5, "nprocs": 2,
               "code": 41, "classification": "crash (exit 41)",
               "straggler_rank": 1, "factor": 5.0,
               "from_world": 4, "to_world": 3,
               "windows": 3, "suspect_rank": 1, "max_age_s": 33.0,
               "kernel": "xla", "mode": "auto", "source": "measured",
               "n_buckets": 3, "aot_s": 1.2, "cache": "warm",
               "latency_s": 0.02, "bucket": 4, "n_valid": 3,
               "batch_s": 0.01, "action": "skip_step", "world": 2,
               "divergent": 0, "stages_total": 3, "stages_failed": 0,
               "regressions": 0, "trigger": "fault", "captured": 1,
               "chunk": 128, "heads_per_program": 8, "programs": 1024,
               "rows_per_program": 512, "form": "forward_loop", "chunks": 8,
               "calls": 1}
    for etype, required in telemetry.SCHEMA.items():
        ev = dict(base, type=etype, **{k: fillers[k] for k in required})
        telemetry.validate_event(ev)                  # must not raise


def test_validate_event_rejects_bad_events():
    with pytest.raises(ValueError, match="missing common field"):
        telemetry.validate_event({"type": "step"})
    with pytest.raises(ValueError, match="unknown telemetry event type"):
        telemetry.validate_event({"t": 0.0, "type": "nope", "rank": 0,
                                  "attempt": 0})
    with pytest.raises(ValueError, match="missing"):
        telemetry.validate_event({"t": 0.0, "type": "step", "rank": 0,
                                  "attempt": 0, "step": 1})
    with pytest.raises(ValueError, match="must be numeric"):
        telemetry.validate_event(_step_ev(compute_s="fast"))
    with pytest.raises(ValueError, match="not finite"):
        telemetry.validate_event(_step_ev(step_s=float("nan")))


def test_emit_validates_and_appends_jsonl(tmp_path):
    tel = telemetry.Telemetry(str(tmp_path), rank=3, attempt=1)
    tel.emit("fault", point="slow_peer", step=7)
    with pytest.raises(ValueError):
        tel.emit("step", step=0)                       # missing timings
    tel.close()
    path = tmp_path / "events.3.jsonl"
    assert path.exists()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    for ev in lines:
        telemetry.validate_event(ev)
    assert [e["type"] for e in lines] == ["fault", "run_end"]
    assert all(e["rank"] == 3 and e["attempt"] == 1 for e in lines)


# -- unit: goodput / MFU math on synthetic timelines -------------------------

def _synthetic_run(n_steps=10, step_s=0.5, compute_s=0.4, compile_s=6.0,
                   flops=2e11):
    """A hand-built timeline shaped like the trainer's real emissions:
    run_start at t=0, n uniform steps where step 0's step_s/compute_s
    carry the XLA compile (paired with a compile event, exactly as the
    first dispatch emits), a checkpoint, run_end — every number chosen so
    goodput and MFU are exact closed forms."""
    t = 0.0
    ev = [{"t": t, "type": "run_start", "rank": 0, "attempt": 0,
           "platform": "tpu", "n_devices": 1, "device_kind": "TPU v5 lite",
           "arch": "resnet18", "global_batch": 128}]
    ev.append({"t": t, "type": "program", "rank": 0, "attempt": 0,
               "flops_per_step": flops})
    for i in range(n_steps):
        extra = compile_s if i == 0 else 0.0
        t += step_s + extra
        if i == 0:
            ev.append({"t": t, "type": "compile", "rank": 0, "attempt": 0,
                       "seconds": compile_s, "phase": "train_step",
                       "step": 0})
        ev.append(_step_ev(t=t, step=i, compute_s=compute_s + extra,
                           step_s=step_s + extra,
                           data_s=0.05, h2d_s=0.01, drain_s=0.0))
    ev.append({"t": t + 1.0, "type": "checkpoint_save", "rank": 0,
               "attempt": 0, "seconds": 1.0, "kind": "epoch"})
    wall = compile_s + n_steps * step_s + 1.0
    productive = n_steps * step_s
    ev.append({"t": wall, "type": "run_end", "rank": 0, "attempt": 0,
               "wall_s": wall, "productive_s": productive,
               "goodput": round(productive / wall, 4),
               "compile_s": compile_s, "checkpoint_s": 1.0, "init_s": 0.0,
               "eval_s": 0.0})
    return ev


def test_analyze_goodput_and_mfu_exact():
    ev = _synthetic_run(n_steps=10, step_s=0.5, compute_s=0.4,
                        compile_s=6.0, flops=2e11)
    a = analyze(ev)
    # goodput = 10*0.5 / (6 + 5 + 1) = 5/12
    assert a["goodput"] == round(5.0 / 12.0, 4)
    assert a["wall_s"] == 12.0 and a["productive_s"] == 5.0
    # MFU = flops / (p50 step_s * peak) ; v5e peak = 197e12
    assert a["mfu"] == round(2e11 / (0.5 * 197e12), 4)
    b = a["budget"]
    assert b["compute_s"]["p50"] == pytest.approx(0.4)
    assert b["data_s"]["p50"] == pytest.approx(0.05)
    # other host = step - data - h2d - compute - drain = 0.04
    assert b["other_host_s"]["p50"] == pytest.approx(0.04)
    # the compile-carrying step 0 is EXCLUDED from steady-state percentiles:
    # its 6.4s compute must not leak into the device-compute p95
    assert b["compute_s"]["p95"] == pytest.approx(0.4)
    assert b["step_s"]["p95"] == pytest.approx(0.5)
    assert a["n_steps"] == 10 and a["checkpoint_s"] == 1.0
    # peak override beats the device table
    a2 = analyze(ev, peak_flops=1e12)
    assert a2["mfu"] == round(2e11 / (0.5 * 1e12), 4)
    report = format_report(a, "synthetic")
    assert "goodput 0.417" in report and "MFU" in report
    assert "device compute" in report and "data wait" in report


def test_analyze_overlap_aware_budget_no_double_count():
    """ISSUE 6 satellite: a timeline where the next batch's staging (loader
    pull + H2D issue) overlaps compute (device prefetch — step events carry
    ``prefetch_s``) must yield phase budgets that sum to ≤ wall time. All
    trainer buckets are DISJOINT host intervals: dispatch is async, so
    ``compute_s`` is the (short) dispatch window, the device-busy wait
    surfaces in ``drain_s``, and ``prefetch_s`` is the host interval the
    in-flight device compute hides. The hidden staging time gets its OWN
    bucket and is subtracted from the other-host residue — counting it
    into data/h2d as well would double-book the same wall seconds."""
    base = {"rank": 0, "attempt": 0}
    ev = [{"t": 0.0, "type": "run_start", "platform": "tpu", "n_devices": 1,
           "device_kind": "TPU v5 lite", "arch": "resnet18",
           "global_batch": 128, **base}]
    n, step_s = 10, 0.10
    for i in range(n):
        # exposed data/h2d are tiny (the queue was warm: the 30 ms of
        # loader+H2D work rode prefetch_s under the in-flight compute);
        # the device-busy wait shows up as the 60 ms metric drain.
        ev.append({"t": 1.0 + i * step_s, "type": "step", "step": i,
                   "epoch": 0, "data_s": 0.002, "h2d_s": 0.001,
                   "compute_s": 0.005, "drain_s": 0.060,
                   "prefetch_s": 0.030, "step_s": step_s, **base})
    for e in ev:
        telemetry.validate_event(e)
    a = analyze(ev)
    b = a["budget"]
    assert b["prefetch_s"]["p50"] == pytest.approx(0.030)
    assert b["data_s"]["p50"] == pytest.approx(0.002)
    # serial phases + overlapped bucket + residue sum to ≤ the step wall —
    # nothing is counted twice (other_host absorbs only the true residue).
    parts = sum(b[k]["p50"] for k in ("data_s", "h2d_s", "compute_s",
                                      "drain_s", "prefetch_s",
                                      "other_host_s"))
    assert parts <= b["step_s"]["p50"] + 1e-9
    assert b["other_host_s"]["p50"] == pytest.approx(
        step_s - 0.002 - 0.001 - 0.005 - 0.060 - 0.030)
    rep = format_report(a, "overlap")
    assert "prefetch (ovl.)" in rep
    # a prefetch-free timeline renders no prefetch row (old runs unchanged)
    for e in ev:
        e.pop("prefetch_s", None)
    a2 = analyze(ev)
    assert "prefetch_s" not in a2["budget"]
    assert "prefetch (ovl.)" not in format_report(a2, "plain")


def test_device_prefetcher_order_depth_and_hidden_accounting():
    """The other half of the overlap contract (tpudist/dist.py
    ``DevicePrefetcher``): batches come out in order and placed exactly as
    the serial ``shard_host_batch`` path would place them, the queue never
    exceeds ``depth``, and the staging done inside ``poke()`` while the
    dispatched step computes is what ``poke()`` returns (the step event's
    ``prefetch_s``); only an empty queue fills inside ``__next__``."""
    import jax
    import numpy as np

    from tpudist.dist import DevicePrefetcher, make_mesh, shard_host_batch

    mesh = make_mesh()
    n = jax.device_count()
    rng = np.random.default_rng(0)
    batches = [(rng.standard_normal((n, 4)).astype(np.float32),
                np.full((n,), i, np.int32)) for i in range(5)]

    pf = DevicePrefetcher(batches, mesh, depth=2)
    seen, hidden = [], []
    for i, (imgs, labels) in enumerate(pf):
        assert pf.last_local_bs == n
        # nothing was prefetched before the first batch (an EXPOSED fill
        # inside __next__); every later one was staged by poke()
        assert len(pf._q) == (0 if i == 0 else pf.depth - 1) \
            or i >= len(batches) - pf.depth
        hidden.append(pf.poke())   # what the trainer does mid-step
        assert len(pf._q) <= pf.depth
        seen.append((np.asarray(imgs), np.asarray(labels)))
    assert len(seen) == len(batches)
    for (gi, gl), host in zip(seen, batches):
        ref_i, ref_l = shard_host_batch(mesh, host)
        np.testing.assert_array_equal(gi, np.asarray(ref_i))
        np.testing.assert_array_equal(gl, np.asarray(ref_l))
    # every later batch was staged by poke(): its time is reported as
    # hidden (overlapped) work, so summarize never books it as data/h2d.
    # (The last pokes found the source exhausted — zero by design.)
    assert all(h > 0.0 for h in hidden[:len(batches) - pf.depth])
    assert hidden[-1] == 0.0
    # exhausted source: poke degrades to a no-op, iteration ends cleanly
    assert pf.poke() == 0.0
    with pytest.raises(StopIteration):
        next(pf)

    # depth floor (a DevicePrefetcher that holds zero batches cannot make
    # progress) and empty-source behavior
    pf0 = DevicePrefetcher([], mesh, depth=0)
    assert pf0.depth == 1
    assert pf0.poke() == 0.0
    with pytest.raises(StopIteration):
        next(pf0)


def test_analyze_crashed_run_reconstructs_goodput():
    ev = _synthetic_run(n_steps=4, step_s=1.0, compile_s=2.0)
    ev = [e for e in ev if e["type"] not in ("run_end", "checkpoint_save")]
    a = analyze(ev)
    # wall from run_start.t to last step.t = 2 + 4; productive = 4 steps * 1s
    assert a["goodput"] == pytest.approx(4.0 / 6.0)


def test_telemetry_accounting_matches_run_end(tmp_path):
    tel = telemetry.Telemetry(str(tmp_path), rank=0, attempt=0,
                              heartbeat=False)
    tel.emit("run_start", platform="cpu", n_devices=1, arch="x",
             global_batch=8, device_kind="cpu")
    tel.step(step=0, epoch=0, data_s=0.0, h2d_s=0.0, compute_s=2.0,
             drain_s=0.0, step_s=2.0, compile_s=2.0)   # pure compile step
    tel.step(step=1, epoch=0, data_s=0.01, h2d_s=0.0, compute_s=0.2,
             drain_s=0.0, step_s=0.25)
    tel.note_checkpoint(0.5, kind="epoch")
    end = tel.close()
    assert end["compile_s"] == 2.0
    assert end["productive_s"] == pytest.approx(0.25)   # compile excluded
    assert end["checkpoint_s"] == 0.5
    assert 0.0 < end["goodput"] <= 1.0
    assert end["steps"] == 2
    a = analyze(load_events(str(tmp_path), strict=True))
    assert a["n_steps"] == 2 and a["goodput"] == end["goodput"]


# -- unit: straggler detection ----------------------------------------------

def _beat(rank, host_p50, n=8, attempt=0, age=0.0):
    return {"rank": rank, "attempt": attempt, "step": n, "n": n,
            "host_p50": host_p50, "step_p50": 0.5, "step_p95": 0.6,
            "updated_at": time.time() - age}


def test_find_stragglers_flags_outlier_against_median_of_others():
    beats = {r: _beat(r, h) for r, h in
             enumerate([0.010, 0.012, 0.009, 0.500])}
    out = telemetry.find_stragglers(beats, factor=4.0)
    assert [s["straggler_rank"] for s in out] == [3]
    assert out[0]["factor"] > 40
    # uniform fleet: nobody flagged
    assert telemetry.find_stragglers(
        {r: _beat(r, 0.01) for r in range(4)}, factor=4.0) == []
    # two-rank fleet stays decidable (median-of-OTHERS, not of all)
    out2 = telemetry.find_stragglers(
        {0: _beat(0, 0.005), 1: _beat(1, 0.400)}, factor=3.0)
    assert [s["straggler_rank"] for s in out2] == [1]


def test_find_stragglers_guards():
    # absolute floor: microsecond jitter on an idle fleet never flags
    beats = {0: _beat(0, 0.00001), 1: _beat(1, 0.0005)}
    assert telemetry.find_stragglers(beats, factor=3.0) == []
    # stale/wrong-attempt/short-window beats are ignored
    beats = {0: _beat(0, 0.01), 1: _beat(1, 0.5, age=120.0)}
    assert telemetry.find_stragglers(beats, factor=3.0) == []
    beats = {0: _beat(0, 0.01), 1: _beat(1, 0.5, attempt=1)}
    assert telemetry.find_stragglers(beats, factor=3.0, attempt=0) == []
    beats = {0: _beat(0, 0.01), 1: _beat(1, 0.5, n=1)}
    assert telemetry.find_stragglers(beats, factor=3.0) == []
    # a single rank has no fleet to compare against
    assert telemetry.find_stragglers({0: _beat(0, 0.5)}, factor=3.0) == []


def test_heartbeat_roundtrip(tmp_path):
    tel = telemetry.Telemetry(str(tmp_path), rank=2)
    for i in range(4):
        tel.step(step=i, epoch=0, data_s=0.0, h2d_s=0.0, compute_s=0.01,
                 drain_s=0.0, step_s=0.11)
    tel.close()
    beats = telemetry.read_heartbeats(telemetry.heartbeat_dir(str(tmp_path)))
    assert set(beats) == {2}
    b = beats[2]
    assert b["n"] == 4 and b["step"] == 3
    assert b["step_p50"] == pytest.approx(0.11)
    assert b["host_p50"] == pytest.approx(0.10)
    # garbage file is skipped, not fatal
    with open(os.path.join(telemetry.heartbeat_dir(str(tmp_path)),
                           "rank9.json"), "w") as f:
        f.write("{torn")
    assert set(telemetry.read_heartbeats(
        telemetry.heartbeat_dir(str(tmp_path)))) == {2}


# -- unit: peak flops / satellites ------------------------------------------

def test_resolve_peak_flops(monkeypatch):
    monkeypatch.delenv(telemetry.ENV_PEAK_FLOPS, raising=False)
    assert telemetry.resolve_peak_flops("TPU v5 lite") == 197e12
    assert telemetry.resolve_peak_flops("TPU v5p chip") == 459e12
    assert telemetry.resolve_peak_flops("cpu") is None
    assert telemetry.resolve_peak_flops(None) is None
    monkeypatch.setenv(telemetry.ENV_PEAK_FLOPS, "2.5e12")
    assert telemetry.resolve_peak_flops("cpu") == 2.5e12
    monkeypatch.setenv(telemetry.ENV_PEAK_FLOPS, "garbage")
    assert telemetry.resolve_peak_flops("cpu") is None


def test_peak_hbm_reports_max_across_local_devices(monkeypatch):
    """Satellite: a multi-chip host with imbalance must report the WORST
    device, not device 0."""
    import jax
    from tpudist.utils.profiling import peak_hbm_gb

    class _Dev:
        def __init__(self, peak):
            self._peak = peak

        def memory_stats(self):
            if self._peak is None:
                raise RuntimeError("no stats on this device")
            return {"peak_bytes_in_use": self._peak}

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev(1 * 2**30), _Dev(None),
                                 _Dev(3 * 2**30), _Dev(2 * 2**30)])
    assert peak_hbm_gb() == 3.0
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(None)])
    assert peak_hbm_gb() is None


def test_step_profiler_attempt_suffixed_dirs(tmp_path, monkeypatch):
    """Satellite: a relaunch must not overwrite the previous attempt's
    trace capture."""
    from tpudist.utils.profiling import StepProfiler
    monkeypatch.delenv("TPUDIST_RESTART_COUNT", raising=False)
    p0 = StepProfiler("1:2", str(tmp_path))
    assert p0.logdir == os.path.join(str(tmp_path), "profile", "attempt_0")
    monkeypatch.setenv("TPUDIST_RESTART_COUNT", "2")
    p2 = StepProfiler("1:2", str(tmp_path))
    assert p2.logdir == os.path.join(str(tmp_path), "profile", "attempt_2")
    assert StepProfiler("1:2", str(tmp_path), attempt=5).logdir.endswith(
        os.path.join("profile", "attempt_5"))


def test_faults_observer_sees_firings():
    seen = []
    faults.set_observer(lambda point, step, info: seen.append((point, step)))
    faults.configure("slow_peer:ms=0@step=2;decode_fail:p=1.0")
    faults.maybe_slow_peer(1)                     # gated off: no firing
    faults.maybe_slow_peer(2)
    assert faults.decode_should_fail(11)
    assert seen[0] == ("slow_peer", 2)
    assert seen[1][0] == "decode_fail"
    # a broken observer must not change fault semantics
    faults.set_observer(lambda *a: 1 / 0)
    faults.configure("slow_peer:ms=0")
    faults.maybe_slow_peer(0)                     # no raise


# -- integration: in-process trainer with --telemetry ------------------------

def test_trainer_telemetry_end_to_end(tmp_path, capsys):
    """Acceptance: a CPU run with --telemetry produces schema-valid
    events.<rank>.jsonl with the per-step data-wait/h2d/compute/drain
    breakdown plus compile, checkpoint, and fault events — and summarize
    prints goodput, MFU, and the step-time budget from the run dir."""
    from tpudist.config import Config
    from tpudist.summarize import main as summarize_main
    from tpudist.trainer import Trainer

    out = str(tmp_path / "out")
    cfg = Config(arch="resnet18", num_classes=4, image_size=16,
                 batch_size=16, epochs=1, lr=0.02, workers=2, print_freq=1,
                 synthetic=True, synthetic_size=32, use_amp=False,
                 outpath=out, overwrite="delete", seed=0, telemetry=True,
                 inject="slow_peer:ms=1@step=1")
    t = Trainer(cfg, writer=None)
    t.fit()

    events = load_events(out, strict=True)        # schema-valid or raise
    types = [e["type"] for e in events]
    assert "run_start" in types and "run_end" in types
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 2                        # 32 samples / batch 16
    for e in steps:
        for k in ("data_s", "h2d_s", "compute_s", "drain_s", "step_s"):
            assert isinstance(e[k], float) and e[k] >= 0.0
        assert e["step_s"] >= e["compute_s"]
    assert any(e["type"] == "compile" and e["phase"] == "train_step"
               for e in events)
    assert any(e["type"] == "checkpoint_save" and e["kind"] == "epoch"
               for e in events)
    assert any(e["type"] == "fault" and e["point"] == "slow_peer"
               for e in events)
    assert any(e["type"] == "eval" for e in events)
    prog = next(e for e in events if e["type"] == "program")
    assert prog["flops_per_step"] > 0             # cost_analysis resolved
    end = next(e for e in events if e["type"] == "run_end")
    assert 0.0 < end["goodput"] <= 1.0
    assert end["compile_s"] > 0.0                 # first dispatch attributed
    assert os.path.exists(os.path.join(
        telemetry.heartbeat_dir(out), "rank0.json"))

    # the summarize CLI turns the run dir into the MFU-budget report
    rc = summarize_main([out, "--peak-flops", "1e12"])
    assert rc == 0
    report = capsys.readouterr().out
    assert "goodput" in report
    assert "MFU" in report
    for phrase in ("data wait", "host→device", "device compute",
                   "metric drain"):
        assert phrase in report
    # teardown cleared the process-wide hooks
    assert telemetry.get() is None


def test_launcher_telemetry_gating_and_laziness(tmp_path):
    """The launcher must never create the run dir out from under rank 0's
    --overwrite handling: auto mode requires --telemetry in the command and
    defers all filesystem side effects until a rank created heartbeats/."""
    import argparse
    from tpudist.launch import _launcher_telemetry

    args = argparse.Namespace(telemetry_dir="")
    out = str(tmp_path / "run")
    # no --telemetry in the command → no launcher telemetry at all
    assert _launcher_telemetry(
        args, ["python", "-m", "tpudist", "--outpath", out]) is None
    # --telemetry but no outpath → nothing to attach to
    assert _launcher_telemetry(
        args, ["python", "-m", "tpudist", "--telemetry"]) is None

    lazy = _launcher_telemetry(
        args, ["python", "-m", "tpudist", "--telemetry", "--outpath", out])
    assert lazy is not None
    lazy.emit("launcher_start", attempt=0, nprocs=2)
    assert not os.path.exists(out)                 # buffered, no side effect
    # a rank sets the dir up (what Telemetry.__init__ does in the trainer)
    os.makedirs(telemetry.heartbeat_dir(out))
    lazy.emit("straggler", attempt=0, straggler_rank=1, factor=5.0)
    events = [json.loads(ln) for ln in
              open(os.path.join(out, "events.launcher.jsonl"))]
    for ev in events:
        telemetry.validate_event(ev)
    # buffered event flushed first, original order kept
    assert [e["type"] for e in events] == ["launcher_start", "straggler"]

    # explicit --telemetry-dir stays eager (operator named the dir)
    eager_dir = str(tmp_path / "explicit")
    eager = _launcher_telemetry(
        argparse.Namespace(telemetry_dir=eager_dir), ["whatever"])
    eager.emit("launcher_start", attempt=0, nprocs=1)
    assert os.path.exists(os.path.join(eager_dir, "events.launcher.jsonl"))


def test_analyze_restart_wall_includes_crashed_final_attempt():
    """goodput_incl_restarts: a final attempt that died without a run_end
    still spent wall time — its steps must extend the denominator."""
    ev = _synthetic_run(n_steps=4, step_s=1.0, compile_s=2.0)  # attempt 0
    t_end = ev[-1]["t"]
    # attempt 1: crashes after 2 steps at t_end+10 .. t_end+12, no run_end
    for i in range(2):
        ev.append(_step_ev(t=t_end + 10.0 + i, step=i, attempt=1,
                           step_s=1.0))
    for e in ev:
        e.setdefault("attempt", 0)
    a = analyze(ev)
    # productive: 4 + 2 steps of 1s; wall: run_start t=0 → last step t
    assert a["wall_incl_restarts_s"] == pytest.approx(t_end + 11.0)
    assert a["goodput_incl_restarts"] == pytest.approx(6.0 / (t_end + 11.0))


# -- e2e: launcher flags the slow_peer straggler -----------------------------

_STRAGGLER_CHILD = r"""
import os, time
import jax
import jax.numpy as jnp

from tpudist import faults
from tpudist.telemetry import Telemetry

rank = int(os.environ["TPUDIST_PROCESS_ID"])
tel = Telemetry(os.environ["TPUDIST_TEST_OUT"], rank=rank)
f = jax.jit(lambda a: (a @ a).sum())
x = jnp.ones((128, 128))
t_prev = time.time()
for s in range(14):
    faults.maybe_slow_peer(s)          # the injected rank stalls host-side
    t_c = time.time()
    f(x).block_until_ready()
    compute_s = time.time() - t_c
    step_s = time.time() - t_prev
    tel.step(step=s, epoch=0, data_s=0.0, h2d_s=0.0, compute_s=compute_s,
             drain_s=0.0, step_s=step_s,
             compile_s=step_s if s == 0 else 0.0)
    t_prev = time.time()
tel.close()
print(f"RANK{rank}_STEPS_DONE", flush=True)
"""


def test_launch_flags_slow_peer_straggler(tmp_path, mp_timeout):
    """Acceptance e2e: slow_peer on rank 1 of a 2-rank launch → the
    launcher's heartbeat aggregation flags rank 1 in its output and in
    events.launcher.jsonl (see module docstring for why the ranks step
    independently on this backend)."""
    out = tmp_path / "out"
    out.mkdir()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["TPUDIST_TEST_OUT"] = str(out)
    r = subprocess.run(
        [sys.executable, "-m", "tpudist.launch", "--nprocs", "2",
         "--devices-per-proc", "1",
         "--telemetry-dir", str(out), "--straggler-factor", "3",
         "--inject", "slow_peer:ms=400@rank=1",
         "--", sys.executable, "-c", _STRAGGLER_CHILD],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=mp_timeout(2, compile_cost=1.5))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "RANK0_STEPS_DONE" in r.stdout and "RANK1_STEPS_DONE" in r.stdout
    assert "straggler: rank 1" in r.stderr, r.stderr[-3000:]
    assert "straggler: rank 0" not in r.stderr

    # launcher event stream recorded it too (plus the attempt start)
    levents = [json.loads(ln) for ln in
               (out / "events.launcher.jsonl").read_text().splitlines()]
    for ev in levents:
        telemetry.validate_event(ev)
    assert any(e["type"] == "launcher_start" for e in levents)
    flags = [e for e in levents if e["type"] == "straggler"]
    assert len(flags) == 1 and flags[0]["straggler_rank"] == 1
    assert flags[0]["factor"] >= 3.0

    # both ranks streamed schema-valid events, and the offline analysis
    # (summarize path) reaches the same verdict from the event stream alone
    events = load_events(str(out), strict=True)
    a = analyze(events)
    assert set(a["ranks"]) == {0, 1}
    assert a["per_rank"][1]["host_p50"] > 3 * a["per_rank"][0]["host_p50"]
    assert [s["straggler_rank"] for s in a["stragglers"]] == [1]
