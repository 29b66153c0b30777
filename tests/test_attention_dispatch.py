"""Measurement-honest attention dispatch (tpudist/ops/attention_dispatch):
the ISSUE-5 honesty invariants, provable without a TPU — synthetic timings
feed the dispatcher through the ``measure_pair`` hook, the cache round-trips
per device_kind, invalidation re-measures, ``--flash auto`` on this CPU
container resolves to XLA without touching Pallas, and the decision rides
the telemetry stream into ``summarize`` and the bench history."""

import json
import os
import subprocess
import sys

import pytest

from tpudist.ops import attention_dispatch as ad

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHAPE = (8, 197, 12, 64, "bfloat16")      # batch, seq, heads, head_dim, dtype
TPU = dict(platform="tpu", device_kind="fake-tpu-v9")


def _pair(flash_ms, xla_ms):
    return lambda: (flash_ms, xla_ms)


def _boom():
    raise AssertionError("dispatcher measured when it must not")


# -- the honesty invariant ---------------------------------------------------

def test_auto_never_selects_a_losing_kernel(tmp_path):
    """Sweep synthetic timing pairs: whichever side loses its own
    measurement is never dispatched, and a tie keeps the XLA baseline."""
    for i, (flash_ms, xla_ms) in enumerate(
            [(1.0, 2.0), (2.0, 1.0), (1.0, 1.0), (0.5, 0.49), (3.7, 9.1)]):
        d = ad.decide(*SHAPE, mode="auto", cache_dir=str(tmp_path / str(i)),
                      measure_pair=_pair(flash_ms, xla_ms), **TPU)
        assert d["source"] == "measured"
        if flash_ms < xla_ms:
            assert d["kernel"] == "flash", (flash_ms, xla_ms, d)
        else:                         # loss OR tie → the compiler baseline
            assert d["kernel"] == "xla", (flash_ms, xla_ms, d)
        assert 0.0 <= d["margin"] <= 1.0


def test_forced_modes_do_not_measure(tmp_path):
    for mode, kernel in (("on", "flash"), ("off", "xla")):
        d = ad.decide(*SHAPE, mode=mode, cache_dir=str(tmp_path),
                      measure_pair=_boom, **TPU)
        assert d["kernel"] == kernel and d["source"] == "forced"
    with pytest.raises(ValueError, match="auto"):
        ad.decide(*SHAPE, mode="fast")


def test_cpu_auto_resolves_xla_without_measuring(tmp_path):
    """Acceptance: on this CPU container `--flash auto` resolves to XLA
    attention without running (meaningless interpreter) measurements —
    platform may be auto-detected or explicit."""
    d = ad.decide(*SHAPE, mode="auto", cache_dir=str(tmp_path),
                  measure_pair=_boom)            # platform auto-detect: cpu
    assert d["kernel"] == "xla" and d["source"] == "platform"
    d = ad.decide(*SHAPE, mode="auto", cache_dir=str(tmp_path),
                  measure_pair=_boom, platform="gpu")
    assert d["kernel"] == "xla" and d["source"] == "platform"


# -- cache behavior ----------------------------------------------------------

def test_cache_round_trips_per_device_kind(tmp_path):
    cache = str(tmp_path)
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(1.0, 2.0), **TPU)
    assert d["kernel"] == "flash" and d["source"] == "measured"
    # Same kind + shape: served from cache, measuring again is an error.
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache, measure_pair=_boom,
                  **TPU)
    assert d["kernel"] == "flash" and d["source"] == "cache" \
        and d["cache_hit"]
    assert d["flash_ms"] == 1.0 and d["xla_ms"] == 2.0
    # Another device kind must NOT inherit the verdict (its own file, its
    # own measurement — a v4 win must never dispatch a v5e).
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(5.0, 1.0), platform="tpu",
                  device_kind="fake-tpu-v10")
    assert d["kernel"] == "xla" and d["source"] == "measured"
    # ...and the first kind's verdict is untouched.
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache, measure_pair=_boom,
                  **TPU)
    assert d["kernel"] == "flash"
    # A different shape within one kind is its own entry.
    d = ad.decide(8, 2048, 12, 64, "bfloat16", mode="auto", cache_dir=cache,
                  measure_pair=_pair(9.0, 1.0), **TPU)
    assert d["kernel"] == "xla" and d["source"] == "measured"
    files = [n for n in os.listdir(cache)
             if n.startswith("attention_dispatch.")]
    assert len(files) == 2, files


def test_cleared_or_invalidated_cache_remeasures(tmp_path):
    cache = str(tmp_path)
    ad.decide(*SHAPE, mode="auto", cache_dir=cache,
              measure_pair=_pair(1.0, 2.0), **TPU)
    # clear_cache → re-measure (the flipped verdict proves it re-ran).
    assert ad.clear_cache(device_kind=TPU["device_kind"], cache_dir=cache) == 1
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(2.0, 1.0), **TPU)
    assert d["kernel"] == "xla" and d["source"] == "measured"
    # A kernel-rev bump orphans the entry: stamp a stale rev and watch the
    # dispatcher re-measure instead of trusting the old kernel's record.
    path = ad.cache_path(TPU["device_kind"], cache)
    obj = json.load(open(path))
    for e in obj["entries"].values():
        e["kernel_rev"] = -1
    json.dump(obj, open(path, "w"))
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(1.0, 2.0), **TPU)
    assert d["kernel"] == "flash" and d["source"] == "measured"
    # A torn/corrupt cache file degrades to re-measuring, never a crash.
    with open(path, "w") as f:
        f.write("{not json")
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(2.0, 1.0), **TPU)
    assert d["kernel"] == "xla" and d["source"] == "measured"
    # refresh=True bypasses a valid entry on demand.
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache, refresh=True,
                  measure_pair=_pair(1.0, 9.0), **TPU)
    assert d["source"] == "measured" and d["kernel"] == "flash"


def test_lookup_is_trace_safe_and_defaults_to_xla(tmp_path):
    """The model-level path: cache/platform only, never measures; an
    unmeasured kernel is never the default on TPU."""
    cache = str(tmp_path)
    shape = (4, 197, 12, 64, "float32")
    # CPU → False (and no cache dir even exists).
    assert ad.lookup(*shape, cache_dir=cache) is False
    # TPU with no entry → False: unmeasured ≠ dispatched.
    assert ad.lookup(*shape, cache_dir=cache, **TPU) is False
    # A measured flash win flips it...
    ad.decide(*shape, mode="auto", cache_dir=cache,
              measure_pair=_pair(1.0, 2.0), **TPU)
    assert ad.lookup(*shape, cache_dir=cache, **TPU) is True
    # ...for exactly that shape/kind, nothing else.
    assert ad.lookup(4, 196, 12, 64, "float32", cache_dir=cache,
                     **TPU) is False
    assert ad.lookup(*shape, cache_dir=cache, platform="tpu",
                     device_kind="fake-tpu-v10") is False
    # train=False is a separate verdict (bwd-heavy losses don't transfer).
    assert ad.lookup(*shape, train=False, cache_dir=cache, **TPU) is False


def test_flash_eligible_policy():
    ok, _ = ad.flash_eligible(seq=197, head_dim=64)
    assert ok
    ok, why = ad.flash_eligible(seq=49, head_dim=32, bias=True)
    assert not ok and "bias" in why
    ok, why = ad.flash_eligible(seq=4, head_dim=64)
    assert not ok and "tile" in why
    ok, why = ad.flash_eligible(seq=2048, head_dim=512)
    assert not ok and "head_dim" in why


# -- telemetry / summarize surfaces ------------------------------------------

def test_decision_event_is_schema_valid(tmp_path):
    from tpudist.telemetry import validate_event
    d = ad.decide(*SHAPE, mode="auto", cache_dir=str(tmp_path),
                  measure_pair=_pair(1.5, 2.5), **TPU)
    ev = {"t": 1.0, "type": "attention_dispatch", "rank": 0, "attempt": 0,
          **ad.event_fields(d)}
    validate_event(ev)                     # raises on schema violation
    assert ev["kernel"] == "flash" and ev["source"] == "measured"
    assert ev["flash_ms"] == 1.5 and ev["dispatch_device_kind"] \
        == TPU["device_kind"]


def _mk_events():
    """Synthetic but schema-valid event stream with a dispatch decision and
    an introspected compile event, for the summarize surfaces."""
    from tpudist.telemetry import validate_event
    base = {"rank": 0, "attempt": 0}
    events = [
        {"t": 0.0, "type": "run_start", "platform": "tpu",
         "n_devices": 1, "arch": "vit_b_16", "global_batch": 128,
         "device_kind": "TPU v4", **base},
        {"t": 0.5, "type": "attention_dispatch", "kernel": "xla",
         "mode": "auto", "source": "measured", "flash_ms": 4.4,
         "xla_ms": 3.4, "margin": 0.22,
         "shape_key": "b16_t197_h12_d64_bfloat16_train_full", **base},
        {"t": 1.0, "type": "program", "flops_per_step": 2.0e12, **base},
        {"t": 1.1, "type": "compile", "seconds": 9.0,
         "phase": "cost_analysis", "flops": 2.0e12, "bytes_accessed": 1.0e9,
         "hbm_compiled_bytes": 2.0e9, "collective_ops": 0,
         "ops_mxu": 120, "ops_vpu": 900, "ops_reduce": 60, "ops_copy": 400,
         "ops_collective": 0, "ops_control": 50, "ops_other": 7, **base},
    ]
    for i in range(4):
        events.append({"t": 2.0 + i, "type": "step", "step": i, "epoch": 0,
                       "data_s": 0.01, "h2d_s": 0.01, "compute_s": 0.01,
                       "drain_s": 0.001, "step_s": 0.04, **base})
    for e in events:
        validate_event(e)
    return events


def test_summarize_dispatch_line_and_op_attribution():
    from tpudist.summarize import analyze, format_report
    a = analyze(_mk_events(), peak_flops=275e12)
    ad_out = a["attention_dispatch"]
    assert ad_out["kernel"] == "xla" and ad_out["source"] == "measured"
    at = a["op_attribution"]
    # MXU roofline: 2e12 flops / 275e12 = 7.27 ms lower bound; HBM: 1e9 /
    # 1228e9 (v4 table) = 0.81 ms; measured compute p50 = 10 ms → mxu-bound
    # with ~2.7 ms unattributed.
    assert at["bound"] == "mxu"
    assert at["mxu_ms_lb"] == pytest.approx(7.273, abs=1e-3)
    assert at["hbm_ms_lb"] == pytest.approx(0.814, abs=1e-3)
    assert at["residual_ms"] == pytest.approx(10.0 - 7.273, abs=1e-2)
    assert at["op_counts"]["vpu"] == 900
    rep = format_report(a)
    assert "attention dispatch: xla attention (mode auto, measured now" \
        in rep
    assert "flash 4.400 ms vs xla 3.400 ms, margin 22.0%" in rep
    assert "op-category attribution" in rep and "mxu-bound" in rep
    assert "MXU roofline" in rep and "unattributed" in rep
    assert "vpu x900" in rep


def test_op_category_counts_rollup():
    from tpudist.obs.xla_introspect import hlo_op_census, op_category_counts
    hlo = "\n".join([
        "%p0 = f32[8,128]{1,0} parameter(0)",
        "%d = f32[8,8]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}",
        "%c = f32[8,128]{1,0:T(8,128)} copy(%p0)",
        "%a = f32[8,128]{1,0} add(%c, %c)",
        "%r = f32[8]{0} reduce(%a, %a), dimensions={1}",
        "%ar = f32[8]{0} all-reduce(%r), replica_groups={}",
        "%f = f32[8]{0} fusion(%ar), kind=kLoop",
        "%t = (f32[8]{0}) tuple(%f)",
    ])
    cats = op_category_counts(hlo_op_census(hlo)["op_counts"])
    assert cats["mxu"] == 1 and cats["vpu"] == 1 and cats["reduce"] == 1
    assert cats["copy"] == 1 and cats["collective"] == 1
    assert cats["control"] == 2          # parameter + tuple; fusion skipped


# -- both clients ride the one generic layer ---------------------------------

def test_clients_are_thin_over_the_generic_layer():
    """No second copy of the cache, the timing harness or the shared
    verdict: the attention module's surfaces ARE the generic layer's
    objects, and both clients' decisions go through ``dispatch.decide`` and
    ``dispatch.shared_decision``."""
    import inspect
    from tpudist.ops import comm_dispatch as cd, dispatch
    assert ad.load_cache is dispatch.load_cache
    assert ad.save_cache is dispatch.save_cache
    assert ad.measure_ms is dispatch.measure_ms
    assert ad.default_cache_dir is dispatch.default_cache_dir
    assert ad.MODES is dispatch.MODES
    for client in (ad, cd):
        assert client.cache_path.func is dispatch.cache_path
        assert client.clear_cache.func is dispatch.clear_cache
        assert client.cache_path.args == client.clear_cache.args \
            == (client.CLIENT,)
        assert "dispatch.decide(" in inspect.getsource(client.decide)
        assert "dispatch.shared_decision(" in inspect.getsource(
            client.shared_decision)
    # and the layer holds nothing that neither client nor the trainer calls
    public = {n for n, v in vars(dispatch).items()
              if inspect.isfunction(v) and not n.startswith("_")}
    assert public == {"default_cache_dir", "cache_path", "load_cache",
                      "save_cache", "clear_cache", "measure_ms", "decide",
                      "lookup", "shared_decision"}


# -- regression-gate coverage of kernel perf ---------------------------------

def test_regress_gates_ms_series_on_increase():
    """`unit: ms` rows (the bench_flash series) regress UPWARD: +20% trips
    the gate, −20% (an improvement) passes, and throughput series keep the
    downward gate."""
    from tpudist.regress import analyze_history

    def rows(vals, unit="ms", metric="attn_vitb_224_flash_fwdbwd_ms_tpu"):
        return [{"metric": metric, "value": v, "unit": unit} for v in vals]

    base = [4.0, 4.1, 3.9, 4.0, 4.05]
    assert analyze_history(rows(base + [4.02]))["status"] == "pass"
    v = analyze_history(rows(base + [4.9]))
    assert v["status"] == "regression" and v["lower_is_better"]
    assert "above the trailing median" in v["reasons"][0]
    assert analyze_history(rows(base + [3.2]))["status"] == "pass"
    # Throughput series unchanged: a DROP still trips (the trainer's rows
    # and bench_prefetch's).
    for metric in ("resnet18_224_bf16_train_images_per_sec_1chip",
                   "prefetch_on_resnet18_224_images_per_sec_tpu"):
        tput = rows([1000, 1001, 999, 1000, 1002, 800], unit="images/sec",
                    metric=metric)
        v = analyze_history(tput)
        assert v["status"] == "regression" and not v["lower_is_better"]
    # Explicit override beats the unit heuristic.
    odd = rows([10, 10, 10, 10, 10, 14], unit="points")
    for r in odd:
        r["lower_is_better"] = True
    assert analyze_history(odd)["status"] == "regression"


def test_bench_history_embedding_in_process(tmp_path, monkeypatch):
    """The bench_flash history emission, unit level: fwd and fwd+bwd become
    separate series, the flash/XLA pair shares one embedded verdict, error
    rows stay out, and a TPU-platform call caches the verdict it derived
    from the rows (measure_pair hook = the rows' own numbers)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_flash", os.path.join(REPO, "benchmarks", "bench_flash.py"))
    bf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bf)

    hist = tmp_path / "hist.jsonl"
    monkeypatch.setenv("TPUDIST_DISPATCH_CACHE", str(tmp_path / "cache"))
    monkeypatch.setenv("TPUDIST_BENCH_HISTORY", str(hist))

    def row(label, value):
        return {"metric": f"attn_vitb_224_{label}_ms_tpu", "value": value,
                "unit": "ms", "shape": [8, 197, 12, 64], "dtype": "bfloat16"}

    rows = {"flash_fwd": row("flash_fwd", 3.4),
            "xla_fwd": row("xla_fwd", 3.6),
            "flash_fwdbwd": row("flash_fwdbwd", 4.4),
            "xla_fwdbwd": {**row("xla_fwdbwd", 0.0), "value": None,
                           "error": "oom"}}
    bf._embed_dispatch_and_append(rows, 8, 197, 12, 64, "bfloat16", "tpu")
    hist_rows = [json.loads(line) for line in open(hist)]
    metrics = {r["metric"] for r in hist_rows}
    assert metrics == {"attn_vitb_224_flash_fwd_ms_tpu",
                       "attn_vitb_224_xla_fwd_ms_tpu",
                       "attn_vitb_224_flash_fwdbwd_ms_tpu"}
    fwd = next(r for r in hist_rows
               if r["metric"] == "attn_vitb_224_flash_fwd_ms_tpu")
    # fwd pair: flash won its own measurement → dispatched, verdict shared.
    assert fwd["dispatch"] == {"kernel": "flash", "source": "measured",
                               "flash_ms": 3.4, "xla_ms": 3.6}
    assert fwd["measured_at"]
    # fwdbwd pair: XLA side errored → no verdict for that pass.
    bwd = next(r for r in hist_rows
               if r["metric"] == "attn_vitb_224_flash_fwdbwd_ms_tpu")
    assert "dispatch" not in bwd
    # The TPU verdict landed in the dispatch cache (bench = cache warm):
    # eval-shape lookup now dispatches flash on this fake platform.
    assert ad.lookup(8, 197, 12, 64, "bfloat16", train=False,
                     platform="tpu", device_kind="fake-bench-kind",
                     cache_dir=str(tmp_path / "cache")) is False  # other kind
    import glob as _glob
    assert _glob.glob(str(tmp_path / "cache" / "attention_dispatch.*.json"))


@pytest.mark.slow
def test_bench_flash_cpu_run_stays_out_of_history(tmp_path):
    """A CPU bench_flash run still prints its rows (capability probing, the
    dispatch verdict embedded on the flash/XLA pairs) but appends NOTHING
    to the bench history and caches NO verdict — interpreter timings are
    not measurements, and a gateable ms series of noise would trip the
    upward regression gate on nonsense. (The TPU-path history emission is
    covered in-process by test_bench_history_embedding_in_process.)"""
    hist = tmp_path / "hist.jsonl"
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", TPUDIST_BENCH_HISTORY=str(hist),
               TPUDIST_DISPATCH_CACHE=str(tmp_path / "cache"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_flash.py"),
         "--steps", "2"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rows NOT appended to bench history" in r.stderr
    assert not hist.exists()
    assert not os.path.isdir(tmp_path / "cache")
    # stdout still carries the capability rows (printed at measurement
    # time, before the history/verdict stage runs).
    out_rows = [json.loads(line) for line in r.stdout.splitlines()
                if line.startswith("{")]
    assert any(row["metric"] == "attn_tiny_64_flash_fwd_ms_cpu"
               for row in out_rows)


def test_decide_and_lookup_enforce_static_eligibility(tmp_path):
    """Shapes the kernel cannot tile never reach a measurement: auto
    resolves them to XLA with source 'ineligible' BEFORE any platform or
    device question, and the trace-safe lookup refuses them even with a
    (stale) flash-winning cache entry."""
    d = ad.decide(8, 2, 12, 64, "float32", mode="auto",
                  cache_dir=str(tmp_path), measure_pair=_boom, **TPU)
    assert d["kernel"] == "xla" and d["source"] == "ineligible"
    assert "tile" in d["reason"]
    d = ad.decide(8, 2048, 12, 512, "bfloat16", mode="auto",
                  cache_dir=str(tmp_path), measure_pair=_boom, **TPU)
    assert d["source"] == "ineligible" and "head_dim" in d["reason"]
    # Forced `on` deliberately bypasses eligibility (tiny-shape A/B work).
    d = ad.decide(8, 2, 12, 64, "float32", mode="on", measure_pair=_boom)
    assert d["kernel"] == "flash" and d["source"] == "forced"
    assert ad.lookup(8, 2, 12, 64, "float32", cache_dir=str(tmp_path),
                     **TPU) is False
    # The ineligible event still schema-validates, reason included.
    from tpudist.telemetry import validate_event
    ev = {"t": 0.0, "type": "attention_dispatch", "rank": 0, "attempt": 0,
          **ad.event_fields(ad.decide(8, 2, 12, 64, "float32",
                                      mode="auto"))}
    validate_event(ev)
    assert ev["source"] == "ineligible" and "tile" in ev["reason"]


def test_shared_decision_gang_agreement(tmp_path):
    """Multi-host agreement: the primary decides and publishes
    attention_dispatch.json into the shared run dir; peers read it instead
    of running their own (noisy) probe; a peer that times out falls back
    to deciding independently."""
    calls = []

    def decide_fn():
        calls.append(1)
        return {"kernel": "flash", "mode": "auto", "source": "measured",
                "flash_ms": 1.0, "xla_ms": 2.0}

    dec = ad.shared_decision(str(tmp_path), True, decide_fn)
    assert dec["kernel"] == "flash" and calls == [1]
    assert json.load(open(tmp_path / "attention_dispatch.json"))[
        "kernel"] == "flash"
    # Peer: reads the primary's verdict, never probes.
    dec = ad.shared_decision(str(tmp_path), False,
                             lambda: (_ for _ in ()).throw(
                                 AssertionError("peer must not measure")))
    assert dec["kernel"] == "flash" and dec["shared_from_primary"] == 1
    # Peer with no published verdict: bounded wait, then its own decision.
    logs = []
    dec = ad.shared_decision(str(tmp_path / "empty"), False, decide_fn,
                             timeout_s=0.3, poll_s=0.05, log=logs.append)
    assert dec["kernel"] == "flash" and len(calls) == 2
    assert logs and "did not appear" in logs[0]


def test_shared_decision_rejects_stale_and_propagates_failure(tmp_path):
    """Post-review hardening: the run dir can carry a decision file from a
    previous attempt or run (--overwrite keep + restart, possibly across a
    KERNEL_REV bump) — peers must not adopt one whose attempt stamp, shape
    key, or kernel rev no longer matches (the exact mixed-backend failure
    shared_decision exists to prevent). And a primary whose probe raises
    must publish the failure so peers fail over immediately and uniformly
    instead of burning the full timeout and then measuring into a
    possibly-split gang."""
    import time as _time

    path = tmp_path / "attention_dispatch.json"
    own = lambda: {"kernel": "xla", "mode": "auto",        # noqa: E731
                   "source": "platform"}
    good = {"kernel": "flash", "mode": "auto", "source": "measured",
            "key": "K1", "attempt": 0}
    for stale in (dict(good, attempt=3),                   # previous attempt
                  dict(good, key="K0"),                    # previous shape
                  dict(good, kernel_rev=ad.kernel_rev() + 1)):  # old kernel
        path.write_text(json.dumps(stale))
        dec = ad.shared_decision(str(tmp_path), False, own,
                                 expect_key="K1", timeout_s=0.2, poll_s=0.05)
        assert dec["kernel"] == "xla", stale
        assert "shared_from_primary" not in dec, stale
    # Matching attempt + key + rev: adopted.
    path.write_text(json.dumps(dict(good, kernel_rev=ad.kernel_rev())))
    dec = ad.shared_decision(str(tmp_path), False,
                             lambda: (_ for _ in ()).throw(
                                 AssertionError("peer must not measure")),
                             expect_key="K1", timeout_s=1.0, poll_s=0.05)
    assert dec["kernel"] == "flash" and dec["shared_from_primary"] == 1
    # Primary probe failure: the exception propagates on the primary AND is
    # published, so a peer raises well under its timeout — every rank then
    # degrades to the caller's model-level-lookup path, identically.
    def boom():
        raise ValueError("pallas exploded")
    with pytest.raises(ValueError, match="pallas exploded"):
        ad.shared_decision(str(tmp_path), True, boom, expect_key="K1")
    t0 = _time.time()
    with pytest.raises(RuntimeError, match="pallas exploded"):
        ad.shared_decision(str(tmp_path), False, own,
                           expect_key="K1", timeout_s=60.0, poll_s=0.05)
    assert _time.time() - t0 < 10


# -- end-to-end: trainer + smoke chain ---------------------------------------

def test_flash_smoke_script(tmp_path, mp_timeout):
    """Satellite: tools/flash_smoke.sh chains cache round-trip →
    forced-flash train step → telemetry run whose summarize shows the
    dispatch event."""
    env = dict(os.environ)
    env["TPUDIST_FLASH_SMOKE_DIR"] = str(tmp_path)
    r = subprocess.run(["bash", os.path.join(REPO, "tools",
                                             "flash_smoke.sh")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=mp_timeout(1, compile_cost=3.0))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "FLASH_SMOKE_OK"


def test_trainer_emits_dispatch_event_on_cpu(tmp_path):
    """A --telemetry ViT Trainer on this CPU container resolves auto→XLA
    outside the trace (model cloned with flash=False), logs the decision,
    and emits the schema-valid attention_dispatch event — WITHOUT fit():
    the decision is a construction-time fact."""
    from tpudist.config import Config
    from tpudist.telemetry import validate_event
    from tpudist.trainer import Trainer

    out = tmp_path / "run"
    cfg = Config(arch="vit_b_32", num_classes=4, image_size=32, batch_size=8,
                 epochs=1, workers=0, synthetic=True, synthetic_size=8,
                 use_amp=False, outpath=str(out), overwrite="delete",
                 seed=0, telemetry=True)
    t = Trainer(cfg, writer=None)
    try:
        dec = t.flash_decision
        # The 2-token workload is statically ineligible (below one (8,128)
        # tile), resolved before the platform is even consulted.
        assert dec is not None and dec["kernel"] == "xla" \
            and dec["source"] == "ineligible"
        assert "tile" in dec["reason"]
        assert t.model.flash is False
        # per-device batch 1, (32/32)² + cls = 2 tokens, 12 heads × 64.
        assert dec["key"] == "b1_t2_h12_d64_float32_train_full"
    finally:
        from tpudist import telemetry as telemetry_lib
        t.telemetry.close()
        telemetry_lib.set_current(None)
    events = [json.loads(line)
              for line in open(out / "events.0.jsonl") if line.strip()]
    for e in events:
        validate_event(e)
    disp = [e for e in events if e["type"] == "attention_dispatch"]
    assert len(disp) == 1
    assert disp[0]["kernel"] == "xla" and disp[0]["mode"] == "auto" \
        and disp[0]["source"] == "ineligible"


# -- PR 28: the probe times the model's own call; KERNEL_REV 3 ----------------

def test_probe_times_the_branches_the_model_calls(monkeypatch):
    """One helper, ``ring_attention.qkv_attention``: ``MultiHeadAttention``
    calls it (by identity), and the probe's two timed functions are that
    call with ``flash`` on and off, on ONE fused [B, T, H, 3, D] array,
    differentiated with respect to it when ``train``."""
    import jax.numpy as jnp
    import numpy as np
    import importlib
    vit = importlib.import_module("tpudist.models.vit")
    # (`tpudist.parallel` re-exports the function under the module's name)
    ring_attention = importlib.import_module("tpudist.parallel.ring_attention")

    assert vit.qkv_attention is ring_attention.qkv_attention
    calls = []
    real = ring_attention.qkv_attention

    def spy(qkv, causal=False, flash=False):
        calls.append((qkv.shape, causal, flash))
        return real(qkv, causal=causal, flash=flash)

    monkeypatch.setattr(ring_attention, "qkv_attention", spy)
    timed = []
    monkeypatch.setattr(ad, "measure_ms", lambda fn, args, steps, warmup: (
        timed.append((fn, args)), float(len(timed)))[1])
    assert ad.measure_attention(1, 16, 2, 64, "float32", True, True) == (
        1.0, 2.0)
    (flash_c, (qkv,)), (xla_c, (qkv2,)) = timed
    assert qkv is qkv2 and qkv.shape == (1, 16, 2, 3, 64)
    got, want = flash_c(qkv), xla_c(qkv)        # grads wrt the projection
    assert got.shape == want.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert calls == [((1, 16, 2, 3, 64), True, True),
                     ((1, 16, 2, 3, 64), True, False)]
    # eval: the same two calls, forward only
    calls.clear()
    flash_f, xla_f = ad.probe_fns(False, False)
    assert flash_f(qkv).shape == xla_f(qkv).shape == (1, 16, 2, 64)
    assert [c[1:] for c in calls] == [(False, True), (False, False)]
    assert jnp.issubdtype(flash_f(qkv).dtype, jnp.floating)


@pytest.mark.parametrize("stale_rev", [7, 6, 5, 4, 3, 2, 1])
def test_verdict_of_an_older_kernel_rev_is_not_used(tmp_path, stale_rev):
    """KERNEL_REV is 8 (an entry for queries and keys wider than the values,
    latent attention's training form; rev 7: under the mask of training by
    diffusion over blocks the streaming kernels run the part of an edge
    tile that the mask reaches): a rev-7 verdict in the cache, win or loss,
    is stale — ``decide`` measures again and the trace-time ``lookup`` does
    not dispatch on it."""
    assert ad.kernel_rev() == 8
    cache = str(tmp_path)
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(1.0, 2.0), **TPU)
    assert d["kernel"] == "flash" and d["kernel_rev"] == 8
    assert ad.lookup(*SHAPE, cache_dir=cache, **TPU) is True
    path = ad.cache_path(TPU["device_kind"], cache)
    obj = json.load(open(path))
    for e in obj["entries"].values():
        e["kernel_rev"] = stale_rev
    json.dump(obj, open(path, "w"))
    assert ad.lookup(*SHAPE, cache_dir=cache, **TPU) is False
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache,
                  measure_pair=_pair(16.32, 4.55), **TPU)
    assert d["source"] == "measured" and d["kernel"] == "xla" \
        and d["kernel_rev"] == 8
    d = ad.decide(*SHAPE, mode="auto", cache_dir=cache, measure_pair=_boom,
                  **TPU)
    assert d["source"] == "cache" and d["kernel"] == "xla"


def test_dispatch_names_the_schedule():
    """The kernel's own shape test, for the trainer's log line and the
    telemetry event: ViT-B/16's 197 tokens take the whole-sequence
    schedule, 2,048 stream; the event carries it when the decision does."""
    from tpudist.telemetry import validate_event
    assert ad.schedule(197, 12, 64, "bfloat16") == "whole_seq"
    assert ad.schedule(2048, 12, 64, "bfloat16") == "streaming"
    dec = {"kernel": "flash", "mode": "auto", "source": "measured",
           "key": "k", "flash_ms": 2.1, "xla_ms": 5.6, "margin": 0.6,
           "schedule": "whole_seq"}
    fields = ad.event_fields(dec)
    assert fields["schedule"] == "whole_seq"
    validate_event({"type": "attention_dispatch", "t": 0.0, "rank": 0,
                    "attempt": 0,
                    **fields})
    dec.pop("schedule")
    assert "schedule" not in ad.event_fields(dec)


def test_dispatch_says_how_far_the_kernel_engages():
    """What the decision says of each attention workload's programs
    (``ad.program``) rides the event as one list a field, in the shape
    keys' order: ViT-B/16's one whole-sequence program of twelve heads, and
    a decoder's windowed and full layers on the streaming schedule, a
    key-value head's eight query heads a program."""
    from tpudist.telemetry import validate_event
    vit = ad.program(197, 12, 64, "bfloat16", fused=True)
    assert vit == {"schedule": "whole_seq", "heads_per_program": 12,
                   "block_q": 197, "block_k": 197, "band_fill": 1.0}
    layers = [ad.program(8192, 32, 128, "bfloat16", kv_heads=4, causal=True,
                         window=w) for w in (1024, None)]
    dec = {"kernel": "flash", "mode": "on", "source": "forced", "key": "k",
           "schedule": "streaming", "programs": layers}
    fields = ad.event_fields(dec)
    validate_event({"type": "attention_dispatch", "t": 0.0, "rank": 0,
                    "attempt": 0, **fields})
    assert set(ad.PROGRAM_FIELDS) <= set(fields)
    assert fields["heads_per_program"] == [8, 8]
    assert fields["block_q"] == [p["block_q"] for p in layers]
    windowed, full = fields["band_fill"]
    assert 0.75 <= windowed <= 1.0 and 0.88 <= full <= 1.0
    json.dumps(fields)
    dec.pop("programs")
    assert not set(ad.PROGRAM_FIELDS) & set(ad.event_fields(dec))


def test_trainer_logs_the_schedule_when_the_kernel_runs(tmp_path):
    """``--flash on`` at an eligible shape: the trainer's decision, its
    ``=> attention dispatch:`` line and its event say which schedule the
    shape selects."""
    from tpudist.config import Config
    from tpudist.trainer import Trainer

    out = tmp_path / "run"
    cfg = Config(arch="vit_b_32", num_classes=4, image_size=128,
                 batch_size=8, epochs=1, workers=0, synthetic=True,
                 synthetic_size=8, use_amp=False, outpath=str(out),
                 overwrite="delete", seed=0, telemetry=True, flash="on")
    t = Trainer(cfg, writer=None)
    try:
        dec = t.flash_decision
        assert dec["kernel"] == "flash" and dec["source"] == "forced"
        # (128/32)^2 + cls = 17 tokens, 12 heads x 64: one block holds it
        assert dec["schedule"] == "whole_seq"
        assert t.model.flash is True
    finally:
        from tpudist import telemetry as telemetry_lib
        t.telemetry.close()
        telemetry_lib.set_current(None)
    log = open(out / "experiment.log").read()
    assert "=> attention dispatch: flash attention (mode on, forced, " \
        "schedule whole_seq, heads_per_program 12 block_q 17 block_k 17 " \
        "band_fill 1.0" in log
    disp = [json.loads(line) for line in open(out / "events.0.jsonl")
            if '"attention_dispatch"' in line]
    assert len(disp) == 1 and disp[0]["schedule"] == "whole_seq"
    assert disp[0]["heads_per_program"] == [12] and disp[0]["block_q"] == [17]
