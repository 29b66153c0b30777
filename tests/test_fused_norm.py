"""Fused BN-epilogue kernels + the generalized dispatch layer (ISSUE 6):

- interpret-mode numerics parity of the Pallas BN+ReLU / BN+add+ReLU
  kernels against the XLA reference — forward AND gradients, f32 ≤1e-5 /
  bf16 ≤1e-2, odd rows/channels included (the zero-padding exactness
  claim);
- `models/layers.py::BatchNorm` wiring: forced-fused train mode matches
  the plain module (outputs, grads, and BIT-IDENTICAL running stats — the
  statistics are computed outside the kernel), while eval mode and SyncBN
  provably never consult the dispatch layer;
- the generic honesty policy (`ops/dispatch`) through the fused_norm
  client: never-pick-a-loser, per-device_kind cache round trips on
  `fused_norm.<kind>.json`, clear/KERNEL_REV invalidation, and — the
  acceptance pin — off-TPU `auto` resolves to XLA with the fused_norm
  Pallas module never entering sys.modules (subprocess-verified);
- `ops/attention_dispatch` is a THIN client of the generic layer (no
  duplicated cache/timing/shared-verdict logic — structural identity
  asserts);
- regress-gate direction coverage for the new series;
- the Trainer emits the `fused_norm_dispatch` event at construction;
- `tools/fused_smoke.sh` end to end.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops import dispatch, norm_dispatch as nd
from tpudist.ops.pallas.fused_norm import (KERNEL_REV, fused_bn_act,
                                           reference_bn_act)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TPU = dict(platform="tpu", device_kind="fake-tpu-v9")
SHAPE = dict(rows=4096, channels=64, dtype="bfloat16")


@pytest.fixture(autouse=True)
def _reset_mode():
    nd.set_mode(None)
    yield
    nd.set_mode(None)


def _pair(pallas_ms, xla_ms):
    return lambda: (pallas_ms, xla_ms)


def _boom():
    raise AssertionError("dispatcher measured when it must not")


def _decide(mode="auto", rows=4096, channels=64, dtype="bfloat16",
            residual=False, **kw):
    return nd.decide(rows, channels, dtype, residual=residual, mode=mode,
                     **kw)


# -- kernel numerics parity (interpret mode, the satellite matrix) -----------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("shape", [(2, 5, 5, 64),    # NHWC, sub-tile rows
                                   (24, 130),        # odd channels (pad 256)
                                   (40, 8)])         # tiny channel dim
@pytest.mark.parametrize("residual", [False, True])
def test_kernel_parity_fwd_and_grad(dtype, tol, shape, residual):
    """fused_bn_act ≡ the XLA reference epilogue: forward and every input
    gradient (x, scale, bias, mean, var, residual) within tolerance, at
    shapes that force row AND channel padding — padded contributions must
    cancel exactly, not approximately."""
    rng = np.random.default_rng(0)
    c = shape[-1]
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    res = jnp.asarray(rng.standard_normal(shape), dtype) if residual else None
    scale = jnp.asarray(rng.standard_normal(c), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(c), jnp.float32)
    mean = jnp.asarray(rng.standard_normal(c), jnp.float32)
    var = jnp.asarray(rng.random(c) + 0.5, jnp.float32)

    y1 = fused_bn_act(x, scale, bias, mean, var, residual=res)
    y2 = reference_bn_act(x, scale, bias, mean, var, residual=res)
    assert y1.dtype == y2.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=tol)

    def loss(fn):
        def f(x, scale, bias, mean, var, res):
            return fn(x, scale, bias, mean, var,
                      residual=res).astype(jnp.float32).sum()
        return f

    argnums = tuple(range(6 if residual else 5))
    g1 = jax.grad(loss(fused_bn_act), argnums=argnums)(
        x, scale, bias, mean, var, res)
    g2 = jax.grad(loss(reference_bn_act), argnums=argnums)(
        x, scale, bias, mean, var, res)
    for i, (a, b) in enumerate(zip(g1, g2)):
        mag = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1.0
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol * 20 * mag, err_msg=f"grad argnum {i}")


def test_batchnorm_module_fused_matches_plain_train_mode():
    """The layers.BatchNorm wiring: forced-fused train mode reproduces the
    plain module's outputs and grads within bf16 tolerance, and the
    running-stats update is BIT-identical (stats are computed outside the
    kernel on both branches). Covers both fused variants via act/residual."""
    from tpudist.models.layers import BatchNorm
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 6, 6, 24)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((4, 6, 6, 24)), jnp.float32)
    bn = BatchNorm(use_running_average=False)
    variables = bn.init(jax.random.PRNGKey(0), x)

    def run(residual):
        def f(params, stats, x):
            y, mut = bn.apply({"params": params, "batch_stats": stats}, x,
                              act="relu", residual=residual,
                              mutable=["batch_stats"])
            return y.astype(jnp.float32).sum(), (y, mut["batch_stats"])
        (loss, (y, stats)), grads = jax.value_and_grad(f, has_aux=True)(
            variables["params"], variables["batch_stats"], x)
        return y, stats, grads, loss

    for residual in (None, res):
        nd.set_mode("off")
        y_ref, stats_ref, g_ref, l_ref = run(residual)
        nd.set_mode("on")
        y_f, stats_f, g_f, l_f = run(residual)
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_ref),
                                   atol=1e-5)
        assert abs(l_f - l_ref) < 1e-3
        # stats identical to the bit: same mean/var computation, same update
        for k in ("mean", "var"):
            np.testing.assert_array_equal(np.asarray(stats_f[k]),
                                          np.asarray(stats_ref[k]))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4), g_f, g_ref)


def test_batchnorm_eval_and_syncbn_fall_back_without_dispatch(monkeypatch):
    """The two structural fallbacks: eval mode (running stats) and SyncBN
    (axis_name set) must take the XLA path WITHOUT asking the dispatch
    layer — even under forced `on` — pinned by making use_fused explode."""
    from tpudist.models.layers import BatchNorm
    monkeypatch.setattr(nd, "use_fused",
                        lambda *a, **k: pytest.fail("dispatch consulted"))
    nd.set_mode("on")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 3, 3, 16)), jnp.float32)
    bn = BatchNorm(use_running_average=False)
    variables = bn.init(jax.random.PRNGKey(0), x)
    # eval mode: use_running_average=True
    y = bn.apply(variables, x, use_running_average=True, act="relu")
    np.testing.assert_array_equal(np.asarray(y) >= 0, True)
    # SyncBN: axis_name bound via vmap
    sbn = BatchNorm(use_running_average=False, axis_name="data")
    sv = jax.vmap(lambda x: sbn.init(jax.random.PRNGKey(0), x),
                  axis_name="data")(x[None])
    sv = jax.tree_util.tree_map(lambda l: l[0], sv)
    y, _ = jax.vmap(
        lambda x: sbn.apply(sv, x, act="relu", mutable=["batch_stats"]),
        axis_name="data")(x[None])
    assert np.isfinite(np.asarray(y)).all()
    # ...and the guard rejects unsupported activations / orphan residuals.
    with pytest.raises(ValueError, match="relu"):
        bn.apply(variables, x, use_running_average=True, act="gelu")
    with pytest.raises(ValueError, match="residual"):
        bn.apply(variables, x, use_running_average=True, residual=x)


# -- the honesty invariants through the fused_norm client --------------------

def test_auto_never_selects_a_losing_kernel(tmp_path):
    for i, (pallas_ms, xla_ms) in enumerate(
            [(1.0, 2.0), (2.0, 1.0), (1.0, 1.0), (0.5, 0.49), (3.7, 9.1)]):
        d = _decide(cache_dir=str(tmp_path / str(i)),
                    measure_pair=_pair(pallas_ms, xla_ms), **TPU)
        assert d["source"] == "measured"
        if pallas_ms < xla_ms:
            assert d["kernel"] == "pallas", (pallas_ms, xla_ms, d)
        else:                         # loss OR tie → the compiler baseline
            assert d["kernel"] == "xla", (pallas_ms, xla_ms, d)
        assert 0.0 <= d["margin"] <= 1.0
        assert d["pallas_ms"] == pallas_ms and d["xla_ms"] == xla_ms


def test_forced_modes_and_eligibility(tmp_path):
    for mode, kernel in (("on", "pallas"), ("off", "xla")):
        d = _decide(mode=mode, cache_dir=str(tmp_path), measure_pair=_boom,
                    **TPU)
        assert d["kernel"] == kernel and d["source"] == "forced"
    with pytest.raises(ValueError, match="auto"):
        _decide(mode="sometimes")
    # A workload the kernel can't tile resolves to XLA before any device
    # question — measure_pair must never be reached.
    d = _decide(rows=4, cache_dir=str(tmp_path), measure_pair=_boom, **TPU)
    assert d["kernel"] == "xla" and d["source"] == "ineligible"
    assert "sublane" in d["reason"]
    d = _decide(channels=9999, cache_dir=str(tmp_path), measure_pair=_boom,
                **TPU)
    assert d["source"] == "ineligible" and "channel" in d["reason"]
    # Eligibility is STRUCTURAL for this client: it outranks even forced
    # `on` (use_fused enforces it at the call site, so a forced decision
    # claiming pallas there would name a kernel the trace never runs).
    d = _decide(mode="on", rows=4, cache_dir=str(tmp_path),
                measure_pair=_boom, **TPU)
    assert d["kernel"] == "xla" and d["source"] == "ineligible"


def test_unwritable_cache_dir_still_binds_lookup(tmp_path, monkeypatch):
    """A measured verdict that cannot persist (read-only cache dir) must
    still bind the process's own trace-time lookups: the dispatch line
    reports pallas, so the trace must compile pallas — the in-process
    overlay bridges the gap. clear_cache drops the overlay too."""
    cache = str(tmp_path)

    def _no_write(path, obj):
        raise OSError("read-only filesystem")
    monkeypatch.setattr(dispatch, "save_cache", _no_write)
    d = _decide(cache_dir=cache, measure_pair=_pair(1.0, 2.0), **TPU)
    assert d["kernel"] == "pallas" and d["source"] == "measured"
    assert d["cache_path"] is None          # the caller can see it degraded
    assert os.listdir(cache) == []
    kw = dict(cache_dir=cache, **TPU)
    assert nd.use_fused(4096, 64, "bfloat16", residual=False, **kw) is True
    assert nd.use_fused(4096, 64, "bfloat16", residual=True, **kw) is False
    assert nd.clear_cache(TPU["device_kind"], cache_dir=cache) == 0
    assert nd.use_fused(4096, 64, "bfloat16", residual=False, **kw) is False


def test_cache_round_trips_and_invalidation(tmp_path):
    cache = str(tmp_path)
    d = _decide(cache_dir=cache, measure_pair=_pair(1.0, 2.0), **TPU)
    assert d["kernel"] == "pallas" and d["source"] == "measured"
    # Cache hit: measuring again is an error; the file is the client's own.
    d = _decide(cache_dir=cache, measure_pair=_boom, **TPU)
    assert d["kernel"] == "pallas" and d["source"] == "cache" \
        and d["cache_hit"] and d["pallas_ms"] == 1.0
    files = os.listdir(cache)
    assert files == ["fused_norm.fake-tpu-v9.json"], files
    # Another device kind decides for itself; another variant is its own
    # entry (res vs plain must not share a verdict).
    d = _decide(cache_dir=cache, measure_pair=_pair(5.0, 1.0),
                platform="tpu", device_kind="fake-tpu-v10")
    assert d["kernel"] == "xla" and d["source"] == "measured"
    d = _decide(cache_dir=cache, residual=True, measure_pair=_pair(9.0, 1.0),
                **TPU)
    assert d["kernel"] == "xla" and d["source"] == "measured"
    d = _decide(cache_dir=cache, measure_pair=_boom, **TPU)
    assert d["kernel"] == "pallas"          # first entry untouched
    # clear_cache → re-measure; KERNEL_REV bump orphans the entry.
    assert nd.clear_cache(TPU["device_kind"], cache_dir=cache) == 1
    d = _decide(cache_dir=cache, measure_pair=_pair(2.0, 1.0), **TPU)
    assert d["kernel"] == "xla" and d["source"] == "measured"
    path = nd.cache_path(TPU["device_kind"], cache)
    obj = json.load(open(path))
    for e in obj["entries"].values():
        e["kernel_rev"] = -1
    json.dump(obj, open(path, "w"))
    d = _decide(cache_dir=cache, measure_pair=_pair(1.0, 2.0), **TPU)
    assert d["kernel"] == "pallas" and d["source"] == "measured"
    assert d["kernel_rev"] == KERNEL_REV


def test_use_fused_is_trace_safe_and_mode_aware(tmp_path):
    cache = str(tmp_path)
    kw = dict(cache_dir=cache, **TPU)
    # auto + no entry → False (unmeasured is never dispatched), even on TPU.
    assert nd.use_fused(4096, 64, "bfloat16", residual=False, **kw) is False
    # a measured win flips exactly that workload
    _decide(cache_dir=cache, measure_pair=_pair(1.0, 2.0), **TPU)
    assert nd.use_fused(4096, 64, "bfloat16", residual=False, **kw) is True
    assert nd.use_fused(4096, 64, "bfloat16", residual=True, **kw) is False
    assert nd.use_fused(2048, 64, "bfloat16", residual=False, **kw) is False
    # forced modes answer directly (no cache consult)
    nd.set_mode("off")
    assert nd.use_fused(4096, 64, "bfloat16", residual=False, **kw) is False
    nd.set_mode("on")
    assert nd.use_fused(4096, 64, "bfloat16", residual=True, **kw) is True
    # ...but never for an ineligible workload
    assert nd.use_fused(2, 64, "bfloat16", residual=False, **kw) is False
    nd.set_mode(None)
    # recording: requests are captured, answers stay False
    with nd.record_requests() as reqs:
        assert nd.use_fused(4096, 64, "bfloat16", residual=False,
                            **kw) is False
    assert len(reqs) == 1
    rows, channels, key, residual, dt = next(iter(reqs))
    assert (rows, channels, residual) == (4096, 64, False)
    assert key == nd.norm_key(4096, 64, "bfloat16", False)


def test_cpu_auto_resolves_xla_without_pallas_import(tmp_path):
    """Acceptance pin: on this CPU container `--fused-bn auto` resolves to
    the XLA epilogue without the fused_norm module (or any Pallas) ever
    being imported — checked in a fresh subprocess, since this test file
    itself imports the kernels."""
    code = """
import sys
import jax.numpy as jnp
from tpudist.ops import norm_dispatch as nd

def boom():
    raise AssertionError("auto measured off-TPU")

d = nd.decide(4096, 64, jnp.bfloat16, residual=False, mode="auto",
              measure_pair=boom)
assert d["kernel"] == "xla" and d["source"] == "platform", d
assert nd.use_fused(4096, 64, jnp.bfloat16, residual=True) is False
assert "tpudist.ops.pallas.fused_norm" not in sys.modules
assert not any("pallas" in m for m in sys.modules)
print("NO_PALLAS_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPUDIST_DISPATCH_CACHE=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "NO_PALLAS_OK" in r.stdout


def test_adopt_decisions_seeds_local_cache(tmp_path):
    """The multi-host peer path: adopting the primary's published verdict
    set makes this host's trace-time lookups agree with the primary's."""
    cache = str(tmp_path)
    key = nd.norm_key(4096, 64, "bfloat16", False)
    decisions = {key: {"kernel": "pallas", "pallas_ms": 1.0, "xla_ms": 2.0,
                       "margin": 0.5, "kernel_rev": KERNEL_REV,
                       "measured_at": "now"}}
    assert nd.adopt_decisions(decisions, TPU["device_kind"],
                              cache_dir=cache) == 1
    assert nd.use_fused(4096, 64, "bfloat16", residual=False,
                        cache_dir=cache, **TPU) is True
    # aggregate() rolls the set into the reportable verdict
    agg = nd.aggregate({**decisions,
                        "k2": {"kernel": "xla", "source": "measured"}},
                       "auto")
    assert agg["kernel"] == "mixed" and agg["n_sites"] == 2 \
        and agg["n_fused"] == 1
    from tpudist.telemetry import validate_event
    ev = {"t": 0.0, "type": "fused_norm_dispatch", "rank": 0, "attempt": 0,
          **nd.event_fields(dict(agg, source="measured"))}
    validate_event(ev)
    assert ev["n_sites"] == 2 and key in ev["detail"]


# -- attention_dispatch is a THIN client (acceptance criterion) --------------

def test_attention_dispatch_is_thin_client_of_generic_layer():
    """No duplicated cache/timing/shared-verdict logic: the attention
    module's surfaces ARE the generic layer's objects, and both clients'
    decisions flow through the one dispatch.decide policy."""
    from tpudist.ops import attention_dispatch as ad
    assert ad.load_cache is dispatch.load_cache
    assert ad.save_cache is dispatch.save_cache
    assert ad.measure_ms is dispatch.measure_ms
    assert ad.default_cache_dir is dispatch.default_cache_dir
    assert getattr(ad.cache_path, "func", None) is dispatch.cache_path
    assert getattr(ad.clear_cache, "func", None) is dispatch.clear_cache
    assert ad.MODES is dispatch.MODES
    # the shared-verdict plumbing has exactly one implementation
    import inspect
    assert "dispatch.shared_decision" in inspect.getsource(ad.shared_decision)
    assert "dispatch.shared_decision" in inspect.getsource(
        nd.shared_decide_all)
    assert "dispatch.decide" in inspect.getsource(ad.decide)
    assert "dispatch.decide" in inspect.getsource(nd.decide)


def test_regress_gate_directions_for_new_series():
    """The fused-kernel ms series gate UPWARD; the prefetch img/s series
    gate DOWNWARD — both through the existing unit heuristic."""
    from tpudist.regress import analyze_history

    def rows(vals, metric, unit):
        return [{"metric": metric, "value": v, "unit": unit} for v in vals]

    ms = rows([4.0, 4.1, 3.9, 4.0, 4.05, 4.9],
              "fusednorm_stage1_b128_pallas_fwdbwd_ms_tpu", "ms")
    v = analyze_history(ms)
    assert v["status"] == "regression" and v["lower_is_better"]
    assert analyze_history(ms[:-1] + [dict(ms[0], value=3.0)])["status"] \
        == "pass"
    tput = rows([9000, 9050, 8990, 9020, 9010, 7000],
                "prefetch_on_resnet18_224_images_per_sec_tpu", "images/sec")
    v = analyze_history(tput)
    assert v["status"] == "regression" and not v["lower_is_better"]


# -- trainer + smoke e2e -----------------------------------------------------

def test_trainer_emits_fused_norm_event_on_cpu(tmp_path):
    """A --telemetry resnet Trainer on this CPU container resolves
    --fused-bn auto to XLA on platform grounds at CONSTRUCTION (no fit),
    logs it, and emits the schema-valid fused_norm_dispatch event."""
    from tpudist.config import Config
    from tpudist.telemetry import validate_event
    from tpudist.trainer import Trainer
    from tpudist import telemetry as telemetry_lib

    out = tmp_path / "run"
    cfg = Config(arch="resnet18", num_classes=4, image_size=32, batch_size=8,
                 epochs=1, workers=0, synthetic=True, synthetic_size=8,
                 use_amp=False, outpath=str(out), overwrite="delete",
                 seed=0, telemetry=True)
    t = Trainer(cfg, writer=None)
    try:
        dec = t.fused_norm_decision
        assert dec is not None and dec["kernel"] == "xla" \
            and dec["source"] == "platform" and dec["mode"] == "auto"
    finally:
        t.telemetry.close()
        telemetry_lib.set_current(None)
    events = [json.loads(line)
              for line in open(out / "events.0.jsonl") if line.strip()]
    for e in events:
        validate_event(e)
    disp = [e for e in events if e["type"] == "fused_norm_dispatch"]
    assert len(disp) == 1 and disp[0]["kernel"] == "xla"


def test_trainer_forced_on_reports_actual_sites(tmp_path, monkeypatch):
    """Forced `--fused-bn on` must report what the trace RUNS: pallas with
    the recorded site count for a BN model, but `no_sites`/xla when the
    model has no fused-eligible BN epilogue — the dispatch line may never
    name a kernel that did not compile."""
    from tpudist.config import Config
    from tpudist.trainer import Trainer
    from tpudist import telemetry as telemetry_lib

    def _cfg(out):
        return Config(arch="resnet18", num_classes=4, image_size=32,
                      batch_size=8, epochs=1, workers=0, synthetic=True,
                      synthetic_size=8, use_amp=False, outpath=str(out),
                      overwrite="delete", seed=0, fused_bn="on")

    try:
        t = Trainer(_cfg(tmp_path / "a"), writer=None)
        dec = t.fused_norm_decision
        assert dec["kernel"] == "pallas" and dec["source"] == "forced"
        assert dec["n_sites"] > 0 and dec["n_fused"] == dec["n_sites"]
        # A model with zero fused-eligible sites (vit/layernorm families —
        # simulated via the recording hook) reports no_sites, not pallas.
        monkeypatch.setattr(
            Trainer, "_record_fused_norm_requests",
            lambda self, ndm: set())
        t = Trainer(_cfg(tmp_path / "b"), writer=None)
        dec = t.fused_norm_decision
        assert dec["kernel"] == "xla" and dec["source"] == "no_sites"
    finally:
        nd.set_mode(None)
        telemetry_lib.set_current(None)


def test_fused_smoke_script(tmp_path, mp_timeout):
    """Satellite: tools/fused_smoke.sh chains cache round-trip →
    forced-fused train step → telemetry run whose summarize shows the
    fused-norm dispatch line and the prefetch budget row."""
    env = dict(os.environ)
    env["TPUDIST_FUSED_SMOKE_DIR"] = str(tmp_path)
    r = subprocess.run(["bash", os.path.join(REPO, "tools",
                                             "fused_smoke.sh")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=mp_timeout(1, compile_cost=3.0))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "FUSED_SMOKE_OK"


# -- ISSUE 12: the shard_map-wrapped epilogue + shard-local honesty -----------

def _mesh42():
    from tpudist.dist import make_mesh
    return make_mesh((4, 2), ("data", "model"), jax.devices())


def _epilogue_args(b=8, h=4, w=4, c=16, residual=True, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, h, w, c)), jnp.float32)
    res = (jnp.asarray(rng.standard_normal((b, h, w, c)), jnp.float32)
           if residual else None)
    scale = jnp.asarray(rng.standard_normal(c), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(c), jnp.float32)
    mean = jnp.asarray(rng.standard_normal(c), jnp.float32)
    var = jnp.asarray(rng.random(c) + 0.5, jnp.float32)
    return x, res, scale, bias, mean, var


@pytest.mark.parametrize("residual", [False, True])
def test_fused_bn_act_spmd_matches_reference_under_mesh(residual):
    """The shard_map-wrapped epilogue (nested manual region over the
    ambient data/model axes) matches the XLA reference — forward AND every
    gradient — inside a partitioned jit. This is the composition the old
    structural stand-down forbade."""
    from tpudist.ops.pallas.fused_norm import fused_bn_act_spmd

    mesh = _mesh42()
    x, res, scale, bias, mean, var = _epilogue_args(residual=residual)

    def loss(fn):
        def f(x, scale, bias, res):
            return fn(x, scale, bias, mean, var,
                      residual=res).astype(jnp.float32).sum()
        return f

    with jax.sharding.set_mesh(mesh):
        g = jax.jit(jax.grad(loss(fused_bn_act_spmd),
                             argnums=(0, 1, 2) + ((3,) if residual else ())))(
            x, scale, bias, res)
        y = jax.jit(lambda *a: fused_bn_act_spmd(
            a[0], a[1], a[2], mean, var, residual=a[3]))(x, scale, bias, res)
    gr = jax.grad(loss(lambda *a, **k: reference_bn_act(*a, **k)),
                  argnums=(0, 1, 2) + ((3,) if residual else ()))(
        x, scale, bias, res)
    yr = reference_bn_act(x, scale, bias, mean, var, residual=res)
    assert float(jnp.max(jnp.abs(y - yr))) < 1e-5
    for a, b in zip(g, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4, (a.shape,)


def test_fused_bn_act_spmd_is_plain_kernel_without_mesh():
    """No ambient mesh → byte-identical to fused_bn_act (nothing to wrap)."""
    from tpudist.ops.pallas.fused_norm import fused_bn_act_spmd

    x, res, scale, bias, mean, var = _epilogue_args(residual=False)
    a = fused_bn_act_spmd(x, scale, bias, mean, var)
    b = fused_bn_act(x, scale, bias, mean, var)
    assert jnp.array_equal(a, b)


def test_shard_local_workload_divides_under_ambient_mesh():
    """The dispatch identity under sharding is the block a device actually
    runs: batch rows divide by the data axis, channels by the model axis
    (where divisible); no ambient mesh → the plain global workload."""
    rows, chans, sharded = nd.shard_local_workload((8, 4, 4, 16))
    assert (rows, chans, sharded) == (8 * 4 * 4, 16, False)
    with jax.sharding.set_mesh(_mesh42()):
        rows, chans, sharded = nd.shard_local_workload((8, 4, 4, 16))
        assert (rows, chans, sharded) == (2 * 4 * 4, 8, True)
        # Undivisible dims stay whole (the wrapper replicates them too).
        rows, chans, sharded = nd.shard_local_workload((9, 4, 4, 15))
        assert (rows, chans, sharded) == (9 * 4 * 4, 15, False)


def test_shard_local_workload_is_local_inside_manual_regions():
    """Inside a shard_map body the traced shapes are ALREADY local — with
    the ambient mesh context still entered (the GSPMD builders' set_mesh
    wraps calls, and a manual region can nest inside), the bound axes
    must NOT divide a second time and the wrapper must not try to rebind
    them (ambient_auto_axes only returns Auto axes)."""
    from jax.sharding import PartitionSpec as P

    mesh = _mesh42()
    seen = {}

    def body(x):
        seen["slw"] = nd.shard_local_workload(x.shape)
        seen["axes"] = nd.epilogue_shard_axes(x.shape)[1:]
        return x

    with mesh:
        jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data", None, None, "model"),),
            out_specs=P("data", None, None, "model"),
            check_vma=False))(jnp.zeros((8, 4, 4, 16), jnp.float32))
    # Body shapes are the (2, 4, 4, 8) local block: no further division.
    assert seen["slw"] == (2 * 4 * 4, 8, False), seen
    assert seen["axes"] == (None, None), seen


def test_use_fused_under_sharding_keys_the_shard_local_workload(tmp_path):
    """ISSUE 12 honesty pin: under a sharded mesh the fused kernel is
    selected ONLY off a measurement of the SHARD-LOCAL workload it will
    actually run — a cached win for the global shape does not flip the
    trace, an unmeasured local shape stays XLA, and a cached LOCAL win
    dispatches."""
    mesh = _mesh42()
    # Global activation (16, 4, 4, 32) → local workload (4·4·4, 16).
    g_key = nd.norm_key(16 * 4 * 4, 32, jnp.bfloat16, False)
    l_key = nd.norm_key(4 * 4 * 4, 16, jnp.bfloat16, False)
    entry = {"kernel": "pallas", "pallas_ms": 1.0, "xla_ms": 2.0,
             "margin": 0.5, "kernel_rev": KERNEL_REV}
    path = nd.cache_path(TPU["device_kind"], str(tmp_path))
    dispatch.save_cache(path, {"version": dispatch.CACHE_VERSION,
                               "device_kind": TPU["device_kind"],
                               "entries": {g_key: entry}})

    def ask():
        rows, chans, _ = nd.shard_local_workload((16, 4, 4, 32))
        return nd.use_fused(rows, chans, jnp.bfloat16, residual=False,
                            cache_dir=str(tmp_path), **TPU)

    with jax.sharding.set_mesh(mesh):
        assert ask() is False, \
            "a GLOBAL-shape verdict must not dispatch the sharded trace"
    # save_cache's os.replace changes the stat key, invalidating lookup()'s
    # memoized read — no manual cache poke needed.
    dispatch.save_cache(path, {"version": dispatch.CACHE_VERSION,
                               "device_kind": TPU["device_kind"],
                               "entries": {g_key: entry, l_key: entry}})
    with jax.sharding.set_mesh(mesh):
        assert ask() is True, \
            "a measured shard-local win must dispatch under the mesh"
    # Losing (or absent) local measurements never dispatch: the generic
    # decide() policy, exercised at the local key.
    dec = nd.decide(4 * 4 * 4, 16, jnp.bfloat16, residual=False,
                    mode="auto", cache_dir=str(tmp_path),
                    measure_pair=_pair(3.0, 2.0), refresh=True, **TPU)
    assert dec["kernel"] == "xla" and dec["source"] == "measured"


def test_batchnorm_gspmd_trace_uses_wrapper_only_when_dispatched(tmp_path):
    """End to end through models/layers.py::BatchNorm under a GSPMD-style
    (global-shape, ambient-mesh) trace: with no verdict the traced program
    contains NO pallas_call; with mode forced on it contains the wrapped
    kernel and still matches the XLA path numerically."""
    from flax import linen as nn
    from tpudist.models.layers import BatchNorm

    mesh = _mesh42()

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            return BatchNorm(name="bn")(x, act="relu")

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 4, 4, 32)), jnp.float32)
    net = Net()
    variables = net.init(jax.random.PRNGKey(0), x, train=False)

    def make_fwd():
        # A FRESH function object per trace: jax caches traces on identity
        # + avals, and the dispatch mode is resolved at trace time — the
        # production contract (Trainer resolves mode before any step is
        # built) never flips mode across one function's traces, but this
        # test does.
        def fwd(v, x):
            return net.apply(v, x, train=True, mutable=["batch_stats"])[0]
        return fwd

    with jax.sharding.set_mesh(mesh):
        base = str(jax.make_jaxpr(make_fwd())(variables, x))
        assert "pallas_call" not in base, \
            "unmeasured auto must trace the XLA epilogue"
        nd.set_mode("on")
        try:
            fused_jaxpr = str(jax.make_jaxpr(make_fwd())(variables, x))
            y_fused = jax.jit(make_fwd())(variables, x)
        finally:
            nd.set_mode(None)
        assert "shard_map" in fused_jaxpr and "pallas_call" in fused_jaxpr
        y_xla = jax.jit(make_fwd())(variables, x)
    assert float(jnp.max(jnp.abs(y_fused - y_xla))) < 1e-5
