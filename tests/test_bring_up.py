"""No fallback hides the device (ISSUE 21): what the chip smoke, the bench,
the perf-CI runner, the launcher and the trainer's ``auto`` dispatch do when
the chip is absent or a probe fails. All on the CPU: the point of each case
is that the CPU is REFUSED, not quietly used."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    """On a machine with no chip ``chip_smoke.py`` exits non-zero and
    prints no result line — the driver's first check of the contract."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode not in (0, None), r.stdout[-500:]
    assert '"ok"' not in r.stdout and "no tpu" in r.stderr


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_for_bring_up", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_refuses_other_platform(capsys):
    """bench.py measures on the platform asked for or not at all: no stale
    record, no shrunk CPU re-run, no row."""
    bench = _bench()
    with pytest.raises(SystemExit, match="refusing to measure"):
        bench.require_platform("tpu")
    assert capsys.readouterr().out == ""
    for gone in ("_try_emit_stale", "_init_backend", "_probe_backend",
                 "_reexec_cpu", "persist_if_accelerator", "LAST_TPU_PATH"):
        assert not hasattr(bench, gone), gone


def test_perfci_failed_platform_probe_is_an_error(monkeypatch, capsys):
    from tpudist import perfci
    monkeypatch.delenv(perfci.ENV_PLATFORM, raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        perfci.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 1, "", "no backend"))
    with pytest.raises(perfci.PlatformError, match="no backend"):
        perfci.detect_platform()
    assert perfci.main(["--dry-run"]) == 2
    assert "platform probe failed" in capsys.readouterr().err


@pytest.mark.parametrize("argv", [
    ["--nprocs", "2", "--platform", "tpu"],
    ["--nprocs", "1", "--platform", "tpu", "--scale-up", "2@1"],
], ids=["nprocs", "scale_up"])
def test_launcher_refuses_two_children_per_host_on_tpu(argv, capsys):
    """A chip belongs to one process and the launcher binds no child to a
    chip: more than one child on the TPU platform fails fast, before
    anything is spawned. (CPU launches — every other launcher test — are
    untouched: ``--platform`` defaults to cpu.)"""
    from tpudist import launch
    with pytest.raises(SystemExit) as e:
        launch.main(argv + ["--", sys.executable, "-c", "pass"])
    assert e.value.code == 2
    assert "one process" in capsys.readouterr().err


def test_launcher_guard_follows_unforced_platform(monkeypatch):
    """With no platform forced, jax would pick the TPU exactly when the
    host exposes one — the guard reads the same fact off /dev."""
    from tpudist import launch
    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setattr(launch, "_tpu_attached", lambda: True)
    with pytest.raises(SystemExit) as e:
        launch.main(["--nprocs", "2", "--platform", "", "--",
                     sys.executable, "-c", "pass"])
    assert e.value.code == 2
    monkeypatch.setattr(launch, "_tpu_attached", lambda: False)
    assert launch.main(["--nprocs", "2", "--platform", "", "--",
                        sys.executable, "-c", "pass"]) == 0


# -- a probe that raises under `auto` on a TPU ends the run ------------------

class _MosaicRefused(RuntimeError):
    pass


def _boom(*a, **k):
    raise _MosaicRefused("Mosaic failed to compile TPU kernel")


def _stub_trainer(cfg, arch, **model_kw):
    """A Trainer with just the state the dispatch resolvers read — no
    device work: the train state is abstract (``eval_shape``)."""
    import jax.numpy as jnp
    from tpudist.dist import make_mesh
    from tpudist.models import create_model
    from tpudist.train import create_train_state
    from tpudist.trainer import Trainer
    t = Trainer.__new__(Trainer)
    t.cfg = cfg.finalize(8)
    t.mesh = make_mesh((8,), ("data",))
    t.model = create_model(arch, num_classes=cfg.num_classes,
                           dtype=jnp.bfloat16, **model_kw)
    t.state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), t.model, t.cfg))
    t.logger = t.telemetry = None
    t.primary = True
    t.data_axis = "data"
    t.trains_tokens = False
    for flag in ("uses_model_axis", "uses_seq_axis", "uses_pipe_axis",
                 "uses_expert_axis", "uses_gspmd_path"):
        setattr(t, flag, False)
    return t


@pytest.mark.parametrize("family", ["attention", "comm"])
def test_probe_failure_under_auto_on_tpu_propagates(family, tmp_path,
                                                    monkeypatch):
    """Under ``auto`` on a TPU only a measured loss or a static
    ineligibility may select the baseline. A probe that RAISES (a kernel
    the compiler refuses) must end the run — before this PR the trainer
    caught it, logged 'probe failed' and trained on XLA with exit 0."""
    from tpudist.config import Config
    from tpudist.ops import attention_dispatch, comm_dispatch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("TPUDIST_DISPATCH_CACHE", str(tmp_path / "verdicts"))
    base = dict(num_classes=4, batch_size=64, synthetic=True, use_amp=True,
                outpath=str(tmp_path / "run"), seed=0)
    if family == "attention":
        monkeypatch.setattr(attention_dispatch, "measure_attention", _boom)
        t = _stub_trainer(Config(arch="vit_b_16", image_size=224, **base),
                          "vit_b_16")
        resolve = t._resolve_attention
    else:
        monkeypatch.setattr(comm_dispatch, "measure_comm", _boom)
        t = _stub_trainer(Config(arch="resnet18", image_size=32,
                                 compress_grads="auto", **base),
                          "resnet18")
        resolve = t._resolve_comm_dispatch
    with pytest.raises(_MosaicRefused):
        resolve()
    # Nothing was cached: the next run asks the compiler again.
    assert not os.path.exists(tmp_path / "verdicts") \
        or not os.listdir(tmp_path / "verdicts")


def test_an_unwritable_verdict_store_still_builds_the_measured_kernel(
        tmp_path, monkeypatch):
    """A verdict that cannot be written down (a read-only cache directory)
    still stands for the run that measured it: the trainer builds its
    model from the decision it logs, not from a look-up in the store, and
    the decision says that nothing was kept (``cache_path`` None)."""
    from tpudist.config import Config
    from tpudist.ops import attention_dispatch, dispatch

    def read_only(path, cache):
        raise OSError("read-only file system")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("TPUDIST_DISPATCH_CACHE", str(tmp_path / "verdicts"))
    monkeypatch.setattr(dispatch, "save_cache", read_only)
    monkeypatch.setattr(attention_dispatch, "measure_attention",
                        lambda *a, **k: (1.0, 2.0))
    t = _stub_trainer(Config(arch="vit_b_16", image_size=224, num_classes=4,
                             batch_size=64, synthetic=True, use_amp=True,
                             outpath=str(tmp_path / "run"), seed=0),
                      "vit_b_16")
    t.log = lambda line: None
    assert t.model.flash is None
    dec = t._resolve_attention()
    assert dec["kernel"] == "flash" and dec["source"] == "measured"
    assert dec["cache_path"] is None and t.model.flash is True
    assert not os.path.exists(tmp_path / "verdicts")
    # the next run measures again: nothing answers a look-up
    assert attention_dispatch.lookup(8, 197, 12, 64, "bfloat16") is False


@pytest.mark.parametrize("schedule", ["whole_seq", "streaming"])
def test_chip_smoke_flash_phase_checks_both_schedules(schedule, monkeypatch):
    """The bring-up gate's flash case (PR 28): at ViT-B/16's token count and
    head size it checks the fused entry on the whole-sequence schedule (the
    kernel ``--flash auto`` now runs on ViT) and the streaming kernels
    beside it. Here in interpret mode on two heads; on the chip Mosaic
    compiles the same bodies."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_for_bring_up", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    monkeypatch.setattr(chip_smoke, "INTERPRET", True)
    monkeypatch.setattr(chip_smoke, "VIT_B16_ATTENTION", (1, 197, 2, 64))
    out = chip_smoke._flash_case(jax.random.PRNGKey(0), schedule)
    assert set(out) == {"o", "dq", "dk", "dv"}
    assert all(v["err_over_tol"] <= 1.0 for v in out.values())
