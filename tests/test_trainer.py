"""Integration tests: full Trainer.fit() on the fake 8-device mesh with
synthetic data (SURVEY.md §4's 'short-run integration' strategy)."""

import os

import numpy as np
import pytest

from tpudist.config import Config
from tpudist.trainer import Trainer


def _cfg(tmp_path, **kw):
    defaults = dict(arch="resnet18", num_classes=8, image_size=32,
                    batch_size=64, epochs=2, step=[1], lr=0.02, workers=2,
                    print_freq=2, synthetic=True, use_amp=False,
                    outpath=str(tmp_path / "out"), overwrite="delete", seed=0)
    defaults.update(kw)
    return Config(**defaults)


@pytest.mark.slow
def test_fit_end_to_end_artifacts(tmp_path):
    cfg = _cfg(tmp_path)
    t = Trainer(cfg, writer=None)
    best = t.fit()
    out = cfg.outpath
    # Reference-compatible artifact surface: experiment.log, settings.log,
    # checkpoint + best files (distributed.py:117-120,210-218).
    assert os.path.exists(os.path.join(out, "experiment.log"))
    assert os.path.exists(os.path.join(out, "settings.log"))
    assert os.path.exists(os.path.join(out, "checkpoint.msgpack"))
    assert os.path.exists(os.path.join(out, "model_best.msgpack"))
    assert best > 0.0
    log = open(os.path.join(out, "experiment.log")).read()
    assert "||==> Train: Epoch[0]" in log
    assert "||==> Val: Epoch[1]" in log


@pytest.mark.slow
def test_resume_continues_from_checkpoint(tmp_path):
    cfg = _cfg(tmp_path, epochs=1)
    t = Trainer(cfg, writer=None)
    t.fit()
    step_after = int(t.state.step)
    assert step_after > 0

    cfg2 = _cfg(tmp_path, epochs=2, outpath=str(tmp_path / "out2"),
                resume=os.path.join(cfg.outpath, "checkpoint.msgpack"))
    t2 = Trainer(cfg2, writer=None)
    assert t2.start_epoch == 1               # resumes at next epoch
    assert int(t2.state.step) == step_after  # optimizer state restored
    t2.fit()
    assert int(t2.state.step) > step_after


@pytest.mark.slow
def test_evaluate_only_path(tmp_path):
    # reference --evaluate short-circuit (distributed.py:181-183)
    cfg = _cfg(tmp_path, evaluate=True, epochs=3)
    t = Trainer(cfg, writer=None)
    acc = t.fit()
    assert acc >= 0.0
    assert not os.path.exists(os.path.join(cfg.outpath, "checkpoint.msgpack"))


def test_require_platform_refuses_wrong_backend(tmp_path):
    """--require-platform tpu on a CPU-initialized process must die at
    Trainer init: a run meant for the chip must not complete on the CPU
    and be read as an on-chip result."""
    cfg = _cfg(tmp_path, require_platform="tpu")
    with pytest.raises(SystemExit, match="require-platform"):
        Trainer(cfg, writer=None)


def test_auto_resume_prefers_configured_backend(tmp_path):
    """When an outpath holds BOTH backends' checkpoints (leftovers of
    different runs that shared it), --resume auto must pick the CONFIGURED
    backend's artifact — the format this run reads and will keep writing —
    not whichever file is mtime-newest (code-review r5: the newest-wins rule
    could resume the other backend's artifact that the configured loader
    then mis-routes). Unit-level via __new__: no model/mesh init needed."""
    from tpudist.checkpoint import CKPT_NAME
    from tpudist.checkpoint_orbax import CKPT_DIR

    out = tmp_path / "both"
    out.mkdir()
    msgpack_p = out / CKPT_NAME
    orbax_p = out / CKPT_DIR
    msgpack_p.write_bytes(b"x")
    orbax_p.mkdir()
    os.utime(msgpack_p, (1_000_000, 1_000_000))       # msgpack much older

    t = Trainer.__new__(Trainer)
    t.primary, t.logger = True, None
    t.cfg = _cfg(tmp_path, outpath=str(out), checkpoint_backend="msgpack")
    # configured backend wins even though the other artifact is newer
    assert t._find_auto_resume() == str(msgpack_p)
    t.cfg = _cfg(tmp_path, outpath=str(out), checkpoint_backend="orbax")
    assert t._find_auto_resume() == str(orbax_p)
    # single candidate: returned regardless of the configured backend
    msgpack_p.unlink()
    t.cfg = _cfg(tmp_path, outpath=str(out), checkpoint_backend="msgpack")
    assert t._find_auto_resume() == str(orbax_p)
    orbax_p.rmdir()
    assert t._find_auto_resume() is None


@pytest.mark.slow
def test_elastic_auto_resume_with_keep(tmp_path):
    """The elastic-restart pattern (launch --max-restarts): --overwrite keep
    + --resume auto. A 'relaunched' trainer on the SAME outpath resumes from
    the previous attempt's checkpoint; on a fresh outpath the same flags
    start cleanly (attempt 0 has nothing to resume)."""
    cfg = _cfg(tmp_path, epochs=1)
    t = Trainer(cfg, writer=None)
    t.fit()
    step_after = int(t.state.step)

    cfg2 = _cfg(tmp_path, epochs=2, overwrite="keep", resume="auto")
    t2 = Trainer(cfg2, writer=None)
    assert t2.start_epoch == 1
    assert int(t2.state.step) == step_after

    cfg3 = _cfg(tmp_path, outpath=str(tmp_path / "fresh"),
                overwrite="keep", resume="auto")
    t3 = Trainer(cfg3, writer=None)
    assert t3.start_epoch == 0
    log = open(os.path.join(cfg3.outpath, "experiment.log")).read()
    assert "starting fresh" in log
