"""Async checkpointing via orbax (optional backend).

The reference's ``torch.save`` (``/root/reference/utils.py:114-118``) blocks
the training loop for the full serialization+write; the default msgpack
backend here (tpudist/checkpoint.py) does too. This backend hands the state
to orbax's ``AsyncCheckpointer``: device→host copies happen synchronously
(cheap), the disk write proceeds on a background thread while the next epoch
trains — the standard TPU practice for large states.

Same two-slot scheme as the reference: ``checkpoint_orbax/`` every epoch,
``model_best_orbax/`` on a new best. Select with
``--checkpoint-backend orbax``.
"""

from __future__ import annotations

import os
import shutil
from typing import Any, Optional

import jax

CKPT_DIR = "checkpoint_orbax"
BEST_DIR = "model_best_orbax"


def _digest_path(ckpt_dir: str) -> str:
    return os.path.normpath(ckpt_dir) + ".sha256"


def _write_digest(ckpt_dir: str, digest: str) -> None:
    # Every rank of a collective save writes this (identical) sidecar: a
    # shared tmp name lets one rank's replace() take the file another is
    # about to replace.
    tmp = f"{_digest_path(ckpt_dir)}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(f"{digest}  {os.path.basename(os.path.normpath(ckpt_dir))}\n")
    os.replace(tmp, _digest_path(ckpt_dir))


def _read_digest(ckpt_dir: str) -> Optional[str]:
    try:
        with open(_digest_path(ckpt_dir)) as f:
            return f.read().split()[0].strip()
    except (OSError, IndexError):
        return None      # pre-integrity checkpoint: stays loadable


class OrbaxBackend:
    def __init__(self) -> None:
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self._ckpt = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())

    def save(self, state_dict: dict, is_best: bool, outpath: str,
             snapshot_best: bool = True) -> str:
        """Async save — in multi-process runs EVERY process must call this
        (orbax saves are collective; a rank-0-only call deadlocks the
        barrier). On a new best, wait for completion then snapshot the
        directory on the coordinating process (``snapshot_best``), via a tmp
        dir + atomic rename so a crash mid-copy never tears the previous
        best.

        Integrity: a content-level sha256 (``checkpoint.tree_digest`` of the
        host copy handed to orbax) is written as ``<dir>.sha256`` beside the
        checkpoint directory; ``load`` re-hashes what orbax returns and
        refuses a mismatch — torn/corrupt files surface as a clear error
        instead of silently resuming garbage weights."""
        from tpudist.checkpoint import tree_digest
        path = os.path.abspath(os.path.join(outpath, CKPT_DIR))
        host_state = jax.device_get(state_dict)
        digest = tree_digest(host_state)
        self._ckpt.save(path, host_state, force=True)
        _write_digest(path, digest)
        if is_best:
            self._ckpt.wait_until_finished()    # the copy must see a finished write
            if snapshot_best:
                best = os.path.abspath(os.path.join(outpath, BEST_DIR))
                tmp = best + ".tmp"
                old = best + ".old"
                # A crash in a previous rotation (between rename(best, old)
                # and rename(tmp, best)) leaves .old as the ONLY best copy —
                # restore it before rotating so we never rmtree the sole
                # survivor (ADVICE r1 #5).
                if os.path.exists(old) and not os.path.exists(best):
                    os.rename(old, best)
                if os.path.exists(tmp):
                    shutil.rmtree(tmp)
                shutil.copytree(path, tmp)
                if os.path.exists(old):
                    shutil.rmtree(old)
                if os.path.exists(best):
                    os.rename(best, old)
                os.rename(tmp, best)            # atomic within the filesystem
                if os.path.exists(old):
                    shutil.rmtree(old)
                _write_digest(best, digest)     # best holds the same content
        return path

    def load(self, path: str) -> dict:
        from tpudist.checkpoint import tree_digest
        if os.path.isdir(path) and os.path.basename(
                os.path.normpath(path)) not in (CKPT_DIR, BEST_DIR):
            path = os.path.join(path, CKPT_DIR)
        self._ckpt.wait_until_finished()
        path = os.path.abspath(path)
        ckpt = self._ocp.Checkpointer(self._ocp.PyTreeCheckpointHandler())
        restored = ckpt.restore(path)
        want = _read_digest(path)
        if want is not None:
            got = tree_digest(restored)
            if got != want:
                raise ValueError(
                    f"orbax checkpoint {path} fails content verification "
                    f"(sha256 {got[:12]}… != recorded {want[:12]}…): torn "
                    f"write or storage corruption — resume from the best "
                    f"snapshot or an earlier checkpoint instead")
        return restored

    def wait(self) -> None:
        self._ckpt.wait_until_finished()

    def close(self) -> None:
        self._ckpt.wait_until_finished()
        self._ckpt.close()


_backend: Optional[OrbaxBackend] = None


def get_backend() -> OrbaxBackend:
    global _backend
    if _backend is None:
        _backend = OrbaxBackend()
    return _backend


def is_orbax_checkpoint(path: str) -> bool:
    """True when ``path`` is an orbax checkpoint dir (CKPT_DIR/BEST_DIR, or a
    directory containing actual orbax metadata) — routing keys off checkpoint
    CONTENT, never name substrings (a user dir named 'try_orbax' holding a
    msgpack file must not come here)."""
    if not os.path.isdir(path):
        return False
    base = os.path.basename(os.path.normpath(path))
    if base in (CKPT_DIR, BEST_DIR):
        return True
    return os.path.isdir(os.path.join(path, CKPT_DIR))
