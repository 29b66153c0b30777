"""Step-window profiler hooks (SURVEY.md §5 "tracing/profiling: none" in the
reference — its only instrumentation is the data_time/batch_time meters,
``/root/reference/distributed.py:239-240,266``, which we keep; this adds the
TPU-native upgrade: ``jax.profiler`` traces viewable in
TensorBoard/Perfetto/XProf).

``StepProfiler`` captures a trace for a configured step window
(``--profile start:end``): it starts the trace when the global step enters
the window and stops it when the step leaves, writing to
``<outpath>/profile/attempt_<n>`` (one subdir per launcher restart attempt,
so a relaunch cannot overwrite the pre-crash capture). The program labels
the capture under the names of ``tpudist/obs/scopes.py``: a
``StepTraceAnnotation`` around each step, a ``tpudist.*`` annotation on every
other part of a loop turn, and a ``tpudist_*`` named scope on every device
operation, so XProf/Perfetto group host time by phase and device ops by
forward / backward / optimizer and by block out of the box.
Capturing a bounded window (not whole-run) is the
standard TPU practice — traces are large and the interesting steps are the
post-compilation steady state.
"""

from __future__ import annotations

import os
from typing import Optional


def parse_window(spec: str) -> Optional[tuple[int, int]]:
    """'10:20' → (10, 20); '15' → (15, 16); '' → None (off)."""
    if not spec:
        return None
    if ":" in spec:
        a, b = spec.split(":", 1)
        start, end = int(a), int(b)
    else:
        start, end = int(spec), int(spec) + 1
    if end <= start:
        raise ValueError(f"empty profile window '{spec}' (need end > start)")
    return start, end


class StepProfiler:
    """Trace global steps in [start, end). Call ``step(global_step)`` once per
    training step, ``close()`` at exit (stops a still-open trace).

    Traces land in ``<logdir>/profile/attempt_<n>`` where ``n`` is the
    launcher's restart counter (``TPUDIST_RESTART_COUNT``, 0 standalone): an
    elastic relaunch into the same outpath must not overwrite the previous
    attempt's capture — the pre-crash trace is often the interesting one.
    """

    def __init__(self, spec: str, logdir: str, enabled: bool = True,
                 attempt: Optional[int] = None):
        self.window = parse_window(spec) if enabled else None
        if attempt is None:
            # Single shared parse of TPUDIST_RESTART_COUNT: profile dirs and
            # telemetry events must agree on the attempt number.
            from tpudist.telemetry import env_attempt
            attempt = env_attempt()
        self.logdir = os.path.join(logdir, "profile", f"attempt_{attempt}")
        self.active = False

    def step(self, global_step: int) -> None:
        if self.window is None:
            return
        start, end = self.window
        if not self.active and start <= global_step < end:
            import jax
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self.active = True
        elif self.active and global_step >= end:
            self.close()

    def epoch_end(self) -> None:
        """Stop an open trace at the epoch boundary so validation/checkpoint
        work never leaks into the capture (a window past the epoch's last
        train step would otherwise only close on the NEXT epoch's first
        ``step()``). If the window extends into the next epoch, ``step()``
        restarts a fresh trace there."""
        self.close()

    def close(self) -> None:
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False


def peak_hbm_gb() -> float | None:
    """Peak per-device memory high-water mark in GiB, maxed over ALL local
    devices (the reference README's per-GPU Memory column,
    ``/root/reference/README.md:9-14``). Device 0 alone under-reports on any
    multi-chip host with imbalance — uneven sharding, stage-0-heavy pipeline
    layouts — and an OOM headroom number must track the WORST chip. TPU
    runtimes expose allocator stats; backends without them (CPU) return
    None. Shared by the trainer's epoch log and bench.py."""
    import jax
    peaks = []
    try:
        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats()
            except Exception:
                continue
            if stats and "peak_bytes_in_use" in stats:
                peaks.append(stats["peak_bytes_in_use"])
    except Exception:
        pass
    return round(max(peaks) / 2**30, 3) if peaks else None
