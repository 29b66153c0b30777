"""Experiment driver (reference L4: ``main()``/``main_worker()``,
``distributed.py:85-224``) and epoch loops (L3: ``train()``/``validate()``,
``distributed.py:227-334``).

One driver covers all four reference recipes (SURVEY.md §7): plain DP, DDP,
DDP+amp, DDP+amp+SyncBN are ``Config`` flag states. Keeps the reference's
observable surface: ``experiment.log``/stdout logging (rank-0 gated),
``settings.log`` dump, per-step console lines every ``print_freq``, epoch
summaries prefixed ``||==>``, TensorBoard scalars (lr, Train_ce_loss,
Train_top1_accuracy, Val_ce_loss, Val_top1_accuracy), per-epoch
checkpoint/best files, best-acc tracking — plus resume, which the reference
lacks.

Hot-loop difference from the reference, by design: the reference pays a
``dist.barrier()`` + 2 allreduces + a blocking ``.item()`` EVERY step
(``distributed.py:253-257``). Here metrics come back as device arrays from the
compiled step and are only materialized every ``print_freq`` steps, so the
host never stalls the device pipeline.
"""

from __future__ import annotations

import signal
import time
from typing import Any, Optional

import os

import jax
import numpy as np

from tpudist import checkpoint as ckpt_lib
from tpudist import faults
from tpudist import telemetry as telemetry_lib
from tpudist.config import Config, write_settings
from tpudist.doctor.policy import RollbackRequested
from tpudist.data import build_train_val_loaders
from tpudist.dist import (data_rank_world, replica_rank_world,
                          shard_host_batch)
from tpudist.models import create_model, model_fields, model_with
from tpudist.obs import scopes
from tpudist.train import (TrainState, compute_dtype, create_train_state,
                           lr_for_epoch, make_eval_step, make_train_step)
from tpudist.utils import (AverageMeter, StepProfiler, Watchdog,
                           assert_replicas_consistent, get_logger,
                           output_process, peak_hbm_gb)
from tpudist.utils.meters import ProgressMeter


def _parse_share(text: str, flag: str) -> tuple[int, int]:
    """'i/n' -> (i, n): the i-th of n holders."""
    try:
        i, n = (int(part) for part in str(text).split("/"))
    except ValueError:
        raise ValueError(f"{flag} takes 'i/n' (the i-th of n holders), "
                         f"got {text!r}") from None
    if not 0 <= i < n:
        raise ValueError(f"{flag} {text!r}: need 0 <= i < n")
    return i, n


class _MetricDrain:
    """Defers device→host metric transfer: update meters in bulk only when
    displayed (fixes reference hot-loop bug #4 while keeping exact averages).

    ``lag`` > 0 is the async-drain mode (``--async-drain``, ROADMAP item
    5's MFU candidate): ``push`` issues an async device→host copy the
    moment the step is dispatched, and ``drain_ready`` materializes only
    entries at least ``lag`` steps old — by then the copy has landed, so
    the drain never blocks on the in-flight step's compute. The trainer
    calls ``drain_ready`` right after dispatching the NEXT step, booking
    the (tiny) host time as the overlapped ``drain_ovl`` telemetry bucket.
    ``drain`` still flushes everything (epoch end — averages stay exact).

    ``observer(step, values)`` (the doctor's signal feed) sees every
    drained entry as host floats — the SAME deferred materialization the
    meters use, so the guard sentinels' flags reach the policy engine
    with zero additional host syncs. Entries flagged ``notfinite`` by the
    guarded step skip the meters (the update was zeroed in-program,
    GradScaler-style — a NaN loss must not poison the epoch averages) but
    still reach the observer, which is how the doctor audits the skip.
    """

    def __init__(self, meters: dict[str, AverageMeter], lag: int = 0,
                 observer=None):
        self.meters = meters
        self.lag = max(0, int(lag))
        self.observer = observer
        self.pending: list[tuple[dict, int, Optional[int]]] = []

    def push(self, metrics: dict, n: int, step: Optional[int] = None) -> None:
        if self.lag:
            for v in metrics.values():
                try:
                    v.copy_to_host_async()
                except AttributeError:
                    pass        # non-jax leaf / backend without async copy
        self.pending.append((metrics, n, step))

    def _apply(self, entries) -> None:
        for metrics, n, step in entries:
            vals = {k: float(v) for k, v in metrics.items()}
            # a model's own counters (an expert layer's pairs a layer and a
            # step): kept for whoever reads them (telemetry.counters())
            for k, v in vals.items():
                if k.startswith(scopes.MODEL_COUNTERS):
                    telemetry_lib.record_counter(k, v)
            if vals.get("notfinite", 0.0) < 0.5:
                for k, meter in self.meters.items():
                    meter.update(vals[k], n)
            if self.observer is not None:
                self.observer(step, vals)

    def drain_ready(self) -> None:
        """Materialize entries at least ``lag`` steps old (their async
        copies have completed behind the subsequent dispatches)."""
        keep = len(self.pending) - self.lag
        if keep <= 0:
            return
        self._apply(self.pending[:keep])
        del self.pending[:keep]

    def drain(self) -> None:
        self._apply(self.pending)
        self.pending.clear()


class PreemptionRequested(Exception):
    """Raised at the next step boundary after SIGTERM/SIGINT: fit() drains,
    writes an emergency checkpoint, and exits PREEMPTED_EXIT_CODE."""


class _PreemptionGuard:
    """SIGTERM/SIGINT → a flag the step loops poll, instead of dying
    mid-step. TPU fleets preempt with SIGTERM + a grace window (and the
    launcher's teardown sends exactly that): the trainer finishes the
    in-flight step, writes an emergency checkpoint, and exits with
    ``faults.PREEMPTED_EXIT_CODE`` so the launcher logs it as resumable.
    A SECOND signal restores default handling — an operator mashing Ctrl-C
    must still be able to kill a trainer wedged in its drain."""

    def __init__(self):
        self.requested: Optional[int] = None
        self._prev: dict[int, Any] = {}

    def _handler(self, signum, frame):
        if self.requested is not None:
            self.uninstall()
            signal.raise_signal(signum)
            return
        self.requested = signum

    def install(self) -> "_PreemptionGuard":
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev[sig] = signal.signal(sig, self._handler)
            except ValueError:
                # Not the main thread (embedded use): polling still works
                # for signals delivered by other means; skip installation.
                pass
        return self

    def uninstall(self) -> None:
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, prev)
            except ValueError:
                pass
        self._prev.clear()

    def check(self) -> None:
        if self.requested is not None:
            raise PreemptionRequested(signal.Signals(self.requested).name)


class Trainer:
    """Build-everything-then-fit (reference ``main_worker``,
    ``distributed.py:108-224``)."""

    def __init__(self, cfg: Config, mesh=None, writer: Any = "auto"):
        self.cfg = cfg
        # every compile of the process from here on is kept with the step it
        # fell in (telemetry.compile_events()); one after the first dispatch
        # is logged (_on_compile reads these three)
        self.global_step = 0
        self._train_dispatched = False
        self._resolving_flops = False
        telemetry_lib.watch_compiles(self._on_compile)
        # Set-up phases (scopes.INIT_*): mark(name) books the seconds since
        # the last mark, so the phases sum to this constructor's wall time.
        t_mark = time.monotonic()
        spent = dict.fromkeys(scopes.INIT_PHASES, 0.0)

        def mark(name: str) -> None:
            nonlocal t_mark
            now = time.monotonic()
            spent[name] = spent.get(name, 0.0) + (now - t_mark)
            t_mark = now

        # Arm fault injection before anything can fail: an explicit
        # cfg.inject wins, else the spec the launcher put in TPUDIST_INJECT.
        faults.configure(cfg.inject if getattr(cfg, "inject", "") else None)
        if cfg.require_platform not in ("any", jax.default_backend()):
            # Fail FAST and loudly: a run that was meant for the chip must
            # not complete on another backend and be read as an on-chip
            # result.
            raise SystemExit(
                f"--require-platform {cfg.require_platform}: jax initialized "
                f"on '{jax.default_backend()}' — refusing to run")
        mark(scopes.INIT_OTHER)
        if mesh is not None:
            self.mesh = mesh
        else:
            # Mesh construction is a plane derivation (ISSUE 12): the
            # requested axis composition is validated LOUDLY (unknown/
            # duplicate axis names, shape/axes mismatch, device-count
            # mismatch, split tp axis on a rule-less family) before any
            # devices are touched.
            from tpudist.parallel.plane import build_mesh
            self.mesh = build_mesh(cfg)
        cfg.finalize(self.mesh.devices.size)
        mark(scopes.INIT_MESH)
        # Data-plane identity: (process_index, process_count) under the real
        # distributed runtime; the launcher's env identity under the elastic
        # CPU gang simulation (dist.data_rank_world) — primary gating rides
        # it so two independent sim ranks cannot both claim rank 0's
        # checkpoint/log duties.
        self.data_rank, self.data_world = data_rank_world()
        self.primary = self.data_rank == 0
        if cfg.torch_checkpoints:
            # Fail in seconds, not at the end-of-epoch save, if the arch has
            # no torch-naming interop.
            from tpudist.compat.torch_checkpoint import _family
            _family(cfg.arch)

        # rank-0-only experiment dir / logger / TB writer (distributed.py:117-120)
        self.logger = None
        self.writer = None
        if self.primary:
            output_process(cfg.outpath, cfg.overwrite)
            self.logger = get_logger(cfg.outpath)
            write_settings(cfg, cfg.outpath)
            if writer == "auto":
                try:
                    from tensorboardX import SummaryWriter
                    self.writer = SummaryWriter(cfg.outpath)
                except Exception:
                    self.writer = None
            else:
                self.writer = writer

        # Persistent XLA compilation cache (serve/cache.py resolves where):
        # configured BEFORE anything compiles so the step builders, the AOT
        # cost-analysis lowering, and any eval program all hit it.
        # Provenance (warm/cold) is stamped on every compile telemetry
        # event below — an elastic restart that re-pays only cache-hit
        # seconds must be attributable as such.
        from tpudist.serve.cache import configure_compile_cache
        cache_dir, self.compile_cache_state = configure_compile_cache(
            cfg.compile_cache, log=self.log)
        self.log(f"=> persistent compilation cache: {cache_dir} "
                 f"({self.compile_cache_state})")

        # Structured telemetry (tpudist/telemetry.py): EVERY rank streams
        # events.<rank>.jsonl + a heartbeat into the (shared-filesystem)
        # outpath — created before load() below so checkpoint restores are
        # on the timeline. Non-primary ranks create the dir themselves
        # (output_process is rank-0-only); with --overwrite delete on a
        # multi-process launch, rank 0's cleanup can race a peer's first
        # write — elastic launches already run --overwrite keep.
        self.telemetry = None
        self.metrics_server = None
        self.blackbox = None
        if cfg.telemetry:
            # Rank identity: jax.process_index() once the distributed
            # runtime is up; otherwise the launcher-assigned env id (a CPU
            # launch sim without --distributed runs independent processes
            # whose process_index is uniformly 0 — their telemetry must not
            # collide in one events.0.jsonl).
            tel_rank = jax.process_index()
            if jax.process_count() == 1:
                try:
                    tel_rank = int(os.environ.get("TPUDIST_PROCESS_ID",
                                                  tel_rank))
                except ValueError:
                    pass
            if not self.primary:
                # Let rank 0's output_process create the dir first: if a
                # peer's makedirs wins the race on a FRESH outpath, rank 0
                # (default --overwrite prompt, headless) sees an "existing"
                # dir and aborts the whole job. Bounded wait, then create
                # anyway (non-trainer layouts may have no rank 0 dir step).
                deadline = time.time() + 10.0
                while not os.path.isdir(cfg.outpath) \
                        and time.time() < deadline:
                    time.sleep(0.05)
            self.telemetry = telemetry_lib.Telemetry(
                cfg.outpath, rank=tel_rank,
                max_mb=getattr(cfg, "telemetry_max_mb", 256.0))
            self.telemetry.compile_cache = self.compile_cache_state
            telemetry_lib.set_current(self.telemetry)
            faults.set_observer(self._on_fault)
            # Live metrics endpoint (tpudist/obs/server.py): the registry is
            # a telemetry SINK, attached before run_start so the very first
            # event is already scrapeable — the hot loop gains no new clocks.
            if getattr(cfg, "metrics_port", -1) >= 0:
                from tpudist.obs.server import MetricsRegistry, MetricsServer
                reg = MetricsRegistry(rank=tel_rank)
                self.telemetry.add_sink(reg.observe)
                try:
                    self.metrics_server = MetricsServer(
                        reg, port=cfg.metrics_port).start()
                except OSError as e:
                    # Same-host multi-rank launches pass every rank the SAME
                    # fixed port; losing the bind race must degrade to an
                    # ephemeral port (discoverable via the port file), not
                    # crash the rank and burn the restart budget.
                    self.log(f"=> metrics port {cfg.metrics_port} "
                             f"unavailable ({e!r}) — falling back to an "
                             f"ephemeral port")
                    self.metrics_server = MetricsServer(reg, port=0).start()
                self.metrics_server.write_portfile(cfg.outpath, tel_rank)
                self.log(f"=> live metrics on :{self.metrics_server.port} "
                         f"(/metrics Prometheus text, /healthz)")
            # Blackbox flight recorder (tpudist/blackbox.py): another
            # telemetry sink, same zero-new-clocks contract as the
            # registry above — the per-step cost is one deque append.
            # SIGUSR2 / POST /capture arm a manual deep capture through
            # the same one-shot path the anomaly triggers use.
            if getattr(cfg, "blackbox", False):
                from tpudist import blackbox as blackbox_lib
                self.blackbox = blackbox_lib.BlackboxRecorder(
                    cfg.outpath, rank=tel_rank,
                    ring=cfg.blackbox_ring,
                    capture_steps=cfg.blackbox_capture_steps,
                    cooldown_s=cfg.blackbox_cooldown_s,
                    telemetry=self.telemetry)
                self.telemetry.add_sink(self.blackbox.observe)
                blackbox_lib.install_sigusr2(self.blackbox)
                if self.metrics_server is not None:
                    self.metrics_server.set_capture(
                        lambda: self.blackbox.request_capture("http"))
                self.log(f"=> blackbox armed: ring {cfg.blackbox_ring}, "
                         f"capture {cfg.blackbox_capture_steps} steps, "
                         f"cooldown {cfg.blackbox_cooldown_s:g}s "
                         f"(SIGUSR2 or POST /capture for manual)")
            mark(scopes.INIT_OTHER)
            self.telemetry.emit(
                "run_start", platform=jax.default_backend(),
                n_devices=jax.device_count(),
                device_kind=jax.devices()[0].device_kind, arch=cfg.arch,
                global_batch=cfg.batch_size,
                # Surfaced here so the LIVE goodput denominator can include
                # pre-trainer init (run_end repeats the final number).
                init_s=round(self.telemetry.init_s, 3),
                # Set-up seconds booked so far (the runtime's `init`, this
                # constructor's first phases); the whole set is logged at
                # the constructor's end and read with telemetry.phases().
                phases={k: round(v, 3) for k, v in
                        {**telemetry_lib.phases(), **spent}.items()})
        else:
            # Nobody will pop dist.initialize_runtime's init stash: clear
            # it so a LATER in-process Telemetry can't inherit this run's
            # init as its own.
            telemetry_lib.clear_pending()
        # Per-step MFU inputs, resolved lazily on the first train step.
        self._flops_per_step = None
        self._peak_flops = None

        # Parallelism mode is a config state of this one trainer (VERDICT r1
        # weak #2), derived by the single parallelism plane (ISSUE 12,
        # parallel/plane.py): a mesh with a 'model' axis selects the GSPMD
        # (pjit) path with per-family rule tables; a 'seq' axis selects
        # sequence-parallel ring attention (ViT family); otherwise the
        # shard_map DP path. The plan's fields are mirrored as attributes
        # because they ARE this trainer's public topology surface.
        from tpudist.parallel import plane
        self.plan = plane.plan(cfg, self.mesh)
        self.uses_model_axis = self.plan.uses_model_axis
        self.uses_seq_axis = self.plan.uses_seq_axis
        self.uses_expert_axis = self.plan.uses_expert_axis
        self.uses_pipe_axis = self.plan.uses_pipe_axis
        self.data_axis = self.plan.data_axis
        self.ep_data_axis = self.plan.ep_data_axis
        self.batch_axes = self.plan.batch_axes
        self.zero_mode = self.plan.zero_mode
        self.zero_axis = self.plan.zero_axis
        self.uses_wus_path = self.plan.uses_wus_path
        self.pp_model_axis = self.plan.pp_model_axis
        self.uses_gspmd_path = self.plan.uses_gspmd_path
        if self.uses_model_axis and not self.uses_pipe_axis:
            # Before any model is built: a 'model' axis over an arch whose
            # rule table is empty would run pure DP through the GSPMD path.
            plane.rules_for_mesh(cfg.arch, self.mesh)
        # What the run asks of the model, by the flag or mesh axis that
        # asks: the model's own fields answer (models.model_with refuses a
        # field the architecture does not have, before any state is made).
        asked = {}
        if cfg.remat:
            asked["--remat"] = dict(remat=True)
        if self.uses_seq_axis:
            if self.data_axis == "seq":
                raise ValueError(
                    "sequence parallelism needs a batch axis alongside "
                    "'seq': the step replicates images over the ring and "
                    "shards them over the data axis. For pure SP use "
                    "--mesh-shape 1,N --mesh-axes data,seq")
            if cfg.pretrained:
                raise ValueError(
                    "--pretrained is not supported with sequence "
                    "parallelism: the SP ViT uses a GAP head (no "
                    "class_token, shorter pos_embedding), which cannot "
                    "match torchvision ViT checkpoints")
            if cfg.flash == "on":
                raise ValueError(
                    "--flash on cannot combine with sequence parallelism: "
                    "the seq-axis attention goes around the ring "
                    "(parallel/ring_attention.py) and does not use the "
                    "Pallas kernel. Use --flash auto or off")
            # Ring attention over the seq axis; GAP head (uniform shards).
            asked["mesh axis 'seq'"] = dict(seq_axis="seq", pool="gap")
        if self.uses_expert_axis:
            if list(cfg.mesh_axes) not in (["expert"], ["data", "expert"]):
                raise ValueError(
                    "expert parallelism uses a pure ('expert',) mesh (the "
                    "expert axis doubles as the batch axis) or a "
                    "('data', 'expert') mesh for dp×ep composition; got "
                    f"mesh_axes={list(cfg.mesh_axes)}")
            if cfg.pretrained:
                raise ValueError("--pretrained is not supported for MoE "
                                 "archs (no torchvision equivalent)")
            asked["mesh axis 'expert'"] = dict(
                expert_axis="expert", num_experts=self.mesh.shape["expert"])
            if self.ep_data_axis:
                # dp×ep: load-balance statistics average over the whole
                # global batch, not one data slice (models/vit_moe.py).
                asked["mesh axis 'expert'"]["aux_axes"] = ("data", "expert")
        if self.uses_pipe_axis:
            if self.data_axis == "pipe":
                raise ValueError(
                    "pipeline parallelism needs a batch axis alongside "
                    "'pipe' (stages see activations only through the ring). "
                    "For pure PP use --mesh-shape 1,N --mesh-axes data,pipe")
            if cfg.pretrained:
                raise ValueError(
                    "--pretrained is not supported for pipelined archs (the "
                    "nn.scan-stacked trunk has no torchvision layout)")
            asked["mesh axis 'pipe'"] = dict(
                pipe_axis="pipe", num_microbatches=cfg.microbatches)
            if self.pp_model_axis:
                asked["mesh axis 'pipe'"]["model_axis"] = self.pp_model_axis
        # Under GSPMD the global-batch BN statistics ARE SyncBN (the
        # partitioner reduces over the whole sharded batch); the explicit
        # pmean-BN flag belongs to the shard_map path only.
        sync_bn = cfg.sync_batchnorm and not self.uses_gspmd_path
        plain = create_model(
            cfg.arch, num_classes=cfg.num_classes, dtype=compute_dtype(cfg),
            sync_batchnorm=sync_bn, bn_axis_name=self.data_axis)
        takes = model_fields(plain)
        if cfg.flash == "on" or (cfg.flash == "off" and "flash" in takes):
            # 'off' asks nothing of a model without attention: a sweep may
            # pass one `--flash off` across mixed archs. `auto` is the
            # resolver's to set, below.
            asked[f"--flash {cfg.flash}"] = dict(flash=cfg.flash == "on")
        # A model of tokens holds a share of a vocabulary, and is told what
        # this holder keeps of a deployment's model.
        self.trains_tokens = "vocab_share" in takes
        share = dict(
            layers=cfg.layers,
            expert_share=_parse_share(cfg.expert_share, "--expert-share"),
            vocab_share=_parse_share(cfg.vocab_share, "--vocab-share"))
        if self.trains_tokens and cfg.seq_len < 1:
            raise ValueError(
                f"'{cfg.arch}' trains on rows of token ids: give "
                f"--seq-len (ids a row; -b counts rows)")
        if self.trains_tokens or share != dict(
                layers=0, expert_share=(0, 1), vocab_share=(0, 1)):
            asked["--layers / --expert-share / --vocab-share"] = share
        self.model = model_with(plain, cfg.arch, asked)
        # Attention's kernel is resolved here, outside any trace: `auto`
        # times the Pallas kernel against XLA attention on the attached
        # chip at the workload's own shape (the verdict kept per
        # device_kind) and never selects a kernel that lost; off the TPU it
        # is the XLA path and Pallas is not touched. A seq-axis run skips
        # it: its attention goes around the ring, not through the kernel.
        self.flash_decision = None
        mark(scopes.INIT_OTHER)
        if "flash" in takes and not self.uses_seq_axis:
            self.flash_decision = self._resolve_attention()
        if self.trains_tokens:
            for event, plan in self.model.plans(cfg.per_device_batch_size,
                                                cfg.seq_len):
                self._announce_plan(event, plan)
        seed = cfg.seed if cfg.seed is not None else 0
        mark(scopes.INIT_DISPATCH)
        # SPMD collectives can't be traced by model.init outside shard_map:
        # init with the unsharded twin (identical param tree — the SP model
        # slices tokens after patchify/pos-embed; the EP twin runs experts
        # dense/vmapped with the same stacked [E] weights).
        self._init_model = self.model.clone(**{
            axis: None for axis in ("seq_axis", "expert_axis", "pipe_axis")
            if axis in takes})
        self.state = create_train_state(jax.random.PRNGKey(seed),
                                        self._init_model, cfg)
        mark(scopes.INIT_MODEL_STATE)
        if cfg.pretrained:
            # Reference: torchvision pretrained=True + "=> using pre-trained
            # model" (distributed.py:134-137). Offline: local torchvision
            # .pth via the compat layer (no dead flags — VERDICT r1 #2).
            from tpudist.compat import load_pretrained, resolve_pretrained_path
            p = resolve_pretrained_path(cfg.arch, cfg.pretrained_path)
            self.state = load_pretrained(self.state, cfg.arch, p)
            self.log(f"=> using pre-trained model '{cfg.arch}' (from {p})")
        else:
            self.log(f"=> creating model '{cfg.arch}'")
        mark(scopes.INIT_OTHER)
        # Measurement-honest gradient-compression dispatch
        # (ops/comm_dispatch, the second client of the generic honesty
        # layer): resolve --compress-grads OUTSIDE any trace, BEFORE the
        # step builders — `auto` A/Bs the quantized exchange against the
        # dense pmean at the exact gradient size over the real mesh
        # (cached per device_kind, one gang-wide verdict, int8 never
        # selected off a measurement it lost); the error-feedback residual
        # is seeded into the train state only when int8 actually dispatches.
        self.comm_decision = None
        self.compress = None
        if getattr(cfg, "compress_grads", "off") != "off":
            self.comm_decision = self._resolve_comm_dispatch()
            if self.comm_decision.get("kernel") == "int8":
                self.compress = "int8"
                from tpudist.parallel.comm import init_comm_state
                self.state = self.state.replace(
                    comm_state=init_comm_state(
                        self.state.params,
                        self.mesh.shape[self.data_axis]))
        zero_axis = self.zero_axis
        # (rules, zero_mode, axis) behind this run's state placement — the
        # inputs `plane.state_specs` needs to reproduce the layout truth
        # on demand (the doctor's SDC probe reads it to know which leaves
        # are dp-replicated and must be bit-identical across replicas).
        self._placement = ((), None, None)
        mark(scopes.INIT_DISPATCH)
        if self.uses_wus_path:
            from tpudist.parallel import (make_wus_eval_step,
                                          make_wus_train_step)
            self.rules = None
            self._placement = ((), "full", self.data_axis)
            self._shard_state = lambda s: plane.shard_state(
                self.mesh, s, (), zero_mode="full",
                data_axis=self.data_axis)
            self.state = self._shard_state(self.state)
            mark(scopes.INIT_SHARD_STATE)
            self.train_step = make_wus_train_step(
                self.mesh, self.model, cfg, data_axis=self.data_axis,
                compress=self.compress)
            self.eval_step = make_wus_eval_step(
                self.mesh, self.model, cfg, data_axis=self.data_axis)
            self.log(f"=> ZeRO-full weight-update sharding over "
                     f"'{self.data_axis}' "
                     f"(x{self.mesh.shape[self.data_axis]}): params + "
                     f"optimizer + EMA sharded, just-in-time all-gather, "
                     f"gradient reduce-scatter"
                     + (", int8-compressed gradient exchange"
                        if self.compress else ""))
        elif self.uses_gspmd_path:
            from tpudist.parallel import (make_gspmd_eval_step,
                                          make_gspmd_train_step)
            # rules_for_mesh closes the silent-no-op hole (VERDICT r5 weak
            # #3): a >1 'model' axis with an empty rule table is a refusal.
            self.rules = (plane.rules_for_mesh(cfg.arch, self.mesh)
                          if self.uses_model_axis else ())
            self._placement = (self.rules, "1" if zero_axis else None,
                               zero_axis)
            self._shard_state = lambda s: plane.shard_state(
                self.mesh, s, self.rules,
                zero_mode=("1" if zero_axis else None),
                data_axis=zero_axis)
            self.state = self._shard_state(self.state)
            mark(scopes.INIT_SHARD_STATE)
            self.train_step = make_gspmd_train_step(
                self.mesh, self.model, cfg, self.rules,
                data_axis=self.data_axis, opt_shard_axis=zero_axis)
            self.eval_step = make_gspmd_eval_step(
                self.mesh, self.model, cfg, self.rules,
                data_axis=self.data_axis, opt_shard_axis=zero_axis)
            self.log(f"=> GSPMD parallelism: mesh "
                     f"{dict(zip(cfg.mesh_axes, self.mesh.devices.shape))}, "
                     f"rules for '{cfg.arch}'"
                     + (", ZeRO-1 weight-update sharding over "
                        f"'{zero_axis}'" if zero_axis else ""))
        elif self.uses_pipe_axis:
            from tpudist.parallel import (make_pp_eval_step,
                                          make_pp_train_step)
            self.rules = None
            self._shard_state = lambda s: s
            self.train_step = make_pp_train_step(
                self.mesh, self.model, cfg, data_axis=self.data_axis,
                pipe_axis="pipe", model_axis=self.pp_model_axis)
            self.eval_step = make_pp_eval_step(
                self.mesh, self.model, cfg, data_axis=self.data_axis,
                pipe_axis="pipe", model_axis=self.pp_model_axis)
            self.log(f"=> pipeline parallelism: "
                     f"{self.mesh.shape['pipe']} stages, GPipe microbatch "
                     f"schedule over 'pipe'"
                     + (f", Megatron TP ×{self.mesh.shape['model']} inside "
                        f"each stage" if self.pp_model_axis else ""))
        elif self.uses_expert_axis:
            from tpudist.parallel import (make_ep_eval_step,
                                          make_ep_train_step)
            self.rules = None
            self._shard_state = lambda s: s
            self.train_step = make_ep_train_step(self.mesh, self.model, cfg,
                                                 expert_axis="expert",
                                                 data_axis=self.ep_data_axis)
            self.eval_step = make_ep_eval_step(self.mesh, self.model, cfg,
                                               expert_axis="expert",
                                               data_axis=self.ep_data_axis)
            self.log(f"=> expert parallelism: "
                     f"{self.mesh.shape['expert']} experts, all_to_all "
                     f"dispatch over 'expert'"
                     + (f", ×{self.mesh.shape['data']} data parallel"
                        if self.ep_data_axis else ""))
        elif self.uses_seq_axis:
            from tpudist.parallel import make_sp_train_step
            self.rules = None
            self._shard_state = lambda s: s
            self.train_step = make_sp_train_step(
                self.mesh, self.model, cfg, data_axis=self.data_axis,
                seq_axis="seq")
            # Eval needs no SP-specific step: shard_map binds the seq axis
            # for the model's ring attention either way.
            self.eval_step = make_eval_step(self.mesh, self.model, cfg,
                                            data_axis=self.data_axis)
            self.log(f"=> sequence parallelism: mesh "
                     f"{dict(zip(cfg.mesh_axes, self.mesh.devices.shape))}, "
                     f"ring attention over 'seq'")
        else:
            self.rules = None
            if self.compress:
                # Everything replicated EXCEPT the (world, n) error-feedback
                # residual, whose row r lives on device r (zero_mode="comm"
                # — the same placement table the step's in_specs use).
                self._placement = ((), "comm", self.data_axis)
                self._shard_state = lambda s: plane.shard_state(
                    self.mesh, s, (), zero_mode="comm",
                    data_axis=self.data_axis)
                self.state = self._shard_state(self.state)
            else:
                # Nothing is placed here: the replicated state goes onto the
                # mesh with the first step's call (init.shard_state reads 0).
                self._shard_state = lambda s: s
            mark(scopes.INIT_SHARD_STATE)
            self.train_step = make_train_step(self.mesh, self.model, cfg,
                                              data_axis=self.data_axis,
                                              compress=self.compress,
                                              guard=cfg.doctor)
            self.eval_step = make_eval_step(self.mesh, self.model, cfg,
                                            data_axis=self.data_axis)
            if self.compress:
                self.log(f"=> int8-compressed gradient exchange over "
                         f"'{self.data_axis}' "
                         f"(x{self.mesh.shape[self.data_axis]}), error "
                         f"feedback carried in state.comm_state")
        mark(scopes.INIT_STEP_BUILD)
        # tpudist.doctor (--doctor): the guarded step's host-side policy
        # engine. The SDC probe reads the placement truth via
        # plane.state_specs so only dp-replicated leaves are compared.
        self.doctor = None
        self._poison_windows: dict[int, list[tuple[int, int]]] = {}
        if cfg.doctor:
            from tpudist.doctor import Doctor
            rules, zmode, zaxis = self._placement
            specs = None
            if zmode is not None or rules:
                specs = plane.state_specs(self.mesh, self.state, rules or (),
                                          zero_mode=zmode, data_axis=zaxis)
            # The probe compares REPLICAS — processes holding nominally
            # bit-identical state — so it rides the replica identity, not
            # the data identity (they differ only in the CPU gang sims;
            # dist.replica_rank_world documents the split).
            rep_rank, rep_world = replica_rank_world()
            self.doctor = Doctor(
                cfg, cfg.outpath, rank=rep_rank, world=rep_world,
                state_specs=specs, data_axis=self.data_axis,
                telemetry=self.telemetry, log=self.log_all,
                primary=self.primary)
            probe_msg = (f"SDC probes every {cfg.doctor_probe_freq} steps"
                         if cfg.doctor_probe_freq else "SDC probes off")
            sentinel = ("in-step sentinels fused (skip-step on non-finite)"
                        if not (self.uses_gspmd_path or self.uses_wus_path
                                or self.uses_seq_axis or self.uses_pipe_axis
                                or self.uses_expert_axis)
                        else "host-side detection only (the in-step "
                             "sentinel covers the DP step builder)")
            self.log(f"=> doctor armed: {sentinel}; EWMA spike detector "
                     f"(σ={cfg.doctor_spike_sigma:g}); {probe_msg}; "
                     f"rollback cap {cfg.doctor_max_rollbacks}")
        self.best_acc1 = 0.0
        self.rows_per_s = None        # the last train epoch's rows a second
        self.start_epoch = cfg.start_epoch
        # Elastic continuation state: a checkpointed mid-epoch sample cursor
        # (set by load() from an emergency save) and this epoch's running
        # global-sample consumption (what the next emergency save records).
        self._pending_cursor: dict | None = None
        self._epoch_consumed = 0
        self._epoch_cursor0 = 0
        # aux subsystems (SURVEY.md §5; absent in the reference)
        self.profiler = StepProfiler(cfg.profile, cfg.outpath,
                                     enabled=self.primary)
        self.watchdog = None   # created in fit() when cfg.stall_timeout > 0
        self.preemption = None  # installed in fit(): SIGTERM-drain guard

        resume_path = cfg.resume
        if resume_path == "auto":
            # Elastic-restart mode (launch --max-restarts + --overwrite
            # keep): resume from whatever checkpoint a previous attempt left
            # in the outpath, or start fresh if this is attempt 0.
            resume_path = self._find_auto_resume()
            if not resume_path:
                self.log("=> --resume auto: no checkpoint in outpath, "
                         "starting fresh")
        mark(scopes.INIT_OTHER)
        if resume_path:
            self.load(resume_path)
            # The optimizer-step counter survives checkpoints; anchor the
            # --profile window / watchdog step count to it so a resumed run
            # does not re-fire an already-captured trace window (ADVICE r1 #3).
            self.global_step = int(jax.device_get(self.state.step))
            mark(scopes.INIT_RESTORE)
        for name, seconds in spent.items():
            telemetry_lib.record_phase(name, seconds)
        self.log("=> set-up phases (s): " + ", ".join(
            f"{name} {seconds:.2f}" for name, seconds in spent.items()))

    def _kick(self) -> None:
        if self.watchdog is not None:
            self.watchdog.kick()

    def _resolve_attention(self) -> dict:
        """Resolve ``--flash`` for the attention the model says its step
        runs (``model.attention_workloads``), host-side, before any step is
        traced; set the model's ``flash`` to the verdict under `auto`, and
        log and emit the decision. One ``fused`` workload is the call the
        start-up probe can time: it goes through ``attention_dispatch.
        decide`` (forced modes too; a probe that raises propagates: only a
        measured loss or a static ineligibility may select the XLA path, a
        kernel that fails to compile is a bug). Any other set of workloads
        (a decoder's grouped-query, windowed or block-masked attention, one
        a layer type) has no probe: what ``--flash`` says, `auto` read as
        `off`, nothing measured and nothing cached; the shape keys carry
        the window and the head grouping."""
        from tpudist.ops import attention_dispatch
        cfg = self.cfg
        dt, train = compute_dtype(cfg), not cfg.evaluate
        # The shape a device ACTUALLY runs: under GSPMD TP the nested manual
        # region (flash_attention_spmd) shards heads over 'model' and batch
        # over 'data' only, so per-shard attention is (per_device_batch ×
        # tp, heads / tp). (The pipe path's TP is dominated by forced modes
        # and microbatching; its probe uses the unsharded shape.)
        tp = (self.mesh.shape["model"]
              if self.uses_model_axis and not self.uses_pipe_axis else 1)
        shapes = []
        for w in self.model.attention_workloads(
                cfg.seq_len if self.trains_tokens else cfg.image_size):
            split = tp if w["heads"] % tp == w["kv_heads"] % tp == 0 else 1
            shapes.append(dict(w, batch=cfg.per_device_batch_size * split,
                               heads=w["heads"] // split,
                               kv_heads=w["kv_heads"] // split))
        keys = [attention_dispatch.shape_key(
                    w["batch"], w["seq"], w["heads"], w["head_dim"], dt,
                    train, w["causal"], kv_heads=w["kv_heads"],
                    window=w["window"],
                    block_diffusion=w.get("block_diffusion"))
                for w in shapes]
        probed = len(shapes) == 1 and shapes[0]["fused"]
        if probed:
            w = shapes[0]

            def _decide():
                return attention_dispatch.decide(
                    w["batch"], w["seq"], w["heads"], w["head_dim"], dt,
                    train=train, causal=w["causal"], mode=cfg.flash)

            if jax.process_count() > 1 and cfg.flash == "auto":
                # One verdict for the gang: a per-host micro-benchmark at a
                # near-tie shape could compile DIFFERENT attention backends
                # into one SPMD program. Primary decides, peers read it
                # from the shared run dir.
                dec = attention_dispatch.shared_decision(
                    cfg.outpath, self.primary, _decide, expect_key=keys[0],
                    log=self.log)
            else:
                dec = _decide()
        else:
            kernel = "flash" if cfg.flash == "on" else "xla"
            key = ",".join(keys)
            why = ("no start-up probe for grouped-query or windowed "
                   "attention: auto is the XLA path"
                   if cfg.flash == "auto" else None)
            dec = {"kernel": kernel, "mode": cfg.flash, "source": "forced",
                   "key": key, "reason": "; ".join(filter(None, [why, key])),
                   "kernel_rev": attention_dispatch.kernel_rev()
                   if kernel == "flash" else None}
        if cfg.flash == "auto":
            self.model = self.model.clone(flash=dec["kernel"] == "flash")
        if dec["kernel"] == "flash":
            # which of the kernel's schedules each shape takes and how far
            # it engages there; one entry a workload, in the keys' order
            dec["programs"] = [attention_dispatch.program(
                w["seq"], w["heads"], w["head_dim"], dt,
                kv_heads=w["kv_heads"], causal=w["causal"],
                window=w["window"], block_diffusion=w.get("block_diffusion"),
                fused=w["fused"]) for w in shapes]
            if dec["programs"]:       # a share may keep no attention
                dec["schedule"] = dec["programs"][0]["schedule"]
        return self._announce_flash_decision(dec)

    def _announce_flash_decision(self, dec: dict) -> dict:
        """The attention decision's log line and telemetry event."""
        from tpudist.ops import attention_dispatch
        msg = (f"=> attention dispatch: {dec['kernel']} attention "
               f"(mode {dec['mode']}, {dec['source']}")
        if dec.get("schedule"):
            msg += f", schedule {dec['schedule']}"
        for p in dec.get("programs", ()):
            msg += (f", heads_per_program {p['heads_per_program']} "
                    f"block_q {p['block_q']} block_k {p['block_k']} "
                    f"band_fill {p['band_fill']}")
            if "mask" in p:
                msg += f" mask {p['mask']} block_length {p['block_length']}"
        if dec.get("reason"):
            msg += f": {dec['reason']}"
        if dec.get("flash_ms") is not None:
            msg += (f"; flash {dec['flash_ms']:.3f} ms vs "
                    f"xla {dec['xla_ms']:.3f} ms, margin "
                    f"{dec.get('margin', 0.0):.1%}")
        self.log(msg + ")")
        if self.telemetry is not None:
            self.telemetry.emit("attention_dispatch",
                                **attention_dispatch.event_fields(dec))
        return dec

    def _announce_plan(self, event: str, plan: dict) -> None:
        """The log line and telemetry event of a plan the model read from
        its shape (``model.plans``: which program runs, nothing to decide):
        its ``kernel`` or ``form``, its other fields, and why (``reason``)
        where a fallback runs."""
        fields = ", ".join(f"{k} {v}" for k, v in plan.items()
                           if k not in ("kernel", "form", "reason"))
        self.log(f"=> {event}: {plan.get('kernel', plan.get('form'))} "
                 f"({fields}"
                 + (f": {plan['reason']}" if "reason" in plan else "") + ")")
        if self.telemetry is not None:
            self.telemetry.emit(event, **plan)

    def _resolve_comm_dispatch(self) -> dict:
        """Resolve ``--compress-grads`` through ``ops/comm_dispatch``
        (host-side, before any step is traced). The workload key is the
        model's exact gradient element count × the data-axis size; under
        `auto` the A/B runs the real exchange over the real mesh on the
        attached fabric (cached per device_kind, never picking int8 off a
        measurement it lost; off-TPU auto = dense). Multi-host gangs get
        ONE verdict via the shared run dir. The decision is logged and
        emitted as a ``comm_dispatch`` telemetry event, carrying the
        dense-equivalent gradient bytes summarize holds the collective
        census against. A probe that raises propagates (only a measured
        loss or a static ineligibility selects dense)."""
        from tpudist.ops import comm_dispatch
        from tpudist.parallel.comm import DEFAULT_CHUNK, grad_size
        cfg = self.cfg
        world = self.mesh.shape[self.data_axis]
        if world < 2:
            raise ValueError(
                f"--compress-grads {cfg.compress_grads}: the "
                f"'{self.data_axis}' axis has size {world} — a "
                f"single-device data axis never reduces a gradient, so "
                f"there is nothing to compress (refusing loudly instead "
                f"of running a silent no-op)")
        n = grad_size(self.state.params)
        dense_bytes = 4 * n               # f32 master gradients
        chunk = DEFAULT_CHUNK

        def _decide():
            return comm_dispatch.decide(
                n, world, mode=cfg.compress_grads, chunk=chunk,
                mesh=self.mesh, data_axis=self.data_axis)

        if jax.process_count() > 1 and cfg.compress_grads == "auto":
            dec = comm_dispatch.shared_decision(
                cfg.outpath, self.primary, _decide,
                expect_key=comm_dispatch.comm_key(n, world, chunk),
                log=self.log)
        else:
            dec = _decide()
        msg = (f"=> comm dispatch: {dec['kernel']} gradient exchange "
               f"(mode {dec['mode']}, {dec['source']}")
        if dec.get("reason"):
            msg += f": {dec['reason']}"
        if dec.get("int8_ms") is not None:
            msg += (f"; int8 {dec['int8_ms']:.3f} ms vs dense "
                    f"{dec['dense_ms']:.3f} ms, margin "
                    f"{dec.get('margin', 0.0):.1%}")
        self.log(msg + f"; dense-equivalent payload "
                       f"{dense_bytes / 2**20:.1f} MiB/step)")
        if self.telemetry is not None:
            self.telemetry.emit(
                "comm_dispatch",
                **comm_dispatch.event_fields(dec, world=world, n_grads=n,
                                             dense_bytes=dense_bytes))
        return dec

    def _on_fault(self, point: str, step, info: dict) -> None:
        """faults.set_observer sink: every injection that fires lands in the
        event stream (may run on loader worker threads — emit is locked)."""
        if self.telemetry is not None:
            fields = {k: v for k, v in info.items()
                      if isinstance(v, (int, float, str))}
            if step is not None:
                fields["step"] = step
            self.telemetry.emit("fault", point=point, **fields)

    def _on_compile(self, ev: dict) -> None:
        """telemetry.watch_compiles' observer: runs where something compiles
        (a retraced step, an eval step's first call, a stray eager op), so a
        turn that compiles nothing pays nothing. A compile that ends after
        the first dispatch is one a steady run should not have: it is logged
        and, with --telemetry, emitted. The cost analysis' own compile
        (``_resolve_step_flops``) is booked there."""
        ev["step"] = step = self.global_step
        if not self._train_dispatched or self._resolving_flops \
                or ev["event"] != telemetry_lib.COMPILE_EVENT:
            return
        self.log(f"=> compile after step {step}: {ev['event']} "
                 f"{ev['seconds']:.2f} s")
        if self.telemetry is not None:
            self.telemetry.note_compile(ev["seconds"], phase="after_dispatch",
                                        step=step, event=ev["event"])

    def _resolve_step_flops(self, images, labels, lr_arr) -> None:
        """Per-device FLOPs of the compiled train step via
        ``.lower().compile().cost_analysis()`` (the same path
        ``tests/test_compiled_cost.py`` goldens) — the numerator of per-step
        MFU. Runs once, right after the first dispatch so the executable is
        already in the persistent compilation cache when one is configured;
        without that cache this costs one extra XLA compile
        (``--no-telemetry_mfu`` opts out)."""
        if not getattr(self.cfg, "telemetry_mfu", True) \
                or self._flops_per_step is not None:
            return
        t0 = time.time()
        flops = None
        intro: dict = {}
        self._resolving_flops = True
        try:
            compiled = self.train_step.lower(
                self.state, images, labels, lr_arr).compile()
            if self.blackbox is not None:
                # A deep capture snapshots this executable's optimized HLO
                # (as_text() is paid at capture time, never here). Strictly
                # optional: --no-telemetry_mfu runs never reach this line
                # and their incident bundles simply carry no HLO artifact.
                self.blackbox.note_compiled(compiled)
            # XLA introspection (tpudist/obs/xla_introspect.py): ONE pass
            # over the compiler surfaces yields the MFU numerator (same
            # cost_analysis unwrap as telemetry.cost_analysis_flops) plus
            # the HBM breakdown + collective census, surfaced on the
            # compile event below so summarize can attribute HBM/comms.
            try:
                from tpudist.obs.xla_introspect import (event_fields,
                                                        introspect)
                intro = event_fields(introspect(
                    compiled, log=lambda m: self.log(f"=> telemetry: {m}")))
            except Exception as e:
                self.log(f"=> telemetry: XLA introspection failed ({e!r})")
            flops = intro.get("flops") or None
            if flops is None:
                self.log("=> telemetry: no cost-analysis flops on this "
                         "backend — per-step MFU will not be reported")
        except Exception as e:
            self.log(f"=> telemetry: step lowering for cost analysis failed "
                     f"({e!r}) — per-step MFU will not be reported")
        finally:
            self._resolving_flops = False
        self._flops_per_step = flops
        self._peak_flops = telemetry_lib.resolve_peak_flops(
            jax.devices()[0].device_kind)
        if self.telemetry is not None:
            self.telemetry.note_compile(time.time() - t0,
                                        phase="cost_analysis", **intro)
            self.telemetry.emit("program", flops_per_step=flops or 0.0,
                                peak_flops=self._peak_flops or 0.0)

    # -- logging ----------------------------------------------------------
    def log(self, msg: str) -> None:
        if self.primary and self.logger is not None:
            self.logger.info(msg)
        elif self.primary:
            print(msg)

    def log_all(self, msg: str) -> None:
        """Every-rank logging (doctor interventions: a non-primary rank
        self-evicting on an SDC verdict must say so SOMEWHERE)."""
        if self.primary:
            self.log(msg)
        else:
            print(f"[rank {self.data_rank}] {msg}", flush=True)

    def scalar(self, tag: str, value: float, step: int) -> None:
        if self.writer is not None:
            self.writer.add_scalar(tag, value, step)

    # -- checkpointing ----------------------------------------------------
    def _topology(self) -> dict:
        """This run's topology tag, stamped into every checkpoint so a
        restore at a different world size can plan its reshard
        (tpudist/elastic/reshard.py)."""
        from tpudist.elastic.reshard import topology_tag
        return topology_tag(
            world=self.data_world,
            mesh_shape=self.mesh.devices.shape,
            mesh_axes=list(self.cfg.mesh_axes),
            n_devices=self.mesh.devices.size,
            per_device_batch=self.cfg.per_device_batch_size,
            global_batch=self.cfg.batch_size,
            zero1=bool(self.zero_axis),
            zero1_axis=(self.data_axis
                        if self.zero_mode in ("1", "full") else ""),
            zero=self.zero_mode)

    def _data_cursor(self, epoch: int, train_loader=None) -> dict:
        """The interrupted epoch's global sample cursor (emergency saves):
        how many positions of the (seed, epoch) global order this epoch has
        consumed, plus the degradation meters so skip/retry accounting
        survives a reform (ShardedSampler.set_cursor semantics)."""
        return {
            "epoch": epoch,
            "consumed": int(self._epoch_consumed),
            "samples_skipped": int(getattr(train_loader, "samples_skipped",
                                           0) or 0),
            "samples_retried": int(getattr(train_loader, "samples_retried",
                                           0) or 0),
        }

    def save(self, epoch: int, is_best: bool) -> None:
        t0 = time.time()
        try:
            self._save(epoch, is_best)
        finally:
            if self.telemetry is not None:
                self.telemetry.note_checkpoint(time.time() - t0,
                                               kind="epoch", epoch=epoch)

    def _save(self, epoch: int, is_best: bool) -> None:
        if self.cfg.checkpoint_backend == "orbax":
            # Orbax saves are COLLECTIVE: every process must enter (a
            # rank-0-only call deadlocks orbax's global barrier). Only the
            # primary snapshots the best copy.
            from tpudist.checkpoint_orbax import get_backend
            state_dict = ckpt_lib.state_to_dict(self.state, self.cfg.arch,
                                                epoch, self.best_acc1,
                                                topology=self._topology(),
                                                doctor=self._doctor_payload())
            get_backend().save(state_dict, is_best, self.cfg.outpath,
                               snapshot_best=self.primary)
        elif self.primary:
            state_dict = ckpt_lib.state_to_dict(self.state, self.cfg.arch,
                                                epoch, self.best_acc1,
                                                topology=self._topology(),
                                                doctor=self._doctor_payload())
            ckpt_lib.save_checkpoint(state_dict, is_best, self.cfg.outpath,
                                     keep=self.cfg.keep_checkpoints)
        if not self.primary:
            return
        if self.cfg.torch_checkpoints:
            # Also mirror the reference's torch files for torch-side tooling.
            import shutil
            from tpudist.compat import save_reference_checkpoint
            # checkpoint.pth.tar is the RESUME artifact: it must hold the
            # live training weights (restore_from_torch re-seeds from it).
            p = save_reference_checkpoint(
                os.path.join(self.cfg.outpath, "checkpoint.pth.tar"),
                self.state, self.cfg.arch, epoch, self.best_acc1)
            if is_best:
                # model_best.pth.tar is the DEPLOY artifact: under
                # --model-ema-decay, best_acc1 was measured on the EMA copy
                # (validate() substitutes it) — export the same weights, or
                # the deployed model would not achieve the recorded metric.
                ema = getattr(self.state, "ema_params", None)
                if ema is None:
                    shutil.copyfile(p, os.path.join(self.cfg.outpath,
                                                    "model_best.pth.tar"))
                else:
                    save_reference_checkpoint(
                        os.path.join(self.cfg.outpath, "model_best.pth.tar"),
                        self.state.replace(params=ema["params"],
                                           batch_stats=ema["batch_stats"]),
                        self.cfg.arch, epoch, self.best_acc1)

    def save_emergency(self, epoch: int, train_loader=None) -> None:
        """Preemption-drain checkpoint: the interrupted epoch is NOT
        complete, so stamp ``epoch - 1`` — resume re-ENTERS epoch ``epoch``
        (state_to_dict stores epoch+1 as the resume point) — and record the
        epoch's global sample cursor so the resumed run (same world or a
        reformed smaller one) CONTINUES the epoch's deterministic sample
        order mid-way instead of replaying consumed samples against
        mid-epoch weights. Never marks best (best_acc1 was measured on a
        finished epoch), and writes the LIVE file only (``keep=0``): a
        history copy would reuse the stored-epoch filename and silently
        overwrite the clean epoch-boundary snapshot in the keep-last-K
        fallback pool with mid-epoch weights."""
        self.log(f"=> preemption: writing emergency checkpoint "
                 f"(will resume at epoch {epoch}, global sample cursor "
                 f"{self._epoch_consumed})")
        t0 = time.time()
        try:
            self._save_emergency(epoch, train_loader)
        finally:
            if self.telemetry is not None:
                self.telemetry.note_checkpoint(time.time() - t0,
                                               kind="emergency", epoch=epoch)

    def _doctor_payload(self) -> dict | None:
        """Doctor replay state for emergency saves: the poison windows and
        rollback count must survive a restart — the emergency cursor counts
        positions of the EXCISED order, so a restarted process that lost
        the windows would apply it to the pristine order (re-delivering the
        poisoned samples), and a per-process rollback count would let a
        deterministic spike loop past --doctor-max-rollbacks forever."""
        if self.doctor is None \
                or not (self._poison_windows or self.doctor.rollbacks):
            return None
        return {"rollbacks": int(self.doctor.rollbacks),
                "poison_windows": {str(ep): [[int(a), int(b)] for a, b in ws]
                                   for ep, ws in self._poison_windows.items()
                                   if ws}}

    def _save_emergency(self, epoch: int, train_loader=None) -> None:
        cursor = self._data_cursor(epoch, train_loader)
        if self.cfg.checkpoint_backend == "orbax":
            from tpudist.checkpoint_orbax import get_backend
            state_dict = ckpt_lib.state_to_dict(self.state, self.cfg.arch,
                                                epoch - 1, self.best_acc1,
                                                topology=self._topology(),
                                                data_cursor=cursor,
                                                doctor=self._doctor_payload())
            get_backend().save(state_dict, False, self.cfg.outpath)
            get_backend().wait()
        elif self.primary:
            state_dict = ckpt_lib.state_to_dict(self.state, self.cfg.arch,
                                                epoch - 1, self.best_acc1,
                                                topology=self._topology(),
                                                data_cursor=cursor,
                                                doctor=self._doctor_payload())
            ckpt_lib.save_checkpoint(state_dict, False, self.cfg.outpath,
                                     keep=0)

    def _find_auto_resume(self) -> str | None:
        """The resumable checkpoint in the outpath. A single run writes
        exactly one backend's artifact (save() routes by
        cfg.checkpoint_backend), so when BOTH exist they are leftovers of
        DIFFERENT runs that shared the outpath. The CONFIGURED backend's
        artifact wins — the same routing _resume_is_orbax applies and the
        format this run will keep writing — but picking by configuration
        can select the OLDER training state (e.g. an epoch-10 msgpack file
        beside an epoch-50 orbax dir after a backend switch), so the choice
        is logged loudly whenever the loser is newer."""
        from tpudist.checkpoint import CKPT_NAME, _history_checkpoints
        from tpudist.checkpoint_orbax import CKPT_DIR
        msgpack_p = os.path.join(self.cfg.outpath, CKPT_NAME)
        orbax_p = os.path.join(self.cfg.outpath, CKPT_DIR)
        # The live msgpack file may have been quarantined (.corrupt) by a
        # previous attempt — history copies still make the outpath resumable
        # (load() walks them newest-valid-first).
        hist = _history_checkpoints(self.cfg.outpath)
        cands = [p for p in (msgpack_p, orbax_p) if os.path.exists(p)]
        if msgpack_p not in cands and hist:
            cands.insert(0, msgpack_p)
        if len(cands) == 2:
            chosen = orbax_p if self.cfg.checkpoint_backend == "orbax" \
                else msgpack_p
            other = msgpack_p if chosen is orbax_p else orbax_p
            if os.path.exists(other) and os.path.exists(chosen) \
                    and os.path.getmtime(other) > os.path.getmtime(chosen):
                self.log(
                    f"=> --resume auto: outpath holds BOTH backends' "
                    f"checkpoints; resuming the configured "
                    f"'{self.cfg.checkpoint_backend}' artifact ({chosen}) "
                    f"even though {other} is newer — pass --resume "
                    f"{other} explicitly to override")
            return chosen
        return cands[0] if cands else None

    def _resume_is_orbax(self, path: str) -> bool:
        """Route by checkpoint CONTENT; when an output dir holds both backends'
        files (user switched backends), the configured backend wins."""
        from tpudist.checkpoint_orbax import is_orbax_checkpoint
        if not is_orbax_checkpoint(path):
            return False
        has_msgpack = (os.path.isdir(path) and
                       os.path.exists(os.path.join(path, "checkpoint.msgpack")))
        return not has_msgpack or self.cfg.checkpoint_backend == "orbax"

    def _check_expert_topology(self, ckpt: dict) -> None:
        """EP binds num_experts to the EXPERT-AXIS size (== device count on a
        pure expert mesh; smaller under dp×ep composition): resuming a
        vit_moe checkpoint on a different expert count must fail with the
        reason, not a raw shape mismatch."""
        if not self.uses_expert_axis:
            return
        n = self.mesh.shape["expert"]
        params = (ckpt.get("state", {}) or {}).get("params", {}) or {}

        def find_expert_dim(tree):
            if isinstance(tree, dict):
                if "moe" in tree and isinstance(tree["moe"], dict) \
                        and "w1" in tree["moe"]:
                    return tree["moe"]["w1"].shape[0]
                for v in tree.values():
                    got = find_expert_dim(v)
                    if got is not None:
                        return got
            return None

        e = find_expert_dim(params)
        if e is not None and e != n:
            raise ValueError(
                f"checkpoint was trained with {e} experts but the current "
                f"mesh has an expert axis of size {n} — expert count is "
                f"bound to the expert-axis size under expert parallelism; "
                f"resume with an expert axis of {e} (or retrain)")

    def load(self, path: str) -> None:
        t0 = time.time()
        try:
            self._load(path)
        finally:
            if self.telemetry is not None:
                self.telemetry.note_restore(time.time() - t0, path=str(path),
                                            epoch=self.start_epoch)

    def _load(self, path: str) -> None:
        if self._resume_is_orbax(path):
            from tpudist.checkpoint_orbax import get_backend
            ckpt = get_backend().load(path)
            self._check_expert_topology(ckpt)
            self.state = ckpt_lib.restore_train_state(
                self.state, ckpt, target_topology=self._topology(),
                log=self.log)
            self.best_acc1 = float(ckpt.get("best_acc1", 0.0))
            self.start_epoch = int(ckpt.get("epoch", 0))
            self.log(f"=> resumed from orbax '{path}' "
                     f"(epoch {self.start_epoch}, "
                     f"best_acc1 {self.best_acc1:.3f})")
            self._after_restore(ckpt)
        elif path.endswith((".pth", ".pth.tar", ".pt")):
            # A reference-format torch checkpoint (utils.py:114-118 schema):
            # migrate params/BN stats in place of a native resume.
            from tpudist.compat import restore_from_torch
            self.state, self.start_epoch, self.best_acc1 = restore_from_torch(
                self.state, path, self.cfg.arch)
            self.log(f"=> imported torch checkpoint '{path}' "
                     f"(epoch {self.start_epoch}, best_acc1 {self.best_acc1:.3f})")
        else:
            live = os.path.join(self.cfg.outpath, ckpt_lib.CKPT_NAME)
            if os.path.abspath(path) in (os.path.abspath(live),
                                         os.path.abspath(self.cfg.outpath)):
                # Resuming OUR outpath (the --resume auto / elastic-restart
                # path): integrity-verify, quarantine a torn/corrupt live
                # file, and fall back to the newest valid history copy
                # instead of crashing the relaunched job.
                ckpt, path = ckpt_lib.load_checkpoint_with_fallback(
                    self.cfg.outpath, log=self.log,
                    keep=self.cfg.keep_checkpoints)
            else:
                # An EXPLICIT external checkpoint: the user named these
                # bytes; silently substituting different weights would be
                # worse than failing.
                ckpt = ckpt_lib.load_checkpoint(path)
            self._check_expert_topology(ckpt)
            self.state = ckpt_lib.restore_train_state(
                self.state, ckpt, target_topology=self._topology(),
                log=self.log)
            self.best_acc1 = float(ckpt.get("best_acc1", 0.0))
            self.start_epoch = int(ckpt.get("epoch", 0))
            self.log(f"=> resumed from '{path}' (epoch {self.start_epoch}, "
                     f"best_acc1 {self.best_acc1:.3f})")
            self._after_restore(ckpt)
        # Checkpoints hold topology-independent host/replicated arrays (the
        # analogue of the reference's unwrapped model.module.state_dict()):
        # re-shard onto the mesh when the GSPMD path is active — under
        # elastic restore this re-cut IS the zero1 reshard the plan above
        # described (partitions re-cut over the new mesh's data axis).
        self.state = self._shard_state(self.state)

    def _after_restore(self, ckpt: dict) -> None:
        """Elastic bookkeeping after a native-format restore: pick up the
        mid-epoch data cursor (emergency saves) and, when the checkpoint's
        topology differs from ours, emit the ``reshard`` telemetry event
        with the plan's numbers."""
        cur = ckpt.get("data_cursor")
        if cur and int(cur.get("consumed", 0)) > 0:
            self._pending_cursor = dict(cur)
            self.log(f"=> checkpoint carries a mid-epoch sample cursor: "
                     f"epoch {cur.get('epoch')} continues at global sample "
                     f"{cur.get('consumed')} (no replay, no drop)")
        doc = ckpt.get("doctor")
        if doc and self.doctor is not None:
            # Doctor replay state stamped by a post-rollback emergency save
            # (_doctor_payload): re-arm the poison windows BEFORE the cursor
            # applies (the cursor counts positions of the excised order) and
            # carry the rollback count so the budget survives the restart.
            try:
                self._poison_windows = {
                    int(ep): [(int(a), int(b)) for a, b in ws]
                    for ep, ws in dict(doc.get("poison_windows") or
                                       {}).items()}
                self.doctor.rollbacks = int(doc.get("rollbacks", 0))
            except (TypeError, ValueError):
                self.log("=> doctor: malformed replay state in checkpoint "
                         "— ignoring (windows lost, budget reset)")
            else:
                if self._poison_windows:
                    self.log(f"=> doctor: checkpoint carries poison "
                             f"windows {self._poison_windows} (rollbacks "
                             f"so far: {self.doctor.rollbacks}) — replay "
                             f"continues with them excised")
        saved_topo = ckpt.get("topology")
        if saved_topo and self.telemetry is not None:
            from tpudist.elastic.reshard import plan_reshard
            plan = plan_reshard(saved_topo, self._topology(),
                                state_dict=ckpt.get("state"))
            if plan.changed:
                self.telemetry.emit(
                    "reshard", from_world=plan.world_from,
                    to_world=plan.world_to,
                    zero1_recut=len(plan.recut),
                    zero1_fallback=len(plan.fallback),
                    tp_from=plan.tp_from, tp_to=plan.tp_to,
                    detail=plan.describe())

    # -- epoch loops (reference train()/validate()) ------------------------
    def train_epoch(self, loader, epoch: int, lr: float) -> tuple[float, float]:
        cfg = self.cfg
        # Async metric drain (--async-drain, default on): metrics copy
        # device→host asynchronously at dispatch and materialize one step
        # late, while the NEXT step computes — the drain leaves the
        # critical path (the epoch summary still flushes everything, so
        # averages are exact; the console line trails by one step).
        async_drain = bool(getattr(cfg, "async_drain", True))
        doctor = self.doctor
        tel = self.telemetry
        # Sample-cursor accounting: start from the continuation offset when
        # this epoch resumes mid-way (set in fit() from the checkpoint's
        # data_cursor), else 0. Each dispatched step consumes
        # local_batch x data_world positions of the epoch's global order.
        self._epoch_consumed = self._epoch_cursor0
        self._epoch_cursor0 = 0
        # Double-buffered device prefetch (--device_prefetch, default on):
        # the iterator hands out batches ALREADY placed on the mesh, and
        # poke() below issues the next batch's H2D while the dispatched
        # step computes — the serial data/h2d phases shrink to their
        # exposed remainder and the hidden work is reported as the step's
        # prefetch_s bucket (overlap-aware accounting; see telemetry.step).
        # Host spans (scopes.SPAN_*): every host microsecond of a loop
        # turn lies inside exactly one top-level tpudist.* annotation or
        # the step annotation, so a device gap a trace cannot attribute is
        # outside this loop. The loop's own activities (scopes.
        # LOOP_ACTIVITIES: prologue, hooks, meters, log, epoch_end; none
        # waits on the device) are spans BESIDE loop_host, not inside it:
        # a trace reduction hands an idle gap to the span that overlaps it
        # most, and a parent always outlasts its child, so a stall inside a
        # nested span would read under the catch-all. loop_host is what is
        # left: taking a staged batch, the poke, the metrics' push.
        # An annotation costs a flag test when no trace is live.
        span = jax.profiler.TraceAnnotation
        pf = None
        with span(scopes.SPAN_LOOP_PROLOGUE):
            batch_time = AverageMeter("Time", ":6.3f")
            data_time = AverageMeter("Data", ":6.3f")
            losses = AverageMeter("Loss", ":.4e")
            top1 = AverageMeter("Acc@1", ":6.2f")
            progress = ProgressMeter(
                len(loader), [batch_time, data_time, losses, top1],
                prefix=f"Epoch[{epoch}]:\t")
            drain = _MetricDrain({"loss": losses, "acc1": top1},
                                 lag=1 if async_drain else 0,
                                 observer=(doctor.on_metrics
                                           if doctor is not None else None))
            lr_arr = jax.numpy.asarray(lr, jax.numpy.float32)
            if getattr(cfg, "device_prefetch", True):
                from tpudist.dist import DevicePrefetcher
                pf = DevicePrefetcher(loader, self.mesh, self.batch_axes)
            batches = iter(pf if pf is not None else loader)
        end = time.time()
        t_prev = end                  # telemetry step boundary (own clock so
        i = -1                        # meters exact)
        while True:
            with span(scopes.SPAN_LOOP_HOST):
                try:
                    if pf is not None:    # its spans: dist.DevicePrefetcher
                        images, labels = next(batches)
                    else:
                        with span(scopes.SPAN_LOADER_NEXT):
                            images, labels = next(batches)
                except StopIteration:
                    break
                i += 1
                local_bs = (pf.last_local_bs if pf is not None
                            else int(images.shape[0]))
                now = time.time()
                data_time.update(now - end)
                data_s = now - t_prev     # loader wait incl. prior-step residue
                step_num = self.global_step
            with span(scopes.SPAN_LOOP_HOOKS):
                self.profiler.step(self.global_step)
                if self.blackbox is not None:
                    # Consumes an armed deep capture / manual flag; idle
                    # cost is two attribute reads (no lock, no clock —
                    # NUM01).
                    self.blackbox.poll(self.global_step)
                # Kick BEFORE dispatch too: the first step blocks on XLA
                # compilation, so the full timeout budget must start here.
                self._kick()
                # Step boundary: the in-flight step has drained — act on
                # a pending SIGTERM/SIGINT now (fit() writes the emergency
                # checkpoint), and consult the hot-loop fault points.
                if self.preemption is not None:
                    self.preemption.check()
                if doctor is not None:
                    # Deliver a pending rollback decision (raises
                    # RollbackRequested — fit() restores
                    # last-verified-good and replays the epoch minus the
                    # poisoned window), then run the periodic SDC probe.
                    # Both happen HERE, at the step boundary where the
                    # in-flight step has drained: the probe digests a
                    # settled state, and a rollback never tears a
                    # dispatched step.
                    doctor.check_response()
                    if doctor.should_probe(self.global_step):
                        self._kick()
                        if doctor.probe(self.global_step,
                                        self.state) == "evict":
                            self.log_all(
                                f"=> doctor: this rank's replicated "
                                f"state is minority-divergent in "
                                f"{doctor.sdc_windows} consecutive probes "
                                f"— silent data corruption on this host; "
                                f"self-quarantining (exit "
                                f"{faults.SDC_EXIT_CODE}, no checkpoint "
                                f"written)")
                            raise SystemExit(faults.SDC_EXIT_CODE)
                faults.maybe_rank_exit(self.global_step)
                faults.maybe_slow_peer(self.global_step)
                faults.maybe_straggle(self.global_step)
                if faults.armed("bitflip"):
                    # SDC injection: corrupt this rank's live params in
                    # place — nothing non-finite, only the cross-replica
                    # digest probe can see it.
                    self.state = faults.maybe_bitflip(self.global_step,
                                                      self.state)
                if faults.armed("lossbomb"):
                    # Health injection: poison the head so the loss
                    # spikes (finite) — the EWMA detector, not the
                    # sentinel, must act.
                    self.state = faults.maybe_lossbomb(self.global_step,
                                                       self.state)
            # StepTraceAnnotation groups this step's device ops under one
            # labeled row in XProf/Perfetto when --profile is capturing.
            with jax.profiler.StepTraceAnnotation(scopes.STEP,
                                                  step_num=step_num):
                t_h = time.time()
                if pf is None:
                    images, labels = shard_host_batch(
                        self.mesh, (images, labels), self.batch_axes)
                if faults.armed("nanbomb"):
                    # Poisoned-batch injection, applied to the PLACED
                    # batch so sharding/dtype survive (the guarded step's
                    # sentinel, not this code, must catch the damage).
                    images = faults.maybe_nanbomb(step_num, images)
                t_c = time.time()
                with span(scopes.SPAN_DISPATCH):
                    self.state, metrics = self.train_step(
                        self.state, images, labels, lr_arr)
                t_done = time.time()
            with span(scopes.SPAN_LOOP_HOST):
                h2d_s, compute_s = t_c - t_h, t_done - t_c
                prefetch_s = None
                if pf is not None:
                    # Stage batch N+1 while step N is in flight on the device:
                    # the whole point of the prefetcher. This host time is
                    # OVERLAPPED work — it rides the step event's prefetch_s
                    # field, not the serial data/h2d buckets.
                    prefetch_s = pf.poke()
                first_dispatch = not self._train_dispatched
                self._train_dispatched = True
                if doctor is not None:
                    # Which global sample positions this step consumed — the
                    # mapping a rollback needs to excise the poisoned window
                    # from the replayed order. Host ints, bounded dict.
                    consumed = local_bs * self.data_world
                    doctor.note_step(step_num, epoch, self._epoch_consumed,
                                     self._epoch_consumed + consumed)
                with span(scopes.SPAN_DRAIN_READY):
                    drain.push(metrics, n=images.shape[0], step=step_num)
                    drain_ovl_s = None
                    if async_drain:
                        # Materialize PRIOR steps' metrics while this step's
                        # compute is in flight (their async copies landed
                        # behind the later dispatches) — overlapped work,
                        # booked in the step event's drain_ovl_s bucket like
                        # prefetch_s.
                        t_do = time.time()
                        drain.drain_ready()
                        drain_ovl_s = time.time() - t_do
            with span(scopes.SPAN_LOOP_METERS):
                self.global_step += 1
                self._epoch_consumed += local_bs * self.data_world
                self._kick()
                batch_time.update(time.time() - end)
                end = time.time()
            drain_s = 0.0
            if i % cfg.print_freq == 0:
                with span(scopes.SPAN_METRIC_DRAIN):
                    t_d = time.time()
                    # Async mode keeps the one-step lag even at display
                    # time — a full drain here would block on the step
                    # just dispatched, re-exposing exactly the sync this
                    # flag removes. The console line trails by one step.
                    drain.drain_ready() if async_drain else drain.drain()
                    drain_s = time.time() - t_d
                with span(scopes.SPAN_LOOP_LOG):
                    self.log(progress.display(i))
            if tel is not None:
                with span(scopes.SPAN_LOOP_METERS):
                    step_s = time.time() - t_prev
                    mfu = None
                    if not first_dispatch and self._flops_per_step \
                            and self._peak_flops:
                        mfu = self._flops_per_step / (
                            step_s * self._peak_flops)
                    # First dispatch blocked on trace+XLA compile:
                    # accounted as compile, not productive step time.
                    tel.step(step=step_num, epoch=epoch, data_s=data_s,
                             h2d_s=h2d_s, compute_s=compute_s,
                             drain_s=drain_s, step_s=step_s,
                             compile_s=(compute_s if first_dispatch
                                        else 0.0),
                             mfu=mfu, prefetch_s=prefetch_s,
                             drain_ovl_s=drain_ovl_s)
                    if first_dispatch:
                        # AFTER the step event so its one-off cost lands
                        # in the compile bucket, not in this step's step_s
                        # (the program is already warm in the executable
                        # cache when one is configured).
                        self._resolve_step_flops(images, labels, lr_arr)
                        # Reset the METER clock too: without this the
                        # next step's data_time/batch_time console meters
                        # would absorb the cost-analysis compile as
                        # phantom data wait.
                        end = time.time()
            t_prev = time.time()
        with span(scopes.SPAN_METRIC_DRAIN):
            drain.drain()
        with span(scopes.SPAN_LOOP_EPOCH_END):
            if doctor is not None:
                # A spike surfacing in the epoch-end flush must act BEFORE
                # this epoch's validate/save — otherwise the poisoned weights
                # get checkpointed first and only un-written one epoch later.
                doctor.check_response()
            self.profiler.epoch_end()
            rate = ""
            if batch_time.sum > 0:
                # rows through optimizer steps over the loop's wall time;
                # a row is an image, or --seq-len ids
                rows_s = self.rows_per_s = (
                    batch_time.count * cfg.batch_size / batch_time.sum)
                rate = (f"\t{rows_s * cfg.seq_len:.1f} tokens/s"
                        if self.trains_tokens else f"\t{rows_s:.1f} img/s")
            self.log(f"||==> Train: Epoch[{epoch}]\tLoss {losses.avg:.4e}\t"
                     f"Acc@1 {top1.avg:6.2f}{rate}")
            skipped = getattr(loader, "samples_skipped", 0)
            retried = getattr(loader, "samples_retried", 0)
            if skipped or retried:
                # Data-path degradation meter: skips consumed corruption
                # budget; retries healed transiently (see data/loader.py).
                self.log(f"||==> Data: Epoch[{epoch}]\tsamples_skipped "
                         f"{skipped}\tsamples_retried {retried}")
                self.scalar("Data_samples_skipped", skipped, epoch)
                self.scalar("Data_samples_retried", retried, epoch)
            self.scalar("lr", lr, epoch)
            self.scalar("Train_ce_loss", losses.avg, epoch)
            self.scalar("Train_top1_accuracy", top1.avg, epoch)
        return losses.avg, top1.avg

    def validate(self, loader, epoch: int) -> float:
        cfg = self.cfg
        batch_time = AverageMeter("Time", ":6.3f")
        losses = AverageMeter("Loss", ":.4e")
        top1 = AverageMeter("Acc@1", ":6.2f")
        progress = ProgressMeter(len(loader), [batch_time, losses, top1],
                                 prefix="Val:\t")
        drain = _MetricDrain({"loss": losses, "acc1": top1})

        # --model-ema-decay: validate (and thereby select 'best') with the
        # EMA copy (params AND BN stats, like torchvision's use_buffers=True
        # EMA) — the weights a user of the EMA recipe would deploy.
        eval_state = self.state
        ema = getattr(self.state, "ema_params", None)
        if ema is not None:
            eval_state = self.state.replace(
                params=ema["params"], batch_stats=ema["batch_stats"])

        end = time.time()
        for i, (images, labels) in enumerate(loader):
            self._kick()   # validation steps are progress too (watchdog)
            if self.preemption is not None:
                self.preemption.check()
            images, labels = shard_host_batch(
                self.mesh, (images, labels), self.batch_axes)
            metrics = self.eval_step(eval_state, images, labels)
            drain.push(metrics, n=images.shape[0])
            batch_time.update(time.time() - end)
            end = time.time()
            if i % cfg.print_freq == 0:
                drain.drain()
                self.log(progress.display(i))
        drain.drain()
        self.log(f"||==> Val: Epoch[{epoch}]\tLoss {losses.avg:.4e}\t"
                 f"Acc@1 {top1.avg:6.2f}")
        self.scalar("Val_ce_loss", losses.avg, epoch)
        self.scalar("Val_top1_accuracy", top1.avg, epoch)
        return top1.avg

    # -- doctor rollback (tpudist/doctor/, docs/DOCTOR.md) -----------------
    def _fresh_initial_state(self):
        """The run's exact t=0 train state — the rollback-to-init fallback
        when a spike lands before any checkpoint exists. Must reproduce
        everything __init__ did to build the state: the same init model
        (the SP/EP/PP paths init with the unsharded twin), the same seed,
        the pretrained weights when --pretrained, and the int8 error-
        feedback residual when compression dispatched (a bare
        create_train_state would hand the compressed step a None
        comm_state and kill the run at the next dispatch)."""
        cfg = self.cfg
        seed = cfg.seed if cfg.seed is not None else 0
        state = create_train_state(jax.random.PRNGKey(seed),
                                   self._init_model, cfg)
        if cfg.pretrained:
            from tpudist.compat import load_pretrained, resolve_pretrained_path
            p = resolve_pretrained_path(cfg.arch, cfg.pretrained_path)
            state = load_pretrained(state, cfg.arch, p)
        if self.compress:
            from tpudist.parallel.comm import init_comm_state
            state = state.replace(comm_state=init_comm_state(
                state.params, self.mesh.shape[self.data_axis]))
        return state

    def _doctor_rollback(self, rb: RollbackRequested) -> int:
        """Respond to a loss spike / persistent non-finite verdict: restore
        the newest *probe-verified-good* checkpoint (falling back to the
        newest merely-intact one only when no verdict exists), record the
        poisoned global-sample window so the replayed epoch excises it,
        and return the epoch to re-enter. ``global_step`` keeps counting
        DISPATCHES monotonically (the optimizer step lives in
        ``state.step`` and rolls back with the weights) — so profiler
        windows, probe cadence and step-gated fault injections never
        re-fire on the replay."""
        cfg = self.cfg
        doctor = self.doctor
        if doctor.rollbacks >= cfg.doctor_max_rollbacks:
            raise RuntimeError(
                f"doctor: {rb.reason} at step {rb.step}, but the rollback "
                f"budget (--doctor-max-rollbacks {cfg.doctor_max_rollbacks}"
                f") is exhausted — the run is deterministically unhealthy "
                f"(diverging recipe, bad lr, or poisoned corpus); refusing "
                f"to replay it forever")
        windows = doctor.windows_for(rb)
        self.log_all(f"=> doctor: {rb.reason} at step {rb.step} — rolling "
                     f"back to the newest verified-good checkpoint")
        t0 = time.time()
        to_epoch = 0
        path = "<fresh init>"
        try:
            ckpt, path = ckpt_lib.load_checkpoint_with_fallback(
                cfg.outpath, log=self.log, keep=cfg.keep_checkpoints,
                require_verified=True)
        except FileNotFoundError:
            # Poisoned before the first save ever landed: roll back to the
            # seeded init — epoch 0 restarts with the window excised.
            self.log_all("=> doctor: no checkpoint exists yet — rolling "
                         "back to the seeded initial state")
            self.state = self._shard_state(self._fresh_initial_state())
        else:
            self.state = ckpt_lib.restore_train_state(
                self.state, ckpt, target_topology=self._topology(),
                log=self.log)
            self.state = self._shard_state(self.state)
            to_epoch = int(ckpt.get("epoch", 0))
            self.best_acc1 = float(ckpt.get("best_acc1", self.best_acc1))
        if self.telemetry is not None:
            self.telemetry.note_restore(time.time() - t0, path=str(path),
                                        epoch=to_epoch, rollback=1)
        for wepoch, a, b in windows:
            self._poison_windows.setdefault(wepoch, []).append((a, b))
        doctor.on_rollback(rb, to_epoch, windows)
        self._pending_cursor = None
        self.log_all(
            f"=> doctor: rolled back to '{path}' (re-entering epoch "
            f"{to_epoch})"
            + ("; " + "; ".join(
                f"epoch {we} will replay minus global samples [{a}, {b})"
                for we, a, b in windows) if windows
               else "; no poisoned window recorded (step out of the "
                    "position ring)"))
        return to_epoch

    # -- fit (reference epoch loop, distributed.py:185-221) ----------------
    def fit(self, train_loader=None, val_loader=None) -> float:
        cfg = self.cfg
        if train_loader is None or val_loader is None:
            t_build = time.monotonic()
            train_loader, val_loader = build_train_val_loaders(
                cfg, vocab_size=getattr(self.model, "vocab_held", None))
            telemetry_lib.record_phase(scopes.INIT_LOADERS,
                                       time.monotonic() - t_build)

        if cfg.evaluate:   # evaluate-only path (distributed.py:181-183)
            try:
                return self.validate(val_loader, epoch=-1)
            finally:
                if self.telemetry is not None:
                    self.telemetry.close()
                    telemetry_lib.set_current(None)
                    faults.set_observer(None)
                if self.metrics_server is not None:
                    self.metrics_server.close()
                    self.metrics_server = None

        if cfg.stall_timeout > 0:
            # Timeout budgets one unit of progress (a train/eval step incl.
            # its compile, a checkpoint save, a replica check) — size it above
            # the slowest of those, not above a whole epoch.
            self.watchdog = Watchdog(cfg.stall_timeout).start()
        self.preemption = _PreemptionGuard().install()

        total_time = 0.0
        epoch = self.start_epoch
        try:
            while epoch < cfg.epochs:
                t0 = time.time()
                train_loader.set_epoch(epoch)   # sampler.set_epoch (distributed.py:188)
                if self._poison_windows.get(epoch):
                    # Doctor rollback replay: re-deliver this epoch's exact
                    # batch sequence minus the quarantined sample windows
                    # (applied AFTER set_epoch, which clears them — same
                    # flow as the elastic cursor below).
                    train_loader.set_skip_windows(self._poison_windows[epoch])
                    self.log(f"=> doctor: epoch {epoch} replays with "
                             f"poisoned window(s) "
                             f"{self._poison_windows[epoch]} excised "
                             f"({len(train_loader)} steps remain)")
                cur = self._pending_cursor
                if cur is not None and int(cur.get("epoch", -1)) == epoch \
                        and hasattr(train_loader, "set_cursor"):
                    # Elastic continuation (set AFTER set_epoch, which
                    # clears the sampler cursor): the interrupted epoch's
                    # remaining global order redistributes over the CURRENT
                    # world — no sample dropped, none double-seen — and the
                    # degradation meters carry the pre-reform counts.
                    consumed = int(cur.get("consumed", 0))
                    train_loader.set_cursor(
                        consumed,
                        samples_skipped=int(cur.get("samples_skipped", 0)),
                        samples_retried=int(cur.get("samples_retried", 0)))
                    self._epoch_cursor0 = consumed
                    self.log(f"=> elastic continuation: epoch {epoch} "
                             f"resumes at global sample {consumed} "
                             f"({len(train_loader)} steps remain on world "
                             f"{self.data_world})")
                self._pending_cursor = None
                lr = lr_for_epoch(cfg, epoch)   # step-at-epoch-start (distributed.py:192)
                self.log(f"self.optimizer={{'lr': {lr}}}")
                try:
                    self.train_epoch(train_loader, epoch, lr)
                except RollbackRequested as rb:
                    epoch = self._doctor_rollback(rb)
                    continue
                t_v = time.time()
                acc1 = self.validate(val_loader, epoch)
                if self.telemetry is not None:
                    self.telemetry.note_eval(time.time() - t_v, epoch=epoch,
                                             acc1=float(acc1))

                if (cfg.replica_check_freq and
                        (epoch + 1) % cfg.replica_check_freq == 0):
                    self._kick()
                    n = assert_replicas_consistent(
                        {"params": self.state.params,
                         "batch_stats": self.state.batch_stats})
                    if n:
                        self.log(f"replica consistency check passed "
                                 f"({n} leaves, epoch {epoch})")
                    else:
                        self.log("replica consistency check skipped: no "
                                 "replicated leaves (single device or fully "
                                 "sharded state)")

                is_best = acc1 > self.best_acc1
                if is_best:
                    self.best_acc1 = float(acc1)
                    self.log(f"best_acc1={self.best_acc1:.3f}, epoch={epoch}")
                self._kick()
                self.save(epoch, is_best)
                self._kick()

                epoch_time = time.time() - t0
                total_time += epoch_time
                hbm = peak_hbm_gb()
                self.log(f"||==> Epoch[{epoch}] time cost {epoch_time:.2f}s, "
                         f"total {total_time:.2f}s"
                         + (f", peak_hbm {hbm:.3f}GB" if hbm else ""))
                if hbm:
                    self.scalar("Peak_HBM_GB", hbm, epoch)
                if self.telemetry is not None:
                    extra = {"peak_hbm_gb": hbm} if hbm else {}
                    # Data-path degradation rides the epoch event so the
                    # live endpoint's samples_skipped counter moves without
                    # a new emit site in the loader.
                    skipped = getattr(train_loader, "samples_skipped", 0)
                    retried = getattr(train_loader, "samples_retried", 0)
                    if skipped or retried:
                        extra.update(samples_skipped=skipped,
                                     samples_retried=retried)
                    rows_s = self.rows_per_s
                    if rows_s:
                        extra["img_per_s"] = round(rows_s, 3)
                        if self.trains_tokens:
                            extra["tokens_per_s"] = round(
                                rows_s * cfg.seq_len, 1)
                    self.telemetry.emit("epoch", epoch=epoch,
                                        seconds=round(epoch_time, 3),
                                        **extra)
                epoch += 1
        except PreemptionRequested as sig:
            # The in-flight step drained before check() raised: snapshot and
            # exit RESUMABLE. Re-running the interrupted epoch from its
            # start keeps epoch semantics exact (sampler order, LR schedule).
            self.log(f"=> caught {sig} — draining for preemption")
            if self.telemetry is not None:
                self.telemetry.emit("preempt", signal=str(sig), epoch=epoch)
            if self.writer is not None:
                # Flush BEFORE the emergency checkpoint: the preemption grace
                # window can expire (SIGKILL) mid-save, and buffered TB
                # scalars for the completed epochs must not die with us —
                # the finally-close below never runs under SIGKILL.
                try:
                    self.writer.flush()
                except Exception:
                    pass
            self.save_emergency(epoch, train_loader)
            self.log(f"=> emergency checkpoint complete; exiting "
                     f"{faults.PREEMPTED_EXIT_CODE} (resumable)")
            raise SystemExit(faults.PREEMPTED_EXIT_CODE)
        finally:
            if self.preemption is not None:
                self.preemption.uninstall()
                self.preemption = None
            self.profiler.close()
            if self.blackbox is not None:
                # Stop a still-open deep-capture trace before telemetry
                # closes (the recorder may emit one last incident event).
                self.blackbox.close()
            if self.watchdog is not None:
                self.watchdog.stop()
            if self.telemetry is not None:
                # run_end carries the goodput summary; drop the process-wide
                # handle so watchdog/faults stop emitting into a closed file.
                self.telemetry.close(best_acc1=float(self.best_acc1))
                telemetry_lib.set_current(None)
                faults.set_observer(None)
            if self.metrics_server is not None:
                # After run_end reached the registry, so a final scrape can
                # still see the closing goodput; then the port is released.
                self.metrics_server.close()
                self.metrics_server = None
            if self.writer is not None:
                self.writer.close()
            if self.cfg.checkpoint_backend == "orbax":
                # Drain the async writer: the final epoch's checkpoint must be
                # finalized on disk before fit() returns (callers/launchers
                # may read it or kill the process immediately after).
                from tpudist.checkpoint_orbax import get_backend
                get_backend().wait()
        return self.best_acc1


def run(cfg: Config) -> float:
    """The reference's ``main()`` (``distributed.py:85-105``): seed handling is
    functional (PRNGKey from cfg.seed) so there is no np.random crash to
    reproduce (bug ledger #1); determinism on TPU comes from XLA, not cudnn
    toggles."""
    from tpudist.dist import initialize_runtime
    if cfg.distributed:
        initialize_runtime(cfg.coordinator_address, cfg.num_processes,
                           cfg.process_id)
    trainer = Trainer(cfg)
    return trainer.fit()
