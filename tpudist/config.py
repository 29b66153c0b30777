"""Run configuration: the reference's argparse flag surface as a typed dataclass.

Reproduces the flag set shared by all three reference recipes
(``/root/reference/distributed.py:43-73``, ``dataparallel.py:40-67``,
``distributed_syncBN_amp.py:42-75``) with the reference's defaults, while fixing
its ledger'd quirks (SURVEY.md §7):

- ``type=bool`` argparse traps (``--evaluate``/``--pretrained``/``--use_amp``/
  ``--sync_batchnorm`` treated any non-empty string as True,
  ``distributed.py:63-64``) become real boolean flags;
- the dead ``--gpus`` flag (``distributed.py:114``) is dropped;
- ``--start-epoch`` actually resumes (see trainer.py) instead of only
  offsetting the epoch range (``distributed.py:54``).

``write_settings`` keeps the reference's ``settings.log`` dump format
(``utils.py:54-62``).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Sequence


@dataclass
class Config:
    """Everything needed to run one experiment.

    Field names follow the reference's ``args`` attribute names so logs and
    ``settings.log`` stay recognizably compatible.
    """

    # data (reference --data, -j/--workers)
    data: str = ""                      # path to ImageFolder root ('' => synthetic)
    workers: int = 8                    # data-loading worker threads
    data_retries: int = 2               # retries per failing sample read/decode
    data_retry_backoff: float = 0.05    # linear backoff between retries (sec)
    data_skip_budget: int = 0           # skipped samples tolerated per epoch
                                        # before the loader fails loudly
                                        # (0 = strict: first persistent
                                        # failure raises after retries)
    image_size: int = 224               # train crop (distributed.py:162)
    val_resize: int = 256               # val resize edge (distributed.py:172)
    synthetic: bool = False             # force synthetic data even if data set
    synthetic_size: int = 0             # synthetic train-set size (0 = auto)

    # model (reference -a/--arch, --pretrained)
    arch: str = "resnet18"
    pretrained: bool = False
    pretrained_path: str = ""           # torchvision .pth file/dir ('' = torch-hub cache)
    num_classes: int = 1000
    # a model of tokens: the rows' length, and what
    # this holder keeps of a deployment's model (models/decoder.py)
    seq_len: int = 0                    # ids a row (required for such a model)
    layers: int = 0                     # leading layers kept (0 = all)
    expert_share: str = "0/1"           # "i/n": the i-th of n holders of
                                        # each layer's experts
    vocab_share: str = "0/1"            # "i/n" of the vocabulary's rows

    # schedule (reference --epochs, --step, --start-epoch, --lr, --momentum,
    # --wd, --gamma, --lr-scheduler)
    epochs: int = 5
    step: Sequence[int] = field(default_factory=lambda: [3, 4])
    start_epoch: int = 0
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    gamma: float = 0.1
    lr_scheduler: str = "steplr"
    optimizer: str = "sgd"              # sgd (reference) | adamw (for the
                                        # transformer-era zoo: vit/swin/convnext)
    adam_b2: float = 0.999              # AdamW's second-moment decay
    warmup_epochs: int = 0              # linear lr warmup epochs (0 = off)
    label_smoothing: float = 0.0        # CE label smoothing (train loss only)
    model_ema_decay: float = 0.0        # EMA of params for eval (0 = off)
    mixup_alpha: float = 0.0            # in-step mixup Beta(a,a) (0 = off)
    cutmix_alpha: float = 0.0           # in-step cutmix Beta(a,a) (0 = off)
    auto_augment: str = ""              # '' | 'ra' | 'ta_wide' train policy
    random_erase: float = 0.0           # RandomErasing probability (train)

    # batch (reference -b: GLOBAL batch across all devices, distributed.py:143)
    batch_size: int = 1200
    accum_steps: int = 1                # microbatches per optimizer step (grad accumulation)
    microbatches: int = 0               # GPipe microbatches per step (pipeline parallel; 0 = stage count)

    # precision / BN (reference --use_amp, --sync_batchnorm)
    use_amp: bool = True                # bf16 compute policy under XLA
    sync_batchnorm: bool = False        # pmean of BN stats across data axis
    amp_dtype: str = "bfloat16"         # "bfloat16" (TPU-native) or "float16"
    remat: bool = False                 # jax.checkpoint each block: recompute
                                        # activations in backward, trading
                                        # ~33% step FLOPs for O(depth) less
                                        # HBM (resnet/vit families)
    flash: str = "auto"                 # Pallas flash attention (attention archs):
                                        # auto = measurement-honest dispatch
                                        # (ops/attention_dispatch: kernel only
                                        # where a cached on-chip measurement
                                        # says it wins); on/off force it
                                        # (off = pure-XLA attention)
    device_prefetch: bool = True        # double-buffered device prefetch:
                                        # issue batch N+1's host→device copy
                                        # while step N computes, so the
                                        # data/h2d phases overlap compute
                                        # (trainer train loop; telemetry
                                        # reports the overlapped time as its
                                        # own prefetch bucket)
    async_drain: bool = True            # defer the device→host metric drain
                                        # by one step (async copy issued at
                                        # dispatch, materialized while the
                                        # NEXT step computes) — the drain
                                        # stops blocking on the in-flight
                                        # step; booked as the overlapped
                                        # drain_ovl bucket, like prefetch
    compile_cache: str = ""             # persistent XLA compilation cache
                                        # dir (env TPUDIST_COMPILE_CACHE;
                                        # default <checkout>/.jax_cache;
                                        # ignored when
                                        # JAX_COMPILATION_CACHE_DIR is
                                        # set — serve/cache.py): a restart
                                        # re-pays cache-hit seconds, not
                                        # the full compile; provenance
                                        # (warm/cold) stamped on compile
                                        # telemetry events

    # misc (reference -p/--print-freq, -e/--evaluate, --seed, --outpath)
    print_freq: int = 10
    evaluate: bool = False
    seed: int | None = None
    outpath: str = "./output_ddp_test"
    resume: str = ""                    # checkpoint path, 'auto' (outpath's checkpoint if present), '' = none
    overwrite: str = "prompt"           # existing outpath: prompt|delete|quit|keep
    torch_checkpoints: bool = False     # also write reference-format .pth.tar
    checkpoint_backend: str = "msgpack"  # msgpack (sync) | orbax (async writes)
    keep_checkpoints: int = 2           # per-epoch history copies kept for
                                        # corrupt-checkpoint fallback
                                        # (msgpack backend; 0 = live file only)
    inject: str = ""                    # fault-injection spec (tpudist/faults.py);
                                        # also read from env TPUDIST_INJECT

    # aux subsystems (SURVEY.md §5 — absent in the reference, added here)
    telemetry: bool = False             # per-rank events.<rank>.jsonl stream
                                        # + heartbeats + goodput accounting
                                        # (tpudist/telemetry.py; report via
                                        # python -m tpudist.summarize)
    telemetry_mfu: bool = True          # with --telemetry: AOT-lower the
                                        # train step once for cost_analysis
                                        # FLOPs (per-step MFU). Costs one
                                        # extra XLA compile unless the
                                        # persistent compilation cache is on
    metrics_port: int = -1              # with --telemetry: per-rank live
                                        # Prometheus endpoint (tpudist/obs/
                                        # server.py). -1 = off; 0 = ephemeral
                                        # port, written to
                                        # <outpath>/metrics.<rank>.port
    telemetry_max_mb: float = 256.0     # size cap per events.<rank>.jsonl
                                        # before it rolls to
                                        # events.<rank>.1.jsonl (0 = uncapped)
    profile: str = ""                   # trace step window 'start:end' ('' = off)
    # tpudist.doctor — guarded train step + detect→respond policies
    # (docs/DOCTOR.md). --doctor fuses the finiteness sentinels into the
    # compiled step (skip-step on non-finite, GradScaler-style), arms the
    # host-side EWMA loss-spike detector on the drained metrics, and
    # enables rollback-to-last-verified-good + data-order replay.
    doctor: bool = False
    doctor_probe_freq: int = 0          # steps between cross-replica SDC
                                        # digest probes (0 = probes off;
                                        # requires --doctor). Probes stamp
                                        # checkpoint verdicts (good/suspect)
    doctor_spike_sigma: float = 6.0     # EWMA spike threshold (σ above the
                                        # running mean flags a poisoned step)
    doctor_spike_min_steps: int = 8     # EWMA warmup before spikes can fire
    doctor_max_skips: int = 5           # consecutive in-step skips before
                                        # escalating to a rollback
    doctor_max_rollbacks: int = 2       # rollbacks tolerated per run before
                                        # failing loudly (a deterministic
                                        # divergence must not loop forever)
    doctor_sdc_windows: int = 2         # consecutive minority-divergent
                                        # probes before a rank self-evicts
    # tpudist.blackbox — always-on flight recorder + anomaly-triggered
    # deep capture (docs/INCIDENTS.md). --blackbox registers a ring-buffer
    # Telemetry sink (last N full-resolution samples per rank); on a
    # trigger (doctor intervention, divergent SDC probe, fault, preempt,
    # SIGUSR2 / POST /capture) the rank dumps the ring and arms a one-shot
    # bounded jax.profiler trace + HLO snapshot, cooldown-bounded per
    # trigger class. The launcher bundles dumps into incidents/<id>/.
    blackbox: bool = False
    blackbox_ring: int = 256            # ring depth: events retained per rank
    blackbox_capture_steps: int = 8     # deep-capture trace length in steps
    blackbox_cooldown_s: float = 120.0  # per-trigger-class storm bound:
                                        # within it, triggers emit incident
                                        # events but dump/capture nothing
    replica_check_freq: int = 0         # check replica consistency every N epochs
    stall_timeout: float = 0.0          # abort if no step completes in N sec (0 = off)
    require_platform: str = "any"       # refuse to run unless jax landed on
                                        # this backend ("tpu"): a run meant
                                        # for the chip must not complete on
                                        # the CPU

    # mesh (TPU-native; no reference equivalent — NCCL topology was implicit)
    mesh_shape: Sequence[int] | None = None   # default: (num_devices,)
    mesh_axes: Sequence[str] = field(default_factory=lambda: ["data"])
    zero_opt: bool = False              # deprecated alias for --zero 1
    zero: str = "off"                   # weight-update sharding: off | 1
                                        # (ZeRO-1: optimizer moments shard,
                                        # GSPMD path) | full (ZeRO-full:
                                        # params + moments + EMA shard,
                                        # explicit gather/scatter step —
                                        # parallel/comm.py; arXiv:2004.13336)
    compress_grads: str = "off"         # gradient-reduction wire format:
                                        # off (dense f32 pmean) | int8
                                        # (quantized two-phase all-reduce
                                        # with error feedback — EQuARX,
                                        # arXiv:2506.17615) | auto
                                        # (measurement-honest dispatch via
                                        # ops/comm_dispatch: int8 only
                                        # where a cached on-chip A/B says
                                        # it wins)
    distributed: bool = False           # call jax.distributed.initialize()
    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None

    # filled at runtime (mirrors reference stuffing nprocs into args,
    # distributed.py:123,127-129)
    nprocs: int = 1
    per_device_batch_size: int = 0

    def finalize(self, num_devices: int) -> "Config":
        """Derive per-device batch from the global batch (distributed.py:143)."""
        self.nprocs = num_devices
        # Round down like the reference's int(batch_size / nprocs)
        # (distributed.py:143), then re-derive the global batch.
        self.per_device_batch_size = max(1, self.batch_size // num_devices)
        self.batch_size = self.per_device_batch_size * num_devices
        if self.synthetic_size < 0:
            raise ValueError(f"--synthetic-size must be >= 0, "
                             f"got {self.synthetic_size}")
        if 0 < self.synthetic_size < self.batch_size:
            # Checked against the device-ROUNDED global batch: drop_last
            # would yield a zero-step epoch that silently checkpoints an
            # untrained model.
            raise ValueError(
                f"--synthetic-size {self.synthetic_size} is smaller than the "
                f"global batch {self.batch_size}; the train loader would "
                f"produce zero batches per epoch")
        if self.telemetry_max_mb < 0:
            raise ValueError(
                f"--telemetry-max-mb must be >= 0 (0 = uncapped), got "
                f"{self.telemetry_max_mb}")
        if self.metrics_port >= 0 and not self.telemetry:
            # The endpoint is FED by the telemetry event stream; without
            # --telemetry it would bind a port that never serves a sample.
            # Fail loudly (the launcher's --metrics-port does the same) —
            # a silent connection-refused on the observability surface is
            # the one place silence is inexcusable.
            raise ValueError(
                f"--metrics-port {self.metrics_port} requires --telemetry "
                f"(the endpoint serves gauges derived from the telemetry "
                f"event stream)")
        if self.flash not in ("auto", "on", "off"):
            # argparse choices guard the CLI only; library callers construct
            # Config directly, where a typo must not silently coerce to off.
            raise ValueError(
                f"--flash must be one of auto|on|off, got '{self.flash}'")
        # -- mesh/axis-composition validation (ISSUE 12: loud errors, not
        # silent pure-DP no-ops). The parallelism plane owns the axis
        # vocabulary and the rule tables; lazily imported (jax-facing) and
        # only when the request differs from the pure-DP default, so the
        # jax-free consumers of this module never pay for it.
        if list(self.mesh_axes) != ["data"] or self.mesh_shape is not None:
            from tpudist.parallel.plane import validate_mesh_request
            validate_mesh_request(tuple(self.mesh_axes), self.mesh_shape,
                                  num_devices, arch=self.arch)
        # -- mode-interaction validation (loud, not a silent no-op) --------
        if self.zero not in ("off", "1", "full"):
            raise ValueError(
                f"--zero must be one of off|1|full, got '{self.zero}'")
        if self.zero_opt and self.zero == "off":
            # Back-compat: the pre-r8 boolean flag means ZeRO-1.
            self.zero = "1"
        if self.compress_grads not in ("off", "int8", "auto"):
            raise ValueError(
                f"--compress-grads must be one of off|int8|auto, got "
                f"'{self.compress_grads}'")
        if self.compress_grads != "off":
            if self.evaluate:
                raise ValueError(
                    "--compress-grads with --evaluate: an eval-only run "
                    "never reduces a gradient — there is nothing to "
                    "compress; drop one of the flags")
            if self.use_amp and self.amp_dtype == "float16":
                raise ValueError(
                    "--compress-grads does not compose with float16 "
                    "dynamic loss scaling (the GradScaler path reduces "
                    "inside flax's DynamicScale grad_fn — no choke point "
                    "to swap); use --amp-dtype bfloat16")
            if self.zero == "1":
                raise ValueError(
                    "--compress-grads with --zero 1: ZeRO-1 rides the "
                    "GSPMD path, where the gradient reduction is inserted "
                    "by the partitioner and cannot be swapped for the "
                    "quantized exchange. Compose compression with --zero "
                    "full (explicit-collective step) or --zero off")
            special = [a for a in self.mesh_axes
                       if a in ("model", "seq", "pipe", "expert")]
            if special:
                raise ValueError(
                    f"--compress-grads covers the data-parallel and --zero "
                    f"full paths; a mesh with {special} axes reduces "
                    f"gradients inside its own parallelism plane — "
                    f"compression there would be a silent no-op, so it is "
                    f"refused instead")
        if self.zero == "full":
            special = [a for a in self.mesh_axes
                       if a in ("model", "seq", "pipe", "expert")]
            if special:
                raise ValueError(
                    f"--zero full shards the whole weight update over the "
                    f"data axis (explicit gather/scatter step) and does "
                    f"not compose with {special} mesh axes; use --zero 1 "
                    f"(GSPMD) with 'model', or drop the axis")
            if self.use_amp and self.amp_dtype == "float16":
                raise ValueError(
                    "--zero full does not support float16 dynamic loss "
                    "scaling (like the SP/EP/PP specialty paths); use "
                    "--amp-dtype bfloat16")
        if not self.doctor:
            # Defaults come from the dataclass fields themselves so the
            # check cannot drift if a default is retuned.
            import dataclasses as _dc
            armed = {f.name: getattr(self, f.name)
                     for f in _dc.fields(self)
                     if f.name.startswith("doctor_")
                     and getattr(self, f.name) != f.default}
            if armed:
                # A doctor knob without the doctor would be silently inert
                # — the exact silent-no-op class finalize refuses.
                raise ValueError(
                    f"--doctor-* tuning requires --doctor (nothing reads "
                    f"these knobs while the doctor is off); got "
                    f"{armed} with --doctor off")
        if self.blackbox and not self.telemetry:
            # The ring is a Telemetry sink; without --telemetry nothing
            # ever feeds it and no trigger can fire (the --metrics-port
            # guard, same reasoning).
            raise ValueError(
                "--blackbox requires --telemetry (the flight recorder is "
                "a telemetry sink: without the event stream the ring "
                "stays empty and triggers never fire)")
        if not self.blackbox:
            import dataclasses as _dc
            armed = {f.name: getattr(self, f.name)
                     for f in _dc.fields(self)
                     if f.name.startswith("blackbox_")
                     and getattr(self, f.name) != f.default}
            if armed:
                # Same silent-no-op refusal as the doctor_* knobs above.
                raise ValueError(
                    f"--blackbox-* tuning requires --blackbox (nothing "
                    f"reads these knobs while the recorder is off); got "
                    f"{armed} with --blackbox off")
        else:
            if self.blackbox_ring < 8:
                raise ValueError(
                    f"--blackbox-ring must be >= 8 (a ring shorter than "
                    f"that cannot span a trigger), got {self.blackbox_ring}")
            if self.blackbox_capture_steps < 1:
                raise ValueError(
                    f"--blackbox-capture-steps must be >= 1, got "
                    f"{self.blackbox_capture_steps}")
            if self.blackbox_cooldown_s < 0:
                raise ValueError(
                    f"--blackbox-cooldown-s must be >= 0, got "
                    f"{self.blackbox_cooldown_s}")
        if self.doctor:
            if self.evaluate:
                raise ValueError(
                    "--doctor with --evaluate: an eval-only run takes no "
                    "optimizer steps — there is nothing to guard; drop "
                    "one of the flags")
            if self.doctor_probe_freq > 0:
                unplumbed = [a for a in self.mesh_axes
                             if a in ("seq", "pipe", "expert")]
                if unplumbed:
                    # The SP/EP/PP paths never derive a state placement
                    # (_placement stays pure-DP), so the probe would digest
                    # per-stage/per-expert shards as if replicated and
                    # evict healthy ranks on the false divergence.
                    raise ValueError(
                        f"--doctor-probe-freq with a "
                        f"{'/'.join(unplumbed)} mesh axis: the SDC probe "
                        f"needs the state placement truth, which the "
                        f"specialty paths don't plumb yet — run probes on "
                        f"dp/dp×tp/ZeRO layouts, or drop the probe "
                        f"cadence (sentinels and the EWMA monitor still "
                        f"arm)")
            if self.checkpoint_backend == "orbax":
                # The rollback walk and the probe's verdict stamps are
                # msgpack-surface (sidecars beside checkpoint.msgpack);
                # under orbax a rollback would find no msgpack candidates
                # and silently reset to fresh init, discarding the run.
                raise ValueError(
                    "--doctor requires --checkpoint-backend msgpack: "
                    "rollback-to-verified-good and probe verdict stamping "
                    "operate on the msgpack checkpoint surface (sidecars "
                    "beside checkpoint.msgpack); the orbax backend has no "
                    "verdict plumbing yet")
            if self.doctor_probe_freq < 0:
                raise ValueError(
                    f"--doctor-probe-freq must be >= 0 (0 = probes off), "
                    f"got {self.doctor_probe_freq}")
            if self.doctor_spike_sigma <= 0:
                raise ValueError(
                    f"--doctor-spike-sigma must be > 0, got "
                    f"{self.doctor_spike_sigma}")
            if self.doctor_max_rollbacks < 0:
                raise ValueError(
                    f"--doctor-max-rollbacks must be >= 0, got "
                    f"{self.doctor_max_rollbacks}")
        if self.val_resize < self.image_size:
            # The center crop would exceed the resized image; the native and
            # PIL val paths pad differently there, so fail fast instead.
            raise ValueError(
                f"--val-resize {self.val_resize} must be >= --image-size "
                f"{self.image_size} (the val stack resizes the shorter edge, "
                f"then center-crops image_size)")
        if isinstance(self.step, str):
            self.step = parse_milestones(self.step)
        return self

    def asdict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def parse_milestones(value: Any) -> list[int]:
    """Accept '[3,4]', '3,4', or a list — the reference's --step has no type=
    (distributed.py:52) so it arrives as a raw string when set on the CLI."""
    if isinstance(value, (list, tuple)):
        return [int(v) for v in value]
    s = str(value).strip().strip("[]()")
    return [int(tok) for tok in s.replace(",", " ").split()] if s else []


def _bool_flag(parser: argparse.ArgumentParser, name: str, default: bool, help: str) -> None:
    """A real boolean flag (fixes the reference's type=bool trap,
    distributed.py:63-64)."""
    parser.add_argument(f"--{name}", dest=name.replace("-", "_"),
                        action=argparse.BooleanOptionalAction, default=default,
                        help=help)


def _fused_bn_off(value: str) -> str:
    if value != "off":
        raise argparse.ArgumentTypeError(
            f"'{value}': the fused BN kernel left in PR 32 and `off` is the "
            f"flag's one value (PERF.md, section 7)")
    return value


def build_parser() -> argparse.ArgumentParser:
    """The reference CLI surface (distributed_syncBN_amp.py:42-75), cleaned up."""
    d = Config()
    p = argparse.ArgumentParser(description="TPU ImageNet Training (tpudist)")
    p.add_argument("--data", metavar="DIR", default=d.data, help="path to dataset (ImageFolder root); empty => synthetic data")
    p.add_argument("-a", "--arch", metavar="ARCH", default=d.arch, help="model architecture name from tpudist.models registry")
    p.add_argument("-j", "--workers", default=d.workers, type=int, metavar="N", help="number of data loading workers")
    p.add_argument("--epochs", default=d.epochs, type=int, metavar="N", help="number of total epochs to run")
    p.add_argument("--step", default=list(d.step), metavar="step decay", help="lr decay milestones, e.g. '3,4'")
    p.add_argument("--start-epoch", default=d.start_epoch, type=int, metavar="N", dest="start_epoch", help="manual epoch number (resume offsets)")
    p.add_argument("-b", "--batch-size", default=d.batch_size, type=int, metavar="N", dest="batch_size", help="GLOBAL batch size across all devices")
    p.add_argument("--accum-steps", default=d.accum_steps, type=int, dest="accum_steps", help="gradient-accumulation microbatches per optimizer step")
    p.add_argument("--microbatches", default=d.microbatches, type=int, help="GPipe microbatches per step under pipeline parallelism (0 = stage count; more microbatches shrink the (S-1)/(M+S-1) bubble)")
    p.add_argument("--lr", "--learning-rate", default=d.lr, type=float, metavar="LR", dest="lr", help="initial learning rate")
    p.add_argument("--momentum", default=d.momentum, type=float, metavar="M", help="momentum")
    p.add_argument("--wd", "--weight-decay", default=d.weight_decay, type=float, metavar="W", dest="weight_decay", help="weight decay")
    p.add_argument("-p", "--print-freq", default=d.print_freq, type=int, metavar="N", dest="print_freq", help="print frequency")
    _bool_flag(p, "evaluate", d.evaluate, "evaluate model on validation set")
    _bool_flag(p, "pretrained", d.pretrained, "use pre-trained model")
    p.add_argument("--pretrained-path", default=d.pretrained_path, dest="pretrained_path", help="local torchvision checkpoint file/dir for --pretrained (default: torch-hub cache dirs)")
    _bool_flag(p, "use_amp", d.use_amp, "bf16 mixed-precision compute policy")
    p.add_argument("--amp-dtype", default=d.amp_dtype, dest="amp_dtype",
                   choices=("bfloat16", "float16"),
                   help="--use_amp compute dtype: bfloat16 (TPU-native, no "
                        "scaler) or float16 (adds dynamic loss scaling — "
                        "torch GradScaler parity; composes with "
                        "--accum-steps on the DP/GSPMD paths)")
    _bool_flag(p, "sync_batchnorm", d.sync_batchnorm, "cross-replica batch norm statistics")
    _bool_flag(p, "remat", d.remat,
               "rematerialize block activations in backward (less HBM, "
               "~33%% more FLOPs; resnet/vit families)")
    p.add_argument("--flash", default=d.flash, choices=("auto", "on", "off"),
                   help="Pallas flash attention for vit archs: auto = "
                        "measurement-honest dispatch (on-device flash-vs-XLA "
                        "micro-benchmark at the exact attention shape, "
                        "verdict cached per device kind — the kernel is "
                        "never selected where it loses; off-TPU auto = XLA "
                        "attention); on forces the kernel (A/B work), off "
                        "forces XLA attention. See docs/ATTENTION.md")
    # the chip benchmark's configurations still write `--fused-bn off`
    # (benchmarks/chip/configs/*.json, which only a `benchmark` PR edits):
    # parsed and dropped until they stop (ROADMAP.md, "pins that are defaults")
    p.add_argument("--fused-bn", type=_fused_bn_off, default=argparse.SUPPRESS,
                   help=argparse.SUPPRESS)
    _bool_flag(p, "device_prefetch", d.device_prefetch,
               "double-buffered device prefetch: issue the next batch's "
               "host-to-device copy while the current step computes "
               "(overlap shows as the 'prefetch' bucket in summarize)")
    _bool_flag(p, "async_drain", d.async_drain,
               "defer the device-to-host metric drain by one step so it "
               "overlaps the next step's compute instead of blocking on "
               "the in-flight one (overlap shows as the 'drain (ovl.)' "
               "bucket in summarize)")
    p.add_argument("--compile-cache", default=d.compile_cache,
                   dest="compile_cache", metavar="DIR",
                   help="persistent XLA compilation cache dir (env "
                        "TPUDIST_COMPILE_CACHE; default "
                        "<checkout>/.jax_cache; ignored when "
                        "JAX_COMPILATION_CACHE_DIR is set): restarts, "
                        "elastic reforms, and serving replicas pay "
                        "cache-hit seconds instead of recompiling; "
                        "warm/cold provenance lands on compile telemetry "
                        "events. See docs/SERVING.md")
    _bool_flag(p, "synthetic", d.synthetic, "use synthetic data")
    p.add_argument("--seed", default=d.seed, type=int, help="seed for initializing training")
    p.add_argument("--outpath", metavar="DIR", default=d.outpath, help="path to output")
    p.add_argument("--lr-scheduler", metavar="LR scheduler", default=d.lr_scheduler, dest="lr_scheduler", help="LR scheduler (steplr|cosine)")
    p.add_argument("--optimizer", default=d.optimizer, choices=("sgd", "adamw"), help="optimizer (sgd = reference parity; adamw for vit/swin/convnext recipes)")
    p.add_argument("--warmup-epochs", default=d.warmup_epochs, type=int, dest="warmup_epochs", help="linear lr warmup epochs before the scheduler takes over")
    p.add_argument("--label-smoothing", default=d.label_smoothing, type=float, dest="label_smoothing", help="cross-entropy label smoothing (train only)")
    p.add_argument("--model-ema-decay", default=d.model_ema_decay, type=float, dest="model_ema_decay", help="per-step EMA decay of model params; val/best use the EMA copy (0 = off)")
    p.add_argument("--mixup-alpha", default=d.mixup_alpha, type=float, dest="mixup_alpha", help="mixup Beta(alpha,alpha) mixing inside the compiled step (0 = off)")
    p.add_argument("--cutmix-alpha", default=d.cutmix_alpha, type=float, dest="cutmix_alpha", help="cutmix Beta(alpha,alpha) box mixing inside the compiled step (0 = off; both set = choose per step)")
    p.add_argument("--auto-augment", default=d.auto_augment, choices=("", "ra", "ta_wide"), dest="auto_augment", help="train-time auto-augment policy: RandAugment or TrivialAugmentWide")
    p.add_argument("--random-erase", default=d.random_erase, type=float, dest="random_erase", help="RandomErasing probability on the train stack (0 = off)")
    p.add_argument("--synthetic-size", default=d.synthetic_size, type=int, dest="synthetic_size", help="synthetic train-set size (0 = auto; val set is half) — for smoke/bench runs")
    p.add_argument("--val-resize", default=d.val_resize, type=int, dest="val_resize", help="val shorter-edge resize before the center crop (reference: 256)")
    p.add_argument("--gamma", default=d.gamma, type=float, metavar="gamma", help="lr decay factor")
    p.add_argument("--resume", default=d.resume, help="checkpoint path to resume from (.msgpack, or a reference .pth.tar to import); 'auto' = resume from outpath's newest VALID checkpoint if one exists, else fresh start (for elastic restarts)")
    _bool_flag(p, "torch_checkpoints", d.torch_checkpoints, "also write reference-format checkpoint.pth.tar/model_best.pth.tar")
    p.add_argument("--checkpoint-backend", default=d.checkpoint_backend, choices=["msgpack", "orbax"], dest="checkpoint_backend", help="msgpack = sync single-file; orbax = async background writes")
    p.add_argument("--keep-checkpoints", default=d.keep_checkpoints, type=int, dest="keep_checkpoints", help="per-epoch history checkpoints kept as the corrupt-fallback pool (msgpack backend; 0 = live file only)")
    p.add_argument("--inject", default=d.inject, help="fault-injection spec, e.g. 'rank_exit@step=7;decode_fail:p=0.01' (tpudist/faults.py; env TPUDIST_INJECT)")
    p.add_argument("--data-retries", default=d.data_retries, type=int, dest="data_retries", help="retries per failing sample read/decode before skip-and-count")
    p.add_argument("--data-retry-backoff", default=d.data_retry_backoff, type=float, dest="data_retry_backoff", help="linear backoff between sample-load retries (seconds)")
    p.add_argument("--data-skip-budget", default=d.data_skip_budget, type=int, dest="data_skip_budget", help="skipped samples tolerated per epoch before the loader fails loudly (0 = strict)")
    _bool_flag(p, "telemetry", d.telemetry, "write structured telemetry: per-rank events.<rank>.jsonl (step timing breakdown, compile/checkpoint/fault events, run goodput) + heartbeats for launcher straggler detection; summarize with python -m tpudist.summarize <outpath>")
    _bool_flag(p, "telemetry_mfu", d.telemetry_mfu, "with --telemetry: compute per-step MFU from the compiled step's cost-analysis FLOPs (one extra XLA compile unless the persistent compile cache is enabled)")
    p.add_argument("--metrics-port", default=d.metrics_port, type=int, dest="metrics_port", help="with --telemetry: serve live Prometheus metrics (step p50/p95, phase breakdown, MFU, goodput, fault counters, heartbeat age) on this port; 0 = pick a free port (written to <outpath>/metrics.<rank>.port); -1 = off")
    p.add_argument("--telemetry-max-mb", default=d.telemetry_max_mb, type=float, dest="telemetry_max_mb", help="roll events.<rank>.jsonl to events.<rank>.1.jsonl past this size (MB; bounds long-run telemetry at ~2x the cap; 0 = uncapped)")
    p.add_argument("--profile", default=d.profile, help="jax.profiler trace window as global-step range 'start:end' (written to outpath/profile/attempt_<n>)")
    _bool_flag(p, "doctor", d.doctor,
               "guarded train step + detect-respond policies "
               "(docs/DOCTOR.md): in-step finiteness sentinels with "
               "GradScaler-style skip-step, EWMA loss-spike detection on "
               "the drained metrics, rollback-to-last-verified-good with "
               "data-order replay, SDC self-quarantine")
    p.add_argument("--doctor-probe-freq", default=d.doctor_probe_freq,
                   type=int, dest="doctor_probe_freq",
                   help="with --doctor: digest the dp-replicated state and "
                        "compare across replicas every N steps (silent-"
                        "data-corruption probe; stamps checkpoint verdicts "
                        "good/suspect; 0 = off)")
    p.add_argument("--doctor-spike-sigma", default=d.doctor_spike_sigma,
                   type=float, dest="doctor_spike_sigma",
                   help="EWMA loss-spike threshold in sigmas above the "
                        "running mean")
    p.add_argument("--doctor-spike-min-steps",
                   default=d.doctor_spike_min_steps, type=int,
                   dest="doctor_spike_min_steps",
                   help="EWMA warmup steps before a spike can fire")
    p.add_argument("--doctor-max-skips", default=d.doctor_max_skips,
                   type=int, dest="doctor_max_skips",
                   help="consecutive non-finite (skipped) steps before the "
                        "doctor escalates to a rollback")
    p.add_argument("--doctor-max-rollbacks", default=d.doctor_max_rollbacks,
                   type=int, dest="doctor_max_rollbacks",
                   help="rollbacks tolerated per run before failing loudly")
    p.add_argument("--doctor-sdc-windows", default=d.doctor_sdc_windows,
                   type=int, dest="doctor_sdc_windows",
                   help="consecutive minority-divergent SDC probes before "
                        "a rank self-quarantines (exit 76, elastic reform)")
    _bool_flag(p, "blackbox", d.blackbox,
               "flight recorder (docs/INCIDENTS.md): ring-buffer the last "
               "N telemetry samples per rank and, on an anomaly trigger "
               "(doctor, SDC divergence, fault, preempt, SIGUSR2, "
               "POST /capture), dump the ring + arm a one-shot bounded "
               "jax.profiler trace and HLO snapshot; requires --telemetry")
    p.add_argument("--blackbox-ring", default=d.blackbox_ring, type=int,
                   dest="blackbox_ring",
                   help="flight-recorder ring depth (events kept per rank)")
    p.add_argument("--blackbox-capture-steps",
                   default=d.blackbox_capture_steps, type=int,
                   dest="blackbox_capture_steps",
                   help="deep-capture profiler trace length, in steps")
    p.add_argument("--blackbox-cooldown-s", default=d.blackbox_cooldown_s,
                   type=float, dest="blackbox_cooldown_s",
                   help="per-trigger-class cooldown: within it a repeat "
                        "trigger emits an incident event but dumps/"
                        "captures nothing (storm bound)")
    p.add_argument("--replica-check-freq", default=d.replica_check_freq, type=int, dest="replica_check_freq", help="verify replicated state is identical across devices every N epochs (0 = off)")
    p.add_argument("--stall-timeout", default=d.stall_timeout, type=float, dest="stall_timeout", help="abort the process if no training step completes for N seconds (0 = off)")
    p.add_argument("--require-platform", default=d.require_platform,
                   dest="require_platform", choices=("any", "tpu", "cpu"),
                   help="refuse to run unless jax initialized on this "
                        "backend (a run meant for the chip must not "
                        "complete on the CPU)")
    p.add_argument("--overwrite", default=d.overwrite, choices=["prompt", "delete", "quit", "keep"], help="what to do if outpath exists (keep = reuse untouched, for elastic restarts)")
    p.add_argument("--num-classes", default=d.num_classes, type=int, dest="num_classes")
    p.add_argument("--seq-len", default=d.seq_len, type=int, dest="seq_len", help="ids a row, for a model of tokens (-b counts rows)")
    p.add_argument("--layers", default=d.layers, type=int, help="leading layers of the model kept here (0 = all); the rest are further pipeline stages")
    p.add_argument("--expert-share", default=d.expert_share, dest="expert_share", help="'i/n': this holder is the i-th of n that divide each layer's experts")
    p.add_argument("--vocab-share", default=d.vocab_share, dest="vocab_share", help="'i/n': this holder's slice of the vocabulary's rows; ids, logits and loss are over the slice")
    p.add_argument("--adam-b2", default=d.adam_b2, type=float, dest="adam_b2", help="AdamW second-moment decay")
    p.add_argument("--image-size", default=d.image_size, type=int, dest="image_size")
    p.add_argument("--mesh-shape", default=None, dest="mesh_shape", help="comma-separated mesh shape, e.g. '8' or '4,2'")
    p.add_argument("--mesh-axes", default=",".join(d.mesh_axes), dest="mesh_axes", help="comma-separated mesh axis names; 'data' = DP, plus ONE of 'model' (tensor parallel), 'seq' (ring-attention sequence parallel, vit_*), 'pipe' (GPipe pipeline parallel, vit_pipe_*), or 'expert' (MoE expert parallel, vit_moe_*; pure 'expert' or composed 'data,expert')")
    _bool_flag(p, "zero_opt", d.zero_opt, "deprecated alias for --zero 1")
    p.add_argument("--zero", default=d.zero, choices=("off", "1", "full"),
                   help="cross-replica weight-update sharding "
                        "(arXiv:2004.13336): 1 = ZeRO-1, optimizer moments "
                        "shard over the data axis (GSPMD path); full = "
                        "ZeRO-full, params + moments + EMA shard on their "
                        "largest divisible dim, params all-gathered "
                        "just-in-time and gradients reduce-scattered "
                        "(parallel/comm.py; composes with "
                        "--compress-grads). See docs/COMMUNICATION.md")
    p.add_argument("--compress-grads", default=d.compress_grads,
                   dest="compress_grads", choices=("off", "int8", "auto"),
                   help="gradient-reduction wire format: int8 = quantized "
                        "two-phase all-reduce with per-chunk scales and "
                        "error feedback (EQuARX, arXiv:2506.17615 — "
                        "~4x fewer interconnect bytes); auto = "
                        "measurement-honest dispatch (compressed-vs-dense "
                        "A/B at the exact gradient size on the attached "
                        "fabric, cached per device kind — int8 is never "
                        "selected where it loses; off-TPU auto = dense). "
                        "See docs/COMMUNICATION.md")
    _bool_flag(p, "distributed", d.distributed, "initialize jax.distributed multi-host runtime")
    p.add_argument("--coordinator-address", default=None, dest="coordinator_address")
    p.add_argument("--num-processes", default=None, type=int, dest="num_processes")
    p.add_argument("--process-id", default=None, type=int, dest="process_id")
    return p


def from_args(argv: Sequence[str] | None = None) -> Config:
    ns = build_parser().parse_args(argv)
    cfg = Config()
    for f in dataclasses.fields(Config):
        if hasattr(ns, f.name):
            setattr(cfg, f.name, getattr(ns, f.name))
    cfg.step = parse_milestones(cfg.step)
    if isinstance(cfg.mesh_shape, str):
        cfg.mesh_shape = [int(x) for x in cfg.mesh_shape.split(",")]
    if isinstance(cfg.mesh_axes, str):
        cfg.mesh_axes = [a for a in cfg.mesh_axes.split(",") if a]
    return cfg


def write_settings(cfg: Config, outpath: str) -> None:
    """Dump every config k/v to ``settings.log`` (reference utils.py:54-62)."""
    with open(os.path.join(outpath, "settings.log"), "w") as f:
        for k, v in cfg.asdict().items():
            f.write(f"{k}: {v}\n")
