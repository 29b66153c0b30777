"""Chip smoke: prove on the attached TPU that the trainer trains, that what
it logs as running is what ran, and that the kernels match their references.

    python3 chip_smoke.py              # one chip: device, train, input, kernels
    python3 chip_smoke.py --multichip  # four chips: DP+SyncBN vs one device,
                                       # one dp x tp 2x2 ViT-B/16 step

One process, and it is the only one that touches jax (a chip belongs to one
process at a time); the children it starts (``make``, the JPEG generator)
never need the chip and have exited before the phase that used them ends.
Each phase prints one JSON line; the last stdout line is the contract's
``{"ok": true, "device": {...}}`` — printed only if every phase passed. On
anything but a TPU the script exits non-zero before the first phase line.

Everything under test goes through the entry points a user calls:
``config.from_args`` -> ``trainer.run`` (what ``python -m tpudist`` runs),
with ``--require-platform tpu`` on every trainer call.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# Module constants, not options: the only other values they ever take are
# the tiny CPU ones a rehearsal script assigns before calling main().
PLATFORM = "tpu"
INTERPRET = False            # Pallas kernels compiled by Mosaic, not interpreted
MODEL = ["-a", "resnet18", "--num-classes", "1000", "--image-size", "224",
         "--use_amp"]
VIT = ["-a", "vit_b_16", "--num-classes", "1000", "--image-size", "224",
       "--use_amp"]
PER_CHIP_BATCH = 128
STEPS_PER_EPOCH = 3          # x 4 epochs = a dozen steps over repeated data,
EPOCHS = 4                   # so the train loss has something to fall on
VIT_B16_ATTENTION = (128, 197, 12, 64)      # batch, tokens, heads, head_dim


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileLog:
    """Times of every backend compile request jax makes in this process
    (persistent-cache hits included: a hit still means a program that the
    in-memory cache did not hold)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **_):
        if name == self.EVENT:
            self.times.append(time.time())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 < t <= t1)


# -- device ------------------------------------------------------------------

def phase_device(want_count: int | None) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != PLATFORM:
        print(f"chip_smoke: jax found no {PLATFORM} (platform "
              f"'{dev['platform']}') — this script only runs on the chip",
              file=sys.stderr)
        raise SystemExit(2)
    if want_count is not None and dev["count"] != want_count:
        print(f"chip_smoke: --multichip needs {want_count} chips, found "
              f"{dev['count']}", file=sys.stderr)
        raise SystemExit(2)
    from tpudist.telemetry import resolve_peak_flops, resolve_peak_hbm
    peak = resolve_peak_flops(dev["kind"])
    if peak is None:
        raise AssertionError(
            f"device_kind '{dev['kind']}' resolves to no peak FLOP/s in "
            f"tpudist.telemetry.PEAK_FLOPS_BY_KIND — MFU would be silently "
            f"unreported on this chip")
    from tpudist.serve.cache import configure_compile_cache
    cache_dir, cache_state = configure_compile_cache()
    say("device", ok=True, **dev, peak_flops=peak,
        peak_hbm_bytes_per_s=resolve_peak_hbm(dev["kind"]),
        jax=jax.__version__, compile_cache=cache_dir,
        compile_cache_state=cache_state)
    return dev


# -- kernels -----------------------------------------------------------------

def _mismatch(got, want, rtol: float, atol: float):
    """(#elements outside tolerance, largest error as a share of its
    tolerance), computed on device."""
    import jax.numpy as jnp
    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    share = jnp.abs(g - w) / (atol + rtol * jnp.abs(w))
    share = jnp.where(jnp.isfinite(g), share, jnp.inf)
    return int(jnp.sum(share > 1.0)), float(jnp.max(share))


def _check(name: str, pairs, tol_fn) -> dict:
    """Compare each (label, got, want) elementwise: every element must be
    inside the tolerance."""
    worst = {}
    for label, got, want in pairs:
        if got.shape != want.shape or got.dtype != want.dtype:
            raise AssertionError(f"{name} {label}: got {got.shape} "
                                 f"{got.dtype}, want {want.shape} "
                                 f"{want.dtype}")
        rtol, atol = tol_fn(want)
        bad, share = _mismatch(got, want, rtol, atol)
        if bad:
            raise AssertionError(
                f"{name} {label}: {bad} of {got.size} elements outside "
                f"rtol={rtol:g} atol={atol:g} (worst error {share:g}x its "
                f"tolerance)")
        worst[label] = {"err_over_tol": round(share, 4)}
    return worst


def _flash_case(key, schedule: str) -> dict:
    """Flash attention fwd + dQ/dK/dV at ViT-B/16 widths vs XLA attention
    on the f32-widened inputs at highest matmul precision (tolerances of
    tests/test_flash_attention.py: 2e-2 forward, 1e-2 relative to max|ref|
    on gradients), from one fused projection [B, T, H, 3, D]. ``whole_seq``
    is the fused entry as ``MultiHeadAttention`` calls it (the schedule this
    shape selects, asserted); ``streaming`` is the split entry, the
    streaming kernels."""
    import jax
    import jax.numpy as jnp
    from tpudist.ops.pallas.flash_attention import (
        flash_attention, flash_attention_qkv, schedule_for)
    from tpudist.parallel.ring_attention import attention, split_qkv
    f32 = jnp.float32
    b, t, h, d = VIT_B16_ATTENTION
    kq, kg = jax.random.split(key)
    qkv = jax.random.normal(kq, (b, t, h, 3, d), jnp.bfloat16)
    g = jax.random.normal(kg, VIT_B16_ATTENTION, f32)

    def loss(fn):
        def f(qkv):
            o = fn(qkv)
            return (o.astype(f32) * g).sum(), o
        return f

    if schedule == "whole_seq":
        assert schedule_for(t, h, d, qkv.dtype) == schedule
        flash = lambda x: flash_attention_qkv(  # noqa: E731
            x, interpret=INTERPRET)
    else:
        flash = lambda x: flash_attention(  # noqa: E731
            *split_qkv(x), interpret=INTERPRET)
    (_, o1), g1 = jax.jit(jax.value_and_grad(
        loss(flash), has_aux=True))(qkv)
    with jax.default_matmul_precision("highest"):
        (_, o2), g2 = jax.jit(jax.value_and_grad(
            loss(lambda x: attention(*split_qkv(x))), has_aux=True))(
                qkv.astype(f32))
    out = _check(f"flash {schedule}", [("o", o1.astype(f32), o2)],
                 lambda want: (2e-2, 2e-2))
    out.update(_check(
        f"flash {schedule}",
        [(n, a.astype(f32), b_) for n, a, b_ in zip(
            ("dq", "dk", "dv"), split_qkv(g1), split_qkv(g2))],
        lambda want: (1e-2, 1e-2 * float(jnp.max(jnp.abs(want))))))
    return out


def phase_kernels(seed: int) -> None:
    import jax
    from tpudist.ops import attention_dispatch
    from tpudist.ops.pallas.flash_attention import KERNEL_REV as flash_rev
    key = jax.random.PRNGKey(seed)
    cases = {}
    # Both schedules at ViT-B/16's shape: the one it selects (and --flash
    # auto runs wherever the kernel wins its probe), and the streaming one.
    for schedule in ("whole_seq", "streaming"):
        key, sub = jax.random.split(key)
        cases["flash_{}_b{}_t{}_h{}_d{}".format(
            schedule, *VIT_B16_ATTENTION)] = _flash_case(sub, schedule)
    # What the default flag (--flash auto) resolves to for ViT-B/16 at the
    # per-chip batch the trainer would run: the same decide() the Trainer
    # calls, so a kernel the compiler refuses raises here too.
    _, t, h, d = VIT_B16_ATTENTION
    dec = attention_dispatch.decide(64, t, h, d, "bfloat16", train=True,
                                    mode="auto")
    say("kernels", ok=True, interpret=INTERPRET, flash_rev=flash_rev,
        flash_schedule=attention_dispatch.schedule(t, h, d, "bfloat16"),
        cases=len(cases),
        worst_err_over_tolerance={k: max(v[n]["err_over_tol"] for n in v)
                                  for k, v in cases.items()},
        attention_dispatch={k: dec.get(k) for k in (
            "kernel", "mode", "source", "key", "flash_ms", "xla_ms",
            "margin")})


# -- trainer runs --------------------------------------------------------------

def _events(outpath: str) -> list[dict]:
    with open(os.path.join(outpath, "events.0.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


_STEP_LOSS = re.compile(r"Epoch\[(\d+)\]:\t\[(\d+)/\d+\].*?Loss (\S+) \(")
_EPOCH_LOSS = re.compile(r"\|\|==> (Train|Val): Epoch\[(\d+)\]\tLoss (\S+)")


def _losses(outpath: str, since: int = 0):
    """(per-step train losses, {epoch: train mean}, {epoch: val mean}) parsed
    from experiment.log (bytes ``since`` onward). With the default async
    metric drain console line i shows step i-1's loss; line 0 shows none."""
    with open(os.path.join(outpath, "experiment.log")) as f:
        f.seek(since)
        text = f.read()
    steps = [float(m.group(3)) for m in _STEP_LOSS.finditer(text)
             if int(m.group(2)) > 0]
    train = {int(m.group(2)): float(m.group(3))
             for m in _EPOCH_LOSS.finditer(text) if m.group(1) == "Train"}
    val = {int(m.group(2)): float(m.group(3))
           for m in _EPOCH_LOSS.finditer(text) if m.group(1) == "Val"}
    return steps, train, val


def _cfg(argv: list[str]):
    """``config.from_args`` plus what every trainer call here carries:
    refuse any other backend, and keep only the live checkpoint file (the
    run dirs come back through the chip tool, which carries 64 MiB)."""
    from tpudist.config import from_args
    return from_args(argv + ["--require-platform", PLATFORM,
                             "--keep-checkpoints", "0"])


def _run_trainer(argv: list[str]) -> float:
    """What ``python -m tpudist <argv>`` runs, in this process."""
    from tpudist.trainer import run
    return run(_cfg(argv))


def _drop_checkpoints(outpath: str) -> None:
    """Delete a finished run's checkpoint files (logs, settings and
    telemetry stay)."""
    for name in os.listdir(outpath):
        if name.endswith(".msgpack"):
            os.remove(os.path.join(outpath, name))


def _check_run(outpath: str, compiles: CompileLog, *, first_epoch: int,
               n_epochs: int, steps_per_epoch: int, log_since: int = 0,
               ev_since: int = 0) -> dict:
    """The assertions every trainer run must meet; returns its record."""
    evs = _events(outpath)[ev_since:]
    start = next(e for e in evs if e["type"] == "run_start")
    assert start["platform"] == PLATFORM, start
    steps = [e for e in evs if e["type"] == "step"]
    assert len(steps) == n_epochs * steps_per_epoch, \
        (len(steps), n_epochs, steps_per_epoch)
    # No compile after warm-up. The trainer's own compile events (first
    # dispatch + cost analysis) must all precede the second step's end, and
    # jax itself must not have been asked to compile anything between the
    # end of the warm-up and the last train step — except in the first
    # epoch's tail, where the eval program compiles once.
    t_warm = steps[1]["t"]
    tel_compiles = [e for e in evs if e["type"] == "compile"]
    late = [e for e in tel_compiles if e["t"] > t_warm]
    assert not late, f"compile telemetry events after warm-up: {late}"
    epoch_ends = [e["t"] for e in evs if e["type"] == "epoch"]
    assert len(epoch_ends) == n_epochs, (len(epoch_ends), n_epochs)
    windows = [(t_warm, steps[steps_per_epoch - 1]["t"])]
    if n_epochs > 1:
        windows.append((epoch_ends[0], epoch_ends[-1]))
    recompiles = sum(compiles.between(a, b) for a, b in windows)
    assert recompiles == 0, \
        f"{recompiles} compile(s) in the steady-state window(s) {windows}"
    per_step, train, val = _losses(outpath, log_since)
    epochs = list(range(first_epoch, first_epoch + n_epochs))
    assert sorted(train) == epochs and sorted(val) == epochs, (train, val)
    every = per_step + list(train.values()) + list(val.values())
    assert every and all(math.isfinite(x) for x in every), every
    peaks = [e["peak_hbm_gb"] for e in evs
             if e["type"] == "epoch" and "peak_hbm_gb" in e]
    assert peaks, "no epoch event reported peak_bytes_in_use"
    end = next(e for e in evs if e["type"] == "run_end")
    compiled = next(e for e in tel_compiles if e["phase"] == "cost_analysis")
    return {
        "steps": len(steps), "per_step_loss": per_step,
        "train_loss_by_epoch": train, "val_loss_by_epoch": val,
        "compile_events": [{k: e.get(k) for k in ("phase", "seconds",
                                                  "cache")}
                           for e in tel_compiles],
        "recompiles_after_warmup": recompiles,
        # The runtime's high-water mark (device.memory_stats()) beside the
        # compiler's own account of the step program.
        "peak_hbm_gb": max(peaks),
        "compiled_step_hbm_gb": {
            k: round(compiled[f] / 2**30, 3)
            for k, f in (("total", "hbm_compiled_bytes"),
                         ("temps", "temp_bytes"), ("args", "arg_bytes"))
            if f in compiled},
        "goodput": end.get("goodput"),
    }


def _dispatch_lines(evs: list[dict]) -> dict:
    out = {}
    for e in evs:
        if e["type"] in ("attention_dispatch", "comm_dispatch"):
            out[e["type"]] = {k: v for k, v in e.items()
                              if k not in ("t", "type", "rank", "attempt")}
    return out


def phase_train(out: str, seed: int, n_dev: int,
                compiles: CompileLog) -> None:
    outpath = os.path.join(out, "train")
    batch = PER_CHIP_BATCH * n_dev
    argv = ["--synthetic", *MODEL, "-b", str(batch),
            "--synthetic-size", str(batch * STEPS_PER_EPOCH),
            "--seed", str(seed), "-p", "1", "-j", "8", "--telemetry",
            "--outpath", outpath]
    _run_trainer(argv + ["--epochs", str(EPOCHS), "--overwrite", "delete"])
    first = _check_run(outpath, compiles, first_epoch=0, n_epochs=EPOCHS,
                       steps_per_epoch=STEPS_PER_EPOCH)
    tl = first["train_loss_by_epoch"]
    assert tl[EPOCHS - 1] < tl[0], \
        f"train loss did not fall over {EPOCHS} epochs: {tl}"
    ckpt = os.path.join(outpath, "checkpoint.msgpack")
    assert os.path.getsize(ckpt) > 0
    evs = _events(outpath)
    say("train", ok=True, model=" ".join(MODEL), global_batch=batch, **first,
        checkpoint_bytes=os.path.getsize(ckpt),
        dispatch=_dispatch_lines(evs))

    # Resume from that checkpoint for one more epoch (--overwrite keep: the
    # outpath holds the checkpoint being resumed).
    log_since = os.path.getsize(os.path.join(outpath, "experiment.log"))
    _run_trainer(argv + ["--epochs", str(EPOCHS + 1), "--overwrite", "keep",
                         "--resume", ckpt])
    resumed = _check_run(outpath, compiles, first_epoch=EPOCHS, n_epochs=1,
                         steps_per_epoch=STEPS_PER_EPOCH,
                         log_since=log_since, ev_since=len(evs))
    with open(os.path.join(outpath, "experiment.log")) as f:
        f.seek(log_since)
        assert f"(epoch {EPOCHS}," in f.read(), "resume line not logged"
    assert resumed["train_loss_by_epoch"][EPOCHS] < tl[0], (resumed, tl)
    _drop_checkpoints(outpath)
    say("resume", ok=True, from_epoch=EPOCHS, **resumed)


def phase_input(out: str, seed: int, n_dev: int,
                compiles: CompileLog) -> None:
    """A few steps of the same model through the real input path: JPEG
    ImageFolder -> (native) decode/crop/flip/normalize -> threaded loader ->
    device prefetch. ``native/`` is rebuilt from source here: the checkout
    holds no binary, and one built on another CPU must not be loaded."""
    native_dir = os.path.join(REPO, "native")
    t0 = time.time()
    mk = subprocess.run(["make", "-C", native_dir, "clean", "all"],
                        capture_output=True, text=True, timeout=600)
    if mk.returncode != 0:
        raise AssertionError(f"native build failed (exit {mk.returncode}):\n"
                             f"{mk.stdout[-2000:]}\n{mk.stderr[-2000:]}")
    build_s = time.time() - t0
    root = os.path.join(out, "imagefolder")
    shutil.rmtree(root, ignore_errors=True)
    batch = PER_CHIP_BATCH * n_dev
    classes, steps = 8, 4
    t0 = time.time()
    subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "make_synth_imagefolder.py"),
         "--root", root, "--classes", str(classes),
         "--train-per-class", str(batch * steps // classes),
         "--val-per-class", str(batch // classes), "--size", "256",
         "--seed", str(seed)],
        check=True, capture_output=True, text=True, timeout=600)
    gen_s = time.time() - t0

    from tpudist.data import native
    decode = ("native-jpeg" if native.jpeg_available()
              else "native-transform+PIL-decode" if native.available()
              else "PIL")
    assert decode == "native-jpeg", \
        f"native library built but the loader would run '{decode}'"
    outpath = os.path.join(out, "input")
    _run_trainer(["--data", root, *MODEL, "-b", str(batch), "--epochs", "1", "--seed", str(seed), "-p", "1", "-j", "8",
                  "--telemetry", "--outpath", outpath,
                  "--overwrite", "delete"])
    rec = _check_run(outpath, compiles, first_epoch=0, n_epochs=1,
                     steps_per_epoch=steps)
    epoch = next(e for e in _events(outpath) if e["type"] == "epoch")
    assert not epoch.get("samples_skipped"), epoch
    _drop_checkpoints(outpath)
    shutil.rmtree(root)
    say("input", ok=True, decode_path=decode,
        native_build_s=round(build_s, 1), jpeg_gen_s=round(gen_s, 1),
        jpegs=batch * steps + batch, **rec)


# -- four chips --------------------------------------------------------------

def _fit(argv: list[str], mesh=None):
    """Trainer + fit (what ``trainer.run`` does), keeping the Trainer so
    its placed state can be inspected."""
    from tpudist.trainer import Trainer
    t = Trainer(_cfg(argv), mesh=mesh)
    t.fit()
    _drop_checkpoints(t.cfg.outpath)
    return t


def _devices_of(tree) -> set:
    import jax
    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs |= {s.device for s in leaf.addressable_shards}
    return devs


def _census(outpath: str) -> dict:
    ev = next(e for e in _events(outpath)
              if e["type"] == "compile" and e.get("phase") == "cost_analysis")
    return {k: v for k, v in ev.items()
            if k.startswith(("all_reduce", "collective"))}


def phase_multichip(out: str, seed: int, compiles: CompileLog) -> None:
    import jax
    import numpy as np
    from tpudist.dist import make_mesh, shard_host_batch
    devs = jax.devices()
    batch, steps = 512, 4

    def dp_argv(name):
        return ["--synthetic", *MODEL, "--sync_batchnorm", "-b", str(batch), "--synthetic-size", str(batch * steps),
                "--epochs", "1", "--seed", str(seed), "-p", "1", "-j", "8",
                "--telemetry", "--outpath", os.path.join(out, name),
                "--overwrite", "delete"]

    # (a) DP + SyncBN over four chips vs the same global batch on one.
    t4 = _fit(dp_argv("dp4"))
    assert t4.mesh.devices.size == 4 and not t4.uses_gspmd_path
    on = _devices_of(t4.state.params)
    assert on == set(devs), f"params live on {on}, not all of {devs}"
    host = (np.zeros((batch, 8, 8, 3), np.float32),
            np.zeros((batch,), np.int32))
    for arr in shard_host_batch(t4.mesh, host):
        shards = arr.addressable_shards
        assert {s.device for s in shards} == set(devs)
        assert all(s.data.shape[0] == batch // 4 for s in shards)
    census4 = _census(os.path.join(out, "dp4"))
    assert census4.get("all_reduce_count", 0) > 0, census4
    rec4 = _check_run(os.path.join(out, "dp4"), compiles, first_epoch=0,
                      n_epochs=1, steps_per_epoch=steps)
    _fit(dp_argv("dp1"), mesh=make_mesh((1,), ("data",), devs[:1]))
    rec1 = _check_run(os.path.join(out, "dp1"), compiles, first_epoch=0,
                      n_epochs=1, steps_per_epoch=steps)
    l4 = rec4["per_step_loss"] + [rec4["train_loss_by_epoch"][0]]
    l1 = rec1["per_step_loss"] + [rec1["train_loss_by_epoch"][0]]
    # bf16 tolerance: 8 mantissa bits through an 18-layer forward; the two
    # programs differ only in reduction order (SyncBN's pmean of shard
    # statistics vs one batch-512 statistic, gradient all-reduce vs none).
    assert len(l4) == len(l1) == steps and np.allclose(l4, l1, rtol=2e-2), \
        (l4, l1)
    say("multichip_dp", ok=True, global_batch=batch, sync_batchnorm=True,
        loss_4chip=l4, loss_1chip=l1,
        max_rel_diff=float(np.max(np.abs(np.subtract(l4, l1))
                                  / np.abs(l1))),
        param_devices=len(on), census=census4,
        compiled_step_hbm_gb_4chip=rec4["compiled_step_hbm_gb"],
        compiled_step_hbm_gb_1chip=rec1["compiled_step_hbm_gb"])

    # (b) one dp x tp 2x2 GSPMD step of ViT-B/16.
    name = os.path.join(out, "vit_dp_tp")
    tv = _fit(["--synthetic", *VIT, "-b", "128",
               "--synthetic-size", "256", "--epochs", "1",
               "--mesh-shape", "2,2", "--mesh-axes", "data,model",
               "--seed", str(seed), "-p", "1", "-j", "8", "--telemetry",
               "--outpath", name, "--overwrite", "delete"])
    assert tv.uses_gspmd_path and dict(tv.mesh.shape) == {"data": 2,
                                                          "model": 2}
    assert _devices_of(tv.state.params) == set(devs)
    cut = [leaf for leaf in jax.tree_util.tree_leaves(tv.state.params)
           if "model" in str(leaf.sharding.spec)]
    assert cut, "no parameter is sharded over the model axis"
    census = _census(name)
    assert census.get("collective_ops", 0) > 0, census
    recv = _check_run(name, compiles, first_epoch=0, n_epochs=1,
                      steps_per_epoch=2)
    say("multichip_vit_dp_tp", ok=True, mesh={"data": 2, "model": 2},
        global_batch=128, tp_sharded_params=len(cut), census=census,
        dispatch=_dispatch_lines(_events(name)), **recv)


# -- driver ----------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip paths (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join("chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args()
    if not __debug__:
        raise SystemExit("chip_smoke.py checks with assert statements: "
                         "do not run it under python -O")

    dev = phase_device(4 if args.multichip else None)
    os.makedirs(args.out, exist_ok=True)
    compiles = CompileLog()
    if args.multichip:
        phase_multichip(args.out, args.seed, compiles)
    else:
        # Trainer first: peak_bytes_in_use is a process-wide high-water
        # mark, and the kernel phase's 112x112x64 operands would own it.
        phase_train(args.out, args.seed, dev["count"], compiles)
        phase_input(args.out, args.seed, dev["count"], compiles)
        phase_kernels(args.seed)
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
