"""Measurement-honest fused-BN-epilogue dispatch (``--fused-bn auto``) — the
second client of the generic dispatch layer (``tpudist/ops/dispatch``),
beside ``ops/attention_dispatch``.

The kernels (``ops/pallas/fused_norm``: BN+ReLU and BN+add+ReLU single-pass
epilogues) are wired into ``models/layers.py::BatchNorm``, which every conv
family shares — so ONE dispatch question covers resnet, vgg, densenet,
regnet, mobilenet, the inception family, … without per-model logic. The
same honesty policy as attention applies, via the same generic machinery:

- ``use_fused()`` is the TRACE-SAFE call BatchNorm makes while the step is
  being traced: mode/eligibility/platform/cache only, never a measurement.
  Unmeasured ⇒ XLA; off-TPU ``auto`` ⇒ XLA without ``fused_norm`` (and its
  Pallas import) ever entering ``sys.modules``.
- the Trainer warms the cache OUTSIDE the trace: ``record_requests()``
  captures every (rows, channels, dtype, variant) workload an
  ``eval_shape`` of the model requests, and ``decide()`` micro-benchmarks
  each exactly once per device kind (cached in
  ``fused_norm.<kind>.json``, invalidated by ``KERNEL_REV``).
- multi-host gangs get ONE verdict set: the primary publishes
  ``fused_norm_dispatch.json`` into the shared run dir
  (``shared_decide_all``), and peers ADOPT it into their local cache so
  their trace-time lookups compile the same kernels — a near-tie shape
  must not mix epilogue backends inside one SPMD program.

Structural fallbacks (not measurement questions, decided at the call
site in ``models/layers.py``): SyncBN (``axis_name`` set — the stat
``pmean`` has no fused kernel) and eval-mode running-stats both take the
XLA path explicitly, even under ``--fused-bn on``.

Mode is process-global (``set_mode`` from ``Config.fused_bn``, env
``TPUDIST_FUSED_BN`` for subprocess-level forcing) because BatchNorm sits
too deep for ctor plumbing through 19 model files — the exact per-model
edits this layer exists to avoid.
"""

from __future__ import annotations

import contextlib
import os
from functools import partial
from typing import Callable, Optional

from tpudist.ops import dispatch

CLIENT = "fused_norm"
NAMES = ("pallas", "xla")
MODES = dispatch.MODES
ENV_MODE = "TPUDIST_FUSED_BN"
SHARED_FILENAME = "fused_norm_dispatch.json"

_mode: Optional[str] = None
_recording: Optional[set] = None


def set_mode(mode: Optional[str]) -> None:
    """Install the process-wide ``--fused-bn`` mode (None = back to the env/
    default resolution). Raises on anything outside auto|on|off so a Config
    typo cannot silently coerce to off."""
    if mode is not None and mode not in MODES:
        raise ValueError(f"fused-bn mode must be one of {MODES}, got "
                         f"{mode!r}")
    global _mode
    _mode = mode


def get_mode() -> str:
    if _mode is not None:
        return _mode
    env = os.environ.get(ENV_MODE, "")
    return env if env in MODES else "auto"


def kernel_rev() -> int:
    """Lazy import: the cache/decision plumbing must not drag Pallas in on
    the XLA-only path."""
    from tpudist.ops.pallas.fused_norm import KERNEL_REV
    return KERNEL_REV


def norm_key(rows: int, channels: int, dtype, residual: bool) -> str:
    """The dispatch identity: the exact epilogue workload. ``rows`` is the
    flattened non-channel extent of the activation the traced step actually
    runs (per-shard under shard_map DP — the shape a device executes)."""
    try:
        import numpy as np
        name = np.dtype(dtype).name
    except TypeError:
        name = getattr(dtype, "name", None) or str(dtype)
    return f"m{rows}_c{channels}_{name}_{'res' if residual else 'plain'}"


def fused_eligible(*, rows: int, channels: int) -> tuple[bool, str]:
    """Static eligibility: workloads the kernel cannot (or will never
    sensibly) tile resolve to XLA before any device question is asked."""
    if rows < 1 or channels < 1:
        return False, "empty activation"
    if channels > 8192:
        return False, (f"channels {channels} exceeds the kernel's channel "
                       f"tiling")
    if rows < 8:
        return False, (f"rows {rows} is below one sublane tile — a "
                       f"streaming epilogue cannot win")
    return True, "eligible"


cache_path = partial(dispatch.cache_path, CLIENT)
clear_cache = partial(dispatch.clear_cache, CLIENT)


def epilogue_shard_axes(shape):
    """``(mesh, batch_axis, channel_axis)`` — THE single derivation of
    which ambient Auto mesh axes cut an ``(..., C)`` epilogue activation:
    the batch (leading) dim over ``data`` and the channel (trailing) dim
    over ``model``, each only when the axis exists, is Auto
    (partitioner-managed — inside a shard_map body both read as Manual and
    nothing cuts, see _jaxshim.ambient_auto_axes), has size > 1, and
    divides the dim. Shared by the dispatch key
    (``shard_local_workload``) and the kernel wrapper
    (``pallas/fused_norm.fused_bn_act_spmd``) so the workload that is
    keyed/measured and the block the wrapper actually runs CANNOT drift —
    a one-sided edit here is the honesty hole this layer exists to close.
    Trace-safe: shapes and mesh context only, no device work, no Pallas
    import."""
    from tpudist._jaxshim import ambient_auto_axes
    mesh, auto = ambient_auto_axes(("data", "model"))
    batch_ax = ("data" if "data" in auto and mesh.shape["data"] > 1
                and int(shape[0]) % mesh.shape["data"] == 0 else None)
    chan_ax = ("model" if "model" in auto and mesh.shape["model"] > 1
               and int(shape[-1]) % mesh.shape["model"] == 0 else None)
    return mesh, batch_ax, chan_ax


def shard_local_workload(shape) -> tuple[int, int, bool]:
    """``(rows, channels, sharded)`` — the PER-SHARD epilogue workload a
    device actually executes for an activation of (global) ``shape``.

    Outside any ambient Auto mesh (eager, the shard_map DP path — where
    the traced shapes are already local) this is the plain
    ``(prod(shape[:-1]), shape[-1], False)``. Under a GSPMD trace (the
    step builders' ``set_mesh`` ambient mesh) the batch dim divides by the ``data`` axis and the channel
    dim by the ``model`` axis exactly as ``fused_bn_act_spmd`` will shard
    them (both read ``epilogue_shard_axes`` — one derivation, no drift),
    so the dispatch key that is recorded, measured, and looked up at
    trace time IS the shard-local workload — probing the global shape
    would re-open the hole the honesty layer closes: a kernel winning an
    unrun shape and losing the real one."""
    rows = 1
    for d in shape[:-1]:
        rows *= int(d)
    channels = int(shape[-1])
    mesh, batch_ax, chan_ax = epilogue_shard_axes(shape)
    if batch_ax is not None:
        rows //= mesh.shape[batch_ax]
    if chan_ax is not None:
        channels //= mesh.shape[chan_ax]
    return rows, channels, batch_ax is not None or chan_ax is not None


@contextlib.contextmanager
def record_requests():
    """While active, every ``use_fused()`` call APPENDS its workload to the
    yielded set (and answers False — the recording pass is an abstract
    ``eval_shape``, its outputs are discarded). The Trainer records, then
    ``decide()``s each request outside the trace."""
    global _recording
    prev, _recording = _recording, set()
    try:
        yield _recording
    finally:
        _recording = prev


def use_fused(rows: int, channels: int, dtype, *, residual: bool,
              cache_dir: Optional[str] = None,
              platform: Optional[str] = None,
              device_kind: Optional[str] = None) -> bool:
    """THE trace-safe question BatchNorm asks: run the fused Pallas epilogue
    for this workload? Forced modes answer directly; ``auto`` consults the
    cache only — no entry (nobody measured) means XLA, and off-TPU the
    answer is False before any Pallas import can happen."""
    mode = get_mode()
    if mode == "off":
        return False
    ok, _ = fused_eligible(rows=rows, channels=channels)
    if not ok:
        return False
    if _recording is not None:
        _recording.add((rows, channels, norm_key(rows, channels, dtype,
                                                 residual), residual, dtype))
        return False
    if mode == "on":
        return True
    return dispatch.lookup(
        CLIENT, norm_key(rows, channels, dtype, residual),
        candidate="pallas", kernel_rev=kernel_rev, cache_dir=cache_dir,
        platform=platform, device_kind=device_kind)


def build_measure_fns(rows: int, channels: int, dtype, residual: bool,
                      *, interpret: bool = False):
    """THE fwd+bwd workload definition the micro-benchmark times —
    ``(pallas_fn, xla_fn, args)``, each fn jitted grad of a scalar loss over
    the epilogue at the exact workload. Shared with
    ``benchmarks/bench_fused_norm.py`` so dispatch verdicts and bench rows
    cannot drift in WHAT they measure any more than (via
    ``dispatch.measure_ms``) in how they time it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudist.ops.pallas.fused_norm import fused_bn_act, reference_bn_act

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows, channels)), dtype)
    res = (jnp.asarray(rng.standard_normal((rows, channels)), dtype)
           if residual else None)
    scale = jnp.asarray(rng.standard_normal(channels), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(channels), jnp.float32)
    mean = jnp.asarray(rng.standard_normal(channels), jnp.float32)
    var = jnp.asarray(rng.random(channels) + 0.5, jnp.float32)

    def loss(fn):
        def f(x, scale, bias, res=None):
            return fn(x, scale, bias, mean, var,
                      residual=res).astype(jnp.float32).sum()
        return f

    argnums = (0, 1, 2, 3) if residual else (0, 1, 2)
    args = (x, scale, bias) + ((res,) if residual else ())

    def fused(x, scale, bias, mean, var, *, residual=None):
        return fused_bn_act(x, scale, bias, mean, var, residual=residual,
                            interpret=interpret)

    pallas_c = jax.jit(jax.grad(loss(fused), argnums=argnums))
    xla_c = jax.jit(jax.grad(loss(reference_bn_act), argnums=argnums))
    return pallas_c, xla_c, args


def measure_fused_norm(rows: int, channels: int, dtype, residual: bool,
                       steps: int = 10, warmup: int = 2
                       ) -> tuple[float, float]:
    """The on-device micro-benchmark: (pallas_ms, xla_ms) for forward +
    backward of the epilogue at the exact workload — BN epilogues only
    matter in training, so fwd+bwd IS the configuration that decides. Only
    meaningful on an accelerator — callers gate on platform."""
    pallas_c, xla_c, args = build_measure_fns(rows, channels, dtype,
                                              residual)
    pallas_ms = dispatch.measure_ms(pallas_c, args, steps, warmup)
    xla_ms = dispatch.measure_ms(xla_c, args, steps, warmup)
    return pallas_ms, xla_ms


def decide(rows: int, channels: int, dtype, *, residual: bool,
           mode: str = "auto", cache_dir: Optional[str] = None,
           measure_pair: Optional[Callable[[], tuple[float, float]]] = None,
           refresh: bool = False, platform: Optional[str] = None,
           device_kind: Optional[str] = None) -> dict:
    """Resolve one epilogue workload through the generic honesty policy
    (``dispatch.decide``, ``names=("pallas", "xla")``): under ``auto`` the
    fused kernel is selected ONLY off the back of a measurement it won
    (fresh or cached per device_kind + key + KERNEL_REV); ties and losses
    keep the XLA epilogue; off-TPU resolves to XLA without measuring.

    Unlike attention (where forced ``on`` bypasses eligibility and the
    ineligible call sites carry tripwires), eligibility here is STRUCTURAL
    — it outranks even forced ``on``, exactly as ``use_fused`` enforces at
    the BatchNorm call site. A decision must name the kernel the trace
    actually runs, so the same rule applies on both surfaces."""
    key = norm_key(rows, channels, dtype, residual)
    ok, why = fused_eligible(rows=rows, channels=channels)
    if mode == "on" and not ok:
        return {"kernel": "xla", "mode": mode, "source": "ineligible",
                "key": key, "reason": why, "pallas_ms": None,
                "xla_ms": None, "margin": None, "cache_hit": False}
    if measure_pair is None:
        measure_pair = lambda: measure_fused_norm(  # noqa: E731
            rows, channels, dtype, residual)
    return dispatch.decide(
        CLIENT, key, mode=mode, names=NAMES, kernel_rev=kernel_rev,
        measure_pair=measure_pair, eligibility=(ok, why),
        cache_dir=cache_dir, refresh=refresh, platform=platform,
        device_kind=device_kind)


def adopt_decisions(decisions: dict, device_kind: str,
                    cache_dir: Optional[str] = None) -> int:
    """Seed the LOCAL cache with another host's measured verdicts (the
    ``shared_decide_all`` peer path): trace-time ``use_fused`` lookups read
    this host's per-device_kind file, so without adoption a peer would
    resolve every site to XLA while the primary compiles Pallas — mixed
    epilogue backends inside one SPMD program. Only measured/cache-sourced
    entries with a kernel_rev are adopted; returns the count."""
    path = cache_path(device_kind, cache_dir)
    cache = dispatch.load_cache(path)
    n = 0
    for key, d in decisions.items():
        if d.get("kernel") in NAMES and d.get("kernel_rev") is not None:
            cache["entries"][key] = {
                "kernel": d["kernel"],
                "pallas_ms": d.get("pallas_ms"),
                "xla_ms": d.get("xla_ms"),
                "margin": d.get("margin"),
                "kernel_rev": d["kernel_rev"],
                "measured_at": d.get("measured_at"),
            }
            n += 1
    if n:
        cache["device_kind"] = device_kind
        try:
            dispatch.save_cache(path, cache)
        except OSError:
            # Unwritable cache dir: the peer must STILL compile what the
            # primary decided — seed the in-process overlay lookup() falls
            # back to, or this rank would trace XLA into the gang's program.
            for key, d in decisions.items():
                if d.get("kernel") in NAMES \
                        and d.get("kernel_rev") is not None:
                    dispatch.seed_local(path, key, cache["entries"][key])
    return n


def combined_key(requests) -> str:
    """One stable key over a request set, for the shared-verdict freshness
    check (peers compute it from their OWN recording, so a stale file for a
    different model/batch never matches)."""
    return "+".join(sorted(r[2] for r in requests))


def shared_decide_all(outpath: str, primary: bool, decide_all_fn,
                      *, expect_key: Optional[str] = None,
                      timeout_s: float = 600.0, poll_s: float = 0.25,
                      log=None, device_kind: Optional[str] = None,
                      cache_dir: Optional[str] = None) -> dict:
    """One fused-norm verdict SET for the whole gang, via the generic
    ``dispatch.shared_decision`` (file ``fused_norm_dispatch.json``).
    ``decide_all_fn`` returns the aggregate dict (``kernel``/``key``/
    ``decisions``); peers adopt the published set into their local cache
    before returning it."""
    dec = dispatch.shared_decision(
        outpath, primary, decide_all_fn, filename=SHARED_FILENAME,
        kernel_rev=kernel_rev, expect_key=expect_key, timeout_s=timeout_s,
        poll_s=poll_s, log=log, what="fused-norm dispatch")
    if dec.get("shared_from_primary") and dec.get("decisions") \
            and device_kind:
        adopt_decisions(dec["decisions"], device_kind, cache_dir)
    return dec


def aggregate(decisions: dict, mode: str) -> dict:
    """Roll per-workload decisions into ONE reportable verdict: ``kernel``
    is "pallas" when every site fused, "mixed" when some did, else "xla";
    ``source`` prefers "measured" over "cache" (any fresh measurement makes
    the run's evidence fresh). The per-key dict rides along for the shared
    file and the telemetry detail."""
    n = len(decisions)
    fused = sum(1 for d in decisions.values() if d.get("kernel") == "pallas")
    if n and fused == n:
        kernel = "pallas"
    elif fused:
        kernel = "mixed"
    else:
        kernel = "xla"
    sources = {d.get("source") for d in decisions.values()}
    source = ("measured" if "measured" in sources
              else "cache" if "cache" in sources
              else next(iter(sources), "platform"))
    out = {"kernel": kernel, "mode": mode, "source": source,
           "n_sites": n, "n_fused": fused, "decisions": decisions}
    revs = {d.get("kernel_rev") for d in decisions.values()
            if d.get("kernel_rev") is not None}
    if len(revs) == 1:
        out["kernel_rev"] = revs.pop()
    return out


def event_fields(decision: dict) -> dict:
    """The aggregate decision as telemetry-event fields (type
    ``fused_norm_dispatch``, schema in tpudist/telemetry.py) so
    ``summarize`` can print the fused-norm dispatch line without re-reading
    any cache."""
    out = {"kernel": decision["kernel"], "mode": decision["mode"],
           "source": decision["source"]}
    for f in ("n_sites", "n_fused"):
        if isinstance(decision.get(f), (int, float)):
            out[f] = decision[f]
    if decision.get("reason"):
        out["reason"] = decision["reason"]
    if decision.get("shared_from_primary"):
        out["shared_from_primary"] = 1
    decs = decision.get("decisions") or {}
    if decs:
        out["detail"] = "; ".join(
            f"{k}={d.get('kernel')}"
            + (f" ({d['pallas_ms']:.3f} vs {d['xla_ms']:.3f} ms)"
               if isinstance(d.get("pallas_ms"), (int, float))
               and isinstance(d.get("xla_ms"), (int, float)) else "")
            for k, d in sorted(decs.items()))[:2000]
    return out
