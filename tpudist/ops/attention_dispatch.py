"""Measurement-honest attention-kernel dispatch (``--flash auto``) — a thin
client of the generic dispatch layer (``tpudist/ops/dispatch``).

An earlier revision of the hand-written Pallas flash kernel *lost* to plain
XLA attention in training while ``--flash auto`` still selected it on TPU —
default ViT training was slower than if the kernel didn't exist. The root failure wasn't the kernel; it was *auto deciding
without a measurement*. PR 5 made the decision empirical; PR 6 hoisted the
machinery (cache, timing harness, never-pick-a-loser invariant, multi-host
shared verdict) into ``ops/dispatch`` so a second client
(``ops/comm_dispatch``) rides the SAME policy instead of a drifting copy.

What stays attention-specific here — and ONLY this:

- the workload identity (``shape_key``: batch, seq, heads, head_dim, dtype,
  train-vs-eval, causal);
- static eligibility (``flash_eligible``: the windowed-attention families'
  additive bias, head_dim/seq tiling limits);
- the on-device micro-benchmark (``measure_attention``: flash vs XLA
  attention, fwd or fwd+bwd, at the exact shape, through the model's own
  call ``ring_attention.qkv_attention``);
- the kernel revision (``flash_attention.KERNEL_REV``, imported lazily so
  the XLA-only path never drags Pallas in);
- the telemetry-event projection (``event_fields``).

Everything else — ``decide``/``lookup``/``shared_decision``/cache
round-trips — delegates to the generic layer with ``names=("flash",
"xla")``, which keeps this module's decision dicts, cache files
(``attention_dispatch.<kind>.json``) and shared-verdict file
(``attention_dispatch.json``) byte-compatible with PR 5's.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

from tpudist.ops import dispatch

CLIENT = "attention_dispatch"
NAMES = ("flash", "xla")
# what the event says of each attention workload's programs (``program``)
PROGRAM_FIELDS = ("heads_per_program", "block_q", "block_k", "band_fill")
# and of a program under the mask of training by diffusion over blocks
MASK_FIELDS = ("mask", "block_length")

# Re-exported so existing callers (bench_flash's timing rows, tests, tools)
# keep ONE surface; these ARE the generic layer's objects — no copies.
MODES = dispatch.MODES
ENV_CACHE_DIR = dispatch.ENV_CACHE_DIR
CACHE_VERSION = dispatch.CACHE_VERSION
default_cache_dir = dispatch.default_cache_dir
load_cache = dispatch.load_cache
save_cache = dispatch.save_cache
measure_ms = dispatch.measure_ms

cache_path = partial(dispatch.cache_path, CLIENT)
clear_cache = partial(dispatch.clear_cache, CLIENT)


def shape_key(batch: int, seq: int, heads: int, head_dim: int, dtype,
              train: bool, causal: bool, kv_heads: Optional[int] = None,
              window: Optional[int] = None,
              block_diffusion: Optional[tuple] = None) -> str:
    """The dispatch identity: the exact attention workload. ``dtype`` may be
    a jnp/numpy dtype, scalar type, or string — normalized to the canonical
    dtype name so every spelling of bfloat16 keys the same cache entry.
    Fewer key-value heads than query heads and a window are part of the
    identity (``_kv4``, ``_w1024`` after the head count and the mask), and
    so is the mask of training by diffusion over blocks (``bd4`` for blocks
    of 4 where ``causal`` / ``full`` stands; ``seq`` is then the doubled
    row's); a workload with none of them keeps the key it always had."""
    try:
        import numpy as np
        name = np.dtype(dtype).name
    except TypeError:
        name = getattr(dtype, "name", None) or str(dtype)
    grouped = f"_kv{kv_heads}" if kv_heads not in (None, heads) else ""
    mask = (f"bd{block_diffusion[1]}" if block_diffusion is not None
            else "causal" if causal else "full")
    return (f"b{batch}_t{seq}_h{heads}{grouped}_d{head_dim}_{name}_"
            f"{'train' if train else 'eval'}_{mask}"
            + (f"_w{window}" if window is not None else ""))


def kernel_rev() -> int:
    """The flash kernel's revision stamp — imported lazily so the cache /
    decision plumbing never drags Pallas in on the XLA-only path."""
    from tpudist.ops.pallas.flash_attention import KERNEL_REV
    return KERNEL_REV


def flash_eligible(*, seq: int, head_dim: int, bias: bool = False,
                   dtype=None) -> tuple[bool, str]:
    """Central static-eligibility check, consulted by every attention call
    site BEFORE any dispatch question is asked. The windowed-attention
    families (swin, maxvit) carry an additive relative-position bias (and
    swin-v2 cosine attention) the Pallas kernel does not implement — for
    them eligibility is statically False and the XLA path IS the dispatched
    choice, recorded here in one place instead of five model files."""
    if bias:
        return False, ("additive attention bias is not implemented by the "
                       "flash kernel")
    if head_dim > 256:
        return False, f"head_dim {head_dim} exceeds the kernel's VMEM tiling"
    if seq < 16:
        return False, (f"seq {seq} is below one (8,128) tile — blockwise "
                       f"streaming cannot win")
    return True, "eligible"


def probe_fns(train: bool, causal: bool):
    """The probe's two timed functions of one fused projection
    [B, T, H, 3, D]: ``qkv_attention`` with ``flash`` on and off — the
    very call ``MultiHeadAttention`` makes, so the verdict is about the
    program the model runs (the kernel's in-place read on one side, the
    slices XLA pays on the other). ``train`` differentiates with respect to
    the projection, as ``in_proj``'s backward does."""
    import jax
    import jax.numpy as jnp

    from tpudist.parallel.ring_attention import qkv_attention

    def fn(flash: bool):
        def f(qkv):
            return qkv_attention(qkv, causal=causal, flash=flash)
        if not train:
            return f
        return jax.grad(lambda qkv: f(qkv).astype(jnp.float32).sum())
    return fn(True), fn(False)


def measure_attention(batch: int, seq: int, heads: int, head_dim: int,
                      dtype, train: bool, causal: bool,
                      steps: int = 10, warmup: int = 2) -> tuple[float, float]:
    """The on-device micro-benchmark: (flash_ms, xla_ms) at the exact shape,
    both sides from one fused projection (``probe_fns``). ``train`` times
    forward+backward (the configuration the r3 capture showed the kernel
    losing); eval times forward only. Only meaningful on an accelerator —
    callers gate on platform."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    qkv = jnp.asarray(
        rng.standard_normal((batch, seq, heads, 3, head_dim)), dtype)
    flash_fn, xla_fn = probe_fns(train, causal)
    flash_ms = measure_ms(jax.jit(flash_fn), (qkv,), steps, warmup)
    xla_ms = measure_ms(jax.jit(xla_fn), (qkv,), steps, warmup)
    return flash_ms, xla_ms


def schedule(seq: int, heads: int, head_dim: int, dtype) -> str:
    """Which of the kernel's schedules (``whole_seq`` | ``streaming``) this
    self-attention shape takes — the kernel's own shape test, for the
    dispatch log line and telemetry event. Imports Pallas: ask only where
    the kernel runs."""
    from tpudist.ops.pallas.flash_attention import schedule_for
    return schedule_for(seq, heads, head_dim, dtype)


def program(seq: int, heads: int, head_dim: int, dtype, **shape) -> dict:
    """How far the kernel engages at a static self-attention shape
    (``flash_attention.program_plan``: its ``schedule``, the query heads a
    program holds, its blocks, ``band_fill`` = scores the mask allows over
    scores the programs run) — for the dispatch log line and telemetry
    event. Imports Pallas: ask only where the kernel runs."""
    from tpudist.ops.pallas.flash_attention import program_plan
    return program_plan(seq, heads, head_dim, dtype, **shape)


def decide(batch: int, seq: int, heads: int, head_dim: int, dtype,
           *, train: bool = True, causal: bool = False, mode: str = "auto",
           cache_dir: Optional[str] = None,
           measure_pair: Optional[Callable[[], tuple[float, float]]] = None,
           refresh: bool = False, platform: Optional[str] = None,
           device_kind: Optional[str] = None) -> dict:
    """Resolve the attention backend for one workload through the generic
    honesty policy (``dispatch.decide``). Returns a decision dict:
    ``kernel`` ("flash"|"xla"), ``mode``, ``source`` ("forced" | "platform"
    | "ineligible" | "cache" | "measured"), timings/margin when measured,
    and cache provenance. ``measure_pair`` injects the benchmark (tests use
    synthetic timings; bench_flash reuses its own measured rows); default
    is ``measure_attention`` at the given shape."""
    if mode not in MODES:
        raise ValueError(f"flash mode must be one of {MODES}, got {mode!r}")
    key = shape_key(batch, seq, heads, head_dim, dtype, train, causal)
    if measure_pair is None:
        measure_pair = lambda: measure_attention(  # noqa: E731
            batch, seq, heads, head_dim, dtype, train, causal)
    return dispatch.decide(
        CLIENT, key, mode=mode, names=NAMES, kernel_rev=kernel_rev,
        measure_pair=measure_pair,
        eligibility=flash_eligible(seq=seq, head_dim=head_dim),
        cache_dir=cache_dir, refresh=refresh, platform=platform,
        device_kind=device_kind)


def lookup(batch: int, seq: int, heads: int, head_dim: int, dtype,
           *, train: bool = True, causal: bool = False,
           cache_dir: Optional[str] = None,
           platform: Optional[str] = None,
           device_kind: Optional[str] = None) -> bool:
    """Trace-safe resolution for model call sites (``flash=None``): the
    generic ``dispatch.lookup`` (cache/platform only, never measures) behind
    the attention eligibility gate."""
    if not flash_eligible(seq=seq, head_dim=head_dim)[0]:
        return False
    key = shape_key(batch, seq, heads, head_dim, dtype, train, causal)
    return dispatch.lookup(CLIENT, key, candidate="flash",
                           kernel_rev=kernel_rev, cache_dir=cache_dir,
                           platform=platform, device_kind=device_kind)


def shared_decision(outpath: str, primary: bool, decide_fn,
                    *, expect_key: Optional[str] = None,
                    timeout_s: float = 300.0, poll_s: float = 0.25,
                    log=None) -> dict:
    """One attention verdict for the whole gang (``attention_dispatch.json``
    in the shared run dir) — the generic ``dispatch.shared_decision`` with
    this client's file name and kernel revision; see that docstring for the
    staleness/failure-propagation contract."""
    return dispatch.shared_decision(
        outpath, primary, decide_fn, filename="attention_dispatch.json",
        kernel_rev=kernel_rev, expect_key=expect_key, timeout_s=timeout_s,
        poll_s=poll_s, log=log, what="attention dispatch")


def event_fields(decision: dict) -> dict:
    """The decision as telemetry-event fields (type ``attention_dispatch``,
    schema in tpudist/telemetry.py). Numeric-or-None timings; the winner,
    mode, provenance, shape key, and measured margin all ride along so
    ``summarize`` can print the dispatch line without re-reading the
    cache."""
    out = {"kernel": decision["kernel"], "mode": decision["mode"],
           "source": decision["source"], "shape_key": decision.get("key")}
    for f in ("flash_ms", "xla_ms", "margin"):
        if isinstance(decision.get(f), (int, float)):
            out[f] = decision[f]
    if decision.get("schedule"):
        out["schedule"] = decision["schedule"]
    if decision.get("programs"):
        # one entry an attention workload, in the shape keys' order
        for f in PROGRAM_FIELDS:
            out[f] = [p[f] for p in decision["programs"]]
        for f in MASK_FIELDS:       # where a program's mask is of its own kind
            if any(f in p for p in decision["programs"]):
                out[f] = [p.get(f) for p in decision["programs"]]
    if decision.get("cache_hit"):
        out["cache_hit"] = 1
    if decision.get("reason"):
        out["reason"] = decision["reason"]
    if decision.get("shared_from_primary"):
        out["shared_from_primary"] = 1
    if decision.get("device_kind"):
        out["dispatch_device_kind"] = decision["device_kind"]
    return out
