"""Measurement-honest kernel dispatch — the GENERIC layer.

PR 5 built this machinery for one client (``--flash auto``,
``ops/attention_dispatch``); a second needs the identical policy, and
duplicating the cache/timing/shared-verdict logic would let the two honesty
policies drift. So the policy lives HERE, once, and each family registers
as a *client*:

- **attention** (``ops/attention_dispatch``): Pallas flash attention vs XLA
  attention, keyed by the exact attention workload;
- **comm** (``ops/comm_dispatch``): the int8 gradient exchange vs the dense
  pmean, keyed by the gradient's element count and the data axis's size.

One timing harness, one cache format, one honesty policy:

- ``decide()`` resolves ``auto`` by a one-time on-device micro-benchmark of
  candidate-vs-baseline **at the exact workload key**, picks the winner,
  and **never selects a kernel that loses its own measurement** (ties go to
  the baseline — the compiler needs no justification, the custom kernel
  does).
- verdicts cache in a per-``device_kind`` JSON file per client
  (``<client>.<kind>.json`` — a v4 verdict must never dispatch a v5e),
  keyed by the workload key AND the client's kernel revision, so a rebuilt
  kernel re-measures instead of inheriting the old kernel's record.
  ``clear_cache()`` / deleting the file forces a re-measure.
- off-TPU, ``auto`` resolves to the baseline immediately — no Pallas
  import, no measurement (interpreter-mode timings are meaningless).
- ``lookup()`` is the trace-safe path (cache/platform only, never
  measures): no cache entry on TPU → baseline — an unmeasured custom
  kernel is never the default.
- ``shared_decision()`` gives a multi-host gang ONE verdict (the primary
  publishes into the shared run dir; peers read a fresh, matching file or
  fail over identically).

The micro-benchmark is injectable (``measure_pair``) so every honesty
property is unit-testable with synthetic timings on CPU.
"""

from __future__ import annotations

import datetime
import json
import os
import re
import time
from typing import Callable, Optional

MODES = ("auto", "on", "off")

ENV_CACHE_DIR = "TPUDIST_DISPATCH_CACHE"
CACHE_VERSION = 1


def default_cache_dir() -> str:
    """Where dispatch verdicts persist across runs: ``TPUDIST_DISPATCH_CACHE``
    or ``~/.cache/tpudist``. Deliberately NOT the run dir — ``--overwrite
    delete`` would discard the measurement the next run needs."""
    env = os.environ.get(ENV_CACHE_DIR, "")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "tpudist")


def _slug(device_kind: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "-", device_kind.strip()) or "unknown"


def cache_path(client: str, device_kind: str,
               cache_dir: Optional[str] = None) -> str:
    """One JSON file per client per device kind: ``<client>.<kind>.json``."""
    return os.path.join(cache_dir or default_cache_dir(),
                        f"{client}.{_slug(device_kind)}.json")


def load_cache(path: str) -> dict:
    """Cache file contents ({} shell on missing/corrupt — a torn write must
    degrade to a re-measure, never crash a training run)."""
    try:
        with open(path) as f:
            obj = json.load(f)
        if isinstance(obj, dict) and obj.get("version") == CACHE_VERSION \
                and isinstance(obj.get("entries"), dict):
            return obj
    except (OSError, ValueError):
        pass
    return {"version": CACHE_VERSION, "entries": {}}


def save_cache(path: str, cache: dict) -> None:
    """Atomic write (tmp + rename): a preempted rank mid-save must not leave
    a torn JSON that poisons every later run's load."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def clear_cache(client: str, device_kind: Optional[str] = None,
                cache_dir: Optional[str] = None) -> int:
    """Drop one client's cached verdicts (all device kinds, or one). Returns
    the number of cache files removed — the documented invalidation path
    alongside the automatic kernel-revision mismatch."""
    d = cache_dir or default_cache_dir()
    removed = 0
    if device_kind is not None:
        paths = [cache_path(client, device_kind, d)]
    else:
        try:
            paths = [os.path.join(d, n) for n in os.listdir(d)
                     if n.startswith(f"{client}.") and n.endswith(".json")]
        except OSError:
            paths = []
    for p in paths:
        try:
            os.remove(p)
            removed += 1
        except OSError:
            pass
    return removed


def measure_ms(fn, args, steps: int = 10, warmup: int = 2) -> float:
    """THE on-device timing harness (mean ms/call over ``steps`` after
    ``warmup``), shared by every dispatch client AND the kernel benchmarks
    (``benchmarks/bench_flash.py``, ``bench_comm.py``) so verdicts and bench
    rows cannot drift in methodology. Completion is forced via
    ``device_get`` of a value depending on the full computation."""
    import jax
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    return (time.perf_counter() - t0) / steps * 1e3


def _now_iso() -> str:
    return datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")


def decide(client: str, key: str, *, mode: str,
           names: tuple[str, str],
           kernel_rev: Callable[[], int],
           measure_pair: Callable[[], tuple[float, float]],
           eligibility: Optional[tuple[bool, str]] = None,
           cache_dir: Optional[str] = None, refresh: bool = False,
           platform: Optional[str] = None,
           device_kind: Optional[str] = None) -> dict:
    """Resolve one workload for one client. ``names = (candidate,
    baseline)`` labels the two sides: the decision dict carries ``kernel``
    (one of the names), ``mode``, ``source`` ("forced" | "platform" |
    "ineligible" | "cache" | "measured"), ``<candidate>_ms``/
    ``<baseline>_ms``/``margin`` when measured, and cache provenance.

    THE honesty invariant: under ``auto`` the candidate kernel is selected
    ONLY off the back of a measurement it won (fresh, or cached for this
    device_kind + key + kernel rev). ``measure_pair`` returns
    ``(candidate_ms, baseline_ms)``; ``kernel_rev`` is a CALLABLE so the
    revision import (which may drag Pallas in) only happens on the TPU
    path. ``eligibility`` is the client's static pre-check — a workload the
    kernel cannot run resolves to the baseline before any device question
    is asked (forced ``on`` deliberately bypasses it, for A/B work).
    """
    if mode not in MODES:
        raise ValueError(f"{client} mode must be one of {MODES}, got "
                         f"{mode!r}")
    cand, base = names
    out = {"kernel": base, "mode": mode, "source": "platform", "key": key,
           f"{cand}_ms": None, f"{base}_ms": None, "margin": None,
           "cache_hit": False}

    if mode in ("on", "off"):
        out["kernel"] = cand if mode == "on" else base
        out["source"] = "forced"
        return out

    # Static eligibility BEFORE anything touches a device: a workload the
    # kernel cannot run must not reach measure_pair (where the Pallas probe
    # would just crash) — `auto` resolves it to the baseline outright.
    if eligibility is not None and not eligibility[0]:
        out["source"] = "ineligible"
        out["reason"] = eligibility[1]
        return out

    if platform is None:
        import jax
        platform = jax.default_backend()
    out["platform"] = platform
    if platform != "tpu":
        # auto off-TPU IS the baseline path: no Pallas import, no
        # measurement — interpreter timings would be noise dressed as data.
        return out

    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    out["device_kind"] = device_kind
    rev = kernel_rev()
    out["kernel_rev"] = rev
    path = cache_path(client, device_kind, cache_dir)
    out["cache_path"] = path
    cache = load_cache(path)
    entry = cache["entries"].get(key)
    if entry and entry.get("kernel_rev") == rev and not refresh:
        out.update(kernel=entry["kernel"], source="cache", cache_hit=True,
                   margin=entry.get("margin"),
                   measured_at=entry.get("measured_at"))
        out[f"{cand}_ms"] = entry.get(f"{cand}_ms")
        out[f"{base}_ms"] = entry.get(f"{base}_ms")
        return out

    cand_ms, base_ms = measure_pair()
    # Strict win required: a tie keeps the compiler baseline. The custom
    # kernel must EARN dispatch; the baseline never has to.
    winner = cand if cand_ms < base_ms else base
    loser_ms = max(cand_ms, base_ms)
    margin = (loser_ms - min(cand_ms, base_ms)) / loser_ms if loser_ms \
        else 0.0
    out.update(kernel=winner, source="measured", margin=round(margin, 4),
               measured_at=_now_iso())
    out[f"{cand}_ms"] = round(cand_ms, 4)
    out[f"{base}_ms"] = round(base_ms, 4)
    cache["device_kind"] = device_kind
    cache["entries"][key] = {
        "kernel": winner, f"{cand}_ms": out[f"{cand}_ms"],
        f"{base}_ms": out[f"{base}_ms"], "margin": out["margin"],
        "kernel_rev": rev, "measured_at": out["measured_at"],
    }
    try:
        save_cache(path, cache)
    except OSError:
        # A read-only cache dir degrades to re-measuring next run, but the
        # decision itself stands: the caller builds its program from it.
        out["cache_path"] = None
    return out


def lookup(client: str, key: str, *, candidate: str,
           kernel_rev: Callable[[], int],
           cache_dir: Optional[str] = None,
           platform: Optional[str] = None,
           device_kind: Optional[str] = None) -> bool:
    """Trace-safe resolution for model call sites: consults platform + cache
    only, NEVER measures (a micro-benchmark cannot run while the step is
    being traced). No cache entry on TPU → False: an unmeasured custom
    kernel is never the default — the Trainer (or a bench) warms the cache
    for the workloads it runs by calling ``decide()`` outside the trace."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    if platform != "tpu":
        return False
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    path = cache_path(client, device_kind, cache_dir)
    entry = load_cache(path)["entries"].get(key)
    return bool(entry and entry.get("kernel_rev") == kernel_rev()
                and entry.get("kernel") == candidate)


def shared_decision(outpath: str, primary: bool, decide_fn,
                    *, filename: str,
                    kernel_rev: Callable[[], int],
                    expect_key: Optional[str] = None,
                    timeout_s: float = 300.0, poll_s: float = 0.25,
                    log=None, what: str = "dispatch") -> dict:
    """One decision for the whole gang. A per-rank micro-benchmark is noisy:
    at a near-tie workload, hosts could measure opposite winners and compile
    DIFFERENT kernels into one SPMD program — non-reproducible trajectories,
    divergent per-rank grads. So the primary rank decides and publishes
    ``<filename>`` into the (shared-filesystem) run dir; every other rank
    reads that instead of measuring.

    The run dir can carry a decision file from a previous attempt or run
    (``--overwrite keep`` + restart, possibly across a kernel-rev bump), so
    peers only adopt a file stamped with THEIR launcher attempt
    (``telemetry.env_attempt``) whose workload key and kernel rev still
    match — anything else is treated as absent until the live primary
    overwrites it. A primary whose probe raises publishes the failure
    instead, so peers fail over immediately and *identically* (every rank
    degrades to the caller's trace-safe-lookup path) rather than burning
    the full timeout and then measuring into a possibly-split gang. A
    non-primary rank that times out (primary still compiling) falls back
    to its own decision — logged loudly, because the
    gang may now be split.
    """
    from tpudist.telemetry import env_attempt
    attempt = env_attempt()
    path = os.path.join(outpath, filename)

    def _publish(obj: dict) -> None:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=1)
        os.replace(tmp, path)

    if primary:
        try:
            dec = decide_fn()
        except Exception as e:
            try:
                _publish({"failed": repr(e)[:500], "key": expect_key,
                          "attempt": attempt})
            except OSError:
                pass
            raise
        try:
            _publish(dict(dec, attempt=attempt))
        except OSError as e:
            if log is not None:
                log(f"{what}: could not publish decision ({e!r}) — peers "
                    f"will decide independently")
        return dec

    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            with open(path) as f:
                dec = json.load(f)
        except (OSError, ValueError):
            dec = None
        fresh = (isinstance(dec, dict)
                 and dec.get("attempt") == attempt
                 and (expect_key is None or dec.get("key") == expect_key)
                 and ("kernel_rev" not in dec
                      or dec["kernel_rev"] == kernel_rev()))
        if fresh:
            if dec.get("failed"):
                raise RuntimeError(
                    f"primary's {what} probe failed: {dec['failed']}")
            if dec.get("kernel"):
                dec["shared_from_primary"] = 1
                return dec
        time.sleep(poll_s)
    if log is not None:
        log(f"{what}: primary's decision file did not appear within "
            f"{timeout_s:.0f}s — deciding independently (gang may mix "
            f"kernels this run)")
    return decide_fn()
