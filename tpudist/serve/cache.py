"""Persistent XLA compilation cache configuration (serving AND training).

Compiling the train step takes tens of seconds — fatal for autoscaling a
serving replica under load, and re-paid in full by every elastic
reform/restart of the trainer. jax already ships the fix (a
content-addressed on-disk executable cache); this module is the repo's ONE
resolver for where it lives, so the trainer, the serve engine, ``bench.py``,
``chip_smoke.py`` and the tests all agree:

- ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from outside and
  jax's own reading of that variable IS the configuration. This module
  then changes no jax setting; ``--compile-cache`` /
  ``TPUDIST_COMPILE_CACHE`` are ignored (with a log line).
- unset: the cache is ON at the explicit flag, else
  ``TPUDIST_COMPILE_CACHE``, else the fixed ``<checkout>/.jax_cache``
  (git-ignored). The path is part of jax's cache key, so it is never
  derived from a pid, uid, time or temp dir — a directory that moves never
  hits. The min-compile-time floor is dropped to 0 so every bucket
  executable persists (the default 1 s floor would silently skip exactly
  the small eval-mode programs a serving bucket set is made of).
- provenance is reported (``"warm"`` = the dir already held entries,
  ``"cold"`` = first fill) and stamped on telemetry ``compile`` events and
  the ``serve_start`` event, so ``summarize`` and the warm-vs-cold startup
  measurement can attribute where the compile seconds went.

Deliberately NOT the run dir (``--overwrite delete`` would discard the
warm cache the next replica needs). docs/SERVING.md covers format and
invalidation.
"""

from __future__ import annotations

import os

ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"
ENV_COMPILE_CACHE = "TPUDIST_COMPILE_CACHE"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(explicit: str = "") -> str:
    """The persistent-cache dir: ``JAX_COMPILATION_CACHE_DIR`` when set
    (placed from outside — it outranks everything), else the explicit
    flag, else ``TPUDIST_COMPILE_CACHE``, else ``<checkout>/.jax_cache``."""
    return (os.environ.get(ENV_JAX_CACHE, "") or explicit
            or os.environ.get(ENV_COMPILE_CACHE, "") or DEFAULT_CACHE_DIR)


def cache_state(cache_dir: str) -> str:
    """``"warm"`` when the dir already holds cache entries, else
    ``"cold"``. A heuristic by necessity (jax exposes no per-compile
    hit/miss API), but an honest one: a warm dir's entries are exactly
    what the next AOT pass will be served from, and the measured
    ``aot_compile_s`` beside it is the ground truth."""
    try:
        return "warm" if any(os.scandir(cache_dir)) else "cold"
    except OSError:
        return "cold"


def configure_compile_cache(explicit: str = "", log=None) -> tuple[str, str]:
    """Turn the persistent compilation cache on at ``resolve_cache_dir``
    (process-global, like the cache itself) and return ``(dir, state)`` —
    the provenance (``"warm"``/``"cold"``) BEFORE this process adds
    entries. With ``JAX_COMPILATION_CACHE_DIR`` set no jax setting is
    touched: jax already reads that variable itself.

    Imports jax lazily so the launcher-side consumers of serve config
    parsing stay jax-free."""
    cache_dir = resolve_cache_dir(explicit)
    os.makedirs(cache_dir, exist_ok=True)
    state = cache_state(cache_dir)
    if os.environ.get(ENV_JAX_CACHE, ""):
        ignored = explicit or os.environ.get(ENV_COMPILE_CACHE, "")
        if ignored and ignored != cache_dir and log is not None:
            log(f"=> {ENV_JAX_CACHE}={cache_dir} is set: ignoring "
                f"--compile-cache/{ENV_COMPILE_CACHE} ({ignored})")
        return cache_dir, state
    import jax
    changed = jax.config.jax_compilation_cache_dir != cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if changed:
        # jax initializes its on-disk cache object at most once per
        # process: a config update AFTER the first compile would silently
        # keep writing to the old dir. reset_cache() returns it to the
        # uninitialized state so the next compile binds the new dir.
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return cache_dir, state
