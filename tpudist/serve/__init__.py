"""tpudist.serve — the serving plane (ISSUE 14, ROADMAP item 1).

The repo trains; production scale means inference traffic. This package
turns a trained checkpoint into a compiled eval-mode inference step and
fronts it with a continuous-batching request queue whose micro-batches are
padded to a FIXED set of bucket shapes, so steady-state traffic never
triggers an XLA recompile:

- ``serve.cache``      — the one resolver for the persistent XLA
  compilation cache (``JAX_COMPILATION_CACHE_DIR``, else
  ``--compile-cache`` / ``TPUDIST_COMPILE_CACHE``, else
  ``<checkout>/.jax_cache``), shared with the trainer: a scaled-up
  replica (or an elastic reform) pays cache-hit seconds, not a compile;
- ``serve.export``     — checkpoint → (model, variables) in eval mode
  (bf16 compute), with ``--flash`` resolved through the SAME
  measurement-honest dispatch client the trainer uses (train=False key);
- ``serve.engine``     — ``ServeEngine``: AOT-compiles the whole bucket
  set at startup (``jit(...).lower().compile()`` per bucket, cache-backed)
  and serves every request from those executables — a compiled executable
  CANNOT recompile, so the zero-recompile property is structural and the
  telemetry compile-event stream proves it (exactly ``len(buckets)``
  events, all phase ``serve_aot``);
- ``serve.batching``   — ``ContinuousBatcher`` (open-loop request queue →
  bucket-padded micro-batches, per-request latency accounting) and the
  synthetic open-loop load generator ``benchmarks/bench_serve.py`` and the
  2-replica e2e drive.

CLI: ``python -m tpudist.serve`` (see ``serve/__main__.py``);
docs: ``docs/SERVING.md``.
"""

# Lazy re-exports: importing the PACKAGE (which `import
# tpudist.serve.cache` does implicitly) must stay cheap and jax-free —
# the trainer reads cache config on every construction, and serve.cache's
# contract is that launcher-side config parsing never drags jax in. The
# engine/export/batching modules load only when their names are touched.
_EXPORTS = {
    "ContinuousBatcher": "batching", "open_loop_load": "batching",
    "pad_to_bucket": "batching", "parse_buckets": "batching",
    "pick_bucket": "batching",
    "configure_compile_cache": "cache", "resolve_cache_dir": "cache",
    "ServeEngine": "engine",
    "load_serve_state": "export", "make_infer_step": "export",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib
        mod = importlib.import_module(f"tpudist.serve.{_EXPORTS[name]}")
        return getattr(mod, name)
    raise AttributeError(f"module 'tpudist.serve' has no attribute "
                         f"{name!r}")
