"""``ServeEngine`` — AOT-compiled bucket set + zero-recompile inference.

The cold-start kill (ISSUE 14 tentpole (c)): a serving replica's startup
cost is the XLA compilation of its bucket set, 25-45 s per program on the
bench rows. The engine attacks it twice:

1. **AOT, up front**: every bucket shape is compiled at construction
   (``jit(step).lower(vars, spec).compile()``) instead of lazily on the
   first request of each size — the replica is either NOT serving or
   serving at full speed, never limping through a compile storm under
   live traffic.
2. **Persistent cache underneath** (``serve/cache.py``): the AOT pass is
   backed by ``jax_compilation_cache_dir``, so a scaled-up replica (the
   launcher's ``--scale-up`` path) or a restarted one pays cache-hit
   deserialization instead of compilation. The engine measures and emits
   both ``aot_s`` (trace+lower+compile wall) and ``aot_compile_s`` (the
   ``.compile()`` slice — the part the cache accelerates; tracing cost is
   cache-immune), plus warm/cold provenance, so the cold-start claim is a
   number in the telemetry stream, not an adjective.

Zero recompiles are STRUCTURAL: steady-state inference calls the
already-compiled executables directly (``self._compiled[bucket]``), and a
compiled executable cannot retrace or recompile — a shape outside the
bucket set is chunked/padded into it by construction. The telemetry proof:
a serving run's compile-event stream holds exactly ``len(buckets)`` events,
all phase ``serve_aot`` (asserted in ``tests/test_serve.py`` over a
mixed-size request stream).
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import numpy as np

from tpudist.serve.batching import pad_to_bucket, pick_bucket
from tpudist.serve.export import make_infer_step


class ServeEngine:
    """Compiled eval-mode inference over a fixed bucket set.

    ``infer(images)`` accepts any row count: it chunks to the largest
    bucket, pads each chunk to its bucket shape, runs the chunk's
    AOT-compiled executable, and returns the valid rows' logits as one
    float32 array. ``last_info`` describes the bucket calls the most
    recent ``infer`` made (the batcher's ``serve_batch`` event source).
    """

    def __init__(self, model, variables: dict, *, image_size: int,
                 buckets: Sequence[int] = (1, 2, 4, 8), channels: int = 3,
                 telemetry=None, cache: str = "off", log=None):
        self.model = model
        self.variables = variables
        self.image_size = int(image_size)
        self.channels = int(channels)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] <= 0:
            raise ValueError(f"buckets must be positive, got {buckets!r}")
        self.telemetry = telemetry
        self.cache = cache                  # "warm" | "cold" | "off"
        self._log = log
        self._step = make_infer_step(model)
        self._compiled: dict[int, object] = {}
        self.aot_s = 0.0                    # trace + lower + compile wall
        self.aot_compile_s = 0.0            # the .compile() slice alone —
        #                                     what the persistent cache
        #                                     accelerates (tracing is not
        #                                     cacheable)
        self.last_info: list[dict] = []
        self._warmup()

    # -- AOT bucket compilation --------------------------------------------
    def _warmup(self) -> None:
        tel = self.telemetry
        if tel is not None and self.cache != "off":
            # Tag every compile event with the persistent-cache provenance
            # (the same field the trainer's --compile-cache stamps).
            tel.compile_cache = self.cache
        t_all = time.perf_counter()
        for b in self.buckets:
            spec = jax.ShapeDtypeStruct(
                (b, self.image_size, self.image_size, self.channels),
                jax.numpy.float32)
            t0 = time.perf_counter()
            lowered = self._step.lower(self.variables, spec)
            t1 = time.perf_counter()
            self._compiled[b] = lowered.compile()
            t2 = time.perf_counter()
            self.aot_compile_s += t2 - t1
            if tel is not None:
                tel.note_compile(t2 - t0, phase="serve_aot", bucket=b)
        self.aot_s = time.perf_counter() - t_all
        if self._log is not None:
            self._log(f"=> serve AOT: {len(self.buckets)} bucket programs "
                      f"{list(self.buckets)} in {self.aot_s:.2f}s "
                      f"(XLA compile {self.aot_compile_s:.2f}s, "
                      f"persistent cache {self.cache})")
        if tel is not None:
            tel.emit("serve_start", n_buckets=len(self.buckets),
                     aot_s=round(self.aot_s, 6),
                     aot_compile_s=round(self.aot_compile_s, 6),
                     cache=self.cache,
                     buckets=",".join(str(b) for b in self.buckets),
                     image_size=self.image_size, arch=type(self.model).__name__)

    # -- steady-state inference --------------------------------------------
    def infer(self, images: np.ndarray) -> np.ndarray:
        """Logits for ``images`` (``(n, H, W, C)`` float32, any n ≥ 1),
        served exclusively from the AOT bucket executables. Blocks until
        the result is host-resident (serving latency must be a real
        number, not an enqueue ack)."""
        images = np.asarray(images, dtype=np.float32)
        n = images.shape[0]
        if n < 1:
            raise ValueError("infer needs at least one row")
        max_b = self.buckets[-1]
        outs: list[np.ndarray] = []
        info: list[dict] = []
        i = 0
        while i < n:
            chunk = images[i:i + max_b]
            valid = chunk.shape[0]
            bucket = pick_bucket(valid, self.buckets)
            padded = pad_to_bucket(chunk, bucket)
            t0 = time.perf_counter()
            logits = self._compiled[bucket](self.variables, padded)
            host = np.asarray(logits)       # forces completion
            info.append({"bucket": bucket, "n_valid": valid,
                         "seconds": time.perf_counter() - t0})
            outs.append(host[:valid])
            i += valid
        self.last_info = info
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    # -- introspection ------------------------------------------------------
    def compiled_buckets(self) -> tuple[int, ...]:
        return tuple(sorted(self._compiled))
