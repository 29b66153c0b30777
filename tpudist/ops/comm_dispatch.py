"""Measurement-honest gradient-compression dispatch (``--compress-grads``)
— the second client of the generic dispatch layer (``tpudist/ops/dispatch``),
beside attention.

The candidate here is not a Pallas kernel but a COLLECTIVE ALGORITHM
(``parallel/comm.py``: int8 two-phase all-reduce with error feedback), so
the dispatch question is different in kind: the quantize/dequantize
arithmetic is pure VPU work that trades compute for interconnect bytes,
and whether that trade wins depends on the fabric (ICI generation, slice
size) and the gradient size — exactly the per-workload, per-device_kind
question the honesty layer answers. The same policy applies unchanged:

- ``auto`` selects int8 ONLY off the back of a measurement it won at the
  exact workload key (total gradient element count × data-axis size ×
  chunk), cached per device_kind in ``comm.<kind>.json``, invalidated by
  ``COMM_REV`` (the wire-format revision). Ties and losses keep the dense
  pmean — the compiler's collective needs no justification.
- off-TPU ``auto`` resolves to dense without measuring: CPU-sim collective
  timings say nothing about ICI. (Forced ``int8`` still works anywhere —
  the algorithm is plain jnp — which is what the CPU parity tests and the
  ≥2-device census acceptance run.)
- multi-host gangs get ONE verdict via ``shared_decision``
  (``comm_dispatch.json`` in the run dir): a near-tie must not compile a
  quantized exchange on one host and a dense pmean on another into the
  same SPMD program.

The A/B measured is the REAL exchange at the real size over the real mesh
(``build_measure_fns``): a jitted shard_map running dense ``lax.pmean``
vs the compressed twin on a synthetic flat gradient of the model's exact
element count — one timing harness (``dispatch.measure_ms``) shared with
``benchmarks/bench_comm.py`` so verdicts and bench rows cannot drift.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

from tpudist.ops import dispatch

CLIENT = "comm"
NAMES = ("int8", "dense")
MODES = ("off", "int8", "auto")
SHARED_FILENAME = "comm_dispatch.json"


def kernel_rev() -> int:
    from tpudist.parallel.comm import COMM_REV
    return COMM_REV


def comm_key(n_grads: int, world: int, chunk: int) -> str:
    """The dispatch identity: the exact reduction workload — total gradient
    element count (f32 master grads), data-axis size, quantization chunk."""
    return f"n{n_grads}_w{world}_c{chunk}"


def comm_eligible(*, n_grads: int, world: int) -> tuple[bool, str]:
    """Static eligibility: a reduction that moves no bytes across ranks can
    never win (and the exchange itself is undefined at world 1)."""
    if world < 2:
        return False, (f"data-axis size {world}: nothing crosses the "
                       f"interconnect, compression cannot win")
    if n_grads < 1:
        return False, "empty gradient"
    return True, "eligible"


cache_path = partial(dispatch.cache_path, CLIENT)
clear_cache = partial(dispatch.clear_cache, CLIENT)


def build_measure_fns(n_grads: int, mesh, data_axis: str, chunk: int):
    """``(int8_fn, dense_fn, args)`` — each a jitted shard_map reducing a
    synthetic flat f32 gradient of the model's exact element count over
    the real mesh. Shared with ``benchmarks/bench_comm.py`` (ONE workload
    definition, ONE timing harness)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudist.parallel.comm import compressed_pmean_flat

    world = mesh.shape[data_axis]
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((world, n_grads)), jnp.float32)
    e = jnp.zeros((world, n_grads), jnp.float32)

    def dense(gv):
        return jax.lax.pmean(gv[0], data_axis)[None]

    def int8(gv, ev):
        red, e_new = compressed_pmean_flat(gv[0], ev[0], data_axis,
                                           chunk=chunk)
        return red[None], e_new[None]

    sh = NamedSharding(mesh, P(data_axis))
    gs, es = jax.device_put(g, sh), jax.device_put(e, sh)
    dense_c = jax.jit(shard_map(dense, mesh=mesh, in_specs=(P(data_axis),),
                                out_specs=P(data_axis), check_vma=False))
    int8_c = jax.jit(shard_map(int8, mesh=mesh,
                               in_specs=(P(data_axis), P(data_axis)),
                               out_specs=(P(data_axis), P(data_axis)),
                               check_vma=False))
    return (lambda: int8_c(gs, es)), (lambda: dense_c(gs)), ()


def measure_comm(n_grads: int, mesh, data_axis: str, chunk: int,
                 steps: int = 10, warmup: int = 2) -> tuple[float, float]:
    """The on-device micro-benchmark: (int8_ms, dense_ms) for one gradient
    exchange at the exact workload. Only meaningful on an accelerator —
    callers gate on platform (the generic layer already does)."""
    int8_fn, dense_fn, args = build_measure_fns(n_grads, mesh, data_axis,
                                                chunk)
    int8_ms = dispatch.measure_ms(int8_fn, args, steps, warmup)
    dense_ms = dispatch.measure_ms(dense_fn, args, steps, warmup)
    return int8_ms, dense_ms


def decide(n_grads: int, world: int, *, mode: str, chunk: int,
           mesh=None, data_axis: str = "data",
           cache_dir: Optional[str] = None,
           measure_pair: Optional[Callable[[], tuple[float, float]]] = None,
           refresh: bool = False, platform: Optional[str] = None,
           device_kind: Optional[str] = None) -> dict:
    """Resolve ``--compress-grads`` for one reduction workload through the
    generic honesty policy. Mode mapping onto the generic layer:
    ``off``→forced dense, ``int8``→forced candidate, ``auto``→measured.
    Forced ``int8`` still refuses an ineligible workload (world < 2):
    there is nothing to exchange, so the decision must report dense —
    ``config.finalize``/the Trainer reject that combination loudly before
    it gets here."""
    if mode not in MODES:
        raise ValueError(f"--compress-grads must be one of {MODES}, got "
                         f"{mode!r}")
    key = comm_key(n_grads, world, chunk)
    ok, why = comm_eligible(n_grads=n_grads, world=world)
    if not ok:
        return {"kernel": "dense", "mode": mode, "source": "ineligible",
                "key": key, "reason": why, "int8_ms": None,
                "dense_ms": None, "margin": None, "cache_hit": False}
    generic_mode = {"off": "off", "int8": "on", "auto": "auto"}[mode]
    if measure_pair is None:
        if mesh is None and generic_mode == "auto":
            raise ValueError("auto needs the mesh (or an injected "
                             "measure_pair) to run the A/B")
        measure_pair = lambda: measure_comm(  # noqa: E731
            n_grads, mesh, data_axis, chunk)
    out = dispatch.decide(
        CLIENT, key, mode=generic_mode, names=NAMES, kernel_rev=kernel_rev,
        measure_pair=measure_pair, eligibility=(ok, why),
        cache_dir=cache_dir, refresh=refresh, platform=platform,
        device_kind=device_kind)
    out["mode"] = mode
    return out


def shared_decision(outpath: str, primary: bool, decide_fn,
                    *, expect_key: Optional[str] = None,
                    timeout_s: float = 300.0, log=None) -> dict:
    """One compressed-vs-dense verdict for the whole gang (file
    ``comm_dispatch.json`` in the shared run dir; same staleness rules as
    the other clients: attempt + key + COMM_REV must match)."""
    return dispatch.shared_decision(
        outpath, primary, decide_fn, filename=SHARED_FILENAME,
        kernel_rev=kernel_rev, expect_key=expect_key, timeout_s=timeout_s,
        log=log, what="comm dispatch")


def event_fields(decision: dict, *, world: int, n_grads: int,
                 dense_bytes: int) -> dict:
    """The decision as ``comm_dispatch`` telemetry-event fields (schema in
    tpudist/telemetry.py). ``dense_bytes`` is the dense-equivalent
    gradient payload (f32 bytes of the whole gradient tree) — the
    numerator of the compression-ratio line summarize prints against the
    census's actual collective bytes."""
    out = {"kernel": decision["kernel"], "mode": decision["mode"],
           "source": decision["source"], "world": world,
           "n_grads": n_grads, "dense_bytes": dense_bytes}
    for f in ("int8_ms", "dense_ms", "margin"):
        if isinstance(decision.get(f), (int, float)):
            out[f] = decision[f]
    if decision.get("reason"):
        out["reason"] = decision["reason"]
    if decision.get("key"):
        out["key"] = decision["key"]
    if decision.get("shared_from_primary"):
        out["shared_from_primary"] = 1
    return out
