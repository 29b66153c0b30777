"""Communication-efficient gradient exchange: quantized collectives with
error feedback + full weight-update sharding (ZeRO-full).

Two independent levers on what crosses the interconnect each step, both
selected per-run and both riding the repo's existing honesty machinery:

**Quantized all-reduce with error feedback** (``--compress-grads int8``,
EQuARX-style — arXiv:2506.17615). The dense gradient pmean at the DP
step's single reduction choke point is replaced by a two-phase exchange
whose every payload is int8 + per-chunk f32 scales:

1. each rank adds its error-feedback residual to its local gradient
   (``c = g + e``), splits the flat vector into one segment per rank, and
   quantizes every chunk (symmetric int8, scale = max|c|/127 per chunk);
2. ``all_to_all`` routes segment *d* to rank *d* (int8 wire format); the
   receiver dequantizes and sums — the reduce-scatter phase. The sum is
   exact in f32: no re-quantization error accumulates across hops;
3. the owner quantizes its reduced segment once and ``all_gather`` fans
   it out (int8 again) — the all-gather phase;
4. error feedback is EXACT by construction: each rank's residual absorbs
   the quantization error of what it sent (step 1), and the segment owner
   additionally books ``world ×`` the broadcast-quantization error of
   step 3 (the mean over ranks then recovers it exactly once). The
   invariant ``mean(c) == applied + mean(residual')`` holds to float
   associativity and is pinned by test.

Residuals live in ``TrainState.comm_state`` as ONE ``(world, n)`` array
sharded over the data axis — per-device cost is one f32 copy of the
gradient — and ride the topology-tagged checkpoint plane: a same-world
restore is bit-exact, a cross-world restore mean-folds the pending error
mass so no gradient signal is dropped (``elastic/reshard.py``).

**ZeRO-full weight-update sharding** (``--zero full``, Xu et al. 2020 —
arXiv:2004.13336). Past zero1 (optimizer moments sharded, GSPMD path):
params, optimizer state AND the EMA copy all shard their leading dim over
the data axis; the train step all-gathers params just-in-time before the
forward, ``psum_scatter``s gradients so each rank reduces only the shard
it owns, and computes the optimizer update on that shard alone. Per-device
state memory drops by ~the data-axis size; the gradient all-reduce becomes
reduce-scatter + all-gather at equal wire volume. The placement is the
same ``tree_shardings`` machinery zero1 uses (``zero_mode="full"``), so
the elastic reshard plane re-cuts it across world changes for free.

Both compose: under ``--zero full --compress-grads int8`` the gradient
exchange runs the quantized two-phase reduce and each rank slices its
owned rows from the reduced result locally (no extra collective).

Everything here is plain ``jnp`` — no Pallas, no custom kernels — so the
``auto`` dispatch decision (``ops/comm_dispatch``) is purely about whether
the quantize/dequantize arithmetic beats the interconnect time it saves at
this workload on this fabric.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.config import Config
from tpudist.obs import scopes

# Bumped whenever the wire format or reduction math changes: cached
# compressed-vs-dense dispatch verdicts (ops/comm_dispatch) are keyed on it
# and re-measure instead of trusting a stale record.
COMM_REV = 1

# Quantization chunk: one f32 scale per CHUNK int8 values (~1.6% overhead).
DEFAULT_CHUNK = 256


# -- quantization primitives (pure jnp; unit-testable off-device) ------------

def quantize_chunks(c: jax.Array, chunk: int = DEFAULT_CHUNK):
    """Symmetric per-chunk int8 quantization of ``c`` (..., m) with
    ``m % chunk == 0``: returns ``(q int8 (..., m//chunk, chunk),
    scale f32 (..., m//chunk))`` with ``scale = max|chunk|/127`` (an
    all-zero chunk keeps scale 0 and decodes to exact zeros)."""
    shp = c.shape
    cc = c.reshape(shp[:-1] + (shp[-1] // chunk, chunk))
    scale = jnp.max(jnp.abs(cc), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(cc / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_chunks(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_chunks``: (..., m//chunk, chunk) int8 + scales
    back to (..., m) f32."""
    out = q.astype(jnp.float32) * scale[..., None]
    return out.reshape(q.shape[:-2] + (q.shape[-2] * q.shape[-1],))


def compressed_pmean_flat(x: jax.Array, e: jax.Array, axis_name: str,
                          chunk: int = DEFAULT_CHUNK):
    """The quantized mean-all-reduce of one flat f32 vector with exact
    error feedback. ``x``/``e`` are this rank's gradient and residual
    (``(n,)`` each, any n); must run inside ``shard_map`` with
    ``axis_name`` bound. Returns ``(reduced_mean (n,), new_residual (n,))``
    — ``reduced_mean`` is identical on every rank (all ranks apply the same
    dequantized broadcast), and
    ``pmean(x + e) == reduced_mean + pmean(new_residual)`` exactly."""
    world = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    n = x.shape[0]
    seg = -(-n // (world * chunk)) * chunk       # ceil to a chunk multiple
    n_pad = world * seg
    c = jnp.zeros((n_pad,), jnp.float32).at[:n].set(
        x.astype(jnp.float32) + e)
    cs = c.reshape(world, seg)                   # row d -> rank d
    q, s = quantize_chunks(cs, chunk)            # (world, seg//chunk, chunk)
    e_new = c - dequantize_chunks(q, s).reshape(n_pad)
    # Phase 1 (reduce-scatter): int8 segments to their owners; the owner
    # dequantizes and sums in f32 — the sum itself adds no error.
    qr = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    sr = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
    red = jnp.sum(dequantize_chunks(qr, sr), axis=0) / world      # (seg,)
    # Phase 2 (all-gather): one more quantization on the reduced segment;
    # the owner books world x its error so the cross-rank mean recovers it
    # exactly once next step.
    q2, s2 = quantize_chunks(red, chunk)
    e2 = red - dequantize_chunks(q2, s2)
    e_new = e_new.reshape(world, seg).at[idx].add(world * e2).reshape(n_pad)
    qg = jax.lax.all_gather(q2, axis_name, axis=0)   # (world, sc, chunk) s8
    sg = jax.lax.all_gather(s2, axis_name, axis=0)
    full = dequantize_chunks(qg, sg).reshape(n_pad)
    return full[:n], e_new[:n]


# -- gradient-tree packing ---------------------------------------------------

def grad_size(tree: Any) -> int:
    """Total element count of a gradient tree — the residual length."""
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(tree))


def _flatten_tree(tree: Any) -> jax.Array:
    return jnp.concatenate(
        [jnp.ravel(l).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(tree)])


def _unflatten_tree(tree: Any, flat: jax.Array) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += int(l.size)
    return jax.tree_util.tree_unflatten(treedef, out)


def compressed_pmean(grads: Any, residual: jax.Array, axis_name: str,
                     chunk: int = DEFAULT_CHUNK):
    """``lax.pmean(grads)``'s drop-in compressed twin over a whole gradient
    tree: flatten (tree_leaves order — deterministic), reduce via
    ``compressed_pmean_flat`` with the carried residual, unflatten back to
    the tree's shapes/dtypes. Returns ``(reduced_tree, new_residual)``."""
    flat = _flatten_tree(grads)
    red, e_new = compressed_pmean_flat(flat, residual, axis_name, chunk)
    return _unflatten_tree(grads, red), e_new


def init_comm_state(params: Any, world: int) -> dict:
    """Fresh error-feedback state for a gradient tree shaped like
    ``params``: one zero ``(world, n)`` f32 residual — rank r's pending
    (untransmitted) gradient mass lives in row r. Stored in
    ``TrainState.comm_state`` and sharded ``P(data)`` so each device holds
    exactly its own row.

    Returned as a HOST array (numpy, uncommitted): a ``jnp.zeros`` here
    would commit the full global ``(world, n)`` buffer to device 0 before
    ``shard_tree`` re-places it — an O(world × gradient-bytes) transient
    spike on one device at exactly the scale-out worlds this exists for.
    The placement (``shard_tree``'s device_put, or the jitted step's
    in_specs) shards it straight from host.

    Checkpoint-size note (docs/COMMUNICATION.md): because checkpoints hold
    full host trees, the residual adds ``world × n × 4`` bytes per file."""
    import numpy as np
    return {"residual": np.zeros((world, grad_size(params)), np.float32)}


# -- ZeRO-full (weight-update-sharded) step builders -------------------------

def _spec_cut_axis(spec, data_axis: str) -> Optional[int]:
    """Which dim a leaf's PartitionSpec cuts over the data axis (None =
    replicated). Derived FROM the spec tree — the single source the
    placement also used — so gather/scatter can never slice a different
    dim than ``shard_tree`` cut."""
    for i, a in enumerate(spec):
        if a == data_axis:
            return i
    return None


def _gather_full(tree: Any, spec_tree: Any, data_axis: str) -> Any:
    """All-gather the sharded leaves back to full arrays (the wus steps'
    just-in-time param materialization; shared by train AND eval so the
    two cannot drift)."""
    def g(leaf, spec):
        ax = _spec_cut_axis(spec, data_axis)
        if ax is None:
            return leaf
        return jax.lax.all_gather(leaf, data_axis, axis=ax, tiled=True)
    return jax.tree_util.tree_map(g, tree, spec_tree)


def _state_spec_tree(mesh: Mesh, state: Any, data_axis: str,
                     zero_mode: Optional[str]) -> Any:
    """The TrainState-shaped PartitionSpec tree the wus/compressed steps
    bind as shard_map in/out specs — a CLIENT of the parallelism plane's
    single placement derivation (``plane.state_specs``, ISSUE 12), so the
    specs the step compiles against can never drift from where
    ``shard_state`` put the arrays."""
    from tpudist.parallel.plane import state_specs
    return state_specs(mesh, state, (), zero_mode=zero_mode,
                       data_axis=data_axis)


def make_wus_train_step(mesh: Mesh, model, cfg: Config,
                        data_axis: str = "data",
                        compress: Optional[str] = None) -> Callable:
    """ZeRO-full train step: (state, images, labels, lr) → (state, metrics).

    State arrives SHARDED: every params / optimizer / EMA leaf whose
    leading dim divides the data-axis size holds only its own rows per
    device (``tree_shardings(..., zero_mode="full")``). The step:

    1. all-gathers the sharded param leaves just-in-time (``tiled=True``
       concat on dim 0) — the only place full params ever materialize;
    2. runs forward/backward on the local batch shard exactly like the DP
       step (same ``_loss_fn``, mixup, accumulation semantics);
    3. reduces gradients with ``psum_scatter`` for sharded leaves (each
       rank receives only the rows it owns) and ``pmean`` for the
       replicated remainder — or, under ``compress="int8"``, the quantized
       two-phase reduce with each rank slicing its rows locally;
    4. applies the optimizer on the shard alone (optax transforms are
       elementwise per leaf, so torch-SGD/AdamW semantics are unchanged),
       leaving the updated state sharded for the next step's gather.

    fp16 dynamic loss scaling is rejected like the other specialty paths
    (``check_step_supported``); bf16 AMP composes.
    """
    from tpudist.ops import accuracy
    from tpudist.parallel._common import (accum_scan, check_step_supported,
                                          donated_jit)
    from tpudist.train import _loss_fn, make_optimizer, update_ema

    check_step_supported(cfg, "zero-full weight-update sharding")
    world = mesh.shape[data_axis]
    if world < 2:
        raise ValueError(
            f"--zero full shards the weight update over the '{data_axis}' "
            f"axis, which has size {world} — nothing to shard; use "
            f"--zero off (or 1) on a single-device data axis")
    tx = make_optimizer(cfg)
    base_rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
    accum = max(1, int(getattr(cfg, "accum_steps", 1)))
    mixing = (getattr(cfg, "mixup_alpha", 0.0) > 0.0
              or getattr(cfg, "cutmix_alpha", 0.0) > 0.0)
    chunk = DEFAULT_CHUNK

    def make_step(specs):

        def own_rows(full_leaf, spec):
            """This rank's shard block of a full (already-reduced) leaf."""
            ax = _spec_cut_axis(spec, data_axis)
            if ax is None:
                return full_leaf
            blk = full_leaf.shape[ax] // world
            idx = jax.lax.axis_index(data_axis)
            return jax.lax.dynamic_slice_in_dim(full_leaf, idx * blk, blk,
                                                axis=ax)

        def reduce_grads(grads, comm_state):
            """Mean-reduce full per-rank grads into per-shard grads."""
            if compress == "int8":
                red_full, e_new = compressed_pmean(
                    grads, comm_state["residual"][0], data_axis, chunk)
                red = jax.tree_util.tree_map(own_rows, red_full,
                                             specs.params)
                return red, {"residual": e_new[None]}

            def r(gleaf, spec):
                ax = _spec_cut_axis(spec, data_axis)
                if ax is None:
                    return jax.lax.pmean(gleaf, data_axis)
                return jax.lax.psum_scatter(
                    gleaf, data_axis, scatter_dimension=ax,
                    tiled=True) / world
            return (jax.tree_util.tree_map(r, grads, specs.params),
                    comm_state)

        def step(state, images, labels, lr):
            rng = jax.random.fold_in(
                jax.random.fold_in(base_rng, state.step),
                jax.lax.axis_index(data_axis))
            labels2, lam = None, None
            if mixing:
                from tpudist.ops.mixup import mix_batch
                k_mix, rng = jax.random.split(rng)
                images, labels, labels2, lam = mix_batch(
                    k_mix, images, labels, cfg.mixup_alpha, cfg.cutmix_alpha)

            params_full = _gather_full(state.params, specs.params,
                                       data_axis)

            if accum > 1:
                def per_mb(rng_i, stats, im_i, lb_i, *lb2_i):
                    lf_i = partial(_loss_fn, model, rng_i,
                                   smoothing=cfg.label_smoothing,
                                   labels2=lb2_i[0] if lb2_i else None,
                                   lam=lam)
                    (loss_i, (outputs, stats)), grads_i = jax.value_and_grad(
                        lf_i, has_aux=True)(params_full, stats, im_i, lb_i)
                    return grads_i, stats, (loss_i,
                                            accuracy(outputs, lb_i, topk=1))

                batch = (images, labels) + ((labels2,)
                                            if labels2 is not None else ())
                grads, new_stats, (loss, acc1) = accum_scan(
                    per_mb, batch, state.batch_stats, rng, accum)
            else:
                lf = partial(_loss_fn, model, rng,
                             smoothing=cfg.label_smoothing,
                             labels2=labels2, lam=lam)
                (loss, (outputs, new_stats)), grads = jax.value_and_grad(
                    lf, has_aux=True)(params_full, state.batch_stats,
                                      images, labels)
                acc1 = accuracy(outputs, labels, topk=1)

            with jax.named_scope(scopes.GRAD_REDUCE):
                grads, new_comm = reduce_grads(grads, state.comm_state)
                new_stats = jax.lax.pmean(new_stats, axis_name=data_axis)
            import optax
            with jax.named_scope(scopes.OPTIMIZER):
                tx_state = state.opt_state
                tx_state.hyperparams["learning_rate"] = lr
                updates, new_opt_state = tx.update(grads, tx_state,
                                                   state.params)
                new_params = optax.apply_updates(state.params, updates)
            with jax.named_scope(scopes.METRICS):
                metrics = {
                    "loss": jax.lax.pmean(loss, axis_name=data_axis),
                    "acc1": jax.lax.pmean(acc1, axis_name=data_axis),
                }
            ema = update_ema(cfg, state.ema_params, new_params, new_stats)
            new_state = state.replace(step=state.step + 1, params=new_params,
                                      batch_stats=new_stats,
                                      opt_state=new_opt_state,
                                      ema_params=ema, comm_state=new_comm)
            return new_state, metrics

        return step

    # Specs depend on the concrete state tree (per-leaf cut-dim
    # divisibility), so the shard_map wrapper is built lazily on first
    # call and cached (parallel/_common.lazy_step — .lower forwarded so
    # --zero full runs keep their MFU numerator and collective-bytes
    # meter).
    from tpudist.parallel._common import lazy_step

    def build(state):
        specs = _state_spec_tree(mesh, state, data_axis, "full")
        return donated_jit(shard_map(
            make_step(specs), mesh=mesh,
            in_specs=(specs, P(data_axis), P(data_axis), P()),
            out_specs=(specs, P()), check_vma=False))

    return lazy_step(build)


def make_wus_eval_step(mesh: Mesh, model, cfg: Config,
                       data_axis: str = "data") -> Callable:
    """Eval twin of the wus step: gathers the sharded param leaves (the
    eval state may be the EMA substitution — same shapes, same specs) and
    runs the standard eval forward on the local batch shard."""
    from tpudist.ops import accuracy, cross_entropy_loss
    from tpudist.parallel._common import lazy_step

    def make_step(specs):
        def step(state, images, labels):
            params = _gather_full(state.params, specs.params, data_axis)
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            outputs = model.apply(variables, images, train=False)
            return {
                "loss": jax.lax.pmean(cross_entropy_loss(outputs, labels),
                                      data_axis),
                "acc1": jax.lax.pmean(accuracy(outputs, labels, topk=1),
                                      data_axis),
            }
        return step

    def build(state):
        specs = _state_spec_tree(mesh, state, data_axis, "full")
        return jax.jit(shard_map(
            make_step(specs), mesh=mesh,
            in_specs=(specs, P(data_axis), P(data_axis)),
            out_specs=P(), check_vma=False))

    return lazy_step(build)
