"""Shared helpers for the SP/EP/PP step builders (single source for the
path-matching, unsupported-config guards, and twin-template construction
that would otherwise be copy-pasted per mode)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpudist.config import Config
from tpudist.obs import scopes


def path_keys(path) -> list[str]:
    """Stringified key names along a jax tree path."""
    return [str(getattr(p, "key", getattr(p, "name", p))) for p in path]


def donated_jit(fn, donate_argnums=(0,), **kwargs):
    """``jax.jit`` with train-state buffer donation — behind the
    ``TPUDIST_NO_DONATE`` escape hatch.

    Donation halves state memory on the hot path and is the right default
    on TPU. But it is an *optimization*, and some CPU runtimes mis-handle
    the donated-buffer aliasing: on jaxlib 0.4.x CPU under gVisor, a step
    whose first call donates a checkpoint-restored (host-numpy-leaved)
    state corrupts the heap — segfault/hang one step later (found by the
    fault-injection suite's restart→resume chain; reproduced on the seed
    code). ``TPUDIST_NO_DONATE=1`` trades the memory win for correctness
    on such runtimes; the fault tests set it for their subprocess ranks.
    """
    import os
    if os.environ.get("TPUDIST_NO_DONATE"):
        return jax.jit(fn, **kwargs)
    return jax.jit(fn, donate_argnums=donate_argnums, **kwargs)


def lazy_step(build, mesh=None):
    """One-wrapper-one-compile-cache for SPEC-DEPENDENT step builders (the
    GSPMD/zero/compressed paths, whose in/out shardings depend on the
    concrete state tree): ``build(state)`` constructs the compiled
    callable on first call; the wrapper caches it and forwards ``.lower``
    so telemetry's cost-analysis/census introspection works on every lazy
    path — this pattern existed as five hand-rolled copies, and the one
    that predated ``.lower`` delegation (GSPMD, r5–r7) silently lost the
    MFU numerator and collective-bytes meter. ``mesh`` wraps calls AND
    lowers in ``jax.sharding.set_mesh`` (the GSPMD builders' ambient-mesh
    requirement: flash_attention_spmd nests a manual region over it)."""
    import contextlib
    cache: dict = {}

    def _fn(state):
        if "fn" not in cache:
            cache["fn"] = build(state)
        return cache["fn"]

    def _ctx():
        return (jax.sharding.set_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())

    def compiled(state, *args):
        with _ctx():
            return _fn(state)(state, *args)

    def lower(state, *args, **kwargs):
        with _ctx():
            return _fn(state).lower(state, *args, **kwargs)

    compiled.lower = lower
    return compiled


def check_step_supported(cfg: Config, mode: str) -> None:
    """Reject config combinations the specialty step builders don't implement
    — with ValueError (user error), never assert (stripped under -O).
    (Gradient accumulation and mixup/cutmix are supported on every specialty
    path since r4 — ``accum_scan`` + per-path ``mix_batch`` wiring; fp16
    dynamic scaling composes with accumulation on the DP/GSPMD paths since
    r5 and stays off SP/EP/PP permanently BY DESIGN: fp16+GradScaler exists
    for parity with the reference's CUDA recipe
    (``distributed_syncBN_amp.py:275-278``), which only ever composes it
    with data parallelism — on TPU the native mixed precision is bf16
    (fp32 exponent range, no scaler), and the SP/EP/PP modes are
    beyond-reference additions that target TPU, so they take the TPU
    precision. See docs/MIGRATION.md's support matrix.)"""
    if cfg.use_amp and cfg.amp_dtype == "float16":
        raise ValueError(
            f"fp16 dynamic loss scaling is not supported with {mode} "
            f"(permanent, by design — fp16 exists for reference-recipe "
            f"parity on the data-parallel paths; TPU-native mixed precision "
            f"is bf16, which needs no scaler); use amp_dtype='bfloat16'")


def accum_steps(cfg: Config) -> int:
    return max(1, int(getattr(cfg, "accum_steps", 1) or 1))


def accum_scan(per_microbatch, batch, stats, rng, accum: int):
    """Shared gradient-accumulation scan for the specialty (SP/EP/PP) step
    builders — torch accumulation semantics, mirroring the DP path
    (train.py:234-275): gradients and scalar metrics AVERAGE over ``accum``
    microbatches; mutable collections (BN stats) thread sequentially; one
    optimizer step results.

    ``batch`` is a tuple of arrays sharing the leading batch dim — (images,
    labels) plus, under mixup/cutmix, the pair labels.
    ``per_microbatch(rng_i, stats, *batch_i) ->
    (grads_i, new_stats, metrics_pytree)`` closes over params. Callers come
    in two flavors: the shard_map builders (DP/SP/EP/PP) call this inside
    their shard_map body with PER-SHARD shapes and keep their cross-shard
    grad reduction after it (the reduction commutes with the microbatch
    average); the GSPMD builder calls it with GLOBAL, partitioner-sharded
    arrays and needs no explicit reduction.

    Returns ``(grads_avg, final_stats, metrics_avg)``.
    """
    n = batch[0].shape[0]
    mb = n // accum
    if mb * accum != n:
        raise ValueError(
            f"batch {n} (as seen by this step: per-shard under shard_map, "
            f"global under GSPMD) is not divisible by accum_steps={accum}")
    split = tuple(a.reshape(accum, mb, *a.shape[1:]) for a in batch)
    rngs = jax.random.split(rng, accum)
    # Zero-init the scan carry from the abstract shapes of one microbatch
    # call (eval_shape: no FLOPs) — keeps this helper agnostic to each
    # path's grad structure and metric set.
    g_shape, _, m_shape = jax.eval_shape(
        lambda r, s, b: per_microbatch(r, s, *b),
        rngs[0], stats, tuple(a[0] for a in split))
    zeros = lambda tree: jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), tree)

    def body(carry, xs):
        stats_c, gsum, msum = carry
        rng_i, b_i = xs
        g_i, stats_c, m_i = per_microbatch(rng_i, stats_c, *b_i)
        return (stats_c,
                jax.tree_util.tree_map(jnp.add, gsum, g_i),
                jax.tree_util.tree_map(jnp.add, msum, m_i)), None

    (stats, gsum, msum), _ = jax.lax.scan(
        body, (stats, zeros(g_shape), zeros(m_shape)), (rngs, split))
    div = lambda tree: jax.tree_util.tree_map(lambda x: x / accum, tree)
    return div(gsum), stats, div(msum)


def scaled_value_and_grad(lf, scale, *args):
    """The per-microbatch half of GradScaler-with-accumulation
    (``torch.amp``: ``scaler.scale(loss).backward()`` per microbatch, ONE
    ``scaler.step``): grads of ``scale * loss`` — the scaling guards each
    microbatch's fp16 backward against underflow — unscaled back to fp32
    before the running sum, so the accumulated average lives in master
    precision. ``lf(*args) -> (loss, aux)``; returns
    ``(loss, aux, unscaled_grads)``."""
    def scaled(*a):
        loss, aux = lf(*a)
        return scale * loss, aux

    (sloss, aux), grads = jax.value_and_grad(scaled, has_aux=True)(*args)
    grads = jax.tree_util.tree_map(
        lambda g: jnp.asarray(g, jnp.float32) / scale, grads)
    return sloss / scale, aux, grads


def ds_finite(grads) -> jax.Array:
    """All-finite flag over a gradient tree (flax ``DynamicScale``'s check,
    applied to the ACCUMULATED average rather than per microbatch)."""
    finite = jnp.array(True)
    for g in jax.tree_util.tree_leaves(grads):
        finite &= jnp.all(jax.lax.is_finite(g))
    return finite


def ds_update(ds, finite: jax.Array):
    """flax ``DynamicScale``'s scale-adjustment arithmetic
    (``dynamic_scale.py`` grad_fn_wrapper), applied ONCE per optimizer step
    — ``torch.amp.GradScaler.update`` semantics. Under accumulation the
    scale must stay FIXED across the microbatch scan (averaging gradients
    produced under different scales would be wrong), so the builders call
    ``scaled_value_and_grad`` inside the scan with the step's scale and
    apply this rule outside it, to the finite flag of the averaged grads."""
    grow = ds.fin_steps == ds.growth_interval
    fin_scale = jnp.where(
        grow & finite,
        jnp.minimum(ds.scale * ds.growth_factor, jnp.finfo(jnp.float32).max),
        ds.scale)
    inf_scale = ds.scale * ds.backoff_factor
    if ds.minimum_scale is not None:
        inf_scale = jnp.maximum(inf_scale, ds.minimum_scale)
    new_scale = jnp.where(finite, fin_scale, inf_scale)
    new_fin = jnp.where(grow | (~finite), 0, ds.fin_steps + 1)
    return ds.replace(fin_steps=new_fin, scale=new_scale)


def apply_optimizer_update(tx, state, grads, lr):
    """The shared optimizer tail of the specialty (SP/EP/PP) train steps:
    inject the per-step lr, apply whatever optimizer make_optimizer(cfg)
    built (torch-SGD or AdamW), return the updated (params, opt_state).
    (The DP step in train.py keeps its own tail — it additionally handles
    the fp16 overflow-skip path.)"""
    import optax
    with jax.named_scope(scopes.OPTIMIZER):
        tx_state = state.opt_state
        tx_state.hyperparams["learning_rate"] = lr
        updates, new_opt_state = tx.update(grads, tx_state, state.params)
        return optax.apply_updates(state.params, updates), new_opt_state


def template_state(model, cfg: Config, **twin_overrides):
    """Abstract TrainState (eval_shape — no FLOPs) for spec-tree construction,
    built from the dense twin (``model.clone(**twin_overrides)``): the SPMD
    form's collectives cannot be traced outside shard_map, even abstractly."""
    from tpudist.train import create_train_state
    twin = model.clone(**twin_overrides)
    return jax.eval_shape(
        lambda: create_train_state(
            jax.random.PRNGKey(0), twin, cfg,
            input_shape=(1, cfg.image_size, cfg.image_size, 3)))
