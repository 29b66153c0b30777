"""Expert-parallel training steps: MoE models over an ('expert',) or
('data', 'expert') mesh.

No reference equivalent (SURVEY.md §2.2: EP "No") — this makes the 'expert'
mesh axis a *Trainer config state* for the MoE ViT family
(``tpudist/models/vit_moe.py``).

Layout: the expert axis doubles as a batch axis (the canonical Switch/
Mesh-TF layout — each device owns one expert's FFN weights AND a token
shard; tokens reach their expert via one ``lax.all_to_all`` each way):

- images/labels shard over ('data',)+'expert' on the batch dim;
- expert FFN leaves (leading ``[num_experts]`` dim: ``moe/w1|b1|w2|b2`` and
  their optimizer-momentum mirrors) shard over 'expert' (replicated over
  'data'); everything else — attention, router, LayerNorms, step counter —
  is replicated;
- gradient reduction is split to match: replicated leaves take
  ``lax.pmean`` over the batch axes (average of per-shard grads); expert
  leaves are already the cross-shard SUM over the expert axis for their
  device's expert (the all_to_all transpose routes every shard's cotangents
  back to the owning device), so they need only a LOCAL ``/ n_expert`` —
  plus, under dp×ep composition (r3), a ``pmean`` over the 'data' axis
  (each data slice ran its own all_to_all over a different token shard);
- the Switch load-balance aux loss (sown into the ``losses`` collection —
  see vit_moe.py for why not ``intermediates``) is added to the task loss
  with weight ``moe_aux_weight``; it is computed from pmean-ed routing
  fractions, so it is already identical on every shard of a data slice.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from tpudist.config import Config
from tpudist.ops import accuracy, cross_entropy_loss
from tpudist.obs import scopes
from tpudist.train import TrainState, make_optimizer, update_ema

from tpudist.parallel._common import (accum_scan, accum_steps,
                                      apply_optimizer_update,
                                      check_step_supported, path_keys,
                                      template_state)

_EXPERT_LEAVES = ("w1", "b1", "w2", "b2")
MOE_AUX_WEIGHT = 0.01     # standard Switch coefficient


def _is_expert_leaf(path) -> bool:
    keys = path_keys(path)
    return "moe" in keys and keys[-1] in _EXPERT_LEAVES


def state_specs(state: TrainState, expert_axis: str = "expert") -> TrainState:
    """Full-structure PartitionSpec tree for a TrainState: expert FFN leaves
    (and their optimizer mirrors, which share the params' path structure)
    shard on their leading [E] dim; everything else replicated."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: P(expert_axis) if _is_expert_leaf(path) else P(),
        state)


def split_grad_reduce(grads, expert_axis: str, n: int,
                      data_axis: str | None = None):
    """Global-batch-average gradients under the split layout: pmean over all
    batch axes for replicated leaves; expert-sharded leaves take a local /n
    (their cross-shard sum over the expert axis already happened in the
    all_to_all transpose) plus a pmean over the data axis when composing
    dp×ep (each data slice contributed an independent expert-grad sum)."""
    batch_axes = (data_axis, expert_axis) if data_axis else (expert_axis,)

    def reduce(path, g):
        if _is_expert_leaf(path):
            g = g / n
            return jax.lax.pmean(g, axis_name=data_axis) if data_axis else g
        return jax.lax.pmean(g, axis_name=batch_axes)

    return jax.tree_util.tree_map_with_path(reduce, grads)


def _moe_loss_fn(model: nn.Module, rng, params, batch_stats, images, labels,
                 smoothing: float = 0.0, labels2=None, lam=None):
    from tpudist.ops.mixup import mixed_ce
    with jax.named_scope(scopes.FORWARD):
        (outputs, mutated) = model.apply(
            {"params": params, "batch_stats": batch_stats},
            images, train=True, mutable=["batch_stats", "losses"],
            rngs={"dropout": rng})
    with jax.named_scope(scopes.LOSS):
        ce = mixed_ce(outputs, labels, labels2, lam, smoothing)
        loss = ce
        for aux in jax.tree_util.tree_leaves(mutated.get("losses", {})):
            loss = loss + MOE_AUX_WEIGHT * aux
    # ce returned separately: the Trainer logs 'Train_ce_loss', which must
    # stay pure CE (comparable with the dense-twin DP path) while the
    # optimizer trains on CE + aux.
    return loss, (outputs, mutated.get("batch_stats", {}), ce)


def _batch_axes(mesh: Mesh, expert_axis: str,
                data_axis: str | None) -> tuple[str, ...]:
    """Validate the mesh shape for (dp×)ep and return the batch axes."""
    names = tuple(mesh.shape.keys())
    if data_axis:
        if names != (data_axis, expert_axis):
            raise ValueError(
                f"dp×ep composition uses a ('{data_axis}', '{expert_axis}') "
                f"mesh; got {dict(mesh.shape)}")
        return (data_axis, expert_axis)
    if names != (expert_axis,):
        raise ValueError(
            f"expert parallelism uses a pure ('{expert_axis}',) mesh (the "
            f"expert axis doubles as the batch axis) or a "
            f"('data', '{expert_axis}') mesh via data_axis=; got "
            f"{dict(mesh.shape)}")
    return (expert_axis,)


def make_ep_train_step(mesh: Mesh, model: nn.Module, cfg: Config,
                       expert_axis: str = "expert",
                       data_axis: str | None = None) -> Callable:
    """(state, images, labels, lr) → (state, metrics); images sharded on the
    batch dim over the batch axes (``data_axis``, if composing, then
    ``expert_axis``); state sharded per ``state_specs``."""
    tx = make_optimizer(cfg)
    base_rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
    n = mesh.shape[expert_axis]
    check_step_supported(cfg, "expert parallelism")
    batch_axes = _batch_axes(mesh, expert_axis, data_axis)
    e = getattr(model, "num_experts", None)
    if e is not None and e != n:
        raise ValueError(
            f"model.num_experts={e} must equal the expert-axis size {n} "
            f"(each expert-axis device holds exactly one expert's weights)")

    accum = accum_steps(cfg)
    mixing = (getattr(cfg, "mixup_alpha", 0.0) > 0.0
              or getattr(cfg, "cutmix_alpha", 0.0) > 0.0)

    def step(state: TrainState, images, labels, lr):
        rng = jax.random.fold_in(base_rng, state.step)
        for ax in batch_axes:                 # unique stream per batch shard
            rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
        labels2, lam = None, None
        if mixing:
            # Per-shard permutation, like the shard_map DP step (the SPMD
            # analogue of torch's in-batch randperm).
            from tpudist.ops.mixup import mix_batch
            k_mix, rng = jax.random.split(rng)
            images, labels, labels2, lam = mix_batch(
                k_mix, images, labels, cfg.mixup_alpha, cfg.cutmix_alpha)
        if accum > 1:
            # Note the expert-leaf semantics hold per microbatch: each
            # microbatch's all_to_all transpose produces that microbatch's
            # cross-shard expert-grad sum, so the summed-then-averaged
            # accumulation equals the full-batch expert gradient and the
            # same split_grad_reduce applies to the average.
            def per_mb(rng_i, stats, im_i, lb_i, *lb2_i):
                lf_i = partial(_moe_loss_fn, model, rng_i,
                               smoothing=cfg.label_smoothing,
                               labels2=lb2_i[0] if lb2_i else None, lam=lam)
                (_, (outputs, stats, ce_i)), g_i = jax.value_and_grad(
                    lf_i, has_aux=True)(state.params, stats, im_i, lb_i)
                return g_i, stats, (ce_i, accuracy(outputs, lb_i, topk=1))

            batch = (images, labels) + ((labels2,) if labels2 is not None
                                        else ())
            grads, new_stats, (ce, acc1) = accum_scan(
                per_mb, batch, state.batch_stats, rng, accum)
        else:
            lf = partial(_moe_loss_fn, model, rng,
                         smoothing=cfg.label_smoothing,
                         labels2=labels2, lam=lam)
            (_, (outputs, new_stats, ce)), grads = jax.value_and_grad(
                lf, has_aux=True)(state.params, state.batch_stats,
                                  images, labels)
            acc1 = accuracy(outputs, labels, topk=1)
        with jax.named_scope(scopes.GRAD_REDUCE):
            grads = split_grad_reduce(grads, expert_axis, n, data_axis)
            new_stats = jax.lax.pmean(new_stats, axis_name=batch_axes)
        new_params, new_opt_state = apply_optimizer_update(tx, state, grads, lr)
        ema = update_ema(cfg, state.ema_params, new_params, new_stats)

        # 'loss' is pure CE (what the Trainer logs as Train_ce_loss,
        # comparable across parallelism modes); the optimizer trained on
        # CE + MOE_AUX_WEIGHT*aux above.
        with jax.named_scope(scopes.METRICS):
            metrics = {
                "loss": jax.lax.pmean(ce, axis_name=batch_axes),
                "acc1": jax.lax.pmean(acc1, axis_name=batch_axes),
            }
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_stats, ema_params=ema,
                                  opt_state=new_opt_state)
        return new_state, metrics

    specs = state_specs(_template_specs(model, cfg), expert_axis)
    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(specs, P(batch_axes), P(batch_axes), P()),
        out_specs=(specs, P()),
        check_vma=False)
    from tpudist.parallel._common import donated_jit
    return donated_jit(sharded)


def _template_specs(model: nn.Module, cfg: Config) -> TrainState:
    return template_state(model, cfg, expert_axis=None)


def make_ep_eval_step(mesh: Mesh, model: nn.Module, cfg: Config,
                      expert_axis: str = "expert",
                      data_axis: str | None = None) -> Callable:
    """``train.make_eval_step`` with the split EP state layout. The batch
    axes tuple rides through make_eval_step's ``data_axis`` (PartitionSpec
    entries and collective axis_names both accept tuples)."""
    from tpudist.train import make_eval_step
    batch_axes = _batch_axes(mesh, expert_axis, data_axis)
    return make_eval_step(
        mesh, model, cfg,
        data_axis=batch_axes if data_axis else expert_axis,
        state_specs=state_specs(_template_specs(model, cfg), expert_axis))
