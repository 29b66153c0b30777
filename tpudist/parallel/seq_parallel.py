"""Sequence-parallel training steps: DP × SP over a ('data', 'seq') mesh.

No reference equivalent (the reference is fixed-224 image classification,
SURVEY.md §5 "long-context: absent entirely") — this is the framework's
long-context capability made a *Trainer config state*: a mesh with a ``seq``
axis trains a ViT whose token dimension is sharded around a ring
(``ring_attention``), so sequences that do not fit one chip's HBM train with
O(T/n) per-device activation memory.

Design:

- images enter sharded over ``data`` on the batch dim and REPLICATED over
  ``seq``; the model (``VisionTransformer(seq_axis=...)``) slices its local
  token block internally, so patchify/pos-embed params keep the exact shapes
  of the unsharded twin (init happens outside shard_map with that twin —
  ring collectives cannot be traced by ``model.init``);
- params/optimizer state are replicated over BOTH axes; every seq shard
  computes the SAME loss value (the GAP head pmean-pools over ``seq``), and
  ``lax.pmean(grads, (data, seq))`` yields the exact global-batch gradient:
  summing per-shard grads is the transpose of the forward's collectives, and
  the mean over identical replicated losses equals the single loss;
- metrics are pmean-ed over ``data`` only (they are already identical across
  ``seq``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from tpudist.config import Config
from tpudist.ops import accuracy
from tpudist.parallel._common import (accum_scan, accum_steps,
                                      apply_optimizer_update,
                                      check_step_supported)
from tpudist.obs import scopes
from tpudist.train import TrainState, _loss_fn, make_optimizer, update_ema


def make_sp_train_step(mesh: Mesh, model: nn.Module, cfg: Config,
                       data_axis: str = "data",
                       seq_axis: str = "seq") -> Callable:
    """(state, images, labels, lr) → (state, metrics); images [B, H, W, C]
    sharded on batch over ``data_axis``, replicated over ``seq_axis``."""
    tx = make_optimizer(cfg)
    base_rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
    check_step_supported(cfg, "sequence parallelism")
    accum = accum_steps(cfg)
    mixing = (getattr(cfg, "mixup_alpha", 0.0) > 0.0
              or getattr(cfg, "cutmix_alpha", 0.0) > 0.0)

    def step(state: TrainState, images, labels, lr):
        # Per-(step, data shard) stream — everything REPLICATED over seq
        # (the mixing permutation/lam must be identical on every seq shard
        # of a data slice, or the ring would attend over inconsistent
        # pixels) derives from this...
        rng_data = jax.random.fold_in(
            jax.random.fold_in(base_rng, state.step),
            jax.lax.axis_index(data_axis))
        # ...while dropout additionally folds the seq index: token-local
        # stochasticity must decorrelate across the ring, replicated-tensor
        # stochasticity is reconciled by the GAP pmean.
        rng = jax.random.fold_in(rng_data, jax.lax.axis_index(seq_axis))

        labels2, lam = None, None
        if mixing:
            from tpudist.ops.mixup import mix_batch
            k_mix, _ = jax.random.split(rng_data)
            images, labels, labels2, lam = mix_batch(
                k_mix, images, labels, cfg.mixup_alpha, cfg.cutmix_alpha)

        if accum > 1:
            def per_mb(rng_i, stats, im_i, lb_i, *lb2_i):
                lf_i = partial(_loss_fn, model, rng_i,
                               smoothing=cfg.label_smoothing,
                               labels2=lb2_i[0] if lb2_i else None, lam=lam)
                (loss_i, (outputs, stats)), g_i = jax.value_and_grad(
                    lf_i, has_aux=True)(state.params, stats, im_i, lb_i)
                return g_i, stats, (loss_i, accuracy(outputs, lb_i, topk=1))

            batch = (images, labels) + ((labels2,) if labels2 is not None
                                        else ())
            grads, new_stats, (loss, acc1) = accum_scan(
                per_mb, batch, state.batch_stats, rng, accum)
        else:
            lf = partial(_loss_fn, model, rng, smoothing=cfg.label_smoothing,
                         labels2=labels2, lam=lam)
            (loss, (outputs, new_stats)), grads = jax.value_and_grad(
                lf, has_aux=True)(state.params, state.batch_stats,
                                  images, labels)
            acc1 = accuracy(outputs, labels, topk=1)
        with jax.named_scope(scopes.GRAD_REDUCE):
            grads = jax.lax.pmean(grads, axis_name=(data_axis, seq_axis))
            # Keep replicated state consistent across data shards (no-op for
            # the BN-free ViT family, where new_stats is {}).
            new_stats = jax.lax.pmean(new_stats, axis_name=data_axis)
        new_params, new_opt_state = apply_optimizer_update(tx, state, grads, lr)
        ema = update_ema(cfg, state.ema_params, new_params, new_stats)

        with jax.named_scope(scopes.METRICS):
            metrics = {
                "loss": jax.lax.pmean(loss, axis_name=data_axis),
                "acc1": jax.lax.pmean(acc1, axis_name=data_axis),
            }
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_stats, ema_params=ema,
                                  opt_state=new_opt_state)
        return new_state, metrics

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis), P()),
        out_specs=(P(), P()),
        check_vma=False)
    from tpudist.parallel._common import donated_jit
    return donated_jit(sharded)


# Eval needs no SP-specific step: ``tpudist.train.make_eval_step`` over the
# same mesh binds the seq axis for the model's ring attention already.
