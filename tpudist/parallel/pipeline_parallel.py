"""Pipeline-parallel training steps: PipelinedViT over a ('data','pipe') mesh.

No reference equivalent (SURVEY.md §2.2: PP "No") — this makes the 'pipe'
mesh axis a *Trainer config state* for the pipelined ViT family
(``tpudist/models/vit_pipe.py``; the low-level schedule lives in
``tpudist/parallel/pipeline.py``).

Layout and gradient math (see vit_pipe.py's module docstring for the
derivation):

- images shard over 'data' on the batch dim and replicate over 'pipe'
  (every pipeline stage sees the activations only through the ring);
- trunk leaves (the nn.scan-stacked encoder layers, path ``…/trunk/…``, and
  their optimizer-momentum mirrors) shard their leading [L] dim over 'pipe';
  embed/head/LN leaves replicate;
- the backward seed is loss/S: then trunk gradients come out exact and
  LOCAL (the ppermute transposes already routed every loss replica's
  cotangent to the owning stage) while replicated leaves need a ``psum``
  over 'pipe' (stage 0 owns the embed cotangent, each stage holds
  (1/S)·dL/dhead); everything then pmean-s over 'data' as usual.
"""

from __future__ import annotations

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


def _is_trunk_leaf(path) -> bool:
    return "trunk" in path_keys(path)


def pp_state_specs(state, pipe_axis: str = "pipe",
                   model_axis: str | None = None):
    """Full-structure spec tree: trunk leaves shard their leading (layer)
    dim over 'pipe'; everything else replicated. With ``model_axis`` (the
    data×pipe×model composition, r3) the trunk's Megatron leaves also shard
    their TP dim — column-split kernels/biases on the output dim,
    row-parallel kernels on the input dim (models/vit.py EncoderBlock
    model_axis layout); LayerNorms and row-parallel biases stay
    pipe-sharded only."""
    def spec(path, leaf):
        if not _is_trunk_leaf(path):
            return P()
        if model_axis:
            name = "/".join(path_keys(path))
            if name.endswith(("in_proj/kernel", "mlp_0/kernel")):
                return P(pipe_axis, None, model_axis)
            if name.endswith(("in_proj/bias", "mlp_0/bias")):
                return P(pipe_axis, model_axis)
            if name.endswith(("out_proj/kernel", "mlp_3/kernel")):
                return P(pipe_axis, model_axis, None)
        return P(pipe_axis)

    return jax.tree_util.tree_map_with_path(spec, state)


def _template_state(model: nn.Module, cfg: Config) -> TrainState:
    return template_state(model, cfg, pipe_axis=None, model_axis=None)


def make_pp_train_step(mesh: Mesh, model: nn.Module, cfg: Config,
                       data_axis: str = "data",
                       pipe_axis: str = "pipe",
                       model_axis: str | None = None) -> Callable:
    """(state, images, labels, lr) → (state, metrics).

    ``model_axis``: Megatron TP inside each pipeline stage (the
    data×pipe×model composition). The gradient convention is UNCHANGED:
    TP-sharded trunk leaves are exact and local like the rest of the trunk
    (the Megatron f-operator in the model psums the partial activation
    cotangents, models/vit.py:_tp_copy), and replicated leaves' grads are
    identical across the model axis, so only the existing pipe-psum +
    data-pmean apply."""
    tx = make_optimizer(cfg)
    s = mesh.shape[pipe_axis]
    check_step_supported(cfg, "pipeline parallelism")
    if model_axis is not None:
        t = mesh.shape[model_axis]
        heads = getattr(model, "num_heads", None)
        mlp = getattr(model, "mlp_dim", None)
        if heads is not None and heads % t:
            raise ValueError(
                f"model-axis size {t} must divide num_heads={heads}")
        if mlp is not None and mlp % t:
            raise ValueError(
                f"model-axis size {t} must divide mlp_dim={mlp}")
    # Static shape preconditions, raised here as user errors (the in-model
    # asserts are developer backstops and vanish under python -O).
    n_layers = getattr(model, "num_layers", None)
    if n_layers is not None and n_layers % s != 0:
        raise ValueError(
            f"num_layers={n_layers} must be divisible by the pipe-axis size "
            f"{s} (one stage per device holds num_layers/S layers)")
    m = getattr(model, "num_microbatches", 0) or s
    accum = accum_steps(cfg)
    local_batch = cfg.batch_size // mesh.shape[data_axis]
    if local_batch % (m * accum) != 0:
        raise ValueError(
            f"per-data-shard batch {local_batch} must be divisible by "
            f"num_microbatches={m} x accum_steps={accum} (each accumulation "
            f"microbatch feeds the pipeline schedule separately)")

    base_rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
    mixing = (getattr(cfg, "mixup_alpha", 0.0) > 0.0
              or getattr(cfg, "cutmix_alpha", 0.0) > 0.0)

    def compute_grads(images, labels, params, labels2=None, lam=None):
        from tpudist.ops.mixup import mixed_ce

        def scaled_loss(params):
            with jax.named_scope(scopes.FORWARD):
                outputs = model.apply({"params": params}, images, train=True)
            with jax.named_scope(scopes.LOSS):
                return mixed_ce(outputs, labels, labels2, lam,
                                cfg.label_smoothing) / s, outputs

        (loss_over_s, outputs), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        return loss_over_s * s, outputs, grads

    def step(state: TrainState, images, labels, lr):
        labels2, lam = None, None
        if mixing:
            # Folded over (step, data shard) but NOT the pipe axis: images
            # replicate over 'pipe', so every stage must mix identically.
            from tpudist.ops.mixup import mix_batch
            k_mix = jax.random.fold_in(
                jax.random.fold_in(base_rng, state.step),
                jax.lax.axis_index(data_axis))
            images, labels, labels2, lam = mix_batch(
                k_mix, images, labels, cfg.mixup_alpha, cfg.cutmix_alpha)
        if accum > 1:
            # The pipeline model is deterministic (no dropout collection) and
            # stateless (no BN), so rng/stats ride the scan unused.
            def per_mb(rng_i, stats, im_i, lb_i, *lb2_i):
                loss_i, outputs, g_i = compute_grads(
                    im_i, lb_i, state.params,
                    labels2=lb2_i[0] if lb2_i else None, lam=lam)
                return g_i, stats, (loss_i, accuracy(outputs, lb_i, topk=1))

            batch = (images, labels) + ((labels2,) if labels2 is not None
                                        else ())
            grads, _, (loss, acc1) = accum_scan(
                per_mb, batch, {},
                jax.random.fold_in(base_rng, state.step), accum)
        else:
            loss, outputs, grads = compute_grads(images, labels, state.params,
                                                 labels2=labels2, lam=lam)
            acc1 = accuracy(outputs, labels, topk=1)
        with jax.named_scope(scopes.GRAD_REDUCE):
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: g if _is_trunk_leaf(path)
                else jax.lax.psum(g, axis_name=pipe_axis), grads)
            grads = jax.lax.pmean(grads, axis_name=data_axis)
        new_params, new_opt_state = apply_optimizer_update(tx, state, grads, lr)
        ema = update_ema(cfg, state.ema_params, new_params, state.batch_stats)

        with jax.named_scope(scopes.METRICS):
            metrics = {
                "loss": jax.lax.pmean(loss, axis_name=data_axis),
                "acc1": jax.lax.pmean(acc1, axis_name=data_axis),
            }
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=state.batch_stats,
                                  ema_params=ema, opt_state=new_opt_state)
        return new_state, metrics

    specs = pp_state_specs(_template_state(model, cfg), pipe_axis, model_axis)
    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(specs, P(data_axis), P(data_axis), P()),
        out_specs=(specs, P()),
        check_vma=False)
    from tpudist.parallel._common import donated_jit
    return donated_jit(sharded)


def make_pp_eval_step(mesh: Mesh, model: nn.Module, cfg: Config,
                      data_axis: str = "data",
                      pipe_axis: str = "pipe",
                      model_axis: str | None = None) -> Callable:
    """``train.make_eval_step`` with the pipeline state layout."""
    from tpudist.train import make_eval_step
    return make_eval_step(
        mesh, model, cfg, data_axis=data_axis,
        state_specs=pp_state_specs(_template_state(model, cfg), pipe_axis,
                                   model_axis))
