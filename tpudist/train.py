"""Compiled SPMD train/eval steps (reference L2+L3: the DDP wrapper + hot loop).

The reference's per-batch hot loop (``distributed.py:237-273``) is:
H2D copy → forward → CE loss → accuracy → barrier + 2 metric allreduces +
blocking ``.item()`` → zero_grad/backward/step, with gradient allreduce done by
DDP's C++ bucketed reducer inside ``backward()``.

Here the WHOLE of that is one XLA program per step, built with ``shard_map``
over the mesh's data axis:

- forward/backward run per-shard on the local batch (DDP's per-GPU compute);
- ``lax.pmean(grads)`` is the gradient allreduce — XLA schedules it on ICI and
  overlaps it with remaining backward compute (what DDP's bucketing does by
  hand in C++, ``SURVEY.md §2.3``);
- loss/accuracy are pmean-ed *inside* the program (the reference's
  ``reduce_mean`` + barrier + ``.item()`` per step, ``distributed.py:253-257``
  — here it costs one fused collective and no host sync);
- SGD(momentum, weight_decay) and MultiStepLR reproduce torch semantics
  exactly (see ``sgd_torch`` and ``lr_for_epoch``) because the 46.83% top-1
  target (BASELINE.md) depends on them.

Mixed precision (reference autocast+GradScaler,
``distributed_syncBN_amp.py:259,275-278``): params stay fp32 (master weights),
activations/matmuls run in bf16 via the model's ``dtype``. bf16 keeps fp32's
exponent range, so no GradScaler is needed; for fp16 parity a dynamic loss
scale is supported via ``amp_dtype='float16'``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from flax.training import dynamic_scale as dynamic_scale_lib
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from tpudist.config import Config
from tpudist.obs import scopes
from tpudist.ops import accuracy, cross_entropy_loss


class TrainState(struct.PyTreeNode):
    """Replicated training state: params (fp32 master), BN running stats,
    SGD momentum buffers, step counter, optional fp16 loss scale, optional
    EMA copy (``--model-ema-decay``; val and best-checkpoint selection use
    it when present). ``ema_params`` is ``{"params": ..., "batch_stats":
    ...}`` — torchvision's ExponentialMovingAverage averages BUFFERS too
    (use_buffers=True): evaluating EMA weights against live BN stats is a
    weight/statistics mismatch that tanks early-run val accuracy."""
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    dynamic_scale: dynamic_scale_lib.DynamicScale | None = struct.field(default=None)
    ema_params: Any = None
    # Gradient-communication state (``--compress-grads``): the per-rank
    # error-feedback residual, ``{"residual": (world, n) f32}`` sharded over
    # the data axis (``parallel/comm.py``). None when compression is off —
    # restore drops/seeds it exactly like ``ema_params`` cross-compat.
    comm_state: Any = None


def sgd_torch(lr_placeholder: float, momentum: float, weight_decay: float) -> optax.GradientTransformation:
    """torch.optim.SGD semantics (reference ``distributed.py:148-149``):
    ``g = g + wd*p``; ``v = mu*v + g``; ``p -= lr*v`` — weight decay folded
    into the gradient BEFORE momentum (not decoupled), applied to ALL params
    including BN scale/bias, exactly as ``model.parameters()`` does. The lr is
    injected per-step via ``optax.inject_hyperparams`` so epoch-boundary decay
    does not retrigger compilation."""
    def make(learning_rate):
        return optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.trace(decay=momentum, nesterov=False),
            optax.scale_by_learning_rate(learning_rate),
        )
    return optax.inject_hyperparams(make)(learning_rate=lr_placeholder)


def adamw_torch(lr_placeholder: float, weight_decay: float,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                mask: Any = None) -> optax.GradientTransformation:
    """torch.optim.AdamW semantics: bias-corrected moments, eps OUTSIDE the
    sqrt (optax ``eps_root=0``), and DECOUPLED weight decay applied after the
    adam scaling, i.e. ``p -= lr*(m̂/(√v̂+eps) + wd*p)`` — torch defaults
    b1=0.9 b2=0.999 eps=1e-8. ``mask=None`` decays every param exactly like a
    single torch param group; pass a mask for recipe-style param groups. The
    lr is injected per-step like sgd_torch."""
    def make(learning_rate):
        return optax.chain(
            optax.scale_by_adam(b1=b1, b2=b2, eps=eps, eps_root=0.0),
            optax.add_decayed_weights(weight_decay, mask=mask),
            optax.scale_by_learning_rate(learning_rate),
        )
    return optax.inject_hyperparams(make)(learning_rate=lr_placeholder)


def no_decay_mask(params: Any) -> Any:
    """Recipe-style AdamW param groups (ViT/Swin/ConvNeXt training recipes):
    decay matrices/convs only — biases, LN/BN scales, convnext layer_scale
    (all ndim<2), swin's relative-position bias tables, and swin v2's
    logit_scale + continuous-position-bias MLP are excluded, as the
    published recipes' torch param groups do."""
    def keep(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        in_cpb = any("cpb_mlp" in (p.key if hasattr(p, "key") else str(p))
                     for p in path)
        return (getattr(leaf, "ndim", 0) >= 2
                and name not in ("relative_position_bias_table",
                                 "logit_scale")
                and not in_cpb)
    return jax.tree_util.tree_map_with_path(keep, params)


def make_optimizer(cfg: Config) -> optax.GradientTransformation:
    """The trainer's optimizer as a config state: 'sgd' is the reference's
    recipe (``distributed.py:148-149``, uniform decay like
    ``model.parameters()``); 'adamw' serves the transformer-era zoo
    (vit/swin/convnext), with the standard no-decay mask standing in for
    those recipes' param groups."""
    if cfg.optimizer == "sgd":
        return sgd_torch(cfg.lr, cfg.momentum, cfg.weight_decay)
    if cfg.optimizer == "adamw":
        return adamw_torch(cfg.lr, cfg.weight_decay,
                           b2=getattr(cfg, "adam_b2", 0.999),
                           mask=no_decay_mask)
    raise ValueError(f"unsupported optimizer '{cfg.optimizer}' (sgd|adamw)")


def lr_for_epoch(cfg: Config, epoch: int) -> float:
    """MultiStepLR with the reference's step-at-epoch-START ordering
    (``distributed.py:192`` calls ``scheduler.step(epoch)`` before training):
    lr(e) = lr0 * gamma^(#milestones <= e). Milestones default [3,4]
    (``distributed.py:52``). 'cosine' is an additive extra."""
    warm = getattr(cfg, "warmup_epochs", 0)
    # Linear warmup (transformer recipes) MULTIPLIES the scheduled lr, so a
    # steplr milestone inside the warmup window still takes effect (no spike
    # + cliff at the handoff); cosine runs on the post-warmup timeline.
    ramp = (epoch + 1) / warm if (warm and epoch < warm) else 1.0
    if cfg.lr_scheduler == "steplr":
        factor = cfg.gamma ** sum(1 for m in cfg.step if epoch >= m)
        return cfg.lr * factor * ramp
    if cfg.lr_scheduler == "cosine":
        import math
        t = max(epoch - warm, 0) / max(cfg.epochs - warm, 1)
        return 0.5 * cfg.lr * (1 + math.cos(math.pi * t)) * ramp
    raise AssertionError(f"unsupported lr scheduler: {cfg.lr_scheduler}")  # distributed.py:153-154


def compute_dtype(cfg: Config):
    if not cfg.use_amp:
        return jnp.float32
    return jnp.bfloat16 if cfg.amp_dtype == "bfloat16" else jnp.float16


def create_train_state(rng: jax.Array, model: nn.Module, cfg: Config,
                       input_shape: Sequence[int] | None = None) -> TrainState:
    """Init params/BN stats (DDP's rank0-broadcast init is implicit: the same
    seed produces identical params everywhere; under pjit they are one
    replicated global array). The model is initialised on its own
    ``example_input()`` where it has one (a model of tokens: a short int32
    row), else on a float image ``[1, image_size, image_size, 3]`` or
    ``input_shape``. The initialisation is one compiled program: the
    forward pass that shapes the parameters is traced and never run (eagerly
    it was most of a decoder's set-up, a small compile an operation)."""
    example = getattr(model, "example_input", None)
    if example is not None and input_shape is None:
        inputs = example()
    else:
        shape = tuple(input_shape or (1, cfg.image_size, cfg.image_size, 3))
        inputs = jnp.ones(shape, jnp.float32)
    variables = jax.jit(partial(model.init, train=False))(rng, inputs)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = make_optimizer(cfg)
    opt_state = tx.init(params)
    ds = (dynamic_scale_lib.DynamicScale()
          if cfg.use_amp and cfg.amp_dtype == "float16" else None)
    ema = (jax.tree_util.tree_map(jnp.copy, {"params": params,
                                             "batch_stats": batch_stats})
           if getattr(cfg, "model_ema_decay", 0.0) > 0.0 else None)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      batch_stats=batch_stats, opt_state=opt_state,
                      dynamic_scale=ds, ema_params=ema)


def _is_count(x) -> bool:
    """A leaf of the statistics that is no statistic: an integer (the raw
    key of a model's noise), alike on every replica by construction."""
    return jnp.issubdtype(x.dtype, jnp.integer)


# Leaves of the statistics that no forward pass writes, by the name their
# model gives them: a router's correction bias (models/decoder.py), alike on
# every replica and to stay so to the bit (the replicas' mean of eight equal
# float32 values is not always that value).
CONSTANT_STATS = ("e_score_correction_bias",)


def mean_stats(stats: Any, axis_name: str) -> Any:
    """The replicas' mean of the running statistics; an integer leaf (a raw
    PRNG key the model advances) and a constant (``CONSTANT_STATS``) are
    replicated, never averaged."""
    def kept(path, x):
        return _is_count(x) or getattr(path[-1], "key", None) in CONSTANT_STATS
    flat = jax.tree_util.tree_leaves_with_path(stats)
    if not any(kept(path, x) for path, x in flat):
        return jax.lax.pmean(stats, axis_name=axis_name)   # as it ever was
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x if kept(path, x) else jax.lax.pmean(x, axis_name),
        stats)


def update_ema(cfg: Config, ema: Any, new_params: Any,
               new_stats: Any) -> Any:
    """torchvision-style model EMA over params AND BN buffers
    (ExponentialMovingAverage(use_buffers=True)): e = d*e + (1-d)*x after
    each optimizer step (no-op when EMA is off). Shared by the DP and GSPMD
    train steps."""
    if ema is None:
        return None
    d = cfg.model_ema_decay
    with jax.named_scope(scopes.OPTIMIZER):
        return jax.tree_util.tree_map(
            lambda e, x: x if _is_count(x) else d * e + (1.0 - d) * x, ema,
            {"params": new_params, "batch_stats": new_stats})


def _loss_fn(model: nn.Module, rng, params, batch_stats, images, labels,
             smoothing: float = 0.0, labels2=None, lam=None):
    # The scopes (tpudist/obs/scopes.py) label the HLO: a trace's device ops
    # group under them in XProf and in the chip benchmark's fwd/bwd/opt
    # split. Metadata only: the compiled program's FLOPs/memory are
    # unchanged — test_compiled_cost pins that.
    # A model that takes its loss itself (a language model: its logits are
    # never whole) is handed the targets and returns an ``ops.Scored``, which
    # the loss and the accuracy below read as they read logits.
    targets = {"targets": labels} if getattr(model, "takes_targets",
                                             False) else {}
    with jax.named_scope(scopes.FORWARD):
        outputs, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            images, train=True, mutable=["batch_stats", "intermediates"],
            rngs={"dropout": rng}, **targets)
    from tpudist.ops.mixup import mixed_ce
    with jax.named_scope(scopes.LOSS):
        loss = mixed_ce(outputs, labels, labels2, lam, smoothing)
        # Aux classifier heads (googlenet 0.3, inception_v3 0.4): their
        # logits are sown to 'intermediates' during training; weight them
        # into the loss so the aux params actually receive gradient
        # (torchvision's train recipe — without this they'd only be decayed
        # noise).
        aux_w = getattr(model, "aux_loss_weight", 0.0)
        if aux_w:
            for aux_logits in jax.tree_util.tree_leaves(
                    mutated.get("intermediates", {})):
                loss = loss + aux_w * mixed_ce(aux_logits, labels, labels2,
                                               lam, smoothing)
    return loss, (outputs, mutated.get("batch_stats", {}))


def model_counters(outputs) -> dict:
    """The counters a model's ``Scored`` carries (an expert layer's pairs,
    its fullest expert over the mean), for the step's metrics; nothing for
    logits."""
    return dict(getattr(outputs, "counters", None) or {})


def global_grad_norm(grads) -> jax.Array:
    """Global L2 norm over a gradient pytree — the doctor sentinel's second
    signal (a diverging run's grad norm explodes steps before the loss
    does; a non-finite one means the backward already blew up). Cheap: one
    fused reduction over buffers the step already holds."""
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree_util.tree_leaves(grads)]
    total = leaves[0]
    for x in leaves[1:]:
        total = total + x
    return jnp.sqrt(total)


def make_train_step(mesh: Mesh, model: nn.Module, cfg: Config,
                    data_axis: str = "data",
                    compress: str | None = None,
                    guard: bool = False) -> Callable:
    """Build the jitted SPMD train step: (state, images, labels, lr) →
    (state, metrics). ``images`` NHWC float32/uint8-normalized, sharded on the
    batch dim; state replicated; metrics are global means (already
    ``reduce_mean``-ed, reference ``distributed.py:254-255``).

    ``compress`` (resolved by the Trainer through ``ops/comm_dispatch`` —
    never raw config) swaps THE single gradient-reduction choke point:
    ``None`` keeps the dense ``lax.pmean`` bit-for-bit (same HLO as before
    the knob existed); ``"int8"`` runs the quantized two-phase exchange
    with the error-feedback residual carried in ``state.comm_state``
    (``parallel/comm.py``). Metric and BN-stat pmeans stay dense — they are
    bytes-trivial and their exactness is load-bearing.

    ``guard`` (``--doctor``, tpudist/doctor/): fuse the anomaly sentinels
    into the compiled step. The step additionally computes the global
    gradient L2 norm and a finiteness flag over (loss, grad norm); when the
    flag trips, the ENTIRE update is skipped GradScaler-style (params,
    optimizer moments, BN stats, EMA and comm residual all keep their
    pre-step values — a NaN batch must not poison the weights OR the
    running statistics) while ``state.step`` still advances. The flag and
    the norm ride the metrics dict, i.e. the existing deferred async
    metric drain — the guard adds NO host sync to the hot loop; the
    host-side policy engine reads them one step late from the drain."""
    tx = make_optimizer(cfg)
    base_rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)

    accum = max(1, int(getattr(cfg, "accum_steps", 1)))
    mixing = (getattr(cfg, "mixup_alpha", 0.0) > 0.0
              or getattr(cfg, "cutmix_alpha", 0.0) > 0.0)
    if compress not in (None, "int8"):
        raise ValueError(f"compress must be None or 'int8', got {compress!r}")
    if compress and cfg.use_amp and cfg.amp_dtype == "float16":
        # The fp16 GradScaler path reduces inside flax's DynamicScale
        # grad_fn, where there is no choke point to swap (config.finalize
        # rejects this combination loudly; this guards library callers).
        raise ValueError("--compress-grads does not compose with float16 "
                         "dynamic loss scaling; use bfloat16")

    def reduce_grads(grads, comm_state):
        """THE gradient-reduction choke point (DDP's C++ bucketed
        allreduce): dense pmean, or the compressed twin threading the
        error-feedback residual. Its scope is the collectives' own."""
        with jax.named_scope(scopes.GRAD_REDUCE):
            if compress is None:
                return jax.lax.pmean(grads, axis_name=data_axis), comm_state
            from tpudist.parallel.comm import compressed_pmean
            red, e_new = compressed_pmean(grads, comm_state["residual"][0],
                                          data_axis)
            return red, {"residual": e_new[None]}

    def step(state: TrainState, images, labels, lr):
        # Per-step, per-shard dropout key (torch: each DDP rank has its own
        # CPU/CUDA RNG stream; here it's derived, so runs are reproducible).
        rng = jax.random.fold_in(jax.random.fold_in(base_rng, state.step),
                                 jax.lax.axis_index(data_axis))
        labels2, lam = None, None
        if mixing:
            from tpudist.ops.mixup import mix_batch
            k_mix, rng = jax.random.split(rng)
            images, labels, labels2, lam = mix_batch(
                k_mix, images, labels, cfg.mixup_alpha, cfg.cutmix_alpha)

        if accum > 1:
            # Gradient accumulation: scan over microbatches so a global batch
            # far beyond one chip's activation memory (e.g. the reference's
            # 1200, distributed.py:52) still takes ONE optimizer step —
            # the shared accum_scan (parallel/_common.py) implements the
            # torch semantics (grads/metrics average, BN stats sequential);
            # one mixing draw per OPTIMIZER step, pair labels ride the scan.
            # fp16: GradScaler-with-accumulation ordering (torch.amp —
            # scale each microbatch's backward, ONE unscale/check/step):
            # the step's scale is FIXED across the scan, the finite check
            # and scale adjustment run once on the averaged grads below.
            from tpudist.parallel._common import (accum_scan, ds_finite,
                                                  ds_update,
                                                  scaled_value_and_grad)
            ds0 = state.dynamic_scale

            def per_mb(rng_i, stats, im_i, lb_i, *lb2_i):
                lf_i = partial(
                    _loss_fn, model, rng_i, smoothing=cfg.label_smoothing,
                    labels2=lb2_i[0] if lb2_i else None, lam=lam)
                if ds0 is not None:
                    loss_i, (outputs, stats), grads_i = scaled_value_and_grad(
                        lf_i, ds0.scale, state.params, stats, im_i, lb_i)
                else:
                    (loss_i, (outputs, stats)), grads_i = jax.value_and_grad(
                        lf_i, has_aux=True)(state.params, stats, im_i, lb_i)
                return grads_i, stats, (loss_i,
                                        accuracy(outputs, lb_i, topk=1))

            batch = (images, labels) + ((labels2,) if labels2 is not None
                                        else ())
            grads, new_stats, (loss, acc1) = accum_scan(
                per_mb, batch, state.batch_stats, rng, accum)
            grads, new_comm = reduce_grads(grads, state.comm_state)
            if ds0 is not None:
                # Post-pmean: the flag (and so the skip/scale decision) is
                # identical on every replica by construction.
                with jax.named_scope(scopes.OPTIMIZER):
                    is_finite = ds_finite(grads)
                    ds = ds_update(ds0, is_finite)
            else:
                ds, is_finite = None, None
        else:
            lf = partial(_loss_fn, model, rng, smoothing=cfg.label_smoothing,
                         labels2=labels2, lam=lam)
            if state.dynamic_scale is not None:
                # fp16 GradScaler parity (distributed_syncBN_amp.py:275-278):
                # scale → backward → unscale/check-finite → conditional step.
                grad_fn = state.dynamic_scale.value_and_grad(
                    lf, has_aux=True, axis_name=data_axis)
                ds, is_finite, (loss, aux), grads = grad_fn(
                    state.params, state.batch_stats, images, labels)
                outputs, new_stats = aux
                new_comm = state.comm_state
            else:
                grad_fn = jax.value_and_grad(lf, has_aux=True)
                (loss, (outputs, new_stats)), grads = grad_fn(
                    state.params, state.batch_stats, images, labels)
                # DDP gradient allreduce (distributed.py:144 → C++ Reducer):
                grads, new_comm = reduce_grads(grads, state.comm_state)
                ds, is_finite = None, None
            acc1 = accuracy(outputs, labels, topk=1)

        # Shared tail: BN-stat sync, SGD update, overflow skip, metric means.
        # Sync BN running stats across replicas so the replicated state stays
        # consistent (torch DDP keeps per-GPU stats and checkpoints rank 0's;
        # averaging is strictly more faithful to the data).
        # (Scopes = trace labels only; see _loss_fn.)
        with jax.named_scope(scopes.GRAD_REDUCE):
            new_stats = mean_stats(new_stats, data_axis)

        with jax.named_scope(scopes.OPTIMIZER):
            tx_state = state.opt_state
            tx_state.hyperparams["learning_rate"] = lr
            updates, new_opt_state = tx.update(grads, tx_state, state.params)
            new_params = optax.apply_updates(state.params, updates)

            if ds is not None:
                # Skip the update when grads overflowed (GradScaler.step
                # behavior).
                new_params = jax.tree_util.tree_map(
                    partial(jnp.where, is_finite), new_params, state.params)
                new_opt_state = jax.tree_util.tree_map(
                    partial(jnp.where, is_finite), new_opt_state,
                    state.opt_state)

        # reduce_mean of loss/acc (distributed.py:78-82,254-255), fused in-program.
        with jax.named_scope(scopes.METRICS):
            metrics = {
                "loss": jax.lax.pmean(loss, axis_name=data_axis),
                "acc1": jax.lax.pmean(acc1, axis_name=data_axis),
            }
            counters = model_counters(outputs) if accum == 1 else {}
            if counters:
                metrics.update(jax.lax.pmean(counters, axis_name=data_axis))
        if guard:
            # Doctor sentinels: global grad norm + finiteness of (mean loss,
            # grad norm). ``grads`` is post-reduction, so both signals are
            # identical on every replica by construction — the skip decision
            # can never diverge the gang. On a tripped flag the whole update
            # is zeroed (GradScaler-style): params, moments, BN stats, and
            # the error-feedback residual all keep their pre-step values.
            with jax.named_scope(scopes.METRICS):
                gnorm = global_grad_norm(grads)
                ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
                if ds is not None:
                    # fp16 dynamic loss scaling: an overflow step is the
                    # scaler's jurisdiction — it already skipped params/opt
                    # and halved the scale (GradScaler semantics predate the
                    # doctor; torch's scaler doesn't flag them either).
                    # Counting scale-search overflows as doctor skips would
                    # escalate a healthy warm-up into a spurious
                    # persistent_nonfinite rollback. The sentinel only flags
                    # anomalies the scaler calls finite — but the overflow is
                    # still REPORTED (scaler_skip) so the host can tell a
                    # bounded scale search from data that is NaN at any scale
                    # (the doctor escalates those on a larger budget).
                    ok = ok | jnp.logical_not(is_finite)
                    metrics["scaler_skip"] = 1.0 - is_finite.astype(
                        jnp.float32)
            with jax.named_scope(scopes.OPTIMIZER):
                new_params = jax.tree_util.tree_map(
                    partial(jnp.where, ok), new_params, state.params)
                new_opt_state = jax.tree_util.tree_map(
                    partial(jnp.where, ok), new_opt_state, state.opt_state)
                new_stats = jax.tree_util.tree_map(
                    partial(jnp.where, ok), new_stats, state.batch_stats)
                if new_comm is not None:
                    new_comm = jax.tree_util.tree_map(
                        partial(jnp.where, ok), new_comm, state.comm_state)
            with jax.named_scope(scopes.METRICS):
                metrics["notfinite"] = 1.0 - ok.astype(jnp.float32)
                metrics["gnorm"] = gnorm
        ema = update_ema(cfg, state.ema_params, new_params, new_stats)
        if guard and ema is not None:
            # A skipped step must not advance the EMA either (averaging the
            # unchanged params would still decay the average).
            with jax.named_scope(scopes.OPTIMIZER):
                ema = jax.tree_util.tree_map(
                    partial(jnp.where, ok), ema, state.ema_params)
        with jax.named_scope(scopes.OPTIMIZER):
            next_step = state.step + 1
        new_state = state.replace(step=next_step, params=new_params,
                                  batch_stats=new_stats, opt_state=new_opt_state,
                                  dynamic_scale=ds, ema_params=ema,
                                  comm_state=new_comm)
        return new_state, metrics

    from tpudist.parallel._common import donated_jit
    if compress is None:
        # Bit-compat with the pre-compression builder: same specs, same HLO.
        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(data_axis), P(data_axis), P()),
            out_specs=(P(), P()),
            check_vma=False)
        return donated_jit(sharded)

    # Compressed path: comm_state shards its (world, n) residual over the
    # data axis while everything else stays replicated — the spec tree
    # depends on the concrete state structure, so the wrapper is built
    # lazily on first call (parallel/_common.lazy_step: one wrapper = one
    # compile cache, with .lower forwarded for telemetry introspection).
    from tpudist.parallel._common import lazy_step

    def build(state):
        if state.comm_state is None:
            raise ValueError(
                "compress='int8' needs state.comm_state (the "
                "error-feedback residual) — seed it with "
                "parallel.comm.init_comm_state(params, world)")
        from tpudist.parallel.tensor_parallel import tree_specs
        specs = tree_specs(mesh, state, (), opt_shard_axis=data_axis,
                           zero_mode="comm")
        return donated_jit(shard_map(
            step, mesh=mesh,
            in_specs=(specs, P(data_axis), P(data_axis), P()),
            out_specs=(specs, P()),
            check_vma=False))

    return lazy_step(build)


def make_eval_step(mesh: Mesh, model: nn.Module, cfg: Config,
                   data_axis: str = "data",
                   state_specs: Any = None) -> Callable:
    """Jitted eval step (reference ``validate``, ``distributed.py:286-334``):
    forward with running BN stats, no_grad, global-mean loss/acc.

    ``state_specs``: optional full-structure PartitionSpec tree for the state
    (default: fully replicated). The expert-parallel path passes its split
    layout (expert FFN leaves sharded over the batch/expert axis)."""
    def step(state: TrainState, images, labels):
        targets = {"targets": labels} if getattr(model, "takes_targets",
                                                 False) else {}
        with jax.named_scope(scopes.EVAL_FORWARD):
            outputs = model.apply(
                {"params": state.params, "batch_stats": state.batch_stats},
                images, train=False, **targets)
        loss = cross_entropy_loss(outputs, labels)
        acc1 = accuracy(outputs, labels, topk=1)
        return {
            "loss": jax.lax.pmean(loss, axis_name=data_axis),
            "acc1": jax.lax.pmean(acc1, axis_name=data_axis),
        }

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P() if state_specs is None else state_specs,
                  P(data_axis), P(data_axis)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(sharded)
