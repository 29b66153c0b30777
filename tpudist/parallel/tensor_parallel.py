"""Tensor (model) parallelism via GSPMD sharding rules.

No reference equivalent — the reference implements only data parallelism
(SURVEY.md §2.2, `distributed.py:144`) — but the framework keeps a ``model``
mesh axis open, and this module fills it the TPU-native way: instead of
hand-writing Megatron-style split layers + explicit collectives (the
CUDA-world design), we keep the model code unchanged, annotate *parameter*
shardings with ``PartitionSpec`` rules, and let XLA's SPMD partitioner insert
the all-reduces/all-gathers and schedule them on ICI.

The ViT rules are the Megatron pattern expressed declaratively:

- ``in_proj``  [D, 3D]  → split the output dim over ``model``; the kernel's
  column layout is head-major ([h][q|k|v][head_dim], see
  ``models/vit.py:MultiHeadAttention``), so when the axis size divides
  ``num_heads`` each shard holds whole heads and attention is head-local;
- ``out_proj`` [Dh, D]  → split the input (head) dim — the contraction over
  the sharded dim becomes one psum per attention block;
- ``mlp_0``    [D, M]   → split the hidden dim;
- ``mlp_3``    [M, D]   → split the input dim — one psum per MLP block;
- everything else (LayerNorms, embeddings, head) replicated.

Because the train step runs on *global* arrays under ``jit`` (not shard_map),
gradient allreduce over the data axis, loss averaging over the global batch,
and cross-replica BN (stats over the global batch = SyncBN) all fall out of
the partitioner automatically — the GSPMD twin of the shard_map path in
``tpudist/train.py``.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Sequence

import jax
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.config import Config
from tpudist.obs import scopes
from tpudist.ops import accuracy, cross_entropy_loss

# (path-regex, spec) pairs, first match wins; path is '/'-joined tree keys.
Rules = Sequence[tuple[str, P]]

# Megatron-style sharding for the ViT family (tpudist/models/vit.py layer names).
VIT_RULES: Rules = (
    (r"in_proj/kernel$", P(None, "model")),
    (r"in_proj/bias$", P("model")),
    (r"out_proj/kernel$", P("model", None)),
    (r"mlp_0/kernel$", P(None, "model")),
    (r"mlp_0/bias$", P("model")),
    (r"mlp_3/kernel$", P("model", None)),
)

# ConvNeXt: the per-position MLP pair (mlp_fc1 [D,4D] / mlp_fc2 [4D,D],
# tpudist/models/convnext.py:CNBlock) is the same Megatron split as ViT's MLP;
# the 7x7 depthwise convs and LayerNorms stay replicated (channel-sharding a
# depthwise conv buys nothing — no cross-channel contraction).
CONVNEXT_RULES: Rules = (
    (r"mlp_fc1/kernel$", P(None, "model")),
    (r"mlp_fc1/bias$", P("model")),
    (r"mlp_fc2/kernel$", P("model", None)),
)

# Swin: attention shards like ViT's — the qkv kernel is head-major
# ([h][q|k|v][head_dim] columns, models/swin.py WindowAttention), so a
# column split lands on whole heads when the axis divides the stage's head
# count; per-head side params (bias table columns, v2 logit_scale and the
# cpb MLP's head-sized output) split on the same head dim, and the output
# projection contracts the sharded head dim into one psum. Stages whose
# head count the axis doesn't divide stay CORRECT under GSPMD (the
# partitioner reshards at the head reshape; swin_t stage0 has 3 heads), and
# their head-sized side params fall back to replicated via spec_for_leaf's
# divisibility check.
SWIN_RULES: Rules = (
    (r"attn/qkv/kernel$", P(None, "model")),
    (r"attn/qkv/bias$", P("model")),
    (r"attn/proj/kernel$", P("model", None)),
    (r"attn/relative_position_bias_table$", P(None, "model")),
    (r"attn/logit_scale$", P("model")),
    (r"attn/cpb_mlp_2/kernel$", P(None, "model")),
    # (?<!cpb_) keeps the v2 continuous-position-bias MLP's HIDDEN layer
    # (cpb_mlp_0, a tiny 2x512 per-attention net) replicated — its output
    # layer shards on heads above, and the block MLP pair shards below.
    (r"(?<!cpb_)mlp_0/kernel$", P(None, "model")),
    (r"(?<!cpb_)mlp_0/bias$", P("model")),
    (r"mlp_3/kernel$", P("model", None)),
)

# -- conv-family TP: channel-sharded convs (ISSUE 12) -------------------------
# The conv twin of the Megatron split: every conv kernel (flax HWIO layout)
# cuts its OUTPUT-channel dim over ``model``, so each shard computes 1/tp of
# the output channels (conv FLOPs and params shard; the partitioner inserts
# the channel all-gather where a consumer needs full input channels), and
# every BN/bias per-channel vector cuts on the same channel dim. BN
# *statistics* are computed in the global trace (models/layers.py): the
# batch mean over a data-sharded
# activation IS SyncBN (partitioner-reduced over ``data``), and the
# per-channel stat vectors shard over ``model`` with their params. Heads
# (fc/classifier) that contract into a small class dim stay replicated,
# except VGG's 4096-wide classifier pair, which is a textbook Megatron
# column/row split.
_CONV_OUT = P(None, None, None, "model")      # HWIO: cut output channels

# ResNet family: conv\d* covers the stem conv1, the block conv1..conv3, and
# (via search) downsample_conv; bn\d* likewise covers bn1..bn3 and
# downsample_bn, params and batch_stats alike (mean/var ride the same
# channel cut). resnext/wide_resnet share these module names but keep their
# grouped-conv trunks pure-DP (NO_TP_FAMILIES) until the grouped split has
# its own rules.
RESNET_RULES: Rules = (
    (r"conv\d*/kernel$", _CONV_OUT),
    (r"bn\d*/(scale|bias|mean|var)$", P("model")),
)

# VGG: features_N is the conv (kernel + torch's conv bias) or, in the _bn
# variants, the BatchNorm at that torchvision Sequential index — one channel
# rule covers both; the 4096-wide classifier pair is the Megatron MLP split
# (column then row, one psum before classifier_6).
VGG_RULES: Rules = (
    (r"features_\d+/kernel$", _CONV_OUT),
    (r"features_\d+/(bias|scale|mean|var)$", P("model")),
    (r"classifier_0/kernel$", P(None, "model")),
    (r"classifier_0/bias$", P("model")),
    (r"classifier_3/kernel$", P("model", None)),
)

# DenseNet: conv\d* covers the conv0 stem, denselayer conv1/conv2, and (via
# search) transitionN_conv; norm\d* covers norm0/1/2/5 and transitionN_norm.
# The channel concat of dense connectivity reshards at the partitioner's
# discretion — correctness is the rule table's job, placement the
# partitioner's.
DENSENET_RULES: Rules = (
    (r"conv\d*/kernel$", _CONV_OUT),
    (r"norm\d*/(scale|bias|mean|var)$", P("model")),
)

# The empty table every unruled arch resolves to (kept as an explicit
# constant so the trainer treats ruled and unruled families uniformly and
# SHARD03 can name it).
DEFAULT_RULES: Rules = ()

# Families DELIBERATELY left pure-DP (empty rule table): grouped/depthwise
# trunks (resnext, mobilenet, shufflenet, …) need a grouped-conv split rule
# that does not exist yet, tiny trunks (alexnet, squeezenet) have nothing
# worth cutting, and maxvit's biased windowed attention is out of scope for
# the declarative rules. This tuple is the explicit no-TP annotation
# ``tpudist-check``'s SHARD03 requires: a family registered in
# models/__init__.py that resolves to an empty rule table and is NOT listed
# here fails the static gate — the silent-pure-DP hole (VERDICT r5 weak #3)
# can no longer reopen by registering a new arch and forgetting the rules.
# require_rules() stays the runtime guard for split axes. (ISSUE 12 removed
# resnet, vgg and densenet: they carry real channel-sharded rules above.)
NO_TP_FAMILIES = (
    "resnext", "wide_resnet", "alexnet", "squeezenet",
    "mobilenet", "shufflenet", "mnasnet", "googlenet",
    "inception", "efficientnet", "regnet", "maxvit",
    # the decoder of tokens divides a layer by expert and vocabulary share
    # (models/decoder.py), not by a 'model' axis: no rule table yet
    "mellum2", "sdar", "nemotron3", "ouro", "joyai",
)


def rules_for(arch: str) -> Rules:
    if arch.startswith("vit"):
        return VIT_RULES
    if arch.startswith("convnext"):
        return CONVNEXT_RULES
    if arch.startswith("swin"):
        return SWIN_RULES
    if arch.startswith("resnet"):
        return RESNET_RULES
    if arch.startswith("vgg"):
        return VGG_RULES
    if arch.startswith("densenet"):
        return DENSENET_RULES
    return DEFAULT_RULES


def require_rules(arch: str, mesh: Mesh, model_axis: str = "model") -> Rules:
    """``rules_for`` with the silent-no-op hole closed (VERDICT r5 weak #3):
    a mesh that actually SPLITS the model axis combined with an arch whose
    rule table is empty would run pure DP through the GSPMD path — no error,
    no log, no sharding, devices wasted. Refuse loudly instead. A size-1
    model axis stays legal (a degenerate axis shards nothing, by
    construction) but gets a loud one-line warning: the user ASKED for a
    model axis, and for this arch it will never do anything — a sweep that
    later widens the axis should not be the first time they hear the rule
    table is empty."""
    rules = rules_for(arch)
    if model_axis in mesh.shape and mesh.shape[model_axis] == 1 and not rules:
        import warnings
        warnings.warn(
            f"mesh declares a (size-1) '{model_axis}' axis but arch "
            f"'{arch}' has an EMPTY tensor-parallel rule table "
            f"(parallel/tensor_parallel.py rules_for): the axis is a no-op "
            f"for this arch and widening it will be refused. Use a ruled "
            f"family (vit*/convnext*/swin*/resnet*/vgg*/densenet*) or "
            f"drop the axis.",
            RuntimeWarning, stacklevel=2)
    if model_axis in mesh.shape and mesh.shape[model_axis] > 1 and not rules:
        raise ValueError(
            f"mesh splits axis '{model_axis}' ×{mesh.shape[model_axis]} but "
            f"arch '{arch}' has an EMPTY tensor-parallel rule table "
            f"(parallel/tensor_parallel.py rules_for): the run would "
            f"silently execute pure data parallelism on 1/"
            f"{mesh.shape[model_axis]} of the requested useful devices. "
            f"Use a ruled family (vit*/convnext*/swin*/resnet*/vgg*/"
            f"densenet*), drop the '{model_axis}' axis, or add sharding "
            f"rules for this arch")
    return rules


def _path_str(path) -> str:
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        elif hasattr(entry, "name"):
            parts.append(str(entry.name))
        else:
            parts.append(str(entry))
    return "/".join(parts)


def spec_for_leaf(path, leaf, rules: Rules, mesh: Mesh) -> P:
    """Resolve the PartitionSpec for one tree leaf. Falls back to replicated
    when no rule matches, the leaf is not an array, the rule's rank doesn't
    fit, or the sharded dim isn't divisible by the mesh axis (a silent wrong
    sharding would be worse than a replicated param)."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return P()
    name = _path_str(path)
    for pattern, spec in rules:
        if re.search(pattern, name):
            if len(spec) > len(shape):
                return P()
            for dim, axis in enumerate(spec):
                if axis is None:
                    continue
                if shape[dim] % mesh.shape[axis] != 0:
                    return P()
            return spec
    return P()


# Which TrainState subtrees each ZeRO mode cuts over the data axis
# (everything else replicated unless a TP rule claims it). "1" = the
# original opt_shard_axis behavior (arXiv:2004.13336's optimizer-state
# sharding, leading dim only); "full" extends the cut to params + the EMA
# copy on each leaf's LARGEST divisible dim (elastic.reshard.zero_full_axis
# — conv kernels lead with 3×3 spatial dims, so a leading-dim rule would
# leave the bulk of a convnet replicated), plus the error-feedback
# comm_state, which always cuts on dim 0 (row r IS rank r's residual);
# "comm" shards ONLY the residual (the DP path under --compress-grads).
ZERO_PREFIXES: dict[str, tuple[str, ...]] = {
    "1": ("opt_state",),
    "full": ("opt_state", "params", "ema_params", "comm_state"),
    "comm": ("comm_state",),
}


def tree_specs(mesh: Mesh, tree: Any, rules: Rules,
               opt_shard_axis: str | None = None,
               zero_mode: str | None = None) -> Any:
    """The raw ``PartitionSpec`` tree behind ``tree_shardings`` — shared
    with the shard_map step builders (``parallel/comm.py``) so the specs a
    step compiles against can never drift from where ``shard_tree`` placed
    the arrays. ``zero_mode`` selects which state subtrees the data axis
    cuts and on which dim (``ZERO_PREFIXES``); the default
    (``opt_shard_axis`` set, no mode) is the original zero1 behavior."""
    zm = zero_mode if zero_mode is not None \
        else ("1" if opt_shard_axis is not None else None)
    prefixes = ZERO_PREFIXES.get(zm, ()) if zm else ()

    def spec(path, leaf):
        s = spec_for_leaf(path, leaf, rules, mesh)
        if not (opt_shard_axis is not None and prefixes and s == P()
                and path and _path_str(path[:1]) in prefixes):
            return s
        shape = getattr(leaf, "shape", None)
        if not shape:
            return s
        world = mesh.shape[opt_shard_axis]
        root = _path_str(path[:1])
        if zm == "full" and root != "comm_state":
            if root == "ema_params" and len(path) > 1 \
                    and _path_str(path[1:2]) == "batch_stats":
                # The EMA's BUFFER half averages against new_stats, which
                # stays replicated (its pmean has no sharded form) — a
                # sharded EMA-stats leaf would shape-mismatch the update.
                return s
            from tpudist.elastic.reshard import zero_full_axis
            ax = zero_full_axis(shape, world)
            if ax is None:
                return s
            return P(*([None] * ax + [opt_shard_axis]))
        if len(shape) >= 1 and shape[0] > 0 and shape[0] % world == 0:
            return P(opt_shard_axis)
        return s

    return jax.tree_util.tree_map_with_path(spec, tree)


def tree_shardings(mesh: Mesh, tree: Any, rules: Rules,
                   opt_shard_axis: str | None = None,
                   zero_mode: str | None = None) -> Any:
    """Map a pytree (params, opt_state, or a whole TrainState) to a pytree of
    ``NamedSharding``. Optimizer momentum buffers pick up their param's rule
    automatically because their tree paths contain the param names.

    ``opt_shard_axis`` enables cross-replica weight-update sharding (ZeRO-1 /
    arXiv:2004.13336, the XLA formulation): optimizer-state leaves that no
    TP rule claims shard their leading dim over the given (data) axis. With
    those in/out shardings on the jitted step, the SPMD partitioner turns
    the gradient all-reduce into reduce-scatter → sharded moment/param
    update → all-gather — per-device optimizer memory drops by the axis size
    (2× params for AdamW moments) at equal collective volume.
    ``zero_mode="full"`` widens the cut to params/EMA/comm_state (ZeRO-full:
    the shard_map wus step in ``parallel/comm.py`` owns the explicit
    gather/scatter). Both require a WHOLE TrainState tree: subtrees are
    recognized by their path's first attribute, so a bare opt_state subtree
    would shard nothing."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        tree_specs(mesh, tree, rules, opt_shard_axis, zero_mode),
        is_leaf=lambda x: isinstance(x, P))


def shard_tree(mesh: Mesh, tree: Any, rules: Rules,
               opt_shard_axis: str | None = None,
               zero_mode: str | None = None) -> Any:
    """Place a (host or replicated) pytree onto the mesh per the rules."""
    shardings = tree_shardings(mesh, tree, rules, opt_shard_axis, zero_mode)
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


# (r5: the flash-under-TP refusal is gone — flash_attention_spmd wraps the
# Pallas kernel in a nested manual region over the ambient mesh's
# batch/head axes, so the GSPMD path composes with --flash; the step
# builders below provide the ambient mesh via jax.sharding.set_mesh.)


def make_gspmd_train_step(mesh: Mesh, model: nn.Module, cfg: Config,
                          rules: Rules | None = None,
                          data_axis: str = "data",
                          opt_shard_axis: str | None = None) -> Callable:
    """GSPMD train step: (state, images, labels, lr) → (state, metrics).

    Input batch sharded ``P(data_axis)`` on its leading dim; state sharded per
    ``rules`` (params + optimizer moments on the ``model`` axis where rules
    say so, replicated otherwise). Semantics match
    ``tpudist.train.make_train_step``: the cfg-dispatched optimizer
    (torch-SGD or AdamW via make_optimizer), CE loss, global-mean metrics —
    the reference hot loop `distributed.py:237-273` as one XLA program.
    """
    import jax.numpy as jnp

    from tpudist.train import (TrainState, make_optimizer,  # circular-import guard
                               update_ema)

    if rules is None:
        rules = require_rules(cfg.arch, mesh)
    accum = max(1, int(getattr(cfg, "accum_steps", 1)))
    # Build-time user-error guards (ValueError, never assert — _common.py).
    # (fp16 × accum composes since r5 — fixed scale across the scan, one
    # finite-check/step/update; see train.py's accum branch.)
    if accum > 1 and cfg.batch_size % accum:
        raise ValueError(
            f"global batch {cfg.batch_size} not divisible by "
            f"accum_steps={accum}")
    tx = make_optimizer(cfg)
    base_rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
    batch_sh = NamedSharding(mesh, P(data_axis))
    repl = NamedSharding(mesh, P())
    mixing = (getattr(cfg, "mixup_alpha", 0.0) > 0.0
              or getattr(cfg, "cutmix_alpha", 0.0) > 0.0)

    def step(state: TrainState, images, labels, lr):
        # Per-step dropout key (the GSPMD partitioner shards the global mask)
        rng = jax.random.fold_in(base_rng, state.step)
        labels2, lam = None, None
        if mixing:
            # Global-batch pairing (the shard_map DP path pairs per shard);
            # the partitioner turns the gather of permuted partners into the
            # appropriate collective.
            from tpudist.ops.mixup import mix_batch
            k_mix, rng = jax.random.split(rng)
            images, labels, labels2, lam = mix_batch(
                k_mix, images, labels, cfg.mixup_alpha, cfg.cutmix_alpha)

        def loss_fn(params, stats, im, lb, lb2, rng_i):
            variables = {"params": params}
            rngs = {"dropout": rng_i}
            if stats:
                variables["batch_stats"] = stats
            with jax.named_scope(scopes.FORWARD):
                outputs, mutated = model.apply(
                    variables, im, train=True,
                    mutable=["batch_stats", "intermediates"], rngs=rngs)
            new_stats = mutated.get("batch_stats", stats)

            from tpudist.ops.mixup import mixed_ce

            def ce(logits):
                return mixed_ce(logits, lb, lb2, lam, cfg.label_smoothing)

            with jax.named_scope(scopes.LOSS):
                loss = ce(outputs)                   # global-batch mean
                # Sown aux-classifier logits (googlenet/inception) weighted
                # into the loss, mirroring tpudist.train._loss_fn — the GSPMD
                # path must not silently drop aux gradients.
                aux_w = getattr(model, "aux_loss_weight", 0.0)
                if aux_w:
                    for aux_logits in jax.tree_util.tree_leaves(
                            mutated.get("intermediates", {})):
                        loss = loss + aux_w * ce(aux_logits)
            return loss, (outputs, new_stats)

        if accum > 1:
            # Gradient accumulation, GSPMD flavor (same semantics as every
            # other path — the shared accum_scan in _common.py): scan over
            # GLOBAL microbatches — each still data-sharded — averaging
            # grads and threading BN stats sequentially; ONE optimizer step.
            # fp16 composes like the DP path (train.py): fixed scale across
            # the scan, one finite-check + scale adjustment on the averaged
            # grads (torch GradScaler-with-accumulation ordering).
            from tpudist.parallel._common import (accum_scan, ds_finite,
                                                  ds_update,
                                                  scaled_value_and_grad)
            ds0 = state.dynamic_scale

            def per_mb(rng_i, stats, im_i, lb_i, *lb2_i):
                args = (state.params, stats, im_i, lb_i,
                        lb2_i[0] if lb2_i else None, rng_i)
                if ds0 is not None:
                    loss_i, (outputs, stats), grads_i = scaled_value_and_grad(
                        loss_fn, ds0.scale, *args)
                else:
                    (loss_i, (outputs, stats)), grads_i = jax.value_and_grad(
                        loss_fn, has_aux=True)(*args)
                return grads_i, stats, (loss_i,
                                        accuracy(outputs, lb_i, topk=1))

            batch = (images, labels) + ((labels2,) if labels2 is not None
                                        else ())
            grads, new_stats, (loss, acc1) = accum_scan(
                per_mb, batch, state.batch_stats, rng, accum)
            if ds0 is not None:
                # Grads of the global-mean loss are already fully reduced by
                # the partitioner, so the flag is globally consistent.
                is_finite = ds_finite(grads)
                ds = ds_update(ds0, is_finite)
            else:
                ds, is_finite = None, None
        elif state.dynamic_scale is not None:
            # fp16 GradScaler parity (distributed_syncBN_amp.py:275-278):
            # scale → backward → unscale/check-finite → conditional step. No
            # axis_name: the global-mean loss already reduces over the
            # partitioner's data sharding.
            grad_fn = state.dynamic_scale.value_and_grad(
                loss_fn, has_aux=True)
            ds, is_finite, (loss, (outputs, new_stats)), grads = grad_fn(
                state.params, state.batch_stats, images, labels, labels2, rng)
            acc1 = accuracy(outputs, labels, topk=1)
        else:
            (loss, (outputs, new_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.batch_stats,
                                       images, labels, labels2, rng)
            # No explicit pmean: grads of a global-mean loss over a
            # data-sharded batch already carry the partitioner-inserted
            # reduce.
            ds, is_finite = None, None
            acc1 = accuracy(outputs, labels, topk=1)

        with jax.named_scope(scopes.OPTIMIZER):
            tx_state = state.opt_state
            tx_state.hyperparams["learning_rate"] = lr
            updates, new_opt_state = tx.update(grads, tx_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            if ds is not None:
                # Skip the update when grads overflowed (GradScaler.step).
                from functools import partial
                new_params = jax.tree_util.tree_map(
                    partial(jnp.where, is_finite), new_params, state.params)
                new_opt_state = jax.tree_util.tree_map(
                    partial(jnp.where, is_finite), new_opt_state,
                    state.opt_state)
        metrics = {"loss": loss, "acc1": acc1}
        ema = update_ema(cfg, state.ema_params, new_params, new_stats)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_stats,
                                  opt_state=new_opt_state,
                                  dynamic_scale=ds, ema_params=ema)
        return new_state, metrics

    # Shardings depend on the concrete state tree, so the jit wrapper is
    # built lazily on first call and cached (parallel/_common.lazy_step —
    # .lower forwarded for telemetry, calls wrapped in set_mesh(mesh): the
    # ambient mesh for trace-time consumers like flash_attention_spmd,
    # whose Pallas kernel nests a manual region over these axes).
    from tpudist.parallel._common import donated_jit, lazy_step

    def build(state):
        st_sh = tree_shardings(mesh, state, rules, opt_shard_axis)
        return donated_jit(
            step, in_shardings=(st_sh, batch_sh, batch_sh, repl),
            out_shardings=(st_sh, repl))

    return lazy_step(build, mesh=mesh)


def make_gspmd_eval_step(mesh: Mesh, model: nn.Module, cfg: Config,
                         rules: Rules | None = None,
                         data_axis: str = "data",
                         opt_shard_axis: str | None = None) -> Callable:
    """GSPMD eval step (reference ``validate``, `distributed.py:286-334`)."""
    if rules is None:
        rules = require_rules(cfg.arch, mesh)
    batch_sh = NamedSharding(mesh, P(data_axis))
    repl = NamedSharding(mesh, P())

    def step(state, images, labels):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        outputs = model.apply(variables, images, train=False)
        return {"loss": cross_entropy_loss(outputs, labels),
                "acc1": accuracy(outputs, labels, topk=1)}

    from tpudist.parallel._common import lazy_step

    def build(state):
        st_sh = tree_shardings(mesh, state, rules, opt_shard_axis)
        return jax.jit(step, in_shardings=(st_sh, batch_sh, batch_sh),
                       out_shardings=repl)

    return lazy_step(build, mesh=mesh)   # see make_gspmd_train_step
