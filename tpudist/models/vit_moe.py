"""Mixture-of-Experts Vision Transformer: the zoo consumer of expert
parallelism (SURVEY.md §2.2 row "EP/MoE" — no reference equivalent; this is
the framework's 'expert' mesh axis made trainable end to end).

Architecture: a ViT whose MLPs are Switch-style top-1-routed expert FFNs in
every OTHER encoder block (the standard MoE-transformer layout, cf. Switch
Transformer/V-MoE — interleaving keeps router count and aux-loss pressure
moderate). Attention, LayerNorms, patchify and the router are replicated;
expert FFN weights carry a leading ``[num_experts]`` dim that the expert-
parallel step shards over the ``expert`` mesh axis (expert e's weights live
on device e; tokens reach it via one ``lax.all_to_all`` each way —
``tpudist/parallel/moe.py``).

Init-vs-apply twin (same pattern as the sequence-parallel ViT): collectives
cannot be traced outside ``shard_map``, so ``expert_axis=None`` builds the
dense twin (identical param tree, vmapped experts, no capacity drops) used
for ``model.init`` and single-device runs; the expert-parallel step applies
the ``expert_axis='expert'`` form inside shard_map.

The Switch load-balancing auxiliary loss is sown into the ``losses``
collection as ``moe_aux`` (NOT ``intermediates`` — that collection carries
aux-classifier LOGITS for googlenet/inception and is consumed as such by
``_loss_fn``); the EP train step adds ``moe_aux_weight * aux`` to the task
loss. A plain-DP run of the dense twin ignores the sown value (sow into a
non-mutable collection is a no-op) and trains without the balance term.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpudist.models.vit import EncoderBlock, MultiHeadAttention, vit_workloads
from tpudist.parallel.moe import moe_dense, moe_spmd


class MoEMLP(nn.Module):
    """Switch top-1 MoE FFN over flattened tokens; params match
    ``parallel.moe.init_moe_params`` layout (router replicated, expert
    weights stacked on a leading [E] dim)."""

    num_experts: int
    mlp_dim: int
    expert_axis: Optional[str] = None
    capacity_factor: float = 2.0
    aux_axes: Optional[tuple] = None   # dp×ep: pmean f/p over ('data','expert')

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        e, h = self.num_experts, self.mlp_dim
        # Inside shard_map each device holds ONE expert's slice: declare the
        # LOCAL leading dim so flax's apply-time shape check matches (the
        # param tree itself is created by the dense twin with the full [E]
        # dim; the expert-parallel step's in_specs deliver the slice). The
        # router is replicated: always full [d, E].
        el = 1 if self.expert_axis is not None else e
        s1 = 1.0 / np.sqrt(d)
        s2 = 1.0 / np.sqrt(h)
        params = {
            "router": self.param(
                "router", lambda k: jax.random.normal(k, (d, e)) * s1),
            "w1": self.param(
                "w1", lambda k: jax.random.normal(k, (el, d, h)) * s1),
            "b1": self.param("b1", nn.initializers.zeros, (el, h)),
            "w2": self.param(
                "w2", lambda k: jax.random.normal(k, (el, h, d)) * s2),
            "b2": self.param("b2", nn.initializers.zeros, (el, d)),
        }
        tokens = x.reshape(b * t, d)
        if self.expert_axis is None:
            y, aux = moe_dense(params, tokens)
        else:
            y, aux = moe_spmd(params, tokens, axis_name=self.expert_axis,
                              capacity_factor=self.capacity_factor,
                              aux_axes=self.aux_axes)
        self.sow("losses", "moe_aux", aux)
        return y.reshape(b, t, d).astype(x.dtype)


class MoEEncoderBlock(nn.Module):
    """EncoderBlock with the dense MLP swapped for ``MoEMLP``."""

    num_heads: int
    mlp_dim: int
    num_experts: int
    dtype: Any = None
    expert_axis: Optional[str] = None
    capacity_factor: float = 2.0
    flash: Optional[bool] = None
    aux_axes: Optional[tuple] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        y = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        y = MultiHeadAttention(self.num_heads, self.dtype, flash=self.flash,
                               name="self_attention")(y.astype(x.dtype))
        x = x + y
        y = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        y = MoEMLP(self.num_experts, self.mlp_dim, self.expert_axis,
                   self.capacity_factor, aux_axes=self.aux_axes,
                   name="moe")(y.astype(x.dtype))
        return x + y


class MoEVisionTransformer(nn.Module):
    """ViT with MoE MLPs in every other encoder block (odd layers)."""

    patch_size: int = 16
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    num_experts: int = 8
    num_classes: int = 1000
    dtype: Any = None
    expert_axis: Optional[str] = None
    capacity_factor: float = 2.0
    # None → measurement-honest auto dispatch via MultiHeadAttention
    # (ops/attention_dispatch); True/False force the Pallas/XLA backend.
    flash: Optional[bool] = None
    aux_axes: Optional[tuple] = None   # dp×ep composition (see MoEMLP)
    # zoo-constructor uniformity (BN-free family)
    sync_batchnorm: bool = False
    bn_axis_name: str = "data"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        b = x.shape[0]
        p = self.patch_size
        x = x.astype(self.dtype or x.dtype)
        x = nn.Conv(self.hidden_dim, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, name="conv_proj")(x)
        x = x.reshape(b, -1, self.hidden_dim)

        cls = self.param("class_token", nn.initializers.zeros,
                         (1, 1, self.hidden_dim), jnp.float32)
        x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, self.hidden_dim)
                                              ).astype(x.dtype), x], axis=1)
        pos = self.param("pos_embedding", nn.initializers.normal(stddev=0.02),
                         (1, x.shape[1], self.hidden_dim), jnp.float32)
        x = x + pos.astype(x.dtype)

        for i in range(self.num_layers):
            if i % 2 == 1:
                x = MoEEncoderBlock(self.num_heads, self.mlp_dim,
                                    self.num_experts, self.dtype,
                                    self.expert_axis, self.capacity_factor,
                                    self.flash, aux_axes=self.aux_axes,
                                    name=f"encoder_layer_{i}")(x)
            else:
                x = EncoderBlock(self.num_heads, self.mlp_dim, self.dtype,
                                 flash=self.flash,
                                 name=f"encoder_layer_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln")(x)
        return nn.Dense(self.num_classes, dtype=self.dtype,
                        name="head")(x[:, 0].astype(self.dtype or x.dtype))

    def attention_workloads(self, image_size: int) -> list[dict]:
        return vit_workloads(self, image_size)


def _vit_moe(patch, hidden, layers, heads, mlp):
    def ctor(num_classes: int = 1000, dtype: Any = None,
             expert_axis: Optional[str] = None, num_experts: int = 8,
             capacity_factor: float = 2.0, aux_axes: Optional[tuple] = None,
             flash: Optional[bool] = None, **kw) -> MoEVisionTransformer:
        kw.pop("sync_batchnorm", None)
        kw.pop("bn_axis_name", None)
        return MoEVisionTransformer(
            patch_size=patch, hidden_dim=hidden, num_layers=layers,
            num_heads=heads, mlp_dim=mlp, num_experts=num_experts,
            num_classes=num_classes, dtype=dtype, expert_axis=expert_axis,
            capacity_factor=capacity_factor, flash=flash,
            aux_axes=aux_axes, **kw)
    return ctor


vit_moe_b_16 = _vit_moe(16, 768, 12, 12, 3072)
vit_moe_s_16 = _vit_moe(16, 384, 12, 6, 1536)
