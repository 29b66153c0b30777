"""Pipeline-parallel Vision Transformer: the zoo consumer of the 'pipe' mesh
axis (SURVEY.md §2.2 row "PP" — no reference equivalent; this makes GPipe
pipeline parallelism a Trainer config state).

Layout: patchify/pos-embed and the classifier head are replicated; the
encoder trunk is an ``nn.scan``-stacked layer stack whose leading layer dim
shards over the ``pipe`` axis — device d holds layers [d·L/S, (d+1)·L/S).
The GPipe microbatch schedule (M microbatches streaming through S stages,
one ``lax.ppermute`` hop per tick, M+S-1 ticks) is itself a lifted
``nn.scan`` with broadcast params, so the WHOLE pipeline — forward and its
transpose (the backward pipeline, fill/drain bubble included) — is one
differentiable SPMD program. No per-stage processes, no send/recv, no
hand-written 1F1B (cf. ``tpudist/parallel/pipeline.py``).

Init-vs-apply twin (same pattern as the SP/EP models): collectives cannot be
traced outside shard_map, so ``pipe_axis=None`` builds the dense twin — the
same scanned trunk with the FULL [L] layer dim — used for ``model.init``,
checkpoints (topology-independent), and single-device runs. Param paths are
identical between the forms (``trunk/trunk/block/...``); only the leading
layer dim differs (global [L] vs local [L/S]), exactly like the MoE expert
leaves.

Gradient convention (derived from the ppermute/psum transposes; pinned by
tests/test_pipeline_parallel.py): seed the backward with loss/S — then trunk
grads come out exact and LOCAL (each device owns its layers' full gradient),
while replicated leaves (embed/head) need a ``psum`` over the pipe axis
(device 0 holds the embed cotangent — it injects every microbatch; the head
contributes (1/S)·dL/dhead per device). ``make_pp_train_step`` implements
this split.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from tpudist.models.vit import EncoderBlock, vit_workloads


class _ScanLayer(nn.Module):
    """One encoder layer in (carry, xs) form for nn.scan over layers."""
    num_heads: int
    mlp_dim: int
    dtype: Any = None
    flash: Optional[bool] = None
    model_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, _):
        y = EncoderBlock(self.num_heads, self.mlp_dim, self.dtype,
                         flash=self.flash, model_axis=self.model_axis,
                         name="block")(x)
        return y, None


def _layer_scan(n_layers: int, num_heads: int, mlp_dim: int, dtype,
                flash, name: str = "trunk", model_axis=None):
    """nn.scan-stacked encoder stack: params carry a leading [n_layers] dim."""
    scanned = nn.scan(_ScanLayer,
                      variable_axes={"params": 0},
                      split_rngs={"params": True},
                      length=n_layers)
    return scanned(num_heads, mlp_dim, dtype, flash, model_axis, name=name)


class _TrunkTwin(nn.Module):
    """Dense-twin trunk (named to mirror the pipelined form's param paths)."""
    num_layers: int
    num_heads: int
    mlp_dim: int
    dtype: Any = None
    flash: Optional[bool] = None

    @nn.compact
    def __call__(self, x):
        y, _ = _layer_scan(self.num_layers, self.num_heads, self.mlp_dim,
                           self.dtype, self.flash)(x, None)
        return y


class _PipeTick(nn.Module):
    """One pipeline tick: stage-0 injects microbatch t, every device runs its
    local layer slice, results hop to the ring neighbor."""
    local_layers: int
    num_heads: int
    mlp_dim: int
    num_microbatches: int
    pipe_axis: str
    dtype: Any = None
    flash: Optional[bool] = None
    model_axis: Optional[str] = None

    @nn.compact
    def __call__(self, carry, t):
        act, outs, xm = carry
        s = lax.axis_size(self.pipe_axis)
        idx = lax.axis_index(self.pipe_axis)
        m = self.num_microbatches
        x_t = lax.dynamic_index_in_dim(xm, jnp.clip(t, 0, m - 1), 0,
                                       keepdims=False)
        my_in = jnp.where(idx == 0, x_t, act)
        y, _ = _layer_scan(self.local_layers, self.num_heads, self.mlp_dim,
                           self.dtype, self.flash,
                           model_axis=self.model_axis)(my_in, None)
        # Microbatch v leaves the last stage at tick v + S - 1.
        v = t - (s - 1)
        updated = lax.dynamic_update_index_in_dim(
            outs, y.astype(outs.dtype), jnp.clip(v, 0, m - 1), 0)
        record = jnp.logical_and(jnp.logical_and(v >= 0, v < m), idx == s - 1)
        outs = jnp.where(record, updated, outs)
        act_next = lax.ppermute(y, self.pipe_axis,
                                [(j, (j + 1) % s) for j in range(s)])
        return (act_next, outs, xm), None


class PipelinedViT(nn.Module):
    """ViT with a pipeline-parallel encoder trunk.

    ``pipe_axis=None``: dense twin (full [L]-stacked trunk, plain forward).
    ``pipe_axis='pipe'``: call inside shard_map on a mesh with that axis;
    the trunk params must arrive sharded to the local [L/S] slice.
    """

    patch_size: int = 16
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    num_classes: int = 1000
    num_microbatches: int = 0          # 0 → pipe-axis size
    dtype: Any = None
    pipe_axis: Optional[str] = None
    model_axis: Optional[str] = None   # Megatron TP inside each stage (r3)
    # None → measurement-honest auto dispatch via MultiHeadAttention
    # (ops/attention_dispatch); True/False force the Pallas/XLA backend.
    flash: Optional[bool] = None
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

        if self.pipe_axis is None:
            x = _TrunkTwin(self.num_layers, self.num_heads, self.mlp_dim,
                           self.dtype, self.flash, name="trunk")(x)
        else:
            s = lax.axis_size(self.pipe_axis)
            assert self.num_layers % s == 0, (
                f"num_layers {self.num_layers} not divisible by pipe-axis "
                f"size {s}")
            m = self.num_microbatches or s
            assert b % m == 0, (
                f"local batch {b} not divisible by {m} microbatches")
            t, d = x.shape[1], x.shape[2]
            xm = x.reshape(m, b // m, t, d)
            tick = nn.scan(_PipeTick,
                           variable_broadcast="params",
                           split_rngs={"params": False},
                           length=m + s - 1)(
                self.num_layers // s, self.num_heads, self.mlp_dim,
                m, self.pipe_axis, self.dtype, self.flash,
                self.model_axis, name="trunk")
            carry0 = (jnp.zeros_like(xm[0]), jnp.zeros_like(xm), xm)
            (_, outs, _), _ = tick(carry0, jnp.arange(m + s - 1))
            # Only the last stage recorded real outputs; re-replicate.
            outs = lax.psum(outs, self.pipe_axis)
            x = outs.reshape(b, t, d)

        x = nn.LayerNorm(dtype=jnp.float32, name="ln")(x)
        return nn.Dense(self.num_classes, dtype=self.dtype,
                        name="head")(x[:, 0].astype(self.dtype or x.dtype))

    def attention_workloads(self, image_size: int) -> list[dict]:
        return vit_workloads(self, image_size)


def _vit_pipe(patch, hidden, layers, heads, mlp):
    def ctor(num_classes: int = 1000, dtype: Any = None,
             pipe_axis: Optional[str] = None, num_microbatches: int = 0,
             model_axis: Optional[str] = None,
             flash: Optional[bool] = None, **kw) -> PipelinedViT:
        kw.pop("sync_batchnorm", None)
        kw.pop("bn_axis_name", None)
        return PipelinedViT(patch_size=patch, hidden_dim=hidden,
                            num_layers=layers, num_heads=heads, mlp_dim=mlp,
                            num_classes=num_classes, dtype=dtype,
                            pipe_axis=pipe_axis, model_axis=model_axis,
                            num_microbatches=num_microbatches,
                            flash=flash, **kw)
    return ctor


vit_pipe_b_16 = _vit_pipe(16, 768, 12, 12, 3072)
vit_pipe_s_16 = _vit_pipe(16, 384, 12, 6, 1536)
