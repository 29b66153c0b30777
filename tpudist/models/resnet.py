"""ResNet family (torchvision-compatible architecture) in flax/NHWC.

The reference builds its model by name from torchvision's zoo
(``models.__dict__[args.arch]()``, ``distributed.py:131-137``) with resnet18 as
the benchmarked flagship (``README.md:5``). This is the same architecture
(BasicBlock/Bottleneck, stage widths 64/128/256/512, 7x7 stem, maxpool,
global-avg-pool, fc) re-expressed TPU-first:

- NHWC layout (XLA:TPU's native conv layout — NCHW would transpose on every op);
- one BatchNorm module for plain-BN and SyncBN (see layers.py), so the
  reference's ``convert_sync_batchnorm`` pass (``distributed_syncBN_amp.py:145``)
  is a constructor flag instead of a model rewrite;
- compute dtype is a parameter: the bf16 "AMP" policy casts activations while
  params stay fp32 (master weights), matching autocast+GradScaler intent
  (``distributed_syncBN_amp.py:259,275-278``) without loss scaling (bf16 has
  fp32's exponent range).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Type

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.layers import BatchNorm, conv_kaiming, dense_torch


class _StemConvS2D(nn.Module):
    """The 7x7/stride-2 stem conv, computed via space-to-depth.

    A 3-channel 7x7 stem feeds the 128-lane MXU at ~2% input utilization —
    the dominant MFU headroom in a roofline estimate. The MLPerf-style fix: pack 2x2 pixel
    blocks into channels (H,W,3 -> H/2,W/2,12) and run the mathematically
    identical 4x4/stride-1 conv there (output rows i of the original conv
    read input rows 2i-3..2i+3, i.e. pixel-blocks i-2..i+1 — four
    consecutive s2d rows). The parameter is the ORIGINAL (7,7,C,F) kernel
    under the same 'conv1' collection — checkpoints, torch interop, and
    init are byte-identical — and the (4,4,4C,F) rearrangement happens at
    trace time: front-pad one zero tap (the a=-1 position 2b+u-1 hits at
    b=0,u=0) then fold (u,v) into channels. Exact up to float summation
    order; the zero tap multiplies only zero weights.
    """

    features: int
    dtype: Any = None
    s2d: bool = True      # False = direct 7x7/s2 conv (the A/B baseline)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.variance_scaling(2.0, "fan_out", "normal"),
            (7, 7, c, self.features))
        # dtype=None keeps nn.Conv's promote_dtype semantics (bf16 input x
        # fp32 kernel computes in fp32) rather than downcasting the kernel.
        dt = self.dtype or jnp.result_type(x.dtype, kernel.dtype)
        n, h, w, _ = x.shape
        if not self.s2d or h % 2 or w % 2:    # odd inputs: direct conv
            return jax.lax.conv_general_dilated(
                x.astype(dt), kernel.astype(dt), window_strides=(2, 2),
                padding=((3, 3), (3, 3)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        xs = x.reshape(n, h // 2, 2, w // 2, 2, c)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
        k = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k = k.reshape(4, 2, 4, 2, c, self.features)
        k = k.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, self.features)
        return jax.lax.conv_general_dilated(
            xs.astype(dt), k.astype(dt), window_strides=(1, 1),
            padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class BasicBlock(nn.Module):
    features: int
    strides: int = 1
    norm: Any = BatchNorm
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool):
        residual = x
        y = conv_kaiming(self.features, 3, self.strides, self.dtype, "conv1")(x)
        y = self.norm(use_running_average=not train, dtype=self.dtype,
                      name="bn1")(y, act="relu")
        y = conv_kaiming(self.features, 3, 1, self.dtype, "conv2")(y)
        if residual.shape != y.shape:
            residual = conv_kaiming(self.features, 1, self.strides, self.dtype, "downsample_conv")(x)
            residual = self.norm(use_running_average=not train, dtype=self.dtype,
                                 name="downsample_bn")(residual)
        return self.norm(use_running_average=not train, dtype=self.dtype,
                         name="bn2")(y, act="relu", residual=residual)


class Bottleneck(nn.Module):
    """torchvision Bottleneck incl. the ResNeXt/WideResNet generalization:
    inner width = int(features * base_width/64) * groups, grouped 3x3
    (torchvision resnet.py Bottleneck.__init__)."""
    features: int
    strides: int = 1
    norm: Any = BatchNorm
    dtype: Any = None
    expansion: int = 4
    groups: int = 1
    base_width: int = 64

    @nn.compact
    def __call__(self, x, train: bool):
        residual = x
        width = int(self.features * (self.base_width / 64.0)) * self.groups
        y = conv_kaiming(width, 1, 1, self.dtype, "conv1")(x)
        y = self.norm(use_running_average=not train, dtype=self.dtype,
                      name="bn1")(y, act="relu")
        y = conv_kaiming(width, 3, self.strides, self.dtype, "conv2",
                         groups=self.groups)(y)
        y = self.norm(use_running_average=not train, dtype=self.dtype,
                      name="bn2")(y, act="relu")
        y = conv_kaiming(self.features * self.expansion, 1, 1, self.dtype, "conv3")(y)
        if residual.shape != y.shape:
            residual = conv_kaiming(self.features * self.expansion, 1, self.strides,
                                    self.dtype, "downsample_conv")(x)
            residual = self.norm(use_running_average=not train, dtype=self.dtype,
                                 name="downsample_bn")(residual)
        return self.norm(use_running_average=not train, dtype=self.dtype,
                         name="bn3")(y, act="relu", residual=residual)


class ResNet(nn.Module):
    """torchvision-architecture ResNet over NHWC inputs.

    ``sync_batchnorm`` + ``bn_axis_name`` select cross-replica BN statistics
    (the reference's SyncBN recipe, ``distributed_syncBN_amp.py:143-147``).
    """

    stage_sizes: Sequence[int]
    block: Type[nn.Module]
    num_classes: int = 1000
    width: int = 64
    dtype: Any = None                         # activation/compute dtype
    sync_batchnorm: bool = False
    bn_axis_name: str = "data"
    remat: bool = False                       # jax.checkpoint each block
    # Stem policy: the DEFAULT is the direct 7x7/s2 conv — the program
    # chip_smoke.py runs on the chip. The s2d rewrite's entire purpose is
    # MXU utilization, which only an on-chip A/B can confirm — so s2d
    # stays an opt-in lever (bench.py --s2d) until that A/B lands, at
    # which point the winner becomes the default WITH its number.
    s2d_stem: bool = False                    # bench A/B lever; same params

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        norm = partial(BatchNorm,
                       axis_name=self.bn_axis_name if self.sync_batchnorm else None)
        x = x.astype(self.dtype or x.dtype)
        x = _StemConvS2D(self.width, dtype=self.dtype, s2d=self.s2d_stem,
                         name="conv1")(x)
        x = norm(use_running_average=not train, dtype=self.dtype,
                 name="bn1")(x, act="relu")
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, num_blocks in enumerate(self.stage_sizes):
            features = self.width * (2 ** i)
            for j in range(num_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                blk = self.block(features=features, strides=strides, norm=norm,
                                 dtype=self.dtype, name=f"layer{i + 1}_{j}")
                if self.remat:
                    # jax.checkpoint at block granularity: backward recomputes
                    # the block's activations instead of holding them across
                    # the whole graph (param tree and numerics unchanged).
                    x = nn.remat(lambda m, y: m(y, train=train))(blk, x)
                else:
                    x = blk(x, train=train)
        x = jnp.mean(x, axis=(1, 2))                     # global average pool
        x = dense_torch(self.num_classes, dtype=self.dtype, name="fc")(x)
        return x


def _resnet(stage_sizes, block, groups: int = 1, width_per_group: int = 64):
    if groups != 1 or width_per_group != 64:
        block = partial(block, groups=groups, base_width=width_per_group)

    def ctor(num_classes: int = 1000, dtype: Any = None,
             sync_batchnorm: bool = False, bn_axis_name: str = "data", **kw) -> ResNet:
        return ResNet(stage_sizes=stage_sizes, block=block, num_classes=num_classes,
                      dtype=dtype, sync_batchnorm=sync_batchnorm,
                      bn_axis_name=bn_axis_name, **kw)
    return ctor


resnet18 = _resnet([2, 2, 2, 2], BasicBlock)
resnet34 = _resnet([3, 4, 6, 3], BasicBlock)
resnet50 = _resnet([3, 4, 6, 3], Bottleneck)
resnet101 = _resnet([3, 4, 23, 3], Bottleneck)
resnet152 = _resnet([3, 8, 36, 3], Bottleneck)
# ResNeXt / WideResNet (torchvision resnet.py resnext50_32x4d/wide_resnet50_2)
resnext50_32x4d = _resnet([3, 4, 6, 3], Bottleneck, groups=32, width_per_group=4)
resnext101_32x8d = _resnet([3, 4, 23, 3], Bottleneck, groups=32, width_per_group=8)
wide_resnet50_2 = _resnet([3, 4, 6, 3], Bottleneck, width_per_group=128)
wide_resnet101_2 = _resnet([3, 4, 23, 3], Bottleneck, width_per_group=128)
