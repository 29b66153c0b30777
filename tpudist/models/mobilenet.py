"""MobileNetV2 / MobileNetV3 (large/small) in flax/NHWC (torchvision
``mobilenetv2.py`` / ``mobilenetv3.py``).

Zoo parity for the reference's by-name model build
(``/root/reference/distributed.py:131-137``). Depthwise convs are grouped
``nn.Conv`` (``feature_group_count == channels``) — XLA:TPU lowers these to
its native depthwise emitters. V3's squeeze-excite and hard-swish follow
torchvision exactly (hardsigmoid = relu6(x+3)/6).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.layers import BatchNorm, conv_kaiming, dense_torch


def _make_divisible(v: float, divisor: int = 8, min_value: int | None = None) -> int:
    """torchvision ``_make_divisible``: round to nearest multiple, never
    dropping more than 10%."""
    if min_value is None:
        min_value = divisor
    new_v = max(min_value, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def relu6(x):
    return jnp.minimum(nn.relu(x), 6.0)


def hardswish(x):
    return x * relu6(x + 3.0) / 6.0


def hardsigmoid(x):
    return relu6(x + 3.0) / 6.0


class ConvBNAct(nn.Module):
    features: int
    kernel: int = 3
    strides: int = 1
    groups: int = 1
    act: Any = relu6
    norm: Any = BatchNorm
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        x = conv_kaiming(self.features, self.kernel, self.strides, self.dtype,
                         "conv", groups=self.groups)(x)
        if self.act is nn.relu:
            # the one activation BatchNorm's epilogue takes (regnet and the
            # V3 relu blocks)
            return self.norm(use_running_average=not train, dtype=self.dtype,
                             name="bn")(x, act="relu")
        x = self.norm(use_running_average=not train, dtype=self.dtype,
                      name="bn")(x)
        return self.act(x) if self.act is not None else x


class SqueezeExcite(nn.Module):
    """torchvision SE block: global-mean squeeze → 1x1 reduce → ``act`` → 1x1
    expand → ``gate`` scale. MobileNetV3 uses the relu/hardsigmoid defaults
    (squeeze = make_divisible(expand/4, 8)); EfficientNet passes
    silu/sigmoid."""
    channels: int
    squeeze: int
    act: Any = nn.relu
    gate: Any = hardsigmoid
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        s = jnp.mean(x, axis=(1, 2), keepdims=True)
        # torchvision inits every Conv2d (SE 1x1s included) kaiming fan_out
        s = conv_kaiming(self.squeeze, 1, 1, self.dtype, "fc1",
                         use_bias=True)(s)
        s = self.act(s)
        s = conv_kaiming(self.channels, 1, 1, self.dtype, "fc2",
                         use_bias=True)(s)
        return x * self.gate(s)


class InvertedResidual(nn.Module):
    """V2/V3 inverted residual: [pw expand] → dw → [SE] → pw-linear, skip when
    stride 1 and shapes match."""
    expanded: int
    out: int
    kernel: int = 3
    strides: int = 1
    use_se: bool = False
    act: Any = relu6
    norm: Any = BatchNorm
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        inp = x.shape[-1]
        y = x
        if self.expanded != inp:
            y = ConvBNAct(self.expanded, 1, 1, act=self.act, norm=self.norm,
                          dtype=self.dtype, name="expand")(y, train)
        y = ConvBNAct(self.expanded, self.kernel, self.strides,
                      groups=self.expanded, act=self.act, norm=self.norm,
                      dtype=self.dtype, name="dw")(y, train)
        if self.use_se:
            y = SqueezeExcite(self.expanded,
                              _make_divisible(self.expanded // 4, 8),
                              dtype=self.dtype, name="se")(y)
        y = ConvBNAct(self.out, 1, 1, act=None, norm=self.norm,
                      dtype=self.dtype, name="project")(y, train)
        if self.strides == 1 and inp == self.out:
            y = x + y
        return y


# t (expand ratio), c (out), n (repeats), s (stride) — torchvision mobilenetv2
_V2_CFG = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
           (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


class MobileNetV2(nn.Module):
    num_classes: int = 1000
    width_mult: float = 1.0
    dtype: Any = None
    dropout: float = 0.2
    sync_batchnorm: bool = False
    bn_axis_name: str = "data"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = x.astype(self.dtype or x.dtype)
        norm = partial(BatchNorm,
                       axis_name=self.bn_axis_name if self.sync_batchnorm else None)
        c_in = _make_divisible(32 * self.width_mult)
        x = ConvBNAct(c_in, 3, 2, norm=norm, dtype=self.dtype,
                      name="features_0")(x, train)
        i = 1
        for t, c, n, s in _V2_CFG:
            c_out = _make_divisible(c * self.width_mult)
            for j in range(n):
                x = InvertedResidual(expanded=c_in * t, out=c_out, kernel=3,
                                     strides=s if j == 0 else 1, norm=norm,
                                     dtype=self.dtype, name=f"features_{i}")(
                                         x, train)
                c_in = c_out
                i += 1
        c_last = _make_divisible(1280 * max(self.width_mult, 1.0))
        x = ConvBNAct(c_last, 1, 1, norm=norm, dtype=self.dtype,
                      name=f"features_{i}")(x, train)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        # torchvision mobilenetv2: Linear → normal(0, 0.01), zero bias
        return dense_torch(self.num_classes, self.dtype, "classifier_1",
                           kernel_init=nn.initializers.normal(0.01),
                           bias_init=nn.initializers.zeros)(x)


# kernel, expanded, out, SE, activation, stride — torchvision mobilenetv3
_V3_LARGE = [
    (3, 16, 16, False, "RE", 1), (3, 64, 24, False, "RE", 2),
    (3, 72, 24, False, "RE", 1), (5, 72, 40, True, "RE", 2),
    (5, 120, 40, True, "RE", 1), (5, 120, 40, True, "RE", 1),
    (3, 240, 80, False, "HS", 2), (3, 200, 80, False, "HS", 1),
    (3, 184, 80, False, "HS", 1), (3, 184, 80, False, "HS", 1),
    (3, 480, 112, True, "HS", 1), (3, 672, 112, True, "HS", 1),
    (5, 672, 160, True, "HS", 2), (5, 960, 160, True, "HS", 1),
    (5, 960, 160, True, "HS", 1),
]
_V3_SMALL = [
    (3, 16, 16, True, "RE", 2), (3, 72, 24, False, "RE", 2),
    (3, 88, 24, False, "RE", 1), (5, 96, 40, True, "HS", 2),
    (5, 240, 40, True, "HS", 1), (5, 240, 40, True, "HS", 1),
    (5, 120, 48, True, "HS", 1), (5, 144, 48, True, "HS", 1),
    (5, 288, 96, True, "HS", 2), (5, 576, 96, True, "HS", 1),
    (5, 576, 96, True, "HS", 1),
]


class MobileNetV3(nn.Module):
    cfg: Sequence
    last_channel: int
    num_classes: int = 1000
    dtype: Any = None
    dropout: float = 0.2
    sync_batchnorm: bool = False
    bn_axis_name: str = "data"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = x.astype(self.dtype or x.dtype)
        # torchvision V3 BN: eps=0.001, momentum=0.01
        norm = partial(BatchNorm, epsilon=1e-3, momentum=0.01,
                       axis_name=self.bn_axis_name if self.sync_batchnorm else None)
        x = ConvBNAct(16, 3, 2, act=hardswish, norm=norm, dtype=self.dtype,
                      name="features_0")(x, train)
        i = 1
        for k, exp, out, se, nl, s in self.cfg:
            act = hardswish if nl == "HS" else nn.relu
            x = InvertedResidual(expanded=exp, out=out, kernel=k, strides=s,
                                 use_se=se, act=act, norm=norm,
                                 dtype=self.dtype, name=f"features_{i}")(x, train)
            i += 1
        x = ConvBNAct(6 * x.shape[-1], 1, 1, act=hardswish, norm=norm,
                      dtype=self.dtype, name=f"features_{i}")(x, train)
        x = jnp.mean(x, axis=(1, 2))
        # torchvision mobilenetv3: Linear → normal(0, 0.01), zero bias
        linear_init = dict(kernel_init=nn.initializers.normal(0.01),
                           bias_init=nn.initializers.zeros)
        x = hardswish(dense_torch(self.last_channel, self.dtype,
                                  "classifier_0", **linear_init)(x))
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return dense_torch(self.num_classes, self.dtype, "classifier_3",
                           **linear_init)(x)


def mobilenet_v2(num_classes: int = 1000, dtype: Any = None,
                 sync_batchnorm: bool = False, bn_axis_name: str = "data",
                 **kw) -> MobileNetV2:
    return MobileNetV2(num_classes=num_classes, dtype=dtype,
                       sync_batchnorm=sync_batchnorm, bn_axis_name=bn_axis_name)


def mobilenet_v3_large(num_classes: int = 1000, dtype: Any = None,
                       sync_batchnorm: bool = False, bn_axis_name: str = "data",
                       **kw) -> MobileNetV3:
    return MobileNetV3(cfg=tuple(_V3_LARGE), last_channel=1280,
                       num_classes=num_classes, dtype=dtype,
                       sync_batchnorm=sync_batchnorm, bn_axis_name=bn_axis_name)


def mobilenet_v3_small(num_classes: int = 1000, dtype: Any = None,
                       sync_batchnorm: bool = False, bn_axis_name: str = "data",
                       **kw) -> MobileNetV3:
    return MobileNetV3(cfg=tuple(_V3_SMALL), last_channel=1024,
                       num_classes=num_classes, dtype=dtype,
                       sync_batchnorm=sync_batchnorm, bn_axis_name=bn_axis_name)
