"""DenseNet 121/161/169/201 in flax/NHWC (torchvision ``densenet.py``).

Zoo parity for the reference's by-name model build
(``/root/reference/distributed.py:131-137``). BN layers are the framework
BatchNorm (layers.py), so ``sync_batchnorm=True`` gives the reference's SyncBN
recipe (``distributed_syncBN_amp.py:145``) on this family too. Module names
mirror torchvision (``features.denseblock1.denselayer1.norm1`` →
``denseblock1_denselayer1`` / ``norm1``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.layers import BatchNorm, conv_kaiming, dense_torch


class DenseLayer(nn.Module):
    growth_rate: int
    bn_size: int
    norm: Any
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        y = self.norm(use_running_average=not train, dtype=self.dtype,
                      name="norm1")(x, act="relu")
        y = conv_kaiming(self.bn_size * self.growth_rate, 1, 1, self.dtype,
                         "conv1")(y)
        y = self.norm(use_running_average=not train, dtype=self.dtype,
                      name="norm2")(y, act="relu")
        y = conv_kaiming(self.growth_rate, 3, 1, self.dtype, "conv2")(y)
        return jnp.concatenate([x, y], axis=-1)


class DenseNet(nn.Module):
    block_config: Sequence[int]
    growth_rate: int = 32
    num_init_features: int = 64
    bn_size: int = 4
    num_classes: int = 1000
    dtype: Any = None
    sync_batchnorm: bool = False
    bn_axis_name: str = "data"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = x.astype(self.dtype or x.dtype)
        norm = partial(BatchNorm,
                       axis_name=self.bn_axis_name if self.sync_batchnorm else None)
        x = conv_kaiming(self.num_init_features, 7, 2, self.dtype, "conv0")(x)
        x = norm(use_running_average=not train, dtype=self.dtype,
                 name="norm0")(x, act="relu")
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1)] * 2)
        features = self.num_init_features
        for bi, num_layers in enumerate(self.block_config):
            for li in range(num_layers):
                x = DenseLayer(self.growth_rate, self.bn_size, norm, self.dtype,
                               name=f"denseblock{bi + 1}_denselayer{li + 1}")(
                                   x, train=train)
            features += num_layers * self.growth_rate
            if bi != len(self.block_config) - 1:      # transition (halve)
                x = norm(use_running_average=not train, dtype=self.dtype,
                         name=f"transition{bi + 1}_norm")(x, act="relu")
                features //= 2
                x = conv_kaiming(features, 1, 1, self.dtype,
                                 f"transition{bi + 1}_conv")(x)
                x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = norm(use_running_average=not train, dtype=self.dtype,
                 name="norm5")(x, act="relu")
        x = jnp.mean(x, axis=(1, 2))
        return dense_torch(self.num_classes, self.dtype, "classifier")(x)


def _densenet(block_config, growth_rate=32, num_init_features=64):
    def ctor(num_classes: int = 1000, dtype: Any = None,
             sync_batchnorm: bool = False, bn_axis_name: str = "data",
             **kw) -> DenseNet:
        return DenseNet(block_config=tuple(block_config),
                        growth_rate=growth_rate,
                        num_init_features=num_init_features,
                        num_classes=num_classes, dtype=dtype,
                        sync_batchnorm=sync_batchnorm, bn_axis_name=bn_axis_name)
    return ctor


densenet121 = _densenet([6, 12, 24, 16])
densenet169 = _densenet([6, 12, 32, 32])
densenet201 = _densenet([6, 12, 48, 32])
densenet161 = _densenet([6, 12, 36, 24], growth_rate=48, num_init_features=96)
