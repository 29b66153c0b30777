"""VGG 11/13/16/19 (+_bn variants) in flax/NHWC (torchvision ``vgg.py``
configs A/B/D/E).

Zoo parity for the reference's by-name model build
(``/root/reference/distributed.py:131-137``). The ``_bn`` variants use the
framework BatchNorm (layers.py), so they get SyncBN for free via
``sync_batchnorm=True`` — the reference's ``convert_sync_batchnorm`` recipe
(``distributed_syncBN_amp.py:145``) applies to any BN model here.

Module names mirror torchvision ``features.N``/``classifier.N`` indices for
checkpoint interop.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
from flax import linen as nn

from tpudist.models.layers import (BatchNorm, adaptive_avg_pool, conv_kaiming,
                                   dense_torch)

CFGS: dict[str, list] = {
    "A": [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
    "B": [64, 64, "M", 128, 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
    "D": [64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M",
          512, 512, 512, "M"],
    "E": [64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M", 512, 512, 512,
          512, "M", 512, 512, 512, 512, "M"],
}


class VGG(nn.Module):
    cfg: Sequence
    batch_norm: bool = False
    num_classes: int = 1000
    dtype: Any = None
    dropout: float = 0.5
    sync_batchnorm: bool = False
    bn_axis_name: str = "data"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = x.astype(self.dtype or x.dtype)
        norm = partial(BatchNorm,
                       axis_name=self.bn_axis_name if self.sync_batchnorm else None)
        idx = 0   # torchvision Sequential index: conv,[bn,]relu per entry; pool
        for v in self.cfg:
            if v == "M":
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
                idx += 1
                continue
            # torch conv bias stays (init 0); kaiming_normal fan_out weights
            x = conv_kaiming(int(v), 3, 1, self.dtype, f"features_{idx}",
                             use_bias=True)(x)
            idx += 1
            if self.batch_norm:
                x = norm(use_running_average=not train, dtype=self.dtype,
                         name=f"features_{idx}")(x, act="relu")
                idx += 1
            else:
                x = nn.relu(x)
            idx += 1
        x = adaptive_avg_pool(x, (7, 7))
        x = x.transpose(0, 3, 1, 2).reshape(x.shape[0], -1)   # NCHW flatten order
        # torchvision VGG._initialize_weights: Linear ~ N(0, 0.01), bias 0
        fc = partial(dense_torch, dtype=self.dtype,
                     kernel_init=nn.initializers.normal(0.01),
                     bias_init=nn.initializers.zeros)
        x = nn.relu(fc(4096, name="classifier_0")(x))
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        x = nn.relu(fc(4096, name="classifier_3")(x))
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return fc(self.num_classes, name="classifier_6")(x)


def _vgg(cfg: str, batch_norm: bool):
    def ctor(num_classes: int = 1000, dtype: Any = None,
             sync_batchnorm: bool = False, bn_axis_name: str = "data", **kw) -> VGG:
        return VGG(cfg=tuple(CFGS[cfg]), batch_norm=batch_norm,
                   num_classes=num_classes, dtype=dtype,
                   sync_batchnorm=sync_batchnorm, bn_axis_name=bn_axis_name)
    return ctor


vgg11 = _vgg("A", False)
vgg13 = _vgg("B", False)
vgg16 = _vgg("D", False)
vgg19 = _vgg("E", False)
vgg11_bn = _vgg("A", True)
vgg13_bn = _vgg("B", True)
vgg16_bn = _vgg("D", True)
vgg19_bn = _vgg("E", True)
