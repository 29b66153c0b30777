"""Building-block layers with torch-matching semantics.

The load-bearing piece is ``BatchNorm``: one module that is BOTH the
reference's plain per-replica BN and its SyncBatchNorm
(``distributed_syncBN_amp.py:145``), selected by ``axis_name``:

- ``axis_name=None``  → statistics over the local shard's batch (what each GPU
  computes under DDP — the reference's default BN);
- ``axis_name='data'`` → statistics ``lax.pmean``-ed across the mesh's data
  axis (exactly what ``nn.SyncBatchNorm`` does with an NCCL allreduce of
  mean/var, but compiled by XLA into the step program over ICI).

Semantics follow torch.nn.BatchNorm2d, NOT flax.linen.BatchNorm, because the
accuracy parity target (46.83% top-1, BASELINE.md) depends on them:

- torch ``momentum=0.1`` means ``running = 0.9*running + 0.1*batch``
  (flax's momentum is the complement);
- normalization uses the biased batch variance, while the running-variance
  update uses the UNBIASED variance (Bessel-corrected) — a torch quirk flax
  does not reproduce.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn


class BatchNorm(nn.Module):
    """torch.nn.BatchNorm2d-semantics batch normalization over NHWC inputs,
    with optional cross-replica statistics (SyncBN) via ``axis_name``.

    The call sites may hand over their epilogue: ``act="relu"`` (and
    ``residual=...`` for the add that closes a residual block) runs
    normalise -> affine -> cast -> (add) -> relu here, in the operation
    order the call sites always had (float32 normalise, then the cast)."""

    momentum: float = 0.1            # torch convention: weight of the NEW stat
    epsilon: float = 1e-5
    use_running_average: Optional[bool] = None
    axis_name: Optional[str] = None  # set to the mesh data axis for SyncBN
    dtype: Any = None                # compute dtype (bf16 under the amp policy)

    @nn.compact
    def __call__(self, x: jax.Array,
                 use_running_average: Optional[bool] = None, *,
                 act: Optional[str] = None,
                 residual: Optional[jax.Array] = None) -> jax.Array:
        if act not in (None, "relu"):
            raise ValueError(f"BatchNorm act must be None or 'relu', "
                             f"got {act!r}")
        if residual is not None and act is None:
            raise ValueError("BatchNorm residual= requires act='relu' "
                             "(the epilogue is BN + add + ReLU)")
        if use_running_average is None:
            use_running_average = self.use_running_average
        use_ra = bool(use_running_average) if use_running_average is not None else False
        features = x.shape[-1]
        reduce_axes = tuple(range(x.ndim - 1))        # all but channel

        scale = self.param("scale", nn.initializers.ones, (features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (features,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (features,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (features,))

        if use_ra:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            # Per-shard statistics...
            mean = jnp.mean(xf, axis=reduce_axes)
            mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
            n = 1
            for a in reduce_axes:
                n *= x.shape[a]
            if self.axis_name is not None:
                # ...or SyncBN: pmean over the data axis — the XLA-compiled
                # equivalent of SyncBatchNorm's stat allreduce.
                mean = jax.lax.pmean(mean, axis_name=self.axis_name)
                mean_sq = jax.lax.pmean(mean_sq, axis_name=self.axis_name)
                n *= jax.lax.psum(1, axis_name=self.axis_name)
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)   # biased, for normalization
            if not self.is_initializing():
                unbiased = var * (n / max(n - 1, 1))             # torch running-var quirk
                m = self.momentum
                ra_mean.value = (1 - m) * ra_mean.value + m * mean
                ra_var.value = (1 - m) * ra_var.value + m * unbiased

        out_dt = self.dtype or x.dtype
        y = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * scale + bias
        y = y.astype(out_dt)
        if residual is not None:
            y = y + residual
        if act == "relu":
            y = nn.relu(y)
        return y


def conv_kaiming(features: int, kernel_size: int, strides: int = 1,
                 dtype: Any = None, name: str | None = None,
                 groups: int = 1, use_bias: bool = False,
                 padding: Any = None) -> nn.Conv:
    """Conv with torchvision's BN-follows init (kaiming_normal, fan_out, relu
    gain — torchvision resnet.py ``_initialize_weights``); ``groups`` covers
    ResNeXt grouped and MobileNet depthwise (groups == in-features) convs."""
    if padding is None:
        padding = [(kernel_size // 2, kernel_size // 2)] * 2
    return nn.Conv(features, (kernel_size, kernel_size),
                   strides=(strides, strides),
                   padding=padding,
                   use_bias=use_bias,
                   feature_group_count=groups,
                   kernel_init=nn.initializers.variance_scaling(2.0, "fan_out", "normal"),
                   dtype=dtype, name=name)


class BasicConv2d(nn.Module):
    """torchvision's Inception-family conv block: conv (no bias) →
    BN(eps=1e-3) → relu. Shared by googlenet.py and inception.py; kernel/
    padding accept int or (h, w) tuples (asymmetric 1x7/7x1 factorizations).

    Init matches torchvision's inception-family ``trunc_normal_``: stddev 0.1
    for inception_v3 (its default when a conv carries no ``stddev`` attr —
    including the aux convs, where torchvision sets ``stddev`` on the wrapper
    module the init loop never reads), 0.01 for googlenet."""
    features: int
    kernel: Any = (1, 1)
    strides: int = 1
    padding: Any = (0, 0)
    norm: Any = None           # partial(BatchNorm, ...) from the parent model
    dtype: Any = None
    stddev: float = 0.1        # torchvision trunc_normal stddev

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        k = ((self.kernel, self.kernel) if isinstance(self.kernel, int)
             else tuple(self.kernel))
        p = ((self.padding, self.padding) if isinstance(self.padding, int)
             else tuple(self.padding))
        norm = self.norm or BatchNorm
        x = nn.Conv(self.features, k, strides=(self.strides,) * 2,
                    padding=[(p[0],) * 2, (p[1],) * 2], use_bias=False,
                    kernel_init=nn.initializers.truncated_normal(self.stddev),
                    dtype=self.dtype, name="conv")(x)
        return norm(use_running_average=not train, epsilon=1e-3,
                    dtype=self.dtype, name="bn")(x, act="relu")


def stochastic_depth(x: jax.Array, rate: float, deterministic: bool,
                     rng: jax.Array | None) -> jax.Array:
    """torchvision ``stochastic_depth(..., mode="row")``: per-sample Bernoulli
    keep of the residual branch, rescaled by the survival rate (EfficientNet/
    ConvNeXt families)."""
    if deterministic or rate == 0.0:
        return x
    survival = 1.0 - rate
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    keep = jax.random.bernoulli(rng, survival, shape)
    return jnp.where(keep, x / survival, 0.0).astype(x.dtype)


def adaptive_avg_pool(x: jax.Array, out_hw: tuple[int, int]) -> jax.Array:
    """torch ``AdaptiveAvgPool2d`` over NHWC: output bin (i,j) averages input
    rows [floor(i*H/oh), ceil((i+1)*H/oh)). Shapes are static under jit, so
    the bin arithmetic happens at trace time."""
    h, w = x.shape[1], x.shape[2]
    oh, ow = out_hw
    if h == oh and w == ow:
        return x
    if h % oh == 0 and w % ow == 0:
        kh, kw = h // oh, w // ow
        return nn.avg_pool(x, (kh, kw), strides=(kh, kw))
    import math
    rows = []
    for i in range(oh):
        h0, h1 = (i * h) // oh, math.ceil((i + 1) * h / oh)
        cols = []
        for j in range(ow):
            w0, w1 = (j * w) // ow, math.ceil((j + 1) * w / ow)
            cols.append(jnp.mean(x[:, h0:h1, w0:w1, :], axis=(1, 2)))
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)


def max_pool_ceil(x: jax.Array, window: int, strides: int,
                  padding: int = 0) -> jax.Array:
    """torch ``MaxPool2d(..., ceil_mode=True)``: pad right/bottom with -inf so
    the last partial window is kept (flax max_pool only floors)."""
    h, w = x.shape[1], x.shape[2]

    def pads(size: int) -> tuple[int, int]:
        size2 = size + 2 * padding
        out_ceil = -(-(size2 - window) // strides) + 1
        extra = (out_ceil - 1) * strides + window - size2
        # torch drops a trailing window that would start entirely in padding
        if (out_ceil - 1) * strides >= size + padding:
            extra -= strides
        return padding, padding + max(extra, 0)

    return nn.max_pool(x, (window, window), strides=(strides, strides),
                       padding=[pads(h), pads(w)])


class DenseTorch(nn.Module):
    """Linear layer with torch.nn.Linear's default init:
    U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for BOTH kernel and bias (flax's
    ``nn.Dense`` can't express the bias part — its bias_init never sees
    fan_in). Param names match nn.Dense ('kernel' [in, out], 'bias') so
    checkpoints stay interchangeable."""

    features: int
    dtype: Any = None
    kernel_init: Optional[Callable] = None   # override torch's default U(±1/√fan_in)
    bias_init: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        fan_in = x.shape[-1]
        bound = 1.0 / (fan_in ** 0.5)

        def uniform_init(key, shape, dt):
            return jax.random.uniform(key, shape, dt, -bound, bound)

        kernel = self.param("kernel", self.kernel_init or uniform_init,
                            (fan_in, self.features), jnp.float32)
        bias = self.param("bias", self.bias_init or uniform_init,
                          (self.features,), jnp.float32)
        dt = self.dtype or x.dtype
        return x.astype(dt) @ kernel.astype(dt) + bias.astype(dt)


def dense_torch(features: int, dtype: Any = None, name: str | None = None,
                kernel_init: Optional[Callable] = None,
                bias_init: Optional[Callable] = None) -> DenseTorch:
    return DenseTorch(features=features, dtype=dtype, name=name,
                      kernel_init=kernel_init, bias_init=bias_init)
