"""Plain float32 reference of the `resnet18_ref` configuration.

torchvision resnet18 (He et al. 2015; torchvision/models/resnet.py) trained
by torch.optim.SGD, written in straight `jax.numpy` / `lax` at
`Precision.HIGHEST`. It imports nothing of `tpudist` and is handed nothing
the program made: weights come from `init` (torchvision's own init, from the
benchmark's seed), batches from the benchmark's traffic generator.

Memory. A float32 batch of 1200 at 224 px does not fit a 16 GB chip the
plain way (the stem's output alone is 3.85 GB a tensor). Every
conv + batch-norm unit is therefore computed in blocks of rows: one scan over
the blocks gathers the whole batch's per-channel moments (Chan's pairwise
update, so no E[x^2]-E[x]^2 cancellation), a second scan normalises block by
block; both scan bodies are rematerialised, so the backward pass holds one
block's intermediates at a time. Batch-norm statistics are over the whole
batch, as the model defines them.

`quant` is for the control only: it rounds both operands of every
convolution and matrix product to fp8 (e4m3, per-tensor scale) or to
bfloat16, the precision steps below the configuration's bf16 / float32.
Departures from torchvision: NHWC layout (a transposition of the same
arithmetic); nothing else.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
STAGES = ((64, 2), (128, 2), (256, 2), (512, 2))      # torchvision resnet18


# ------------------------------------------------------------------ init --

def _kaiming(key, shape):
    """kaiming_normal_(mode='fan_out', nonlinearity='relu') over HWIO."""
    fan_out = shape[0] * shape[1] * shape[3]
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_out) ** 0.5


def _bn(c):
    return ({"scale": jnp.ones((c,), jnp.float32),
             "bias": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def init(key, cfg):
    """(params, batch_stats) as torchvision initialises resnet18."""
    keys = iter(jax.random.split(key, 64))
    params, stats = {}, {}
    params["conv1"] = {"kernel": _kaiming(next(keys), (7, 7, 3, 64))}
    params["bn1"], stats["bn1"] = _bn(64)
    c_in = 64
    for i, (c, n) in enumerate(STAGES):
        for j in range(n):
            name = f"layer{i + 1}_{j}"
            stride = 2 if (i > 0 and j == 0) else 1
            p, s = {}, {}
            p["conv1"] = {"kernel": _kaiming(next(keys), (3, 3, c_in, c))}
            p["bn1"], s["bn1"] = _bn(c)
            p["conv2"] = {"kernel": _kaiming(next(keys), (3, 3, c, c))}
            p["bn2"], s["bn2"] = _bn(c)
            if stride != 1 or c_in != c:
                p["downsample_conv"] = {
                    "kernel": _kaiming(next(keys), (1, 1, c_in, c))}
                p["downsample_bn"], s["downsample_bn"] = _bn(c)
            params[name], stats[name] = p, s
            c_in = c
    k = int(cfg["num_classes"])
    bound = 1.0 / c_in ** 0.5                       # torch.nn.Linear default
    params["fc"] = {
        "kernel": jax.random.uniform(next(keys), (c_in, k), jnp.float32,
                                     -bound, bound),
        "bias": jax.random.uniform(next(keys), (k,), jnp.float32,
                                   -bound, bound)}
    return params, stats


# --------------------------------------------------------------- forward --

def _q(x, quant):
    """Operand rounding for the lower-precision control (straight-through
    gradient, so the backward products see the rounded operands too)."""
    if quant is None:
        return x
    if quant == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        amax = jnp.max(jnp.abs(x))
        s = jnp.where(amax > 0, 448.0 / amax, 1.0)
        r = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + lax.stop_gradient(r - x)


def _conv(x, w, stride, pad, quant):
    return lax.conv_general_dilated(
        _q(x, quant), _q(w, quant), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _maxpool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                             ((0, 0), (1, 1), (1, 1), (0, 0)))


def conv_bn(x, w, bn, stride, pad, *, eps, rows, quant, relu=True,
            residual=None, pool=False):
    """conv -> batch norm over the WHOLE batch -> (+residual) -> relu
    (-> 3x3/2 max pool), computed `rows` rows at a time. Returns the output
    and the batch's (mean, biased var, count)."""
    n = x.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"batch {n} is not a multiple of block rows {rows}")
    nb = n // rows
    xb = x.reshape((nb, rows) + x.shape[1:])

    @jax.checkpoint
    def moments(_, xi):
        y = _conv(xi, w, stride, pad, quant)
        m = jnp.mean(y, axis=(0, 1, 2))
        return None, (m, jnp.sum(jnp.square(y - m), axis=(0, 1, 2)),
                      jnp.float32(y.shape[0] * y.shape[1] * y.shape[2]))

    _, (mb, m2b, cnt) = lax.scan(moments, None, xb)
    cnt = cnt[:, None]
    total = jnp.sum(cnt)
    mean = jnp.sum(mb * cnt, axis=0) / total
    var = (jnp.sum(m2b, axis=0)
           + jnp.sum(cnt * jnp.square(mb - mean), axis=0)) / total

    @jax.checkpoint
    def apply(_, xr):
        xi, ri = xr
        y = _conv(xi, w, stride, pad, quant)
        z = (y - mean) * lax.rsqrt(var + eps) * bn["scale"] + bn["bias"]
        if ri is not None:
            z = z + ri
        if relu:
            z = jnp.maximum(z, 0.0)
        if pool:
            z = _maxpool(z)
        return None, z

    rb = None if residual is None else residual.reshape(
        (nb, rows) + residual.shape[1:])
    _, out = lax.scan(apply, None, (xb, rb))
    return out.reshape((n,) + out.shape[2:]), (mean, var, total)


def _basic_block(p, x, stride, kw):
    m = {}
    y, m["bn1"] = conv_bn(x, p["conv1"]["kernel"], p["bn1"], stride, 1, **kw)
    res = x
    if "downsample_conv" in p:
        res, m["downsample_bn"] = conv_bn(
            x, p["downsample_conv"]["kernel"], p["downsample_bn"], stride, 0,
            relu=False, **kw)
    x, m["bn2"] = conv_bn(y, p["conv2"]["kernel"], p["bn2"], 1, 1,
                          residual=res, **kw)
    return x, m


def forward(params, images, cfg, quant=None):
    """Train-mode logits and every batch-norm layer's batch moments. The stem
    and each residual block are rematerialised as a whole, so only their
    boundaries (5.3 GB at a float32 batch of 1200) stay resident."""
    kw = dict(eps=float(cfg["bn_eps"]), rows=int(cfg["reference_block_rows"]),
              quant=quant)
    moments = {}
    x, moments["bn1"] = jax.checkpoint(
        lambda w, bn, im: conv_bn(im, w, bn, 2, 3, pool=True, **kw))(
        params["conv1"]["kernel"], params["bn1"], images)
    for i, (_, n) in enumerate(STAGES):
        for j in range(n):
            name = f"layer{i + 1}_{j}"
            stride = 2 if (i > 0 and j == 0) else 1
            x, moments[name] = jax.checkpoint(
                partial(_basic_block, stride=stride, kw=kw))(params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(_q(x, quant), _q(params["fc"]["kernel"], quant),
                     precision=HI) + params["fc"]["bias"]
    return logits, moments


def cross_entropy(logits, labels, smoothing):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    k = logits.shape[-1]
    target = jax.nn.one_hot(labels, k, dtype=jnp.float32)
    target = target * (1.0 - smoothing) + smoothing / k
    return -jnp.mean(jnp.sum(target * logp, axis=-1))


def loss_fn(params, images, labels, cfg, quant=None):
    logits, moments = forward(params, images, cfg, quant)
    return cross_entropy(logits, labels,
                         float(cfg.get("label_smoothing", 0.0))), moments


# ------------------------------------------------------------------ step --

def init_opt(params):
    return {"momentum": jax.tree_util.tree_map(jnp.zeros_like, params)}


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _step(params, stats, opt, images, labels, cfg_items, lr, quant=None):
    cfg = dict(cfg_items)
    (loss, moments), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, images, labels, cfg, quant)
    wd, mu, m = (float(cfg["weight_decay"]), float(cfg["momentum"]),
                 float(cfg["bn_momentum"]))
    # torch.optim.SGD: g += wd*p; v = mu*v + g; p -= lr*v
    g = jax.tree_util.tree_map(lambda g_, p: g_ + wd * p, grads, params)
    v = jax.tree_util.tree_map(lambda v_, g_: mu * v_ + g_, opt["momentum"], g)
    new_params = jax.tree_util.tree_map(lambda p, v_: p - lr * v_, params, v)

    def running(s, mom):
        mean, var, n = mom
        return {"mean": (1 - m) * s["mean"] + m * mean,
                "var": (1 - m) * s["var"] + m * var * (n / (n - 1.0))}
    new_stats = jax.tree_util.tree_map(
        running, stats, moments,
        is_leaf=lambda t: isinstance(t, dict) and "mean" in t)
    return loss, grads, new_params, new_stats, {"momentum": v}


def step(params, stats, opt, images, labels, cfg, lr, quant=None):
    """One optimizer step. Returns (loss, grads, params, stats, opt)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    return _step(params, stats, opt, images, labels, items,
                 jnp.asarray(lr, jnp.float32), quant=quant)
