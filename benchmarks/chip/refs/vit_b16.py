"""Plain float32 reference of the `vit_b16` configuration.

ViT-Base/16 of arXiv:2010.11929 Table 1 as torchvision builds `vit_b_16`
(class token, learned position embedding, pre-norm encoder blocks, fused QKV
projection, LayerNorm eps 1e-6, no dropout), trained by torch.optim.AdamW.
Straight `jax.numpy` at `Precision.HIGHEST`; it imports nothing of `tpudist`
and is handed nothing the program made. Each encoder block is rematerialised
so the float32 backward of a batch of 128 fits beside nothing else on a
16 GB chip.

Departures from torchvision, each because the program under test does the
same and the comparison is of precision, not of these choices (listed in
PERF.md for a later PR):
- GELU is the tanh approximation (torchvision: erf);
- the fused QKV kernel's columns are head-major [head][q|k|v][head_dim]
  (torch: [q|k|v][head][head_dim]) - a column permutation of a random matrix;
- weight decay applies to leaves of `decay_min_ndim` dimensions or more;
- the head is drawn N(0, 0.02) where torchvision zero-fills it: under a zero
  head every gradient but the head's is exactly zero at the first step and
  the comparison would see nothing.

`quant` is for the control only (see resnet18_ref.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


# ------------------------------------------------------------------ init --

def _xavier(key, shape):
    bound = (6.0 / (shape[0] + shape[1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _ln(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def init(key, cfg):
    """(params, batch_stats={}) drawn as torchvision's VisionTransformer
    does (head excepted, see above)."""
    d, mlp, p = (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
                 int(cfg["patch_size"]))
    layers, k = int(cfg["num_hidden_layers"]), int(cfg["num_classes"])
    tokens = (int(cfg["image_size"]) // p) ** 2 + 1
    keys = iter(jax.random.split(key, 8 + 8 * layers))
    fan_in = 3 * p * p
    params = {
        "conv_proj": {
            "kernel": jax.random.truncated_normal(
                next(keys), -2.0, 2.0, (p, p, 3, d), jnp.float32)
            * (1.0 / fan_in) ** 0.5,
            "bias": jnp.zeros((d,), jnp.float32)},
        "class_token": jnp.zeros((1, 1, d), jnp.float32),
        "pos_embedding": jax.random.normal(next(keys), (1, tokens, d),
                                           jnp.float32) * 0.02,
        "ln": _ln(d),
        "head": {"kernel": jax.random.normal(next(keys), (d, k),
                                             jnp.float32) * 0.02,
                 "bias": jnp.zeros((k,), jnp.float32)},
    }
    for i in range(layers):
        bound = 1.0 / d ** 0.5
        params[f"encoder_layer_{i}"] = {
            "ln_1": _ln(d),
            "self_attention": {
                "in_proj": {"kernel": _xavier(next(keys), (d, 3 * d)),
                            "bias": jnp.zeros((3 * d,), jnp.float32)},
                "out_proj": {"kernel": jax.random.uniform(
                    next(keys), (d, d), jnp.float32, -bound, bound),
                    "bias": jnp.zeros((d,), jnp.float32)}},
            "ln_2": _ln(d),
            "mlp_0": {"kernel": _xavier(next(keys), (d, mlp)),
                      "bias": jax.random.normal(next(keys), (mlp,),
                                                jnp.float32) * 1e-6},
            "mlp_3": {"kernel": _xavier(next(keys), (mlp, d)),
                      "bias": jax.random.normal(next(keys), (d,),
                                                jnp.float32) * 1e-6},
        }
    return params, {}


# --------------------------------------------------------------- forward --

def _q(x, quant):
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


def _dense(x, p, quant):
    return jnp.dot(_q(x, quant), _q(p["kernel"], quant),
                   precision=HI) + p["bias"]


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        (2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def _block(x, p, heads, eps, quant):
    b, t, d = x.shape
    hd = d // heads
    y = _layer_norm(x, p["ln_1"], eps)
    qkv = _dense(y, p["self_attention"]["in_proj"], quant)
    qkv = qkv.reshape(b, t, heads, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant),
                   precision=HI) / hd ** 0.5
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(a, quant), _q(v, quant),
                   precision=HI).reshape(b, t, d)
    x = x + _dense(o, p["self_attention"]["out_proj"], quant)
    y = _layer_norm(x, p["ln_2"], eps)
    y = _gelu_tanh(_dense(y, p["mlp_0"], quant))
    return x + _dense(y, p["mlp_3"], quant)


def forward(params, images, cfg, quant=None):
    d, p = int(cfg["hidden_size"]), int(cfg["patch_size"])
    heads, eps = int(cfg["num_attention_heads"]), float(cfg["layer_norm_eps"])
    b = images.shape[0]
    x = lax.conv_general_dilated(
        _q(images, quant), _q(params["conv_proj"]["kernel"], quant), (p, p),
        "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    x = x.reshape(b, -1, d) + params["conv_proj"]["bias"]
    cls = jnp.broadcast_to(params["class_token"], (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embedding"]
    block = jax.checkpoint(partial(_block, heads=heads, eps=eps, quant=quant))
    for i in range(int(cfg["num_hidden_layers"])):
        x = block(x, params[f"encoder_layer_{i}"])
    x = _layer_norm(x, params["ln"], eps)
    return _dense(x[:, 0], params["head"], quant), {}


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
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    return {"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.float32)}


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _step(params, stats, opt, images, labels, cfg_items, lr, quant=None):
    cfg = dict(cfg_items)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, images, labels, cfg, quant)
    b1, b2, eps = float(cfg["adam_b1"]), float(cfg["adam_b2"]), float(
        cfg["adam_eps"])
    wd, min_ndim = float(cfg["weight_decay"]), int(cfg["decay_min_ndim"])
    t = opt["count"] + 1.0
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                opt["nu"], grads)

    # torch.optim.AdamW: p -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)
    def update(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= min_ndim:
            u = u + wd * p
        return p - lr * u
    new_params = jax.tree_util.tree_map(update, params, mu, nu)
    return loss, grads, new_params, stats, {"mu": mu, "nu": nu, "count": t}


def step(params, stats, opt, images, labels, cfg, lr, quant=None):
    """One optimizer step. Returns (loss, grads, params, stats, opt)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    return _step(params, stats, opt, images, labels, items,
                 jnp.asarray(lr, jnp.float32), quant=quant)
