"""Plain float32 reference of the `mellum2_12b_ep4` configuration: one
chip's share of Mellum2-12B-A2.5B (JetBrains; `model_type` `mellum`), as the
configuration's file states it, trained by AdamW.

Straight `jax.numpy` under `jax.default_matmul_precision("highest")`; it
imports nothing of `tpudist` and is handed nothing the program made. Every
size is read from the configuration (the tiny twin of the CPU tests runs the
same code).

A layer, for one sequence `x` [T, hidden]:

    h = x + Attn(RMSNorm(x)),   y = h + MoE(RMSNorm(h))

- RMSNorm in float32, eps `rms_norm_eps`, a weight a feature.
- Attn: `q = x Wq` [T, heads, head_dim], `k = x Wk`, `v = x Wv`
  [T, kv_heads, head_dim], no bias; RMSNorm over `head_dim` on q and k
  (`assumed`); RoPE (rotate-half, positions 0..T-1) by the table of the
  layer's type, `rope_parameters[layer_types[l]]`: `default` is `inv_freq_i =
  theta^(-2i/head_dim)`; `yarn` as `transformers` computes it (`_yarn`); query
  head j reads key-value head `j // (heads / kv_heads)`; scores `q k^T /
  sqrt(head_dim)`; query i sees key j where `j <= i` and, in a
  `sliding_attention` layer, `i - j < sliding_window`; softmax; `o =
  concat(heads) Wo`.
- MoE: `p = softmax(u Wr)` over all `num_experts`; the `num_experts_per_tok`
  largest, `w_e = p_e / sum of those`; the result is the sum over the chosen
  e THAT ARE HELD HERE (`num_experts_held` consecutive experts, the
  `expert_share`-th group) of `w_e * (silu(u Wg_e) * (u Wu_e)) Wd_e`: a loop
  over the held experts and a mask. The weights stay normalised over all
  chosen, held or not; what the absent experts would add is left out.
- Model: embedding [vocab_size held, hidden], `num_hidden_layers` layers,
  final RMSNorm, untied head [hidden, vocab_size held]; the loss is the mean
  over all rows x T positions of the cross entropy against the next id.

Attention runs `reference_block_rows` query rows at a time and the head and
loss as many positions at a time, each block made again in the backward
pass, and every layer and every expert is rematerialised: at the cell's
size (two sequences of 8,192) the float32 step then fits a 16 GB chip beside
its own parameters and gradient and the harness's copy of the first
parameters; AdamW's moments wait on the host between steps (`init_opt`).

`quant` is for the control only (see resnet18_ref.py): every matrix product
reads its operands through fp8 or bf16 and back.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STD = 0.02


# ------------------------------------------------------------------ sizes --

def _sizes(cfg):
    held = int(cfg["num_experts_held"])
    share = int(str(cfg.get("expert_share", "0 of 1")).split(" of ")[0])
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        layers=int(cfg["num_hidden_layers"]), vocab=int(cfg["vocab_size"]),
        experts=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        held=held, first=share * held, f=int(cfg["moe_intermediate_size"]),
        window=int(cfg["sliding_window"]), eps=float(cfg["rms_norm_eps"]),
        rows=int(cfg["reference_block_rows"]))


# ------------------------------------------------------------------- init --

def init(key, cfg):
    """(params, batch_stats={}): every matrix N(0, 0.02), every norm 1; the
    embedding's rows N(0, `embedding_std`) where the configuration says so
    (`assumed` there says why)."""
    z = _sizes(cfg)
    keys = iter(jax.random.split(key, 2 + 8 * z["layers"]))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * STD

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    embedding = normal(z["vocab"], z["d"]) * (
        float(cfg.get("embedding_std", STD)) / STD)
    params = {"embed": {"embedding": embedding},
              "norm": ones(z["d"]),
              "head": normal(z["d"], z["vocab"])}
    for i in range(z["layers"]):
        params[f"layer_{i}"] = {
            "input_norm": ones(z["d"]),
            "self_attention": {
                "q_proj": {"kernel": normal(z["d"], z["heads"] * z["hd"])},
                "k_proj": {"kernel": normal(z["d"], z["kv"] * z["hd"])},
                "v_proj": {"kernel": normal(z["d"], z["kv"] * z["hd"])},
                "o_proj": {"kernel": normal(z["heads"] * z["hd"], z["d"])},
                "q_norm": ones(z["hd"]), "k_norm": ones(z["hd"])},
            "post_norm": ones(z["d"]),
            "moe": {"router": normal(z["d"], z["experts"]),
                    "gate": normal(z["held"], z["d"], z["f"]),
                    "up": normal(z["held"], z["d"], z["f"]),
                    "down": normal(z["held"], z["f"], z["d"])}}
    return params, {}


# ------------------------------------------------------------------- rope --

def _yarn(p, hd):
    """`transformers`' `_compute_yarn_parameters`: (inv_freq, factor on cos
    and sin)."""
    base, factor = float(p["rope_theta"]), float(p["factor"])
    original = float(p["original_max_position_embeddings"])
    pos_freqs = base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return (hd * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = correction_dim(float(p["beta_fast"]))
    high = correction_dim(float(p["beta_slow"]))
    if p.get("truncate", True):                  # `assumed`: true
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (interpolation * (1 - keep) + extrapolation * keep,
            float(p["attention_factor"]))


def rope_tables(p, hd, t):
    """cos, sin [t, hd] float32 of one `rope_parameters` entry."""
    if p["rope_type"] == "yarn":
        inv_freq, factor = _yarn(p, hd)
    elif p["rope_type"] == "default":
        inv_freq = float(p["rope_theta"]) ** (
            -np.arange(0, hd, 2, dtype=np.float64) / hd)
        factor = 1.0
    else:
        raise ValueError(f"rope_type {p['rope_type']!r}")
    freqs = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (jnp.asarray(np.cos(emb) * factor, jnp.float32),
            jnp.asarray(np.sin(emb) * factor, jnp.float32))


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


# ---------------------------------------------------------------- forward --

def _q(x, quant):
    if quant is None:
        return x
    if quant == "bf16":
        # not astype there and back: XLA on the TPU elides that round trip
        r = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif quant == "fp8":
        amax = jnp.max(jnp.abs(x))
        s = jnp.where(amax > 0, 448.0 / amax, 1.0)
        r = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + lax.stop_gradient(r - x)


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant))


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * p["scale"]


def _attention(x, p, z, kind, rope_parameters, quant):
    b, t, _ = x.shape
    group = z["heads"] // z["kv"]
    q = _mm(x, p["q_proj"]["kernel"], quant).reshape(b, t, z["heads"], z["hd"])
    k = _mm(x, p["k_proj"]["kernel"], quant).reshape(b, t, z["kv"], z["hd"])
    v = _mm(x, p["v_proj"]["kernel"], quant).reshape(b, t, z["kv"], z["hd"])
    cos, sin = rope_tables(rope_parameters[kind], z["hd"], t)
    q = _rope(_rms(q, p["q_norm"], z["eps"]), cos, sin)
    k = _rope(_rms(k, p["k_norm"], z["eps"]), cos, sin)
    q = q.reshape(b, t, z["kv"], group, z["hd"])
    rows = z["rows"] if t % z["rows"] == 0 else t
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = jnp.einsum("brgjd,bkgd->bgjrk", _q(qb, quant),
                       _q(k, quant)) / math.sqrt(z["hd"])
        at = i * rows + jnp.arange(rows)
        seen = cols[None, :] <= at[:, None]
        if kind == "sliding_attention":
            seen &= at[:, None] - cols[None, :] < z["window"]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgjrk,bkgd->brgjd", _q(a, quant), _q(v, quant))

    out = lax.map(block, jnp.arange(t // rows))      # [blocks, b, rows, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, z["heads"] * z["hd"])
    return _mm(out, p["o_proj"]["kernel"], quant)


def _moe(u, p, z, quant):
    """The held experts' part of the layer's result for tokens u [T, d]."""
    probs = jax.nn.softmax(jnp.matmul(u, p["router"]), axis=-1)
    top, chosen = lax.top_k(probs, z["k"])
    weights = top / jnp.sum(top, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(index, gate, up, down):
        mine = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
        h = jax.nn.silu(_mm(u, gate, quant)) * _mm(u, up, quant)
        return mine[:, None] * _mm(h, down, quant)

    # the sum is carried, the rematerialised part is not handed it: the
    # backward pass then keeps no step's sum for the next
    held = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(lambda y, e: (y + one(*e), None), jnp.zeros_like(u),
                    (held, p["gate"], p["up"], p["down"]))
    # (token, expert) pairs of each held expert, and every token's chosen
    # experts: printed, not compared
    pairs = jnp.sum(chosen[:, :, None] == held[None, None, :], axis=(0, 1))
    return y, (lax.stop_gradient(pairs), lax.stop_gradient(chosen))


def _layer(x, p, z, kind, rope_parameters, quant):
    h = x + _attention(_rms(x, p["input_norm"], z["eps"]),
                       p["self_attention"], z, kind, rope_parameters, quant)
    b, t, d = h.shape
    u = _rms(h, p["post_norm"], z["eps"]).reshape(b * t, d)
    y, routed = _moe(u, p["moe"], z, quant)
    return h + y.reshape(b, t, d), routed


def loss_fn(params, tokens, targets, cfg, quant=None):
    z = _sizes(cfg)
    x = params["embed"]["embedding"][tokens]
    routed = []
    for i in range(z["layers"]):
        layer = jax.checkpoint(functools.partial(
            _layer, z=z, kind=cfg["layer_types"][i],
            rope_parameters=cfg["rope_parameters"], quant=quant))
        x, of_layer = layer(x, params[f"layer_{i}"])
        routed.append(of_layer)
    x = _rms(x, params["norm"], z["eps"])
    n = x.shape[0] * x.shape[1]
    rows = z["rows"] if n % z["rows"] == 0 else n

    @jax.checkpoint
    def block(total, xs):
        h, y = xs
        logits = _mm(h, params["head"], quant)
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        return total + jnp.sum(nll), None

    total, _ = lax.scan(block, jnp.zeros((), jnp.float32),
                        (x.reshape(n // rows, rows, -1),
                         targets.reshape(n // rows, rows)))
    return total / n, (jnp.stack([pairs for pairs, _ in routed]),
                       jnp.stack([chosen for _, chosen in routed]))


# ------------------------------------------------------------------- step --

def routed_otherwise(params, tokens, targets, cfg, quant="bf16"):
    """By hand (`selftest/read_limits_mix.py`): the share of (token, expert)
    pairs, a layer, that this reference routes otherwise once every matrix
    product reads its operands through `quant` (the router's own product
    stays float32, as the program's does): how many near-tied pairs a sound
    bfloat16 program can be expected to give to another expert."""
    def chosen(q):
        with jax.default_matmul_precision("highest"):
            _, (_, experts) = jax.jit(
                lambda p, x, y: loss_fn(p, x, y, cfg, q))(params, tokens,
                                                          targets)
        return np.asarray(experts)
    a, b = chosen(None), chosen(quant)
    kept = (a[..., :, None] == b[..., None, :]).any(axis=-1)
    return (1.0 - kept.mean(axis=(1, 2))).tolist()


def init_opt(params):
    """AdamW's moments, kept on the HOST between steps (numpy): with them on
    the chip the gradient's program peaked at 16.67 of its 16.91 GB beside
    the harness's copy of the first parameters."""
    def zeros():
        return jax.tree_util.tree_map(
            lambda p: np.zeros(p.shape, np.float32), params)
    return {"mu": zeros(), "nu": zeros(), "count": np.zeros((), np.float32)}


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, quant):
    cfg = json.loads(cfg_json)
    b1, b2 = float(cfg["adam_b1"]), float(cfg["adam_b2"])
    eps, wd = float(cfg["adam_eps"]), float(cfg["weight_decay"])
    min_ndim = int(cfg["decay_min_ndim"])

    def grads(params, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_fn, has_aux=True)(
                params, tokens, targets, cfg, quant)

    # torch.optim.AdamW: p -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)
    def update(params, g, opt, lr):
        t = opt["count"] + 1.0
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x,
                                    opt["mu"], g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    opt["nu"], g)

        def new(p, m, v):
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if p.ndim >= min_ndim:
                u = u + wd * p
            return p - lr * u
        return (jax.tree_util.tree_map(new, params, mu, nu),
                {"mu": mu, "nu": nu, "count": t})

    return jax.jit(grads), jax.jit(update, donate_argnums=(2,))


def step(params, stats, opt, tokens, targets, cfg, lr, quant=None):
    """One optimizer step. Returns (loss, grads, params, stats, opt); the
    moments come and go as host arrays (`init_opt`)."""
    grads, update = _programs(json.dumps(cfg, sort_keys=True), quant)
    (loss, (pairs, _)), g = grads(params, tokens, targets)
    said = {"quant": quant,
            "pairs_by_layer_and_held_expert": np.asarray(pairs).tolist()}
    print("bench moe_route_reference " + json.dumps(said), flush=True)
    # the moments visit the chip for the update, which writes them in place
    new_params, new_opt = update(params, g, jax.device_put(opt),
                                 jnp.asarray(lr, jnp.float32))
    return loss, g, new_params, stats, jax.device_get(new_opt)
