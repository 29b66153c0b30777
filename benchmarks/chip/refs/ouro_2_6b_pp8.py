"""Plain float32 reference of the `ouro_2_6b_pp8` configuration: one pipeline
stage's share of Ouro-2.6B (ByteDance; `model_type` `ouro`; the looped
language model of arXiv:2510.25741), as the configuration's file states it,
trained by AdamW.

Straight `jax.numpy` under `jax.default_matmul_precision("highest")`; it
imports nothing of `tpudist` and is handed nothing the program made. Every
size is read from the configuration (the tiny twin of the CPU tests runs the
same code). `T = total_ut_steps` passes over `N = num_hidden_layers` layers,
as Python loops: nothing here is tied by construction, the same leaves are
simply read `T` times.

    h = Embed(ids)
    for t = 1..T:
        for l = 1..N:
            h = h + Norm2_l(Attn_l(Norm1_l(h)))
            h = h + Norm4_l(MLP_l(Norm3_l(h)))
        h = Norm_f(h);   h_t = h            (h_t goes on into pass t + 1)
        logits_t = h_t W_head;   lambda_t = sigmoid(h_t . w_g + b_g)

- Every norm an RMSNorm in float32, eps `rms_norm_eps`, its own weight.
- Attn: `q = x Wq`, `k = x Wk`, `v = x Wv` [L, heads, head_dim] (as many
  key-value heads as query heads: a group of one), no bias, no q / k norm
  (`assumed`); RoPE (rotate-half, `inv_freq_i = rope_theta^(-2i/head_dim)`,
  positions 0..L-1, the same at every pass); scores `q k^T /
  sqrt(head_dim)`; query i sees key j where `j <= i`; softmax; `o =
  concat(heads) Wo`.
- MLP: `down(silu(gate(x)) * up(x))`, width `intermediate_size`, no bias.
- The exit distribution, a position: `S_1 = 1`, `S_{t+1} = S_t (1 -
  lambda_t)`; `p_t = lambda_t S_t` for `t < T`, `p_T = S_T`. The loss is the
  mean over rows x L of `sum_t p_t CE(logits_t, y) - beta H(p)`, `H(p) = -
  sum_t p_t log p_t` (`exit_beta`; `log` of at least 1e-30, where the
  program floors it too: 0 log 0 is 0), the four terms written out.

Attention runs `reference_block_rows` query rows at a time and the
feed-forward, the head and the loss as many positions at a time, each block
made again in the backward pass, and every layer pass is rematerialised: at the cell's size (one
sequence of 8,192, 24 layer passes) the float32 step fits a 16 GB chip beside
its own parameters and gradient and the harness's copy of the first
parameters; AdamW's moments wait on the host between steps (`init_opt`).

`quant` is for the control only (see resnet18_ref.py): every matrix product
reads its operands through fp8 or bf16 and back (the gate's own product stays
float32, as the program's does: it reads the float32 norm, like a router).
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
TINY = 1e-30


# ------------------------------------------------------------------ sizes --

def _sizes(cfg):
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        layers=int(cfg["num_hidden_layers"]), vocab=int(cfg["vocab_size"]),
        f=int(cfg["intermediate_size"]), passes=int(cfg["total_ut_steps"]),
        beta=float(cfg["exit_beta"]), theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]), rows=int(cfg["reference_block_rows"]))


# ------------------------------------------------------------------- init --

def init(key, cfg):
    """(params, batch_stats={}): every matrix N(0, 0.02) (the gate's weight
    too), every norm 1, the gate's bias 0; the embedding's rows N(0,
    `embedding_std`) where the configuration says so (`assumed` there says
    why)."""
    z = _sizes(cfg)
    if z["heads"] != z["kv"]:
        raise ValueError("this reference is of plain multi-head attention")
    keys = iter(jax.random.split(key, 3 + 7 * z["layers"]))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * STD

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    embedding = normal(z["vocab"], z["d"]) * (
        float(cfg.get("embedding_std", STD)) / STD)
    width = z["heads"] * z["hd"]
    params = {"embed": {"embedding": embedding},
              "norm": ones(z["d"]),
              "head": normal(z["d"], z["vocab"]),
              "exit_gate": {"kernel": normal(z["d"], 1),
                            "bias": jnp.zeros((1,), jnp.float32)}}
    for i in range(z["layers"]):
        params[f"layer_{i}"] = {
            "input_norm": ones(z["d"]), "attn_out_norm": ones(z["d"]),
            "post_norm": ones(z["d"]), "mlp_out_norm": ones(z["d"]),
            "self_attention": {
                "q_proj": {"kernel": normal(z["d"], width)},
                "k_proj": {"kernel": normal(z["d"], width)},
                "v_proj": {"kernel": normal(z["d"], width)},
                "o_proj": {"kernel": normal(width, z["d"])}},
            "mlp": {"gate_proj": {"kernel": normal(z["d"], z["f"])},
                    "up_proj": {"kernel": normal(z["d"], z["f"])},
                    "down_proj": {"kernel": normal(z["f"], z["d"])}}}
    return params, {}


# ---------------------------------------------------------------- forward --

def rope_tables(theta, hd, t):
    """cos, sin [t, hd] float32."""
    inv_freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    freqs = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (jnp.asarray(np.cos(emb), jnp.float32),
            jnp.asarray(np.sin(emb), jnp.float32))


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


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


def _attention(x, p, z, quant):
    b, t, _ = x.shape
    shape = (b, t, z["heads"], z["hd"])
    cos, sin = rope_tables(z["theta"], z["hd"], t)
    q = _rope(_mm(x, p["q_proj"]["kernel"], quant).reshape(shape), cos, sin)
    k = _rope(_mm(x, p["k_proj"]["kernel"], quant).reshape(shape), cos, sin)
    v = _mm(x, p["v_proj"]["kernel"], quant).reshape(shape)
    rows = z["rows"] if t % z["rows"] == 0 else t
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = jnp.einsum("brhd,bkhd->bhrk", _q(qb, quant),
                       _q(k, quant)) / math.sqrt(z["hd"])
        seen = cols[None, :] <= (i * rows + jnp.arange(rows))[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhrk,bkhd->brhd", _q(a, quant), _q(v, quant))

    out = lax.map(block, jnp.arange(t // rows))      # [blocks, b, rows, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, z["heads"] * z["hd"])
    return _mm(out, p["o_proj"]["kernel"], quant)


def _mlp(x, p, z, quant):
    b, t, d = x.shape
    rows = z["rows"] if (b * t) % z["rows"] == 0 else b * t

    @jax.checkpoint
    def block(xb):
        h = jax.nn.silu(_mm(xb, p["gate_proj"]["kernel"], quant)) * _mm(
            xb, p["up_proj"]["kernel"], quant)
        return _mm(h, p["down_proj"]["kernel"], quant)

    return lax.map(block, x.reshape(b * t // rows, rows, d)).reshape(b, t, d)


def _layer(x, p, z, quant):
    h = x + _rms(_attention(_rms(x, p["input_norm"], z["eps"]),
                            p["self_attention"], z, quant),
                 p["attn_out_norm"], z["eps"])
    return h + _rms(_mlp(_rms(h, p["post_norm"], z["eps"]), p["mlp"], z,
                         quant), p["mlp_out_norm"], z["eps"])


def passes(params, tokens, cfg, quant=None, layer_params=None):
    """[(h_1, leaves), .., (h_T, leaves)]: each pass's result [rows, L,
    hidden] and the parameters as that pass reads them (the head and the
    gate of a pass are read from there too). `layer_params(t, l)` names the
    leaves pass `t` reads for layer `l` (the tests' untied twin holds `T N`
    layers; None: `layer_<l>` at every pass).

    Pass `t` reads the parameters through `lax.optimization_barrier` of
    what pass `t - 1` read: an identity, there for the memory alone. A tied
    leaf's gradient is the sum of `T` partial ones; without the barriers
    XLA sums them in one fusion behind the last and holds all of them (and
    what it derives from a leaf for one pass's products through every other
    pass): 11.6 GiB of temporaries at the cell's size, 16 GB with the
    gradient and two sets of parameters. Chained, each partial gradient is
    added as it is made."""
    z = _sizes(cfg)
    layer = jax.checkpoint(functools.partial(_layer, z=z, quant=quant))
    h, out = params["embed"]["embedding"][tokens], []
    for t in range(z["passes"]):
        params = lax.optimization_barrier(params)
        for i in range(z["layers"]):
            h = layer(h, layer_params(params, t, i) if layer_params
                      else params[f"layer_{i}"])
        h = _rms(h, params["norm"], z["eps"])
        out.append((h, params))
    return out


def exit_distribution(leave):
    """[p_1, .., p_T] from [lambda_1, .., lambda_T] (the last is not read:
    what is left exits at the last pass)."""
    survive, p = jnp.ones_like(leave[0]), []
    for lam in leave[:-1]:
        p.append(lam * survive)
        survive = survive * (1.0 - lam)
    return p + [survive]


def _cross_entropy(h, head, targets, rows, quant):
    """CE of every position [rows x L], the head `rows` positions a time."""
    n = h.shape[0] * h.shape[1]
    rows = rows if n % rows == 0 else n

    @jax.checkpoint
    def block(_, xs):
        x, y = xs
        logits = _mm(x, head, quant)
        return None, (jax.nn.logsumexp(logits, axis=-1)
                      - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])

    _, nll = lax.scan(block, None, (h.reshape(n // rows, rows, -1),
                                    targets.reshape(n // rows, rows)))
    return nll.reshape(targets.shape)


def loss_fn(params, tokens, targets, cfg, quant=None, layer_params=None):
    z = _sizes(cfg)
    leave, ce = [], []
    for h, read in passes(params, tokens, cfg, quant, layer_params):
        gate = read["exit_gate"]
        leave.append(jax.nn.sigmoid(
            jnp.matmul(h, gate["kernel"])[..., 0] + gate["bias"]))
        ce.append(_cross_entropy(h, read["head"], targets, z["rows"], quant))
    p = exit_distribution(leave)
    expected = p[0] * ce[0]
    entropy = -p[0] * jnp.log(jnp.maximum(p[0], TINY))
    exit_step = p[0]
    for t in range(1, z["passes"]):
        expected = expected + p[t] * ce[t]
        entropy = entropy - p[t] * jnp.log(jnp.maximum(p[t], TINY))
        exit_step = exit_step + (t + 1) * p[t]
    loss = jnp.mean(expected - z["beta"] * entropy)
    return loss, (lax.stop_gradient(jnp.mean(exit_step)),
                  lax.stop_gradient(jnp.mean(entropy)),
                  lax.stop_gradient(jnp.stack([jnp.mean(c) for c in ce])))


# ------------------------------------------------------------------- step --

def init_opt(params):
    """AdamW's moments, kept on the HOST between steps (numpy), as the other
    decoders' references keep them: the chip holds the gradient's program,
    its parameters, its gradient and the harness's copy of the first
    parameters."""
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
    (loss, (exit_step, entropy, ce)), g = grads(params, tokens, targets)
    print("bench loop_exit_reference " + json.dumps({
        "quant": quant, "loop_expected_exit": float(exit_step),
        "loop_exit_entropy": float(entropy),
        "cross_entropy_by_pass": np.asarray(ce).tolist()}), flush=True)
    # the moments visit the chip for the update, which writes them in place
    new_params, new_opt = update(params, g, jax.device_put(opt),
                                 jnp.asarray(lr, jnp.float32))
    return loss, g, new_params, stats, jax.device_get(new_opt)
