"""Plain float32 reference of the `sdar_30b_ep8` configuration: one chip's
share of SDAR-30B-A3B-Chat (JetLM; `model_type` `sdar_moe`), as the
configuration's file states it, trained by diffusion over blocks with AdamW.

Straight `jax.numpy` under `jax.default_matmul_precision("highest")`; it
imports nothing of `tpudist` and is handed nothing the program made but the
seeded state it drew itself. Every size is read from the configuration (the
tiny twin of the CPU tests runs the same code).

The objective (SDAR, arXiv:2510.06303; the vectorised training of BD3-LM,
arXiv:2503.09573; masking and weights of LLaDA's guideline,
arXiv:2502.09992), for a row `x_0` of L ids in blocks of `block_length`
(`n(i) = i // block_length`):

1. `noise(key, x_0)`: from the raw `uint32[2]` key that `stats["noise_key"]`
   holds, `next, use = split(key)`, `k_t, k_m = split(use)`; a block's `t =
   uniform(k_t, [rows, ceil(L / block_length)], noise_eps, 1)`; position i is
   masked where `uniform(k_m, [rows, L]) < t_n(i)`; `x_t` holds
   `mask_token_id` there and `x_0` elsewhere. `next` is the key of the next
   step (`step` hands it back in `stats`).
2. The model sees one row `z = [x_t ; x_0]` of 2L ids at positions
   `[0..L-1 ; 0..L-1]`.
3. Query i sees key j (`seen`): both in the noised half, `n(j) == n(i)`;
   i noised and j clean, `n(j) < n(i)`; both clean, `n(j) <= n(i)`; i clean
   and j noised, never.
4. `loss = sum over the noised half of (m_i / t_n(i)) * (logsumexp(logits_i)
   - logits_i[x_0,i]) / (rows * L)`: a masked position's logits predict that
   position's clean id, with no shift; the head runs over the noised half.

A layer, for one row `x` [2L, hidden]:

    h = x + Attn(RMSNorm(x)),   y = h + MoE(RMSNorm(h))

- RMSNorm in float32, eps `rms_norm_eps`, a weight a feature.
- Attn: `q = x Wq` [2L, heads, head_dim], `k = x Wk`, `v = x Wv`
  [2L, kv_heads, head_dim], no bias; RMSNorm over `head_dim` on q and k
  (`assumed`); RoPE (rotate-half, `inv_freq_i = rope_theta^(-2i/head_dim)`,
  no scaling) at the position of each id; query head j reads key-value head
  `j // (heads / kv_heads)`; scores `q k^T / sqrt(head_dim)` under the mask
  of 3; softmax; `o = concat(heads) Wo`.
- MoE: `p = softmax(u Wr)` over all `num_experts`; the `num_experts_per_tok`
  largest, `w_e = p_e / sum of those` (`norm_topk_prob`); the result is the
  sum over the chosen e THAT ARE HELD HERE (`num_experts_held` consecutive
  experts, the `expert_share`-th group) of `w_e * (silu(u Wg_e) * (u Wu_e))
  Wd_e`: a loop over the held experts and a mask. What the absent experts
  would add is left out.
- Model: embedding [vocab_size held, hidden], `num_hidden_layers` layers,
  final RMSNorm, untied head [hidden, vocab_size held].

Attention runs `reference_block_rows` query rows at a time against all 2L
keys and the head as many positions at a time, each block made again in the
backward pass, and every layer and every expert is rematerialised: at the
cell's size (two rows of 8,192 ids, 16,384 positions each) the float32 step
then fits a 16 GB chip beside its own parameters and gradient and the
harness's copy of the first parameters; AdamW's moments wait on the host
between steps (`init_opt`).

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
NOISE_FOLD = 0x5DA2         # init: the noise key is fold_in(seed key, this)


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
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        rows=int(cfg["reference_block_rows"]), bl=int(cfg["block_length"]),
        noise_eps=float(cfg["noise_eps"]), mask=int(cfg["mask_token_id"]))


# ------------------------------------------------------------------- init --

def init(key, cfg):
    """(params, stats): every matrix N(0, 0.02), every norm 1, the
    embedding's rows N(0, `embedding_std`) (`assumed` says why); `stats` is
    the one leaf the objective needs, the raw key of the first step's
    noise."""
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
            "moe": {"router": _seat_mask_token(
                        normal(z["d"], z["experts"]), embedding[z["mask"]],
                        z),
                    "gate": normal(z["held"], z["d"], z["f"]),
                    "up": normal(z["held"], z["d"], z["f"]),
                    "down": normal(z["held"], z["f"], z["d"])}}
    return params, {"noise_key": jax.random.fold_in(key, NOISE_FOLD)}


def _seat_mask_token(router, mask_row, z):
    """The router's columns relabelled (the experts are drawn alike, so a
    relabelling is the same model) so that exactly ONE of the experts the
    mask token routes to is among those held here, in every layer: what the
    deployment's chips hold of them on average.

    Why (`assumed`, "mask token's experts"): at initialisation every masked
    position, a quarter of all positions, carries the same row of norm 45
    through every layer (what the layers add is a few percent of it), so all
    of them route to the same eight experts, one draw a layer. Left to the
    seed, 0 to 3 of the eight are held here and a layer's held pairs are
    24.6 / 32.8 / 41.0 / 49.2 thousand: the step's time followed the seed's
    draws by 1.3 % between six seeds, more than the benchmark's bound can
    tell apart. The mask token's favourite expert takes the first seat held,
    the next `num_experts - held` in its order the seats not held, its least
    favoured the rest: no near-tie at the eighth place can move a pair in or
    out of the held seats. Other tokens' logits do not depend on the mask
    row's, so their routing is drawn as before."""
    u = mask_row * lax.rsqrt(jnp.mean(jnp.square(mask_row)) + z["eps"])
    order = jnp.argsort(-jnp.matmul(u, router))     # its favourite first
    held = np.arange(z["first"], z["first"] + z["held"])
    seats = np.concatenate([
        held[:1], np.setdiff1d(np.arange(z["experts"]), held), held[1:]])
    return router[:, jnp.zeros_like(order).at[seats].set(order)]


# ------------------------------------------------------------------ noise --

def noise(key, x0, z):
    """-> (the next step's key, x_t, m / t, m) for rows `x0` [rows, L]."""
    rows, length = x0.shape
    carry, use = jax.random.split(key)
    k_t, k_m = jax.random.split(use)
    t = jax.random.uniform(k_t, (rows, -(-length // z["bl"])), jnp.float32,
                           minval=z["noise_eps"], maxval=1.0)
    t = jnp.repeat(t, z["bl"], axis=1)[:, :length]
    m = jax.random.uniform(k_m, (rows, length), jnp.float32) < t
    return carry, jnp.where(m, z["mask"], x0), jnp.where(m, 1.0 / t, 0.0), m


def seen(rows_at, length, bl):
    """[rows, 2L] bool: which keys of `[x_t ; x_0]` the queries at
    `rows_at` (indices into the same 2L) may see: the four rules."""
    cols = jnp.arange(2 * length)
    q_clean, k_clean = rows_at[:, None] >= length, cols[None, :] >= length
    n_q = (rows_at[:, None] % length) // bl
    n_k = (cols[None, :] % length) // bl
    return jnp.where(q_clean, k_clean & (n_k <= n_q),
                     jnp.where(k_clean, n_k < n_q, n_k == n_q))


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


def _rope(x, positions, theta):
    """Rotate `x` [b, t, heads, hd] to the position of each of its t ids."""
    hd = x.shape[-1]
    inv_freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    freqs = np.asarray(positions, np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), jnp.float32)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(x, p, z, length, quant):
    """`x` [b, 2L, d]: the doubled rows."""
    b, t, _ = x.shape
    group = z["heads"] // z["kv"]
    q = _mm(x, p["q_proj"]["kernel"], quant).reshape(b, t, z["heads"], z["hd"])
    k = _mm(x, p["k_proj"]["kernel"], quant).reshape(b, t, z["kv"], z["hd"])
    v = _mm(x, p["v_proj"]["kernel"], quant).reshape(b, t, z["kv"], z["hd"])
    positions = np.concatenate([np.arange(length), np.arange(length)])
    q = _rope(_rms(q, p["q_norm"], z["eps"]), positions, z["theta"])
    k = _rope(_rms(k, p["k_norm"], z["eps"]), positions, z["theta"])
    q = q.reshape(b, t, z["kv"], group, z["hd"])
    rows = z["rows"] if t % z["rows"] == 0 else t

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = jnp.einsum("brgjd,bkgd->bgjrk", _q(qb, quant),
                       _q(k, quant)) / math.sqrt(z["hd"])
        allowed = seen(i * rows + jnp.arange(rows), length, z["bl"])
        a = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
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
    # (token, expert) pairs of each held expert: printed, not compared
    pairs = jnp.sum(chosen[:, :, None] == held[None, None, :], axis=(0, 1))
    return y, lax.stop_gradient(pairs)


def _layer(x, p, z, length, quant):
    h = x + _attention(_rms(x, p["input_norm"], z["eps"]),
                       p["self_attention"], z, length, quant)
    b, t, d = h.shape
    u = _rms(h, p["post_norm"], z["eps"]).reshape(b * t, d)
    y, pairs = _moe(u, p["moe"], z, quant)
    return h + y.reshape(b, t, d), pairs


def hidden_states(params, xt, x0, cfg, quant=None):
    """The last layer's output [rows, 2L, d] for `[x_t ; x_0]`, and the held
    experts' pairs a layer."""
    z = _sizes(cfg)
    length = x0.shape[1]
    x = params["embed"]["embedding"][jnp.concatenate([xt, x0], axis=1)]
    routed = []
    for i in range(z["layers"]):
        layer = jax.checkpoint(functools.partial(
            _layer, z=z, length=length, quant=quant))
        x, pairs = layer(x, params[f"layer_{i}"])
        routed.append(pairs)
    return x, jnp.stack(routed)


def head_loss(params, hidden, targets, weights, cfg, quant=None):
    """`sum(weights * cross entropy(head(RMSNorm(hidden)), targets)) / (rows
    * L)` for `hidden` [rows, L, d], `reference_block_rows` positions at a
    time."""
    z = _sizes(cfg)
    x = _rms(hidden, params["norm"], z["eps"])
    n = targets.size
    rows = z["rows"] if n % z["rows"] == 0 else n

    @jax.checkpoint
    def block(total, xs):
        h, y, w = xs
        logits = _mm(h, params["head"], quant)
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        return total + jnp.sum(w * nll), None

    total, _ = lax.scan(block, jnp.zeros((), jnp.float32),
                        (x.reshape(n // rows, rows, -1),
                         targets.reshape(n // rows, rows),
                         weights.reshape(n // rows, rows)))
    return total / n


def loss_fn(params, key, x0, cfg, quant=None):
    """-> (loss, (the next step's key, pairs a layer and held expert, the
    masked share, the sum of m / t over rows x L))."""
    length = x0.shape[1]
    carry, xt, weights, m = noise(key, x0, _sizes(cfg))
    x, routed = hidden_states(params, xt, x0, cfg, quant)
    # the noised half's logits against the clean ids, position for position
    loss = head_loss(params, x[:, :length], x0, weights, cfg, quant)
    return loss, (carry, routed, jnp.mean(m.astype(jnp.float32)),
                  jnp.sum(weights) / m.size)


# ------------------------------------------------------------------- step --

def init_opt(params):
    """AdamW's moments, kept on the HOST between steps (numpy), as
    refs/mellum2_12b_ep4.py keeps them and for its reason: the gradient's
    program needs the chip beside the harness's copy of the first
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

    def grads(params, key, x0):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_fn, has_aux=True)(
                params, key, x0, cfg, quant)

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
    """One optimizer step. `tokens` is the clean row `x_0`; the mix's next-id
    `targets` are not read: a masked position's target is its own id.
    Returns (loss, grads, params, stats, opt): `stats` holds the next step's
    noise key; the moments come and go as host arrays (`init_opt`)."""
    del targets
    grads, update = _programs(json.dumps(cfg, sort_keys=True), quant)
    (loss, (carry, pairs, masked, weight)), g = grads(
        params, stats["noise_key"], tokens)
    said = {"quant": quant, "bd_masked_share": float(masked),
            "bd_weight_sum": float(weight),
            "pairs_by_layer_and_held_expert": np.asarray(pairs).tolist()}
    print("bench bd_reference " + json.dumps(said), flush=True)
    # the moments visit the chip for the update, which writes them in place
    new_params, new_opt = update(params, g, jax.device_put(opt),
                                 jnp.asarray(lr, jnp.float32))
    return (loss, g, new_params, {"noise_key": carry},
            jax.device_get(new_opt))
