"""Plain float32 reference of the `nemotron3_nano_ep16` configuration: one
chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type` `nemotron_h`),
as the configuration's file states it, trained on the next id by AdamW.

Straight `jax.numpy` under `jax.default_matmul_precision("highest")`; it
imports nothing of `tpudist` and is handed nothing the program made. Every
size is read from the configuration (the tiny twin of the CPU tests runs the
same code).

A block, for one sequence `x` [T, hidden], is ONE mixer behind one RMSNorm
(float32, eps `layer_norm_epsilon`, a weight a feature):

    y = x + Mixer(RMSNorm(x))

and the mixer is what the block's letter in `hybrid_override_pattern` says
(the first `num_hidden_layers` letters are kept):

- `M`, Mamba-2. `u W_in` [T, 2 I + 2 G N + H] splits into the gate `z` [I =
  H P], `xBC` [I + 2 G N] and `dt` [H] (H = `mamba_num_heads`, P =
  `mamba_head_dim`, N = `ssm_state_size`, G = `n_groups`). A causal depthwise
  convolution of `conv_kernel` taps with bias over `xBC` (`y_t = b + sum_k
  w_k x_(t - K + 1 + k)`, zeros before the row), then SiLU; `xBC` -> `x` [H,
  P], `B`, `C` [G, N] (a group serves H / G heads). `dt = softplus(dt +
  dt_bias)`, `A = -exp(A_log)` a scalar a head. Then THE RECURRENCE ITSELF,
  one position at a time (`lax.scan` over positions, no chunked form), a head:

      h_t = exp(dt_t A) h_(t-1) + dt_t x_t B_t^T      (h is [P, N], h_0 = 0)
      y_t = h_t C_t + D x_t

  `y <- RMSNorm_by_group(y * silu(z)) * w` (the gate first, then the norm
  over each group's I / G channels); `y W_out`. No bias but the convolution's.
- `*`, attention: `q = u Wq` [T, heads, head_dim], `k = u Wk`, `v = u Wv`
  [T, kv_heads, head_dim], no bias, NO rotation and NO q/k norm (`assumed`:
  `nemotron_h`'s attention reads neither `rope_theta` nor
  `partial_rotary_factor`; the Mamba layers carry position); query head j
  reads key-value head `j // (heads / kv_heads)`; scores `q k^T /
  sqrt(head_dim)`; query i sees key j where `j <= i`; softmax; `o =
  concat(heads) Wo`.
- `E`, experts: `s = sigmoid(u Wr)` over all `n_routed_experts`; the
  `num_experts_per_tok` largest of `s + e_score_correction_bias` are chosen
  (`n_group` 1, `topk_group` 1: no grouping); `w_e = routed_scaling_factor *
  s_e / (sum of the chosen s + 1e-20)` (the bias chooses and does not
  weigh); the result is the sum over the chosen e THAT ARE HELD HERE
  (`num_experts_held` consecutive experts, the `expert_share`-th group) of
  `w_e relu(u Wup_e)^2 Wdown_e` (ungated; the leaf `up` holds `Wup_e^T`,
  [width, hidden], as a Linear's weight lies), plus one shared expert
  `relu(u Wsu)^2 Wsd` of width `moe_shared_expert_intermediate_size` for
  every token, whole. The weights stay normalised over all chosen, held or
  not; what the absent experts would add is left out.
- Model: embedding [vocab_size held, hidden], the blocks, final RMSNorm,
  untied head [hidden, vocab_size held]; the loss is the mean over all rows x
  T positions of the cross entropy against the next id.

Departures from the published description, each `assumed` in the
configuration's file: the correction bias is set once, at initialisation,
by the balancing rule on a seeded probe (`_balance`; zero without
`router_balance`) and is not updated (a leaf of `batch_stats`: no optimizer
touches it), no auxiliary loss; `dt` is not clamped (`time_step_limit` is
(0, inf)); the initialisation below.

The recurrence runs a row at a time, `reference_block_rows` positions to a
rematerialised block (the backward keeps one state a block and the states of
one block), attention as many query rows at a time and the head and loss as
many positions, and every block and every expert is rematerialised: at the
cell's size (two sequences of 8,192) the float32 step then fits a 16 GB
chip beside its own parameters and gradient and the harness's copy of the
first parameters; AdamW's moments wait on the host between steps
(`init_opt`).

`quant` is for the control only (see resnet18_ref.py): every matrix product
reads its operands through fp8 or bf16 and back: the projections, the
experts, attention's two products and, in the recurrence, `x`, `B`, `C` and
the state `h` where `h_t C_t` reads it (the router's product and every
`exp` stay float32, as in the program).
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
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


# ------------------------------------------------------------------ sizes --

def _sizes(cfg):
    held = int(cfg["num_experts_held"])
    share = int(str(cfg.get("expert_share", "0 of 1")).split(" of ")[0])
    layers = int(cfg["num_hidden_layers"])
    return dict(
        d=int(cfg["hidden_size"]), layers=layers,
        pattern=str(cfg["hybrid_override_pattern"])[:layers],
        vocab=int(cfg["vocab_size"]), eps=float(cfg["layer_norm_epsilon"]),
        rows=int(cfg["reference_block_rows"]),
        # Mamba-2
        mh=int(cfg["mamba_num_heads"]), mp=int(cfg["mamba_head_dim"]),
        n=int(cfg["ssm_state_size"]), g=int(cfg["n_groups"]),
        taps=int(cfg["conv_kernel"]),
        # attention
        heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        # experts
        experts=int(cfg["n_routed_experts"]),
        k=int(cfg["num_experts_per_tok"]), held=held, first=share * held,
        f=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["moe_shared_expert_intermediate_size"]),
        scaling=float(cfg["routed_scaling_factor"]))


# ------------------------------------------------------------------- init --

def init(key, cfg):
    """(params, batch_stats): every matrix N(0, 0.02), every norm 1; the
    embedding's rows N(0, `embedding_std`); Mamba's `A_log = log(1 .. H)`, `D
    = 1`, `dt_bias` the inverse softplus of a log-uniform draw in
    [`time_step_min`, `time_step_max`] floored at `time_step_floor`, the
    convolution's kernel and bias U(-1 / sqrt(taps), 1 / sqrt(taps)) (torch's
    `Conv1d` default, which the family's initialiser leaves), `out_proj`
    divided by sqrt(published blocks) (`rescale_prenorm_residual`).
    `batch_stats` holds each expert block's correction bias: zero, or where
    the configuration has `router_balance` what `_balance` gives."""
    z = _sizes(cfg)
    keys = iter(jax.random.split(key, 2 + 8 * z["layers"]))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * STD

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    inner, gn = z["mh"] * z["mp"], z["g"] * z["n"]
    bound = 1.0 / math.sqrt(z["taps"])
    lo, hi = float(cfg["time_step_min"]), float(cfg["time_step_max"])
    published = int(cfg.get("num_hidden_layers_published", z["layers"]))
    rescale = (1.0 / math.sqrt(published)
               if cfg.get("rescale_prenorm_residual") else 1.0)

    def mamba():
        dt = jnp.exp(jax.random.uniform(next(keys), (z["mh"],), jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, float(cfg["time_step_floor"]))
        return {
            "in_proj": {"kernel": normal(z["d"], 2 * inner + 2 * gn
                                         + z["mh"])},
            "conv_kernel": jax.random.uniform(
                next(keys), (z["taps"], inner + 2 * gn), jnp.float32,
                -bound, bound),
            "conv_bias": jax.random.uniform(
                next(keys), (inner + 2 * gn,), jnp.float32, -bound, bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jnp.arange(1, z["mh"] + 1, dtype=jnp.float32)),
            "D": jnp.ones((z["mh"],), jnp.float32),
            "norm_scale": jnp.ones((inner,), jnp.float32),
            "out_proj": {"kernel": normal(inner, z["d"]) * rescale}}

    def attention():
        return {"q_proj": {"kernel": normal(z["d"], z["heads"] * z["hd"])},
                "k_proj": {"kernel": normal(z["d"], z["kv"] * z["hd"])},
                "v_proj": {"kernel": normal(z["d"], z["kv"] * z["hd"])},
                "o_proj": {"kernel": normal(z["heads"] * z["hd"], z["d"])}}

    def moe():
        return {"router": normal(z["d"], z["experts"]),
                "up": normal(z["held"], z["f"], z["d"]),         # [out, in]
                "down": normal(z["held"], z["f"], z["d"]),
                "shared_up": normal(z["d"], z["shared"]),
                "shared_down": normal(z["shared"], z["d"])}

    embedding = normal(z["vocab"], z["d"]) * (
        float(cfg.get("embedding_std", STD)) / STD)
    params = {"embed": {"embedding": embedding}, "norm": ones(z["d"]),
              "head": normal(z["d"], z["vocab"])}
    stats = {}
    for i, letter in enumerate(z["pattern"]):
        kind = KINDS[letter]
        params[f"layer_{i}"] = {
            "norm": ones(z["d"]),
            "mixer": {"mamba": mamba, "attention": attention,
                      "moe": moe}[kind]()}
        if kind == "moe":
            stats[f"layer_{i}"] = {"mixer": {
                "e_score_correction_bias": jnp.zeros((z["experts"],),
                                                     jnp.float32)}}
    if cfg.get("router_balance"):
        stats = _balance(params, stats, key, cfg)
    return params, stats


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


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def _mamba_row(u, p, z, quant):
    """One row `u` [T, hidden] through the mixer."""
    t = u.shape[0]
    heads, hp, n, g = z["mh"], z["mp"], z["n"], z["g"]
    inner, gn = heads * hp, g * n
    proj = _mm(u, p["in_proj"]["kernel"], quant)
    gate, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * gn], axis=-1)
    # the causal depthwise convolution as its shifted sum, then SiLU
    padded = jnp.pad(xbc, ((z["taps"] - 1, 0), (0, 0)))
    xbc = p["conv_bias"] + sum(
        p["conv_kernel"][k] * padded[k:k + t] for k in range(z["taps"]))
    xbc = jax.nn.silu(xbc)
    x, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    # [T, G, H / G, P]: a group's heads lie together and read its B and C
    x = _q(x, quant).reshape(t, g, heads // g, hp)
    bm = _q(bm, quant).reshape(t, g, n)
    cm = _q(cm, quant).reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # [T, H]
    a = -jnp.exp(p["A_log"])

    def position(h, at):
        x_t, b_t, c_t, dt_t = at
        keep = jnp.exp(dt_t * a).reshape(g, -1, 1, 1)
        write = (dt_t.reshape(g, -1, 1) * x_t)[..., None]     # dt_t x_t
        h = keep * h + write * b_t[:, None, None, :]          # ... B_t^T
        return h, jnp.einsum("grpn,gn->grp", _q(h, quant), c_t)

    @jax.checkpoint
    def block(h, rows):
        return lax.scan(position, h, rows)

    rows = z["rows"] if t % z["rows"] == 0 else t
    by_block = tuple(v.reshape(t // rows, rows, *v.shape[1:])
                     for v in (x, bm, cm, dt))
    _, y = lax.scan(block, jnp.zeros((g, heads // g, hp, n), jnp.float32),
                    by_block)
    y = y.reshape(t, heads, hp) + p["D"][:, None] * x.reshape(t, heads, hp)
    # the gate first, then RMSNorm over each group's channels
    y = (y.reshape(t, inner) * jax.nn.silu(gate)).reshape(t, g, -1)
    y = _rms(y, 1.0, z["eps"]).reshape(t, inner) * p["norm_scale"]
    carry = jnp.exp(jnp.sum(dt, axis=0) * a)      # what outlives a whole row
    return (_mm(y, p["out_proj"]["kernel"], quant), jnp.mean(dt),
            jnp.min(carry))


def _mamba(u, p, z, quant):
    """The mixer over `u` [rows, T, hidden], a row at a time, each made
    again in the backward pass: one row's float32 temporaries at a time."""
    y, dt_mean, carry = lax.map(jax.checkpoint(functools.partial(
        _mamba_row, p=p, z=z, quant=quant)), u)
    return y, lax.stop_gradient((jnp.mean(dt_mean), jnp.min(carry)))


def _attention(u, p, z, quant):
    b, t, _ = u.shape
    group = z["heads"] // z["kv"]
    q = _mm(u, p["q_proj"]["kernel"], quant).reshape(b, t, z["kv"], group,
                                                     z["hd"])
    k = _mm(u, p["k_proj"]["kernel"], quant).reshape(b, t, z["kv"], z["hd"])
    v = _mm(u, p["v_proj"]["kernel"], quant).reshape(b, t, z["kv"], z["hd"])
    rows = z["rows"] if t % z["rows"] == 0 else t
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = jnp.einsum("brgjd,bkgd->bgjrk", _q(qb, quant),
                       _q(k, quant)) / math.sqrt(z["hd"])
        at = i * rows + jnp.arange(rows)
        seen = cols[None, :] <= at[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgjrk,bkgd->brgjd", _q(a, quant), _q(v, quant))

    out = lax.map(block, jnp.arange(t // rows))      # [blocks, b, rows, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, z["heads"] * z["hd"])
    return _mm(out, p["o_proj"]["kernel"], quant)


def route(u, router, bias, z):
    """(chosen [T, k], weights [T, k]) of tokens u [T, d]: the rule of the
    module's docstring."""
    scores = jax.nn.sigmoid(jnp.matmul(u, router))
    _, chosen = lax.top_k(scores + bias, z["k"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, z["scaling"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def _relu2_mlp(u, up, down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(u, up, quant))), down, quant)


def _routed(u, p, bias, z, quant):
    """The held experts' part of the layer's result for tokens u [T, d]."""
    chosen, weights = route(u, p["router"], bias, z)

    @jax.checkpoint
    def one(index, up, down):
        mine = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
        # a routed expert's `up` lies [width, hidden], as a Linear's weight
        return mine[:, None] * _relu2_mlp(u, up.T, down, quant)

    # the sum is carried, the rematerialised part is not handed it: the
    # backward pass then keeps no step's sum for the next
    held = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(lambda y, e: (y + one(*e), None), jnp.zeros_like(u),
                    (held, p["up"], p["down"]))
    # (token, expert) pairs of each held expert: printed, not compared
    pairs = jnp.sum(chosen[:, :, None] == held[None, None, :], axis=(0, 1))
    return y, lax.stop_gradient(pairs)


def _moe(u, p, bias, z, quant):
    """Routed part held here + the shared expert, whole."""
    y, pairs = _routed(u, p, bias, z, quant)
    shared = jax.checkpoint(functools.partial(_relu2_mlp, quant=quant))(
        u, p["shared_up"], p["shared_down"])
    return y + shared, pairs


def _block(x, p, bias, z, kind, quant):
    u = _rms(x, p["norm"]["scale"], z["eps"])
    said = None
    if kind == "mamba":
        y, said = _mamba(u, p["mixer"], z, quant)
    elif kind == "attention":
        y = _attention(u, p["mixer"], z, quant)
    else:
        b, t, d = u.shape
        y, said = _moe(u.reshape(b * t, d), p["mixer"], bias, z, quant)
        y = y.reshape(b, t, d)
    return x + y, said


def loss_fn(params, stats, tokens, targets, cfg, quant=None):
    z = _sizes(cfg)
    x = params["embed"]["embedding"][tokens]
    pairs, ssm = [], []
    for i, letter in enumerate(z["pattern"]):
        kind = KINDS[letter]
        bias = (stats[f"layer_{i}"]["mixer"]["e_score_correction_bias"]
                if kind == "moe" else None)
        block = jax.checkpoint(functools.partial(
            _block, z=z, kind=kind, quant=quant))
        x, said = block(x, params[f"layer_{i}"], bias)
        if kind == "moe":
            pairs.append(said)
        elif kind == "mamba":
            ssm.append(jnp.stack(said))
    x = _rms(x, params["norm"]["scale"], z["eps"])
    n = x.shape[0] * x.shape[1]
    rows = z["rows"] if n % z["rows"] == 0 else n

    @jax.checkpoint
    def chunk(total, xs):
        h, y = xs
        logits = _mm(h, params["head"], quant)
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        return total + jnp.sum(nll), None

    total, _ = lax.scan(chunk, jnp.zeros((), jnp.float32),
                        (x.reshape(n // rows, rows, -1),
                         targets.reshape(n // rows, rows)))
    return total / n, (jnp.stack(pairs) if pairs else jnp.zeros((0,)),
                       jnp.stack(ssm) if ssm else jnp.zeros((0, 2)))


# ---------------------------------------------------------------- balance --

def _balance(params, stats, key, cfg):
    """The correction biases a balanced router would hold, in place of
    zeros: the family's balancing rule (arXiv:2408.15664: `bias_e += rate *
    sign(mean load - load_e)`; the rule's rate is in no config key) run at
    initialisation on one seeded probe row, block by block, until the
    experts' loads on the probe are even. `router_balance` gives the probe's
    length, the rule's turns, its first rate and the factor a turn shrinks it
    by. Why: with zeros, seeded weights favour some experts for EVERY token
    (a `relu^2` expert's output has a mean all tokens share, so deeper
    routers see a common offset), the held experts' pairs a step swing by a
    quarter between seeds and the step's time with them; a trained router's
    bias exists to take exactly that out. The biases are drawn from the seed
    with the weights, and neither side updates them afterwards."""
    z = _sizes(cfg)
    spec = cfg["router_balance"]
    ids = jax.random.randint(jax.random.fold_in(key, 0xBA1A),
                             (1, int(spec["probe_tokens"])), 0, z["vocab"])
    rate, shrink = float(spec["rate"]), float(spec["shrink"])
    stats = dict(stats)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids]
        for i, letter in enumerate(z["pattern"]):
            kind, p = KINDS[letter], params[f"layer_{i}"]
            bias = None
            if kind == "moe":
                u = _rms(x, p["norm"]["scale"], z["eps"]).reshape(
                    -1, z["d"])
                scores = jax.nn.sigmoid(jnp.matmul(u, p["mixer"]["router"]))
                even = u.shape[0] * z["k"] / z["experts"]

                def turn(t, bias):
                    _, chosen = lax.top_k(scores + bias, z["k"])
                    load = jnp.sum(jax.nn.one_hot(
                        chosen, z["experts"], dtype=jnp.float32), axis=(0, 1))
                    return bias + rate * shrink ** t * jnp.sign(even - load)
                bias = lax.fori_loop(0, int(spec["turns"]), turn, jnp.zeros(
                    (z["experts"],), jnp.float32))
                stats[f"layer_{i}"] = {"mixer": {
                    "e_score_correction_bias": bias}}
            x, _ = _block(x, p, bias, z, kind, None)
    return stats


# ------------------------------------------------------------------- step --

def init_opt(params):
    """AdamW's moments, kept on the HOST between steps (numpy), as
    refs/mellum2_12b_ep4.py keeps them and for its reason."""
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

    def grads(params, stats, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_fn, has_aux=True)(
                params, stats, tokens, targets, cfg, quant)

    # torch.optim.AdamW: p -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * p);
    # leaves of one dimension (every norm, the convolution's bias, A_log, D,
    # dt_bias) are not decayed
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
    moments come and go as host arrays (`init_opt`); `stats` (the correction
    biases) come back as they went."""
    grads, update = _programs(json.dumps(cfg, sort_keys=True), quant)
    (loss, (pairs, ssm)), g = grads(params, stats, tokens, targets)
    ssm = np.asarray(ssm)
    said = {"quant": quant,
            "pairs_by_layer_and_held_expert": np.asarray(pairs).tolist(),
            "ssm_dt_mean_by_block": ssm[:, 0].tolist(),
            "ssm_row_carry_min_by_block": ssm[:, 1].tolist()}
    print("bench moe_route_reference " + json.dumps(said), flush=True)
    # the moments visit the chip for the update, which writes them in place
    new_params, new_opt = update(params, g, jax.device_put(opt),
                                 jnp.asarray(lr, jnp.float32))
    return loss, g, new_params, stats, jax.device_get(new_opt)
