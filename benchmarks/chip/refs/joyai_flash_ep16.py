"""Plain float32 reference of the `joyai_flash_ep16` configuration: one
chip's share of JoyAI-LLM-Flash (`model_type` `joyai_llm_flash`, the
DeepSeek-V3 family's layers), as the configuration's file states it, trained
on the next id and, by its multi-token-prediction module, on the one after,
by AdamW.

Straight `jax.numpy` under `jax.default_matmul_precision("highest")`; it
imports nothing of `tpudist` and is handed nothing the program made. Every
size is read from the configuration (the tiny twin of the CPU tests runs the
same code). Python loops over the layers; a scan over the held experts.

A block, for `x` [rows, T, hidden] (RMSNorm in float32, eps `rms_norm_eps`,
a weight a feature; no bias anywhere):

    h = x + MLA(RMSNorm(x)),   y = h + FFN(RMSNorm(h))

- MLA (multi-head latent attention, the training form: a head of its own
  keys). `c_q = RMSNorm(u W_qa)` [`q_lora_rank`], `q = c_q W_qb` -> heads of
  `[q_nope qk_nope_head_dim | q_rope qk_rope_head_dim]`. `[c_kv kv_lora_rank |
  k_r qk_rope_head_dim] = u W_kva`, `c_kv = RMSNorm(c_kv)`, `c_kv W_kvb` ->
  heads of `[k_nope | v v_head_dim]`. `q_rope` (every head) and `k_r` (ONE
  head, shared by all) are rotated, neighbouring pairs (`rope_interleave`):
  `(x_2i, x_2i+1)` turns by `pos * rope_theta^(-2i / qk_rope_head_dim)`, no
  scaling (`rope_scaling` null), and stays where it lay. `k = [k_nope | k_r]`,
  scores `q k^T / sqrt(qk_nope_head_dim + qk_rope_head_dim)` (no `mscale`:
  `assumed`); query i sees key j where `j <= i`; softmax; `o = P v`; `concat(
  heads) W_o`.
- FFN of the first `first_k_dense_replace` layers: a dense SwiGLU of
  `intermediate_size`, `(silu(u W_g) * (u W_u)) W_d`.
- FFN of every other layer, the experts: `s = sigmoid(u W_r)` over all
  `n_routed_experts`; the `num_experts_per_tok` largest of `s +
  e_score_correction_bias` are chosen (`n_group` 1, `topk_group` 1: no
  grouping); `w_e = routed_scaling_factor * s_e / (sum of the chosen s +
  1e-20)` (`norm_topk_prob`; the bias chooses and does not weigh); the
  result is the sum over the chosen e THAT ARE HELD HERE (`num_experts_held`
  consecutive experts, the `expert_share`-th group) of `w_e (silu(u Wg_e) *
  (u Wu_e)) Wd_e` at `moe_intermediate_size`, plus the shared expert, the
  same body at `n_shared_experts * moe_intermediate_size`, for every token,
  unweighted and whole. The weights stay normalised over all chosen, held or
  not; what the absent experts would add is left out.
- Model: embedding [vocab_size held, hidden], `num_hidden_layers` blocks,
  final RMSNorm, untied head [hidden, vocab_size held]; `L_main` is the mean
  over all rows x T positions of the cross entropy against the next id `y_i
  = x_(i+1)`.
- The multi-token-prediction module (`num_nextn_predict_layers` 1; its
  structure is DeepSeek-V3's, arXiv:2412.19437 section 2.2: `assumed`). With
  `h_L` the last kept block's result BEFORE the final norm: `m = [RMSNorm_e(
  Emb(y)) ; RMSNorm_h(h_L)] W_eh` (2 hidden -> hidden), `h' = Block_mtp(m)`
  (an MLA + expert block of its own leaves, positions 0 .. T - 1),
  `logits' = RMSNorm_s(h') W_head` with the model's own embedding and head.
  `L_mtp` is the mean over rows and positions `i <= T - 2` of `CE(logits'_i,
  y_(i+1))` (the last position has no second-next id in the row). The loss
  is `L_main + mtp_loss_weight * L_mtp`.

Departures from the published description, each `assumed` in the
configuration's file: the module reads the kept depth's `h_L` (layer 5's,
the cut in depth); the correction bias is zeros and is not updated (a leaf
of `batch_stats`: no optimizer touches it), no auxiliary loss; the
initialisation below.

Attention runs `reference_block_rows` query rows at a time and the head and
loss as many positions, each block made again in the backward pass, and
every block (a row of it at a time) and every expert is rematerialised: at
the cell's size (two sequences of 8,192) the float32 step then fits a 16 GB
chip beside its own parameters and gradient and the harness's copy of the
first parameters (whole blocks over both rows needed 12.44 GiB compiled for
a described v5e, 14.97 with that copy, of 15.75).
AdamW's moments wait on the host between steps (`init_opt`) and visit the
chip a top-level entry of the tree at a time: whole, they and the new
parameters would be 16.3 GB beside the rest.

`quant` is for the control only (see resnet18_ref.py): every matrix product
reads its operands through fp8 or bf16 and back (the router's product and
every `exp` stay float32, as in the program). `wrong` is for the tests only
(`tests/test_mla.py`, `tests/test_mtp.py`): a named fault of the layer's or
the loss's mathematics (`WRONG`), which the comparison that holds the program
to this file has to refuse; None everywhere else.
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
# what `wrong` may name: latent attention's (`mla`) and the second loss's
# (`losses`)
WRONG = ("scale_nope", "rotate_halves", "key_a_head", "no_latent_norm",
         "shift_one", "last_weighted", "embed_x")


# ------------------------------------------------------------------ sizes --

def _sizes(cfg):
    held = int(cfg["num_experts_held"])
    share = int(str(cfg.get("expert_share", "0 of 1")).split(" of ")[0])
    return dict(
        d=int(cfg["hidden_size"]), layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        dense_f=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
        rows=int(cfg["reference_block_rows"]),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]),
        experts=int(cfg["n_routed_experts"]),
        k=int(cfg["num_experts_per_tok"]), held=held, first=share * held,
        f=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]) * int(
            cfg["moe_intermediate_size"]),
        scaling=float(cfg["routed_scaling_factor"]),
        mtp=int(cfg["num_nextn_predict_layers"]),
        mtp_weight=float(cfg["mtp_loss_weight"]))


# ------------------------------------------------------------------- init --

def init(key, cfg):
    """(params, batch_stats): every matrix N(0, 0.02), every norm 1, the
    embedding's rows N(0, `embedding_std`); `batch_stats` holds each expert
    layer's correction bias, zeros."""
    z = _sizes(cfg)
    if z["mtp"] != 1:
        raise ValueError("one multi-token-prediction module")
    keys = iter(jax.random.split(key, 4 + 16 * (z["layers"] + 1)))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * STD

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    d, h = z["d"], z["heads"]

    def attention():
        return {"q_a_proj": kernel(d, z["q_rank"]),
                "q_a_norm": ones(z["q_rank"]),
                "q_b_proj": kernel(z["q_rank"], h * (z["nope"] + z["rope"])),
                "kv_a_proj": kernel(d, z["kv_rank"] + z["rope"]),
                "kv_a_norm": ones(z["kv_rank"]),
                "kv_b_proj": kernel(z["kv_rank"], h * (z["nope"] + z["v"])),
                "o_proj": kernel(h * z["v"], d)}

    def block(dense: bool):
        out = {"input_norm": ones(d), "self_attention": attention(),
               "post_norm": ones(d)}
        if dense:
            out["mlp"] = {"gate_proj": kernel(d, z["dense_f"]),
                          "up_proj": kernel(d, z["dense_f"]),
                          "down_proj": kernel(z["dense_f"], d)}
        else:
            out["moe"] = {"router": normal(d, z["experts"]),
                          "gate": normal(z["held"], d, z["f"]),
                          "up": normal(z["held"], d, z["f"]),
                          "down": normal(z["held"], z["f"], d),
                          "shared_gate": normal(d, z["shared"]),
                          "shared_up": normal(d, z["shared"]),
                          "shared_down": normal(z["shared"], d)}
        return out

    def bias():
        return {"moe": {"e_score_correction_bias": jnp.zeros(
            (z["experts"],), jnp.float32)}}

    embedding = normal(z["vocab"], d) * (
        float(cfg.get("embedding_std", STD)) / STD)
    params = {"embed": {"embedding": embedding}, "norm": ones(d),
              "head": normal(d, z["vocab"])}
    stats = {}
    for i in range(z["layers"]):
        params[f"layer_{i}"] = block(i < z["dense"])
        if i >= z["dense"]:
            stats[f"layer_{i}"] = bias()
    params["mtp"] = {"enorm": ones(d), "hnorm": ones(d),
                     "eh_proj": kernel(2 * d, d), "block": block(False),
                     "norm": ones(d)}
    stats["mtp"] = {"block": bias()}
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


def _rotate(x, z, wrong=None):
    """Neighbouring pairs of `x` [rows, T, heads, rope] turned by the
    position's angle, each pair where it lay."""
    t, r = x.shape[1], z["rope"]
    freq = z["theta"] ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(t, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    if wrong == "rotate_halves":          # (x_i, x_(i + r/2)) for the pairs
        a, b = x[..., :r // 2], x[..., r // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def mla(u, p, z, quant=None, wrong=None):
    """`u` [rows, T, hidden] through latent attention (the module's
    docstring)."""
    b, t, _ = u.shape
    h, dn, dr, dv = z["heads"], z["nope"], z["rope"], z["v"]

    def latent_norm(x, scale):
        return x if wrong == "no_latent_norm" else _rms(x, scale, z["eps"])
    c_q = latent_norm(_mm(u, p["q_a_proj"]["kernel"], quant),
                      p["q_a_norm"]["scale"])
    q = _mm(c_q, p["q_b_proj"]["kernel"], quant).reshape(b, t, h, dn + dr)
    c_kv = _mm(u, p["kv_a_proj"]["kernel"], quant)
    c_kv, k_r = c_kv[..., :z["kv_rank"]], c_kv[..., z["kv_rank"]:]
    kv = _mm(latent_norm(c_kv, p["kv_a_norm"]["scale"]),
             p["kv_b_proj"]["kernel"], quant).reshape(b, t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], z, wrong)],
                        axis=-1)
    k_r = _rotate(k_r[:, :, None, :], z, wrong)      # one head for all
    k_r = jnp.broadcast_to(k_r, (b, t, h, dr))
    if wrong == "key_a_head":             # head j's own: the columns rolled
        k_r = jnp.stack([jnp.roll(k_r[:, :, j], j, axis=-1)
                         for j in range(h)], axis=2)
    k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn if wrong == "scale_nope" else dn + dr)
    rows = z["rows"] if t % z["rows"] == 0 else t
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = jnp.einsum("brhd,bkhd->bhrk", _q(qb, quant),
                       _q(k, quant)) * scale
        at = i * rows + jnp.arange(rows)
        seen = cols[None, :] <= at[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhrk,bkhd->brhd", _q(a, quant), _q(v, quant))

    out = lax.map(block, jnp.arange(t // rows))      # [blocks, b, rows, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, h * dv)
    return _mm(out, p["o_proj"]["kernel"], quant)


def route(u, router, bias, z):
    """(chosen [T, k], weights [T, k]) of tokens u [T, d]: the rule of the
    module's docstring."""
    scores = jax.nn.sigmoid(jnp.matmul(u, router))
    _, chosen = lax.top_k(scores + bias, z["k"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, z["scaling"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def _swiglu(u, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(u, gate, quant)) * _mm(u, up, quant), down,
               quant)


def experts(u, p, bias, z, quant=None, shared=True):
    """Tokens `u` [T, d] through the expert layer: the held experts' part
    and (`shared`) the shared expert, whole; and the held experts' pairs."""
    chosen, weights = route(u, p["router"], bias, z)

    @jax.checkpoint
    def one(index, gate, up, down):
        mine = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
        return mine[:, None] * _swiglu(u, gate, up, down, quant)

    # the sum is carried, the rematerialised part is not handed it: the
    # backward pass then keeps no step's sum for the next
    held = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(lambda y, e: (y + one(*e), None), jnp.zeros_like(u),
                    (held, p["gate"], p["up"], p["down"]))
    if shared:
        y = y + jax.checkpoint(functools.partial(_swiglu, quant=quant))(
            u, p["shared_gate"], p["shared_up"], p["shared_down"])
    # (token, expert) pairs of each held expert: printed, not compared
    pairs = jnp.sum(chosen[:, :, None] == held[None, None, :], axis=(0, 1))
    return y, lax.stop_gradient(pairs)


def _block(x, p, bias, z, quant, wrong=None):
    h = x + mla(_rms(x, p["input_norm"]["scale"], z["eps"]),
                p["self_attention"], z, quant, wrong)
    u = _rms(h, p["post_norm"]["scale"], z["eps"])
    if "mlp" in p:
        m = p["mlp"]
        return h + _swiglu(u, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                           m["down_proj"]["kernel"], quant), jnp.zeros(
                               (z["held"],), jnp.int32)
    b, t, d = u.shape
    y, pairs = experts(u.reshape(b * t, d), p["moe"], bias, z, quant)
    return h + y.reshape(b, t, d), pairs


def _head_loss(x, head, targets, weights, z, quant):
    """Sum over positions of `weights * CE(x head, targets)`, `rows`
    positions at a time."""
    n = x.shape[0] * x.shape[1]
    rows = z["rows"] if n % z["rows"] == 0 else n

    @jax.checkpoint
    def chunk(total, xs):
        h, y, w = xs
        logits = _mm(h, head, quant)
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        return total + jnp.sum(nll * w), None

    total, _ = lax.scan(chunk, jnp.zeros((), jnp.float32),
                        (x.reshape(n // rows, rows, -1),
                         targets.reshape(n // rows, rows),
                         weights.reshape(n // rows, rows)))
    return total


def losses(params, stats, tokens, targets, cfg, quant=None, wrong=None):
    """(L_main, L_mtp, pairs by block and held expert)."""
    z = _sizes(cfg)
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown fault {wrong!r}")
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    pairs = []
    one_row = jax.checkpoint(functools.partial(_block, z=z, quant=quant,
                                               wrong=wrong))

    def block(x, p, bias):
        # a row at a time, each made again in the backward pass: one row's
        # float32 temporaries at a time (attention and the experts read a
        # row, or a token, alone)
        y, said = lax.map(lambda row: one_row(row[None], p, bias), x)
        return y[:, 0], jnp.sum(said, axis=0)
    for i in range(z["layers"]):
        bias = (stats[f"layer_{i}"]["moe"]["e_score_correction_bias"]
                if i >= z["dense"] else None)
        x, said = block(x, params[f"layer_{i}"], bias)
        pairs.append(said)
    rows, t = tokens.shape
    main = _head_loss(_rms(x, params["norm"]["scale"], z["eps"]),
                      params["head"], targets, jnp.ones((rows, t)), z,
                      quant) / (rows * t)
    # the module: the NEXT id's embedding beside the trunk's last hidden
    # state (before the final norm), one more block, the same head
    m = params["mtp"]
    merged = jnp.concatenate(
        [_rms(emb[tokens if wrong == "embed_x" else targets],
              m["enorm"]["scale"], z["eps"]),
         _rms(x, m["hnorm"]["scale"], z["eps"])], axis=-1)
    x, said = block(_mm(merged, m["eh_proj"]["kernel"], quant), m["block"],
                    stats["mtp"]["block"]["moe"]["e_score_correction_bias"])
    pairs.append(said)
    # position i predicts y_(i + 1); the last has none (an id of 0 there,
    # weight 0)
    ahead = jnp.pad(targets[:, 1:], ((0, 0), (0, 1)))
    seen = jnp.broadcast_to(jnp.arange(t) < t - 1, (rows, t))
    count = rows * (t - 1)
    if wrong == "shift_one":
        ahead = targets
    elif wrong == "last_weighted":
        seen, count = jnp.ones_like(seen), rows * t
    mtp = _head_loss(_rms(x, m["norm"]["scale"], z["eps"]), params["head"],
                     ahead, seen.astype(jnp.float32), z, quant) / max(
                         count, 1)
    return main, mtp, jnp.stack(pairs)


def loss_fn(params, stats, tokens, targets, cfg, quant=None, wrong=None):
    main, mtp, pairs = losses(params, stats, tokens, targets, cfg, quant,
                              wrong)
    return main + _sizes(cfg)["mtp_weight"] * mtp, (main, mtp, pairs)


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
    # leaves of one dimension (every norm) are not decayed. One top-level
    # entry of the tree a call.
    def update(params, g, mu, nu, t, lr):
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)

        def new(p, m, v):
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if p.ndim >= min_ndim:
                u = u + wd * p
            return p - lr * u
        return jax.tree_util.tree_map(new, params, mu, nu), mu, nu

    return jax.jit(grads), jax.jit(update, donate_argnums=(2, 3))


def step(params, stats, opt, tokens, targets, cfg, lr, quant=None):
    """One optimizer step. Returns (loss, grads, params, stats, opt); the
    moments come and go as host arrays (`init_opt`), an entry of the tree at
    a time; `stats` (the correction biases) come back as they went."""
    grads, update = _programs(json.dumps(cfg, sort_keys=True), quant)
    (loss, (main, mtp, pairs)), g = grads(params, stats, tokens, targets)
    said = {"quant": quant, "lm_loss_main": float(main),
            "mtp_loss": float(mtp),
            "pairs_by_block_and_held_expert": np.asarray(pairs).tolist()}
    print("bench moe_route_reference " + json.dumps(said), flush=True)
    t = jnp.asarray(opt["count"] + 1.0, jnp.float32)
    new_params, mu, nu = {}, {}, {}
    for name in params:
        new_params[name], m, v = update(
            params[name], g[name], jax.device_put(opt["mu"][name]),
            jax.device_put(opt["nu"][name]), t, jnp.asarray(lr, jnp.float32))
        mu[name], nu[name] = jax.device_get((m, v))
    return loss, g, new_params, stats, {"mu": mu, "nu": nu,
                                        "count": np.asarray(t)}
