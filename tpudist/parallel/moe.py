"""Mixture-of-Experts with expert parallelism over a mesh axis.

No reference equivalent (SURVEY.md §2.2: EP/MoE "No") — this fills the
``expert`` mesh axis the TPU-native way. Design (Switch-Transformer-style
top-1 routing, cf. Fedus et al., and the Mesh-TF capacity formulation):

- tokens are sharded over the ``expert`` axis (each device holds a token
  shard AND one expert's FFN weights — expert e lives on device e);
- the router is replicated; each device computes softmax gates for its local
  tokens and packs them into a fixed-capacity dispatch buffer [E, C, d]
  (static shapes — XLA requirement; overflow tokens are dropped, the standard
  capacity-factor tradeoff);
- ONE ``lax.all_to_all`` ships buffer row e to device e (the canonical MoE
  collective, riding ICI), the local expert FFN runs on everything received,
  and a second all_to_all ships results back;
- combine multiplies by the gate prob; dropped tokens contribute zero (they
  pass through the residual connection in a transformer block);
- the Switch load-balancing auxiliary loss (E * Σ_e f_e·p_e) comes back with
  the output; add it to the task loss scaled by e.g. 1e-2.

``moe_spmd`` is the inside-shard_map form; ``moe_dense`` is the
single-device reference (same routing math, no capacity drop when C covers
all tokens) used by tests and small-scale runs.

``moe_topk_held`` (last section) is the other layer: softmax over all
experts, the k largest renormalised, no capacity and no dropped pair, for a
holder of ``n`` consecutive experts of ``E`` that computes its own experts'
part of the result (grouped products over the experts held:
``ops/pallas/grouped_matmul.py``). On one chip it
runs without an exchange; the exchange across the chips that share a layer
is not written yet.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.obs import scopes


def init_moe_params(rng: jax.Array, d_model: int, d_hidden: int,
                    num_experts: int) -> dict:
    """Router [d, E] replicated; expert FFN weights stacked on a leading [E]
    dim (shard it over the ``expert`` axis)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale1 = 1.0 / jnp.sqrt(d_model)
    scale2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": jax.random.normal(k1, (d_model, num_experts)) * scale1,
        "w1": jax.random.normal(k2, (num_experts, d_model, d_hidden)) * scale1,
        "b1": jnp.zeros((num_experts, d_hidden)),
        "w2": jax.random.normal(k3, (num_experts, d_hidden, d_model)) * scale2,
        "b2": jnp.zeros((num_experts, d_model)),
    }


def _route(x: jax.Array, router: jax.Array, capacity: int):
    """Top-1 routing with capacity: returns (expert_idx, slot, keep, gate,
    aux_loss) for tokens x [T, d]."""
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)                  # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert_idx, probs.shape[-1], dtype=jnp.int32)
    # Slot of each token within its expert's capacity buffer (arrival order).
    slot = (jnp.cumsum(onehot, axis=0) - 1)                  # [T, E]
    slot = jnp.sum(slot * onehot, axis=-1)                   # [T]
    keep = slot < capacity
    # Switch aux-loss ingredients: f_e = fraction of tokens routed to e,
    # p_e = mean router prob of e. Returned separately so the SPMD caller can
    # average each over the mesh BEFORE taking the product (mean-of-products
    # over shards is not the global loss).
    f = jnp.mean(onehot.astype(jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return expert_idx, slot, keep, gate, (f, p)


def _ffn(x: jax.Array, w1, b1, w2, b2) -> jax.Array:
    h = jax.nn.relu(x @ w1 + b1)
    return h @ w2 + b2


def moe_spmd(params: dict, x: jax.Array, axis_name: str = "expert",
             capacity_factor: float = 2.0, aux_axes=None):
    """Expert-parallel MoE INSIDE ``shard_map``.

    params: ``init_moe_params`` tree with expert leaves sharded to leading
    local dim 1; router replicated. x: [T_local, d] local token shard.
    Returns (y [T_local, d], aux_loss scalar — already pmean'd over
    ``aux_axes``, default the expert axis). Under dp×ep composition pass
    ``aux_axes=('data', 'expert')`` so the load-balance statistics f/p
    average over the WHOLE global batch (matching ``moe_dense`` on it), not
    one data slice."""
    e = lax.psum(1, axis_name)
    t_local, d = x.shape
    capacity = max(1, int(capacity_factor * t_local / e))
    expert_idx, slot, keep, gate, (f, p) = _route(x, params["router"], capacity)
    ax = axis_name if aux_axes is None else aux_axes
    aux = e * jnp.sum(lax.pmean(f, ax) * lax.pmean(p, ax))

    # Pack local tokens into the dispatch buffer [E, C, d]. (expert, slot)
    # pairs are unique per kept token, so the scatter-add has no collisions.
    buf = jnp.zeros((e, capacity, d), x.dtype)
    buf = buf.at[expert_idx, jnp.clip(slot, 0, capacity - 1)].add(
        jnp.where(keep[:, None], x, 0))
    # Ship row j to device j; receive one row from every peer: [E, C, d]
    # becomes "from-source-device" major on the receiver.
    recv = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
    # Local expert on everything received.
    w1, b1 = params["w1"][0], params["b1"][0]
    w2, b2 = params["w2"][0], params["b2"][0]
    out = _ffn(recv.reshape(e * capacity, d).astype(jnp.float32),
               w1.astype(jnp.float32), b1, w2.astype(jnp.float32), b2)
    out = out.reshape(e, capacity, d)
    # Ship results back (all_to_all is its own inverse for this pattern).
    back = lax.all_to_all(out.astype(x.dtype), axis_name,
                          split_axis=0, concat_axis=0, tiled=True)
    # Unpack: token i reads its slot, weighted by its gate; dropped → 0.
    y = back[expert_idx, jnp.clip(slot, 0, capacity - 1)]
    y = y * (gate * keep).astype(y.dtype)[:, None]
    return y, aux


def moe_dense(params: dict, x: jax.Array):
    """Single-device reference: identical top-1 routing/combine math with
    unlimited capacity (no drops). x: [T, d] → (y, aux)."""
    t, _ = x.shape
    e = params["w1"].shape[0]
    expert_idx, _, _, gate, (f, p) = _route(x, params["router"], capacity=t)
    aux = e * jnp.sum(f * p)
    outs = jax.vmap(lambda w1, b1, w2, b2: _ffn(
        x.astype(jnp.float32), w1.astype(jnp.float32), b1,
        w2.astype(jnp.float32), b2))(
        params["w1"], params["b1"], params["w2"], params["b2"])   # [E, T, d]
    y = jnp.take_along_axis(
        outs, expert_idx[None, :, None], axis=0)[0]               # [T, d]
    return (y * gate[:, None]).astype(x.dtype), aux


def make_moe(mesh: Mesh, expert_axis: str = "expert",
             capacity_factor: float = 2.0):
    """Wrap ``moe_spmd`` in shard_map over global arrays: tokens [T@expert, d],
    expert weights [E@expert, ...], router replicated."""
    fn = partial(moe_spmd, axis_name=expert_axis,
                 capacity_factor=capacity_factor)
    param_specs = {"router": P(), "w1": P(expert_axis), "b1": P(expert_axis),
                   "w2": P(expert_axis), "b2": P(expert_axis)}
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs, P(expert_axis)),
        out_specs=(P(expert_axis), P()),
        check_vma=False))


# -- top-k routing over all experts, a share of them held ---------------------

def route_topk(u: jax.Array, router: jax.Array, top_k: int):
    """``p = softmax(u router)`` over all experts in float32 (the product at
    full precision: a near-tie decides which expert runs), the ``top_k``
    largest and their weights ``p_e / sum of the top_k``. u [T, d] ->
    (experts [T, k] int32, weights [T, k] float32)."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = lax.top_k(probs, top_k)
    # the chosen probabilities, read through a one-hot: the transpose is a
    # sum where top_k's own would scatter T x k scalars
    chosen = jax.nn.one_hot(experts, probs.shape[-1], dtype=probs.dtype)
    top = jnp.sum(probs[:, None, :] * chosen, axis=-1)
    return experts, top / jnp.sum(top, axis=-1, keepdims=True)


@jax.custom_vjp
def _take_pairs(x, tok, pos):
    """The pair buffer's rows: ``x[tok]`` [C, d]. Its transpose is a gather
    too (every token sums the rows of its own pairs, found at ``pos``
    [T, k]; C marks a pair with no row), where XLA's would scatter-add C
    rows."""
    return x[tok]


def _take_pairs_fwd(x, tok, pos):
    return x[tok], pos


def _take_pairs_bwd(pos, g):
    g = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)])
    return g[pos].astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


_take_pairs.defvjp(_take_pairs_fwd, _take_pairs_bwd)


def _rows_of_pairs(out, pos):
    """[T, k, d]: the row of each of a token's pairs, zeros for a pair with
    no row (``pos == C``)."""
    return jnp.concatenate(
        [out, jnp.zeros((1, out.shape[1]), out.dtype)])[pos]


@jax.custom_vjp
def _combine_pairs(out, w, tok, pair, pos):
    """``y[t] = sum_k w[t, k] out[pos[t, k]]`` in float32 (a pair with no
    row adds nothing). ``tok`` / ``pair`` [C] name the token and the pair
    (``t * k + slot``) of each row: the transpose with respect to ``out``
    gathers with them."""
    return _combine_pairs_fwd(out, w, tok, pair, pos)[0]


def _combine_pairs_fwd(out, w, tok, pair, pos):
    rows = _rows_of_pairs(out, pos)
    y = jnp.einsum("tk,tkd->td", w, rows.astype(jnp.float32))
    return y.astype(out.dtype), (rows, w, tok, pair)


def _combine_pairs_bwd(res, dy):
    rows, w, tok, pair = res
    d_out = (w.reshape(-1)[pair][:, None] * dy[tok].astype(jnp.float32)
             ).astype(rows.dtype)
    d_w = jnp.einsum("td,tkd->tk", dy.astype(jnp.float32),
                     rows.astype(jnp.float32))
    return d_out, d_w, None, None, None


_combine_pairs.defvjp(_combine_pairs_fwd, _combine_pairs_bwd)


def moe_topk_held(params: dict, u: jax.Array, *, top_k: int,
                  first_expert: int = 0,
                  router_input: jax.Array | None = None):
    """The part of a top-k expert layer that the holder of experts
    ``first_expert .. first_expert + n - 1`` computes.

    ``params``: ``router`` [d, E] over ALL experts, ``gate`` / ``up``
    [n, d, f] and ``down`` [n, f, d] of the n held. ``u`` [T, d] (the
    products run in its dtype, accumulated in float32); ``router_input``,
    where given, is what the router reads instead (the same values before
    they were rounded to ``u``'s dtype). Every token routes over all E
    experts (``route_topk``); a (token, expert) pair whose expert is held
    here gets a row in the pair buffer, sorted by expert, and the result is
    ``sum over a token's held pairs of w * (silu(x gate_e) * (x up_e))
    down_e``. The weights stay normalised over all k chosen, held or not;
    what the absent experts would add is left out; a token none of whose k
    is held gets zero.

    Nothing is dropped and nothing can be: the pair buffer has the worst
    case's ``k T`` rows (every pair of every token held here). The grouped
    products walk only the tiles that hold pairs; the gathers move every
    row of the buffer, filled or not.

    Returns (y [T, d], counters): ``moe_pairs`` (pairs the held experts
    computed), ``moe_load_max_over_mean`` (the fullest held expert's pairs
    over the mean)."""
    t, _ = u.shape
    n = params["gate"].shape[0]
    n_pairs = t * top_k
    with jax.named_scope(scopes.MOE_ROUTER):
        experts, weights = route_topk(
            u if router_input is None else router_input, params["router"],
            top_k)
    with jax.named_scope(scopes.MOE_DISPATCH):
        local = experts - first_expert
        held = (local >= 0) & (local < n)
        key = jnp.where(held, local, n).reshape(-1).astype(jnp.int32)
        iota = jnp.arange(n_pairs, dtype=jnp.int32)
        # held pairs first, by expert, in token order; then the rest
        _, order = lax.sort((key, iota), num_keys=1)
        _, rank = lax.sort((order, iota), num_keys=1)
        load = jnp.sum(jax.nn.one_hot(key, n, dtype=jnp.int32), axis=0)
        rows = jnp.sum(load)
        tok = order // top_k
        # the row of each of a token's pairs; ``n_pairs`` for one not held
        pos = jnp.where(held.reshape(-1), rank, n_pairs).reshape(t, top_k)
        x = _take_pairs(u, tok, pos)
    with jax.named_scope(scopes.MOE_EXPERTS):
        from tpudist.ops.pallas.grouped_matmul import grouped_matmul
        dt = u.dtype

        def grouped(a, w):
            return grouped_matmul(a, w.astype(dt), load)
        gate = grouped(x, params["gate"]).astype(jnp.float32)
        h = (jax.nn.silu(gate) * grouped(x, params["up"])).astype(dt)
        out = grouped(h, params["down"])
    with jax.named_scope(scopes.MOE_COMBINE):
        # a row past the last pair holds whatever the product left there
        # (its input was some token's row, its group nobody's)
        out = jnp.where((iota < rows)[:, None], out, 0)
        y = _combine_pairs(out, jnp.where(held, weights, 0.0), tok, order,
                           pos)
    total = rows.astype(jnp.float32)
    counters = {
        scopes.MOE_PAIRS: total,
        scopes.MOE_LOAD: jnp.max(load).astype(jnp.float32)
        * n / jnp.maximum(total, 1.0),
    }
    return y, counters
