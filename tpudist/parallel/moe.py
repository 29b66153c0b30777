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

``moe_topk_held`` (last section) is the other layer: a score over all
experts, the k largest chosen and their weights renormalised, no capacity
and no dropped pair, for a holder of ``n`` consecutive experts of ``E`` that
computes its own experts' part of the result (grouped products over the
experts held: ``ops/pallas/grouped_matmul.py``). Two router rules
(``route_topk``: a softmax's, or sigmoid scores with a bias that chooses and
does not weigh, and a scaling factor) and two expert bodies (SwiGLU, gated:
``gate`` / ``up`` / ``down``; ``relu(x up^T)^2 down``, ungated: ``up`` /
``down``) go through the same pair buffer, grouped products and walks; which,
the registered model says. ``shared_expert`` is the dense expert every token
visits, which every holder computes whole. On one chip the layer runs without
an exchange; the exchange across the chips that share a layer is not written
yet.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

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

ROUTER_RULES = ("softmax", "sigmoid")


def route_topk(u: jax.Array, router: jax.Array, top_k: int, *,
               rule: str = "softmax", bias: jax.Array | None = None,
               scale: float = 1.0):
    """The ``top_k`` experts of each token and their weights, from logits
    ``u router`` over all experts in float32 (the product at full precision:
    a near-tie decides which expert runs). u [T, d] -> (experts [T, k]
    int32, weights [T, k] float32).

    ``softmax``: ``p = softmax(logits)``, the ``top_k`` largest, ``w_e = p_e
    / sum of the top_k``. ``sigmoid``: ``s = sigmoid(logits)``, the ``top_k``
    largest of ``s + bias`` (``bias`` [E]: it moves the choice and never the
    weight), ``w_e = scale * s_e / (sum of the chosen s + 1e-20)``."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if rule == "softmax":
        probs = ranked = jax.nn.softmax(logits, axis=-1)
    elif rule == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        ranked = probs if bias is None else probs + lax.stop_gradient(bias)
    else:
        raise ValueError(f"router rule {rule!r} (one of {ROUTER_RULES})")
    _, experts = lax.top_k(ranked, top_k)
    # the chosen probabilities, read through a one-hot: the transpose is a
    # sum where top_k's own would scatter T x k scalars
    chosen = jax.nn.one_hot(experts, probs.shape[-1], dtype=probs.dtype)
    top = jnp.sum(probs[:, None, :] * chosen, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    if rule == "sigmoid":
        total = total + 1e-20
    weights = top / total
    return experts, weights if scale == 1.0 else weights * scale


class _Pairs(NamedTuple):
    """Where the held (token, expert) pairs lie in a layer's pair buffer of
    ``C = k T`` rows, sorted by expert, the first ``rows`` of them filled.
    A pair is ``t * k + slot``."""
    rows: jax.Array      # [] int32: pairs held
    tok: jax.Array       # [C] the token of each row
    pair: jax.Array      # [C] the pair of each row
    by_pair: jax.Array   # [C] the held pairs ascending (a token's lie
    src: jax.Array       # [C] together), C past the last; the row of each
    pos: jax.Array       # [T, k] the row of each pair; C for one not held


def _block(c: int) -> int:
    """Rows a turn of the loops over a pair buffer of ``c`` rows: up to four
    of the grouped product's row tiles (2,048 rows of 131,072), whole turns
    of the buffer."""
    from tpudist.ops.pallas.grouped_matmul import _row_tile
    tile = _row_tile(c)
    return next(m * tile for m in (4, 2, 1) if c % (m * tile) == 0)


def _walk(rows, like, body):
    """Buffers like ``like`` (a tree of [C, ...] shapes and dtypes) written
    a block at a time: ``body(at, filled) -> blocks`` for each block of
    ``_block(C)`` rows that holds one of the ``rows`` pairs (on the device),
    ``ceil(rows / block)`` turns, none for an empty buffer. ``at(a, more=0)``
    is the block's rows of a [C, ...] array (and ``more`` after them);
    ``filled`` [block, 1] says which of them hold a pair: ``body`` writes
    zeros in the others. A block no pair reaches is never written and holds
    whatever the memory held (``lax.empty``: zeros off the TPU), as the
    grouped products leave theirs: nothing reads it. A loop writes fresh
    buffers only (one whose carry it also read copied the whole buffer
    every turn, on the chip). Only the hand-written halves of the custom
    VJPs below call this: a loop whose trip count is on the device has no
    transpose."""
    block = _block(jax.tree.leaves(like)[0].shape[0])
    iota = jnp.arange(block, dtype=jnp.int32)[:, None]

    def turn(i, bufs):
        start = i * block

        def at(a, more=0):
            return lax.dynamic_slice_in_dim(a, start, block + more)
        return jax.tree.map(
            lambda buf, blk: lax.dynamic_update_slice_in_dim(
                buf, blk.astype(buf.dtype), start, 0),
            bufs, body(at, iota < rows - start))
    return lax.fori_loop(
        0, (rows + block - 1) // block, turn,
        jax.tree.map(lambda a: lax.empty(a.shape, a.dtype), like))


def _sum_by_token(buf, pairs: _Pairs, weights=None):
    """[T, d] in ``buf``'s dtype: the float32 sum, a token, of its pairs'
    rows of ``buf`` [C, d], each times its weight (``weights`` [T k]) where
    given; zeros for a token that holds none.

    The rows are taken in (token, slot) order, a block at a time, so a
    token's rows lie together, at most ``k`` of them: log2(k) shifted adds
    leave each token's sum on its first row, which a gather of T rows
    reads. No [T, k, d] tensor, and the work follows the pairs held."""
    c = buf.shape[0]
    k = pairs.pos.shape[1]
    shifts = [1 << i for i in range((k - 1).bit_length())]
    halo = sum(shifts) + 1                     # k, rounded up to a power of 2
    by_pair = jnp.concatenate([pairs.by_pair, jnp.full((halo,), c, jnp.int32)])
    src = jnp.concatenate([pairs.src, jnp.zeros((halo,), jnp.int32)])

    def sums(at, _):
        pair = at(by_pair, halo)
        z = buf[at(src, halo)].astype(jnp.float32)
        if weights is not None:
            z = z * weights[jnp.minimum(pair, c - 1)][:, None]
        z = jnp.where((pair < c)[:, None], z, 0.0)
        tok = pair // k
        for s in shifts:
            same = jnp.concatenate([tok[s:] == tok[:-s],
                                    jnp.zeros((s,), bool)])
            z = z + jnp.where(same[:, None], jnp.roll(z, -s, axis=0), 0.0)
        return z[:-halo]
    by_first = _walk(pairs.rows, buf, sums)
    count = jnp.sum(pairs.pos < c, axis=1)
    # a token that holds a pair has its first among the filled rows
    return jnp.where((count > 0)[:, None],
                     by_first[jnp.cumsum(count) - count], 0)


def _take_pairs(u, pairs: _Pairs):
    """The pair buffer's rows: ``u[tok]`` [C, d] for the rows that hold a
    pair."""
    like = jax.ShapeDtypeStruct((pairs.tok.shape[0], u.shape[1]), u.dtype)
    with jax.named_scope(scopes.MOE_DISPATCH):
        return _walk(pairs.rows, like, lambda at, _: u[at(pairs.tok)])


def _product(a, w, load, transposed=False):
    """``a @ w[g]`` a group; ``transposed``: ``w`` lies [G, out, in]."""
    # Pallas is imported where a layer is traced, not with the package
    from tpudist.ops.pallas.grouped_matmul import grouped_matmul
    return grouped_matmul(a, w, load, transpose_rhs=transposed)


def _transposes(a, w, load, g, transposed=False):
    """The grouped product's two cotangents, handed on together: the
    weights' is then computed where the rows' is, and not at the
    scheduler's leisure with both of its [C, .] operands kept until then (a
    whole step's peak read 14.0 GiB for 11.97)."""
    # the product's own transposes (its forward, unused here, is dropped)
    _, back = jax.vjp(lambda a, w: _product(a, w, load, transposed), a, w)
    return lax.optimization_barrier(back(g))


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _project_pairs(u, w, pairs: _Pairs, load, transposed=False):
    """The grouped product of the pairs' rows ``u[tok]`` with their
    experts' ``w`` ([n, d, f]; [n, f, d] where ``transposed``). The rows are
    not kept for the transposes but taken again (one more gather of the
    filled blocks): kept, they would hold [C, d] through the whole of the
    layer's backward. The cotangent of ``u`` sums a token's rows in
    float32."""
    return _project_pairs_fwd(u, w, pairs, load, transposed)[0]


def _project_pairs_fwd(u, w, pairs, load, transposed):
    return (_product(_take_pairs(u, pairs), w, load, transposed),
            (u, w, pairs, load))


def _project_pairs_bwd(transposed, res, g):
    u, w, pairs, load = res
    dx, dw = _transposes(_take_pairs(u, pairs), w, load, g, transposed)
    with jax.named_scope(scopes.MOE_DISPATCH):
        du = _sum_by_token(dx, pairs)
    return du, dw, None, None


_project_pairs.defvjp(_project_pairs_fwd, _project_pairs_bwd)


@jax.custom_vjp
def _swiglu(gu, rows):
    """``silu(gate) * up`` in float32 of ``gu`` = [gate | up] [C, 2f], over
    the ``rows`` rows that hold a pair; zeros in the rest of their last
    block, in the result and in the cotangent."""
    return _swiglu_fwd(gu, rows)[0]


def _swiglu_fwd(gu, rows):
    c, f = gu.shape[0], gu.shape[1] // 2

    def act(at, filled):
        blk = at(gu).astype(jnp.float32)
        return jnp.where(filled, jax.nn.silu(blk[:, :f]) * blk[:, f:], 0.0)
    h = _walk(rows, jax.ShapeDtypeStruct((c, f), gu.dtype), act)
    return h, (gu, rows)


def _swiglu_bwd(res, dh):
    gu, rows = res
    f = gu.shape[1] // 2

    def act_t(at, filled):
        blk = at(gu).astype(jnp.float32)
        gate, up = blk[:, :f], blk[:, f:]
        d = at(dh).astype(jnp.float32)
        s = jax.nn.sigmoid(gate)
        return jnp.where(filled, jnp.concatenate(
            [d * up * s * (1.0 + gate * (1.0 - s)), d * gate * s], axis=1),
            0.0)
    return _walk(rows, gu, act_t), None


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@jax.custom_vjp
def _relu2(up, rows):
    """``relu(up)^2`` in float32 of ``up`` [C, f], over the ``rows`` rows
    that hold a pair, as ``_swiglu`` walks its own."""
    return _relu2_fwd(up, rows)[0]


def _relu2_fwd(up, rows):
    def act(at, filled):
        return jnp.where(filled, jnp.square(
            jax.nn.relu(at(up).astype(jnp.float32))), 0.0)
    return _walk(rows, up, act), (up, rows)


def _relu2_bwd(res, dh):
    up, rows = res

    def act_t(at, filled):
        return jnp.where(filled, 2.0 * jax.nn.relu(
            at(up).astype(jnp.float32)) * at(dh).astype(jnp.float32), 0.0)
    return _walk(rows, up, act_t), None


_relu2.defvjp(_relu2_fwd, _relu2_bwd)


def shared_expert(params: dict, u: jax.Array) -> jax.Array:
    """The dense expert every token visits: whole on every holder of a
    layer, so counted once where the holders' parts are summed. Its body is
    the routed experts' (here is where it is chosen, from the leaves): with
    a ``gate`` a SwiGLU, ``(silu(u gate) * (u up)) down``; without, ungated
    ``relu(u up)^2 down`` (``gate``, ``up`` [d, f], ``down`` [f, d]; products
    in ``u``'s dtype, the activation in float32)."""
    with jax.named_scope(scopes.MOE_SHARED):
        dt = u.dtype

        def product(x, name):
            return jnp.dot(x, params[name].astype(dt),
                           preferred_element_type=jnp.float32)
        if "gate" in params:
            h = (jax.nn.silu(product(u, "gate")) * product(u, "up"))
        else:
            h = jnp.square(jax.nn.relu(product(u, "up")))
        return product(h.astype(dt), "down").astype(dt)


@jax.custom_vjp
def _grouped(a, w, load):
    """``ops/pallas/grouped_matmul.py``'s product, with ``_transposes``."""
    return _product(a, w, load)


def _grouped_fwd(a, w, load):
    return _product(a, w, load), (a, w, load)


def _grouped_bwd(res, g):
    return (*_transposes(*res, g), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@jax.custom_vjp
def _combine_pairs(out, w, pairs: _Pairs):
    """``y[t] = sum over t's held pairs of w[t, k] out[row of (t, k)]`` in
    float32: no row past the last pair is read, and their cotangent in the
    last block is zero."""
    return _combine_pairs_fwd(out, w, pairs)[0]


def _combine_pairs_fwd(out, w, pairs):
    return _sum_by_token(out, pairs, w.reshape(-1)), (out, w, pairs)


def _combine_pairs_bwd(res, dy):
    out, w, pairs = res
    c = out.shape[0]
    flat = w.reshape(-1)

    def spread(at, filled):
        g = dy[at(pairs.tok)].astype(jnp.float32)
        dot = jnp.sum(g * at(out).astype(jnp.float32), axis=1, keepdims=True)
        return (jnp.where(filled, flat[at(pairs.pair)][:, None] * g, 0.0),
                jnp.where(filled, dot, 0.0)[:, 0])
    d_out, dot = _walk(pairs.rows, (
        out, jax.ShapeDtypeStruct((c,), jnp.float32)), spread)
    d_w = jnp.where(pairs.pos < c, dot[jnp.minimum(pairs.pos, c - 1)], 0.0)
    return d_out, d_w, None


_combine_pairs.defvjp(_combine_pairs_fwd, _combine_pairs_bwd)


def moe_topk_held(params: dict, u: jax.Array, *, top_k: int,
                  first_expert: int = 0,
                  router_input: jax.Array | None = None,
                  rule: str = "softmax", scale: float = 1.0):
    """The part of a top-k expert layer that the holder of experts
    ``first_expert .. first_expert + n - 1`` computes.

    ``params``: ``router`` [d, E] over ALL experts; of the n held, ``gate``
    / ``up`` [n, d, f] and ``down`` [n, f, d] where the experts are gated
    (SwiGLU), or ``up`` [n, f, d] (as a Linear's weight lies, [out, in]) and
    ``down`` [n, f, d] where they are not (``relu(x up_e^T)^2 down_e``: no
    ``gate``); ``router_bias`` [E] where the rule takes one. ``u`` [T, d]
    (the products run in its dtype, accumulated in float32);
    ``router_input``, where given, is what the router reads instead (the
    same values before they were rounded to ``u``'s dtype). Every token
    routes over all E experts (``route_topk`` by ``rule`` and ``scale``); a
    (token, expert) pair whose expert is held here gets a row in the pair
    buffer, sorted by expert, and the result is ``sum over a token's held
    pairs of w * (silu(x gate_e) * (x up_e)) down_e`` (or the ungated
    body's). The weights stay normalised over all k chosen, held or not;
    what the absent experts would add is left out; a token none of whose k
    is held gets zero.

    Nothing is dropped and nothing can be: the pair buffer has the worst
    case's ``k T`` rows (every pair of every token held here). What is
    touched of it follows the pairs held, counted on the device: the grouped
    products walk only the tiles that hold pairs, and everything around
    them (the rows' gather, SwiGLU, the weighted sum back to tokens and
    their transposes) only the blocks that do (``_walk``).

    Returns (y [T, d], counters): ``moe_pairs`` (pairs the held experts
    computed), ``moe_load_max_over_mean`` (the fullest held expert's pairs
    over the mean), ``moe_rows_walked`` (rows of the buffer the block loops
    touched: the pairs, rounded up to a block)."""
    t, _ = u.shape
    n = params["down"].shape[0]
    n_pairs = t * top_k
    with jax.named_scope(scopes.MOE_ROUTER):
        experts, weights = route_topk(
            u if router_input is None else router_input, params["router"],
            top_k, rule=rule, bias=params.get("router_bias"), scale=scale)
    with jax.named_scope(scopes.MOE_DISPATCH):
        local = experts - first_expert
        held = (local >= 0) & (local < n)
        key = jnp.where(held, local, n).reshape(-1).astype(jnp.int32)
        iota = jnp.arange(n_pairs, dtype=jnp.int32)
        # held pairs first, by expert, in token order; then the rest
        _, order = lax.sort((key, iota), num_keys=1)
        load = jnp.sum(jax.nn.one_hot(key, n, dtype=jnp.int32), axis=0)
        rows = jnp.sum(load)
        # the filled rows again, by pair: a token's rows lie together
        by_pair, src = lax.sort(
            (jnp.where(iota < rows, order, n_pairs), iota), num_keys=1)
        # a held pair's row: as many rows before it as held pairs
        flat = held.reshape(-1)
        before = jnp.cumsum(flat, dtype=jnp.int32) - flat
        pairs = _Pairs(
            rows=rows, tok=order // top_k, pair=order, by_pair=by_pair,
            src=src, pos=jnp.where(flat, src[before], n_pairs).reshape(
                t, top_k))
    with jax.named_scope(scopes.MOE_EXPERTS):
        dt = u.dtype
        if "gate" in params:
            # gate and up in one product: one cotangent for the pairs' rows
            gate_up = jnp.concatenate([params["gate"], params["up"]], axis=2)
            h = _swiglu(_project_pairs(u, gate_up.astype(dt), pairs, load),
                        rows)
        else:
            h = _relu2(_project_pairs(u, params["up"].astype(dt), pairs,
                                      load, True), rows)
        out = _grouped(h, params["down"].astype(dt), load)
    with jax.named_scope(scopes.MOE_COMBINE):
        y = _combine_pairs(out, jnp.where(held, weights, 0.0), pairs)
    total, block = rows.astype(jnp.float32), _block(n_pairs)
    counters = {
        scopes.MOE_PAIRS: total,
        scopes.MOE_LOAD: jnp.max(load).astype(jnp.float32)
        * n / jnp.maximum(total, 1.0),
        scopes.MOE_WALKED: (-(-rows // block) * block).astype(jnp.float32),
    }
    return y, counters
