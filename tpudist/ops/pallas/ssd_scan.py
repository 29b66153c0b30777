"""The Mamba-2 chunked scan (``ops/ssd.py`` has the equations and what is
float32) as a Pallas TPU kernel pair behind one custom VJP.

**A program** holds one chunk of one row for one group's heads. The grid is
(rows, groups, chunks), the chunks innermost and sequential, and **the state
that enters a chunk lives in VMEM scratch from chunk to chunk**: zero at a
row's first chunk, ``exp(cum_last) x state + S_c`` after each. It lies
transposed, [N, heads x P]: a group's heads side by side on the lanes, as
``x`` and ``y`` lie, so the carried part (``C`` times the state) and a
chunk's own state (``B^T`` times the weighted ``dt x``) are products a whole
lane tile wide and nothing of a head is ever moved across lanes. A lane tile
holds ``128 / P`` heads (two of 64); what differs by head inside a tile (the
decay) runs a product a head over the whole tile and keeps that head's lanes.
``C B^T`` is taken once a group; a head's lower-triangular decay, ``mixed``,
``dt x``, the chunk's state, the carried part and ``D x`` are made and used
in VMEM. The running sums of ``dt A`` come in (``ssd._running_sum`` stays
the seam: [rows, chunks, groups, heads a group, Q] float32), positions on
the lanes; the kernel turns them once a chunk to have them on the sublanes
too (a decay needs ``cum_i - cum_j``).

**Forward** out: ``y`` float32 and, where a backward follows, the state that
entered every chunk (float32 [rows, groups, chunks, N, heads x P]: at the
published shape 268 MB a block, live through that block's backward only).
**Backward**: the same walk from the last chunk to the first, the cotangent
of the state that leaves a chunk carried in VMEM. A chunk recomputes ``B
C^T``, the decays (transposed: [j, i]), ``dt x`` and the weighted ``dt x``
and writes ``dx``, ``dB``, ``dC`` (summed over the group's heads here, which
is why a program holds a whole group), the cotangents of ``dt`` (through
``dt x``) and of the running sums, of the chunks' decays (lane-wise sums: the
caller's ``exp`` and ``repeat`` are JAX's) and of ``D``.

Precision is ``ssd.ssd_scan``'s to the letter: float32 ``dt``, running sums,
exps, carried state and ``y``; the products take operands in ``x``'s dtype,
rounded where ``ssd_scan`` rounds them, and accumulate in float32. The
cotangent of ``y`` is rounded to that dtype where a product reads it, as
XLA's default precision rounds it in JAX's own backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.ssd import LANES

_F32 = jnp.float32


def _dot(a, b, contract):
    """A product contracting ``a``'s and ``b``'s axes ``contract``,
    accumulated in float32."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=_F32)


_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)


def _turn(pad_ref, rows):
    """``rows`` [r, Q] (r <= 128) -> [Q, 128] with them on the first r
    lanes: through a [128, Q] scratch whose other rows were zeroed at the
    row's first chunk (a transpose wants whole tiles)."""
    pad_ref[:rows.shape[0], :] = rows
    return pad_ref[...].T


class _Tile:
    """One lane tile of a group's ``heads x P`` lanes: its lanes, the heads
    it holds and which of them owns a lane."""

    def __init__(self, index: int, p: int, q: int):
        self.width = max(p, LANES)
        self.heads = [index * (self.width // p) + k
                      for k in range(self.width // p)]
        self.lanes = slice(index * self.width, (index + 1) * self.width)
        self._p = p
        self._lane = None if len(self.heads) == 1 else (
            jax.lax.broadcasted_iota(jnp.int32, (q, self.width), 1))

    def of(self, k: int, value, other=0.0):
        """``value`` on head k's lanes, ``other`` elsewhere."""
        if self._lane is None:
            return value
        mine = (self._lane >= k * self._p) & (self._lane < (k + 1) * self._p)
        return jnp.where(mine, value, other)

    def spread(self, cols):
        """[Q, width]: each head's column of ``cols`` [Q, 128] (heads on the
        lanes) over that head's lanes."""
        out = None
        for k, h in enumerate(self.heads):
            col = jnp.broadcast_to(cols[:, h:h + 1],
                                   (cols.shape[0], self.width))
            out = col if out is None else self.of(k, col, out)
        return out

    def gather(self, k: int, value):
        """[Q, 1]: the sum of ``value`` [Q, width] over head k's lanes."""
        return jnp.sum(self.of(k, value), axis=1, keepdims=True)


def _chunk_scalars(pad_ref, dt_ref, cum_ref):
    """A chunk's float32 scalars a head with positions on the sublanes
    ([Q, 128], heads on the lanes): dt, the running sum, ``exp(cum_last -
    cum)`` and ``exp(cum)``; and the running sums as they came ([heads, Q])."""
    cum_rows = cum_ref[...]
    cum = _turn(pad_ref, cum_rows)
    dt = _turn(pad_ref, dt_ref[...])
    last = cum[cum.shape[0] - 1:, :]
    return dt, cum, jnp.exp(last - cum), jnp.exp(cum), cum_rows


def _forward_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, keep_ref, d_ref,
                    y_ref, *rest, p: int, keep_entered: bool):
    entered_ref = rest[0] if keep_entered else None
    state_ref, pad_ref = rest[-2:]
    q, lanes = x_ref.shape
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state_ref[...] = jnp.zeros_like(state_ref)
        pad_ref[...] = jnp.zeros_like(pad_ref)

    bm, cm = b_ref[...], c_ref[...]
    scores = _dot(cm, bm, _NT)                              # [i, j] a group
    dt, cum, to_end, grown, cum_rows = _chunk_scalars(pad_ref, dt_ref,
                                                      cum_ref)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    if keep_entered:
        entered_ref[...] = state_ref[...]
    for index in range(lanes // max(p, LANES)):
        tile = _Tile(index, p, q)
        xs = x_ref[:, tile.lanes].astype(_F32)
        xdt = xs * tile.spread(dt)
        xdt_lo = xdt.astype(dtype)
        y = None
        for k, h in enumerate(tile.heads):
            # masked BEFORE the exp: the exponent is a difference <= 0
            decay = jnp.exp(jnp.where(
                lower, cum[:, h:h + 1] - cum_rows[h:h + 1, :], -jnp.inf))
            part = _dot((scores * decay).astype(dtype), xdt_lo, _NN)
            y = part if y is None else tile.of(k, part, y)
        entered = state_ref[:, tile.lanes]
        y = y + _dot(cm, entered.astype(dtype), _NN) * tile.spread(grown)
        y_ref[:, tile.lanes] = y + xs * d_ref[:, tile.lanes]
        own = _dot(bm, (xdt * tile.spread(to_end)).astype(dtype), _TN)
        state_ref[:, tile.lanes] = entered * keep_ref[:, tile.lanes] + own


def _backward_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, keep_ref, d_ref,
                     entered_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                     dcum_ref, dkeep_ref, dd_ref, dstate_ref, pad_ref, *,
                     p: int):
    q, lanes = x_ref.shape
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        pad_ref[...] = jnp.zeros_like(pad_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[...], c_ref[...]
    scores_t = _dot(bm, cm, _NT)                            # [j, i] a group
    dt, cum, to_end, grown, cum_rows = _chunk_scalars(pad_ref, dt_ref,
                                                      cum_ref)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    dkeep_ref[...] = jnp.sum(dstate_ref[...] * entered_ref[...], axis=0,
                             keepdims=True)
    dscores_t = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    # cotangents a head with positions on the sublanes, heads on the lanes
    ddt = d_grown = d_to_end = d_decay = jnp.zeros((q, LANES), _F32)
    dcum_rows = []

    def place(cols, h, col):
        """``col`` [Q, 1] on head h's lane of ``cols``."""
        return jnp.where(head_lane == h, col, cols)

    for index in range(lanes // max(p, LANES)):
        tile = _Tile(index, p, q)
        xs = x_ref[:, tile.lanes].astype(_F32)
        dt_w, to_end_w, grown_w = (tile.spread(v)
                                   for v in (dt, to_end, grown))
        xdt = xs * dt_w
        xdt_lo = xdt.astype(dtype)
        dy = dy_ref[:, tile.lanes]
        dy_lo = dy.astype(dtype)
        dd_ref[:, tile.lanes] += jnp.sum(dy * xs, axis=0, keepdims=True)
        entered_lo = entered_ref[:, tile.lanes].astype(dtype)
        dnext = dstate_ref[:, tile.lanes]
        dnext_lo = dnext.astype(dtype)
        # the carried part: y += (C entered) exp(cum)
        carried = _dot(cm, entered_lo, _NN)
        dcarried_lo = (dy * grown_w).astype(dtype)
        dc = dc + _dot(dcarried_lo, entered_lo, _NT)
        dstate_ref[:, tile.lanes] = (dnext * keep_ref[:, tile.lanes]
                                     + _dot(cm, dcarried_lo, _TN))
        grown_term = dy * carried * grown_w
        # the chunk's own state: B^T (dt x exp(cum_last - cum))
        dweighted = _dot(bm, dnext_lo, _NN)
        db = db + _dot((xdt * to_end_w).astype(dtype), dnext_lo, _NT)
        to_end_term = dweighted * xdt * to_end_w
        dxdt = dweighted * to_end_w
        for k, h in enumerate(tile.heads):
            # within the chunk, transposed: [j, i], j <= i
            decay_t = jnp.exp(jnp.where(
                upper, cum_rows[h:h + 1, :] - cum[:, h:h + 1], -jnp.inf))
            dxdt = dxdt + tile.of(k, _dot(
                (scores_t * decay_t).astype(dtype), dy_lo, _NN))
            dmixed_t = _dot(tile.of(k, xdt_lo, jnp.zeros((), dtype)), dy_lo,
                            _NT) * decay_t
            dscores_t = dscores_t + dmixed_t
            through_decay = dmixed_t * scores_t
            dcum_rows.append(jnp.sum(through_decay, axis=0, keepdims=True))
            d_decay = place(d_decay, h, jnp.sum(through_decay, axis=1,
                                                keepdims=True))
            d_grown = place(d_grown, h, tile.gather(k, grown_term))
            d_to_end = place(d_to_end, h, tile.gather(k, to_end_term))
        # after the tile's heads, not inside their loop: there the backward
        # took 4.38 ms a block for 4.22 (timed alone on the chip)
        for k, h in enumerate(tile.heads):
            ddt = place(ddt, h, tile.gather(k, dxdt * xs))
        dx_ref[:, tile.lanes] = (dxdt * dt_w
                                 + dy * d_ref[:, tile.lanes]).astype(dtype)
    dscores_lo = dscores_t.astype(dtype)
    db_ref[...] = (db + _dot(dscores_lo, cm, _NN)).astype(dtype)
    dc_ref[...] = (dc + _dot(dscores_lo, bm, _TN)).astype(dtype)
    # cum_last is the chunk's last running sum: what exp(cum_last - cum)
    # hands back lands on the last position
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 0) == q - 1
    dcum = (d_grown - d_to_end - d_decay + jnp.where(
        at_last, jnp.sum(d_to_end, axis=0, keepdims=True), 0.0))
    heads = len(dcum_rows)
    dcum_ref[...] = dcum.T[:heads] + jnp.concatenate(dcum_rows, axis=0)
    ddt_ref[...] = ddt.T[:heads]


def _specs(rep, p, n, q, at):
    """The BlockSpecs of x / y (a group's lanes of a chunk), B / C, the
    chunk's scalars a head, a row of the group's lanes a chunk (the chunks'
    decays) and the entering state; ``at`` maps the grid's chunk index to
    the chunk."""
    rp = rep * p
    return dict(
        x=pl.BlockSpec((None, q, rp), lambda b, g, z: (b, at(z), g)),
        bc=pl.BlockSpec((None, q, n), lambda b, g, z: (b, at(z), g)),
        scalars=pl.BlockSpec((None, None, None, rep, q),
                             lambda b, g, z: (b, at(z), g, 0, 0)),
        keep=pl.BlockSpec((None, None, 1, rp),
                          lambda b, g, z: (b, at(z), 0, g)),
        d=pl.BlockSpec((1, rp), lambda b, g, z: (0, g)),
        entered=pl.BlockSpec((None, None, None, n, rp),
                             lambda b, g, z: (b, g, at(z), 0, 0)),
        dd=pl.BlockSpec((None, 1, rp), lambda b, g, z: (b, 0, g)))


_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sizes(x, b, dt):
    bsz, tp, hp = x.shape
    _, nc, groups, rep, q = dt.shape
    return bsz, nc, groups, rep, hp // (groups * rep), b.shape[2] // groups, q


def _products(bsz, nc, groups, rep, p, n, q):
    """Operations of the forward's four products (``C B^T`` a group; within
    the chunk, the chunk's state and the carried part a head)."""
    return 2 * bsz * nc * q * (groups * q * n
                               + groups * rep * (q * p + 2 * p * n))


# jitted: a step's four blocks then share one trace of each kernel (a body of
# some thousand operations, traced and lowered to Mosaic at every start)
@functools.partial(jax.jit, static_argnames=("keep_entered", "interpret"))
def _forward(x, b, c, dt, cum, keep, d, keep_entered, interpret):
    bsz, nc, groups, rep, p, n, q = sizes = _sizes(x, b, dt)
    spec = _specs(rep, p, n, q, at=lambda z: z)
    rp = rep * p
    out_shape = [jax.ShapeDtypeStruct(x.shape, _F32)]
    out_specs = [spec["x"]]
    if keep_entered:
        out_shape.append(jax.ShapeDtypeStruct((bsz, groups, nc, n, rp), _F32))
        out_specs.append(spec["entered"])
    moved = (x.size * x.dtype.itemsize + 2 * b.size * b.dtype.itemsize
             + 4 * (2 * dt.size + keep.size + x.size)
             + (4 * bsz * groups * nc * n * rp if keep_entered else 0))
    out = pl.pallas_call(
        functools.partial(_forward_kernel, p=p, keep_entered=keep_entered),
        grid=(bsz, groups, nc),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["scalars"],
                  spec["scalars"], spec["keep"], spec["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, rp), _F32),
                        pltpu.VMEM((LANES, q), _F32)],
        compiler_params=_GRID_SEMANTICS,
        cost_estimate=pl.CostEstimate(
            flops=_products(*sizes), bytes_accessed=moved,
            transcendentals=bsz * nc * groups * rep * q * (q + 2)),
        interpret=interpret,
    )(x, b, c, dt, cum, keep, d)
    return out if keep_entered else (out[0], None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(x, b, c, dt, cum, keep, d, entered, dy, interpret):
    bsz, nc, groups, rep, p, n, q = sizes = _sizes(x, b, dt)
    spec = _specs(rep, p, n, q, at=lambda z: nc - 1 - z)
    rp = rep * p
    moved = (2 * x.size * x.dtype.itemsize + 4 * b.size * b.dtype.itemsize
             + 4 * (4 * dt.size + 2 * keep.size + x.size + entered.size))
    return pl.pallas_call(
        functools.partial(_backward_kernel, p=p),
        grid=(bsz, groups, nc),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["scalars"],
                  spec["scalars"], spec["keep"], spec["d"], spec["entered"],
                  spec["x"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["scalars"],
                   spec["scalars"], spec["keep"], spec["dd"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct(keep.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, 1, groups * rp), _F32)],
        scratch_shapes=[pltpu.VMEM((n, rp), _F32),
                        pltpu.VMEM((LANES, q), _F32)],
        compiler_params=_GRID_SEMANTICS,
        # each product's two transposes, and the two recomputed (B C^T
        # and C times the entering state)
        cost_estimate=pl.CostEstimate(
            flops=2 * _products(*sizes) + 2 * bsz * nc * q * (
                groups * q * n + groups * rep * p * n),
            bytes_accessed=moved,
            transcendentals=bsz * nc * groups * rep * q * (q + 2)),
        interpret=interpret,
    )(x, b, c, dt, cum, keep, d, entered, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan(x, b, c, dt, cum, keep, d, interpret):
    return _forward(x, b, c, dt, cum, keep, d, False, interpret)[0]


def _scan_fwd(x, b, c, dt, cum, keep, d, interpret):
    y, entered = _forward(x, b, c, dt, cum, keep, d, True, interpret)
    return y, (x, b, c, dt, cum, keep, d, entered)


def _scan_bwd(interpret, res, dy):
    *grads, dd = _backward(*res, dy, interpret)
    return (*grads, jnp.sum(dd, axis=0))


# under ``jax.checkpoint`` the first pass wants no residuals: it then runs
# the primal, which keeps no entering states
_scan.defvjp(_scan_fwd, _scan_bwd, optimize_remat=True)


def scan_chunks(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
                cum: jax.Array, keep: jax.Array, d: jax.Array,
                interpret: bool | None = None) -> jax.Array:
    """``y`` [B, T, H P] float32 of the chunked recurrence with ``D x``.

    ``x`` [B, T, H P] and ``b``, ``c`` [B, T, G N] in the products' dtype, T
    whole chunks; ``dt`` and ``cum`` (the running sum of ``dt A`` inside a
    chunk) [B, chunks, G, H / G, Q] float32; ``keep`` [B, chunks, 1, H P]
    float32, a head's ``exp(cum_last)`` over its P lanes; ``d`` [1, H P]
    float32, a head's ``D`` likewise. Differentiable in all seven.
    Interpreted off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _scan(x, b, c, dt, cum, keep, d, interpret)
