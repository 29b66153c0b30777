"""The streaming attention kernels' entry for queries and keys WIDER than the
values: latent (compressed key-value) attention in its training form.

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) trains with a
head of its own keys: ``q = [q_nope | q_rope]`` and ``k = [k_nope | k_rope]``
of ``Dn + Dr`` columns (128 + 64) against values of ``Dv`` (128), and the
``Dr`` rotated key columns are ONE head that every query head reads. The
kernels of ``flash_attention.py`` carry one head size and refuse ``k.shape !=
v.shape``; 192 is no whole number of lane tiles either. Here a score tile is
the sum of two products,

    S = (scale q_nope) k_nope^T + (scale q_rope) k_rope^T,

so every operand stays what it is: ``k_rope`` is an array [B, T, Dr] with no
head axis, fetched from there by every program, and no [B, T, H, Dn + Dr] key
is ever built. What is shared with ``flash_attention.py``: the band
(``_Band``: which tiles run, which build a mask), the blocks of a group of
one, the fp32 online softmax, the two-pass backward from the saved logsumexp
and ``delta = rowsum(dO o)``, the names under which a rematerialised layer
keeps the forward's two results.

What differs, beside the two products:

- causal self-attention only (what a decoder trains under); the row is
  padded to the block with zeros and run as if that long: a padded key lies
  behind every true query, a padded query row is dropped on the way out and
  its dO and delta are zero, so no tile needs a mask for the padding.
- two entries over the same three kernel bodies. ``flash_attention_latent``
  takes [B, T, H, D] operands of any length: XLA moves them to [B, H, T, D]
  and pads the row. ``flash_attention_latent_laid`` takes them where they
  lie and moves nothing: q_nope and q_rope [B, H, T, D] as
  ``latent_rope.py`` writes them; a head's ``[k_nope | v]`` as ONE block of
  ``Dn + Dv`` columns of ``kv_b_proj``'s [B, T, H * (Dn + Dv)] result, which
  the body reads as its two halves (``_keys_apart``); o and dO as column
  block ``head`` of [B, T, H * Dv], where ``o_proj`` reads and writes; the
  dKV pass writes a head's dk_nope and dv into one block of ``kv_b_proj``'s
  cotangent (no join). Only the block addresses differ (``_column_spec``
  beside ``_head_spec``). q_rope and its cotangent, 64 wide, stay [B, H, T,
  64]: a 64-column block of a wider row is no whole lane tile.
- the row statistics (logsumexp, delta) lie LANE-DENSE, [B, H, 1, T]
  float32: a group of one puts one float in a row of the older entries'
  [B, Hkv, T, G] layout, which the chip pads to 128 lanes (256 MiB a call at
  two rows of 8,192 x 32 heads, and a rematerialised layer keeps it). The
  forward and the dQ pass hold their statistics as (bq, 1) columns and turn
  them to and from (1, bq) rows once a q block (``_to_row`` / ``_to_col``:
  a masked sum against the identity, 128 rows at a time: Mosaic lowers no
  sublane-to-lane reshape).
- the dKV pass runs TRANSPOSED, ``S^T = (scale k) q^T`` [bk, bq]: its
  per-query statistics are then rows as they lie in memory, and its two
  gradient products contract over the tile's minor dimension (``dV += P^T
  dO``, ``dK += dS^T q``: no transposed operand). Its grid is (batch, k
  block, head, q step): the shared ``k_rope`` block stays resident across
  the heads (read once a position), and its gradient, the sum over all
  heads, is taken in one float32 scratch tile and written once.

The path is chosen from the shapes a module hands over
(``models/decoder.py::LatentAttention`` calls the addressed entry where
``latent_rope.latent_plan`` says the shape allows it, this one for its split
operands otherwise; nothing probes or flags it). ``interpret`` off the TPU,
as there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.pallas.flash_attention import (
    _GRID_SEMANTICS, _LANES, NEG_INF, SAVED_BY_NAME, _Band, _ceil_to,
    _default_blocks, _scaled, _valid)

# the dKV pass's grid (batch, k block, head, q step): the shared key's
# gradient is summed over the heads and the q steps in scratch, so both run
# in order
_DKV_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_GRID_SEMANTICS.vmem_limit_bytes)


def _eye():
    return (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1))


def _to_row(col):
    """A (n * 128, 1) column as the (1, n * 128) row."""
    eye = _eye()
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, col[i:i + _LANES], 0.0), axis=0,
                 keepdims=True)
         for i in range(0, col.shape[0], _LANES)], axis=1)


def _to_col(row_ref):
    """A (1, n * 128) row (read from its block, a lane tile at a time) as
    the (n * 128, 1) column."""
    eye = _eye()
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, row_ref[:, i:i + _LANES], 0.0), axis=1,
                 keepdims=True)
         for i in range(0, row_ref.shape[1], _LANES)], axis=0)


def _on_tiles(band: _Band, row0, col0, run, step_fn, keys_axis: int = 1):
    """``step_fn(valid)`` for the tile at (row0, col0) where the step runs:
    None inside the band, the causal mask on the diagonal (``keys_axis`` 0:
    of the transposed tile)."""
    inside = band.interior(row0, col0)
    shape = (band.bq, band.bk) if keys_axis else (band.bk, band.bq)
    pl.when(jnp.logical_and(run, inside))(lambda: step_fn(None))
    pl.when(jnp.logical_and(run, jnp.logical_not(inside)))(
        lambda: step_fn(_valid(shape, row0, col0, causal=True,
                               q_len=band.q_len, k_len=band.k_len,
                               mask_k=False, keys_axis=keys_axis)))


def _nt(a, b):
    """``a b^T`` in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                qn_scr, qr_scr, m_scr, l_scr, acc_scr, *, band: _Band,
                scale: float):
    iq, step = pl.program_id(2), pl.program_id(3)
    col0, run, _ = band.step(iq, step)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        qn_scr[...] = _scaled(qn_ref[...], scale)
        qr_scr[...] = _scaled(qr_ref[...], scale)

    def _step(valid):
        v = v_ref[...]
        s = _nt(qn_scr[...], kn_ref[...]) + _nt(qr_scr[...], kr_ref[...])
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[:, 0:1], l_scr[:, 0:1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        m_scr[:, 0:1] = m_next
        l_scr[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _nn(p.astype(v.dtype), v)

    _on_tiles(band, iq * band.bq, col0, run, _step)

    @pl.when(step == band.steps - 1)
    def _finish():
        # every row sees its own key: l > 0
        l = l_scr[:, 0:1]
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = _to_row(m_scr[:, 0:1] + jnp.log(l))


def _dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, do_ref, lse_ref,
               dqn_ref, dqr_ref, delta_ref, qn_scr, qr_scr, dqn_scr, dqr_scr,
               stat_scr, *, band: _Band, scale: float):
    """dQ pass: a q block resident, the keys stream; also takes ``delta`` of
    its rows (lane 1 of ``stat_scr``; lane 0 holds the logsumexp)."""
    iq, step = pl.program_id(2), pl.program_id(3)
    col0, run, _ = band.step(iq, step)

    @pl.when(step == 0)
    def _init():
        dqn_scr[...] = jnp.zeros_like(dqn_scr)
        dqr_scr[...] = jnp.zeros_like(dqr_scr)
        qn_scr[...] = _scaled(qn_ref[...], scale)
        qr_scr[...] = _scaled(qr_ref[...], scale)
        delta = jnp.sum(do_ref[...].astype(jnp.float32)
                        * o_ref[...].astype(jnp.float32), axis=1,
                        keepdims=True)
        stat_scr[:, 0:1] = _to_col(lse_ref)
        stat_scr[:, 1:2] = delta
        delta_ref[...] = _to_row(delta)

    def _step(valid):
        kn, kr, do = kn_ref[...], kr_ref[...], do_ref[...]
        s = _nt(qn_scr[...], kn) + _nt(qr_scr[...], kr)
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - stat_scr[:, 0:1])
        ds = (p * (_nt(do, v_ref[...]) - stat_scr[:, 1:2])).astype(kn.dtype)
        dqn_scr[...] += _nn(ds, kn)
        dqr_scr[...] += _nn(ds, kr)

    _on_tiles(band, iq * band.bq, col0, run, _step)

    @pl.when(step == band.steps - 1)
    def _finish():
        dqn_ref[...] = (dqn_scr[...] * scale).astype(dqn_ref.dtype)
        dqr_ref[...] = (dqr_scr[...] * scale).astype(dqr_ref.dtype)


def _dkv_kernel(kn_ref, kr_ref, v_ref, qn_ref, qr_ref, do_ref, lse_ref,
                delta_ref, dkn_ref, dkr_ref, dv_ref, kn_scr, kr_scr, dkn_scr,
                dkr_scr, dv_scr, *, band: _Band, heads: int, scale: float):
    """dKV pass, transposed tiles [bk, bq]: a k block resident over all the
    heads (the grid's third dimension) and the q steps of each (its fourth);
    dK_rope sums over both."""
    ik, head, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    row0, run, _ = band.step(ik, step)

    @pl.when(step == 0)
    def _init():
        dkn_scr[...] = jnp.zeros_like(dkn_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        kn_scr[...] = _scaled(kn_ref[...], scale)

    @pl.when(jnp.logical_and(step == 0, head == 0))
    def _init_shared():
        dkr_scr[...] = jnp.zeros_like(dkr_scr)
        kr_scr[...] = _scaled(kr_ref[...], scale)

    def _step(valid):
        qn, qr, do = qn_ref[...], qr_ref[...], do_ref[...]
        s = _nt(kn_scr[...], qn) + _nt(kr_scr[...], qr)        # (bk, bq)
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse_ref[...])
        dv_scr[...] += _nn(p.astype(do.dtype), do)
        ds = (p * (_nt(v_ref[...], do) - delta_ref[...])).astype(qn.dtype)
        dkn_scr[...] += _nn(ds, qn)
        dkr_scr[...] += _nn(ds, qr)

    _on_tiles(band, row0, ik * band.bk, run, _step, keys_axis=0)

    last = step == band.steps - 1

    @pl.when(last)
    def _finish():
        dkn_ref[...] = (dkn_scr[...] * scale).astype(dkn_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(last, head == heads - 1))
    def _finish_shared():
        dkr_ref[...] = (dkr_scr[...] * scale).astype(dkr_ref.dtype)


def _cost(band: _Band, b, h, columns: int, arrays: int, rows: int, isz):
    """``columns``: the contracted or produced columns of the products a
    pass claims, a score (the true 192 + 128, never a padded width);
    ``arrays``: bytes a position of its operands and results but the
    statistics (``rows`` of them, float32)."""
    scores = b * h * band.scores()
    t = band.tq_pad
    return pl.CostEstimate(
        flops=2 * scores * columns, transcendentals=scores,
        bytes_accessed=b * t * arrays * isz + 4 * rows * b * h * t)


def _stream(band: _Band, i, s):
    """The streamed side's block number at step ``s`` of resident block
    ``i`` (no window: every step is an aligned block)."""
    return band.step(i, s)[2] // band.b


def _q_grid(block, t: int):
    """(band, three index maps) of the passes that keep a q block resident,
    grid (batch, head, q block, k step): the block's own rows, a head's
    streamed keys, the shared key's."""
    band = _Band(causal=True, window=None, block_q=block[0],
                 block_k=block[1], q_len=t, k_len=t)
    return (band, lambda b_, h_, iq, s: (b_, h_, iq),
            lambda b_, h_, iq, s: (b_, h_, _stream(band, iq, s)),
            lambda b_, h_, iq, s: (b_, _stream(band, iq, s)))


def _head_spec(rows: int, d: int, index):
    """``rows`` positions of one head of a [B, H, T, D] operand; ``index``
    gives (batch, head, block of rows)."""
    return pl.BlockSpec((None, None, rows, d),
                        lambda *g: (*index(*g), 0))


def _column_spec(rows: int, d: int, index):
    """The same of a [B, T, H * D] operand, where a projection wrote or reads
    it: column block ``head`` of the row block (the move between the two
    layouts is this address)."""
    return pl.BlockSpec((None, rows, d),
                        lambda *g: (lambda b_, h_, r: (b_, r, h_))(
                            *index(*g)))


def _shared_spec(rows: int, d: int, index):
    """The same of [B, T, D]: (batch, block of rows)."""
    return pl.BlockSpec((None, rows, d), lambda *g: (*index(*g), 0))


def _stat_spec(rows: int, index):
    """``rows`` positions of a [B, H, 1, T] row statistic."""
    return pl.BlockSpec((None, None, 1, rows),
                        lambda *g: (lambda b_, h_, r: (b_, h_, 0, r))(
                            *index(*g)))


def _keys_apart(kernel, dn: int, at=(2,)):
    """``kernel`` for a head's ``[k_nope | v]`` as ONE block of ``Dn + Dv``
    columns where ``kv_b_proj`` wrote it (or reads its cotangent): each ref
    at ``at`` is handed to the body as its two halves, with the shared key's
    ref between them as the body's signature has it."""
    @functools.wraps(kernel)
    def apart(*refs, **kw):
        refs = list(refs)
        for i in sorted(at, reverse=True):
            refs[i:i + 2] = [refs[i].at[:, :dn], refs[i + 1],
                             refs[i].at[:, dn:]]
        return kernel(*refs, **kw)
    return apart


def _sizes(qn, qr, keys):
    """(B, H, T, Dn, Dr, Dv, addressed) of a call's operands; ``keys`` is
    ``(k_nope, v)`` [B, H, T, D] each, or (``addressed``) ``(kv,)`` [B, T, H
    * (Dn + Dv)] where ``kv_b_proj`` wrote it."""
    b, h, t, dn = qn.shape
    addressed = len(keys) == 1
    dv = keys[0].shape[-1] // h - dn if addressed else keys[1].shape[-1]
    return b, h, t, dn, qr.shape[-1], dv, addressed


def _key_specs(addressed: bool, rows: int, dn: int, dv: int, index):
    """The specs of one head's keys and values, the shared key's between
    them left to the caller: [k_nope's, v's], or (``addressed``) the one
    block of ``Dn + Dv`` columns that holds both."""
    if addressed:
        return [_column_spec(rows, dn + dv, index)]
    return [_head_spec(rows, dn, index), _head_spec(rows, dv, index)]


def _values_spec(addressed: bool, rows: int, dv: int, index):
    """o's and dO's spec: [B, H, T, Dv], or (``addressed``) column block
    ``head`` of [B, T, H * Dv], where ``o_proj`` reads and writes them."""
    return (_column_spec if addressed else _head_spec)(rows, dv, index)


def _forward(qn, qr, keys, kr, block, interpret):
    b, h, t, dn, dr, dv, addressed = _sizes(qn, qr, keys)
    band, own, streamed, shared = _q_grid(block, t)
    scale = 1.0 / ((dn + dr) ** 0.5)
    isz = qn.dtype.itemsize
    key_specs = _key_specs(addressed, band.bk, dn, dv, streamed)
    return pl.pallas_call(
        functools.partial(
            _keys_apart(_fwd_kernel, dn) if addressed else _fwd_kernel,
            band=band, scale=scale),
        grid=(b, h, band.n, band.steps),
        in_specs=[_head_spec(band.bq, dn, own), _head_spec(band.bq, dr, own),
                  key_specs[0], _shared_spec(band.bk, dr, shared),
                  *key_specs[1:]],
        out_specs=[_values_spec(addressed, band.bq, dv, own),
                   _stat_spec(band.bq, own)],
        out_shape=[jax.ShapeDtypeStruct(
                       (b, t, h * dv) if addressed else (b, h, t, dv),
                       qn.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((band.bq, dn), qn.dtype),
                        pltpu.VMEM((band.bq, dr), qn.dtype),
                        pltpu.VMEM((band.bq, _LANES), jnp.float32),
                        pltpu.VMEM((band.bq, _LANES), jnp.float32),
                        pltpu.VMEM((band.bq, dv), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        # S over the keys' true width and P V; q, k_nope, v and o a head,
        # the rotated key once a position
        cost_estimate=_cost(band, b, h, dn + dr + dv,
                            h * (2 * dn + dr + 2 * dv) + dr, 1, isz),
        interpret=interpret,
    )(qn, qr, keys[0], kr, *keys[1:])


def _backward(qn, qr, keys, kr, o, lse, do, block, interpret):
    b, h, t, dn, dr, dv, addressed = _sizes(qn, qr, keys)
    scale = 1.0 / ((dn + dr) ** 0.5)
    isz = qn.dtype.itemsize
    band, own, streamed, shared = _q_grid(block, t)
    stat = jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32)
    key_specs = _key_specs(addressed, band.bk, dn, dv, streamed)
    dqn, dqr, delta = pl.pallas_call(
        functools.partial(
            _keys_apart(_dq_kernel, dn) if addressed else _dq_kernel,
            band=band, scale=scale),
        grid=(b, h, band.n, band.steps),
        in_specs=[_head_spec(band.bq, dn, own), _head_spec(band.bq, dr, own),
                  key_specs[0], _shared_spec(band.bk, dr, shared),
                  *key_specs[1:],
                  _values_spec(addressed, band.bq, dv, own),
                  _values_spec(addressed, band.bq, dv, own),
                  _stat_spec(band.bq, own)],
        out_specs=[_head_spec(band.bq, dn, own), _head_spec(band.bq, dr, own),
                   _stat_spec(band.bq, own)],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype), stat],
        scratch_shapes=[pltpu.VMEM((band.bq, dn), qn.dtype),
                        pltpu.VMEM((band.bq, dr), qn.dtype),
                        pltpu.VMEM((band.bq, dn), jnp.float32),
                        pltpu.VMEM((band.bq, dr), jnp.float32),
                        pltpu.VMEM((band.bq, _LANES), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        # the model's work: dP and dQ (the recomputed scores left out, as
        # the older entries leave them); q, k_nope, v, o, dO in and dQ out a
        # head, the rotated key once
        cost_estimate=_cost(band, b, h, dv + dn + dr,
                            h * (3 * dn + 2 * dr + 3 * dv) + dr, 2, isz),
        interpret=interpret,
    )(qn, qr, keys[0], kr, *keys[1:], o, do, lse)

    band = _Band(causal=True, window=None, block_q=block[0],
                 block_k=block[1], q_len=t, k_len=t, stream="q")

    def own_k(b_, ik, h_, s):
        return b_, h_, ik

    def own_shared(b_, ik, h_, s):
        return b_, ik

    def streamed_q(b_, ik, h_, s):
        return b_, h_, _stream(band, ik, s)

    # k_nope's and v's specs serve their cotangents too: where the keys are
    # addressed, one block of kv_b_proj's cotangent holds a head's both
    key_specs = _key_specs(addressed, band.bk, dn, dv, own_k)
    first, dkr, *second = pl.pallas_call(
        functools.partial(
            _keys_apart(_dkv_kernel, dn, at=(0, 7)) if addressed
            else _dkv_kernel, band=band, heads=h, scale=scale),
        grid=(b, band.n, h, band.steps),
        in_specs=[key_specs[0], _shared_spec(band.bk, dr, own_shared),
                  *key_specs[1:],
                  _head_spec(band.bq, dn, streamed_q),
                  _head_spec(band.bq, dr, streamed_q),
                  _values_spec(addressed, band.bq, dv, streamed_q),
                  _stat_spec(band.bq, streamed_q),
                  _stat_spec(band.bq, streamed_q)],
        out_specs=[key_specs[0], _shared_spec(band.bk, dr, own_shared),
                   *key_specs[1:]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (keys[0], kr, *keys[1:])],
        scratch_shapes=[pltpu.VMEM((band.bk, dn), qn.dtype),
                        pltpu.VMEM((band.bk, dr), qn.dtype),
                        pltpu.VMEM((band.bk, dn), jnp.float32),
                        pltpu.VMEM((band.bk, dr), jnp.float32),
                        pltpu.VMEM((band.bk, dv), jnp.float32)],
        compiler_params=_DKV_SEMANTICS,
        # the model's work: dV and dK; q, k_nope, v, dO in and dK_nope, dV
        # out a head, the rotated key in and its gradient out once
        cost_estimate=_cost(band, b, h, dv + dn + dr,
                            h * (3 * dn + dr + 3 * dv) + 2 * dr, 2, isz),
        interpret=interpret,
    )(keys[0], kr, *keys[1:], qn, qr, do, lse, delta)
    return dqn, dqr, (first, *second), dkr


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _latent_vjp(qn, qr, keys, kr, block, interpret):
    return _forward(qn, qr, keys, kr, block, interpret)[0]


def _latent_vjp_fwd(qn, qr, keys, kr, block, interpret):
    o, lse = _forward(qn, qr, keys, kr, block, interpret)
    # a layer that rematerialises keeps these two and runs no second forward
    o, lse = checkpoint_name(o, SAVED_BY_NAME[0]), checkpoint_name(
        lse, SAVED_BY_NAME[1])
    return o, (qn, qr, keys, kr, o, lse)


def _latent_vjp_bwd(block, interpret, res, g):
    return _backward(*res, g, block, interpret)


_latent_vjp.defvjp(_latent_vjp_fwd, _latent_vjp_bwd)


def _lay(x, t_pad: int):
    """[B, T, H, D] as the kernels read it, [B, H, t_pad, D]."""
    x = jnp.moveaxis(x, 1, 2)
    return jnp.pad(x, ((0, 0), (0, 0), (0, t_pad - x.shape[2]), (0, 0)))


def _blocks(t: int, block_q: int | None = None, block_k: int | None = None):
    """((block_q, block_k), the padded length) of a row of ``t``: one query
    head a program, ``_default_blocks``' rule for a group of one; the
    arguments are the tests' (multiples of 128)."""
    rule = _default_blocks(t, t, None, 1).fwd
    block = (block_q or rule[0], block_k or rule[1])
    if any(x % _LANES for x in block):
        raise ValueError(f"blocks {block}: whole lane tiles of 128 (the row "
                         f"statistics lie on the lanes)")
    return block, _ceil_to(t, max(block) if max(block) % min(block) == 0
                           else block[0] * block[1])


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def flash_attention_latent(q_nope: jax.Array, q_rope: jax.Array,
                           k_nope: jax.Array, k_rope: jax.Array,
                           v: jax.Array, block_q: int | None = None,
                           block_k: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Causal self-attention whose queries and keys are ``[nope | rope]``
    and wider than its values: ``q_nope``, ``k_nope`` [B, T, H, Dn],
    ``q_rope`` [B, T, H, Dr], ``k_rope`` [B, T, Dr] (ONE head, read by all
    ``H``), ``v`` [B, T, H, Dv]; returns [B, T, H, Dv]. Scores ``(q_nope
    k_nope^T + q_rope k_rope^T) / sqrt(Dn + Dr)``, fp32 online softmax,
    products in the operands' dtype with fp32 accumulation. Differentiable
    in all five; ``k_rope``'s cotangent is the sum over the heads. The block
    arguments are for tests (multiples of 128): they select no path. XLA
    moves the operands to [B, H, T, D] and pads the row to the block: the
    entry for any length (``flash_attention_latent_laid`` for operands that
    lie where the kernels read them)."""
    b, t, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    if (q_rope.shape != (b, t, h, dr) or k_nope.shape != q_nope.shape
            or k_rope.shape != (b, t, dr) or v.shape[:3] != (b, t, h)):
        raise ValueError(
            f"latent attention's operands: q_nope {q_nope.shape}, q_rope "
            f"{q_rope.shape}, k_nope {k_nope.shape}, k_rope {k_rope.shape} "
            f"(one head), v {v.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block, t_pad = _blocks(t, block_q, block_k)
    o = _latent_vjp(
        _lay(q_nope, t_pad), _lay(q_rope, t_pad),
        (_lay(k_nope, t_pad), _lay(v, t_pad)),
        jnp.pad(k_rope, ((0, 0), (0, t_pad - t), (0, 0))), block, interpret)
    return jnp.moveaxis(o[:, :, :t], 1, 2)


def why_not_laid(t: int, block_q: int | None = None,
                 block_k: int | None = None) -> str | None:
    """Why a caller cannot hand a row of ``t`` positions to
    ``flash_attention_latent_laid``, None where it can (static, from the
    shape): one array serves all three passes where it lies, so none of them
    may pad the row."""
    block, t_pad = _blocks(t, block_q, block_k)
    if t_pad != t:
        return (f"a row of {t} positions is padded to {t_pad} (blocks of "
                f"{block[0]} x {block[1]})")
    return None


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def flash_attention_latent_laid(q_nope: jax.Array, q_rope: jax.Array,
                                kv: jax.Array, k_rope: jax.Array,
                                block_q: int | None = None,
                                block_k: int | None = None,
                                interpret: bool | None = None) -> jax.Array:
    """``flash_attention_latent`` for operands that lie where a projection
    or ``latent_rope.py`` wrote them, nothing moved or padded: ``q_nope`` [B,
    H, T, Dn] and ``q_rope`` [B, H, T, Dr] (the pass writes them so), ``kv``
    [B, T, H * (Dn + Dv)] as ``kv_b_proj`` wrote it (a head's ``[k_nope |
    v]`` together, fetched as one block of its columns), ``k_rope`` [B, T,
    Dr]; returns o [B, T, H * Dv], where ``o_proj`` reads it. The cotangents
    leave the same way: dq_nope and dq_rope laid, dkv whole (the dKV kernel
    writes a head's two halves into one block), dO read by column. ``Dn``
    and ``Dv`` whole lane tiles and a length no pass pads
    (``why_not_laid``): anything else is refused."""
    b, h, t, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = kv.shape[-1] // h - dn
    if (q_rope.shape != (b, h, t, dr) or k_rope.shape != (b, t, dr)
            or kv.shape != (b, t, h * (dn + dv)) or dv <= 0):
        why = "not one latent attention's operands"
    elif dn % _LANES or dv % _LANES:
        why = "a block of a projection's columns is whole lane tiles"
    else:
        why = why_not_laid(t, block_q, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if why:
        raise ValueError(
            f"laid operands q_nope {q_nope.shape}, q_rope {q_rope.shape}, "
            f"kv {kv.shape}, k_rope {k_rope.shape}: {why}")
    return _latent_vjp(q_nope, q_rope, (kv,), k_rope,
                       _blocks(t, block_q, block_k)[0], interpret)
