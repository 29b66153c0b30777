"""Latent attention's rotation of q_rope and k_r as one Pallas pass that
writes q where the latent attention kernels read it, behind one custom VJP.

Between a latent-attention block's up-projections and its kernels
(``mla_attention.py``) lies elementwise work over q ``[B, T, H * (Dn +
Dr)]``, a head ``[q_nope | q_rope]`` of 128 + 64 columns, and over the ONE
rotated key head ``k_r`` that ``kv_a_proj`` wrote as the last ``Dr`` of its
``[B, T, kv_rank + Dr]`` columns: the rotation of ``q_rope`` and ``k_r`` by
neighbouring pairs in float32, the cut of q into its two parts and the move
into ``[B, H, T, D]``. As ``jax.numpy`` (``rope.apply_pairs`` with a strided
de-interleave, a concatenate, slices, a ``moveaxis``; what initialisation,
the XLA attention path and the reference keep) XLA ran it at 7.5 times its
bytes' time, forward, rematerialised and transposed.

**A program** holds ``rows`` positions of one batch row for ``heads`` query
heads (an even number: two heads are 384 columns, three whole lane tiles, so
a program's block never cuts a tile). Head ``j`` is two static lane slices of
the block, ``q_nope`` copied as it is and ``q_rope`` rotated, stored as tile
``j`` of the ``(heads, rows, Dn)`` / ``(heads, rows, Dr)`` blocks of ``[B, H,
T, Dn]`` / ``[B, H, T, Dr]``: the move into the kernels' layout is made by
the block addresses. The grid is (batch, row blocks, head blocks), the heads
innermost, so the tables' block stays where it is while a position's heads
go by; the first head block also rotates ``k_r``, fetched as the lane tile
that starts at column ``kv_rank`` of ``kv_a_proj``'s result (its last, half
outside the array).

**The rotation needs no de-interleave**: ``out = x cos2 + swap(x) sin2``
with ``swap`` the exchange of lanes ``2i`` and ``2i + 1`` (two lane rolls
and a select), ``cos2 = [c0, c0, c1, c1, ...]`` and ``sin2 = [-s0, s0, -s1,
s1, ...]`` built on the host (``pair_tables``). The rotated columns stay in
the order they lay, on q_rope and k_r alike (``rope.apply_pairs`` lays them
``[evens | odds]``: a score is a sum over columns q and k share, so the two
forms differ by a permutation both sides share). Float32 in VMEM, ONE
rounding on the way out, as ``rope.apply`` rounds once.

**The backward** reads the cotangents as the dQ and dKV kernels wrote them,
rotates back (``g cos2 - swap(g) sin2``: the rotation's transpose needs no
saved operand) and writes dq ``[B, T, H * (Dn + Dr)]`` whole, where
``q_b_proj``'s transposed products read it, and the raw dk_r ``[B, T, Dr]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.pallas.qk_norm_rope import _F32, _LANES, _ROWS

# query heads a program: 512 x 8 x 192 is 1.5 MB in and out in bfloat16
_HEADS = 8


def _heads_per_program(heads: int) -> int:
    """The largest even divisor of ``heads`` up to ``_HEADS``; 0: none."""
    return next((n for n in range(min(_HEADS, heads), 1, -1)
                 if heads % n == 0 and n % 2 == 0), 0)


def latent_plan(rows: int, seq_len: int, heads: int, nope_dim: int,
                rope_dim: int, v_dim: int, kv_rank: int, *,
                flash: bool) -> dict:
    """Which program rotates a latent-attention block's q_rope and k_r and
    lays its operands at a shape, read from the shape alone: this pass and
    the addressed entry behind it (``kernel`` "pallas") where the streaming
    kernels run, every part of a head is whole lane tiles but the rotated
    one, two of which fill whole tiles (64 or 128 columns), the rotated
    key starts a lane tile of ``kv_a_proj``'s result and no pass pads the
    row; the ``jax.numpy`` form and the padding entry otherwise, with why
    (``reason``). ``programs`` is the pass's grid a layer."""
    from tpudist.ops.pallas.mla_attention import why_not_laid
    per, hp = min(_ROWS, seq_len), _heads_per_program(heads)
    if not flash:
        why = "the streaming attention kernels do not run behind it"
    elif nope_dim % _LANES or v_dim % _LANES:
        why = (f"heads of {nope_dim} unrotated and {v_dim} value columns "
               f"are no whole numbers of lane tiles")
    elif rope_dim not in (_LANES // 2, _LANES):
        why = (f"two heads' rotated parts of {rope_dim} columns are no "
               f"whole lane tile")
    elif kv_rank % _LANES:
        why = (f"the rotated key starts at column {kv_rank}, inside a lane "
               f"tile")
    elif not hp:
        why = f"{heads} query heads are no whole number of head pairs"
    elif seq_len % per or per % 16:
        why = f"blocks of {per} positions do not tile a row of {seq_len}"
    else:
        why = why_not_laid(seq_len)
    plan = dict(kernel="jax.numpy" if why else "pallas",
                rows_per_program=per,
                programs=rows * -(-seq_len // per) * (heads // hp if hp
                                                      else heads))
    if why:
        plan["reason"] = why
    return plan


def pair_tables(cos, sin):
    """(``cos2``, ``sin2``) [T, D] float32 of the rotation by neighbouring
    pairs without a de-interleave, from ``rope.tables``' [T, D] (each
    frequency once a half): ``cos2 = [c0, c0, c1, c1, ...]``, ``sin2 = [-s0,
    s0, -s1, s1, ...]``, so that ``x cos2 + swap(x) sin2`` turns ``(x_2i,
    x_2i+1)`` by ``pos * inv_freq_i`` (on the host, where the tables are
    numpy's: constants of the step)."""
    half = cos.shape[-1] // 2
    sign = np.tile(np.asarray([-1.0, 1.0], np.float32), half)
    return (np.repeat(np.asarray(cos, np.float32)[:, :half], 2, axis=1),
            np.repeat(np.asarray(sin, np.float32)[:, :half], 2, axis=1)
            * sign)


def _swap(x):
    """Lanes ``2i`` and ``2i + 1`` of ``x`` [rows, D] exchanged."""
    d = x.shape[-1]
    even = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) % 2 == 0
    return jnp.where(even, pltpu.roll(x, d - 1, axis=1),
                     pltpu.roll(x, 1, axis=1))


def _turned(x, cos, sin, back: bool = False):
    """``x`` [rows, D] rotated by the block's tables in float32, in ``x``'s
    dtype; ``back``: the rotation's transpose (``swap`` is its own, the
    sine's sign turns)."""
    x32 = x.astype(_F32)
    other = _swap(x32) * sin
    return (x32 * cos - other if back else x32 * cos + other).astype(x.dtype)


def _forward_kernel(q_ref, kva_ref, cos_ref, sin_ref, qn_ref, qr_ref, kr_ref,
                    *, heads: int, dn: int, dr: int):
    cos, sin = cos_ref[...], sin_ref[...]
    for j in range(heads):
        lo = j * (dn + dr)
        qn_ref[j] = q_ref[:, lo:lo + dn]
        qr_ref[j] = _turned(q_ref[:, lo + dn:lo + dn + dr], cos, sin)

    @pl.when(pl.program_id(2) == 0)
    def _shared_key():
        kr_ref[...] = _turned(kva_ref[:, :dr], cos, sin)


def _backward_kernel(dqn_ref, dqr_ref, dkr_ref, cos_ref, sin_ref, dq_ref,
                     dk_ref, *, heads: int, dn: int, dr: int):
    cos, sin = cos_ref[...], sin_ref[...]
    for j in range(heads):
        lo = j * (dn + dr)
        dq_ref[:, lo:lo + dn] = dqn_ref[j]
        dq_ref[:, lo + dn:lo + dn + dr] = _turned(dqr_ref[j], cos, sin,
                                                  back=True)

    @pl.when(pl.program_id(2) == 0)
    def _shared_key():
        dk_ref[...] = _turned(dkr_ref[...], cos, sin, back=True)


def _specs(heads: int, dn: int, dr: int, rows: int, key_tile: int):
    """BlockSpecs over the grid (batch, row block, head block): raw q where
    ``q_b_proj`` wrote it and ``kv_a_proj``'s lane tile ``key_tile``; the two
    tables; q_nope, q_rope and the rotated key laid."""
    raw = [pl.BlockSpec((None, rows, heads * (dn + dr)),
                        lambda b, i, h: (b, i, h)),
           pl.BlockSpec((None, rows, _LANES),
                        lambda b, i, h: (b, i, key_tile))]
    tables = [pl.BlockSpec((rows, dr), lambda b, i, h: (i, 0))] * 2
    laid = [pl.BlockSpec((None, heads, rows, dn),
                         lambda b, i, h: (b, h, i, 0)),
            pl.BlockSpec((None, heads, rows, dr),
                         lambda b, i, h: (b, h, i, 0)),
            pl.BlockSpec((None, rows, dr), lambda b, i, h: (b, i, 0))]
    return raw, tables, laid


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _cost(b, t, h, dn, dr, itemsize):
    """A call: q read and written once, the rotated key's ``Dr`` columns
    and the two float32 tables; three operations and the casts an element
    that is rotated."""
    turned = b * t * (h + 1) * dr
    return pl.CostEstimate(
        flops=5 * turned, transcendentals=0,
        bytes_accessed=2 * (b * t * h * dn + turned) * itemsize
        + 2 * 4 * t * dr)


# jitted: a step's blocks then share one trace of each kernel
@functools.partial(jax.jit, static_argnames=("heads", "kv_rank",
                                             "interpret"))
def _forward(q, kva, cos, sin, heads, kv_rank, interpret):
    b, t, width = q.shape
    dr = cos.shape[-1]
    dn = width // heads - dr
    rows, hp = min(_ROWS, t), _heads_per_program(heads)
    raw, tables, laid = _specs(hp, dn, dr, rows, kv_rank // _LANES)
    return pl.pallas_call(
        functools.partial(_forward_kernel, heads=hp, dn=dn, dr=dr),
        grid=(b, t // rows, heads // hp),
        in_specs=raw + tables, out_specs=laid,
        out_shape=[jax.ShapeDtypeStruct((b, heads, t, dn), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, t, dr), q.dtype),
                   jax.ShapeDtypeStruct((b, t, dr), kva.dtype)],
        compiler_params=_SEMANTICS,
        cost_estimate=_cost(b, t, heads, dn, dr, q.dtype.itemsize),
        interpret=interpret,
    )(q, kva, cos, sin)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(dqn, dqr, dkr, cos, sin, interpret):
    b, heads, t, dn = dqn.shape
    dr = dqr.shape[-1]
    rows, hp = min(_ROWS, t), _heads_per_program(heads)
    (raw_q, _), tables, laid = _specs(hp, dn, dr, rows, 0)
    return pl.pallas_call(
        functools.partial(_backward_kernel, heads=hp, dn=dn, dr=dr),
        grid=(b, t // rows, heads // hp),
        in_specs=laid + tables, out_specs=[raw_q, laid[2]],
        out_shape=[jax.ShapeDtypeStruct((b, t, heads * (dn + dr)),
                                        dqn.dtype),
                   jax.ShapeDtypeStruct(dkr.shape, dkr.dtype)],
        compiler_params=_SEMANTICS,
        cost_estimate=_cost(b, t, heads, dn, dr, dqn.dtype.itemsize),
        interpret=interpret,
    )(dqn, dqr, dkr, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pass(q, kva, cos, sin, heads, kv_rank, interpret):
    return tuple(_forward(q, kva, cos, sin, heads, kv_rank, interpret))


def _pass_fwd(q, kva, cos, sin, heads, kv_rank, interpret):
    return _pass(q, kva, cos, sin, heads, kv_rank, interpret), (cos, sin)


def _pass_bwd(heads, kv_rank, interpret, tables, cotangents):
    dq, dk = _backward(*cotangents, *tables, interpret)
    # the rotated key's cotangent, in kv_a_proj's last columns (the tables
    # are constants: no cotangent)
    return dq, jnp.pad(dk, ((0, 0), (0, 0), (kv_rank, 0))), None, None


_pass.defvjp(_pass_fwd, _pass_bwd)


def latent_rope(q: jax.Array, kva: jax.Array, cos, sin, *, heads: int,
                kv_rank: int, interpret: bool | None = None):
    """(q_nope ``[B, H, T, Dn]``, q_rope ``[B, H, T, Dr]`` rotated, k_r ``[B,
    T, Dr]`` rotated) from ``q_b_proj``'s ``q`` [B, T, H * (Dn + Dr)] (a
    head's ``Dn`` unrotated columns, then its ``Dr`` rotated ones: what a
    reshape to [B, T, H, Dn + Dr] reads) and ``kv_a_proj``'s ``kva`` [B, T,
    kv_rank + Dr] (the rotated key its last ``Dr`` columns). ``cos``,
    ``sin`` [T, Dr] float32 as ``ops/rope.py::tables`` makes them; the
    rotation is by neighbouring pairs and leaves the columns where they lay
    (``pair_tables``). Differentiable in q and kva (the first ``kv_rank``
    columns' cotangent is zero). ``latent_plan`` says for which shapes this
    is the program to run; interpreted off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cos2, sin2 = pair_tables(cos, sin)
    return _pass(q, kva, jnp.asarray(cos2), jnp.asarray(sin2), heads,
                 kv_rank, interpret)
