"""The Mamba-2 mixer's causal depthwise convolution with bias, SiLU and the
cast as one Pallas pass each way behind one custom VJP: it reads ``xBC``
where ``in_proj`` wrote it (a column offset into the projection's result,
no slice) and writes x, B and C as three arrays, where the scan kernel
(``ops/pallas/ssd_scan.py``) reads them (no split).

As ``jax.numpy`` (``ssd.causal_conv1d`` + ``jax.nn.silu`` + ``jnp.split``,
which stays the fallback and what the tests are held to) XLA runs it as a
pad, four slices shifted by one to three rows of a packed array, a float32
intermediate the size of the input that autodiff keeps or recomputes, four
more shifted passes and four reductions over every row for the kernel's
gradient, and a copy a result: six to eight times its bytes' time.

**A program** holds ``rows`` positions of one batch row for one channel
block of each result: the grid is (batch, channel blocks, time blocks), and
a result of width ``w`` is cut into as many blocks as the others (``w / n``
lanes each, whole lane tiles), so that one program reads three column
ranges of the source and writes one block of each result: **the split is
made by the block addresses**. The ``taps - 1`` positions before a time
block come in as a halo block of ``HALO`` rows (one packed bfloat16 tile)
of the same array, zeros at a row's start; the shifts are sublane rolls of
the float32 block with its halo in front.

**Forward**: ``silu(bias + sum over k of kernel[k] x_(t - taps + 1 + k))``,
float32 sums of the source's values times float32 taps in
``ssd.causal_conv1d``'s order, float32 SiLU, ONE rounding where the result
is written. **Backward**: reads the three cotangents as the scan's backward
wrote them and the source with a halo before and after, recomputes the
pre-activation in VMEM (nothing float32 lives between the passes: the
residual is the source itself), forms ``du = g silu'(u)`` for the block and
the ``HALO`` rows after it (zero past the row's end), writes ``d x_s = sum
over k of kernel[k] du_(s + taps - 1 - k)`` and adds ``d kernel`` and ``d
bias`` up in a float32 block that stays resident over the time axis, a
partial a batch row, summed by the caller. The three ``d x`` leave as three
arrays; the source's cotangent is their sum, each padded to the source's
columns, which XLA lays side by side in one pass with the cotangents of the
source's other columns.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.ssd import CONV_HALO as HALO, CONV_SUMS as _SUMS, LANES

_F32 = jnp.float32
# rows and lanes a chain of operations runs over inside a program (its
# values then stay near the registers: [128 + 2 HALO, 256] float32 is 40)
_CHUNK_ROWS, _CHUNK_LANES = 128, 256


def _chunks(rows: int, width: int):
    """The (first row, rows, first lane, lanes) of a block's chunks."""
    r, l = min(_CHUNK_ROWS, rows), min(_CHUNK_LANES, width)
    return [(r0, min(r, rows - r0), l0, min(l, width - l0))
            for l0 in range(0, width, l) for r0 in range(0, rows, r)]


def _rows_of(ref, halo_ref, start: int, lanes, edge):
    """``HALO`` rows of a block's array from row ``start`` of the block,
    float32: the block's own where they lie inside it, else the halo
    block's, zeros where ``edge`` says the row ends there."""
    if 0 <= start < ref.shape[0]:
        return ref[start:start + HALO, lanes].astype(_F32)
    return jnp.where(edge, 0.0, halo_ref[:, lanes].astype(_F32))


def _shifted(cat, back: int, rows: int):
    """Rows ``HALO - back`` to ``HALO - back + rows`` of ``cat``: the
    position ``back`` before each of ``rows`` positions behind a halo."""
    if back:
        cat = pltpu.roll(cat, back, axis=0)
    return cat[HALO:HALO + rows]


def _preactivation(cat, k_ref, b_ref, lanes, taps: int, rows: int):
    """(``bias + sum over k of kernel[k] x_(t - taps + 1 + k)`` for the
    ``rows`` positions behind ``cat``'s halo, the shifted operands by k),
    in ``ssd.causal_conv1d``'s order."""
    operands = [_shifted(cat, taps - 1 - k, rows) for k in range(taps)]
    u = b_ref[:, lanes]
    for k, x in enumerate(operands):
        u = u + x * k_ref[k:k + 1, lanes]
    return u, operands


def _sigmoid(u):
    # (``jax.nn.sigmoid`` lowers to a division: the reciprocal times one)
    return pl.reciprocal(1.0 + jnp.exp(-u))


def _forward_kernel(*refs, parts: int, taps: int):
    srcs, befores, kernels, biases, outs = (
        refs[i * parts:(i + 1) * parts] for i in range(5))
    first = pl.program_id(2) == 0
    for x_ref, before_ref, k_ref, b_ref, o_ref in zip(
            srcs, befores, kernels, biases, outs):
        for r0, rows, l0, width in _chunks(*x_ref.shape):
            lanes = slice(l0, l0 + width)
            cat = jnp.concatenate(
                [_rows_of(x_ref, before_ref, r0 - HALO, lanes, first),
                 x_ref[r0:r0 + rows, lanes].astype(_F32)], axis=0)
            u, _ = _preactivation(cat, k_ref, b_ref, lanes, taps, rows)
            o_ref[r0:r0 + rows, lanes] = (u * _sigmoid(u)).astype(
                o_ref.dtype)


def _backward_kernel(*refs, parts: int, taps: int):
    (gs, g_afters, srcs, befores, afters, kernels, biases, dxs, sums) = (
        refs[i * parts:(i + 1) * parts] for i in range(9))
    t = pl.program_id(2)
    first, last = t == 0, t == pl.num_programs(2) - 1

    @pl.when(first)
    def _first_block():
        for s_ref in sums:
            s_ref[...] = jnp.zeros_like(s_ref)

    for (g_ref, g_after_ref, x_ref, before_ref, after_ref, k_ref, b_ref,
         dx_ref, s_ref) in zip(gs, g_afters, srcs, befores, afters, kernels,
                               biases, dxs, sums):
        for r0, rows, l0, width in _chunks(*x_ref.shape):
            lanes = slice(l0, l0 + width)
            # the chunk with HALO rows before (the taps reach back) and
            # after (du of the next positions reaches this chunk's d x)
            cat = jnp.concatenate(
                [_rows_of(x_ref, before_ref, r0 - HALO, lanes, first),
                 x_ref[r0:r0 + rows, lanes].astype(_F32),
                 _rows_of(x_ref, after_ref, r0 + rows, lanes, last)], axis=0)
            u, operands = _preactivation(cat, k_ref, b_ref, lanes, taps,
                                         rows + HALO)
            g = jnp.concatenate(
                [g_ref[r0:r0 + rows, lanes].astype(_F32),
                 _rows_of(g_ref, g_after_ref, r0 + rows, lanes, last)],
                axis=0)
            sig = _sigmoid(u)
            du = g * (sig * (1.0 + u * (1.0 - sig)))
            dx = None
            for k in range(taps):
                ahead = taps - 1 - k
                rolled = pltpu.roll(du, rows + HALO - ahead,
                                    axis=0) if ahead else du
                term = rolled[:rows] * k_ref[k:k + 1, lanes]
                dx = term if dx is None else dx + term
            dx_ref[r0:r0 + rows, lanes] = dx.astype(dx_ref.dtype)
            du = du[:rows]
            for k, x in enumerate(operands):
                s_ref[k:k + 1, lanes] += jnp.sum(du * x[:rows], axis=0,
                                                 keepdims=True)
            s_ref[taps:taps + 1, lanes] += jnp.sum(du, axis=0, keepdims=True)


def _layout(widths, offset: int):
    """(channel blocks, each result's block width, its first block in the
    source, its first block among the convolution's channels)."""
    n = math.gcd(*(w // LANES for w in widths))
    blocks = [w // n for w in widths]
    starts = [sum(widths[:i]) for i in range(len(widths))]
    return (n, blocks, [(offset + s) // b for s, b in zip(starts, blocks)],
            [s // b for s, b in zip(starts, blocks)])


def _by_result(blocks, firsts, shape, index):
    """A BlockSpec a result over the grid (batch, channel block, time
    block): a block of ``shape`` and the result's width at ``index(b, t)``
    and the result's block ``first + j`` of the minor axis."""
    return [pl.BlockSpec((*shape, w),
                         lambda b, j, t, f=f: (*index(b, t), f + j))
            for w, f in zip(blocks, firsts)]


def _specs(blocks, firsts, rows: int, t_blocks: int):
    """BlockSpecs of a [B, T, columns] array's blocks by result, and of the
    halo blocks before and after them (clamped at a row's ends, where the
    kernel reads zeros)."""
    per = rows // HALO
    return (
        _by_result(blocks, firsts, (None, rows), lambda b, t: (b, t)),
        _by_result(blocks, firsts, (None, HALO), lambda b, t: (
            b, jnp.maximum(t * per - 1, 0))),
        _by_result(blocks, firsts, (None, HALO), lambda b, t: (
            b, jnp.minimum((t + 1) * per, t_blocks * per - 1))))


def _taps_specs(blocks, firsts, taps: int):
    """The kernel's [taps, w] and the bias's [1, w] blocks by result."""
    return (_by_result(blocks, firsts, (taps,), lambda b, t: (0,)),
            _by_result(blocks, firsts, (1,), lambda b, t: (0,)))


# jitted: a step's Mamba blocks then share one trace of each kernel
@functools.partial(jax.jit, static_argnames=("offset", "widths", "rows",
                                             "interpret"))
def _forward(src, kernel, bias, offset, widths, rows, interpret):
    bsz, t, _ = src.shape
    taps, parts = kernel.shape[0], len(widths)
    n, blocks, firsts, own = _layout(widths, offset)
    at, before, _ = _specs(blocks, firsts, rows, t // rows)
    kernels, biases = _taps_specs(blocks, own, taps)
    zero = [0] * parts
    elements = bsz * t * sum(widths)
    return pl.pallas_call(
        functools.partial(_forward_kernel, parts=parts, taps=taps),
        grid=(bsz, n, t // rows),
        in_specs=at + before + kernels + biases,
        out_specs=_specs(blocks, zero, rows, t // rows)[0],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, w), src.dtype)
                   for w in widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        # an element: a product and a sum a tap, four for SiLU, the casts
        cost_estimate=pl.CostEstimate(
            flops=elements * (2 * taps + 6), transcendentals=elements,
            bytes_accessed=2 * elements * src.dtype.itemsize),
        interpret=interpret,
    )(*[src] * (2 * parts), *[kernel] * parts,
      *[bias.reshape(1, -1)] * parts)


@functools.partial(jax.jit, static_argnames=("offset", "rows", "interpret"))
def _backward(cotangents, src, kernel, bias, offset, rows, interpret):
    bsz, t, _ = src.shape
    widths = tuple(g.shape[2] for g in cotangents)
    taps, parts = kernel.shape[0], len(widths)
    n, blocks, firsts, own = _layout(widths, offset)
    at, before, after = _specs(blocks, firsts, rows, t // rows)
    kernels, biases = _taps_specs(blocks, own, taps)
    zero = [0] * parts
    g_at, _, g_after = _specs(blocks, zero, rows, t // rows)
    elements = bsz * t * sum(widths)
    outs = pl.pallas_call(
        functools.partial(_backward_kernel, parts=parts, taps=taps),
        grid=(bsz, n, t // rows),
        in_specs=g_at + g_after + at + before + after + kernels + biases,
        out_specs=g_at + [pl.BlockSpec((None, _SUMS, w),
                                       lambda b, j, t: (b, 0, j))
                          for w in blocks],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, w), src.dtype)
                   for w in widths]
        + [jax.ShapeDtypeStruct((bsz, _SUMS, w), _F32) for w in widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # the forward's again, SiLU's derivative, the transposed taps and
        # the taps' and the bias's sums
        cost_estimate=pl.CostEstimate(
            flops=elements * (6 * taps + 14), transcendentals=elements,
            bytes_accessed=3 * elements * src.dtype.itemsize),
        interpret=interpret,
    )(*cotangents, *cotangents, *[src] * (3 * parts), *[kernel] * parts,
      *[bias.reshape(1, -1)] * parts)
    sums = jnp.sum(jnp.concatenate(outs[parts:], axis=-1), axis=0)
    return outs[:parts], sums[:taps], sums[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pass(src, kernel, bias, offset, widths, rows, interpret):
    return tuple(_forward(src, kernel, bias, offset, widths, rows,
                          interpret))


def _pass_fwd(src, kernel, bias, offset, widths, rows, interpret):
    return (_pass(src, kernel, bias, offset, widths, rows, interpret),
            (src, kernel, bias))


def _pass_bwd(offset, widths, rows, interpret, res, cotangents):
    src, kernel, bias = res
    dxs, dkernel, dbias = _backward(tuple(cotangents), src, kernel, bias,
                                    offset, rows, interpret)
    # each d x padded to the source's columns and summed: XLA lays the pads
    # of a sum side by side in one pass, with those of the source's other
    # columns' cotangents (a concatenate with zeros it writes out first)
    dsrc, start = None, offset
    for dx in dxs:
        after = src.shape[2] - start - dx.shape[2]
        padded = jnp.pad(dx, ((0, 0), (0, 0), (start, after)))
        dsrc = padded if dsrc is None else dsrc + padded
        start += dx.shape[2]
    return dsrc, dkernel, dbias


_pass.defvjp(_pass_fwd, _pass_bwd)


def conv_silu_split(src: jax.Array, kernel: jax.Array, bias: jax.Array, *,
                    offset: int, widths: tuple, rows: int,
                    interpret: bool | None = None) -> tuple:
    """``silu(causal_conv1d(src[..., offset:offset + sum(widths)], kernel,
    bias))`` in ``src``'s dtype as ``len(widths)`` arrays ``[B, T, w]``, the
    columns one after another (what a ``jnp.split`` of it reads).

    ``src`` [B, T, columns] (``in_proj``'s result as it lies); ``kernel``
    [taps, sum(widths)] and ``bias`` [sum(widths)] float32; ``rows`` the
    time block (``ssd.conv_plan``'s ``rows_per_program``). Differentiable in
    all three. ``ssd.conv_plan`` says for which shapes this is the program
    to run; interpreted off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pass(src, kernel.astype(_F32), bias.astype(_F32), int(offset),
                 tuple(int(w) for w in widths), int(rows), interpret)
