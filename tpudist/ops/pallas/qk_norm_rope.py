"""q / k RMSNorm and RoPE as one Pallas pass that writes q and k where the
streaming attention kernels read them, behind one custom VJP.

Between a decoder block's projections and its attention kernels lies
elementwise work over q ``[B, T, H * D]`` and k ``[B, T, Hkv * D]``: RMSNorm
over a head's ``D`` in float32, rotate-half RoPE by the layer's tables, and
the move into ``[B, Hkv, G, T, D]`` / ``[B, Hkv, 1, T, D]`` (``G = H / Hkv``:
``flash_attention._operand``'s layout). As ``jax.numpy`` (``RMSNorm`` +
``rope.apply`` + a ``moveaxis``, which stays what initialisation, the XLA
attention path and the tests are held to) XLA runs it as several fusions a
layer, forward, rematerialised and transposed, at ten times its bytes' time.

**A program** holds ``rows`` positions of one batch row for one key-value
head: the ``G * D`` columns of q that are its group's heads (whole lane
tiles where ``D`` is), and the group's one k head. The grid is (batch, row
blocks, key-value heads), the heads innermost, so the tables' block ``[rows,
D]`` stays where it is while a position's heads go by. Member ``j`` of the
group is the static lane slice ``[:, j * D:(j + 1) * D]`` of the q block and
tile ``j`` of the ``(G, rows, D)`` block that is written: **the move into
the kernels' layout is made by the block addresses**, nothing is transposed.
Rotate-half is a lane roll by ``D / 2`` (its own inverse) with the sign
folded into the sine table on the host: ``rope(n) = n cos + roll(n) sin_``,
``sin_ = [-sin | sin]``.

**Precision.** Operands and results in the projections' dtype (bfloat16
under the AMP policy); the norm, its statistics, the rotation and the tables
float32 in VMEM; ONE rounding, where the result is written (the
``jax.numpy`` form rounds between the norm and the rotation too). The
backward likewise: the cotangents come in as the dQ and dKV kernels wrote
them (laid), are rotated back and taken through the norm in float32 with
the statistics recomputed from raw q and k, and leave as ``[B, T, H * D]``,
where the projections' transposed products read them; the two norm scales'
cotangents are summed over a position block's heads in VMEM and over
blocks by the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.pallas.flash_attention import why_not_laid

_F32 = jnp.float32
_LANES = 128
# positions a program: 512 x (8 + 1) heads x 128 is 1.2 MB in and out in
# bfloat16, some 3 us at the HBM's rate against a third of one a grid step
_ROWS = 512


def qk_plan(rows: int, seq_len: int, heads: int, kv_heads: int,
            head_dim: int, *, norm: bool, rotate: bool, flash: bool,
            window: int | None = None,
            block_diffusion: tuple | None = None) -> dict:
    """Which program norms and rotates q and k at a shape, read from the
    shape and the layer's fields alone: this pass (``kernel`` "pallas") where
    the layer norms or rotates, the streaming kernels run behind it,
    ``head_dim`` is a whole number of lane tiles, one program holds a
    key-value head's whole group and the length needs no padding in any of
    the three attention passes; the ``jax.numpy`` form otherwise, with why
    (``reason``). ``programs`` is the grid a layer."""
    per = min(_ROWS, seq_len)
    if not (norm or rotate):
        why = "the layer neither norms nor rotates q and k"
    elif not flash:
        why = "the streaming attention kernels do not run behind it"
    elif head_dim % _LANES:
        why = f"a head of {head_dim} is no whole number of lane tiles"
    elif seq_len % per or per % 16:
        why = f"blocks of {per} positions do not tile a row of {seq_len}"
    else:
        why = why_not_laid(seq_len, heads // kv_heads, window,
                           block_diffusion)
    plan = dict(kernel="jax.numpy" if why else "pallas",
                rows_per_program=per,
                programs=rows * kv_heads * -(-seq_len // per))
    if why:
        plan["reason"] = why
    return plan


def signed_sin(sin):
    """The sine table with rotate-half's sign folded in: ``[-sin | sin]``
    over a head's two halves, so that ``rotate_half(x) * sin == roll(x, D /
    2) * signed_sin`` (on the host where the table is numpy's: a constant
    of the step)."""
    d = sin.shape[-1]
    return sin * np.where(np.arange(d) < d // 2, -1.0, 1.0).astype(np.float32)


def _rotate(x, cos_ref, sin_ref):
    """``x`` [rows, D] float32 rotated by the block's tables."""
    return (x * cos_ref[...]
            + pltpu.roll(x, x.shape[-1] // 2, axis=1) * sin_ref[...])


def _lane_mean(x):
    """The mean over a head's D lanes, a column [rows, 1]."""
    return jnp.mean(x, axis=-1, keepdims=True)


def _inv_rms(x, eps: float):
    return jax.lax.rsqrt(_lane_mean(x * x) + eps)


def _head(q_ref, k_ref, j, d: int):
    """The [rows, D] columns of query head ``j`` of the program's group in
    a raw q block; the k block for ``j`` None."""
    return k_ref if j is None else q_ref.at[:, j * d:(j + 1) * d]


def _members(group: int):
    """A program's heads: its group's query heads, then (None) its key."""
    return [*range(group), None]


def _layer_refs(refs, norm: bool, rotate: bool):
    """(the two norm scales' refs, the two tables' refs, the refs behind
    them) of a kernel's refs behind its blocks of q and k: a layer hands in
    the scales and the tables it has."""
    scales = refs[:2] if norm else (None, None)
    refs = refs[2 * norm:]
    return scales, refs[:2] if rotate else None, refs[2 * rotate:]


def _forward_kernel(q_ref, k_ref, *refs, group: int, d: int, eps: float,
                    norm: bool, rotate: bool):
    scales, tables, (ql_ref, kl_ref) = _layer_refs(refs, norm, rotate)
    for j in _members(group):
        x = _head(q_ref, k_ref, j, d)[...].astype(_F32)
        if norm:
            x = x * _inv_rms(x, eps) * scales[j is None][...]
        if rotate:
            x = _rotate(x, *tables)
        if j is None:
            kl_ref[...] = x.astype(kl_ref.dtype)
        else:
            ql_ref[j] = x.astype(ql_ref.dtype)


def _backward_kernel(dql_ref, dkl_ref, q_ref, k_ref, *refs, group: int,
                     d: int, eps: float, norm: bool, rotate: bool):
    scales, tables, (dq_ref, dk_ref, *dscales) = _layer_refs(refs, norm,
                                                             rotate)
    if norm:
        @pl.when(pl.program_id(2) == 0)
        def _first_head():
            for ref in dscales:
                ref[...] = jnp.zeros_like(ref)

    for j in _members(group):
        g = (dkl_ref[...] if j is None else dql_ref[j]).astype(_F32)
        if rotate:
            # the rotation's transpose: the roll is its own inverse
            cos_ref, sin_ref = tables
            g = (g * cos_ref[...]
                 + pltpu.roll(g * sin_ref[...], d // 2, axis=1))
        if norm:
            x = _head(q_ref, k_ref, j, d)[...].astype(_F32)
            inv = _inv_rms(x, eps)
            normed = x * inv
            dscales[j is None][...] += jnp.sum(g * normed, axis=0,
                                               keepdims=True)
            g = g * scales[j is None][...]
            g = inv * (g - normed * _lane_mean(g * normed))
        _head(dq_ref, dk_ref, j, d)[...] = g.astype(dq_ref.dtype)


def _specs(group: int, d: int, rows: int, norm: bool, rotate: bool):
    """BlockSpecs over the grid (batch, row block, key-value head): raw q
    and k where the projections wrote them, the scales and the tables as
    the layer has them, q and k laid."""
    raw = [pl.BlockSpec((None, rows, group * d), lambda b, i, h: (b, i, h)),
           pl.BlockSpec((None, rows, d), lambda b, i, h: (b, i, h))]
    given = []
    if norm:
        given += [pl.BlockSpec((1, d), lambda b, i, h: (0, 0))] * 2
    if rotate:
        given += [pl.BlockSpec((rows, d), lambda b, i, h: (i, 0))] * 2
    laid = [pl.BlockSpec((None, None, group, rows, d),
                         lambda b, i, h: (b, h, 0, i, 0)),
            pl.BlockSpec((None, None, None, rows, d),
                         lambda b, i, h: (b, h, 0, i, 0))]
    return raw, given, laid


def _given(q_scale, k_scale, cos, sin):
    """The scales as [1, D] rows and the tables, those the layer has."""
    out = []
    if q_scale is not None:
        out += [q_scale.reshape(1, -1).astype(_F32),
                k_scale.reshape(1, -1).astype(_F32)]
    if cos is not None:
        out += [cos, sin]
    return out


def _moved(q, k, arrays: int, tables: bool, t: int, d: int) -> int:
    """Bytes a call moves: q and k ``arrays`` times, the two float32 tables
    once (their block stays while a position's heads go by)."""
    return (arrays * (q.size + k.size) * q.dtype.itemsize
            + (2 * 4 * t * d if tables else 0))


# jitted: a step's layers then share one trace of each kernel
@functools.partial(jax.jit, static_argnames=("kv_heads", "eps", "interpret"))
def _forward(q, k, q_scale, k_scale, cos, sin, kv_heads, eps, interpret):
    b, t, hd = q.shape
    d = k.shape[2] // kv_heads
    group = hd // (kv_heads * d)
    norm, rotate = q_scale is not None, cos is not None
    rows = min(_ROWS, t)
    raw, given, laid = _specs(group, d, rows, norm, rotate)
    return pl.pallas_call(
        functools.partial(_forward_kernel, group=group, d=d, eps=eps,
                          norm=norm, rotate=rotate),
        grid=(b, t // rows, kv_heads),
        in_specs=raw + given, out_specs=laid,
        out_shape=[jax.ShapeDtypeStruct((b, kv_heads, group, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b, kv_heads, 1, t, d), k.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # an element: the square, the mean's sum, two products for the norm;
        # two products and a sum for the rotation; the casts
        cost_estimate=pl.CostEstimate(
            flops=(q.size + k.size) * (4 * norm + 3 * rotate + 2),
            transcendentals=(q.size + k.size) // d if norm else 0,
            bytes_accessed=_moved(q, k, 2, rotate, t, d)),
        interpret=interpret,
    )(q, k, *_given(q_scale, k_scale, cos, sin))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _backward(dql, dkl, q, k, q_scale, k_scale, cos, sin, eps, interpret):
    b, kv_heads, group, t, d = dql.shape
    norm, rotate = q_scale is not None, cos is not None
    rows = min(_ROWS, t)
    raw, given, laid = _specs(group, d, rows, norm, rotate)
    out_specs, out_shape = list(raw), [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct(k.shape, k.dtype)]
    if norm:
        # a scale's cotangent a position block, summed over its heads here
        out_specs += [pl.BlockSpec((None, None, 1, d),
                                   lambda b, i, h: (b, i, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((b, t // rows, 1, d), _F32)] * 2
    dq, dk, *dscales = pl.pallas_call(
        functools.partial(_backward_kernel, group=group, d=d, eps=eps,
                          norm=norm, rotate=rotate),
        grid=(b, t // rows, kv_heads),
        in_specs=laid + raw + given, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=(q.size + k.size) * (12 * norm + 3 * rotate + 2),
            transcendentals=(q.size + k.size) // d if norm else 0,
            bytes_accessed=_moved(q, k, 3 if norm else 2, rotate, t, d)),
        interpret=interpret,
    )(dql, dkl, q, k, *_given(q_scale, k_scale, cos, sin))
    return dq, dk, tuple(jnp.sum(s, axis=(0, 1, 2)) for s in dscales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _pass(q, k, q_scale, k_scale, cos, sin, kv_heads, eps, interpret):
    return tuple(_forward(q, k, q_scale, k_scale, cos, sin, kv_heads, eps,
                          interpret))


def _pass_fwd(q, k, q_scale, k_scale, cos, sin, kv_heads, eps, interpret):
    laid = _pass(q, k, q_scale, k_scale, cos, sin, kv_heads, eps, interpret)
    return laid, (q, k, q_scale, k_scale, cos, sin)


def _pass_bwd(kv_heads, eps, interpret, res, cotangents):
    q, k, q_scale, k_scale, cos, sin = res
    dq, dk, dscales = _backward(*cotangents, q, k, q_scale, k_scale, cos,
                                sin, eps, interpret)
    # (the tables are constants: no cotangent)
    return (dq, dk, *(dscales or (None, None)), None, None)


_pass.defvjp(_pass_fwd, _pass_bwd)


def qk_norm_rope(q: jax.Array, k: jax.Array, *, kv_heads: int,
                 q_scale: jax.Array | None = None,
                 k_scale: jax.Array | None = None, cos=None, sin=None,
                 eps: float = 1e-6, interpret: bool | None = None):
    """q ``[B, Hkv, G, T, D]`` and k ``[B, Hkv, 1, T, D]``, normed and
    rotated, from the projections' ``q`` [B, T, H * D] and ``k`` [B, T, Hkv
    * D] (a head's D columns together, a key-value head's G query heads one
    after another: what a reshape to [B, T, H, D] reads).

    ``q_scale``, ``k_scale`` [D] float32: the two RMSNorms' weights (both or
    neither; none: no norm). ``cos``, ``sin`` [T, D] float32: the rotation's
    tables as ``ops/rope.py::tables`` makes them, a row a position of the
    row (both or neither; none: no rotation). Differentiable in q, k and the
    scales. ``qk_plan`` says for which shapes this is the program to run;
    interpreted off the TPU."""
    if (q_scale is None) != (k_scale is None) or (cos is None) != (
            sin is None):
        raise ValueError("both norm scales or neither, both tables or "
                         "neither")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if cos is not None:
        cos, sin = jnp.asarray(cos, _F32), jnp.asarray(signed_sin(sin))
    return _pass(q, k, q_scale, k_scale, cos, sin, kv_heads, float(eps),
                 interpret)
