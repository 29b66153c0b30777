"""Pallas TPU fused BatchNorm epilogues: BN+ReLU and BN+add+ReLU in one pass.

PR 5's op-category attribution table names where the missing MFU goes on the
conv families: VPU-bound normalize/activate epilogues around every conv.
XLA emits the BN-apply → (add) → relu chain as its own fusion cluster, but
each cluster still round-trips the activation tensor through HBM between the
conv that produced it and the conv that consumes it, and the backward
re-reads it twice more. These kernels collapse the whole epilogue — both
directions — into ONE streaming pass each:

- **forward**: ``y = relu(x·a + b [+ residual])`` where the per-channel
  ``a = scale·rsqrt(var+eps)`` and ``b = bias − mean·a`` are folded OUTSIDE
  the kernel (two O(C) vectors — XLA fuses them into dust). One read of x
  (+residual), one write of y; the VPU does one fma + max per element
  instead of the unfused sub/rsqrt/mul/add/add/max chain.
- **backward**: one pass reads x (+residual) and dy and emits dx
  (+dresidual) AND the per-channel partial sums ``Σ g·x`` / ``Σ g``
  (g = dy masked by the recomputed relu sign), blocked over rows so each
  grid program owns a disjoint (8, C) partial tile — no cross-program
  accumulation hazard. The tile is one sublane group because Mosaic only
  lowers blocks whose last two dims are (8, 128)-aligned or the array's
  own; folding the row block onto 8 sublanes is also pure VPU adds, no
  cross-sublane reduce. The (grid·8, C) partials reduce to vectors in XLA,
  and autodiff maps them back through the a/b folding to dscale/dbias/
  dmean/dvar — so the FULL BatchNorm gradient (including the paths through
  the batch statistics) is exact without the kernel knowing BN exists.

Numerics: all kernel math in fp32 regardless of the storage dtype (bf16
under the AMP policy); relu' at exactly 0 is 0, matching ``nn.relu``'s
custom JVP. Zero-padding is exact by construction: padded rows/channels
carry a = b = x = dy = 0, so pre-activation = 0, the mask gates g to 0, and
every partial-sum contribution cancels — no in-kernel masking needed.

Whether this actually beats the XLA epilogue on a real chip is decided by
measurement, not this docstring: ``ops/norm_dispatch`` (a client of the
generic ``ops/dispatch`` honesty layer) A/Bs both per workload and caches
the winner per device kind. ``KERNEL_REV`` below invalidates those cached
verdicts whenever the kernel changes.

Falls back to interpreter mode off-TPU so CPU tests exercise the same
kernel bodies that compile on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bumped whenever kernel math/scheduling changes: norm_dispatch keys its
# cached pallas-vs-XLA verdicts on this, so a rebuilt kernel re-measures
# instead of inheriting the old kernel's win/loss record.
#   rev 2: backward partial sums are (8, bc) sublane tiles — the (1, bc)
#          partial rows of rev 1 were refused by the TPU lowering.
KERNEL_REV = 2

_LANES = 128
_SUBLANES = 8
# Target block footprint: ~512 KiB of fp32 per (bm, bc) tile keeps the
# backward's ~6 live buffers + Pallas double-buffering inside VMEM.
_BLOCK_BYTES = 512 * 1024
_MAX_BC = 2048


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _blocks(m: int, c: int) -> tuple[int, int, int, int]:
    """(bm, bc, m_pad, c_pad): channel blocks lane-aligned (≤ _MAX_BC), row
    blocks sized so one fp32 tile is ~_BLOCK_BYTES, floor 16 sublanes (the
    bf16 minimum tile)."""
    c_pad = _ceil_to(c, _LANES) if c > _LANES else c
    bc = min(c_pad, _MAX_BC)
    c_pad = _ceil_to(c_pad, bc)
    bm = max(16, min(1024, (_BLOCK_BYTES // (4 * bc)) // 8 * 8))
    bm = min(bm, _ceil_to(m, 16))
    m_pad = _ceil_to(m, bm)
    return bm, bc, m_pad, c_pad


def _fwd_kernel(x_ref, a_ref, b_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    pre = xf * a_ref[...] + b_ref[...]
    o_ref[...] = jnp.maximum(pre, 0.0).astype(o_ref.dtype)


def _fwd_res_kernel(x_ref, r_ref, a_ref, b_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    # Round the normalized value to the storage dtype BEFORE the residual
    # add, exactly as the unfused call sites did (bn output cast → bf16 add
    # → relu): the fused path must be a pure scheduling change, not a
    # numerics change the parity tests would have to special-case.
    q = (xf * a_ref[...] + b_ref[...]).astype(o_ref.dtype)
    pre = q + r_ref[...].astype(o_ref.dtype)
    o_ref[...] = jnp.maximum(pre, 0.0).astype(o_ref.dtype)


def _fold_rows(t):
    """(bm, bc) → (_SUBLANES, bc): row r lands on sublane r % 8 (bm is a
    multiple of 8 by ``_blocks``), summed over the bm/8 sublane groups."""
    bm, bc = t.shape
    return jnp.sum(t.reshape(bm // _SUBLANES, _SUBLANES, bc), axis=0)


def _bwd_kernel(x_ref, dy_ref, a_ref, b_ref, dx_ref, da_ref, db_ref):
    xf = x_ref[...].astype(jnp.float32)
    a = a_ref[...]
    pre = xf * a + b_ref[...]
    g = jnp.where(pre > 0.0, dy_ref[...].astype(jnp.float32), 0.0)
    dx_ref[...] = (g * a).astype(dx_ref.dtype)
    da_ref[...] = _fold_rows(g * xf)
    db_ref[...] = _fold_rows(g)


def _bwd_res_kernel(x_ref, r_ref, dy_ref, a_ref, b_ref, dx_ref, dr_ref,
                    da_ref, db_ref):
    xf = x_ref[...].astype(jnp.float32)
    a = a_ref[...]
    # Recompute the relu sign with the SAME storage-dtype rounding as the
    # forward (cast-then-add) — an f32 recompute could flip the mask on a
    # value that rounds across zero.
    q = (xf * a + b_ref[...]).astype(dr_ref.dtype)
    pre = q + r_ref[...].astype(dr_ref.dtype)
    # Compare in f32 (exact widening, same mask): v5e's VPU has no bf16
    # compare and Mosaic refuses the narrow cmpf outright.
    g = jnp.where(pre.astype(jnp.float32) > 0.0,
                  dy_ref[...].astype(jnp.float32), 0.0)
    dx_ref[...] = (g * a).astype(dx_ref.dtype)
    dr_ref[...] = g.astype(dr_ref.dtype)
    da_ref[...] = _fold_rows(g * xf)
    db_ref[...] = _fold_rows(g)


def _pad2(x, m_pad: int, c_pad: int):
    m, c = x.shape
    if m == m_pad and c == c_pad:
        return x
    return jnp.pad(x, ((0, m_pad - m), (0, c_pad - c)))


def _row_spec(bc):
    return pl.BlockSpec((1, bc), lambda im, ic: (0, ic))


def _tile_spec(bm, bc):
    return pl.BlockSpec((bm, bc), lambda im, ic: (im, ic))


def _part_spec(bc):
    return pl.BlockSpec((_SUBLANES, bc), lambda im, ic: (im, ic))


def _fwd_call(x2, r2, a2, b2, out_dtype, interpret):
    m, c = x2.shape
    bm, bc, m_pad, c_pad = _blocks(m, c)
    grid = (m_pad // bm, c_pad // bc)
    xp = _pad2(x2, m_pad, c_pad)
    ap = _pad2(a2, 1, c_pad)
    bp = _pad2(b2, 1, c_pad)
    operands = [xp]
    in_specs = [_tile_spec(bm, bc)]
    kernel = _fwd_kernel
    if r2 is not None:
        operands.append(_pad2(r2, m_pad, c_pad))
        in_specs.append(_tile_spec(bm, bc))
        kernel = _fwd_res_kernel
    operands += [ap, bp]
    in_specs += [_row_spec(bc), _row_spec(bc)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=_tile_spec(bm, bc),
        out_shape=jax.ShapeDtypeStruct((m_pad, c_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)
    return out[:m, :c]


def _bwd_call(x2, r2, dy2, a2, b2, interpret):
    m, c = x2.shape
    bm, bc, m_pad, c_pad = _blocks(m, c)
    nm, nc = m_pad // bm, c_pad // bc
    xp = _pad2(x2, m_pad, c_pad)
    dyp = _pad2(dy2, m_pad, c_pad)
    ap = _pad2(a2, 1, c_pad)
    bp = _pad2(b2, 1, c_pad)
    tile, row, part = _tile_spec(bm, bc), _row_spec(bc), _part_spec(bc)
    if r2 is None:
        dx, da_p, db_p = pl.pallas_call(
            _bwd_kernel,
            grid=(nm, nc),
            in_specs=[tile, tile, row, row],
            out_specs=[tile, part, part],
            out_shape=[
                jax.ShapeDtypeStruct((m_pad, c_pad), x2.dtype),
                jax.ShapeDtypeStruct((nm * _SUBLANES, c_pad), jnp.float32),
                jax.ShapeDtypeStruct((nm * _SUBLANES, c_pad), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(xp, dyp, ap, bp)
        dr = None
    else:
        rp = _pad2(r2, m_pad, c_pad)
        dx, dr, da_p, db_p = pl.pallas_call(
            _bwd_res_kernel,
            grid=(nm, nc),
            in_specs=[tile, tile, tile, row, row],
            out_specs=[tile, tile, part, part],
            out_shape=[
                jax.ShapeDtypeStruct((m_pad, c_pad), x2.dtype),
                jax.ShapeDtypeStruct((m_pad, c_pad), r2.dtype),
                jax.ShapeDtypeStruct((nm * _SUBLANES, c_pad), jnp.float32),
                jax.ShapeDtypeStruct((nm * _SUBLANES, c_pad), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(xp, rp, dyp, ap, bp)
        dr = dr[:m, :c]
    # (grid_rows·8, C) partials → per-channel vectors; an O(nm·C) XLA reduce.
    da = jnp.sum(da_p, axis=0)[:c]
    db = jnp.sum(db_p, axis=0)[:c]
    return dx[:m, :c], dr, da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_plain(x2, a, b, out_dtype_name, interpret):
    return _fwd_call(x2, None, a[None, :], b[None, :],
                     jnp.dtype(out_dtype_name), interpret)


def _fused_plain_fwd(x2, a, b, out_dtype_name, interpret):
    y = _fwd_call(x2, None, a[None, :], b[None, :],
                  jnp.dtype(out_dtype_name), interpret)
    return y, (x2, a, b)


def _fused_plain_bwd(out_dtype_name, interpret, res, g):
    x2, a, b = res
    dx, _, da, db = _bwd_call(x2, None, g, a[None, :], b[None, :], interpret)
    return dx, da, db


_fused_plain.defvjp(_fused_plain_fwd, _fused_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_res(x2, r2, a, b, out_dtype_name, interpret):
    return _fwd_call(x2, r2, a[None, :], b[None, :],
                     jnp.dtype(out_dtype_name), interpret)


def _fused_res_fwd(x2, r2, a, b, out_dtype_name, interpret):
    y = _fwd_call(x2, r2, a[None, :], b[None, :],
                  jnp.dtype(out_dtype_name), interpret)
    return y, (x2, r2, a, b)


def _fused_res_bwd(out_dtype_name, interpret, res, g):
    x2, r2, a, b = res
    dx, dr, da, db = _bwd_call(x2, r2, g, a[None, :], b[None, :], interpret)
    return dx, dr, da, db


_fused_res.defvjp(_fused_res_fwd, _fused_res_bwd)


def fused_bn_act(x: jax.Array, scale: jax.Array, bias: jax.Array,
                 mean: jax.Array, var: jax.Array, *, eps: float = 1e-5,
                 residual: jax.Array | None = None, out_dtype=None,
                 interpret: bool | None = None) -> jax.Array:
    """Fused BN epilogue: ``relu(normalize(x)·scale + bias [+ residual])``.

    ``x``/``residual``: any ``(..., C)`` layout (NHWC activations);
    ``scale``/``bias``/``mean``/``var``: per-channel fp32 vectors — the
    batch (or running) statistics are computed by the CALLER, which is what
    lets one kernel serve train mode, and lets autodiff through the a/b
    folding below recover the exact full BN gradient (the dmean/dvar paths
    ride the fold, not the kernel). Returns ``out_dtype`` (default: x's).

    Differentiable via a single-pass Pallas backward (see module docstring).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    a = scale.astype(f32) * jax.lax.rsqrt(var.astype(f32) + eps)
    b = bias.astype(f32) - mean.astype(f32) * a
    shape = x.shape
    c = shape[-1]
    out_dt = jnp.dtype(out_dtype) if out_dtype is not None \
        else jnp.dtype(x.dtype)
    x2 = x.reshape(-1, c)
    if residual is None:
        y2 = _fused_plain(x2, a, b, out_dt.name, interpret)
    else:
        if residual.shape != shape:
            raise ValueError(
                f"fused residual shape {residual.shape} != x {shape}")
        y2 = _fused_res(x2, residual.reshape(-1, c), a, b, out_dt.name,
                        interpret)
    return y2.reshape(shape)


def fused_bn_act_spmd(x: jax.Array, scale: jax.Array, bias: jax.Array,
                      mean: jax.Array, var: jax.Array, *, eps: float = 1e-5,
                      residual: jax.Array | None = None, out_dtype=None,
                      interpret: bool | None = None) -> jax.Array:
    """``fused_bn_act`` that composes with the GSPMD (jit + sharding rules)
    path — the fused-epilogue twin of ``flash_attention_spmd``.

    ``pallas_call`` has no SPMD partitioning rule, so inside a partitioned
    jit XLA would gather the activation and replicate the epilogue on every
    device — the structural stand-down that pinned ``--fused-bn`` off on
    every sharded path until this PR. But the epilogue needs NO cross-shard
    math at all (``relu(x·a + b [+ r])`` is elementwise over rows ×
    channels), so under an ambient mesh with Auto ``data``/``model`` axes
    this wraps the kernel in a nested manual ``shard_map``: batch rows
    shard over ``data``, channels (and the per-channel vectors) over
    ``model`` where divisible — exactly the layout the conv TP rules
    (``parallel/tensor_parallel``) give the surrounding convs, so no
    reshard is forced on either side. Each shard runs the kernel on its
    LOCAL block — the workload ``norm_dispatch.shard_local_workload``
    keys, records, and measures, so ``auto``'s never-pick-a-loser verdict
    is about the work a device actually executes.

    With no ambient mesh, inside an already-manual region (the shard_map
    DP path — local shapes already), or when nothing divides, this is
    ``fused_bn_act`` unchanged."""
    from jax.sharding import PartitionSpec as P

    # THE shared cut derivation (norm_dispatch.epilogue_shard_axes): the
    # axes this wrapper shards are BY CONSTRUCTION the axes the dispatch
    # key divided by — key/measure/execute cannot drift.
    from tpudist.ops.norm_dispatch import epilogue_shard_axes

    plain = functools.partial(fused_bn_act, eps=eps, residual=residual,
                              out_dtype=out_dtype, interpret=interpret)
    mesh, batch_ax, chan_ax = epilogue_shard_axes(x.shape)
    if batch_ax is None and chan_ax is None:
        return plain(x, scale, bias, mean, var)
    xs = P(batch_ax, *([None] * (x.ndim - 2)), chan_ax)
    vs = P(chan_ax)
    manual = frozenset(a for a in (batch_ax, chan_ax) if a)
    fn = functools.partial(fused_bn_act, eps=eps, out_dtype=out_dtype,
                           interpret=interpret)
    if residual is None:
        body = lambda x_, s_, b_, m_, v_: fn(x_, s_, b_, m_, v_)  # noqa: E731
        return jax.shard_map(
            body, mesh=mesh, axis_names=manual,
            in_specs=(xs, vs, vs, vs, vs), out_specs=xs,
            check_vma=False)(x, scale, bias, mean, var)
    body = lambda x_, s_, b_, m_, v_, r_: fn(  # noqa: E731
        x_, s_, b_, m_, v_, residual=r_)
    return jax.shard_map(
        body, mesh=mesh, axis_names=manual,
        in_specs=(xs, vs, vs, vs, vs, xs), out_specs=xs,
        check_vma=False)(x, scale, bias, mean, var, residual)


def reference_bn_act(x: jax.Array, scale: jax.Array, bias: jax.Array,
                     mean: jax.Array, var: jax.Array, *, eps: float = 1e-5,
                     residual: jax.Array | None = None,
                     out_dtype=None) -> jax.Array:
    """The pure-XLA twin of ``fused_bn_act`` with the EXACT op order the
    model call sites historically ran (f32 normalize → cast → add → relu):
    the fallback path in ``models/layers.py::BatchNorm``, the parity
    oracle for the interpret-mode tests, and the baseline side of
    ``norm_dispatch``'s micro-benchmark."""
    f32 = jnp.float32
    y = (x.astype(f32) - mean) * jax.lax.rsqrt(var.astype(f32) + eps)
    y = y * scale + bias
    y = y.astype(out_dtype or x.dtype)
    if residual is not None:
        y = y + residual
    return jax.nn.relu(y)
