"""Grouped matrix products over rows sorted by group: for each group ``g``,
``x[start_g : start_g + sizes[g]] @ w[g]``.

The kernel is JAX's own Pallas TPU grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``, with its transposed twin for
the weights' gradient behind one custom VJP); this module chooses its tiles
from the shapes and runs it interpreted off the TPU. It walks only the row
tiles that lie inside a group, so a buffer sized for the worst case costs
what its filled rows cost. JAX's kernel states its cost from the buffer's
rows (``2 M K N``), filled or not, and gives a caller no way to say
otherwise: an executable's FLOP count reads high by the empty part (under
``moe_topk_held``'s worst-case buffer, four times the products' work where a
quarter of the experts is held). A grouped product of this repo's own would
state the expected share's.

Why not ``jax.lax.ragged_dot``: on this chip XLA's own grouped product ran at
a fifth of the MXU's peak, over every row of the buffer, and its custom call
carries no name of the program's (a step's time under it read as unscoped).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _tile(dim: int, most: int = 1024) -> int:
    """The largest multiple of 128 lanes up to ``most`` that divides
    ``dim``. Where none does: a toy width (under 128) is one tile; a real
    one (1,856 = 14.5 x 128) takes the multiple of 128 whose tiles overhang
    its end the least, the largest of those (640: three tiles, 64 columns
    over). The kernel masks a contraction's overhang and drops a result's;
    its transposes run the same tiles over the other dimension, so a tile
    has to be whole lanes there too."""
    for t in range(most - most % 128, 0, -128):
        if dim % t == 0:
            return t
    if dim < 128:
        return dim
    return min(range(128, most + 1, 128),
               key=lambda t: (-(-dim // t) * t, -t))


def _row_tile(rows: int) -> int:
    """Rows a tile: the largest power of two from 512 down to 8 that divides
    the buffer (the kernel wants whole tiles); else the whole buffer."""
    return next((t for t in (512, 256, 128, 64, 32, 16, 8) if rows % t == 0),
                rows)


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array,
                   interpret: bool | None = None,
                   transpose_rhs: bool = False) -> jax.Array:
    """``x`` [M, K] (rows sorted by group, the groups' rows first), ``w``
    [G, K, N] and ``sizes`` [G] int32 (their sum at most M) -> [M, N] in
    ``x``'s dtype, accumulated in float32. A row past the groups' sum holds
    whatever was there: mask it. Differentiable in ``x`` and ``w``.

    ``transpose_rhs``: ``w`` lies [G, N, K] (as a Linear's weight, [out,
    in]) and the product is ``x @ w[g].T``; its gradient comes out of the
    kernel [G, N, K] too, row-major as the leaf lies (``_transposed``)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if transpose_rhs:
        return _transposed(x, w, sizes.astype(jnp.int32), interpret)
    m, k = x.shape
    tiling = (_row_tile(m), _tile(k), _tile(w.shape[2]))
    return gmm(x, w, sizes.astype(jnp.int32), x.dtype, tiling,
               interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _transposed(x, w, sizes, interpret):
    """``x @ w[g].T`` for ``w`` [G, N, K]. Why not JAX's own transposes of
    its kernel: they would make the weights' gradient [G, K, N] and swap its
    axes afterwards, and where N is no whole number of lane tiles (1,856)
    the chip holds a [G, K, N] leaf in another layout than the kernel's, so
    the step copied the parameter and AdamW's two moments of it in and out
    around every update (12 ms of a 570 ms step). Here the kernel writes
    the gradient as the leaf lies, and each of the three calls takes the
    tiles of its own two dimensions."""
    return _transposed_fwd(x, w, sizes, interpret)[0]


def _kernels():
    """JAX's module of the two kernels (the package exports its
    differentiable ``gmm`` under the module's own name)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _transposed_fwd(x, w, sizes, interpret):
    kernels = _kernels()
    (m, k), n = x.shape, w.shape[1]
    out = kernels.gmm(x, w, sizes, x.dtype, (_row_tile(m), _tile(k), _tile(n)),
                      transpose_rhs=True, interpret=interpret)
    return out, (x, w, sizes)


def _transposed_bwd(interpret, res, g):
    kernels = _kernels()
    x, w, sizes = res
    (m, k), n = x.shape, w.shape[1]
    # dx = g @ w[g]: contracts N; dw[g] = g[rows of g].T @ x[rows of g]
    tiling = (_row_tile(m), _tile(n), _tile(k))
    dx = kernels.gmm(g, w, sizes, x.dtype, tiling, interpret=interpret)
    dw = kernels.tgmm(g.swapaxes(0, 1), x, sizes, w.dtype, tiling,
                      interpret=interpret)
    return dx, dw, None


_transposed.defvjp(_transposed_fwd, _transposed_bwd)
