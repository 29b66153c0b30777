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

import jax
import jax.numpy as jnp


def _tile(dim: int, most: int = 1024) -> int:
    """The largest multiple of 128 lanes up to ``most`` that divides
    ``dim``; a dimension with none (a toy width) is one tile."""
    for t in range(most - most % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _row_tile(rows: int) -> int:
    """Rows a tile: the largest power of two from 512 down to 8 that divides
    the buffer (the kernel wants whole tiles); else the whole buffer."""
    return next((t for t in (512, 256, 128, 64, 32, 16, 8) if rows % t == 0),
                rows)


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """``x`` [M, K] (rows sorted by group, the groups' rows first), ``w``
    [G, K, N] and ``sizes`` [G] int32 (their sum at most M) -> [M, N] in
    ``x``'s dtype, accumulated in float32. A row past the groups' sum holds
    whatever was there: mask it. Differentiable in ``x`` and ``w``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    tiling = (_row_tile(m), _tile(k), _tile(w.shape[2]))
    return gmm(x, w, sizes.astype(jnp.int32), x.dtype, tiling,
               interpret=interpret)
