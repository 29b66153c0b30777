"""The pieces of a Mamba-2 mixer (arXiv:2405.21060) that are not a dense
product: the causal depthwise convolution over time, the selective
state-space recurrence in its chunked form (state-space duality), and the
gated group norm. Plain ``jax.numpy``, differentiated by JAX, but for the
scan and the convolution at shapes that are whole lane tiles, which run as
Pallas kernel pairs (``ops/pallas/ssd_scan.py``, ``ops/pallas/
causal_conv.py``; ``scan_plan`` and ``conv_plan`` say which from the shape).

The recurrence, for one head with a scalar ``A < 0``, a state ``h`` [P, N],
``x_t`` [P], ``B_t`` and ``C_t`` [N] (a group of heads shares B and C),
``dt_t > 0``:

    h_t = exp(dt_t A) h_(t-1) + dt_t x_t B_t^T,     y_t = h_t C_t + D x_t

**The chunked form** (``ssd_scan``). With ``a_t = dt_t A`` and ``cum_i`` the
running sum of ``a`` inside a chunk of ``Q`` positions:

- within a chunk, ``y_i += sum over j <= i of (C_i . B_j) exp(cum_i - cum_j)
  dt_j x_j``: a [Q, Q] product of C and B a group, times the lower-triangular
  decay a head, times the chunk's ``dt x`` (three matrix products);
- a chunk's own state, ``S_c = sum over j of exp(cum_last - cum_j) dt_j x_j
  B_j^T`` [P, N] a head;
- a pass over the chunks of a row carries states forward: the state that
  enters chunk c + 1 is ``exp(cum_last of c)`` times the one that entered c,
  plus ``S_c`` (the one sequential part, ``T / Q`` turns: a ``lax.scan``
  here, the grid's innermost axis with the state in VMEM in the kernel);
- the carried state's part, ``y_i += exp(cum_i) C_i . (state that entered)``.

**Precision.** ``dt``, ``A``, every running sum of ``dt A`` and every ``exp``
of one are float32: a decay is a product of up to ``Q`` factors, and a
rounded exponent is a relative error in all of them at once. The products
between C, B, x and the states run in the activations' dtype and accumulate
in float32; the carried state is float32 between chunks. The exponent is
always a difference that is <= 0 (``cum_i - cum_j`` with ``j <= i``, masked
BEFORE the exp), so nothing overflows whatever ``dt`` is.

A length that the chunk does not divide is padded on the right with ``dt =
0`` and ``x = B = C = 0`` (a position that neither decays nor writes the
state) and cut afterwards.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  bias: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time: ``y_t = bias + sum over k of
    kernel[k] x_(t - K + 1 + k)`` for ``x`` [B, T, C], ``kernel`` [K, C]
    (``kernel[K - 1]`` weighs the position itself, as torch's ``conv1d``
    with ``padding = K - 1`` cut to T), zeros before the row. float32 out:
    K shifted multiply-adds, no [T, K, C] window."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for k in range(taps):
        y = y + padded[:, k:k + t].astype(jnp.float32) * kernel[k]
    return y


LANES = 128
# the convolution's pass (``ops/pallas/causal_conv.py``): rows of a halo
# block (one packed bfloat16 tile, two float32 ones); rows of the block that
# adds up d kernel (its first ``taps``) and d bias; elements of the source a
# program holds (512 positions x 768 lanes are 0.8 MB in and out in
# bfloat16, some 2 us at the HBM's rate against a third of one a grid step)
CONV_HALO, CONV_SUMS = 16, 8
_CONV_ELEMENTS = 512 * 768


def conv_plan(rows: int, t: int, channels: int, widths: tuple, taps: int,
              offset: int) -> dict:
    """Which program runs the convolution and SiLU over columns ``offset``
    to ``offset + sum(widths)`` of a ``[rows, t, channels]`` array, read
    from the shape alone: the Pallas pass (``ops/pallas/causal_conv.py``)
    where every width is a whole number of lane tiles, each result's columns
    start at a whole number of its blocks and a time block of whole halos
    divides ``t``; the ``jax.numpy`` form otherwise, with why (``reason``).
    ``rows_per_program`` is the pass's time block, ``programs`` its grid a
    Mamba block (one program where ``jax.numpy`` runs)."""
    if offset + sum(widths) > channels:
        raise ValueError(f"columns {offset} to {offset + sum(widths)} of "
                         f"{channels}")
    why, per, n = None, None, 1
    if not 1 < taps <= min(CONV_HALO, CONV_SUMS - 1):
        why = f"{taps} taps are not 2 to {min(CONV_HALO, CONV_SUMS - 1)}"
    elif any(w % LANES or not w for w in widths):
        bad = next(w for w in widths if w % LANES or not w)
        why = f"a width of {bad} is no whole number of lane tiles"
    else:
        n = math.gcd(*(w // LANES for w in widths))
        lanes = sum(widths) // n
        starts = [offset + sum(widths[:i]) for i in range(len(widths))]
        per = next((r for r in (1024, 512, 256, 128, 64, 32, 16)
                    if t % r == 0 and r * lanes <= _CONV_ELEMENTS), None)
        off = [(s, w // n) for s, w in zip(starts, widths) if s % (w // n)]
        if off:
            why = (f"columns from {off[0][0]} are no whole number of blocks "
                   f"of {off[0][1]}")
        elif per is None:
            why = (f"no block of {CONV_HALO} positions or more tiles a row "
                   f"of {t} at {lanes} lanes a program")
    plan = dict(kernel="jax.numpy" if why else "pallas",
                rows_per_program=t if why else per,
                programs=1 if why else rows * n * (t // per))
    if why:
        plan["reason"] = why
    return plan


def conv_silu_split(src: jax.Array, kernel: jax.Array, bias: jax.Array,
                    offset: int, widths: tuple) -> tuple:
    """``silu(causal_conv1d(src[..., offset:offset + sum(widths)], kernel,
    bias))`` in ``src``'s dtype, split into ``widths``: a Mamba-2 mixer's x,
    B and C from ``in_proj``'s result as it lies. One algorithm, two
    programs of it, chosen by ``conv_plan`` from the shape: the Pallas pass,
    which reads the columns by their offset and writes the results apart, or
    the ``jax.numpy`` form below (the fallback, and what the kernel's tests
    are held to)."""
    plan = conv_plan(*src.shape, widths, kernel.shape[0], offset)
    if plan["kernel"] == "pallas":
        from tpudist.ops.pallas.causal_conv import conv_silu_split as by_pass
        return by_pass(src, kernel, bias, offset=offset, widths=widths,
                       rows=plan["rows_per_program"])
    xbc = lax.slice_in_dim(src, offset, offset + sum(widths), axis=-1)
    y = jax.nn.silu(causal_conv1d(xbc, kernel, bias)).astype(src.dtype)
    return tuple(jnp.split(
        y, [sum(widths[:i]) for i in range(1, len(widths))], axis=-1))


def gated_group_norm(y: jax.Array, z: jax.Array, weight: jax.Array,
                     groups: int, eps: float) -> jax.Array:
    """``RMSNorm_by_group(y * silu(z)) * weight`` in float32: the gate
    first, then the norm over each of ``groups`` equal runs of the last
    axis."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = g.reshape(*g.shape[:-1], groups, -1)
    by_group = by_group * lax.rsqrt(
        jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + eps)
    return by_group.reshape(g.shape) * weight


def _running_sum(da: jax.Array) -> jax.Array:
    """The running sum of ``dt A`` inside a chunk, float32. (A function of
    its own so that the benchmark's control can take it, and with it every
    ``exp`` below, in bfloat16: ``selftest/bf16_decay_on_chip.py``.)"""
    return jnp.cumsum(da, axis=-1)


def scan_plan(rows: int, t: int, heads: int, p: int, groups: int, n: int,
              chunk: int) -> dict:
    """Which program ``ssd_scan`` runs at a shape, read from the shape alone:
    the Pallas kernel pair (``ops/pallas/ssd_scan.py``) where the chunk, the
    state size and a group's ``heads x P`` lanes are whole numbers of lane
    tiles (and a head's P divides a tile or is whole tiles), the ``jax.numpy``
    form otherwise, with why. ``programs`` is the kernel's grid a block."""
    rep = heads // groups
    width = max(p, LANES)
    why = None
    if chunk % LANES:
        why = f"a chunk of {chunk} is no whole number of lane tiles"
    elif n % LANES:
        why = f"a state of {n} is no whole number of lane tiles"
    elif width % p or (rep * p) % width:
        why = (f"a group's {rep} heads of {p} are no whole number of lane "
               f"tiles")
    plan = dict(kernel="xla" if why else "pallas", chunk=chunk,
                heads_per_program=rep,
                programs=rows * groups * -(-t // chunk))
    if why:
        plan["reason"] = why
    return plan


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, chunk: int):
    """The recurrence of the module's docstring in its chunked form.

    ``x`` [B, T, H, P]; ``dt`` [B, T, H] float32, positive; ``a`` [H]
    float32, negative; ``b``, ``c`` [B, T, G, N] (G divides H: head ``h``
    reads group ``h // (H / G)``); ``d`` [H]. Returns ``(y [B, T, H, P]
    float32, carry_min)``: ``carry_min`` is the least ``exp(sum of dt A over
    a chunk)`` over rows, chunks and heads, what of a state outlives one
    chunk where it fades fastest.

    One algorithm, two programs of it, chosen by ``scan_plan`` from the
    shape: the kernel pair or the ``jax.numpy`` form below (the fallback, and
    what the kernel's tests are held to)."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    f32 = jnp.float32
    # [B, chunks, H, Q]: the running sum lies on the minor axis
    dtc = jnp.moveaxis(dt.astype(f32).reshape(bsz, nc, chunk, heads), 2, 3)
    cum = _running_sum(dtc * a.astype(f32)[:, None])
    chunk_decay = jnp.exp(cum[..., -1]).astype(f32)          # [B, chunks, H]
    if scan_plan(bsz, t, heads, p, groups, n, chunk)["kernel"] == "pallas":
        y = _by_kernel(x, b, c, d, dtc, cum, chunk_decay)
    else:
        y = _by_fusions(x, b, c, d, dtc, cum, chunk_decay)
    return y[:, :t], jnp.min(chunk_decay)


def _by_kernel(x, b, c, d, dtc, cum, chunk_decay):
    """``y`` by the kernel pair: x, B and C as they lie ([B, T, lanes]), the
    chunk's scalars a group, and a head's chunk decay and ``D`` over its P
    lanes (what the kernel multiplies whole lane tiles by)."""
    from tpudist.ops.pallas.ssd_scan import scan_chunks
    bsz, tp, heads, p = x.shape
    groups = b.shape[2]
    f32 = jnp.float32

    def by_group(v):
        return v.astype(f32).reshape(bsz, v.shape[1], groups, heads // groups,
                                     v.shape[3])
    y = scan_chunks(
        x.reshape(bsz, tp, heads * p), b.reshape(bsz, tp, -1),
        c.reshape(bsz, tp, -1), by_group(dtc), by_group(cum),
        jnp.repeat(chunk_decay, p, axis=-1)[:, :, None],
        jnp.repeat(d.astype(f32), p)[None])
    return y.reshape(bsz, tp, heads, p)


def _by_fusions(x, b, c, d, dtc, cum, chunk_decay):
    """``y`` by ``jax.numpy``, differentiated by JAX."""
    bsz, tp, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    rep = heads // groups
    nc, chunk = cum.shape[1], cum.shape[3]
    dtype = x.dtype
    f32 = jnp.float32
    last = cum[..., -1:]

    xc = x.reshape(bsz, nc, chunk, groups, rep, p)
    bc = b.reshape(bsz, nc, chunk, groups, n)
    cc = c.reshape(bsz, nc, chunk, groups, n)

    def by_position(v):
        """[B, chunks, H, Q] -> [B, chunks, Q, G, H / G, 1]."""
        return jnp.moveaxis(v, 2, 3).reshape(bsz, nc, chunk, groups, rep, 1)

    # within a chunk: (C B^T) a group, times the decay a head, times dt x
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf)).astype(f32)
    scores = jnp.einsum("bzign,bzjgn->bzgij", cc, bc,
                        preferred_element_type=f32)
    mixed = (scores[:, :, :, None]
             * decay.reshape(bsz, nc, groups, rep, chunk, chunk)).astype(dtype)
    xdt = xc.astype(f32) * by_position(dtc)
    y = jnp.einsum("bzgrij,bzjgrp->bzigrp", mixed, xdt.astype(dtype),
                   preferred_element_type=f32)

    # a chunk's own state: what its positions leave at its end
    to_end = jnp.exp(last - cum).astype(f32)
    states = jnp.einsum("bzjgn,bzjgrp->bzgrpn", bc,
                        (xdt * by_position(to_end)).astype(dtype),
                        preferred_element_type=f32)

    # the pass between chunks: the state that enters each, float32
    keep = chunk_decay.reshape(bsz, nc, groups, rep, 1, 1)

    def carry(state, turn):
        kept, own = turn
        return kept * state + own, state
    _, entered = lax.scan(
        carry, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(states, 1, 0)))
    entered = jnp.moveaxis(entered, 0, 1)

    # the carried state's part
    y = y + jnp.einsum("bzign,bzgrpn->bzigrp", cc, entered.astype(dtype),
                       preferred_element_type=f32) * by_position(
                           jnp.exp(cum).astype(f32))
    y = y.reshape(bsz, tp, heads, p)
    return y + x.astype(f32) * d.astype(f32)[:, None]
