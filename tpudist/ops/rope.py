"""Rotary position embeddings: the frequency tables a model's
``rope_parameters`` describe, and their application (rotate-half).

Two kinds, as ``transformers`` computes them: ``default`` (``inv_freq_i =
theta^(-2i/d)``) and ``yarn`` (arXiv:2309.00071: the interpolated
``inv_freq / factor`` and the plain ``inv_freq`` blended by a linear ramp
between the dimensions that ``beta_fast`` and ``beta_slow`` rotations at
``original_max_position_embeddings`` give; cos and sin times
``attention_factor``). Host-side numpy: a table is a constant of the
compiled step.

A model may rotate a PART of a head (latent attention: 64 of a key's 192
columns): the tables are then of that part's width (``tables(params, 64,
...)``) and the caller hands ``apply`` that part alone. A model that
publishes ``rope_interleave`` pairs neighbours, ``(x_2i, x_2i+1)``, where
rotate-half pairs ``(x_i, x_{i + d/2})``: ``apply_pairs``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def inv_freq(params: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """(``inv_freq`` [head_dim / 2] float64, the factor on cos and sin) of
    one ``rope_parameters`` entry (``rope_type`` ``default`` | ``yarn``)."""
    kind = params.get("rope_type", "default")
    base = float(params["rope_theta"])
    plain = base ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r} (default | yarn)")
    factor = float(params["factor"])
    original = float(params["original_max_position_embeddings"])
    attention_factor = params.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = correction_dim(float(params.get("beta_fast", 32)))
    high = correction_dim(float(params.get("beta_slow", 1)))
    if params.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: the dimension turns fast enough to extrapolate (plain); 1: it
    # is interpolated (divided by the factor)
    return plain / factor * ramp + plain * (1.0 - ramp), float(attention_factor)


def tables(params: dict, head_dim: int, positions
           ) -> tuple[np.ndarray, np.ndarray]:
    """cos, sin [T, head_dim] float32, each frequency twice (the halves that
    rotate-half pairs), for the ``positions`` of a row's T ids: a length
    (positions 0 .. T - 1) or the positions themselves (a static sequence:
    a row that holds two copies of a document counts 0 .. L - 1 twice)."""
    freq, factor = inv_freq(params, head_dim)
    if isinstance(positions, (int, np.integer)):
        positions = np.arange(positions)
    angle = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    return ((np.cos(angle) * factor).astype(np.float32),
            (np.sin(angle) * factor).astype(np.float32))


def apply(x: jax.Array, cos, sin) -> jax.Array:
    """Rotate ``x`` [B, T, H, D] by the tables [T, D], in float32; the
    result in ``x``'s dtype."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :]
            + rotated * sin[None, :, None, :]).astype(x.dtype)


def apply_pairs(x: jax.Array, cos, sin) -> jax.Array:
    """Rotate neighbouring pairs ``(x_2i, x_2i+1)`` of ``x`` [B, T, H, D] by
    ``pos * inv_freq_i`` (the same tables). The rotated columns leave in the
    order ``[evens | odds]``, which is rotate-half's: a score is a sum over
    columns, so the order is free as long as q and k share it, and they
    do (both come through here)."""
    return apply(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                 cos, sin)
